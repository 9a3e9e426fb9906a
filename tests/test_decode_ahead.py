"""Decode-ahead of arrived lines during the pacing wait: a batch whose
lines were decoded in passes before its poll is the batch one decode at
the poll gives. What changes is where the decoder's time lies, nothing a
step, a sink or a checkpoint can see.

(a) the packed matrix: passes at random line-boundary cuts, finished at
    the provisional base or one or two seconds past it, against the
    one-shot ``decode_packed`` of the same blob at that base;
(b) is in ``tests/test_socket_source.py`` (the arrived-lines view);
(c) ``StreamingHost.run`` on a socket fed across the wait, on one chip
    and on a mesh of four of the suite's devices (the same matrix, put
    with its capacity axis sharded);
(d) what every batch of such a host says of its rows' wait, of the time
    none of its spans holds and of how late the loop began it."""

import json
import random
import socket
import threading
import time

import numpy as np
import pytest

from data_accelerator_tpu.core.config import SettingDictionary
from data_accelerator_tpu.core.schema import Schema, StringDictionary
from data_accelerator_tpu.native import NativeDecoder, packed_shard_bytes
from data_accelerator_tpu.runtime.host import StreamingHost
from data_accelerator_tpu.runtime.processor import FlowProcessor
from data_accelerator_tpu.runtime.sinks import OutputDispatcher, OutputOperator
from data_accelerator_tpu.runtime.sources import SocketSource

SCHEMA_JSON = json.dumps({"type": "struct", "fields": [
    {"name": "k", "type": "long", "nullable": False, "metadata": {}},
    {"name": "kind", "type": "string", "nullable": False, "metadata": {}},
    {"name": "v", "type": "double", "nullable": False, "metadata": {}},
    {"name": "on", "type": "boolean", "nullable": False, "metadata": {}},
    {"name": "ts", "type": "timestamp", "nullable": False, "metadata": {}},
]})
SCHEMA = Schema.from_spark_json(SCHEMA_JSON)
N_COLS = len(SCHEMA.columns)
BASE_MS = 1_700_000_000_000  # the base the passes expect
TRANSFORM = "--DataXQuery--\nOut = SELECT k, v FROM DataXProcessedInput\n"


def _proc(tmp_path, capacity, extra=None):
    t = tmp_path / "ahead.transform"
    t.write_text(TRANSFORM)
    conf = {
        "datax.job.name": "Ahead",
        "datax.job.input.default.inputtype": "socket",
        "datax.job.input.default.blobschemafile": SCHEMA_JSON,
        "datax.job.process.transform": str(t),
        "datax.job.process.ingest.decoderthreads": "4",
    }
    conf.update(extra or {})
    return FlowProcessor(
        SettingDictionary(conf), batch_capacity=capacity,
        output_datasets=["Out"],
    )


# -- (a) the matrix ----------------------------------------------------------

def _event(i, ts, kind=None):
    return json.dumps({
        "k": i, "kind": kind or f"kind{i % 5}", "v": i / 8.0,
        "on": i % 3 == 0, "ts": ts,
    }).encode()


def clean(n):
    return [_event(i, BASE_MS + 3 + 17 * i) for i in range(n)]


def malformed_lines(n):
    lines = clean(n)
    for i in range(3, n, 11):
        lines[i] = [b'{"k": 1, "kind": "brok', b"not json at all",
                    b'["an", "array"]', b'{"k": }'][i % 4]
    return lines


def missing_time_fields(n):
    lines = clean(n)
    for i in range(0, n, 4):
        lines[i] = json.dumps({"k": i, "kind": "bare", "v": 1.0}).encode()
    for i in range(1, n, 9):
        lines[i] = _event(i, 0)  # epoch zero reads as missing, too
    return lines


def times_at_both_clips(n):
    lines = clean(n)
    for i in range(2, n, 7):
        lines[i] = _event(i, BASE_MS + 2**31 + 5_000 * (i % 2) - 1)
    for i in range(5, n, 7):
        lines[i] = _event(i, BASE_MS - 2**31 - 500 + 1_000 * (i % 2))
    return lines


def times_at_the_bases(n):
    # a time AT a base reads 0 there, like a missing field: at the
    # provisional base, and at the ones the poll may come at
    return [_event(i, BASE_MS + 1000 * (i % 4)) for i in range(n)]


def strings_new_to_the_dictionary(n):
    # new strings all along the blob, so that every pass meets some
    # (and a malformed line that interned one before it broke)
    lines = [_event(i, BASE_MS + 1 + i, kind=f"new-{i // 3}")
             for i in range(n)]
    lines[n // 2] = b'{"kind": "only-in-a-broken-line", "k": }'
    return lines


def bad_timestamps(n):
    lines = clean(n)
    for i in range(4, n, 13):
        lines[i] = json.dumps(
            {"k": i, "kind": "late", "v": 0.5, "ts": "the day before"}
        ).encode()
    for i in range(6, n, 13):
        lines[i] = json.dumps(
            {"k": i, "kind": "iso", "v": 0.5, "ts": "2023-11-14T22:13:27Z"}
        ).encode()
    return lines


def sharded_passes(n):
    # passes over the 256 KB from which the decoder shards, with
    # malformed lines and new strings inside the shards
    lines = [_event(i, BASE_MS + 1 + i, kind=f"wide-{i % 601}-{'x' * 80}")
             for i in range(8 * n)]
    for i in range(50, len(lines), 997):
        lines[i] = b'{"k": 1, "kind": "wide-broken", "v": }'
    return lines


CASES = [clean, malformed_lines, missing_time_fields, times_at_both_clips,
         times_at_the_bases, strings_new_to_the_dictionary, bad_timestamps,
         sharded_passes]


def _one_shot(blob, capacity, base_ms):
    """The reference: one ``decode_packed`` of the blob at the base."""
    dictionary = StringDictionary()
    decoder = NativeDecoder(SCHEMA, dictionary, threads=4)
    mat = np.full((N_COLS + 1, capacity), -7, dtype=np.int32)
    rows, consumed = decoder.decode_packed(
        blob, mat, list(range(N_COLS)), N_COLS, base_ms, max_rows=capacity
    )
    return mat, rows, consumed, decoder.last_bad_timestamps, dictionary


def _in_passes(tmp_path, lines, capacity, cuts, base_ms, polled=None):
    """The lines before each cut through ``decode_ahead`` against
    BASE_MS, out of a receive buffer like the socket source's; then the
    blob of the first ``polled`` lines (all of them when None) through
    the poll's encode at ``base_ms``."""
    proc = _proc(tmp_path, capacity)
    blob = b"".join(ln + b"\n" for ln in lines)
    received = memoryview(bytearray(blob))
    at_line = at_byte = 0
    for cut in cuts:
        nbytes = sum(len(ln) + 1 for ln in lines[at_line:cut])
        assert proc.decode_ahead_cursor() == (at_byte, at_line)
        if proc.decode_ahead(
            received[at_byte:at_byte + nbytes], cut - at_line, BASE_MS
        ):
            at_line, at_byte = cut, at_byte + nbytes
    # nothing is counted before the poll has said what the batch is
    assert proc.ingest_stats == {} and proc.malformed_rows_total == 0
    raw = proc.encode_json_bytes(
        b"".join(ln + b"\n" for ln in lines[:polled]), base_ms,
        to_device=False, ahead_bytes=at_byte,
    )
    return proc, raw


@pytest.mark.parametrize("seconds_late", [0, 1, 2])
@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__)
def test_passes_then_finish_equal_the_one_shot_decode(
    tmp_path, case, seconds_late
):
    n = 900
    lines = case(n)
    capacity = len(lines) + 60
    base_ms = BASE_MS + 1000 * seconds_late
    rng = random.Random(f"{case.__name__}/{seconds_late}")
    if case is sharded_passes:
        # three passes of a third of the blob each: all over 256 KB
        cuts = [len(lines) // 3 + rng.randrange(50),
                2 * len(lines) // 3 + rng.randrange(50)]
    else:
        cuts = sorted(rng.sample(range(1, len(lines)), rng.randrange(1, 9)))
    blob = b"".join(ln + b"\n" for ln in lines)
    want, rows, consumed, bad_ts, dictionary = _one_shot(
        blob, capacity, base_ms)
    assert consumed == len(blob)

    proc, raw = _in_passes(tmp_path, lines, capacity, cuts, base_ms)
    got = raw.data
    early, of, _ms, passes = proc.decode_ahead_stats["default"]
    assert of == rows and passes == len(cuts)
    # passes against another base than the poll's are decoded again
    assert (early == 0) == (seconds_late > 0)
    valid = want[N_COLS] != 0
    assert int(valid.sum()) == rows
    if rows == len(lines):
        # no line malformed: byte for byte, zeroed tail included
        np.testing.assert_array_equal(got[:N_COLS], want[:N_COLS])
        np.testing.assert_array_equal(got[-1], want[N_COLS])
    else:
        # a malformed line's empty slot lies at the end of its pass
        # (or shard): the same valid rows in the same order, the
        # same cells, every other slot zero
        got_valid = got[-1] != 0
        np.testing.assert_array_equal(
            got[:N_COLS][:, got_valid], want[:N_COLS][:, valid])
        assert not got[:N_COLS][:, ~got_valid].any()
        assert set(np.unique(got[-1])) <= {0, 1}
    # rows the decoder does not own, whole
    assert not got[N_COLS:-1].any()
    assert proc.ingest_stats.get("malformed_rows", 0) == len(lines) - rows \
        == proc.malformed_rows_total
    assert proc.ingest_stats.get("bad_timestamps", 0) == bad_ts
    # the dictionary: the same strings under the same ids
    assert list(proc.dictionary.entries()) == list(dictionary.entries())
    # the matrix went to the batch, none is left staged
    assert proc._staged == {} and raw._ingest_pool[1] is got


@pytest.mark.parametrize("seconds_late", [0, 1, 2])
def test_more_lines_than_slots_finish_like_the_one_shot_decode(
    tmp_path, seconds_late
):
    """A blob of more lines than the matrix has slots: the passes that
    fit are kept, one that does not is refused, and the finish stops
    where one decode of the blob stops."""
    lines = clean(500)
    capacity = 400
    base_ms = BASE_MS + 1000 * seconds_late
    blob = b"".join(ln + b"\n" for ln in lines)
    want, rows, _consumed, _bad, dictionary = _one_shot(
        blob, capacity, base_ms)
    assert rows == capacity
    proc, raw = _in_passes(tmp_path, lines, capacity, [150, 390, 450],
                           base_ms)
    early, of, _ms, passes = proc.decode_ahead_stats["default"]
    assert (early, of, passes) == (0 if seconds_late else 390, capacity, 2)
    np.testing.assert_array_equal(raw.data[:N_COLS], want[:N_COLS])
    np.testing.assert_array_equal(raw.data[-1], want[N_COLS])
    assert proc.ingest_stats.get("malformed_rows", 0) == 0
    assert list(proc.dictionary.entries()) == list(dictionary.entries())


@pytest.mark.parametrize("kept", [0, 1, 2, 3])
def test_a_poll_that_cuts_before_the_passes_end_drops_the_rest(
    tmp_path, kept
):
    """The poll's cut is the batch: passes past it are dropped, their
    slots zeroed, and their lines left to the batch that is handed
    them (malformed lines and bad timestamps among them are counted
    with that batch, not twice); the next batch starts from an empty
    cursor."""
    lines = bad_timestamps(300)
    lines[7::40] = [b"broken"] * len(lines[7::40])
    cut_line = [40, 130, 250, 300][kept]
    blob = b"".join(ln + b"\n" for ln in lines[:cut_line])
    want, rows, _c, bad_ts, _d = _one_shot(blob, 320, BASE_MS)
    proc, raw = _in_passes(tmp_path, lines, 320, [100, 200, 300],
                           BASE_MS, polled=cut_line)
    valid, got_valid = want[N_COLS] != 0, raw.data[-1] != 0
    np.testing.assert_array_equal(
        raw.data[:N_COLS][:, got_valid], want[:N_COLS][:, valid])
    assert not raw.data[:N_COLS][:, ~got_valid].any()
    early = proc.decode_ahead_stats["default"][0]
    assert proc.decode_ahead_stats["default"][1] == rows
    assert (early > 0) == (kept > 0) and early <= rows
    assert proc.ingest_stats.get("malformed_rows", 0) == cut_line - rows \
        == proc.malformed_rows_total
    assert proc.ingest_stats.get("bad_timestamps", 0) == bad_ts > 0
    assert proc.decode_ahead_cursor() == (0, 0)


def test_dropping_the_staged_matrix_gives_the_slot_back(tmp_path):
    proc = _proc(tmp_path, 64, {
        "datax.job.process.debug.buffersanitizer": "true"})
    line = _event(1, BASE_MS + 5) + b"\n"
    assert proc.decode_ahead(memoryview(bytearray(line)), 1, BASE_MS)
    pool = proc._ingest_pools["default"]
    assert (pool.alloc_count, len(pool._free)) == (1, 0)
    proc.drop_decode_ahead()
    assert proc._staged == {} and len(pool._free) == 1
    # and a blob that does not begin with what was decoded ahead
    # (ahead_bytes 0) is decoded whole, in the slot that was being filled
    assert proc.decode_ahead(memoryview(bytearray(line)), 1, BASE_MS)
    other = _event(2, BASE_MS + 9) + b"\n"
    raw = proc.encode_json_bytes(other, BASE_MS, to_device=False)
    assert raw.data[0, 0] == 2 and raw.data[-1].sum() == 1
    assert proc.decode_ahead_stats["default"][:2] == (0, 1)
    assert (pool.alloc_count, len(pool._free)) == (1, 0)


# -- (c) the host ------------------------------------------------------------

INTERVAL_S = 0.5
INPUT_ROWS = "Input_DataXProcessedInput_Events_Count"


class _RecordingSink:
    kind = "recording"

    def __init__(self):
        self.batches = []  # [k...] a landed batch
        self.landed = threading.Event()

    def write(self, dataset, rows, batch_time_ms):
        self.batches.append([r["k"] for r in rows])
        self.landed.set()
        return len(rows)


MESHES = pytest.mark.parametrize("mesh", [None, 4], ids=["one-chip", "mesh4"])


def _host(tmp_path, extra=None, mesh=None):
    tmp_path.mkdir(exist_ok=True)
    t = tmp_path / "ahead.transform"
    t.write_text(TRANSFORM)
    conf = {
        "datax.job.name": "AheadHost",
        "datax.job.input.default.blobschemafile": SCHEMA_JSON,
        "datax.job.input.default.eventhub.maxrate": str(int(2000 / INTERVAL_S)),
        "datax.job.input.default.eventhub.checkpointdir": str(tmp_path / "ck"),
        "datax.job.input.default.eventhub.checkpointinterval": "1 millisecond",
        "datax.job.input.default.streaming.intervalinseconds": str(INTERVAL_S),
        "datax.job.process.transform": str(t),
        "datax.job.process.batchcapacity": "2048",
        "datax.job.process.debug.buffersanitizer": "true",
        "datax.job.process.debug.protocolmonitor": "true",
        "datax.job.output.Out.console.maxrows": "0",
    }
    conf.update(extra or {})
    if mesh:
        conf["datax.job.process.numchips"] = str(mesh)
    src = SocketSource(port=0)
    host = StreamingHost(SettingDictionary(conf), source=src)
    assert (host.processor.mesh.size if mesh else host.processor.mesh) == mesh
    # a pass waits for the bytes from which the decoder shards; a test's
    # lines are few: every wake of the wait makes a pass of them, as it
    # does at a deployment's rates, and not the last alone
    assert host._ahead_pass_bytes == (
        packed_shard_bytes() if host._ahead_sources else 0)
    host._ahead_pass_bytes = 1
    sink = _RecordingSink()
    host.dispatcher = OutputDispatcher(
        {"Out": OutputOperator("Out", [sink])}, host.metric_logger)
    metrics = []
    run_batch = host.run_batch

    def recording_run_batch():
        metrics.append(run_batch())
        return metrics[-1]

    host.run_batch = recording_run_batch
    # the backpressure of a batch that compiled is not the subject
    host._update_backpressure = lambda busy_ms: None
    # nor is the compile: an empty first batch takes it, so that every
    # later one is done long before its interval ends
    host.run(max_batches=1)
    del metrics[:], sink.batches[:]
    sink.landed.clear()
    return host, src, sink, metrics


def _payload(ks):
    return [_event(k, int(time.time() * 1000)) + b"\n" for k in ks]


def _send_after_each_landing(src, sink, groups, conns=1, stop=None):
    """A feeder thread: the first group at once, each later group in
    chunks over the ~60 ms after a batch has landed, that is inside
    the wait before the next poll. ``conns`` connections take the
    chunks in turn."""
    socks = [socket.create_connection(("127.0.0.1", src.port), 5.0)
             for _ in range(conns)]

    def feed():
        try:
            for g, ks in enumerate(groups):
                if g:
                    if not sink.landed.wait(60):
                        return
                    sink.landed.clear()
                lines = _payload(ks)
                for c in range(0, len(lines) // 100 + 1):
                    socks[c % conns].sendall(
                        b"".join(lines[100 * c:100 * c + 100]))
                    # every connection holds lines before a wake can
                    # see one of them alone
                    if g and c % conns == conns - 1:
                        time.sleep(0.01)
        finally:
            for s in socks:
                s.close()
            if stop is not None:
                stop.set()

    th = threading.Thread(target=feed, daemon=True)
    th.start()
    return th


def _wait_rows(src, n):
    deadline = time.time() + 30
    while src.buffered_rows < n and time.time() < deadline:
        time.sleep(0.005)
    assert src.buffered_rows >= n


def _pool_is_whole(host):
    pool = host.processor._ingest_pools["default"]
    return len(pool._free) == pool.alloc_count and not host.processor._staged


def _offsets(host):
    return dict(host.checkpointer.starting_positions())


@MESHES
def test_run_decodes_the_waits_arrivals_ahead_and_lands_the_same_rows(tmp_path, mesh):
    host, src, sink, metrics = _host(tmp_path, mesh=mesh)
    groups = [range(0, 300), range(300, 900), range(900, 1500),
              range(1500, 1800)]
    try:
        feeder = _send_after_each_landing(src, sink, groups)
        _wait_rows(src, 300)
        host.run(max_batches=5)
        feeder.join(30)
        assert not feeder.is_alive()
        # what one decode at each poll lands: every line once, in order,
        # a batch the lines that were whole at its poll
        assert sink.batches == [list(g) for g in groups]
        assert [m[INPUT_ROWS] for m in metrics] == [300, 600, 600, 300]
        assert [m.get("Input_malformed_rows_Count", 0) for m in metrics] \
            == [0, 0, 0, 0]
        assert _offsets(host) == {("socket", 0): 1800}
        # the first batch had no wait before it; the others were in
        # their matrices when their polls came, all but a last slice
        pct = [m["Decode_Ahead_Pct"] for m in metrics]
        assert pct[0] == 0.0 and min(pct[1:]) > 0.0
        assert all(m["Decode_Ahead_Passes"] >= 1 and m["Decode_Ahead_Ms"] > 0
                   for m in metrics[1:])
        assert all(m["Source_Backlog_Rows"] == 0 for m in metrics)
        assert all(m["Decode_RowsPerSec"] > 0 for m in metrics)
        san = host.processor.buffer_sanitizer
        assert san.poison_hits == 0 and san.drain_events() == []
        assert _pool_is_whole(host)
    finally:
        host.stop()


@MESHES
def test_a_poll_cut_below_what_was_staged_takes_its_rows_and_no_more(
    tmp_path, mesh
):
    """The poll's admission falls below what the wait staged (the
    pilot's ``admit_events``, here the rate scale, said at the poll):
    the batch is the poll's cut, the rest the next batch's."""
    host, src, sink, metrics = _host(tmp_path, mesh=mesh)
    groups = [range(0, 100), range(100, 1000)]
    poll = host._poll_and_encode

    def admitting_400_rows():
        host._rate_scale = 0.2
        try:
            return poll()
        finally:
            host._rate_scale = 1.0

    try:
        host._poll_and_encode = admitting_400_rows
        feeder = _send_after_each_landing(src, sink, groups)
        _wait_rows(src, 100)
        host.run(max_batches=5)
        feeder.join(30)
        assert sink.batches == [
            list(range(0, 100)), list(range(100, 500)),
            list(range(500, 900)), list(range(900, 1000))]
        assert [m[INPUT_ROWS] for m in metrics] == [100, 400, 400, 100]
        assert [m["Source_Backlog_Rows"] for m in metrics] == [0, 500, 100, 0]
        assert _offsets(host) == {("socket", 0): 1000}
        # passes ran before every poll but the first; what a poll's cut
        # left behind was staged again by the batch that took it (the
        # last batch's lines had all been decoded when its poll came)
        assert all(m["Decode_Ahead_Passes"] >= 1 for m in metrics[1:])
        assert metrics[-1]["Decode_Ahead_Pct"] == 100.0
        assert host.processor.buffer_sanitizer.poison_hits == 0
        assert _pool_is_whole(host)
    finally:
        host.stop()


def test_a_backlog_over_what_the_poll_admits_is_not_staged(tmp_path):
    """A host that is behind (``_rate_scale`` < 1 and more lines waiting
    than its poll will take) decodes the poll's cut once, at the poll:
    no pass decodes lines that the cut would drop."""
    host, src, sink, metrics = _host(tmp_path)
    host._rate_scale = 0.2  # a poll admits 400 rows
    try:
        conn = socket.create_connection(("127.0.0.1", src.port), 5.0)
        conn.sendall(b"".join(_payload(range(900))))
        _wait_rows(src, 900)
        host.run(max_batches=4)
        conn.close()
        assert sink.batches == [
            list(range(0, 400)), list(range(400, 800)),
            list(range(800, 900))]
        # 900 and 500 waiting lines are over the 400 a poll takes; the
        # 100 left after two polls are staged in the wait before the third
        assert [m["Decode_Ahead_Passes"] for m in metrics] == [0.0, 0.0, 1.0]
        assert [m["Decode_Ahead_Pct"] for m in metrics] == [0.0, 0.0, 100.0]
        assert _offsets(host) == {("socket", 0): 900}
        assert _pool_is_whole(host)
    finally:
        host.stop()


def test_a_failed_pass_leaves_the_lines_to_the_poll(tmp_path):
    """A pass that raises does not end the loop: the staging is dropped,
    the source is decoded at its polls from then on, rows as ever."""
    host, src, sink, metrics = _host(tmp_path)
    groups = [range(0, 100), range(100, 400), range(400, 500)]
    decode_pass = host.processor._decode_pass

    def failing_pass(decoder, data, staged, col_rows, valid_row, lines=None):
        if lines is not None:
            raise RuntimeError("pass boom")
        return decode_pass(decoder, data, staged, col_rows, valid_row)

    try:
        host.processor._decode_pass = failing_pass
        feeder = _send_after_each_landing(src, sink, groups)
        _wait_rows(src, 100)
        host.run(max_batches=4)
        feeder.join(30)
        assert sink.batches == [list(g) for g in groups]
        assert not host._ahead_sources
        assert all("Decode_Ahead_Pct" not in m for m in metrics[1:])
        assert _offsets(host) == {("socket", 0): 500}
        assert _pool_is_whole(host)
    finally:
        host.stop()


@MESHES
def test_a_batch_failed_at_dispatch_after_staging_is_redelivered_whole(tmp_path, mesh):
    host, src, sink, metrics = _host(tmp_path, mesh=mesh)
    groups = [range(0, 200), range(200, 700)]
    real_step = host.processor._step
    calls = []

    def failing_step(*a, **kw):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("dispatch boom")
        return real_step(*a, **kw)

    try:
        host.processor._step = failing_step
        feeder = _send_after_each_landing(src, sink, groups)
        _wait_rows(src, 200)
        with pytest.raises(RuntimeError, match="dispatch boom"):
            host.run(max_batches=4)
        feeder.join(30)
        assert sink.batches == [list(range(0, 200))]
        # the failed batch had been decoded ahead; its slot is back
        assert _pool_is_whole(host)
        # redelivered byte for byte, before anything new
        sink.landed.clear()
        feeder = _send_after_each_landing(
            src, sink, [range(700, 800), range(800, 1000)])
        _wait_rows(src, 100)
        assert src.arrived_lines() is None  # a requeued batch goes first
        blob, n, offsets = src.poll_raw(2000)
        assert n == 500 and offsets == {("socket", 0): (200, 700)}
        assert [json.loads(ln)["k"] for ln in blob.splitlines()] \
            == list(range(200, 700))
        src.requeue_unacked()

        host.processor._step = real_step
        host.run(max_batches=4)
        feeder.join(30)
        assert sink.batches == [
            list(range(0, 200)), list(range(200, 700)),
            list(range(700, 1000))]
        # the redelivery is decoded from its blob; the batch behind it
        # is intact, and staged like any other
        assert metrics[-2]["Decode_Ahead_Pct"] == 0.0
        assert metrics[-1]["Decode_Ahead_Pct"] > 0.0
        assert _offsets(host) == {("socket", 0): 1000}
        assert host.processor.buffer_sanitizer.poison_hits == 0
        assert _pool_is_whole(host)
    finally:
        host.stop()


@MESHES
def test_two_connections_fall_back_to_the_decode_at_the_poll(tmp_path, mesh):
    host, src, sink, metrics = _host(tmp_path, mesh=mesh)
    groups = [range(0, 200), range(200, 800), range(800, 1400)]
    try:
        feeder = _send_after_each_landing(src, sink, groups, conns=2)
        _wait_rows(src, 200)
        host.run(max_batches=4)
        feeder.join(30)
        assert [sorted(b) for b in sink.batches] == [list(g) for g in groups]
        assert [m[INPUT_ROWS] for m in metrics] == [200, 600, 600]
        assert [m["Decode_Ahead_Pct"] for m in metrics] == [0.0, 0.0, 0.0]
        assert [m["Decode_Ahead_Passes"] for m in metrics] == [0.0, 0.0, 0.0]
        assert _offsets(host) == {("socket", 0): 1400}
        assert _pool_is_whole(host)
    finally:
        host.stop()


@MESHES
def test_a_stop_during_the_wait_releases_the_staged_slot(tmp_path, mesh):
    host, src, sink, _metrics = _host(tmp_path, mesh=mesh)
    groups = [range(0, 100), range(100, 400)]
    try:
        feeder = _send_after_each_landing(src, sink, groups)
        _wait_rows(src, 100)
        loop = threading.Thread(target=host.run, daemon=True)
        loop.start()
        # the second group arrives in the wait after the first batch and
        # is staged; the loop is stopped before its poll
        deadline = time.time() + 60
        while not host.processor._staged and time.time() < deadline:
            time.sleep(0.002)
        assert host.processor._staged
        host._stop = True
        loop.join(30)
        feeder.join(30)
        assert not loop.is_alive()
        assert sink.batches == [list(range(0, 100))]
        assert _pool_is_whole(host)
        # nothing was delivered by the passes: the lines are still the
        # source's, from the first on
        assert src.buffered_rows == 300
        assert src.poll_raw(2000)[2] == {("socket", 0): (100, 400)}
    finally:
        host.stop()


def test_passes_under_a_mesh_land_what_one_decode_at_the_poll_lands(tmp_path):
    """A four-device mesh host decodes the wait's arrivals into the
    batch's matrix as a one-chip host does and puts it with its
    capacity axis sharded, a block a device: the rows at the sinks, the
    offsets and the input counts are those of the same host with the
    passes off (one decode at each poll)."""
    groups = [range(0, 300), range(300, 900), range(900, 1500),
              range(1500, 1800)]
    ran = {}
    for passes in (True, False):
        host, src, sink, metrics = _host(tmp_path / str(passes), mesh=4)
        rec = _Recorder(host)
        if not passes:
            host._ahead_sources.clear()
        try:
            feeder = _send_after_each_landing(src, sink, groups)
            _wait_rows(src, 300)
            host.run(max_batches=5)
            feeder.join(30)
            assert not feeder.is_alive()
            assert host.processor.last_decoder_path == "native-sharded"
            assert host.processor.placement()["rawDevices"] == {"default": 4}
            # the put is the mesh's own span, inside ``decode``
            for _m, spans, _root in rec.batches():
                lo, whole = spans["decode"]
                start, ms = spans["shard-put"]
                assert lo <= start and start + ms / 1e3 <= lo + whole / 1e3 + 1e-3
            assert host.processor.buffer_sanitizer.poison_hits == 0
            assert _pool_is_whole(host)
            ran[passes] = (sink.batches, _offsets(host),
                           [m[INPUT_ROWS] for m in metrics], metrics)
        finally:
            host.stop()
    assert ran[True][:3] == ran[False][:3]
    assert ran[True][0] == [list(g) for g in groups]
    pct = [m["Decode_Ahead_Pct"] for m in ran[True][3]]
    assert pct[0] == 0.0 and min(pct[1:]) > 0.0
    assert all(m["Decode_Ahead_Passes"] >= 1 and m["Decode_Ahead_Ms"] > 0
               for m in ran[True][3][1:])
    assert all("Decode_Ahead_Pct" not in m for m in ran[False][3])
    # the benchmark's two readers of the mesh cell read these counters;
    # a program that decodes nothing ahead under a mesh (the parent of
    # the PR that made it) has none, and the line leaves the metrics out
    from benchmark import readers

    ahead = {"measurements": ran[True][3][1:], "spans": []}
    bare = {"measurements": ran[False][3], "spans": []}
    assert readers.read_one("decode_ahead_pct.mesh4", {}, {}, ahead, {}) \
        == float(np.median(pct[1:]))
    assert readers.read_one("decode_ahead_ms.mesh4", {}, {}, ahead, {}) > 0.0
    for name in ("decode_ahead_pct.mesh4", "decode_ahead_ms.mesh4"):
        assert readers.read_one(name, {}, {}, bare, {}) is None


def test_a_pass_that_cannot_end_by_the_deadline_does_not_start(tmp_path):
    """The poll must not start later than it did: by the speed of the
    passes before it, a pass that would run past the deadline is left
    to the poll, and the wait's last wake leaves a pass its time."""
    host, src, _sink, _metrics = _host(tmp_path)
    passes = []
    try:
        host.processor.decode_ahead = lambda *a, **kw: passes.append(a) or True
        conn = socket.create_connection(("127.0.0.1", src.port), 5.0)
        conn.sendall(b"".join(_payload(range(1000))))
        _wait_rows(src, 1000)
        nbytes = len(src.arrived_lines()[0])
        # 1 MB/s measured: these bytes take far longer than is left
        host._ahead_passes.append((1 << 20, 1.0))
        assert host._pass_s(nbytes) == 1.5 * nbytes / (1 << 20)
        assert host._pass_s() == 1.5
        host._decode_arrived(time.time() + 0.010)
        assert passes == []
        # at the speed of a real pass they fit, and the pass is timed
        host._ahead_passes.clear()
        host._ahead_passes.append((nbytes, 0.0001))
        host._decode_arrived(time.time() + 0.5)
        assert len(passes) == 1 and len(host._ahead_passes) == 2
        # the slowest of the latest passes decides
        host._ahead_passes.append((nbytes, 0.004))
        assert host._pass_s(2 * nbytes) == pytest.approx(0.012)
        conn.close()
    finally:
        host.stop()


@pytest.mark.parametrize("slow", [
    (1 << 20, 10.0),  # by it no pass fits in the whole wait
    (1 << 10, 0.1),   # the wait wakes, and every pass is too long for it
])
def test_a_wait_that_makes_no_pass_measures_anew(tmp_path, slow):
    """One slow pass must not keep every later wait from making one: a
    wait in which, by the passes before it, no pass could end by the
    deadline forgets them, and the next wait makes passes again."""
    host, src, _sink, _metrics = _host(tmp_path)
    passes = []
    try:
        host.processor.decode_ahead = lambda *a, **kw: passes.append(a) or True
        conn = socket.create_connection(("127.0.0.1", src.port), 5.0)
        conn.sendall(b"".join(_payload(range(1000))))
        _wait_rows(src, 1000)
        host._ahead_passes.append(slow)
        host._pace(time.time() + 0.3)
        assert passes == [] and not host._ahead_passes
        host._pace(time.time() + 0.3)
        assert passes and host._ahead_passes
        conn.close()
    finally:
        host.stop()


def test_a_batch_off_the_packed_path_leaves_no_stale_ahead_stats(tmp_path):
    proc = _proc(tmp_path, 64)
    line = _event(1, BASE_MS + 5) + b"\n"
    proc.encode_json_bytes(line, BASE_MS, to_device=False)
    assert proc.decode_ahead_stats["default"] == (0, 1, 0.0, 0)
    proc.encode_json_bytes(line, BASE_MS, packed=False)
    assert proc.decode_ahead_stats == {}


# -- (d) the event's wait, and what no span holds ----------------------------

WAIT = ("Source_Wait_P50_Ms", "Source_Wait_P95_Ms", "Source_Wait_Max_Ms")
LANDING = ("Event_Landing_P50_Ms", "Event_Landing_P95_Ms")
CHAIN = ("decode", "dispatch", "device-step", "collect", "sinks")


class _Recorder:
    """A flight recorder in memory: a batch's end event and its spans."""

    def __init__(self, host):
        self.records = []
        host.telemetry.writers.append(self)

    def write(self, record):
        self.records.append(record)

    def batches(self):
        """(the end event's measurements, name -> (start s, ms) of the
        batch's spans, the root span's properties) a batch, in order."""
        spans, roots = {}, {}
        for r in self.records:
            if r["type"] == "span":
                spans.setdefault(r["trace"], {})[r["name"]] = (
                    r["startTs"], r["durationMs"])
                if r["name"] == "streaming/batch":
                    roots[r["properties"]["batchTime"]] = r
        return [
            (r["measurements"],
             spans[roots[r["properties"]["batchTime"]]["trace"]],
             roots[r["properties"]["batchTime"]]["properties"])
            for r in self.records if r.get("name") == "streaming/batch/end"]


def _unspanned_by_the_spans(spans):
    begin = spans["streaming/batch"][0]
    return (spans["emit"][0] - begin) * 1000.0 - sum(
        spans[name][1] for name in CHAIN)


def test_every_batch_says_how_old_its_rows_were_and_what_no_span_holds(
    tmp_path
):
    host, src, sink, metrics = _host(tmp_path)
    rec = _Recorder(host)
    groups = [range(0, 300), range(300, 900), range(900, 1500),
              range(1500, 1800)]
    try:
        feeder = _send_after_each_landing(src, sink, groups)
        _wait_rows(src, 300)
        time.sleep(0.1)
        host.run(max_batches=5)  # _host() ran the first
        feeder.join(30)
        batches = rec.batches()
        assert [m for m, _sp, _root in batches] == metrics
        assert [m[INPUT_ROWS] for m in metrics] == [300, 600, 600, 300]
        for i, (m, spans, root) in enumerate(batches):
            assert all(k in m for k in WAIT + LANDING), sorted(m)
            assert 0.0 <= m["Source_Wait_P50_Ms"] <= m["Source_Wait_P95_Ms"] \
                <= m["Source_Wait_Max_Ms"]
            # every row of a batch lands at one instant: its age there
            # is its wait at the cut plus the chain from the cut to the
            # end of ``sinks``
            sinks_end = spans["sinks"][0] + spans["sinks"][1] / 1000.0
            chain_ms = (sinks_end - root["polledTs"]) * 1000.0
            assert chain_ms > 0.0
            for q in ("P50", "P95"):
                assert m[f"Event_Landing_{q}_Ms"] - m[f"Source_Wait_{q}_Ms"] \
                    == pytest.approx(chain_ms, abs=0.1)
            # the cut lies inside the poll
            poll = spans["source-poll"]
            assert poll[0] <= root["polledTs"] <= poll[0] + poll[1] / 1000.0
            assert m["Batch_Unspanned_Ms"] >= 0.0
            assert m["Batch_Unspanned_Ms"] == pytest.approx(
                _unspanned_by_the_spans(spans), abs=0.1)
            assert m["Batch_Unspanned_Ms"] < m["Latency-Batch"]
            assert m["Host_Preempted_Count"] >= 0.0
            # a run's first batch follows no wait: nothing to be late for
            assert ("Loop_Late_Ms" in m) == (i > 0)
            assert m.get("Loop_Late_Ms", 0.0) >= 0.0
            assert m.get("Loop_Late_Ms", 0.0) < INTERVAL_S * 1000.0
        # the first group waited 100 ms and more for the run to begin;
        # the others were sent in the ~60 ms after a landing and waited
        # out what was left of the interval
        assert metrics[0]["Source_Wait_P50_Ms"] >= 100.0
        for m in metrics[1:]:
            assert 0.5 * INTERVAL_S * 1000.0 < m["Source_Wait_P50_Ms"] \
                < INTERVAL_S * 1000.0
            assert m["Event_Landing_P50_Ms"] < 1.5 * INTERVAL_S * 1000.0
        assert _pool_is_whole(host)
    finally:
        host.stop()


def test_a_redelivered_batch_is_as_old_as_its_first_arrival(tmp_path):
    host, src, sink, metrics = _host(tmp_path)
    real_step = host.processor._step
    calls = []

    def failing_step(*a, **kw):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("dispatch boom")
        return real_step(*a, **kw)

    try:
        host.processor._step = failing_step
        conn = socket.create_connection(("127.0.0.1", src.port), 5.0)
        conn.sendall(b"".join(_payload(range(200))))
        _wait_rows(src, 200)
        with pytest.raises(RuntimeError, match="dispatch boom"):
            host.run(max_batches=2)
        time.sleep(0.3)
        host.run(max_batches=2)
        conn.close()
        assert sink.batches == [list(range(200))]
        # the rows' age runs from when they first came in, not from
        # their second poll
        assert metrics[0]["Source_Wait_P50_Ms"] >= 300.0
        assert metrics[0]["Event_Landing_P50_Ms"] \
            > metrics[0]["Source_Wait_P50_Ms"]
    finally:
        host.stop()


def test_the_unpaced_loop_has_no_deadline_to_be_late_for(tmp_path):
    host, src, _sink, _metrics = _host(tmp_path)
    rec = _Recorder(host)
    try:
        conn = socket.create_connection(("127.0.0.1", src.port), 5.0)
        conn.sendall(b"".join(_payload(range(900))))
        _wait_rows(src, 900)
        host._rate_scale = 0.2  # a poll admits 400 rows
        host.run_pipelined(max_batches=4)  # _host() ran the first
        conn.close()
        batches = rec.batches()
        assert [m[INPUT_ROWS] for m, _sp, _root in batches] == [400, 400, 100]
        for m, spans, _root in batches:
            assert all(k in m for k in WAIT + LANDING)
            assert "Loop_Late_Ms" not in m
            assert m["Batch_Unspanned_Ms"] == pytest.approx(
                _unspanned_by_the_spans(spans), abs=0.1)
            # the landing thread's time to resolve the streamed tables
            # is the ``collect`` span's: one measurement, not two
            assert m["Transfer_Background_LandMs"] == pytest.approx(
                spans["collect"][1], abs=1e-3)
        # a backlog's rows are older at each poll
        waits = [m["Source_Wait_P50_Ms"] for m, _sp, _root in batches]
        assert waits == sorted(waits)
    finally:
        host.stop()


def test_a_source_with_no_notion_of_arrival_reports_no_wait(tmp_path):
    t = tmp_path / "local.transform"
    t.write_text(TRANSFORM)
    host = StreamingHost(SettingDictionary({
        "datax.job.name": "LocalWait",
        "datax.job.input.default.inputtype": "local",
        "datax.job.input.default.blobschemafile": SCHEMA_JSON,
        "datax.job.input.default.eventhub.maxrate": "100",
        "datax.job.input.default.streaming.intervalinseconds": "0.2",
        "datax.job.process.transform": str(t),
        "datax.job.process.batchcapacity": "64",
        "datax.job.output.Out.console.maxrows": "0",
    }))
    rec = _Recorder(host)
    try:
        host.run(max_batches=3)
        batches = rec.batches()
        assert len(batches) == 3
        for i, (m, spans, root) in enumerate(batches):
            assert not any(k in m for k in WAIT + LANDING)
            assert "polledTs" not in root
            assert m["Batch_Unspanned_Ms"] == pytest.approx(
                _unspanned_by_the_spans(spans), abs=0.1)
            assert m["Host_Preempted_Count"] >= 0.0
            assert ("Loop_Late_Ms" in m) == (i > 0)
    finally:
        host.stop()
