"""Restart/recovery e2e: offset checkpoint resume, A/B state reload,
backpressure, the profiler hook (SURVEY §5.3/§5.4 hardening), and the
depth-N in-flight window's failure semantics (FIFO commit +
at-least-once requeue at depths 1/2/4, UDF refresh mid-window)."""

import json
import os
import socket
import time as _time

import numpy as np
import pytest

from data_accelerator_tpu.core.config import SettingDictionary
from data_accelerator_tpu.runtime.host import StreamingHost
from data_accelerator_tpu.runtime.sources import FileSource, SocketSource

SCHEMA = json.dumps({"type": "struct", "fields": [
    {"name": "k", "type": "long", "nullable": False, "metadata": {}},
    {"name": "v", "type": "double", "nullable": False, "metadata": {}},
]})


def _write_events(path, rows):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


def _conf(tmp_path, extra=None):
    t = tmp_path / "t.transform"
    if not t.exists():
        t.write_text(
            "--DataXQuery--\n"
            "merged = SELECT k, v FROM DataXProcessedInput "
            "UNION ALL SELECT k, v FROM seen\n"
            "--DataXQuery--\n"
            "seen = SELECT k, MAX(v) AS v FROM merged GROUP BY k\n"
            "--DataXQuery--\n"
            "Out = SELECT k, v FROM DataXProcessedInput\n"
        )
    d = {
        "datax.job.name": "RecFlow",
        "datax.job.input.default.inputtype": "file",
        "datax.job.input.default.blobpathregex": str(tmp_path / "in" / "*.json"),
        "datax.job.input.default.blobschemafile": SCHEMA,
        "datax.job.input.default.eventhub.maxrate": "100",
        "datax.job.input.default.eventhub.checkpointdir": str(tmp_path / "ckpt"),
        "datax.job.input.default.eventhub.checkpointinterval": "0 second",
        "datax.job.input.default.streaming.intervalinseconds": "1",
        "datax.job.process.transform": str(t),
        "datax.job.process.batchcapacity": "16",
        "datax.job.process.statetable.seen.schema": "k long, v double",
        "datax.job.process.statetable.seen.location": str(tmp_path / "state"),
        "datax.job.output.Out.console.maxrows": "0",
    }
    d.update(extra or {})
    return SettingDictionary(d)


def _state_map(host):
    loaded = host.processor.state_tables["seen"].load(host.processor.dictionary)
    return {
        int(k): float(v) for k, v, ok in zip(
            np.asarray(loaded.cols["k"]),
            np.asarray(loaded.cols["v"]),
            np.asarray(loaded.valid),
        ) if ok
    }


def test_restart_resumes_offsets_and_state(tmp_path):
    """Kill the host after batch 1, start a fresh one: the file source
    resumes past consumed files (offsets.txt) and the A/B state table
    reloads the accumulated rows."""
    _write_events(str(tmp_path / "in" / "a.json"),
                  [{"k": 1, "v": 5.0}, {"k": 2, "v": 7.0}])
    host1 = StreamingHost(_conf(tmp_path))
    host1.run_batch()
    host1.stop()
    assert os.path.exists(tmp_path / "ckpt" / "offsets.txt")
    assert _state_map(host1) == {1: 5.0, 2: 7.0}

    # second file arrives; a NEW host process takes over
    _write_events(str(tmp_path / "in" / "b.json"), [{"k": 1, "v": 9.0}])
    host2 = StreamingHost(_conf(tmp_path))
    m = host2.run_batch()
    host2.stop()
    # only the new file's rows were ingested (a.json not replayed)
    assert m["Input_DataXProcessedInput_Events_Count"] == 1.0
    # state reloaded + accumulated across the restart
    assert _state_map(host2) == {1: 9.0, 2: 7.0}


def test_write_offsets_fsyncs_file_and_directory(tmp_path, monkeypatch):
    """Satellite: the offsets checkpoint must survive POWER LOSS, not
    just a process crash — the tmp file is fsynced before os.replace
    and the directory entry is fsynced after it. Verified by recording
    every fsync the write performs and mapping the fds back to their
    paths."""
    from data_accelerator_tpu.runtime.checkpoint import (
        OffsetCheckpointer,
        PartitionOffset,
    )

    synced = []
    real_fsync = os.fsync

    def spy_fsync(fd):
        try:
            synced.append(os.readlink(f"/proc/self/fd/{fd}"))
        except OSError:
            synced.append("<unknown>")
        return real_fsync(fd)

    monkeypatch.setattr(os, "fsync", spy_fsync)
    ck = OffsetCheckpointer(str(tmp_path / "ck"))
    ck.write_offsets([PartitionOffset(1, "default", 0, 0, 42)])
    # the data file (still named .tmp when synced) and its directory
    assert any(p.endswith("offsets.txt.tmp") for p in synced), synced
    assert any(p.rstrip("/").endswith("ck") for p in synced), synced
    # and the write still round-trips
    assert ck.read_offsets() == [PartitionOffset(1, "default", 0, 0, 42)]
    assert ck.starting_positions() == {("default", 0): 42}


def test_state_table_writes_survive_torn_write(tmp_path, monkeypatch):
    """Satellite: StateTable snapshots now carry the checkpointers'
    power-loss contract — table.npz/meta.json AND the pointer commit
    are fsynced (file + directory) through _durable_replace, and a torn
    active-side write (power loss mid-flush) falls back to the standby
    commit instead of killing the host."""
    import jax.numpy as jnp

    from data_accelerator_tpu.compile.planner import TableData, ViewSchema
    from data_accelerator_tpu.core.schema import StringDictionary
    from data_accelerator_tpu.runtime.statetable import StateTable

    synced = []
    real_fsync = os.fsync

    def spy_fsync(fd):
        try:
            synced.append(os.readlink(f"/proc/self/fd/{fd}"))
        except OSError:
            synced.append("<unknown>")
        return real_fsync(fd)

    monkeypatch.setattr(os, "fsync", spy_fsync)
    schema = ViewSchema({"k": "long", "v": "double"})
    d = StringDictionary()

    def table(v):
        return TableData(
            {"k": jnp.asarray(np.array([7], np.int32)),
             "v": jnp.asarray(np.array([v], np.float32))},
            jnp.asarray(np.array([True])),
        )

    st = StateTable("seen", schema, 4, str(tmp_path / "st"), partitions=2)
    st.overwrite(table(1.0), d)
    st.persist()
    # snapshot data, sidecar and pointer all fsynced while still .tmp
    assert any(p.endswith("table.npz.tmp") for p in synced), synced
    assert any(p.endswith("meta.json.tmp") for p in synced), synced
    assert any(p.endswith("pointer.tmp") for p in synced), synced
    st.overwrite(table(2.0), d)
    st.persist()

    # torn write: truncate the ACTIVE side's snapshot of key 7's
    # partition, as a crash-then-power-loss would leave it
    from data_accelerator_tpu.runtime.statepartition import (
        LocalSnapshotStore,
        partition_of,
    )

    p = partition_of(7, 2)
    active = LocalSnapshotStore(str(tmp_path / "st")).get_pointer(f"p{p:02d}")
    path = tmp_path / "st" / f"p{p:02d}" / active / "table.npz"
    path.write_bytes(path.read_bytes()[:8])

    stats = {}
    st2 = StateTable("seen", schema, 4, str(tmp_path / "st"), partitions=2,
                     stats=stats)
    loaded = st2.load(StringDictionary())
    vals = {
        int(k): float(v) for k, v, ok in zip(
            np.asarray(loaded.cols["k"]), np.asarray(loaded.cols["v"]),
            np.asarray(loaded.valid),
        ) if ok
    }
    assert vals == {7: 1.0}  # the standby (previous) commit, not a crash
    assert stats["LoadFallback_Count"] >= 1


def test_window_checkpoint_restores_previous_on_truncated_tmp(tmp_path):
    """Satellite: a crash mid-save leaves a torn ``window.npz.tmp``
    behind — restore must come from the previous COMPLETE checkpoint,
    never the torn tmp file."""
    from data_accelerator_tpu.runtime.checkpoint import (
        WindowStateCheckpointer,
    )

    ck = WindowStateCheckpointer(str(tmp_path / "ck"))
    snap = {
        "rings": {"T": {
            "cols": {"k": np.arange(8, dtype=np.int32).reshape(2, 4)},
            "valid": np.ones((2, 4), bool),
        }},
        "slot_counter": 5,
        "base_ms": 123_000,
    }
    ck.save(snap)
    # a later save died mid-write: torn tmp beside the good checkpoint
    good = open(ck.path, "rb").read()
    with open(ck.path + ".tmp", "wb") as f:
        f.write(good[: len(good) // 3])
    restored = WindowStateCheckpointer(str(tmp_path / "ck")).load()
    assert restored is not None
    assert restored["slot_counter"] == 5
    assert (restored["rings"]["T"]["cols"]["k"]
            == snap["rings"]["T"]["cols"]["k"]).all()

    # and a torn MAIN file falls back to the .old backup
    ck.save({**snap, "slot_counter": 6})  # rotates the good one to .old
    with open(ck.path, "wb") as f:
        f.write(good[: len(good) // 3])
    restored = WindowStateCheckpointer(str(tmp_path / "ck")).load()
    assert restored is not None and restored["slot_counter"] == 5


def test_backpressure_halves_rate_on_overrun(tmp_path, monkeypatch):
    _write_events(str(tmp_path / "in" / "a.json"), [{"k": 1, "v": 1.0}])
    host = StreamingHost(_conf(tmp_path, {
        "datax.job.input.default.streaming.intervalinseconds": "0.001",
    }))
    host.run_batch()  # any real batch overruns a 1 ms interval
    assert host._rate_scale == 0.5
    host.stop()


# ---------------------------------------------------------------------------
# depth-N in-flight window: failure injection at depths 1/2/4
# ---------------------------------------------------------------------------
DEPTH_SCHEMA = json.dumps({"type": "struct", "fields": [
    {"name": "k", "type": "long", "nullable": False, "metadata": {}},
    {"name": "v", "type": "double", "nullable": False, "metadata": {}},
]})


class _RecordingSink:
    """Records successful writes in arrival order; raises (BEFORE
    recording) on any batch containing a poisoned k value while armed.
    Also records which thread each write ran on (the background landing
    path runs sinks on the dedicated landing worker) and optionally
    sleeps first so landings genuinely queue behind the dispatch
    loop."""

    kind = "recording"

    def __init__(self):
        self.batches = []  # (batch_time_ms, [k...]) per successful write
        self.poison_k = None
        self.threads = []  # thread name per write attempt
        self.delay_s = 0.0

    def write(self, dataset, rows, batch_time_ms):
        import threading

        self.threads.append(threading.current_thread().name)
        if self.delay_s:
            _time.sleep(self.delay_s)
        ks = [r["k"] for r in rows]
        if self.poison_k is not None and self.poison_k in ks:
            raise RuntimeError(f"poisoned batch (k={self.poison_k})")
        self.batches.append((batch_time_ms, ks))
        return len(rows)


def _depth_host(tmp_path, depth):
    """StreamingHost over a SocketSource (the UnackedFifo source) with a
    recording sink on its one output; 4 events per poll."""
    from data_accelerator_tpu.runtime.sinks import (
        OutputDispatcher,
        OutputOperator,
    )

    t = tmp_path / "depth.transform"
    t.write_text(
        "--DataXQuery--\n"
        "Out = SELECT k, v FROM DataXProcessedInput\n"
    )
    conf = SettingDictionary({
        "datax.job.name": f"Depth{depth}",
        "datax.job.input.default.blobschemafile": DEPTH_SCHEMA,
        "datax.job.input.default.eventhub.maxrate": "4",
        "datax.job.input.default.streaming.intervalinseconds": "1",
        "datax.job.process.transform": str(t),
        "datax.job.process.batchcapacity": "4",
        "datax.job.process.pipeline.depth": str(depth),
        # the buffer sanitizer rides every recovery drill: crash/requeue
        # churn at depth 2/4 is exactly where an escaped pooled view
        # would surface, and the suite asserts it stays silent
        "datax.job.process.debug.buffersanitizer": "true",
        # the protocol monitor rides along too: every sealed batch
        # (including the poisoned/requeued ones) must linearize to the
        # declared sink -> flip -> ack ordering
        "datax.job.process.debug.protocolmonitor": "true",
        "datax.job.output.Out.console.maxrows": "0",
    })
    src = SocketSource(port=0)
    host = StreamingHost(conf, source=src)
    sink = _RecordingSink()
    host.dispatcher = OutputDispatcher(
        {"Out": OutputOperator("Out", [sink])}, host.metric_logger
    )
    return host, src, sink


def _feed_socket(src, n_events):
    conn = socket.create_connection(("127.0.0.1", src.port), timeout=5)
    payload = b"".join(
        json.dumps({"k": i, "v": float(i)}).encode() + b"\n"
        for i in range(n_events)
    )
    conn.sendall(payload)
    conn.close()
    deadline = _time.time() + 5
    while _time.time() < deadline and src.buffered_rows < n_events:
        _time.sleep(0.01)
    assert src.buffered_rows == n_events


def _delivered_ks(blob):
    return [json.loads(ln)["k"] for ln in blob.splitlines() if ln.strip()]


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_depth_window_sink_failure_fifo_and_requeue(tmp_path, depth):
    """A sink failure anywhere in the window: batches already finished
    stay committed in FIFO order, the failed batch and EVERY un-acked
    batch behind it requeue in order, and a rerun delivers all events
    exactly once through the sink (no lost, no duplicated offsets)."""
    host, src, sink = _depth_host(tmp_path, depth)
    try:
        _feed_socket(src, 16)  # batches B1(k 0-3) .. B4(k 12-15)
        sink.poison_k = 9  # B3's finish fails at the sink
        with pytest.raises(RuntimeError, match="poisoned"):
            host.run_pipelined(max_batches=4)
        # FIFO: exactly B1 and B2 committed, in dispatch order
        assert [ks for _t, ks in sink.batches] == [
            [0, 1, 2, 3], [4, 5, 6, 7],
        ]
        times = [t for t, _ks in sink.batches]
        assert times == sorted(times)
        assert host.batches_processed == 2

        # every un-acked batch in the window re-delivers in order
        b3, n3, _ = src.poll_raw(4)
        assert _delivered_ks(b3) == [8, 9, 10, 11]
        b4, n4, _ = src.poll_raw(4)
        assert _delivered_ks(b4) == [12, 13, 14, 15]
        src.requeue_unacked()  # hand them back for the rerun

        # rerun with the sink healed: everything lands exactly once
        sink.poison_k = None
        host.run_pipelined(max_batches=4)
        assert host.batches_processed == 4
        all_ks = [k for _t, ks in sink.batches for k in ks]
        assert all_ks == list(range(16))  # no loss, no duplication
        # the armed buffer sanitizer saw the whole failure/rerun cycle:
        # zero poison hits means no pooled/donated view outlived its slot
        san = host.processor.buffer_sanitizer
        assert san is not None and san.poison_hits == 0
        assert san.drain_events() == []
    finally:
        host.stop()


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_depth_window_dispatch_failure_requeues_window(tmp_path, depth):
    """A dispatch failure mid-window: nothing is acked past the oldest
    committed batch, every polled-but-unfinished batch requeues in
    order, and a rerun completes with exactly-once sink delivery."""
    host, src, sink = _depth_host(tmp_path, depth)
    try:
        _feed_socket(src, 16)
        real_dispatch = host.processor.dispatch_batch
        calls = {"n": 0}

        def failing_dispatch(*a, **kw):
            calls["n"] += 1
            if calls["n"] == 3:  # B3's dispatch blows up (re-trace error)
                raise RuntimeError("dispatch boom")
            return real_dispatch(*a, **kw)

        host.processor.dispatch_batch = failing_dispatch
        with pytest.raises(RuntimeError, match="dispatch boom"):
            host.run_pipelined(max_batches=4)
        finished = [ks for _t, ks in sink.batches]
        # at depth 1 B1 finished before B3's dispatch; at depth >= 2 the
        # whole window was still in flight — either way commit order is
        # FIFO with no gaps
        assert finished == [[0, 1, 2, 3]][: len(finished)]
        n_done = host.batches_processed

        # un-acked batches (everything not finished) re-deliver in order
        redelivered = []
        for _ in range(4 - n_done):
            blob, n, _ = src.poll_raw(4)
            assert n == 4
            redelivered.extend(_delivered_ks(blob))
        assert redelivered == list(range(n_done * 4, 16))
        src.requeue_unacked()

        host.processor.dispatch_batch = real_dispatch
        host.run_pipelined(max_batches=4)
        assert host.batches_processed == 4
        all_ks = [k for _t, ks in sink.batches for k in ks]
        assert all_ks == list(range(16))
    finally:
        host.stop()


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_background_landing_failure_drains_and_requeues(tmp_path, depth):
    """Tentpole failure injection: sinks run on the BACKGROUND landing
    thread (counts-only sync on the dispatch loop) and the sink throws
    while later batches' transfers are in flight. The whole un-acked
    window requeues, pending landings are drained (not left queued),
    FIFO commit order holds, and a healed rerun delivers every event
    exactly once."""
    import threading

    host, src, sink = _depth_host(tmp_path, depth)
    try:
        assert host._landing_pool is not None  # one chip: background landing
        # spy on the batch tail so the test can prove it ran on the
        # background landing worker, not the dispatch loop
        tail_threads = []
        orig_tail = host._finish_tail

        def spy_tail(*a, **kw):
            tail_threads.append(threading.current_thread().name)
            return orig_tail(*a, **kw)

        host._finish_tail = spy_tail
        sink.delay_s = 0.05  # landings queue while the loop dispatches
        _feed_socket(src, 16)  # batches B1(k 0-3) .. B4(k 12-15)
        sink.poison_k = 9  # B3's landing fails at the sink
        with pytest.raises(RuntimeError, match="poisoned"):
            host.run_pipelined(max_batches=4)
        # batch tails genuinely ran out-of-band on the landing worker
        assert tail_threads and all(
            t.startswith("landing") for t in tail_threads
        )
        # the landing queue was drained before the requeue — nothing
        # still in flight to ack a requeued batch behind our back
        assert len(host._landings) == 0
        assert host._landing_failed is not None
        # FIFO: exactly B1 and B2 committed, in dispatch order
        assert [ks for _t, ks in sink.batches] == [
            [0, 1, 2, 3], [4, 5, 6, 7],
        ]
        assert host.batches_processed == 2
        # every un-acked batch in the window re-delivers in order
        redelivered = []
        for _ in range(2):
            blob, n, _ = src.poll_raw(4)
            assert n == 4
            redelivered.extend(_delivered_ks(blob))
        assert redelivered == list(range(8, 16))
        src.requeue_unacked()

        # healed rerun: exactly-once delivery, failure flag re-armed
        sink.poison_k = None
        sink.delay_s = 0.0
        host.run_pipelined(max_batches=4)
        assert host._landing_failed is None
        assert host.batches_processed == 4
        all_ks = [k for _t, ks in sink.batches for k in ks]
        assert all_ks == list(range(16))
    finally:
        host.stop()


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_decode_buffer_pool_safe_under_pipelined_window(tmp_path, depth):
    """Satellite: the pooled ingest matrices under decode-ahead at
    depths 1/2/4 with failure-requeue. The pool may hand a matrix to a
    new decode ONLY after its owning batch released it (landed or
    abandoned post-step) — never while the batch is in flight, where
    the device step zero-copies the buffer. Asserted structurally (no
    matrix is double-acquired while outstanding) and end-to-end (after
    a poisoned-sink failure plus requeue, every event lands exactly
    once with correct VALUES — a clobbered in-flight buffer would
    corrupt rows, not just ordering)."""
    from data_accelerator_tpu.native import native_available

    if not native_available():
        pytest.skip("native decoder unavailable")
    host, src, sink = _depth_host(tmp_path, depth)
    try:
        # instrument every pool the processor creates: acquire must
        # never return a matrix that is still owned by an un-released
        # batch
        outstanding = set()
        violations = []
        orig_encode = host.processor._encode_packed_native

        def spy_encode(*args):
            pr = orig_encode(*args)
            pool, mat = pr._ingest_pool
            if id(mat) in outstanding:
                violations.append(id(mat))
            outstanding.add(id(mat))
            orig_release = pool.release

            def tracked_release(m, _orig=orig_release):
                outstanding.discard(id(m))
                _orig(m)

            pool.release = tracked_release
            return pr

        host.processor._encode_packed_native = spy_encode

        _feed_socket(src, 16)  # batches B1(k 0-3) .. B4(k 12-15)
        sink.poison_k = 9  # B3 fails at the sink mid-window
        with pytest.raises(RuntimeError, match="poisoned"):
            host.run_pipelined(max_batches=4)
        src.requeue_unacked()
        sink.poison_k = None
        host.run_pipelined(max_batches=4)

        assert not violations, (
            "ingest pool handed out a matrix still owned by an "
            "in-flight batch"
        )
        # exactly-once with intact VALUES through the reused buffers
        all_ks = [k for _t, ks in sink.batches for k in ks]
        assert all_ks == list(range(16))
        # the pool genuinely reused matrices, bounded by the window
        # (decode-ahead + pending + landing backlog), NOT one fresh
        # allocation for each of the 8 decodes across the two runs
        pools = host.processor._ingest_pools.values()
        assert sum(p.reuse_count for p in pools) > 0
        assert all(p.alloc_count <= depth + 4 for p in pools)
        # nothing left un-released once every batch landed
        assert not outstanding
    finally:
        host.stop()


def test_udf_refresh_mid_window_uses_snapshotted_pipeline(tmp_path):
    """A UDF on_interval refresh (re-trace) while earlier batches are
    still in flight: each PendingBatch decodes against the
    pipeline/schemas of the step that produced it — batches dispatched
    before the refresh keep the old captured state, the one after gets
    the new state, collected FIFO across the window."""
    import jax.numpy as jnp

    from data_accelerator_tpu.runtime.processor import FlowProcessor
    from data_accelerator_tpu.udf import JaxUdf

    state = {"factor": 2.0, "pending": False}

    def refresh(ts):
        if state["pending"]:
            state["factor"] = 3.0
            state["pending"] = False
            return True
        return False

    u = JaxUdf(
        "dynscale",
        lambda x: x.astype(jnp.float32) * state["factor"],
        out_type="double",
        on_interval=refresh,
    )
    t = tmp_path / "udf.transform"
    t.write_text(
        "--DataXQuery--\n"
        "T = SELECT k, dynscale(v) AS s FROM DataXProcessedInput\n"
    )
    proc = FlowProcessor(
        SettingDictionary({
            "datax.job.name": "RefreshWindow",
            "datax.job.input.default.blobschemafile": DEPTH_SCHEMA,
            "datax.job.process.transform": str(t),
            "datax.job.process.batchcapacity": "8",
            "datax.job.process.pipeline.depth": "4",
        }),
        udfs={"dynscale": u},
        output_datasets=["T"],
    )
    rows = [{"k": 1, "v": 5.0}]
    h1 = proc.dispatch_batch(proc.encode_rows(rows, 0), 1000)
    h2 = proc.dispatch_batch(proc.encode_rows(rows, 0), 2000)
    state["pending"] = True  # the NEXT dispatch's refresh re-traces
    h3 = proc.dispatch_batch(proc.encode_rows(rows, 0), 3000)
    # collect strictly FIFO, all three still in flight until now
    d1, _ = h1.collect()
    d2, _ = h2.collect()
    d3, _ = h3.collect()
    assert d1["T"][0]["s"] == 10.0  # old trace (factor 2)
    assert d2["T"][0]["s"] == 10.0  # dispatched pre-refresh: snapshot
    assert d3["T"][0]["s"] == 15.0  # post-refresh trace (factor 3)


def test_profiler_hook_writes_trace(tmp_path):
    """On-demand profiler surface (obs/profiler.py, the first-N-batches
    dump's replacement): arming a capture on a live host writes a
    loadable jax trace under the capture dir, and the finished capture
    drains into the next batch's trace as a profiler/capture span."""
    prof_dir = tmp_path / "prof"
    _write_events(str(tmp_path / "in" / "a.json"),
                  [{"k": 1, "v": 1.0}, {"k": 2, "v": 2.0}])
    host = StreamingHost(_conf(tmp_path, {
        "datax.job.process.observability.profilerdir": str(prof_dir),
    }))
    assert host.profiler is not None
    res = host.profiler.start(seconds=60)  # stopped explicitly below
    assert res.get("path"), res
    host.run_batch()
    host.profiler.stop()
    host.run_batch()  # drains the capture into this batch's trace
    assert host.profiler.captures_count == 1
    host.stop()
    traces = []
    for root, _d, files in os.walk(res["path"]):
        traces += [f for f in files if "trace" in f or f.endswith(".pb")]
    assert traces, f"no profiler trace written under {res['path']}"


# ---------------------------------------------------------------------------
# the seeded PR 18 regression: the SAME ack-before-checkpoint reorder
# of StreamingHost's batch tail is caught by BOTH halves of the DX9xx
# protocol gate — statically (analysis/protocheck.py names the
# function) and dynamically (the armed ProtocolMonitor fires DX906
# under sink-failure injection, exactly once)
# ---------------------------------------------------------------------------
_SEEDED_REORDER_SRC = '''\
class StreamingHost:
    def _finish(self, handle, batch_time_ms):
        try:
            datasets, metrics = handle.collect_tables()
            for name, s in self.sources.items():
                s.ack()
            self.dispatcher.dispatch(datasets, batch_time_ms)
            self.processor.commit()
        except Exception:
            for name, s in self.sources.items():
                s.requeue_unacked()
            raise
'''


def test_seeded_ack_reorder_caught_statically(tmp_path):
    """The static half: a StreamingHost whose tail acks the FIFO first
    (the seeded reorder below, verbatim) analyzes to DX900 naming
    StreamingHost._finish — plus the DX904 rider on the now-post-ack
    sink emit. The protocol gate fails this source before it ships."""
    from data_accelerator_tpu.analysis import analyze_proto_modules

    seeded = tmp_path / "seeded_host.py"
    seeded.write_text(_SEEDED_REORDER_SRC)
    report = analyze_proto_modules([str(seeded)])
    assert not report.ok
    assert {d.code for d in report.diagnostics} == {"DX900", "DX904"}
    (dx900,) = [d for d in report.diagnostics if d.code == "DX900"]
    assert "StreamingHost._finish" in dx900.message
    assert "before the durable pointer flip" in dx900.message


def test_seeded_ack_reorder_caught_dynamically_by_monitor(tmp_path):
    """The dynamic half: bind the SAME reorder onto a live host (ack
    before dispatch/commit), poison the sink, run one batch. The acked
    FIFO has nothing left to requeue — the classic lost-batch bug —
    and the armed ProtocolMonitor convicts it: the failed batch seals
    to [FIFO_ACK, REQUEUE] and fires EXACTLY ONE DX906 citing DX900."""
    import types

    host, src, sink = _depth_host(tmp_path, depth=1)

    def _reordered_tail(self, handle, consumed, batch_time_ms, t0,
                        trace, inflight_depth, stall_ms, backlog,
                        requeue_on_error=True):
        pm = self.protocol_monitor
        try:
            with trace.activate():
                datasets, _metrics = handle.collect_tables()
                for name, s in self.sources.items():
                    s.ack()  # the seeded bug: ack FIRST
                    if pm is not None:
                        pm.record("FIFO_ACK", source=name)
                self.dispatcher.dispatch(datasets, batch_time_ms)
                if pm is not None:
                    pm.record("SINK_EMIT", detail="dispatcher.dispatch")
                self.processor.commit()
                if pm is not None:
                    pm.record("POINTER_FLIP", detail="processor.commit")
        except Exception:
            trace.end(status="error")
            if requeue_on_error:
                for name, s in self.sources.items():
                    s.requeue_unacked()
                    if pm is not None:
                        pm.record("REQUEUE", source=name)
            if pm is not None:
                pm.seal_batch(batch_time_ms, failed=True)
            raise
        if pm is not None:
            pm.seal_batch(batch_time_ms)
        self.batches_processed += 1
        return {}

    host._finish_tail = types.MethodType(_reordered_tail, host)
    try:
        _feed_socket(src, 4)  # one batch (k 0-3)
        sink.poison_k = 1     # fails at the sink — AFTER the ack
        pm = host.protocol_monitor
        assert pm is not None  # armed by _depth_host's conf
        with pytest.raises(RuntimeError, match="poisoned"):
            host.run_pipelined(max_batches=1)
        # the monitor convicted the reorder on the failed batch
        assert pm.violations == 1
        assert pm.batches_sealed == 1
        events = pm.drain_events()
        assert len(events) == 1, events
        ev = events[0]
        assert ev["code"] == "DX906"
        assert ev["rule"] == "DX900"
        assert ev["failed"] is True
        # the pipelined window requeues at the WINDOW level after the
        # tail seals (host.run_pipelined's except), so the sealed
        # linearization is the bare premature ack
        assert ev["sequence"] == ["FIFO_ACK"]
        assert "FAILED batch" in ev["message"]
        # and the bug is REAL: the acked FIFO had nothing to requeue,
        # so the poisoned batch is gone (the loss DX900 predicts)
        blob, n, _ = src.poll_raw(4)
        assert n == 0 and not blob.strip()
    finally:
        host.stop()
