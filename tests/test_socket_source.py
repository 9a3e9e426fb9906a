"""SocketSource: the wire's bytes are received into one contiguous
buffer a connection and handed to the decoder as a cut of it that ends
at a line boundary. What the per-line reader did (`for line in f`,
`strip()`, a list of `bytes`) has to hold without an object a line."""

import gc
import json
import socket
import sys
import threading
import time

import numpy as np
import pytest

from data_accelerator_tpu.core.schema import Schema, StringDictionary
from data_accelerator_tpu.native import NativeDecoder
from data_accelerator_tpu.runtime import sources
from data_accelerator_tpu.runtime.sources import (
    LocalSource,
    SocketSource,
    _Receiver,
    row_waits_ms,
)

SCHEMA = Schema.from_spark_json(json.dumps({
    "type": "struct",
    "fields": [
        {"name": "id", "type": "long", "nullable": False, "metadata": {}},
        {"name": "kind", "type": "string", "nullable": False, "metadata": {}},
        {"name": "temp", "type": "double", "nullable": False, "metadata": {}},
    ],
}))


def _line(i: int, kind: str = "DoorLock") -> bytes:
    # fixed width, so a width of lines is a known number of bytes
    return b'{"id":%8d,"kind":"%s","temp":%8.2f}\n' % (
        i, kind.encode(), i / 4.0)


def _connect(src):
    return socket.create_connection(("127.0.0.1", src.port), 5.0)


def _wait_rows(src, n, timeout_s=30.0):
    deadline = time.time() + timeout_s
    while src.buffered_rows < n and time.time() < deadline:
        time.sleep(0.002)
    assert src.buffered_rows == n


def _decoded(blob: bytes):
    cols, valid, rows, consumed = NativeDecoder(
        SCHEMA, StringDictionary()).decode(blob, 16)
    assert consumed == len(blob)
    return rows, {c: a[valid] for c, a in cols.items()}


def line_split_across_two_sends(src):
    with _connect(src) as conn:
        conn.sendall(b'{"id":       1,"ki')
        time.sleep(0.05)
        assert src.poll_raw(10) == (b"", 0, {("socket", 0): (0, 0)})
        conn.sendall(b'nd":"DoorLock","temp":    0.25}\n{"id"')
        _wait_rows(src, 1)
        blob, n, offsets = src.poll_raw(10)
        assert (blob, n) == (_line(1), 1)
        assert offsets == {("socket", 0): (0, 1)}
        conn.sendall(b':       2,"kind":"DoorLock","temp":    0.50}\n')
        _wait_rows(src, 1)
        assert src.poll_raw(10) == (_line(2), 1, {("socket", 0): (1, 2)})


def crlf_and_blank_lines(src):
    with _connect(src) as conn:
        conn.sendall(
            b"\n" + _line(1)[:-1] + b"\r\n" + b"\r\n" + b"  \t \n"
            + _line(2) + b"\n\n")
        _wait_rows(src, 2)
    blob, n, offsets = src.poll_raw(10)
    # blank lines are neither delivered nor counted
    assert n == 2 and offsets == {("socket", 0): (0, 2)}
    assert blob == _line(1)[:-1] + b"\r\n" + _line(2)
    assert src.backlog_rows == 0 and src.buffered_rows == 0
    # a \r\n line decodes as its \n twin, in the decoder and in poll()
    rows, cols = _decoded(blob)
    twin_rows, twin = _decoded(_line(1) + _line(2))
    assert rows == twin_rows == 2
    for c in twin:
        np.testing.assert_array_equal(cols[c], twin[c])
    src.requeue_unacked()
    assert src.poll(10)[0] == [json.loads(_line(1)), json.loads(_line(2))]


def unterminated_tail_waits_for_end_of_stream(src):
    conn = _connect(src)
    conn.sendall(_line(1) + _line(2)[:-1])
    _wait_rows(src, 1)
    time.sleep(0.05)
    assert src.poll_raw(10) == (_line(1), 1, {("socket", 0): (0, 1)})
    assert src.poll_raw(10)[1] == 0  # still held back
    conn.close()
    _wait_rows(src, 1)
    # delivered at end of stream, as `for line in f` delivers it
    assert src.poll_raw(10) == (_line(2), 1, {("socket", 0): (1, 2)})
    assert src._receivers == []  # a drained, closed connection is let go


def two_connections_never_interleave_inside_a_line(src):
    per_conn = 400
    streams = [
        b"".join(_line(i, kind) for i in range(per_conn))
        for kind in ("DoorLock", "Heating")
    ]
    conns = [_connect(src), _connect(src)]
    # odd-sized pieces, the two senders taking turns: most pieces end
    # inside a line
    pos = 0
    while pos < len(streams[0]):
        for conn, stream in zip(conns, streams):
            conn.sendall(stream[pos:pos + 37])
        pos += 37
    _wait_rows(src, 2 * per_conn)
    got = []
    while len(got) < 2 * per_conn:
        blob, n, _ = src.poll_raw(300)
        assert 0 < n <= 300 and blob.count(b"\n") == n
        got.extend(blob.splitlines(True))
    for conn in conns:
        conn.close()
    # every line is one sender's whole line, each sender's in order
    for kind, stream in zip((b"DoorLock", b"Heating"), streams):
        assert b"".join(g for g in got if kind in g) == stream
    assert src.backlog_rows == 0


def max_events_cuts_inside_the_buffer(src):
    with _connect(src) as conn:
        conn.sendall(b"".join(_line(i) for i in range(10)))
        _wait_rows(src, 10)
        blob, n, offsets = src.poll_raw(4)
        assert blob == b"".join(_line(i) for i in range(4)) and n == 4
        assert offsets == {("socket", 0): (0, 4)}
        assert src.backlog_rows == 6 and src.buffered_rows == 6
        blob, n, offsets = src.poll_raw(4)
        assert blob == b"".join(_line(i) for i in range(4, 8)) and n == 4
        assert offsets == {("socket", 0): (4, 8)} and src.backlog_rows == 2
        conn.sendall(_line(10))
        _wait_rows(src, 3)
        blob, n, offsets = src.poll_raw(4)
        assert blob == b"".join(_line(i) for i in range(8, 11)) and n == 3
        assert offsets == {("socket", 0): (8, 11)} and src.backlog_rows == 0


def requeue_redelivers_two_batches_in_flight(src):
    with _connect(src) as conn:
        conn.sendall(b"".join(_line(i) for i in range(9)))
        _wait_rows(src, 9)
        first = src.poll_raw(3)
        src.ack()
        in_flight = [src.poll_raw(3), src.poll_raw(3)]
        assert [d[2] for d in in_flight] == [
            {("socket", 0): (3, 6)}, {("socket", 0): (6, 9)}]
        conn.sendall(_line(9))
        _wait_rows(src, 1)
        src.requeue_unacked()
        # the same bytes and offsets, in order, before anything new
        assert [src.poll_raw(3), src.poll_raw(3)] == in_flight
        assert first[0] + in_flight[0][0] + in_flight[1][0] == b"".join(
            _line(i) for i in range(9))
        assert src.poll_raw(3) == (_line(9), 1, {("socket", 0): (9, 10)})
        for _ in range(3):
            src.ack()
        src.requeue_unacked()
        assert src.poll_raw(3)[1] == 0


def backlog_of_three_widths_grows_the_buffer(src):
    width = 8192  # 50 B a line: three widths outgrow the first buffer
    lines = [_line(i) for i in range(5 * width)]
    assert 3 * width * len(lines[0]) > 1 << 20
    with _connect(src) as conn:
        conn.sendall(b"".join(lines[:3 * width]))
        _wait_rows(src, 3 * width)
        got, grows = [], []
        for k in range(3):
            blob, n, offsets = src.poll_raw(width)
            assert n == width
            assert offsets == {("socket", 0): (k * width, (k + 1) * width)}
            assert src.backlog_rows == (2 - k) * width
            got.append(blob)
            grows.append(src.buffer_grows)
        # the buffer was replaced on the way in, and only then
        assert grows[0] > 0 and grows[1:] == [0, 0]
        # a source that keeps up reuses what it has
        for k in (3, 4):
            conn.sendall(b"".join(lines[k * width:(k + 1) * width]))
            _wait_rows(src, width)
            blob, n, _ = src.poll_raw(width)
            assert n == width and src.buffer_grows == 0
            got.append(blob)
    assert b"".join(got) == b"".join(lines)  # nothing lost, in order


def polling_allocates_no_object_a_line(src):
    n_lines = 100_000
    payload = b"".join(_line(i) for i in range(n_lines))
    with _connect(src) as conn:
        conn.sendall(payload)
        _wait_rows(src, n_lines)
        gc.collect()
        before = sys.getallocatedblocks()
        blob, n, offsets = src.poll_raw(n_lines)
        after = sys.getallocatedblocks()
    assert n == n_lines and blob == payload
    assert offsets == {("socket", 0): (0, n_lines)}
    # the per-line reader held over 100,000 blocks here
    assert after - before < 300


def arrived_lines_are_whole_lines_not_yet_delivered(src):
    with _connect(src) as conn:
        assert src.arrived_lines() is None  # nothing has arrived
        conn.sendall(_line(1) + _line(2) + _line(3)[:20])
        _wait_rows(src, 2)
        time.sleep(0.05)
        data, lines = src.arrived_lines()
        # the unterminated tail is not shown, nor is anything delivered
        assert (bytes(data), lines) == (_line(1) + _line(2), 2)
        assert src.buffered_rows == 2
        # a caller that has worked on them is shown what came since
        assert src.arrived_lines(len(data), lines) is None
        conn.sendall(_line(3)[20:] + _line(4))
        _wait_rows(src, 4)
        more, n = src.arrived_lines(len(data), lines)
        assert (bytes(more), n) == (_line(3) + _line(4), 2)
        # the poll delivers what it delivers without the views: the blob
        # begins with what was shown
        blob, n, offsets = src.poll_raw(3)
        assert (blob, n) == (_line(1) + _line(2) + _line(3), 3)
        assert offsets == {("socket", 0): (0, 3)}
        assert src.polled_arrived and src.backlog_rows == 1
        # what the cut left is shown again from its first byte
        rest, n = src.arrived_lines()
        assert (bytes(rest), n) == (_line(4), 1)
        assert src.poll_raw(3)[:2] == (_line(4), 1) and src.polled_arrived
        # a poll nothing was shown before says so
        conn.sendall(_line(5))
        _wait_rows(src, 1)
        assert src.poll_raw(3)[:2] == (_line(5), 1)
        assert not src.polled_arrived


def arrived_lines_survive_a_replaced_buffer_and_a_rewind(src):
    width = 8192  # 50 B a line: three widths outgrow the first buffer
    lines = [_line(i) for i in range(4 * width)]
    with _connect(src) as conn:
        conn.sendall(b"".join(lines[:width]))
        _wait_rows(src, width)
        first, n = src.arrived_lines()
        assert n == width
        held = src._receivers[0].buf
        conn.sendall(b"".join(lines[width:3 * width]))
        _wait_rows(src, 3 * width)
        # the buffer was replaced under the first view, which still
        # reads the bytes it was given; the cursor holds in the new one
        assert src._receivers[0].buf is not held
        assert bytes(first) == b"".join(lines[:width])
        more, n = src.arrived_lines(len(first), width)
        assert n == 2 * width
        assert bytes(more) == b"".join(lines[width:3 * width])
        blob, n, _ = src.poll_raw(3 * width)
        assert n == 3 * width and blob == b"".join(lines[:3 * width])
        assert src.polled_arrived and src.buffer_grows > 0
        # everything was delivered: the buffer was rewound, and the
        # next lines are shown from its front
        assert src._receivers[0].head == 0
        conn.sendall(b"".join(lines[3 * width:]))
        _wait_rows(src, width)
        data, n = src.arrived_lines()
        assert n == width and bytes(data) == b"".join(lines[3 * width:])
        assert src.poll_raw(width)[0] == bytes(data) and src.polled_arrived


def arrived_lines_are_withheld_when_the_blob_would_not_begin_with_them(src):
    with _connect(src) as conn:
        conn.sendall(_line(1) + _line(2))
        _wait_rows(src, 2)
        first = src.poll_raw(1)
        src.requeue_unacked()
        # a requeued batch goes first: its blob is its own bytes
        assert src.arrived_lines() is None
        assert src.poll_raw(1) == first and not src.polled_arrived
        assert bytes(src.arrived_lines()[0]) == _line(2)
        # blank lines wait: the blob is rewritten without them
        conn.sendall(b"\n" + _line(3))
        _wait_rows(src, 2)
        assert src.arrived_lines() is None
        blob, n, _ = src.poll_raw(10)
        assert (blob, n) == (_line(2) + _line(3), 2)
        assert not src.polled_arrived
        # a second connection that holds lines: the blob joins the two
        conn.sendall(_line(4))
        _wait_rows(src, 1)
        assert src.arrived_lines() is not None
        with _connect(src) as other:
            other.sendall(_line(5, "Heating"))
            _wait_rows(src, 2)
            assert src.arrived_lines() is None
            # the lines shown before it came still lead the blob
            blob, n, _ = src.poll_raw(10)
            assert (blob, n) == (_line(4) + _line(5, "Heating"), 2)
            assert src.polled_arrived
            # an idle connection holds nothing back, and one that gets
            # the blob's first lines after others were shown is told apart
            conn.sendall(_line(6))
            _wait_rows(src, 1)
            assert bytes(src.arrived_lines()[0]) == _line(6)
            other.sendall(_line(7, "Heating"))
            _wait_rows(src, 2)
            blob, n, _ = src.poll_raw(10)
            assert n == 2 and sorted(blob.splitlines(True)) == sorted(
                [_line(6), _line(7, "Heating")])
            assert src.polled_arrived == blob.startswith(_line(6))


def _stamps_count_the_waiting_rows(src):
    with src._lock:
        for rx in src._receivers:
            assert sum(rx.stamp_rows) == rx.rows
            assert rx.stamp_ts == sorted(rx.stamp_ts)
            assert len(rx.stamp_ts) == len(rx.stamp_rows)


def wait_quantiles_weigh_a_stamp_by_its_rows(src):
    with _connect(src) as conn:
        first_sent = time.time()
        conn.sendall(b"".join(_line(i) for i in range(100)))
        _wait_rows(src, 100)
        time.sleep(0.2)
        second_sent = time.time()
        conn.sendall(b"".join(_line(i) for i in range(100, 400)))
        _wait_rows(src, 400)
        time.sleep(0.1)
        _stamps_count_the_waiting_rows(src)
        before = time.time()
        assert src.poll_raw(1000)[1] == 400
        polled_ts, ts, rows = src.wait_stamps
        assert before <= polled_ts <= time.time()
        assert sum(rows) == 400 and ts == sorted(ts)
        assert first_sent <= ts[0] and second_sent <= ts[-1] <= before
        # 300 of the 400 rows came with the second chunk: the median is
        # theirs, the 95th percentile and the oldest the first chunk's
        old = (polled_ts - first_sent) * 1000.0
        new = (polled_ts - second_sent) * 1000.0
        assert old - new >= 200.0
        got_ts, (p50, p95, p10), oldest = row_waits_ms(
            [src], (0.5, 0.95, 0.1))
        assert got_ts == polled_ts
        assert new - 30.0 <= p10 <= p50 <= new
        assert old - 30.0 <= p95 <= oldest <= old
        # what numpy says of the rows, one value a row
        per_row = np.repeat((polled_ts - np.asarray(ts)) * 1000.0, rows)
        np.testing.assert_allclose(
            [p10, p50, p95, oldest],
            list(np.percentile(per_row, [10, 50, 95])) + [per_row.max()])
        # a poll that finds nothing says when it cut, and of no row
        assert src.poll_raw(1000)[1] == 0
        assert src.wait_stamps[1:] == ([], []) and row_waits_ms([src]) is None


def a_cut_leaves_the_rest_of_a_stamps_rows_their_arrival(src):
    with _connect(src) as conn:
        conn.sendall(b"".join(_line(i) for i in range(10)))
        _wait_rows(src, 10)
        with src._lock:
            came = list(src._receivers[0].stamp_ts)  # when the ten did
        assert src.poll_raw(4)[1] == 4
        polled, ts, rows = src.wait_stamps
        assert sum(rows) == 4 and set(ts) <= set(came)
        _stamps_count_the_waiting_rows(src)
        time.sleep(0.1)
        conn.sendall(_line(10))
        _wait_rows(src, 7)
        assert src.poll_raw(4)[1] == 4
        later, ts2, rows2 = src.wait_stamps
        # the backlog's rows are as old as they are: they came with the
        # first send, not with this poll nor with the line sent since
        assert sum(rows2) == 4 and set(ts2) <= set(came)
        assert later - polled >= 0.1
        assert row_waits_ms([src], (0.0,))[1][0] >= 100.0
        _stamps_count_the_waiting_rows(src)
        assert src.poll_raw(4)[1] == 3
        _polled, ts3, rows3 = src.wait_stamps
        assert sum(rows3) == 3 and rows3[-1] == 1
        assert ts3[0] in came and ts3[-1] - came[-1] >= 0.1
        assert src._receivers[0].stamp_ts == []


def a_requeued_batch_is_as_old_as_its_first_arrival(src):
    with _connect(src) as conn:
        conn.sendall(b"".join(_line(i) for i in range(6)))
        _wait_rows(src, 6)
        first = src.poll_raw(3)
        polled, ts, rows = src.wait_stamps
        waited = row_waits_ms([src])
        second = src.poll_raw(3)
        stamps2 = src.wait_stamps[1:]
        src.requeue_unacked()
        time.sleep(0.1)
        assert src.poll_raw(3) == first
        again, ts_again, rows_again = src.wait_stamps
        assert (ts_again, rows_again) == (ts, rows) and again - polled >= 0.1
        assert row_waits_ms([src])[2] >= waited[2] + 100.0
        assert src.poll_raw(3) == second and src.wait_stamps[1:] == stamps2


def two_connections_stamps_pool(src):
    with _connect(src) as a, _connect(src) as b:
        a.sendall(_line(1) + _line(2) + _line(3))
        _wait_rows(src, 3)
        time.sleep(0.15)
        b.sendall(b"".join(_line(i, "Heating") for i in range(5)))
        _wait_rows(src, 8)
        assert src.poll_raw(100)[1] == 8
        polled, ts, rows = src.wait_stamps
        assert sum(rows) == 8
        by_arrival = sorted(zip(ts, rows))
        assert by_arrival[-1][0] - by_arrival[0][0] >= 0.15
        assert sum(n for t, n in by_arrival
                   if t - by_arrival[0][0] < 0.1) == 3
        # five of the eight rows are the younger ones
        _ts, (p50,), oldest = row_waits_ms([src], (0.5,))
        assert oldest - p50 >= 150.0
        # a source with no notion of an arrival is not pooled, nor asked
        local = LocalSource(SCHEMA)
        assert local.wait_stamps is None
        assert row_waits_ms([local]) is None
        assert row_waits_ms([local, src]) == row_waits_ms([src])


def a_replaced_buffer_and_a_rewind_lose_no_stamp(src):
    width = 8192  # 50 B a line: three widths outgrow the first buffer
    lines = [_line(i) for i in range(4 * width)]
    with _connect(src) as conn:
        conn.sendall(b"".join(lines[:3 * width]))
        _wait_rows(src, 3 * width)
        _stamps_count_the_waiting_rows(src)
        first_arrival = src._receivers[0].stamp_ts[0]
        grows = 0
        for k in range(3):
            assert src.poll_raw(width)[1] == width
            grows += src.buffer_grows
            polled, ts, rows = src.wait_stamps
            assert sum(rows) == width and ts == sorted(ts)
            assert first_arrival <= ts[0] and ts[-1] <= polled
            _stamps_count_the_waiting_rows(src)
        assert grows > 0  # make_room took a fresh buffer on the way in
        # everything was delivered: the buffer was rewound
        assert src._receivers[0].head == 0
        assert src._receivers[0].stamp_ts == []
        conn.sendall(b"".join(lines[3 * width:]))
        _wait_rows(src, width)
        _stamps_count_the_waiting_rows(src)
        assert src.poll_raw(width)[1] == width
        assert sum(src.wait_stamps[2]) == width
        assert src.wait_stamps[1][0] > polled


def stamps_hold_under_many_senders_and_a_polling_thread(src):
    """More senders than cores, the interpreter switching threads every
    10 us: every poll's stamps count its rows, and none is lost."""
    senders, per_sender = 16, 1500
    conns = [_connect(src) for _ in range(senders)]

    def send(conn, k):
        data = b"".join(_line(k * per_sender + i) for i in range(per_sender))
        for at in range(0, len(data), 997):
            conn.sendall(data[at:at + 997])
        conn.close()

    threads = [threading.Thread(target=send, args=(c, k), daemon=True)
               for k, c in enumerate(conns)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        got, deadline = 0, time.time() + 60
        while got < senders * per_sender and time.time() < deadline:
            n = src.poll_raw(700)[1]
            polled, ts, rows = src.wait_stamps
            assert sum(rows) == n and len(ts) == len(rows)
            assert all(r > 0 for r in rows) and all(t <= polled for t in ts)
            _stamps_count_the_waiting_rows(src)
            got += n
        for t in threads:
            t.join(30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert got == senders * per_sender and src.buffered_rows == 0


CASES = [
    stamps_hold_under_many_senders_and_a_polling_thread,
    wait_quantiles_weigh_a_stamp_by_its_rows,
    a_cut_leaves_the_rest_of_a_stamps_rows_their_arrival,
    a_requeued_batch_is_as_old_as_its_first_arrival,
    two_connections_stamps_pool,
    a_replaced_buffer_and_a_rewind_lose_no_stamp,
    arrived_lines_are_whole_lines_not_yet_delivered,
    arrived_lines_survive_a_replaced_buffer_and_a_rewind,
    arrived_lines_are_withheld_when_the_blob_would_not_begin_with_them,
    line_split_across_two_sends,
    crlf_and_blank_lines,
    unterminated_tail_waits_for_end_of_stream,
    two_connections_never_interleave_inside_a_line,
    max_events_cuts_inside_the_buffer,
    requeue_redelivers_two_batches_in_flight,
    backlog_of_three_widths_grows_the_buffer,
    polling_allocates_no_object_a_line,
]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__)
def test_socket_source(case):
    src = SocketSource()
    try:
        case(src)
    finally:
        src.close()


def _receive(rx, data: bytes):
    rx.make_room()
    rx.buf[rx.tail:rx.tail + len(data)] = data
    rx.received(len(data))


@pytest.mark.parametrize("step_s, most", [
    (0.0001, 1 + 10_000 // 10),  # ten recvs a millisecond share a stamp
    (0.002, sources._STAMP_LIMIT),  # nobody polls: neighbours merge
], ids=["within_a_millisecond", "over_the_limit"])
def test_arrival_stamps_coalesce_and_stay_bounded(monkeypatch, step_s, most):
    clock = [1_700_000_000.0]

    def ticking():
        clock[0] += step_s
        return clock[0]

    monkeypatch.setattr(sources.time, "time", ticking)
    rx = _Receiver()
    for i in range(10_000):
        # a recv that ends no line is not stamped, nor is a blank line
        _receive(rx, _line(i)[:20])
        _receive(rx, _line(i)[20:] + (b"\n" if i % 100 == 0 else b""))
        assert len(rx.stamp_ts) <= most
    assert rx.rows == 10_000 == sum(rx.stamp_rows) and rx.blank == 100
    assert rx.stamp_ts == sorted(set(rx.stamp_ts))
    assert len(rx.stamp_ts) == len(rx.stamp_rows) > most // 2 - 1
    if step_s < sources._STAMP_MERGE_S:
        # a stamp holds the lines of the millisecond after it
        assert min(np.diff(rx.stamp_ts)) >= sources._STAMP_MERGE_S
    newest = rx.stamp_ts[-1]
    # rows are consumed oldest first, whatever merged
    taken = 0
    while rx.rows:
        _part, rows, _blank, ts, counts = rx.take(999)
        assert rows == sum(counts) == min(999, 10_000 - taken)
        assert ts == sorted(ts) and len(ts) == len(counts)
        taken += rows
    assert taken == 10_000 and ts[-1] == newest and rx.stamp_ts == []
