"""SocketSource: the wire's bytes are received into one contiguous
buffer a connection and handed to the decoder as a cut of it that ends
at a line boundary. What the per-line reader did (`for line in f`,
`strip()`, a list of `bytes`) has to hold without an object a line."""

import gc
import json
import socket
import sys
import time

import numpy as np
import pytest

from data_accelerator_tpu.core.schema import Schema, StringDictionary
from data_accelerator_tpu.native import NativeDecoder
from data_accelerator_tpu.runtime.sources import SocketSource

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


CASES = [
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
