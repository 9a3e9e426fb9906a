"""Tests for the SQL, document, and stream sinks (SqlSinker /
CosmosDBSinker / EventHubStreamPoster analogs), and for the bytes the
NDJSON sinks (file, stream) write from a columnar batch."""

import gzip
import json
import os
import socket
import sqlite3
import threading
import time

import numpy as np
import pytest

from data_accelerator_tpu.compile.planner import TableData, ViewSchema
from data_accelerator_tpu.core.config import SettingDictionary
from data_accelerator_tpu.core.schema import StringDictionary
from data_accelerator_tpu.obs import telemetry, tracing
from data_accelerator_tpu.obs.metrics import MetricLogger
from data_accelerator_tpu.obs.store import MetricStore
from data_accelerator_tpu.obs.tracing import Tracer
from data_accelerator_tpu.runtime.materialize import ColumnBatch
from data_accelerator_tpu.runtime.sinks import (
    DocumentSink,
    FileSink,
    OutputDispatcher,
    OutputOperator,
    SqlSink,
    StreamSink,
    build_output_operators,
)
from data_accelerator_tpu.runtime.sources import SocketSource
from data_accelerator_tpu.utils import fs

ROWS = [
    {"deviceId": 1, "temperature": 71.5, "deviceType": "Heating"},
    {"deviceId": 2, "temperature": 22.0, "deviceType": "DoorLock"},
]


def test_sql_sink_append(tmp_path):
    db = str(tmp_path / "out.db")
    sink = SqlSink(db, "alerts")
    assert sink.write("Alerts", ROWS, 1000) == 2
    assert sink.write("Alerts", ROWS, 2000) == 2
    conn = sqlite3.connect(db)
    rows = conn.execute("SELECT deviceId, temperature FROM alerts").fetchall()
    conn.close()
    assert len(rows) == 4
    assert rows[0] == (1, 71.5)


def test_sql_sink_overwrite_drops_previous_table(tmp_path):
    db = str(tmp_path / "out.db")
    SqlSink(db, "t").write("D", ROWS, 1000)
    sink2 = SqlSink(db, "t", write_mode="overwrite")
    sink2.write("D", ROWS[:1], 1000)
    conn = sqlite3.connect(db)
    assert conn.execute("SELECT COUNT(*) FROM t").fetchone()[0] == 1
    conn.close()


def test_sql_sink_jdbc_url_and_nested_values(tmp_path):
    db = str(tmp_path / "j.db")
    sink = SqlSink(f"jdbc:sqlite:{db}", "t")
    sink.write("D", [{"a": 1, "nested": {"x": 2}}], 0)
    conn = sqlite3.connect(db)
    (val,) = conn.execute("SELECT nested FROM t").fetchone()
    conn.close()
    assert json.loads(val) == {"x": 2}


def test_sql_sink_schema_evolution(tmp_path):
    """Later batches may carry new columns; the table grows instead of
    poisoning the stream with OperationalError."""
    db = str(tmp_path / "e.db")
    sink = SqlSink(db, "t")
    sink.write("D", [{"a": 1}], 0)
    sink.write("D", [{"a": 2, "alertLevel": "high"}, {"a": 3, "extra": 1.5}], 0)
    conn = sqlite3.connect(db)
    rows = conn.execute("SELECT a, alertLevel, extra FROM t ORDER BY a").fetchall()
    conn.close()
    assert rows == [(1, None, None), (2, "high", None), (3, None, 1.5)]


def test_document_sink_assigns_ids(tmp_path):
    sink = DocumentSink(str(tmp_path), "mydb", "events")
    assert sink.write("D", ROWS, 0) == 2
    lines = open(tmp_path / "mydb" / "events" / "docs.jsonl").read().splitlines()
    docs = [json.loads(x) for x in lines]
    assert len(docs) == 2
    assert all("id" in d and len(d["id"]) == 36 for d in docs)
    assert docs[0]["deviceId"] == 1


def test_stream_sink_feeds_socket_source():
    """The stream sink speaks SocketSource's wire format — chained flows."""
    src = SocketSource(port=0)
    try:
        sink = StreamSink("127.0.0.1", src.port)
        assert sink.write("D", ROWS, 0) == 2
        deadline = time.time() + 5
        rows = []
        while time.time() < deadline and len(rows) < 2:
            got, _ = src.poll(10)
            rows.extend(got)
            src.ack()
            time.sleep(0.02)
        assert [r["deviceId"] for r in rows] == [1, 2]
    finally:
        src.close()


def test_build_operators_constructs_new_sinks(tmp_path):
    d = SettingDictionary({
        "datax.job.name": "F",
        "datax.job.output.A.sql.connectionstring": str(tmp_path / "a.db"),
        "datax.job.output.A.sql.table": "a",
        "datax.job.output.B.cosmosdb.connectionstring": str(tmp_path / "docs"),
        "datax.job.output.B.cosmosdb.database": "db1",
        "datax.job.output.B.cosmosdb.collection": "c1",
        "datax.job.output.C.eventhub.connectionstring": "127.0.0.1:9",
    })
    ml = MetricLogger("DATAX-F", store=MetricStore())
    ops = build_output_operators(
        d, ml, {"A": ["A"], "B": ["B"], "C": ["C"]}
    )
    kinds = {name: [s.kind for s in op.sinks] for name, op in ops.items()}
    assert kinds == {"A": ["sql"], "B": ["cosmosdb"], "C": ["eventhub"]}


# -- the NDJSON sinks' bytes -------------------------------------------------
BASE_MS = 1_790_000_000_123


def _batch(n, seed=0, base_ms=BASE_MS):
    """A columnar batch of every kind of column, and the payload
    ``json.dumps`` a row gives over its rows."""
    rng = np.random.default_rng(seed)
    d = StringDictionary()
    ids = [d.encode(s) for s in ('say "hi"', "café ☃", "back\\slash\n")]
    table = TableData({
        "deviceId": (np.arange(n) + seed * 1_000_003).astype(np.int32),
        "AvgT": rng.uniform(60, 90, n).astype(np.float32),
        "open": rng.integers(0, 2, n).astype(np.bool_),
        "home": np.int32(ids)[rng.integers(0, 3, n)],
        "at": rng.integers(0, 1000, n).astype(np.int32),
    }, np.ones(n, np.bool_))
    schema = ViewSchema({
        "deviceId": "long", "AvgT": "double", "open": "boolean",
        "home": "string", "at": "timestamp",
    })
    batch = ColumnBatch(table, schema, d, base_ms)
    assert batch.columnar
    expected = "".join(
        json.dumps(r, default=str) + "\n"
        for r in ColumnBatch(table, schema, d, base_ms).rows()
    ).encode()
    return batch, expected


def _read(path):
    with open(path, "rb") as f:
        return f.read()


def _files(folder):
    return sorted(
        os.path.join(root, f)
        for root, _dirs, names in os.walk(folder) for f in names
    )


@pytest.mark.parametrize("compression", ["none", "gzip"])
def test_file_sink_writes_the_encoders_bytes_by_temp_and_rename(
    tmp_path, monkeypatch, compression
):
    batch, expected = _batch(500)
    renames = []
    real_replace = os.replace

    def watched_replace(src, dst):
        # the whole payload is in the temp file, and the target is not
        # there, until this one call
        raw = _read(src)
        renames.append((
            os.path.basename(src), os.path.exists(dst),
            gzip.decompress(raw) if dst.endswith(".gz") else raw,
        ))
        real_replace(src, dst)

    monkeypatch.setattr(os, "replace", watched_replace)
    sink = FileSink(str(tmp_path), compression)
    assert sink.write("Out", batch, 1_700_000_000_000) == 500
    assert batch._rows is None, "the file sink built the rows"
    (path,) = _files(tmp_path)
    assert path.endswith(".json.gz" if compression == "gzip" else ".json")
    raw = _read(path)
    assert (gzip.decompress(raw) if compression == "gzip" else raw) \
        == expected
    ((temp, target_was_there, temp_bytes),) = renames
    assert ".tmp." in temp and not target_was_there
    assert temp_bytes == expected
    # the next batch through the same sink, smaller: the kept buffer's
    # stale tail is not written
    small, small_expected = _batch(3, seed=1)
    sink.write("Out", small, 1_700_000_001_000)
    second = [p for p in _files(tmp_path) if p != path]
    raw = _read(second[0])
    assert (gzip.decompress(raw) if compression == "gzip" else raw) \
        == small_expected


@pytest.mark.parametrize("name", ["out.json", "out.json.gz"])
def test_an_aborted_bytes_write_leaves_no_file(tmp_path, name):
    path = str(tmp_path / "d" / name)
    superseded = threading.Event()
    superseded.set()
    with pytest.raises(InterruptedError):
        fs.write_bytes(path, memoryview(b'{"a": 1}\n'), abort=superseded)
    assert _files(tmp_path) == []
    # and one that is not aborted installs exactly the bytes
    fs.write_bytes(path, memoryview(b'{"a": 1}\n'), abort=threading.Event())
    assert _files(tmp_path) == [path]
    assert fs.read_text(path) == '{"a": 1}\n'


def test_stream_sink_sends_the_encoders_bytes():
    batch, expected = _batch(2000)
    got = bytearray()
    with socket.create_server(("127.0.0.1", 0)) as server:
        def receive():
            conn, _ = server.accept()
            with conn:
                while chunk := conn.recv(1 << 16):
                    got.extend(chunk)

        reader = threading.Thread(target=receive)
        reader.start()
        sink = StreamSink("127.0.0.1", server.getsockname()[1])
        assert sink.write("Out", batch, 0) == 2000
        assert batch._rows is None and batch.encoded_rows == 2000
        sink._sock.close()
        reader.join(timeout=10)
        assert not reader.is_alive()
    assert bytes(got) == expected


class _CaptureWriter:
    def __init__(self):
        self.records = []

    def write(self, record):
        self.records.append(record)

    def flush(self):
        pass


def _traced_dispatch(dispatcher, datasets, t_ms):
    """One dispatch under a batch trace, as the host's tail makes it:
    the trace's counters and its spans by name."""
    w = _CaptureWriter()
    trace = Tracer(telemetry.TelemetryLogger("app", [w])).begin()
    with trace.activate(), tracing.span("sinks"):
        dispatcher.dispatch(datasets, t_ms)
    trace.end()
    spans = {}
    for r in w.records:
        if r["type"] == "span":
            spans.setdefault(r["name"], []).append(r)
    return trace.counters, spans


def test_two_outputs_of_one_batch_encode_beside_each_other(tmp_path):
    """The dispatcher's pool writes both outputs at once, each through
    its own sink and so its own buffer: every payload of every batch is
    intact, and the counter reads the rows the encoder wrote."""
    ops = {
        name: OutputOperator(name, [FileSink(str(tmp_path / name), "none")])
        for name in ("HeatAvg", "OpenDoors")
    }
    dispatcher = OutputDispatcher(
        ops, MetricLogger("DATAX-F", store=MetricStore())
    )
    buffers = {id(op.sinks[0]._buffer) for op in ops.values()}
    assert len(buffers) == 2
    try:
        for b in range(4):
            heat, heat_expected = _batch(30_000 + b, seed=2 * b)
            doors, doors_expected = _batch(20_000 - b, seed=2 * b + 1)
            t_ms = 1_700_000_000_000 + 1000 * b
            counters, spans = _traced_dispatch(
                dispatcher, {"HeatAvg": heat, "OpenDoors": doors}, t_ms
            )
            assert counters["Sink_NativeEncoded_Rows"] == 50_000.0
            (h,) = [p for p in _files(tmp_path / "HeatAvg") if str(t_ms) in p]
            (o,) = [p for p in _files(tmp_path / "OpenDoors")
                    if str(t_ms) in p]
            assert _read(h) == heat_expected
            assert _read(o) == doors_expected
            # a sink write = its encode + the file; the span says how much
            assert len(spans["sink/encode"]) == 2
            written = {
                s["properties"]["dataset"]: s["properties"]["bytes"]
                for s in spans["sink/file"]
            }
            assert written == {
                "HeatAvg": len(heat_expected),
                "OpenDoors": len(doors_expected),
            }
            parents = {s["span"] for s in spans["sink/file"]}
            assert {s["parent"] for s in spans["sink/encode"]} == parents
    finally:
        dispatcher.close()


def test_two_outputs_routed_to_one_sink_take_turns_at_its_buffer(tmp_path):
    shared = FileSink(str(tmp_path), "none")
    ops = {n: OutputOperator(n, [shared]) for n in ("A", "B")}
    dispatcher = OutputDispatcher(
        ops, MetricLogger("DATAX-F", store=MetricStore())
    )
    try:
        for b in range(4):
            a, a_expected = _batch(25_000, seed=10 + b)
            c, c_expected = _batch(24_000, seed=20 + b)
            t_ms = 1_700_000_000_000 + 1000 * b
            dispatcher.dispatch({"A": a, "B": c}, t_ms)
            got = {
                os.path.basename(p).split("_")[0]: _read(p)
                for p in _files(tmp_path) if str(t_ms) in p
            }
            assert got == {"A": a_expected, "B": c_expected}
    finally:
        dispatcher.close()


def test_a_batch_that_fell_back_to_rows_counts_no_encoded_row(tmp_path):
    """A schema the columns cannot hold (a nested name) goes row by
    row behind the same type: its file is ``json.dumps`` a row, the
    native counter reads 0 and a plain list reads 0 too."""
    table = TableData(
        {"k": np.int32([1, 2]), "m.a": np.int32([3, 4])},
        np.ones(2, np.bool_),
    )
    nested = ColumnBatch(
        table, ViewSchema({"k": "long", "m.a": "long"}), StringDictionary()
    )
    assert not nested.columnar
    plain = [{"a": 1, "s": "café"}]
    ops = {
        n: OutputOperator(n, [FileSink(str(tmp_path / n), "none")])
        for n in ("Nested", "Plain", "Empty")
    }
    dispatcher = OutputDispatcher(
        ops, MetricLogger("DATAX-F", store=MetricStore())
    )
    try:
        counters, spans = _traced_dispatch(
            dispatcher, {"Nested": nested, "Plain": plain, "Empty": []}, 0
        )
    finally:
        dispatcher.close()
    assert counters["Sink_NativeEncoded_Rows"] == 0.0
    (path,) = _files(tmp_path / "Nested")
    assert _read(path) == \
        b'{"k": 1, "m": {"a": 3}}\n{"k": 2, "m": {"a": 4}}\n'
    (path,) = _files(tmp_path / "Plain")
    assert _read(path) == '{"a": 1, "s": "caf\\u00e9"}\n'.encode()
    assert not (tmp_path / "Empty").exists()
    assert len(spans["sink/encode"]) == 2  # the fallback's encode is timed too
