"""Columnar egress: ``collect_tables()`` hands each output to the sinks
as a ``ColumnBatch`` (runtime/materialize.py).

- a flat schema stays columns and its NDJSON, written by the native
  encoder from those columns, is byte for byte what
  ``json.dumps(row, default=str)`` a row gives over ``materialize_rows``
  (the per-row code, unchanged, is the reference in every case);
- any other schema (nested struct, array ``.__valid``, CONCAT,
  CONCAT_WS, host-side ORDER BY / LIMIT) takes the per-row path behind
  the same type and gives the rows the parent commit gave (goldens
  below were printed by the parent's ``process_batch``);
- every sink kind accepts the batch; the file sinks' bytes are the
  parent's; the two ``Egress_*_Rows`` counters say which path ran.
"""

import gzip
import json
import sqlite3
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from data_accelerator_tpu.compile.planner import TableData, ViewSchema
from data_accelerator_tpu.constants import MetricName
from data_accelerator_tpu.core.config import SettingDictionary
from data_accelerator_tpu.core.schema import StringDictionary
from data_accelerator_tpu.obs.metrics import MetricLogger
from data_accelerator_tpu.obs.store import MetricStore
from data_accelerator_tpu.obs.tracing import Tracer
from data_accelerator_tpu.runtime.materialize import (
    ColumnBatch,
    materialize_rows,
    ndjson,
)
from data_accelerator_tpu.runtime.processor import FlowProcessor
from data_accelerator_tpu.runtime.sinks import (
    ConsoleSink,
    DocumentSink,
    ExternalFunctionSink,
    FileSink,
    HttpPostSink,
    KafkaSink,
    MetricSink,
    OutputDispatcher,
    OutputOperator,
    SqlSink,
    StreamSink,
)
from data_accelerator_tpu.runtime.sources import SocketSource

BASE_MS = 1_790_000_000_123  # far above 2**31: int32 + base must widen


def per_row_payload(rows):
    """The file sink's payload before this batch type existed."""
    return (
        "\n".join(json.dumps(r, default=str) for r in rows) + "\n"
    ).encode()


def _dictionary(strings):
    d = StringDictionary()
    return d, [d.encode(s) for s in strings]


def _strings_case():
    d, ids = _dictionary(
        ['say "hi"', "back\\slash", "café ☃ \U0001f600",
         "tab\there\nline", None, "back\\slash", 'say "hi"', ""]
    )
    ids.append(999)  # an id the dictionary never gave: decodes to null
    return {"s": np.int32(ids)}, {"s": "string"}, d


def _f32(values):
    return np.asarray(values, np.float32)


# name -> (cols, types, dictionary or None, valid or None)
FLAT_CASES = {
    "long": lambda: (
        {"n": np.int32([0, -1, 7, 2**31 - 1, -(2**31)])}, {"n": "long"},
    ),
    "double_float32_origin": lambda: (
        {"price": _f32([100, 3, 12345.678, 0.1]) * np.float32(0.908)},
        {"price": "double"},
    ),
    "double_negative_zero": lambda: (
        {"x": _f32([-0.0, 0.0])}, {"x": "double"},
    ),
    "double_1e16": lambda: (
        {"x": _f32([1e16, -1e16, 1e22, 3.4e38])}, {"x": "double"},
    ),
    "double_1e-5": lambda: (
        {"x": _f32([1e-5, 1e-4, 1.5e-7, 1e-45])}, {"x": "double"},
    ),
    "double_nan": lambda: (
        {"x": _f32([1.5, np.nan, 2.5])}, {"x": "double"},
    ),
    "double_infinities": lambda: (
        {"x": _f32([np.inf, -np.inf, 0.25, np.nan])}, {"x": "double"},
    ),
    "double_from_float64_column": lambda: (
        {"x": np.float64([0.1, 1 / 3, 1e300])}, {"x": "double"},
    ),
    "boolean": lambda: (
        {"b": np.bool_([True, False, True])}, {"b": "boolean"},
    ),
    "boolean_from_int_column": lambda: (
        {"b": np.int32([0, 1, 5])}, {"b": "boolean"},
    ),
    "string_escapes_and_repeats": _strings_case,
    "timestamp_base_above_2_31": lambda: (
        {"t": np.int32([0, 999, -5000, 2**31 - 1])}, {"t": "timestamp"},
    ),
    "tssec": lambda: (
        {"t": np.int32([0, 59, -1])}, {"t": "tssec"},
    ),
    "q1_shape": lambda: (
        {"auction": np.int32([1001, 1002, 1001]),
         "bidder": np.int32([5, 6, 7]),
         "price": _f32([10, 20, 30]) * np.float32(0.908),
         "dateTime": np.int32([0, 20, 40])},
        {"auction": "long", "bidder": "long", "price": "double",
         "dateTime": "timestamp"},
    ),
    "key_needs_escaping": lambda: (
        {'100%"q"': np.int32([1, 2]), "café": _f32([0.5, 1.5])},
        {'100%"q"': "long", "café": "double"},
    ),
    "some_rows_invalid": lambda: (
        {"n": np.int32([1, 2, 3, 4]), "x": _f32([1, np.nan, 3, 4])},
        {"n": "long", "x": "double"}, None,
        np.bool_([True, False, True, False]),
    ),
    "no_row_valid": lambda: (
        {"n": np.int32([1, 2])}, {"n": "long"}, None,
        np.bool_([False, False]),
    ),
    "empty_table": lambda: (
        {"n": np.int32([]), "s": np.int32([])},
        {"n": "long", "s": "string"},
    ),
}


def _flat_case(name):
    cols, types, *rest = FLAT_CASES[name]()
    dictionary = (rest[0] if rest else None) or StringDictionary()
    valid = rest[1] if len(rest) > 1 else None
    n = len(next(iter(cols.values())))
    if valid is None:
        valid = np.ones(n, np.bool_)
    return TableData(cols, valid), ViewSchema(dict(types)), dictionary


@pytest.mark.parametrize("name", sorted(FLAT_CASES))
def test_flat_schema_ndjson_is_the_per_row_payload(name):
    table, schema, dictionary = _flat_case(name)
    reference = materialize_rows(table, schema, dictionary, BASE_MS)
    batch = ColumnBatch(table, schema, dictionary, BASE_MS)
    assert batch.columnar
    assert len(batch) == len(reference)
    # bytes first: nothing below may have built the dicts yet
    assert batch._rows is None
    payload = batch.ndjson()
    assert batch._rows is None, "the encoder built a dict"
    assert payload == (per_row_payload(reference) if reference else b"")
    assert ndjson(batch) == payload
    # ... and the other view of the same batch: the very rows (compared
    # as text, since a NaN is not equal to itself)
    assert json.dumps(batch.rows()) == json.dumps(reference)
    assert batch.rows() is batch.rows()  # built once, kept
    assert [type(v) for r in batch for v in r.values()] == [
        type(v) for r in reference for v in r.values()
    ]


@pytest.mark.parametrize("max_rows", [0, 1, 2, 10])
def test_max_rows_cuts_like_the_per_row_code(max_rows):
    table, schema, dictionary = _flat_case("some_rows_invalid")
    reference = materialize_rows(
        table, schema, dictionary, BASE_MS, max_rows=max_rows
    )
    batch = ColumnBatch(table, schema, dictionary, BASE_MS, max_rows=max_rows)
    assert batch.columnar and len(batch) == len(reference)
    assert json.dumps(batch.rows()) == json.dumps(reference)
    assert batch.ndjson() == (per_row_payload(reference) if reference else b"")


def test_batch_is_a_read_only_sequence_of_the_rows():
    table, schema, dictionary = _flat_case("q1_shape")
    reference = materialize_rows(table, schema, dictionary, BASE_MS)
    batch = ColumnBatch(table, schema, dictionary, BASE_MS)
    assert len(batch) == 3 and bool(batch)
    assert batch[0] == reference[0] and batch[-1] == reference[-1]
    assert batch[1:] == reference[1:] and isinstance(batch[:2], list)
    assert list(batch) == reference and batch == reference
    assert batch != reference[:2]
    assert reference[1] in batch
    assert json.loads(json.dumps(list(batch))) == reference
    assert not hasattr(batch, "append")
    with pytest.raises(TypeError):
        batch[0] = {}
    empty = ColumnBatch(*_flat_case("empty_table"))
    assert len(empty) == 0 and not empty and list(empty) == []
    assert empty.ndjson() == b"" and ndjson([]) == b""


def test_ndjson_of_a_plain_list_is_the_per_row_payload():
    rows = [{"a": 1, "s": "café", "x": float("nan")}, {"a": None}]
    assert ndjson(rows) == per_row_payload(rows)


# -- the schema alone picks the path ---------------------------------------
NOT_FLAT = {
    "nested_name": ViewSchema({"k": "long", "m.a": "long"}),
    "valid_flag": ViewSchema({"Rules.0.__valid": "boolean",
                              "Rules.0.ruleId": "string"}),
    "deferred_template": ViewSchema(
        {"k": "long", "__defer.c.0": "long"},
        {"c": ("x-", ("__defer.c.0", "long"))},
    ),
    "literal_only_template": ViewSchema({"k": "long"}, {"c": ("x",)}),
}


@pytest.mark.parametrize("name", sorted(NOT_FLAT))
def test_schema_that_is_not_flat_takes_the_per_row_path(name):
    schema = NOT_FLAT[name]
    cols = {
        c: (np.bool_([True, False]) if t == "boolean" else np.int32([1, 0]))
        for c, t in schema.types.items()
    }
    table = TableData(cols, np.ones(2, np.bool_))
    d, _ = _dictionary(["R1"])
    batch = ColumnBatch(table, schema, d, BASE_MS)
    assert not batch.columnar
    reference = materialize_rows(table, schema, d, BASE_MS)
    assert batch.rows() == reference
    assert batch.ndjson() == per_row_payload(reference)


def test_column_of_another_shape_or_kind_falls_back():
    """What the columns cannot render exactly goes row by row: a column
    missing from the table, one that is not a row vector, a 'long'
    column holding floats, a schema of no column at all."""
    valid = np.ones(2, np.bool_)
    d = StringDictionary()
    floats = TableData({"n": np.float32([1.5, 2.5])}, valid)
    batch = ColumnBatch(floats, ViewSchema({"n": "long"}), d)
    assert not batch.columnar and batch.rows() == [{"n": 1}, {"n": 2}]
    empty = TableData({}, np.zeros(2, np.bool_))
    batch = ColumnBatch(empty, ViewSchema({"n": "long"}), d)
    assert not batch.columnar and len(batch) == 0
    # rows without columns are still rows
    batch = ColumnBatch(TableData({}, valid), ViewSchema({}), d)
    assert not batch.columnar and batch.rows() == [{}, {}]


def test_finish_hook_sends_the_batch_through_the_rows():
    table, schema, dictionary = _flat_case("q1_shape")
    batch = ColumnBatch(
        table, schema, dictionary, BASE_MS,
        finish=lambda rows: sorted(rows, key=lambda r: -r["bidder"])[:2],
    )
    assert not batch.columnar and len(batch) == 2
    assert [r["bidder"] for r in batch] == [7, 6]
    assert batch.ndjson() == per_row_payload(batch.rows())


# -- through the processor: goldens from the parent commit -----------------
SCHEMA = json.dumps({"type": "struct", "fields": [
    {"name": "k", "type": "long", "nullable": False, "metadata": {}},
    {"name": "v", "type": "double", "nullable": False, "metadata": {}},
    {"name": "s", "type": "string", "nullable": True, "metadata": {}},
    {"name": "t", "type": "string", "nullable": True, "metadata": {}},
]})

INPUT = [
    {"k": 3, "v": 95.5, "s": "east", "t": "a1"},
    {"k": 1, "v": 30.25, "s": "west", "t": None},
    {"k": 2, "v": 91.0, "s": None, "t": "b9"},
    {"k": 4, "v": 10.0, "s": "east", "t": "a1"},
]

# what process_batch returned at the parent commit (e827a0b) for INPUT
FLOWS = {
    "flat": (
        "Out = SELECT k, v, s, v > 50 AS hot FROM DataXProcessedInput",
        True,
        [{"k": 3, "v": 95.5, "s": "east", "hot": True},
         {"k": 1, "v": 30.25, "s": "west", "hot": False},
         {"k": 2, "v": 91.0, "s": None, "hot": True},
         {"k": 4, "v": 10.0, "s": "east", "hot": False}],
    ),
    "nested_struct": (
        "Out = SELECT k, MAP('val', v, 'site', s) AS m "
        "FROM DataXProcessedInput",
        False,
        [{"k": 3, "m": {"val": 95.5, "site": "east"}},
         {"k": 1, "m": {"val": 30.25, "site": "west"}},
         {"k": 2, "m": {"val": 91.0, "site": None}},
         {"k": 4, "m": {"val": 10.0, "site": "east"}}],
    ),
    "array_valid_flag": (
        "Out = SELECT k, filterNull(Array(IF(v > 90, MAP('ruleId', 'R1', "
        "'severity', 'Critical'), NULL))) AS Rules FROM DataXProcessedInput",
        False,
        [{"k": 3, "Rules": [{"ruleId": "R1", "severity": "Critical"}]},
         {"k": 1, "Rules": []},
         {"k": 2, "Rules": [{"ruleId": "R1", "severity": "Critical"}]},
         {"k": 4, "Rules": []}],
    ),
    "concat": (
        "Out = SELECT CONCAT('dev ', s, ' #', k) AS c, k "
        "FROM DataXProcessedInput",
        False,
        [{"k": 3, "c": "dev east #3"},
         {"k": 1, "c": "dev west #1"},
         {"k": 2, "c": None},
         {"k": 4, "c": "dev east #4"}],
    ),
    "concat_ws_null_part": (
        "Out = SELECT k, CONCAT_WS('-', s, t) AS c FROM DataXProcessedInput",
        False,
        [{"k": 3, "c": "east-a1"},
         {"k": 1, "c": "west"},
         {"k": 2, "c": "b9"},
         {"k": 4, "c": "east-a1"}],
    ),
    "host_order_and_limit": (
        "Out = SELECT CONCAT(t, '/', k) AS c, k FROM DataXProcessedInput "
        "ORDER BY c DESC LIMIT 3",
        False,
        [{"k": 2, "c": "b9/2"},
         {"k": 4, "c": "a1/4"},
         {"k": 3, "c": "a1/3"}],
    ),
}


def _proc(tmp_path, query, extra=None):
    tmp_path.mkdir(parents=True, exist_ok=True)
    t = tmp_path / "t.transform"
    t.write_text("--DataXQuery--\n" + query + "\n")
    d = {
        "datax.job.name": "EgressFlow",
        "datax.job.input.default.blobschemafile": SCHEMA,
        "datax.job.process.transform": str(t),
        "datax.job.process.batchcapacity": "64",
    }
    d.update(extra or {})
    return FlowProcessor(SettingDictionary(d), output_datasets=["Out"])


@pytest.mark.parametrize("name", sorted(FLOWS))
def test_flow_rows_are_the_parents_and_the_counters_name_the_path(
    tmp_path, name
):
    query, columnar, golden = FLOWS[name]
    proc = _proc(tmp_path, query)
    h = proc.dispatch_batch(proc.encode_rows(INPUT, 0), 1000)
    datasets, metrics = h.collect_tables()
    batch = datasets["Out"]
    assert isinstance(batch, ColumnBatch)
    assert batch.columnar is columnar
    assert batch.rows() == golden
    assert [list(r) for r in batch] == [list(r) for r in golden]  # key order
    n = float(len(golden))
    assert metrics["Egress_Columnar_Rows"] == (n if columnar else 0.0)
    assert metrics["Egress_Fallback_Rows"] == (0.0 if columnar else n)
    # ... and through a file sink under the batch's trace: the native
    # encoder wrote the columnar rows and none of the fallback's
    dispatcher = OutputDispatcher(
        {"Out": OutputOperator("Out", [FileSink(str(tmp_path / "o"), "none")])},
        MetricLogger("DATAX-Egress", store=MetricStore()),
    )
    trace = Tracer().begin()
    try:
        with trace.activate():
            dispatcher.dispatch(datasets, 1000)
    finally:
        dispatcher.close()
    assert trace.counters["Sink_NativeEncoded_Rows"] == \
        metrics["Egress_Columnar_Rows"]
    (path,) = [p for p in (tmp_path / "o").rglob("Out_*") if p.is_file()]
    assert path.read_bytes() == per_row_payload(golden)
    assert batch.ndjson() == per_row_payload(golden)
    # collect() / process_batch: the same batch as a plain list
    rows, m2 = proc.process_batch(proc.encode_rows(INPUT, 0), 2000)
    assert type(rows["Out"]) is list and rows["Out"] == golden
    assert m2["Egress_Columnar_Rows"] + m2["Egress_Fallback_Rows"] == n


def test_counters_are_on_an_empty_batch_and_registered(tmp_path):
    proc = _proc(tmp_path, FLOWS["flat"][0])
    _d, metrics = proc.dispatch_batch(
        proc.encode_rows([], 0), 1000
    ).collect_tables()
    assert metrics["Egress_Columnar_Rows"] == 0.0
    assert metrics["Egress_Fallback_Rows"] == 0.0
    for name in ("Egress_Columnar_Rows", "Egress_Fallback_Rows",
                 "Sink_NativeEncoded_Rows"):
        assert MetricName.is_runtime_metric(name)
    assert not MetricName.is_runtime_metric("Egress_Rows")


# -- both benchmark flows, 2,048 rows a batch, fixed seed and clock ---------
@pytest.mark.parametrize("config_name", ["nexmark-q1", "homeautomation-5s"])
def test_benchmark_flow_sink_files_are_the_per_row_bytes(
    tmp_path, config_name
):
    """The files the benchmark reads back: each is byte for byte the
    per-row code's payload over the same landed table, every output of
    both flows takes the columnar path, and no dict is built."""
    import importlib
    import os

    import jax

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
        root, "benchmark", "configs", config_name + ".json"
    ), encoding="utf-8") as f:
        config = json.load(f)
    flow = importlib.import_module("benchmark.flows." + config["flow"])
    (tmp_path / "s.json").write_text(json.dumps(config["schema"]))
    (tmp_path / "t.transform").write_text(config["transform"])
    conf = {
        "datax.job.name": config["job_name"],
        "datax.job.input.default.blobschemafile": str(tmp_path / "s.json"),
        "datax.job.input.default.streaming.intervalinseconds":
            str(config["interval_s"]),
        "datax.job.process.batchcapacity": "2048",
        "datax.job.process.transform": str(tmp_path / "t.transform"),
        "datax.job.process.watermark": config["guarantees"]["watermark"],
    }
    # a conf file spells a newline inside a value as backslash-n
    conf.update(
        {k: v.replace("\\n", "\n") for k, v in config["conf"].items()}
    )
    proc = FlowProcessor(
        SettingDictionary(conf), output_datasets=config["outputs"]
    )
    n, base = 2048, 1_790_000_000_000
    ev = flow.make_events(4_294_967_311, 3 * n, 0)
    ev["due_ms"] = base + np.arange(3 * n, dtype=np.int64) // 2
    sinks = {
        o: FileSink(str(tmp_path / "out" / o), "none")
        for o in config["outputs"]
    }
    total = 0
    for b in range(3):
        t_ms = base + 1000 * (b + 1)
        raw = proc.encode_json_bytes(
            flow.lines(ev, b * n, (b + 1) * n), t_ms
        )
        h = proc.dispatch_batch(raw, t_ms)
        landed = jax.device_get(h.out_datasets)
        datasets, metrics = h.collect_tables()
        assert metrics["Egress_Fallback_Rows"] == 0.0
        assert metrics["Egress_Columnar_Rows"] == float(
            sum(len(rows) for rows in datasets.values())
        )
        for o, rows in datasets.items():
            assert rows.columnar
            sinks[o].write(o, rows, t_ms)
            assert rows._rows is None, "the file sink built a dict"
            reference = materialize_rows(
                landed[o], h.pipeline.schema_of(o), proc.dictionary,
                h.base_ms,
            )
            (path,) = (tmp_path / "out" / o).rglob(f"{o}_{t_ms}_*.json")
            assert path.read_bytes() == per_row_payload(reference)
            total += len(reference)
    assert total > 3 * 8  # HeatAvg's 8 groups a batch at the very least


# -- sinks -------------------------------------------------------------------
def _batch(name="q1_shape"):
    table, schema, dictionary = _flat_case(name)
    reference = materialize_rows(table, schema, dictionary, BASE_MS)
    return ColumnBatch(table, schema, dictionary, BASE_MS), reference


@pytest.mark.parametrize("compression", ["none", "gzip"])
def test_file_sink_bytes_are_the_per_row_payload(tmp_path, compression):
    batch, reference = _batch("string_escapes_and_repeats")
    sink = FileSink(str(tmp_path), compression)
    assert sink.write("Out", batch, 1_700_000_000_000) == len(reference)
    assert batch._rows is None, "the file sink built a dict"
    (path,) = [p for p in tmp_path.rglob("Out_*") if p.is_file()]
    assert not list(tmp_path.rglob("*.tmp.*"))  # temp + rename, as before
    raw = path.read_bytes()
    if compression == "gzip":
        assert path.name.endswith(".json.gz")
        raw = gzip.decompress(raw)
    assert raw == per_row_payload(reference)
    # a plain list still writes the same file
    other = FileSink(str(tmp_path / "list"), compression)
    other.write("Out", reference, 1_700_000_000_000)
    (path2,) = [p for p in (tmp_path / "list").rglob("Out_*") if p.is_file()]
    raw2 = path2.read_bytes()
    assert (gzip.decompress(raw2) if compression == "gzip" else raw2) == raw
    # an empty batch writes no file
    assert FileSink(str(tmp_path / "none"), compression).write(
        "Out", ColumnBatch(*_flat_case("empty_table")), 0
    ) == 0
    assert not (tmp_path / "none").exists()


class _Http:
    """A local endpoint that keeps every POSTed body."""

    def __enter__(self):
        bodies = self.bodies = []

        class H(BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length") or 0)
                bodies.append(json.loads(self.rfile.read(n)))
                self.send_response(200)
                self.send_header("Content-Length", "2")
                self.end_headers()
                self.wfile.write(b"ok")

            def log_message(self, *a):
                pass

        self.srv = ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=self.srv.serve_forever, daemon=True).start()
        self.url = f"http://127.0.0.1:{self.srv.server_address[1]}"
        return self

    def __exit__(self, *exc):
        self.srv.shutdown()
        self.srv.server_close()


def _console(tmp_path, batch, reference):
    lines = []
    assert ConsoleSink(2, lines.append).write("Out", batch, 0) == 3
    assert lines == [f"[Out] {json.dumps(r)}" for r in reference[:2]]


def _file(tmp_path, batch, reference):
    assert FileSink(str(tmp_path), "none").write("Out", batch, 0) == 3
    (path,) = [p for p in tmp_path.rglob("Out_*") if p.is_file()]
    assert path.read_bytes() == per_row_payload(reference)


def _httppost(tmp_path, batch, reference):
    with _Http() as http:
        assert HttpPostSink(http.url).write("Out", batch, 0) == 3
    # a JSON array of objects, not the batch's str()
    assert http.bodies == [reference]


def _externalfn(tmp_path, batch, reference):
    with _Http() as http:
        assert ExternalFunctionSink(http.url, api="run").write(
            "Out", batch, 0
        ) == 3
    assert http.bodies == reference


def _sql(tmp_path, batch, reference):
    db = str(tmp_path / "out.db")
    assert SqlSink(db, "q1").write("Out", batch, 0) == 3
    got = sqlite3.connect(db).execute(
        "SELECT auction, bidder, price, dateTime FROM q1"
    ).fetchall()
    assert got == [tuple(r.values()) for r in reference]


def _cosmosdb(tmp_path, batch, reference):
    assert DocumentSink(str(tmp_path), "db", "c").write("Out", batch, 0) == 3
    docs = [
        json.loads(line)
        for line in (tmp_path / "db" / "c" / "docs.jsonl").open()
    ]
    assert [{k: v for k, v in d.items() if k != "id"} for d in docs] \
        == reference
    assert len({d["id"] for d in docs}) == 3


def _eventhub(tmp_path, batch, reference):
    import time

    src = SocketSource(port=0)
    try:
        assert StreamSink("127.0.0.1", src.port).write("Out", batch, 0) == 3
        rows, deadline = [], time.time() + 5
        while time.time() < deadline and len(rows) < 3:
            got, _ = src.poll(10)
            rows.extend(got)
            src.ack()
            time.sleep(0.02)
        assert rows == reference
    finally:
        src.close()


def _kafka(tmp_path, batch, reference):
    from test_kafka_wire import FakeBroker

    b = FakeBroker({"alerts": {0: []}})
    try:
        sink = KafkaSink(f"127.0.0.1:{b.port}", "alerts")
        assert sink.write("Out", batch, 0) == 3
        sink.close()
        assert [json.loads(v) for v in b.topics["alerts"][0]] == reference
    finally:
        b.close()


def _metric(tmp_path, batch, reference):
    store = MetricStore()
    sink = MetricSink(MetricLogger("DATAX-Egress", store=store))
    assert sink.write("Out", batch, 1000) == 3
    assert store.keys("DATAX-Egress:")


SINK_KINDS = {
    "console": _console, "file": _file, "httppost": _httppost,
    "externalfn": _externalfn, "sql": _sql, "cosmosdb": _cosmosdb,
    "eventhub": _eventhub, "kafka": _kafka, "metric": _metric,
}


@pytest.mark.parametrize("kind", sorted(SINK_KINDS))
def test_every_sink_kind_accepts_the_batch(tmp_path, kind):
    batch, reference = _batch()
    SINK_KINDS[kind](tmp_path, batch, reference)
    # ... and through the operator, which reads len() for its span
    if kind == "console":
        op = OutputOperator("Out", [ConsoleSink(1, lambda line: None)])
        assert op.write(batch, 0) == {"console": 3}
