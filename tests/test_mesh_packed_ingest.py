"""The packed raw matrix under a mesh: a non-local source's batch is one
[columns + 1, capacity] matrix on four chips as on one, put with its
capacity axis sharded, and the step it feeds is the step the row layout
(a leaf a column, rows sharded) feeds: the same tables, window state and
counts, the same collectives."""

import json

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from data_accelerator_tpu.compile.planner import TableData
from data_accelerator_tpu.core.config import SettingDictionary
from data_accelerator_tpu.dist import make_mesh
from data_accelerator_tpu.dist.mesh import (
    packed_sharding,
    row_sharding,
    step_shardings,
)
from data_accelerator_tpu.runtime.processor import FlowProcessor, PackedRaw

SCHEMA = json.dumps({"type": "struct", "fields": [
    {"name": "deviceId", "type": "long", "nullable": False, "metadata": {}},
    {"name": "kind", "type": "string", "nullable": False, "metadata": {}},
    {"name": "temperature", "type": "double", "nullable": False,
     "metadata": {}},
    {"name": "open", "type": "boolean", "nullable": False, "metadata": {}},
]})
GROUPED = (
    "--DataXQuery--\n"
    "Hot = SELECT deviceId, kind, temperature FROM DataXProcessedInput "
    "WHERE temperature > 50 AND open\n"
    "--DataXQuery--\n"
    "PerDevice = SELECT deviceId, COUNT(*) AS Cnt, MAX(temperature) AS MaxT, "
    "AVG(temperature) AS AvgT FROM DataXProcessedInput_3seconds "
    "GROUP BY deviceId\n"
)
# a statement that reads the window's rows keeps the raw-row ring; the
# GROUP BY alone is held as per-slot partial aggregates
ROW_READER = (
    "--DataXQuery--\n"
    "Recent = SELECT deviceId, temperature FROM DataXProcessedInput_3seconds "
    "WHERE temperature > 90\n"
)
OUTPUTS = {"ring": ["Hot", "PerDevice", "Recent"],
           "partials": ["Hot", "PerDevice"]}
CAPACITY = 512
T0_MS = 1_700_000_000_000


def _proc(tmp_path, state, mesh=4, inputtype="socket"):
    tmp_path.mkdir(exist_ok=True)
    t = tmp_path / "t.transform"
    t.write_text(GROUPED + (ROW_READER if state == "ring" else ""))
    conf = {
        "datax.job.name": "MeshPacked",
        "datax.job.input.default.inputtype": inputtype,
        "datax.job.input.default.blobschemafile": SCHEMA,
        "datax.job.input.default.streaming.intervalinseconds": "1",
        "datax.job.process.timestampcolumn": "eventTimeStamp",
        "datax.job.process.watermark": "0 second",
        "datax.job.process.transform": str(t),
        "datax.job.process.timewindow.DataXProcessedInput_3seconds"
        ".windowduration": "3 seconds",
        "datax.job.process.projection":
            "current_timestamp() AS eventTimeStamp\nRaw.*",
    }
    if mesh:
        conf["datax.job.process.numchips"] = str(mesh)
    return FlowProcessor(SettingDictionary(conf), batch_capacity=CAPACITY,
                         output_datasets=OUTPUTS[state])


def _blob(batch, rows=300):
    rng = np.random.RandomState(100 + batch)
    lines = [
        json.dumps({
            "deviceId": int(rng.randint(1, 9)), "kind": f"kind{i % 3}",
            "temperature": float(np.float32(rng.uniform(0, 100))),
            "open": bool(i % 2),
        }).encode()
        for i in range(rows)
    ]
    lines[7] = b'{"deviceId": 1, "kind": '  # a malformed line's empty slot
    return b"".join(ln + b"\n" for ln in lines)


def _run(proc, packed, batches=4):
    landed = []
    for i in range(batches):
        raw = proc.encode_json_bytes(_blob(i), T0_MS + 1000 * i,
                                     packed=packed, to_device=False)
        assert isinstance(raw, PackedRaw if packed else TableData)
        datasets, metrics = proc.process_batch(raw, T0_MS + 1000 * i + 5)
        landed.append((
            {n: sorted(map(json.dumps, rows)) for n, rows in datasets.items()},
            {k: v for k, v in metrics.items()
             if k.startswith(("Input_", "Output_"))},
        ))
    state = [np.asarray(a) for a in
             jax.tree_util.tree_leaves(proc.window_buffers)]
    return landed, state


@pytest.mark.parametrize("state", ["ring", "partials"])
def test_the_packed_matrix_and_the_row_layout_feed_one_step_under_a_mesh(
    tmp_path, state
):
    packed = _proc(tmp_path / "p", state)
    rows = _proc(tmp_path / "r", state)
    assert (set(packed.ring_slots), set(packed.window_states)) == (
        ({"DataXProcessedInput"}, set()) if state == "ring"
        else (set(), {"PerDevice"}))
    got, got_state = _run(packed, True)
    want, want_state = _run(rows, False)
    assert got == want
    assert any(ds["PerDevice"] for ds, _m in got)
    assert [m["Input_DataXProcessedInput_Events_Count"] for _d, m in got] \
        == [299.0] * 4
    assert len(got_state) == len(want_state)
    for a, b in zip(got_state, want_state):
        np.testing.assert_array_equal(a, b)
    assert packed.last_decoder_path == "native-sharded"
    assert rows.last_decoder_path == "native-mt"
    assert packed.placement()["rawDevices"] == {"default": 4} \
        == rows.placement()["rawDevices"]
    # the socket's form is the matrix: the step was built for it; the
    # host handed tables was jitted again for them, once
    assert packed._raw_packed == {"default": True}
    assert rows._raw_packed == {"default": False}
    # and what the chips exchange is the same: the matrix's rows are
    # local slices of a chip's block, none of it is gathered
    packed.refresh_mesh_collectives()
    rows.refresh_mesh_collectives()
    assert packed.mesh_collectives and rows.mesh_collectives
    assert packed.mesh_collectives.op_count > 0
    assert packed.mesh_collectives.to_dict() == rows.mesh_collectives.to_dict()


def test_the_mesh_and_one_chip_take_the_same_matrix(tmp_path):
    """The matrix the mesh's chips hold in blocks is the one-chip
    host's, cell for cell."""
    one = _proc(tmp_path / "one", "partials", mesh=None)
    four = _proc(tmp_path / "four", "partials")
    a = one.encode_json_bytes(_blob(0), T0_MS, to_device=False)
    b = four.encode_json_bytes(_blob(0), T0_MS, to_device=False)
    assert isinstance(a.data, np.ndarray) and a.layout == b.layout
    assert b.data.sharding.spec == P(None, "data")
    n_rows = len(a.layout) + 1
    assert {s.data.shape for s in b.data.addressable_shards} \
        == {(n_rows, CAPACITY // 4)}
    np.testing.assert_array_equal(np.asarray(b.data), a.data)
    # the pooled slot rides the PackedRaw on both layouts
    assert a._ingest_pool[1] is a.data and b._ingest_pool[1].shape == a.data.shape
    d1, _m = one.process_batch(a, T0_MS + 5)
    d4, _m = four.process_batch(b, T0_MS + 5)
    assert {n: sorted(map(json.dumps, r)) for n, r in d1.items()} \
        == {n: sorted(map(json.dumps, r)) for n, r in d4.items()}
    for proc in (one, four):
        pool = proc._ingest_pools["default"]
        assert len(pool._free) == pool.alloc_count == 1


def test_step_shardings_follow_each_sources_raw_form():
    mesh = make_mesh(4)
    ins, _outs = step_shardings(
        mesh, packed={"socketed": True, "generated": False})
    assert ins[0]["socketed"] == packed_sharding(mesh)
    assert ins[0]["socketed"].spec == P(None, "data")
    assert ins[0]["generated"] == row_sharding(mesh)
    assert ins[0]["generated"].spec == P("data")
    # every source in columns: one prefix for all, as before
    assert step_shardings(mesh)[0][0] == row_sharding(mesh)
    assert step_shardings(mesh, packed={})[0][0] == row_sharding(mesh)


def test_a_local_sources_columns_stay_sharded_on_their_rows(tmp_path):
    proc = _proc(tmp_path, "partials", inputtype="local")
    assert proc._raw_packed == {"default": False}
    raw = proc.encode_columns({
        "deviceId": np.arange(100, dtype=np.int32),
        "temperature": np.full(100, 60.0, np.float32),
        "open": np.ones(100, np.bool_),
    }, 100)
    assert all(a.sharding.spec == P("data")
               for a in (*raw.cols.values(), raw.valid))
    datasets, m = proc.process_batch(raw, T0_MS)
    assert len(datasets["Hot"]) == 100 and "Retrace_Count" not in m


def test_an_absent_source_runs_empty_in_its_own_form(tmp_path):
    """A batch that brings nothing for a source runs with an empty batch
    of that source's form: the matrix for a socket, under a mesh sharded
    like a full one, so that the step is not built again."""
    proc = _proc(tmp_path, "partials")
    empty = proc._empty_raw(proc.specs["default"])
    assert isinstance(empty, PackedRaw)
    assert empty.data.sharding.spec == P(None, "data")
    assert not np.asarray(empty.data).any()
    step = proc._step
    proc.process_batch({}, T0_MS)
    raw = proc.encode_json_bytes(_blob(1), T0_MS + 1000, to_device=False)
    datasets, m = proc.process_batch(raw, T0_MS + 1005)
    assert m["Input_DataXProcessedInput_Events_Count"] == 299.0
    assert proc._step is step and "Retrace_Count" not in m


def test_rows_handed_to_a_socket_declared_mesh_step_rebuild_it_once(tmp_path):
    """A non-local source that hands rows and not bytes (a file source,
    a Kafka client without raw batches) gives tables where the input
    type said matrix: the mesh step is jitted again for what it is
    handed, counted as a re-trace, and stays."""
    proc = _proc(tmp_path, "partials")
    rows = [{"deviceId": i % 5, "kind": "k", "temperature": 75.0,
             "open": True} for i in range(40)]
    built_for_the_matrix = proc._step
    datasets, m = proc.process_batch(
        proc.encode_rows(rows, T0_MS), T0_MS + 5)
    assert len(datasets["Hot"]) == 40
    assert proc._raw_packed == {"default": False}
    assert m["Retrace_Count"] == 1.0
    step = proc._step
    assert step is not built_for_the_matrix
    _d, m = proc.process_batch(
        proc.encode_rows(rows, T0_MS + 1000), T0_MS + 1005)
    assert proc._step is step and "Retrace_Count" not in m


def test_the_byte_models_price_the_processors_matrix(tmp_path):
    from data_accelerator_tpu.analysis import analyze_processor
    from data_accelerator_tpu.analysis.meshcheck import analyze_processor_mesh

    proc = _proc(tmp_path, "partials")
    n_rows = len(proc.specs["default"].raw_schema.types) + 1
    device = {s.name: s for s in analyze_processor(proc).stages}
    assert device["input:default"].hbm_bytes \
        == device["input:default"].model_bytes == n_rows * CAPACITY * 4
    mesh = {s.name: s for s in
            analyze_processor_mesh(proc, lower=False).stages}
    assert mesh["input:default"].hbm_bytes == n_rows * CAPACITY * 4
    assert mesh["input:default"].per_chip_bytes == n_rows * CAPACITY
