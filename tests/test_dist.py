"""Multi-device sharded execution on the virtual 8-device CPU mesh.

Mirrors what the reference gets from Spark data-parallelism + shuffle
(CommonProcessorFactory.scala:405-421, spark.sql shuffles at :257,271):
rows shard over the mesh, group-bys cross shard boundaries, window ring
state shards its capacity dim — and results must be identical to
single-device execution.
"""

import json

import jax
import numpy as np
import pytest

from data_accelerator_tpu.compile.planner import TableData
from data_accelerator_tpu.core.config import SettingDictionary
from data_accelerator_tpu.dist import make_mesh, row_sharding
from data_accelerator_tpu.runtime.processor import FlowProcessor

import jax.numpy as jnp

INPUT_SCHEMA = json.dumps({
    "type": "struct",
    "fields": [
        {"name": "deviceId", "type": "long", "nullable": False,
         "metadata": {"allowedValues": [1, 2, 3, 4, 5]}},
        {"name": "temperature", "type": "double", "nullable": False,
         "metadata": {"minValue": 0, "maxValue": 100}},
    ],
})

TRANSFORM = (
    "--DataXQuery--\n"
    "Hot = SELECT deviceId, temperature FROM DataXProcessedInput "
    "WHERE temperature > 50\n"
    "--DataXQuery--\n"
    "PerDevice = SELECT deviceId, COUNT(*) AS Cnt, MAX(temperature) AS MaxT "
    "FROM DataXProcessedInput_2seconds GROUP BY deviceId\n"
)


# a statement that reads the window's rows: the planner then keeps the
# raw-row ring (a decomposable GROUP BY alone is held as per-slot partial
# aggregates, tests/test_window_partials.py)
ROW_READER = (
    "--DataXQuery--\n"
    "Recent = SELECT deviceId, temperature FROM DataXProcessedInput_2seconds "
    "WHERE temperature > 99\n"
)


def make_conf(tmp_path, transform_text=TRANSFORM):
    transform = tmp_path / "t.transform"
    transform.write_text(transform_text)
    return SettingDictionary({
        "datax.job.name": "DistTest",
        "datax.job.input.default.inputtype": "local",
        "datax.job.input.default.blobschemafile": INPUT_SCHEMA,
        "datax.job.input.default.streaming.intervalinseconds": "1",
        "datax.job.process.timestampcolumn": "eventTimeStamp",
        "datax.job.process.watermark": "0 second",
        "datax.job.process.transform": str(transform),
        "datax.job.process.timewindow.DataXProcessedInput_2seconds.windowduration": "2 seconds",
        "datax.job.process.projection": (
            "current_timestamp() AS eventTimeStamp\nRaw.*"
        ),
    })


def crafted_raw(proc, n_rows=96):
    cap = proc.batch_capacity
    rng = np.random.RandomState(7)
    cols = {}
    for c, t in proc.raw_schema.types.items():
        if c == "deviceId":
            cols[c] = np.asarray(rng.randint(1, 6, size=cap), np.int32)
        elif c == "temperature":
            cols[c] = np.asarray(rng.uniform(0, 100, size=cap), np.float32)
        elif t == "double":
            cols[c] = np.zeros(cap, np.float32)
        else:
            cols[c] = np.zeros(cap, np.int32)
    valid = np.zeros(cap, bool)
    valid[:n_rows] = True
    return cols, valid


def run_flow(proc, cols, valid, batches=3):
    out = []
    for i in range(batches):
        raw = TableData(
            {k: jnp.asarray(v) for k, v in cols.items()}, jnp.asarray(valid)
        )
        datasets, metrics = proc.process_batch(
            raw, batch_time_ms=1_700_000_000_000 + i * 1000
        )
        out.append((datasets, metrics))
    return out


def canon(rows, keys):
    return sorted(
        tuple(r[k] for k in keys) for r in rows
    )


def test_sharded_matches_single_device(tmp_path):
    d = make_conf(tmp_path)
    mesh = make_mesh(8)
    single = FlowProcessor(d, batch_capacity=256,
                           output_datasets=["Hot", "PerDevice"])
    sharded = FlowProcessor(d, batch_capacity=256, mesh=mesh,
                            output_datasets=["Hot", "PerDevice"])
    assert sharded.batch_capacity % 8 == 0

    cols, valid = crafted_raw(single)
    res_single = run_flow(single, cols, valid)
    res_sharded = run_flow(sharded, cols, valid)

    for (ds_s, m_s), (ds_m, m_m) in zip(res_single, res_sharded):
        assert canon(ds_s["Hot"], ["deviceId", "temperature"]) == canon(
            ds_m["Hot"], ["deviceId", "temperature"]
        )
        # windowed cross-batch group-by: identical per-device aggregates
        assert canon(ds_s["PerDevice"], ["deviceId", "Cnt", "MaxT"]) == canon(
            ds_m["PerDevice"], ["deviceId", "Cnt", "MaxT"]
        )
        assert m_s["Input_DataXProcessedInput_Events_Count"] == (
            m_m["Input_DataXProcessedInput_Events_Count"]
        )


def test_sharded_input_placement(tmp_path):
    """Raw columns pre-placed with the row sharding are consumed without
    resharding; the ring state stays sharded across steps."""
    d = make_conf(tmp_path, TRANSFORM + ROW_READER)
    mesh = make_mesh(8)
    proc = FlowProcessor(d, batch_capacity=256, mesh=mesh,
                         output_datasets=["PerDevice"])
    cols, valid = crafted_raw(proc)
    sh = row_sharding(mesh)
    raw = TableData(
        {k: jax.device_put(jnp.asarray(v), sh) for k, v in cols.items()},
        jax.device_put(jnp.asarray(valid), sh),
    )
    proc.process_batch(raw, batch_time_ms=1_700_000_000_000)
    ring = proc.window_buffers["DataXProcessedInput"]
    ts = ring.cols[proc.timestamp_column]
    assert len(ts.sharding.device_set) == 8


def test_numchips_builds_the_mesh_and_places_state_and_ingest_on_it(tmp_path):
    """process.numchips=N makes an N-device mesh: the rings start out
    sharded over it (not whole on device 0 until the first step), the
    encoders hand each chip its row shard, and placement() reports
    both."""
    conf = dict(make_conf(tmp_path, TRANSFORM + ROW_READER).dict)
    conf["datax.job.process.numchips"] = "4"
    proc = FlowProcessor(SettingDictionary(conf), batch_capacity=256,
                         output_datasets=["PerDevice"])
    assert proc.mesh is not None and proc.mesh.size == 4
    ring = proc.window_buffers["DataXProcessedInput"]
    assert len(ring.valid.sharding.device_set) == 4
    cols, valid = crafted_raw(proc)
    raw = proc.encode_columns(cols, 96)
    assert all(
        len(a.sharding.device_set) == 4
        for a in (*raw.cols.values(), raw.valid)
    )
    proc.process_batch(raw, batch_time_ms=1_700_000_000_000)
    placed = proc.placement()
    assert placed["stepDevices"] == 4
    assert placed["ringDevices"] == {"DataXProcessedInput": 4}
    assert placed["rawDevices"] == {"default": 4}
    assert len(placed["deviceBytesInUse"]) == 4


def test_numchips_above_the_visible_devices_raises(tmp_path):
    """A conf that asks for more chips than this process can see is an
    error, never a smaller mesh with a log line."""
    from data_accelerator_tpu.core.config import EngineException

    conf = dict(make_conf(tmp_path).dict)
    conf["datax.job.process.numchips"] = str(len(jax.devices()) + 1)
    with pytest.raises(EngineException, match="numchips=9.*only 8"):
        FlowProcessor(SettingDictionary(conf), batch_capacity=256)


def test_host_ingest_plan_single_process_owns_everything(tmp_path):
    """On one process the plan covers all partitions/rows; the global
    batch assembled from 'local' data is correctly row-sharded and runs
    through a sharded step."""
    import numpy as np

    from data_accelerator_tpu.dist import HostIngestPlan, make_mesh

    mesh = make_mesh(8)
    plan = HostIngestPlan(
        mesh, global_capacity=64, n_partitions=16, max_rate=32000,
    )
    assert plan.partitions == list(range(16))
    assert plan.local_capacity == 64
    assert plan.max_rate == 32000

    cols = {"v": np.arange(64, dtype=np.int32)}
    valid = np.ones(64, dtype=bool)
    table = plan.make_global(cols, valid)
    assert table.cols["v"].shape == (64,)
    assert len(table.cols["v"].sharding.device_set) == 8
    assert np.asarray(table.cols["v"]).tolist() == list(range(64))


def test_assigned_partitions_balance():
    from data_accelerator_tpu.dist import assigned_partitions

    p0 = assigned_partitions(10, process_index=0, process_count=4)
    p3 = assigned_partitions(10, process_index=3, process_count=4)
    assert p0 == [0, 4, 8]
    assert p3 == [3, 7]
    allp = sorted(
        sum((assigned_partitions(10, i, 4) for i in range(4)), [])
    )
    assert allp == list(range(10))


def test_host_ingest_plan_rejects_wrong_shard_size():
    import numpy as np
    import pytest

    from data_accelerator_tpu.dist import HostIngestPlan, make_mesh

    plan = HostIngestPlan(make_mesh(8), 64, 4, 1000)
    with pytest.raises(ValueError):
        plan.make_global({"v": np.zeros(32, np.int32)}, np.ones(32, bool))


# compiled HLO as the TPU backend prints it (lines from the 4-chip v5e
# run of __graft_entry__.dryrun_multichip, PR 21): tiled layouts, a
# combined tuple-shaped all-reduce with /*index=N*/ markers, and uses of
# a collective's NAME as an operand, which are not collectives
_TPU_HLO = """
  %all-reduce.9 = (s32[384]{0:T(512)}, s32[384]{0:T(512)}, s32[384]{0:T(512)}, s32[384]{0:T(512)}, s32[384]{0:T(512)}, /*index=5*/s32[384]{0:T(512)}, s32[384]{0:T(512)}) all-reduce(%dynamic-update-slice, %dynamic-update-slice.1, /*index=5*/%dynamic-update-slice.5), channel_id=1, replica_groups=[1,4]<=[4], to_apply=%add
  %get-tuple-element = s32[384]{0:T(512)} get-tuple-element(%all-reduce.9), index=0
  %all-reduce.6 = f32[384]{0:T(512)} all-reduce(%dynamic-update-slice.6), channel_id=7, replica_groups=[1,4]<=[4], use_global_device_ids=true, to_apply=%add.6
  %all-reduce.8 = u32[1,1,128]{2,1,0:T(1,128)} all-reduce(%bitcast.2), channel_id=9, replica_groups=[1,4]<=[4], to_apply=%add.8.clone
  %bitcast.3 = pred[384]{0:T(512)(128)(4,1)} bitcast(%all-reduce.8)
  %compare_select_fusion.5 = s32[704]{0} fusion(%copy.80, %all-reduce.17, %copy.81), kind=kLoop, calls=%fused_computation.9
  %all-gather = pred[6,64]{0,1} all-gather(%select_dynamic-update-slice_fusion.1), channel_id=3, replica_groups=[1,4]<=[4], dimensions={1}
  %collective-permute.2 = s32[48]{0:T(128)} collective-permute(%wrapped_slice.7), channel_id=30, source_target_pairs={{0,1},{1,2},{2,3}}
  ROOT %tuple.2 = (s32[384]{0:T(512)}, pred[384]{0:T(512)(128)(4,1)}) tuple(%get-tuple-element, %bitcast.3)
"""


def test_collective_census_reads_tpu_hlo():
    from data_accelerator_tpu.dist.mesh import collective_summary

    assert collective_summary(_TPU_HLO).to_dict() == {
        "all-reduce": {"count": 3,
                       "resultBytes": 7 * 384 * 4 + 384 * 4 + 128 * 4},
        "all-gather": {"count": 1, "resultBytes": 6 * 64},
        "collective-permute": {"count": 1, "resultBytes": 48 * 4},
    }


def test_collective_census_counts_an_async_pair_once_by_its_result():
    """-start returns (operand, result[, context scalars]); only the
    -done's shape is the result."""
    from data_accelerator_tpu.dist.mesh import collective_summary

    hlo = (
        "  %all-gather-start.1 = (f32[16384]{0:T(1024)}, "
        "f32[65536]{0:T(1024)}) all-gather-start(%param.3), channel_id=5, "
        "dimensions={0}\n"
        "  %all-gather-done.1 = f32[65536]{0:T(1024)} "
        "all-gather-done(%all-gather-start.1)\n"
        "  %collective-permute-start = (s32[16]{0}, s32[16]{0}, u32[], "
        "u32[]) collective-permute-start(%x), source_target_pairs={{0,1}}\n"
        "  %collective-permute-done = s32[16]{0} "
        "collective-permute-done(%collective-permute-start)\n"
    )
    assert collective_summary(hlo).to_dict() == {
        "all-gather": {"count": 1, "resultBytes": 65536 * 4},
        "collective-permute": {"count": 1, "resultBytes": 64},
    }
