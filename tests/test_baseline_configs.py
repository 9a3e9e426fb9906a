"""End-to-end coverage of the BASELINE.md staged configs 2-5.

Config 1 (SimulatedData IoT hello-world threshold alert) is
tests/test_onebox_e2e.py. These exercise the rest:

2. tumbling-window COUNT/AVG over the event stream (TIMEWINDOW tables)
3. accumulator state + sliding-window join (raw-row retention on device)
4. multi-rule anomaly alerting with a Pallas-tier UDF
5. high-fanout group-by sharded across the virtual 8-device mesh
"""

import json

import numpy as np
import pytest

from data_accelerator_tpu.core.config import SettingDictionary
from data_accelerator_tpu.runtime.processor import FlowProcessor

SCHEMA = json.dumps({"type": "struct", "fields": [
    {"name": "deviceId", "type": "long", "nullable": False, "metadata": {}},
    {"name": "temperature", "type": "double", "nullable": False, "metadata": {}},
    {"name": "eventTimeStamp", "type": "timestamp", "nullable": False,
     "metadata": {"useCurrentTimeMillis": True}},
]})


def _conf(tmp_path, transform, extra=None):
    tmp_path.mkdir(parents=True, exist_ok=True)
    t = tmp_path / "flow.transform"
    t.write_text(transform)
    d = {
        "datax.job.name": "BaselineCfg",
        "datax.job.input.default.blobschemafile": SCHEMA,
        "datax.job.process.transform": str(t),
        "datax.job.process.timestampcolumn": "eventTimeStamp",
        "datax.job.process.watermark": "0 second",
        "datax.job.process.batchcapacity": "32",
    }
    d.update(extra or {})
    return SettingDictionary(d)


def _rows(ids, temps, ts_ms):
    return [
        {"deviceId": i, "temperature": t, "eventTimeStamp": ts}
        for i, t, ts in zip(ids, temps, ts_ms)
    ]


# -- config 2: tumbling-window COUNT/AVG ---------------------------------

def test_config2_window_count_avg_accumulates_across_batches(tmp_path):
    proc = FlowProcessor(
        _conf(
            tmp_path,
            "--DataXQuery--\n"
            "WinAgg = SELECT deviceId, COUNT(*) AS Cnt, "
            "AVG(temperature) AS AvgT "
            "FROM DataXProcessedInput_10seconds GROUP BY deviceId\n",
            {"datax.job.process.timewindow.DataXProcessedInput_10seconds"
             ".windowduration": "10 seconds"},
        ),
        output_datasets=["WinAgg"],
    )
    base = 1_700_000_000_000
    # batch 1: device 1 twice, device 2 once
    proc.process_batch(
        proc.encode_rows(_rows([1, 1, 2], [10.0, 20.0, 5.0],
                               [base, base, base]), base),
        base,
    )
    # batch 2 (3 s later, batch 1 still inside the 10 s window): device 1
    # again. The timestamp column comes from the payload, so this is an
    # event-time window (runtime/timewindow.py): with a watermark of 0 it
    # holds the 10 whole seconds that end one before the batch's own (a
    # batch's on-time rows are stamped in its own second and the one
    # before), and a row is read once the batch is two seconds past it
    datasets, _ = proc.process_batch(
        proc.encode_rows(_rows([1], [30.0], [base + 3000]), base + 3000),
        base + 3000,
    )
    agg = {r["deviceId"]: r for r in datasets["WinAgg"]}
    assert agg[1]["Cnt"] == 2
    assert agg[1]["AvgT"] == pytest.approx(15.0)
    assert agg[2]["Cnt"] == 1

    # batch 3, 12 s after batch 1: batch-1 rows fell out of the window,
    # batch 2's row is in it, batch 3's own not yet
    datasets, _ = proc.process_batch(
        proc.encode_rows(_rows([2], [50.0], [base + 12000]), base + 12000),
        base + 12000,
    )
    agg = {r["deviceId"]: r for r in datasets["WinAgg"]}
    assert set(agg) == {1}  # device 1's old rows and device 2's evicted
    assert agg[1]["Cnt"] == 1 and agg[1]["AvgT"] == pytest.approx(30.0)

    # batch 4, two seconds later: batch 3's row has entered
    datasets, _ = proc.process_batch(
        proc.encode_rows(_rows([], [], []), base + 14000), base + 14000,
    )
    agg = {r["deviceId"]: r for r in datasets["WinAgg"]}
    assert agg[2]["Cnt"] == 1 and agg[2]["AvgT"] == pytest.approx(50.0)


# -- config 3: accumulator + sliding-window join --------------------------

def test_config3_state_accumulator_and_window_join(tmp_path):
    """Join the current batch against the 5 s window of raw rows (the
    sliding-window-join case: raw-row retention on device) while an
    accumulation table carries device peaks across batches."""
    transform = (
        "--DataXQuery--\n"
        "peaks_in = SELECT deviceId, temperature AS peak "
        "FROM DataXProcessedInput WHERE temperature > 50\n"
        "--DataXQuery--\n"
        "merged = SELECT deviceId, peak FROM peaks_in "
        "UNION ALL SELECT deviceId, peak FROM peaks\n"
        "--DataXQuery--\n"
        "peaks = SELECT deviceId, MAX(peak) AS peak FROM merged "
        "GROUP BY deviceId\n"
        "--DataXQuery--\n"
        "Joined = SELECT a.deviceId, a.temperature, b.temperature AS prior "
        "FROM DataXProcessedInput a INNER JOIN "
        "DataXProcessedInput_5seconds b ON a.deviceId = b.deviceId "
        "WHERE b.temperature < a.temperature\n"
    )
    proc = FlowProcessor(
        _conf(
            tmp_path, transform,
            {
                "datax.job.process.timewindow.DataXProcessedInput_5seconds"
                ".windowduration": "5 seconds",
                "datax.job.process.statetable.peaks.schema":
                    "deviceId long, peak double",
                "datax.job.process.statetable.peaks.location":
                    str(tmp_path / "state"),
            },
        ),
        output_datasets=["Joined"],
    )
    base = 1_700_000_000_000
    proc.process_batch(
        proc.encode_rows(_rows([1], [60.0], [base]), base), base
    )
    proc.commit()
    # batch 2 at +2 s: row (1, 80) joins batch-1's (1, 60) in the window
    datasets, _ = proc.process_batch(
        proc.encode_rows(_rows([1], [80.0], [base + 2000]), base + 2000),
        base + 2000,
    )
    proc.commit()
    joined = datasets["Joined"]
    assert any(
        r["deviceId"] == 1 and r["temperature"] == 80.0 and r["prior"] == 60.0
        for r in joined
    )
    # the accumulator kept the running max across batches
    loaded = proc.state_tables["peaks"].load(proc.dictionary)
    peaks = {
        int(k): float(v) for k, v, ok in zip(
            np.asarray(loaded.cols["deviceId"]),
            np.asarray(loaded.cols["peak"]),
            np.asarray(loaded.valid),
        ) if ok
    }
    assert peaks[1] == 80.0


# -- config 4: multi-rule anomaly alerting with a Pallas UDF --------------

def test_config4_multi_rule_with_pallas_udf(tmp_path):
    from data_accelerator_tpu.udf.samples import anomalyscore

    transform = (
        "--DataXQuery--\n"
        "Scored = SELECT deviceId, temperature, "
        "anomalyscore(temperature, deviceId) AS score "
        "FROM DataXProcessedInput\n"
        "--DataXQuery--\n"
        "HotAlerts = SELECT deviceId, temperature FROM Scored "
        "WHERE temperature > 90\n"
        "--DataXQuery--\n"
        "AnomalyAlerts = SELECT deviceId, score FROM Scored "
        "WHERE score > 0.9\n"
    )
    proc = FlowProcessor(
        _conf(tmp_path, transform),
        udfs={"anomalyscore": anomalyscore(interpret=True)},
        output_datasets=["HotAlerts", "AnomalyAlerts"],
    )
    base = 1_700_000_000_000
    datasets, metrics = proc.process_batch(
        proc.encode_rows(
            _rows([1, 2, 3], [95.0, 20.0, 400.0], [base] * 3), base
        ),
        base,
    )
    assert {r["deviceId"] for r in datasets["HotAlerts"]} == {1, 3}
    # the far-outlier reading scores ~1.0 on the pallas kernel
    assert any(r["deviceId"] == 3 for r in datasets["AnomalyAlerts"])
    assert metrics["Output_HotAlerts_Events_Count"] == 2.0


# -- config 5: high-fanout group-by sharded over the mesh -----------------

def test_config5_high_fanout_groupby_sharded_matches_single(tmp_path):
    import jax

    from data_accelerator_tpu.compile.planner import TableData
    from data_accelerator_tpu.dist import make_mesh, row_sharding

    transform = (
        "--DataXQuery--\n"
        "Fanout = SELECT deviceId, COUNT(*) AS Cnt, "
        "SUM(temperature) AS SumT FROM DataXProcessedInput "
        "GROUP BY deviceId\n"
    )
    cap = 512
    rng = np.random.RandomState(7)
    ids = rng.randint(0, 200, cap)  # high fanout: ~200 groups
    temps = rng.uniform(0, 100, cap)
    ts = [1_700_000_000_000] * cap
    rows = _rows(ids.tolist(), temps.tolist(), ts)

    single = FlowProcessor(
        _conf(tmp_path / "s", transform,
              {"datax.job.process.batchcapacity": str(cap),
               "datax.job.process.groupcapacity": "256"}),
        output_datasets=["Fanout"],
    )
    d1, _ = single.process_batch(
        single.encode_rows(rows, 1_700_000_000_000), 1_700_000_000_000
    )

    mesh = make_mesh(8)
    sharded = FlowProcessor(
        _conf(tmp_path / "m", transform,
              {"datax.job.process.batchcapacity": str(cap),
               "datax.job.process.groupcapacity": "256"}),
        output_datasets=["Fanout"],
        mesh=mesh,
    )
    raw = sharded.encode_rows(rows, 1_700_000_000_000)
    sh = row_sharding(mesh)
    raw = TableData(
        {k: jax.device_put(v, sh) for k, v in raw.cols.items()},
        jax.device_put(raw.valid, sh),
    )
    d2, _ = sharded.process_batch(raw, 1_700_000_000_000)

    def to_map(rows_):
        return {
            r["deviceId"]: (r["Cnt"], round(r["SumT"], 3)) for r in rows_
        }

    assert to_map(d1["Fanout"]) == to_map(d2["Fanout"])
    assert len(d1["Fanout"]) == len(set(ids))


def test_config5_stress_high_cardinality_sharded(tmp_path):
    """Config 5 at stress scale: 65k rows, ~12k distinct groups, conf'd
    group capacity, sharded over the virtual 8-device mesh — aggregates
    must match single-device exactly and fit the configured bound."""
    import jax

    from data_accelerator_tpu.compile.planner import TableData
    from data_accelerator_tpu.dist import make_mesh, row_sharding

    transform = (
        "--DataXQuery--\n"
        "Fanout = SELECT deviceId, COUNT(*) AS Cnt, SUM(temperature) AS S, "
        "MAX(temperature) AS M FROM DataXProcessedInput GROUP BY deviceId\n"
    )
    cap = 65536
    rng = np.random.RandomState(11)
    ids = rng.randint(0, 12_000, cap)
    temps = rng.uniform(0, 100, cap).round(3)
    extra = {
        "datax.job.process.batchcapacity": str(cap),
        "datax.job.process.maxgroups": "16384",
    }

    def run(mesh):
        proc = FlowProcessor(
            _conf(tmp_path / ("m" if mesh else "s"), transform, extra),
            output_datasets=["Fanout"], mesh=mesh,
        )
        cols = {
            "deviceId": ids.astype(np.int32),
            "temperature": temps.astype(np.float32),
            "eventTimeStamp": np.zeros(cap, np.int32),
        }
        raw = proc.encode_columns(cols, cap)
        if mesh is not None:
            sh = row_sharding(mesh)
            raw = TableData(
                {k: jax.device_put(v, sh) for k, v in raw.cols.items()},
                jax.device_put(raw.valid, sh),
            )
        d, m = proc.process_batch(raw, 1_700_000_000_000)
        return d, m

    d1, m1 = run(None)
    d2, m2 = run(make_mesh(8))

    def to_map(rows_):
        return {
            r["deviceId"]: (r["Cnt"], round(r["S"], 1), round(r["M"], 3))
            for r in rows_
        }

    a, b = to_map(d1["Fanout"]), to_map(d2["Fanout"])
    assert len(a) == len(set(ids))  # every distinct key surfaced
    assert a == b
    assert m1["Output_Fanout_GroupsDropped"] == 0.0
    assert m2["Output_Fanout_GroupsDropped"] == 0.0
