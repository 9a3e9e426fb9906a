"""Multi-source flows: N named input streams per flow, each with its own
schema and projection target, joined across sliding windows — BASELINE
config 3 done with two genuinely independent streams (reference: the
``input.sources`` map in flattenerConfig.json and the per-source routing
of BlobPointerInput.scala:30-160) — plus the join/group overflow metrics
and flow-configured planner capacities, and window-state checkpointing
across a restart (StreamingHost.scala:83-89's StreamingContext role).
"""

import json
import os

import numpy as np
import pytest

from data_accelerator_tpu.core.config import EngineException, SettingDictionary
from data_accelerator_tpu.runtime.checkpoint import WindowStateCheckpointer
from data_accelerator_tpu.runtime.processor import FlowProcessor

IOT_SCHEMA = json.dumps({"type": "struct", "fields": [
    {"name": "deviceId", "type": "long", "nullable": False, "metadata": {}},
    {"name": "temperature", "type": "double", "nullable": False, "metadata": {}},
    {"name": "eventTimeStamp", "type": "timestamp", "nullable": False,
     "metadata": {}},
]})

WX_SCHEMA = json.dumps({"type": "struct", "fields": [
    {"name": "stationId", "type": "long", "nullable": False, "metadata": {}},
    {"name": "windSpeed", "type": "double", "nullable": False, "metadata": {}},
    {"name": "eventTimeStamp", "type": "timestamp", "nullable": False,
     "metadata": {}},
]})

JOIN_TRANSFORM = (
    "--DataXQuery--\n"
    "Joined = SELECT a.deviceId, a.temperature, b.windSpeed "
    "FROM DataXProcessedInput a INNER JOIN Weather_5seconds b "
    "ON a.deviceId = b.stationId\n"
)


def _conf(tmp_path, transform, extra=None):
    tmp_path.mkdir(parents=True, exist_ok=True)
    t = tmp_path / "flow.transform"
    t.write_text(transform)
    d = {
        "datax.job.name": "MultiSrc",
        "datax.job.input.sources.default.blobschemafile": IOT_SCHEMA,
        "datax.job.input.sources.wx.blobschemafile": WX_SCHEMA,
        "datax.job.input.sources.wx.target": "Weather",
        "datax.job.process.transform": str(t),
        "datax.job.process.timestampcolumn": "eventTimeStamp",
        "datax.job.process.watermark": "0 second",
        "datax.job.process.batchcapacity": "32",
        "datax.job.process.timewindow.Weather_5seconds"
        ".windowduration": "5 seconds",
    }
    d.update(extra or {})
    return SettingDictionary(d)


def _iot_rows(ids, temps, ts):
    return [
        {"deviceId": i, "temperature": t, "eventTimeStamp": s}
        for i, t, s in zip(ids, temps, ts)
    ]


def _wx_rows(ids, winds, ts):
    return [
        {"stationId": i, "windSpeed": w, "eventTimeStamp": s}
        for i, w, s in zip(ids, winds, ts)
    ]


BASE = 1_700_000_000_000


def test_two_stream_sliding_window_join(tmp_path):
    """Two independent streams with different schemas; the current IoT
    batch joins weather events retained in the 5 s window — including
    weather rows from EARLIER batches (true sliding-window join), and
    they evict once the window passes."""
    proc = FlowProcessor(_conf(tmp_path, JOIN_TRANSFORM),
                         output_datasets=["Joined"])
    # batch 1: only the weather stream speaks
    proc.process_batch(
        {"wx": proc.encode_rows(
            _wx_rows([7, 9], [55.0, 10.0], [BASE, BASE]), BASE, source="wx")},
        BASE,
    )
    # batch 2 (+2 s): only IoT; joins batch-1's weather via the window
    datasets, metrics = proc.process_batch(
        {"default": proc.encode_rows(
            _iot_rows([7, 8], [21.0, 22.0], [BASE + 2000] * 2),
            BASE + 2000)},
        BASE + 2000,
    )
    joined = datasets["Joined"]
    assert len(joined) == 1
    assert joined[0]["deviceId"] == 7
    assert joined[0]["temperature"] == 21.0
    assert joined[0]["windSpeed"] == 55.0
    # per-stream ingest metrics (multi-source observability)
    assert metrics["Input_DataXProcessedInput_Events_Count"] == 2.0
    assert metrics["Input_Weather_Events_Count"] == 0.0

    # batch 3 (+12 s): weather from batch 1 fell out of the 5 s window
    datasets, _ = proc.process_batch(
        {"default": proc.encode_rows(
            _iot_rows([7], [25.0], [BASE + 12000]), BASE + 12000)},
        BASE + 12000,
    )
    assert datasets["Joined"] == []


def test_two_stream_join_sharded_matches_single(tmp_path):
    from data_accelerator_tpu.compile.planner import TableData
    from data_accelerator_tpu.dist import make_mesh, row_sharding
    import jax

    rng = np.random.RandomState(3)
    n = 64
    iot = _iot_rows(
        rng.randint(1, 9, n).tolist(),
        rng.uniform(0, 40, n).round(2).tolist(),
        [BASE + 2000] * n,
    )
    wx = _wx_rows(
        rng.randint(1, 9, n).tolist(),
        rng.uniform(0, 80, n).round(2).tolist(),
        [BASE] * n,
    )

    def run(mesh):
        proc = FlowProcessor(
            _conf(tmp_path / ("m" if mesh else "s"), JOIN_TRANSFORM,
                  {"datax.job.process.batchcapacity": "64"}),
            output_datasets=["Joined"], mesh=mesh,
        )
        def place(t):
            if mesh is None:
                return t
            sh = row_sharding(mesh)
            return TableData(
                {k: jax.device_put(v, sh) for k, v in t.cols.items()},
                jax.device_put(t.valid, sh),
            )
        proc.process_batch(
            {"wx": place(proc.encode_rows(wx, BASE, source="wx"))}, BASE
        )
        d, _ = proc.process_batch(
            {"default": place(proc.encode_rows(iot, BASE + 2000))},
            BASE + 2000,
        )
        return sorted(
            (r["deviceId"], r["temperature"], r["windSpeed"])
            for r in d["Joined"]
        )

    single = run(None)
    sharded = run(make_mesh(8))
    assert single == sharded
    assert len(single) > 0  # the join actually matched across streams


def test_join_overflow_metric_and_configured_capacity(tmp_path):
    """process.joincapacity bounds join output; overflowing it surfaces
    as Output_<n>_JoinRowsDropped instead of silence (the claim in
    ops/join.py's docstring, now true)."""
    proc = FlowProcessor(
        _conf(tmp_path, JOIN_TRANSFORM,
              {"datax.job.process.joincapacity": "8"}),
        output_datasets=["Joined"],
    )
    # 8 IoT rows x 4 matching weather rows = 32 pairs > capacity 8
    proc.process_batch(
        {"wx": proc.encode_rows(
            _wx_rows([1] * 4, [50.0] * 4, [BASE] * 4), BASE, source="wx")},
        BASE,
    )
    # (the weather window goes by the rows' own time: it reads a second
    # once the batch is two past it, runtime/timewindow.py)
    datasets, metrics = proc.process_batch(
        {"default": proc.encode_rows(
            _iot_rows([1] * 8, [20.0] * 8, [BASE + 2000] * 8),
            BASE + 2000)},
        BASE + 2000,
    )
    assert len(datasets["Joined"]) == 8
    assert metrics["Output_Joined_Events_Count"] == 8.0
    assert metrics["Output_Joined_JoinRowsDropped"] == 24.0

    # within capacity: metric present and zero (the -1 sentinel is only
    # for outputs that track no join at all)
    datasets, metrics = proc.process_batch(
        {"default": proc.encode_rows(
            _iot_rows([1], [20.0], [BASE + 3000]), BASE + 3000)},
        BASE + 3000,
    )
    assert metrics["Output_Joined_JoinRowsDropped"] == 0.0


def test_maxgroups_conf_bounds_groupby_and_counts_drops(tmp_path):
    transform = (
        "--DataXQuery--\n"
        "Agg = SELECT deviceId, COUNT(*) AS Cnt "
        "FROM DataXProcessedInput GROUP BY deviceId\n"
    )
    proc = FlowProcessor(
        _conf(tmp_path, transform,
              {"datax.job.process.maxgroups": "4"}),
        output_datasets=["Agg"],
    )
    datasets, metrics = proc.process_batch(
        {"default": proc.encode_rows(
            _iot_rows(list(range(10)), [1.0] * 10, [BASE] * 10), BASE)},
        BASE,
    )
    assert len(datasets["Agg"]) == 4
    assert metrics["Output_Agg_GroupsDropped"] == 6.0


def test_unknown_source_rejected(tmp_path):
    proc = FlowProcessor(_conf(tmp_path, JOIN_TRANSFORM),
                         output_datasets=["Joined"])
    with pytest.raises(EngineException):
        proc.dispatch_batch(
            {"nosuch": proc.encode_rows([], BASE)}, BASE
        )


def test_window_target_validation(tmp_path):
    with pytest.raises(EngineException):
        FlowProcessor(_conf(
            tmp_path, JOIN_TRANSFORM,
            {"datax.job.process.timewindow.Nowhere_5seconds"
             ".windowduration": "5 seconds"},
        ))


# -- window-state checkpoint/restore --------------------------------------

WINAGG_TRANSFORM = (
    "--DataXQuery--\n"
    "WinAgg = SELECT deviceId, COUNT(*) AS Cnt "
    "FROM DataXProcessedInput_10seconds GROUP BY deviceId\n"
)


def _winagg_conf(tmp_path, extra=None):
    tmp_path.mkdir(parents=True, exist_ok=True)
    t = tmp_path / "flow.transform"
    t.write_text(WINAGG_TRANSFORM)
    d = {
        "datax.job.name": "WinCkpt",
        "datax.job.input.default.blobschemafile": IOT_SCHEMA,
        "datax.job.process.transform": str(t),
        "datax.job.process.timestampcolumn": "eventTimeStamp",
        "datax.job.process.watermark": "0 second",
        "datax.job.process.batchcapacity": "16",
        "datax.job.process.timewindow.DataXProcessedInput_10seconds"
        ".windowduration": "10 seconds",
    }
    d.update(extra or {})
    return SettingDictionary(d)


def test_window_state_survives_restart(tmp_path):
    """Kill/restart: a TIMEWINDOW aggregate spanning the restart counts
    rows from BEFORE the restart. Without the snapshot the ring re-zeroes
    and the count silently drops to 1."""
    ckpt = WindowStateCheckpointer(str(tmp_path / "ckpt"))

    proc1 = FlowProcessor(_winagg_conf(tmp_path / "a"),
                          output_datasets=["WinAgg"])
    proc1.process_batch(
        proc1.encode_rows(_iot_rows([5, 5], [1.0, 2.0], [BASE] * 2), BASE),
        BASE,
    )
    ckpt.save(proc1.snapshot_window_state())
    del proc1

    # "restart": a fresh processor restores the rings from disk
    proc2 = FlowProcessor(_winagg_conf(tmp_path / "b"),
                          output_datasets=["WinAgg"])
    snap = ckpt.load()
    assert snap is not None
    assert proc2.restore_window_state(snap)
    datasets, _ = proc2.process_batch(
        proc2.encode_rows(_iot_rows([5], [3.0], [BASE + 3000]), BASE + 3000),
        BASE + 3000,
    )
    agg = {r["deviceId"]: r["Cnt"] for r in datasets["WinAgg"]}
    # the 2 pre-restart rows; the post-restart row is stamped in its
    # batch's own second, which an event-time window (the timestamp
    # column comes from the payload: runtime/timewindow.py) reads once
    # the batch is two seconds past it
    assert agg[5] == 2

    # ...and eviction still works off the restored (rebased) timestamps:
    # at +12 s the 10 s window holds seconds +1 .. +10 — the two BASE
    # rows restored from the snapshot are out, the +3 s row remains
    datasets, _ = proc2.process_batch(
        proc2.encode_rows(_iot_rows([5], [4.0], [BASE + 12000]),
                          BASE + 12000),
        BASE + 12000,
    )
    agg = {r["deviceId"]: r["Cnt"] for r in datasets["WinAgg"]}
    assert agg[5] == 1


def test_window_state_restart_preserves_string_ids(tmp_path):
    """Ring columns hold dictionary ids; the snapshot carries the
    dictionary so a restarted process decodes restored ids to the SAME
    strings (a fresh dictionary would silently rebind them)."""
    str_schema = json.dumps({"type": "struct", "fields": [
        {"name": "site", "type": "string", "nullable": False, "metadata": {}},
        {"name": "eventTimeStamp", "type": "timestamp", "nullable": False,
         "metadata": {}},
    ]})
    transform = (
        "--DataXQuery--\n"
        "BySite = SELECT site, COUNT(*) AS Cnt "
        "FROM DataXProcessedInput_10seconds GROUP BY site\n"
    )

    def conf(sub):
        d = tmp_path / sub
        d.mkdir(parents=True, exist_ok=True)
        t = d / "flow.transform"
        t.write_text(transform)
        return SettingDictionary({
            "datax.job.name": "StrCkpt",
            "datax.job.input.default.blobschemafile": str_schema,
            "datax.job.process.transform": str(t),
            "datax.job.process.timestampcolumn": "eventTimeStamp",
            "datax.job.process.watermark": "0 second",
            "datax.job.process.batchcapacity": "16",
            "datax.job.process.timewindow.DataXProcessedInput_10seconds"
            ".windowduration": "10 seconds",
        })

    ckpt = WindowStateCheckpointer(str(tmp_path / "ckpt"))
    proc1 = FlowProcessor(conf("a"), output_datasets=["BySite"])
    rows = [{"site": s, "eventTimeStamp": BASE} for s in
            ["sea", "sea", "ams"]]
    proc1.process_batch(proc1.encode_rows(rows, BASE), BASE)
    ckpt.save(proc1.snapshot_window_state())
    del proc1

    proc2 = FlowProcessor(conf("b"), output_datasets=["BySite"])
    assert proc2.restore_window_state(ckpt.load())
    datasets, _ = proc2.process_batch(
        proc2.encode_rows(
            [{"site": "sea", "eventTimeStamp": BASE + 3000}], BASE + 3000
        ),
        BASE + 3000,
    )
    agg = {r["site"]: r["Cnt"] for r in datasets["BySite"]}
    # the restored rows under their own strings (the batch's own row is
    # read once the batch is two seconds past it)
    assert agg == {"sea": 2, "ams": 1}


def test_window_snapshot_rejected_on_shape_change(tmp_path):
    ckpt = WindowStateCheckpointer(str(tmp_path / "ckpt"))
    proc1 = FlowProcessor(_winagg_conf(tmp_path / "a"),
                          output_datasets=["WinAgg"])
    ckpt.save(proc1.snapshot_window_state())
    # restart with a different batch capacity -> different ring shape
    proc2 = FlowProcessor(
        _winagg_conf(tmp_path / "b",
                     {"datax.job.process.batchcapacity": "32"}),
        output_datasets=["WinAgg"],
    )
    assert proc2.restore_window_state(ckpt.load()) is False


def test_streaming_host_restores_window_state(tmp_path):
    """Host-level restart: the second StreamingHost picks the snapshot up
    from the checkpoint dir automatically and the windowed aggregate
    spans the restart."""
    from data_accelerator_tpu.runtime.host import StreamingHost
    from data_accelerator_tpu.runtime.sources import FileSource

    def write_events(name, rows):
        p = tmp_path / "in" / name
        os.makedirs(p.parent, exist_ok=True)
        with open(p, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")

    def conf(sub):
        return _winagg_conf(tmp_path / sub, {
            "datax.job.input.default.inputtype": "file",
            "datax.job.input.default.blobpathregex":
                str(tmp_path / "in" / "*.json"),
            "datax.job.input.default.eventhub.checkpointdir":
                str(tmp_path / "ckpt"),
            "datax.job.input.default.eventhub.checkpointinterval":
                "0 second",
            "datax.job.output.WinAgg.console.maxrows": "0",
        })

    import time as _time

    def just_past_a_seconds_edge():
        """Now, in ms, within 200 ms after a second's edge: a row stamped
        300 ms ago then lies in the second before the batch's own."""
        while _time.time() % 1 > 0.2:
            _time.sleep(0.02)
        return int(_time.time() * 1000)

    # the file's watermark is 0, upstream's default: rows stamped a few
    # hundred ms before their batch, on the other side of a second's
    # edge, are on time (a batch's rows came in over the interval before
    # its time: runtime/timewindow.py)
    host1 = StreamingHost(conf("h1"))
    now = just_past_a_seconds_edge()
    write_events("b1.json", _iot_rows([5, 5], [1.0, 2.0],
                                      [now - 300, now - 700]))
    metrics = host1.run_batch()
    host1.stop()
    assert metrics["Window_TooLate_Rows_Dropped"] == 0.0
    assert metrics["Window_Late_Rows"] == 0.0

    # an event-time window trails its batch: the first host's rows are
    # read once the batch is two seconds past theirs
    _time.sleep(2.1)
    host2 = StreamingHost(conf("h2"))
    assert host2.processor._slot_counter > 0  # snapshot restored
    write_events("b2.json", _iot_rows([5], [3.0],
                                      [int(_time.time() * 1000)]))
    collected = {}

    orig = host2.dispatcher.dispatch

    def capture(datasets, batch_time_ms):
        collected.update(datasets)
        return orig(datasets, batch_time_ms)

    host2.dispatcher.dispatch = capture
    host2.run_batch()
    host2.stop()
    agg = {r["deviceId"]: r["Cnt"] for r in collected["WinAgg"]}
    assert agg[5] == 2  # the rows the first host took, restored
