"""Roofline time-model conformance (PR 12): machine-profile
calibration (determinism, persistence, objstore sharing), the latency
closed forms and their report/runtime surfaces, the DX520/DX521/DX522
drift trios (clean / drifting / missing model, mirroring the DX501
tests), histogram exemplars, the on-demand profiler surface, and the
`obs spans --aggregate` flame table."""

import json
import os
import urllib.request

import pytest

from data_accelerator_tpu.core.config import SettingDictionary
from data_accelerator_tpu.obs import calibrate
from data_accelerator_tpu.obs.conformance import (
    ConformanceModel,
    ConformanceMonitor,
    DRIFT_CODES,
)

SCHEMA = json.dumps({
    "type": "struct",
    "fields": [
        {"name": "k", "type": "long", "nullable": False, "metadata": {}},
        {"name": "v", "type": "double", "nullable": False, "metadata": {}},
    ],
})


def _run(monitor, metrics, n):
    gauges, all_events = None, []
    for i in range(n):
        gauges, events = monitor.observe(dict(metrics), 1000 + i)
        all_events += events
    return gauges, all_events


# -- calibration -------------------------------------------------------------

def test_calibration_deterministic_within_band():
    """Two calibrations of the same machine agree within a generous
    band (best-of-N probes shrug off scheduler noise; the DX520 band
    itself is 10x, so a <3x calibration wobble cannot flip a verdict
    on its own)."""
    a = calibrate.calibrate()
    b = calibrate.calibrate()
    for field in (
        "hbm_read_gbps", "hbm_write_gbps", "flops_gflops",
        "dispatch_overhead_us", "d2h_gbps",
    ):
        va, vb = getattr(a, field), getattr(b, field)
        assert va > 0 and vb > 0, field
        assert max(va, vb) / min(va, vb) < 3.0, (field, va, vb)
    assert a.backend == b.backend == "cpu"
    assert a.probe_ms > 0


def test_profile_file_roundtrip(tmp_path):
    p = calibrate.calibrate()
    path = str(tmp_path / "profile.json")
    calibrate.save_profile(p, path)
    loaded = calibrate.load_profile(path)
    assert loaded is not None
    assert loaded.to_dict() == p.to_dict()
    assert calibrate.load_profile(str(tmp_path / "nope.json")) is None
    # garbage file -> None, not a crash
    (tmp_path / "bad.json").write_text("{not json")
    assert calibrate.load_profile(str(tmp_path / "bad.json")) is None


@pytest.fixture
def store(tmp_path):
    from data_accelerator_tpu.serve.objectstore import ObjectStoreServer

    srv = ObjectStoreServer(root=str(tmp_path / "store")).start()
    yield srv
    srv.stop()


def test_profile_objstore_roundtrip(store, tmp_path, monkeypatch):
    """A calibrated profile pushes to the shared store and a peer with
    the same backend+device pulls it instead of re-probing (the
    compile-cache sharing pattern applied to the machine model)."""
    url = f"objstore://127.0.0.1:{store.port}/fleet/calib"
    p = calibrate.calibrate()
    p.probe_ms = 123.456  # distinctive marker: a pull, not a re-probe
    assert calibrate.push_shared(url, p)
    pulled = calibrate.pull_shared(url, p.backend, p.device_kind)
    assert pulled is not None and pulled.probe_ms == 123.456
    # get_profile prefers the shared copy over re-calibrating (and
    # persists it locally); reset the process cache to force the path
    monkeypatch.setattr(calibrate, "_cached", None)
    local = str(tmp_path / "calib.json")
    got = calibrate.get_profile(cache_file=local, share_url=url)
    assert got.probe_ms == 123.456
    assert calibrate.load_profile(local).probe_ms == 123.456
    # a dead store degrades to live calibration, never a crash
    monkeypatch.setattr(calibrate, "_cached", None)
    got2 = calibrate.get_profile(
        share_url="objstore://127.0.0.1:1/fleet/calib"
    )
    assert got2.probe_ms != 123.456


def test_mismatched_cached_profile_recalibrates(tmp_path, monkeypatch):
    """A cached profile for another backend/device (or probe version)
    is ignored — stale machine constants must never price another
    machine's roofline."""
    stale = calibrate.MachineProfile(
        backend="tpu", device_kind="v5e", hbm_read_gbps=819.0,
        hbm_write_gbps=819.0, flops_gflops=1e6,
        dispatch_overhead_us=1.0, d2h_gbps=8.0, probe_ms=777.0,
    )
    local = str(tmp_path / "calib.json")
    calibrate.save_profile(stale, local)
    monkeypatch.setattr(calibrate, "_cached", None)
    got = calibrate.get_profile(cache_file=local)
    assert got.backend == "cpu" and got.probe_ms != 777.0


# -- latency closed forms ----------------------------------------------------

def _profile_dict(**over):
    base = {
        "backend": "cpu", "device_kind": "cpu",
        "hbm_read_gbps": 10.0, "hbm_write_gbps": 10.0,
        "flops_gflops": 100.0, "dispatch_overhead_us": 100.0,
        "d2h_gbps": 1.0, "ici_gbps": 2.0,
    }
    base.update(over)
    return base


def test_stage_time_ms_is_a_roofline():
    from data_accelerator_tpu.analysis.costmodel import stage_time_ms

    prof = _profile_dict()
    # memory-bound: 10 MB at 10 GB/s = 1 ms >> flop term
    assert stage_time_ms(10e6, 1e3, prof) == pytest.approx(1.0)
    # compute-bound: 1 GFLOP at 100 GFLOP/s = 10 ms >> byte term
    assert stage_time_ms(1e3, 1e9, prof) == pytest.approx(10.0)
    # the slower of read/write streams prices the memory term
    slow_write = _profile_dict(hbm_write_gbps=1.0)
    assert stage_time_ms(10e6, 0, slow_write) == pytest.approx(10.0)


def test_latency_model_block_and_stage_predictions():
    from data_accelerator_tpu.analysis.costmodel import (
        latency_model,
        stage_latency_predictions,
    )

    stages = [
        {"name": "a", "kind": "project", "hbmBytes": 10e6, "flops": 1e3},
        {"name": "b", "kind": "group", "hbmBytes": 1e3, "flops": 1e9},
    ]
    totals = {"d2hBytesPerBatch": 2e6, "iciWireBytesPerBatch": 4e6}
    lm = latency_model(stages, totals, _profile_dict(), "calibrated")
    assert lm["profileSource"] == "calibrated"
    assert [s["computeMs"] for s in lm["stages"]] == [
        pytest.approx(1.0), pytest.approx(10.0)
    ]
    t = lm["totals"]
    assert t["computeMs"] == pytest.approx(11.0)
    assert t["dispatchOverheadMs"] == pytest.approx(0.1)
    assert t["deviceStepMs"] == pytest.approx(11.1)
    assert t["d2hMs"] == pytest.approx(2.0)
    assert t["iciMs"] == pytest.approx(2.0)
    assert t["batchMs"] == pytest.approx(15.1)
    preds = stage_latency_predictions(lm)
    assert preds == {
        "device-step": pytest.approx(11.1), "collect": pytest.approx(2.0)
    }
    # no ici link -> no ici term, still a valid block
    lm2 = latency_model(stages, totals, _profile_dict(ici_gbps=None))
    assert lm2["totals"]["iciMs"] is None


def test_device_report_carries_latency_model_and_flops():
    """The --device report (and thus the designer Validate cost table)
    carries a latencyModel block, and the conf-embedded runtime model
    now ships per-stage FLOPs — the DX520 inputs."""
    from data_accelerator_tpu.analysis import analyze_flow_device
    from data_accelerator_tpu.serve.scenarios import probe_deploy_gui

    report = analyze_flow_device(probe_deploy_gui())
    assert report.stages
    plan = report.plan_dict()
    lm = plan["latencyModel"]
    assert lm["profileSource"] == "default"
    assert lm["totals"]["batchMs"] > 0
    assert len(lm["stages"]) == len(plan["stages"])
    rt = report.runtime_model()
    assert rt["totals"]["flops"] and rt["totals"]["flops"] > 0
    assert any(s.get("flops") for s in rt["stages"])
    # the embedded model + a calibrated profile price into predictions
    model = ConformanceModel.from_json(json.dumps(rt))
    preds, compute_ms, overhead_ms = model.latency_predictions(
        _profile_dict()
    )
    assert preds["device-step"] > 0
    assert compute_ms >= 0 and overhead_ms == pytest.approx(0.1)


def test_mesh_report_latency_model():
    from data_accelerator_tpu.analysis.meshcheck import MeshPlanReport

    report = MeshPlanReport(flow="f", chips=8, stages=[], diagnostics=[])
    lm = report.latency_model(_profile_dict())
    assert lm["iciGBps"] == 2.0
    assert lm["totals"]["iciMs"] == pytest.approx(0.0)
    assert "latencyModel" in report.mesh_dict()


# -- DX520: stage-time drift (clean / drifting / missing) --------------------

def test_clean_stage_times_stay_silent():
    mon = ConformanceMonitor(ConformanceModel(), warmup=2, window=4)
    mon.set_latency(
        {"device-step": 10.0, "collect": 2.0},
        compute_ms=9.0, overhead_ms=1.0,
    )
    gauges, events = _run(
        mon,
        {"Latency-DeviceStep-p50": 25.0, "Latency-Collect-p50": 3.0},
        8,
    )
    assert events == []  # 2.5x and 1.5x sit inside the 10x band
    assert gauges["Conformance_StageTime_DeviceStep_Ratio"] == \
        pytest.approx(2.5)
    assert gauges["Conformance_StageTime_Collect_Ratio"] == \
        pytest.approx(1.5)


def test_stage_time_drift_fires_dx520_once_and_rearms():
    mon = ConformanceMonitor(ConformanceModel(), warmup=2, window=4)
    mon.set_latency({"device-step": 2.0}, compute_ms=1.9, overhead_ms=0.1)
    fired = []
    for i in range(6):
        _, events = mon.observe({"Latency-DeviceStep-p50": 50.0}, i)
        fired += events
    assert [e.code for e in fired] == ["DX520"]
    ev = fired[0]
    assert ev.metric == "Latency-DeviceStep-p50"
    assert ev.ratio == pytest.approx(25.0)
    assert ev.to_props()["name"] == "stage-time-drift"
    assert "DX520" in DRIFT_CODES
    # recovery re-arms; a later episode fires a fresh event
    for i in range(4):
        _, events = mon.observe({"Latency-DeviceStep-p50": 5.0}, 10 + i)
        assert not events
    _, events = _run(mon, {"Latency-DeviceStep-p50": 80.0}, 4)
    assert [e.code for e in events] == ["DX520"]
    assert mon.drift_count == 2


def test_missing_latency_model_disables_dx520_silently():
    mon = ConformanceMonitor(
        ConformanceModel(d2h_bytes_per_batch=1000.0), warmup=1, window=4
    )
    gauges, events = _run(
        mon,
        {"Transfer_D2HBytes": 950.0, "Latency-DeviceStep-p50": 1e9},
        8,
    )
    assert events == []
    assert not any(k.startswith("Conformance_StageTime") for k in gauges)


def test_sub_floor_predictions_decline_to_judge():
    """A sub-millisecond roofline prediction means host fixed costs
    dominate the observation; the check exports the ratio gauge but
    never fires — unless the prediction was explicitly pinned."""
    mon = ConformanceMonitor(ConformanceModel(), warmup=1, window=4)
    mon.set_latency({"collect": 0.001}, 0.0, 0.0)
    gauges, events = _run(mon, {"Latency-Collect-p50": 55.0}, 6)
    assert events == []
    assert gauges["Conformance_StageTime_Collect_Ratio"] > 1000
    pinned = ConformanceMonitor(ConformanceModel(), warmup=1, window=4)
    pinned.set_latency({"collect": 0.001}, pinned=True)
    _, events = _run(pinned, {"Latency-Collect-p50": 55.0}, 6)
    assert [e.code for e in events] == ["DX520"]


# -- DX521: dispatch-overhead-dominated --------------------------------------

def test_overhead_bound_model_fires_dx521_not_dx520():
    mon = ConformanceMonitor(ConformanceModel(), warmup=2, window=4)
    # the model says the step is all fixed dispatch cost
    mon.set_latency(
        {"device-step": 1.1}, compute_ms=0.1, overhead_ms=1.0
    )
    _, events = _run(mon, {"Latency-DeviceStep-p50": 50.0}, 6)
    assert [e.code for e in events] == ["DX521"]
    assert events[0].to_props()["name"] == "dispatch-overhead-dominated"
    assert "per-dispatch fixed" in events[0].message
    # a compute-bound model with the same drift is plain DX520
    mon2 = ConformanceMonitor(ConformanceModel(), warmup=2, window=4)
    mon2.set_latency(
        {"device-step": 1.1}, compute_ms=1.0, overhead_ms=0.1
    )
    _, events = _run(mon2, {"Latency-DeviceStep-p50": 50.0}, 6)
    assert [e.code for e in events] == ["DX520"]


# -- DX522: HBM footprint drift (clean / drifting / missing) -----------------

def test_clean_hbm_watermark_stays_silent():
    mon = ConformanceMonitor(
        ConformanceModel(hbm_bytes=1_000_000.0), warmup=2, window=4
    )
    gauges, events = _run(mon, {"Hbm_PeakBytes": 1_200_000.0}, 8)
    assert events == []  # 1.2x < the 1.5x band
    assert gauges["Conformance_Hbm_Ratio"] == pytest.approx(1.2)


def test_hbm_drift_fires_dx522_once_and_rearms():
    mon = ConformanceMonitor(
        ConformanceModel(hbm_bytes=1_000_000.0), warmup=2, window=2
    )
    fired = []
    for i in range(6):
        _, events = mon.observe({"Hbm_PeakBytes": 3_000_000.0}, i)
        fired += events
    assert [e.code for e in fired] == ["DX522"]
    assert fired[0].to_props()["name"] == "hbm-footprint-drift"
    assert fired[0].ratio == pytest.approx(3.0)
    for i in range(6):
        _, events = mon.observe({"Hbm_PeakBytes": 900_000.0}, 10 + i)
        assert not events
    _, events = _run(mon, {"Hbm_PeakBytes": 5_000_000.0}, 6)
    assert [e.code for e in events] == ["DX522"]
    assert mon.drift_count == 2


def test_missing_hbm_model_disables_dx522_silently():
    mon = ConformanceMonitor(ConformanceModel(), warmup=1, window=4)
    gauges, events = _run(mon, {"Hbm_PeakBytes": 1e15}, 8)
    assert events == []
    assert "Conformance_Hbm_Ratio" not in gauges


def test_latency_pin_parses_from_conf_and_survives_calibration():
    d = SettingDictionary({
        "datax.job.process.conformance.latency": json.dumps(
            {"device-step": 7.5}
        ),
    })
    mon = ConformanceMonitor.from_conf(d, flow="F")
    assert mon is not None  # a pin alone arms the monitor
    assert mon.latency == {"device-step": 7.5}
    assert mon.latency_pinned
    # the host's computed (non-pinned) predictions must not clobber it
    mon.set_latency({"device-step": 0.001}, 0.0, 0.0)
    assert mon.latency == {"device-step": 7.5}
    # garbage pin: ignored, monitor off (no model either)
    bad = SettingDictionary({
        "datax.job.process.conformance.latency": "{not json",
    })
    assert ConformanceMonitor.from_conf(bad) is None


# -- host acceptance ---------------------------------------------------------

def _host_conf(tmp_path, extra=None):
    from data_accelerator_tpu.obs.histogram import HISTOGRAMS

    HISTOGRAMS.clear()
    os.makedirs(tmp_path / "in", exist_ok=True)
    with open(tmp_path / "in" / "a.json", "w", encoding="utf-8") as f:
        for i in range(8):
            f.write(json.dumps({"k": i, "v": float(i)}) + "\n")
    t = tmp_path / "t.transform"
    t.write_text(
        "--DataXQuery--\nOut = SELECT k, v FROM DataXProcessedInput\n"
    )
    d = {
        "datax.job.name": "TimeModel",
        "datax.job.input.default.inputtype": "file",
        "datax.job.input.default.blobpathregex": str(
            tmp_path / "in" / "*.json"
        ),
        "datax.job.input.default.blobschemafile": SCHEMA,
        "datax.job.input.default.eventhub.maxrate": "100",
        "datax.job.input.default.streaming.intervalinseconds": "1",
        "datax.job.process.transform": str(t),
        "datax.job.process.batchcapacity": "16",
        "datax.job.output.Out.console.maxrows": "0",
    }
    d.update(extra or {})
    return SettingDictionary(d)


class _CaptureWriter:
    def write(self, record):
        self.records.append(record)

    def __init__(self):
        self.records = []


def test_injected_slowdown_fires_dx520_exactly_once(tmp_path):
    """Acceptance: a live host whose latency prediction is pinned far
    below reality fires DX520 exactly once (the transition), while the
    calibrated clean run of the same flow stays silent (covered for
    the shipped flow in test_conformance's clean-baseline run)."""
    from data_accelerator_tpu.runtime.host import StreamingHost

    host = StreamingHost(_host_conf(tmp_path, {
        "datax.job.process.conformance.latency": json.dumps(
            {"device-step": 0.0001}
        ),
        "datax.job.process.conformance.warmup": "1",
    }))
    cap = _CaptureWriter()
    host.telemetry.writers.append(cap)
    try:
        host.run(max_batches=6)
    finally:
        host.stop()
    drift = [r for r in cap.records
             if r.get("type") == "event"
             and r.get("name") == "conformance/drift"]
    assert [r["properties"]["code"] for r in drift] == ["DX520"]
    # the host also exported the machine profile as Calib_* gauges
    keys = host.metric_logger.store.keys("DATAX-TimeModel:")
    metrics = {k.partition(":")[2] for k in keys}
    assert "Calib_DispatchOverheadUs" in metrics
    assert "Conformance_StageTime_DeviceStep_Ratio" in metrics


def test_post_profile_on_live_host_writes_capture_into_batch_trace(
    tmp_path,
):
    """Acceptance: POST /profile?seconds=N on a live host's
    observability port arms a capture; the capture directory fills with
    a loadable jax trace and its path lands as a profiler/capture span
    in the batch trace plus the Profiler_Captures_Count series."""
    from data_accelerator_tpu.runtime.host import StreamingHost

    host = StreamingHost(_host_conf(tmp_path, {
        "datax.job.process.observability.port": "0",
        "datax.job.process.observability.profilerdir": str(
            tmp_path / "prof"
        ),
    }))
    cap = _CaptureWriter()
    host.telemetry.writers.append(cap)
    try:
        port = host.obs_server.port
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/profile?seconds=0.2",
            data=b"", method="POST",
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            payload = json.loads(r.read())
        assert payload["path"].startswith(str(tmp_path / "prof"))
        host.run_batch()
        import time as _time

        # wait out the capture window + the timer's stop_trace flush
        deadline = _time.time() + 10.0
        while host.profiler.captures_count == 0 \
                and _time.time() < deadline:
            _time.sleep(0.05)
        host.run_batch()  # drains the finished capture into this trace
        # GET reports the surface state
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/profile", timeout=10
        ) as r:
            state = json.loads(r.read())
        assert state["captures"] == 1
    finally:
        host.stop()
    spans = [r for r in cap.records if r.get("type") == "span"
             and r.get("name") == "profiler/capture"]
    assert spans and spans[0]["properties"]["path"] == payload["path"]
    files = []
    for _root, _d, fs in os.walk(payload["path"]):
        files += fs
    assert files, "profiler capture directory is empty"
    pts = host.metric_logger.store.points(
        "DATAX-TimeModel:Profiler_Captures_Count"
    )
    assert pts and pts[-1]["val"] == 1.0


def test_profile_endpoint_501_when_surface_disabled():
    """A host with the profiler surface conf'd OFF answers 501 — never
    an exception."""
    from data_accelerator_tpu.obs.exposition import (
        HealthState,
        ObservabilityServer,
    )

    srv = ObservabilityServer(HealthState(flow="f"), port=0, profiler=None)
    srv.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/profile", data=b"", method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(req, timeout=10)
        assert err.value.code == 501
        assert "disabled" in json.loads(err.value.read())["error"]
    finally:
        srv.stop()


def test_double_start_conflicts_and_stop_is_idempotent(tmp_path):
    from data_accelerator_tpu.obs.profiler import ProfilerSurface

    surface = ProfilerSurface(str(tmp_path / "p"), flow="f")
    res = surface.start(seconds=60)
    assert res.get("path")
    again = surface.start(seconds=60)
    assert "error" in again and again["path"] == res["path"]
    assert surface.stop() == res["path"]
    assert surface.stop() is None
    assert surface.captures_count == 1
    caps = surface.drain_finished()
    assert [c["path"] for c in caps] == [res["path"]]
    assert surface.drain_finished() == []


# -- histogram exemplars -----------------------------------------------------

def test_histogram_exemplar_tracks_window_max_trace():
    from data_accelerator_tpu.obs.histogram import LatencyHistogram

    hist = LatencyHistogram(window=4)
    assert hist.exemplar() is None
    hist.observe(5.0, trace_id="t-a")
    hist.observe(80.0, trace_id="t-spike")
    hist.observe(7.0, trace_id="t-b")
    ex = hist.exemplar()
    assert ex == {"ms": 80.0, "traceId": "t-spike"}
    # the spike ages out of the 4-sample window
    for i in range(4):
        hist.observe(1.0 + i, trace_id=f"t-{i}")
    assert hist.exemplar()["traceId"] == "t-3"


def test_metrics_exposition_carries_exemplar_trace_id():
    from data_accelerator_tpu.obs.exposition import render_prometheus
    from data_accelerator_tpu.obs.histogram import HistogramRegistry

    reg = HistogramRegistry()
    reg.observe("F", "device-step", 3.0, trace_id="abc-123")
    reg.observe("F", "device-step", 42.0, trace_id="def-456")
    text = render_prometheus(reg)
    line = next(
        ln for ln in text.splitlines()
        if 'le="+Inf"' in ln and 'stage="device-step"' in ln
    )
    assert '# {trace_id="def-456"} 42' in line
    # spans recorded through the tracer carry their trace id into the
    # exemplar automatically
    from data_accelerator_tpu.obs.tracing import Tracer

    reg2 = HistogramRegistry()
    tracer = Tracer(None, histograms=reg2, flow="F", enabled=False)
    ctx = tracer.begin("streaming/batch")
    with ctx.activate():
        from data_accelerator_tpu.obs import tracing

        with tracing.span("decode"):
            pass
    ctx.end()
    ex = reg2.get("F", "decode").exemplar()
    assert ex is not None and ex["traceId"] == ctx.trace_id


# -- obs spans --aggregate ---------------------------------------------------

def test_spans_aggregate_flame_table(tmp_path, capsys):
    from data_accelerator_tpu.obs.__main__ import main as obs_main

    path = str(tmp_path / "telemetry.jsonl")
    with open(path, "w", encoding="utf-8") as f:
        for i, (name, dur, trace) in enumerate([
            ("decode", 1.0, "t1"), ("decode", 3.0, "t2"),
            ("device-step", 10.0, "t1"), ("device-step", 30.0, "t2"),
            ("streaming/batch", 50.0, "t2"),
        ]):
            f.write(json.dumps({
                "type": "span", "name": name, "trace": trace,
                "span": str(i), "parent": None, "startTs": i,
                "durationMs": dur,
            }) + "\n")
        f.write(json.dumps({"type": "event", "name": "noise"}) + "\n")
    rc = obs_main(["spans", "--aggregate", "--file", path])
    out = capsys.readouterr().out
    assert rc == 0
    lines = out.splitlines()
    assert lines[0].startswith("stage")
    # sorted by total desc: batch 50 > device-step 40 > decode 4
    assert lines[1].split()[0] == "streaming/batch"
    assert lines[2].split()[0] == "device-step"
    assert "t2" in lines[2]  # the max observation's trace id
    rc = obs_main(["spans", "--aggregate", "--json", "--file", path])
    rows = json.loads(capsys.readouterr().out)
    ds = next(r for r in rows if r["stage"] == "device-step")
    assert ds["count"] == 2 and ds["totalMs"] == 40.0
    assert ds["p99Ms"] == pytest.approx(29.8)
    assert ds["maxTrace"] == "t2"


# -- HBM sampler hook --------------------------------------------------------

def test_device_memory_stats_posture(tmp_path):
    """The processor hook returns either None (backend without
    allocator stats — CPU) or a well-formed in-use/peak dict; the host
    turns it into the Hbm_* series only when present."""
    from data_accelerator_tpu.runtime.processor import FlowProcessor

    conf = _host_conf(tmp_path)
    proc = FlowProcessor(conf, output_datasets=["Out"])
    stats = proc.device_memory_stats()
    if stats is not None:
        assert stats["peak_bytes_in_use"] >= stats["bytes_in_use"] >= 0
