"""Tests for the runtime observability layer: latency histograms, span
tracing, JSONL flight-recorder rotation, the trace CLI, and the
Prometheus/health exposition surface."""

import contextlib
import io
import json
import os
import re
import threading
import urllib.request

import numpy as np
import pytest

from data_accelerator_tpu.obs import telemetry, tracing
from data_accelerator_tpu.obs.exposition import (
    HealthState,
    ObservabilityServer,
    render_prometheus,
)
from data_accelerator_tpu.obs.histogram import HistogramRegistry, LatencyHistogram
from data_accelerator_tpu.obs.store import MetricStore
from data_accelerator_tpu.obs.tracing import Tracer


class CaptureWriter(telemetry.TelemetryWriter):
    def __init__(self):
        self.records = []

    def write(self, record):
        self.records.append(record)


# -- histograms ------------------------------------------------------------

def test_histogram_buckets_and_counts():
    h = LatencyHistogram(buckets_ms=(1, 10, 100))
    for v in (0.5, 5, 50, 500):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 4
    assert snap["cumulative"] == [1, 2, 3, 4]  # le=1, le=10, le=100, +Inf
    assert snap["sum_ms"] == pytest.approx(555.5)


def test_histogram_percentile_matches_numpy():
    h = LatencyHistogram()
    rng = np.random.RandomState(7)
    samples = rng.lognormal(1.0, 1.0, 500)
    for s in samples:
        h.observe(s)
    for q in (50, 95, 99):
        assert h.percentile(q) == pytest.approx(
            float(np.percentile(samples, q))
        )


def test_histogram_window_is_bounded():
    h = LatencyHistogram(window=8)
    for i in range(100):
        h.observe(float(i))
    # window holds the last 8 samples (92..99); count keeps the total
    assert h.count == 100
    assert h.percentile(0) >= 92.0


def test_registry_keys_by_flow_and_stage():
    r = HistogramRegistry()
    r.observe("f1", "decode", 1.0)
    r.observe("f1", "sync", 2.0)
    r.observe("f2", "decode", 3.0)
    assert r.stages("f1") == ["decode", "sync"]
    assert r.percentile("f1", "decode", 50) == 1.0
    assert r.percentile("f2", "missing", 50) is None


# -- tracing ---------------------------------------------------------------

def test_span_tree_and_histogram_feed():
    w = CaptureWriter()
    t = telemetry.TelemetryLogger("app", [w])
    hist = HistogramRegistry()
    tracer = Tracer(t, histograms=hist, flow="F")
    ctx = tracer.begin("streaming/batch")
    with ctx.activate():
        with tracing.span("decode"):
            with tracing.span("inner"):
                pass
        with tracing.span("dispatch"):
            pass
    ctx.end(batchTime=123)
    spans = {r["name"]: r for r in w.records if r["type"] == "span"}
    assert set(spans) == {"streaming/batch", "decode", "inner", "dispatch"}
    root = spans["streaming/batch"]
    assert root["parent"] is None
    assert root["properties"]["batchTime"] == 123
    assert spans["decode"]["parent"] == root["span"]
    assert spans["inner"]["parent"] == spans["decode"]["span"]
    # every span observed into its stage histogram; the root's
    # "streaming/" prefix is stripped
    assert set(hist.stages("F")) == {"batch", "decode", "inner", "dispatch"}


def test_span_is_noop_without_active_trace():
    with tracing.span("decode"):  # must not raise nor emit
        pass
    assert tracing.current_trace() is None


def test_cross_thread_capture_and_record_since():
    w = CaptureWriter()
    tracer = Tracer(telemetry.TelemetryLogger("app", [w]))
    ctx = tracer.begin()
    ctx.mark("dispatch-done")
    results = []

    def worker(cap):
        with tracing.activated(cap):
            with tracing.span("sink/file"):
                results.append(tracing.current_trace() is ctx)

    with ctx.activate():
        with tracing.span("sinks"):
            cap = tracing.capture()
            th = threading.Thread(target=worker, args=(cap,))
            th.start()
            th.join()
    ctx.record_since("device-step", "dispatch-done")
    ctx.end()
    assert results == [True]
    spans = {r["name"]: r for r in w.records if r["type"] == "span"}
    # the worker's span parents under the "sinks" span, not the root
    assert spans["sink/file"]["parent"] == spans["sinks"]["span"]
    assert spans["device-step"]["durationMs"] >= 0


def test_disabled_tracer_still_feeds_histograms():
    w = CaptureWriter()
    hist = HistogramRegistry()
    tracer = Tracer(
        telemetry.TelemetryLogger("app", [w]), histograms=hist,
        flow="F", enabled=False,
    )
    ctx = tracer.begin()
    with ctx.span("decode"):
        pass
    ctx.end()
    assert not [r for r in w.records if r["type"] == "span"]
    assert hist.stages("F") == ["batch", "decode"]


# -- JSONL rotation --------------------------------------------------------

def test_jsonl_writer_rotates_at_cap(tmp_path):
    p = str(tmp_path / "t.jsonl")
    w = telemetry.JsonlWriter(p, max_bytes=400)
    t = telemetry.TelemetryLogger("app", [w])
    for i in range(40):
        t.track_event("e", {"i": i})
    assert os.path.exists(p + ".1")
    assert os.path.getsize(p) <= 400
    assert os.path.getsize(p + ".1") <= 400
    # both files still parse line-by-line; records were never split
    recs = []
    for path in (p + ".1", p):
        recs += [json.loads(ln) for ln in open(path).read().splitlines()]
    assert all(r["name"] == "e" for r in recs)
    # the most recent records survive rotation
    assert recs[-1]["properties"]["i"] == 39


def test_jsonl_writer_keeps_n_rotations(tmp_path):
    """Satellite: configurable rotation count — `.1` is the newest
    rotated segment, `.keep` the oldest still on disk."""
    p = str(tmp_path / "t.jsonl")
    w = telemetry.JsonlWriter(p, max_bytes=200, keep=3)
    t = telemetry.TelemetryLogger("app", [w])
    for i in range(200):
        t.track_event("e", {"i": i})
    assert os.path.exists(p + ".1")
    assert os.path.exists(p + ".3")
    assert not os.path.exists(p + ".4")  # oldest dropped, not shifted
    # ordering: .3 holds older records than .1 holds older than active
    def first_i(path):
        return json.loads(open(path).readline())["properties"]["i"]

    assert first_i(p + ".3") < first_i(p + ".1") < first_i(p)


def test_jsonl_writer_gzips_rotated_segments(tmp_path):
    import gzip

    p = str(tmp_path / "t.jsonl")
    w = telemetry.JsonlWriter(p, max_bytes=300, keep=2, compress=True)
    t = telemetry.TelemetryLogger("app", [w])
    for i in range(120):
        t.track_event("e", {"i": i})
    assert os.path.exists(p + ".1.gz")
    assert not os.path.exists(p + ".1")
    # the active file stays plain text (tail/grep keep working)
    assert open(p).readline().startswith("{")
    with gzip.open(p + ".1.gz", "rt") as f:
        assert json.loads(f.readline())["name"] == "e"


def test_rotation_never_loses_in_progress_batch_spans(tmp_path):
    """Satellite acceptance: a batch whose spans straddle one or more
    rotations still reconstructs completely — rotation renames whole
    files, and the trace reader stitches every segment (gz included)."""
    from data_accelerator_tpu.obs.__main__ import find_traces, load_spans

    p = str(tmp_path / "t.jsonl")
    # cap small enough that a single batch's spans straddle several
    # rotations; keep sized so retention covers the whole batch
    w = telemetry.JsonlWriter(p, max_bytes=700, keep=12, compress=True)
    t = telemetry.TelemetryLogger("app", [w])
    tracer = Tracer(t)
    ctx = tracer.begin("streaming/batch")
    n_children = 24
    with ctx.activate():
        for i in range(n_children):
            with tracing.span(f"stage-{i:02d}"):
                pass
    ctx.end(batchTime=42)
    assert os.path.exists(p + ".1.gz")  # rotation actually happened
    spans = load_spans(p)
    mine = [s for s in spans if s["trace"] == ctx.trace_id]
    assert len(mine) == n_children + 1  # every span survived
    assert find_traces(spans, "42") == [ctx.trace_id]


# -- trace CLI -------------------------------------------------------------

def test_trace_cli_reconstructs_span_tree(tmp_path, capsys):
    from data_accelerator_tpu.obs.__main__ import main as obs_main

    p = str(tmp_path / "t.jsonl")
    t = telemetry.TelemetryLogger("app", [telemetry.JsonlWriter(p)])
    tracer = Tracer(t)
    ctx = tracer.begin("streaming/batch")
    with ctx.activate():
        with tracing.span("decode"):
            pass
        with tracing.span("collect"):
            with tracing.span("materialize"):
                pass
    ctx.end(batchTime=1700000000123)

    rc = obs_main(["trace", "1700000000123", "--file", p])
    out = capsys.readouterr().out
    assert rc == 0
    assert "streaming/batch" in out
    assert "├─ decode" in out
    assert "└─ materialize" in out
    # trace-id lookup works too
    assert obs_main(["trace", ctx.trace_id, "--file", p]) == 0
    # unknown batch id fails with the known ids listed
    assert obs_main(["trace", "999", "--file", p]) == 1
    assert "1700000000123" in capsys.readouterr().err


# -- Prometheus rendering --------------------------------------------------

PROM_LINE = re.compile(
    r"^(#.*|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [-+]?[0-9.eE+]+)$"
)


def test_render_prometheus_is_valid_text_format():
    hist = HistogramRegistry(buckets_ms=(1, 10))
    hist.observe("My Flow", "decode", 0.5)
    hist.observe("My Flow", "decode", 5.0)
    store = MetricStore()
    store.add_point('DATAX-F:Input_Events_Count', 1000, 7)
    store.zadd("DATAX-F:Alert", 1000.0, json.dumps({"Pivot1": "x"}))
    health = HealthState(flow="My Flow")
    health.record_batch(123, ok=True, latency_ms=5.0)
    text = render_prometheus(hist, store, health)
    for line in text.strip().splitlines():
        assert PROM_LINE.match(line), line
    assert 'datax_stage_latency_ms_bucket{flow="My Flow",stage="decode",le="1"} 1' in text
    assert 'datax_stage_latency_ms_bucket{flow="My Flow",stage="decode",le="+Inf"} 2' in text
    assert 'datax_stage_latency_ms_count{flow="My Flow",stage="decode"} 2' in text
    assert 'datax_metric_last_value{app="DATAX-F",metric="Input_Events_Count"} 7' in text
    # detail-event members (JSON rows) are not gauges and must be skipped
    assert "Alert" not in text
    assert 'datax_batches_processed_total{flow="My Flow"} 1' in text


# -- health/readiness ------------------------------------------------------

def _get(port, path):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10
        ) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_observability_server_probes():
    health = HealthState(flow="F", batch_interval_s=1.0)
    srv = ObservabilityServer(health, HistogramRegistry(), MetricStore(), port=0)
    srv.start()
    try:
        status, body = _get(srv.port, "/healthz")
        assert status == 200 and body["status"] == "ok"
        # not ready before the first batch
        status, body = _get(srv.port, "/readyz")
        assert status == 503 and "no batch processed yet" in body["reasons"]
        health.record_batch(1000, ok=True, latency_ms=4.2)
        status, body = _get(srv.port, "/readyz")
        assert status == 200 and body["ready"]
        # a failed batch flips readiness off and healthz to degraded
        health.record_batch(2000, ok=False, error="boom")
        status, body = _get(srv.port, "/readyz")
        assert status == 503 and any("boom" in r for r in body["reasons"])
        status, body = _get(srv.port, "/healthz")
        assert status == 200 and body["status"] == "degraded"
        # /metrics serves the Prometheus content type
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=10
        ) as r:
            assert r.status == 200
            assert "text/plain" in r.headers.get("Content-Type", "")
    finally:
        srv.stop()


def test_checkpoint_staleness_gates_readiness():
    health = HealthState(flow="F", checkpoint_interval_s=0.01)
    health.record_batch(1000, ok=True)
    health.record_checkpoint()
    import time as _time

    _time.sleep(0.05)  # > 3x the 10ms interval
    reasons = health.readiness()
    assert any("checkpoint stale" in r for r in reasons)


# -- one clock, one tree: annotations, the uncovered stretches, stages --------
def test_a_span_opens_a_trace_annotation_of_its_name(monkeypatch):
    """Every ``_child`` span is a ``dx/<name>`` TraceAnnotation for its
    duration (a capture then holds the host's stages on the device's
    clock); ``record``/``record_since`` spans, whose ends are seen at two
    call sites, get none."""
    import jax

    seen = []

    class Note:
        def __init__(self, name, **stats):
            self.name, self.stats = name, stats

        def __enter__(self):
            seen.append(("enter", self.name, self.stats))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Note)
    w = CaptureWriter()
    tracer = Tracer(telemetry.TelemetryLogger("app", [w]), flow="f")
    ctx = tracer.begin("streaming/batch")
    ctx.add(batchTime=1234)
    with ctx.activate():
        with tracing.span("decode"):
            with tracing.span("source-poll"):
                pass
    ctx.mark("m")
    ctx.record_since("device-step", "m")
    ctx.end()
    assert [s[:2] for s in seen] == [
        ("enter", "dx/decode"), ("enter", "dx/source-poll"),
        ("exit", "dx/source-poll"), ("exit", "dx/decode")]
    assert seen[0][2] == {"batch": 1234}
    with tracing.annotation("pace"):
        pass
    assert seen[-2][:2] == ("enter", "dx/pace")
    # the annotation ends before the span's record is written
    names = [r["name"] for r in w.records if r["type"] == "span"]
    assert names == ["source-poll", "decode", "device-step",
                     "streaming/batch"]


def test_spans_stay_plain_where_jax_is_not_loaded(monkeypatch):
    """The control plane must not pay a jax import for its spans: a
    process that never imported jax can hold no capture."""
    import sys

    monkeypatch.delitem(sys.modules, "jax")
    assert isinstance(tracing.annotation("x"), contextlib.nullcontext)
    w = CaptureWriter()
    tracer = Tracer(telemetry.TelemetryLogger("app", [w]), flow="f")
    ctx = tracer.begin("rest/x")
    with ctx.span("admission"):
        pass
    ctx.end()
    assert "jax" not in sys.modules
    assert [r["name"] for r in w.records] == ["admission", "rest/x"]


@pytest.mark.parametrize("python, level", [(False, 0), (True, 1)])
def test_profiler_surface_leaves_the_python_tracer_off(
        tmp_path, monkeypatch, python, level):
    import jax

    from data_accelerator_tpu.obs.profiler import ProfilerSurface

    calls = []
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda path, profiler_options=None: calls.append(profiler_options))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    surface = ProfilerSurface(str(tmp_path), flow="f")
    got = surface.start(seconds=60, python=python)
    surface.stop()
    assert got["python"] is python and "error" not in got
    assert calls[0].python_tracer_level == level
    assert calls[0].host_tracer_level == 2


def test_profile_endpoint_and_cli_pass_python(monkeypatch):
    from data_accelerator_tpu.obs import __main__ as cli

    asked = []

    class Surface:
        captures_count = 0

        def start(self, seconds, python=False):
            asked.append((seconds, python))
            return {"path": "p", "seconds": seconds, "python": python}

    srv = ObservabilityServer(HealthState(flow="f"), port=0,
                              profiler=Surface())
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        assert cli.main(["profile", base, "--seconds", "2"]) == 0
        assert cli.main(["profile", base, "--seconds", "3", "--python"]) == 0
    finally:
        srv.stop()
    assert asked == [(2.0, False), (3.0, True)]


@pytest.fixture(scope="module")
def home_batches(tmp_path_factory):
    """The benchmark's HomeAutomation deployment in this process at a
    2,048-row width, its socket fed 5,000 events before the first poll:
    three batches, their span records and end events."""
    import socket
    import time

    from benchmark import run as bench, served, traffic
    from data_accelerator_tpu.core.confmanager import ConfigManager
    from data_accelerator_tpu.runtime.host import StreamingHost

    run_dir = str(tmp_path_factory.mktemp("home"))
    cell = bench.load_cell("homeautomation.paced")
    port = served.free_port()
    conf_path = served.write_conf(run_dir, cell["config"], 2048, port, None)
    ConfigManager.reset()
    ConfigManager.get_configuration_from_arguments([f"conf={conf_path}"])
    host = StreamingHost(ConfigManager.load_config())
    src = next(iter(host.sources.values()))
    sent = 5000
    try:
        with socket.create_connection(("127.0.0.1", port), 5.0) as conn:
            conn.sendall(traffic.EventStream(cell["flow"], 7).render(
                0, sent, time.time()))
        deadline = time.time() + 30
        while src.buffered_rows < sent and time.time() < deadline:
            time.sleep(0.01)
        assert src.buffered_rows == sent
        for _ in range(3):
            host.run_batch()
    finally:
        host.stop()
        ConfigManager.reset()
    with open(os.path.join(run_dir, "telemetry.jsonl"), encoding="utf-8") as f:
        records = [json.loads(line) for line in f]
    traces = {}
    for r in records:
        if r.get("type") == "span":
            traces.setdefault(r["trace"], []).append(r)
    ends = [r for r in records if r.get("name") == "streaming/batch/end"]
    return {"traces": list(traces.values()), "ends": ends, "sent": sent,
            "host": host}


def _by_name(spans):
    return {s["name"]: s for s in spans}


def test_the_four_uncovered_stretches_have_spans(home_batches):
    for spans in home_batches["traces"]:
        by = _by_name(spans)
        root = by["streaming/batch"]["span"]
        assert by["source-poll"]["parent"] == by["decode"]["span"]
        assert by["native-decode"]["parent"] == by["decode"]["span"]
        assert by["source-poll"]["durationMs"] \
            + by["native-decode"]["durationMs"] <= by["decode"]["durationMs"]
        assert by["emit"]["parent"] == root
        assert by["sync"]["parent"] == root
        # the counts read is the one `sync` span: no child repeats it
        assert not [n for n in by if n.startswith("sync-")]
        end = lambda s: s["startTs"] + s["durationMs"] / 1000.0  # noqa: E731
        assert end(by["sinks"]) <= by["emit"]["startTs"] + 1e-3
        if "checkpoint" in by:
            assert end(by["emit"]) <= by["checkpoint"]["startTs"] + 1e-3
    assert any("checkpoint" in _by_name(t) for t in home_batches["traces"])


def test_compile_spans_name_the_programs_of_the_first_batch(home_batches):
    """One ``compile`` span a program jax compiled or loaded, under the
    batch that paid for it: the first batch compiles the step."""
    first = [s for s in home_batches["traces"][0] if s["name"] == "compile"]
    assert first, "the first batch recorded no compile span"
    root = _by_name(home_batches["traces"][0])["streaming/batch"]["span"]
    assert all(s["parent"] == root for s in first)
    assert any("step" in s["properties"]["fn"] for s in first), [
        s["properties"] for s in first]
    assert {s["properties"]["cache"] for s in first} <= {"hit", "miss", None}
    counted = home_batches["ends"][0]["measurements"]
    assert len([s for s in first if s["properties"]["cache"]]) == int(
        counted.get("Compile_Cache_Hit_Count", 0)
        + counted.get("Compile_Cache_Miss_Count", 0))


def test_source_backlog_rows_is_rows_sent_less_rows_polled(home_batches):
    polled = 0
    for e in home_batches["ends"]:
        # (the loop halves its poll after the compiling first batch)
        polled += e["measurements"]["Input_DataXProcessedInput_Events_Count"]
        assert e["measurements"]["Source_Backlog_Rows"] \
            == home_batches["sent"] - polled
    assert home_batches["ends"][0]["measurements"]["Source_Backlog_Rows"] > 0
    from data_accelerator_tpu.constants import MetricName

    assert MetricName.is_runtime_metric("Source_Backlog_Rows")
    # the receive buffer's own counter rides beside it: whatever the
    # 5,000 lines cost on the way in, a poll that only cuts costs none
    assert MetricName.is_runtime_metric("Source_Buffer_Grow_Count")
    assert home_batches["ends"][-1]["measurements"][
        "Source_Buffer_Grow_Count"] == 0


def test_every_operation_of_the_step_lies_under_a_stage_scope(home_batches):
    """``build_step_fn`` names its stages on the device: every operation
    of the lowered HomeAutomation step carries a ``dx.<stage>`` scope in
    its location (metadata only: the text without locations, which the
    compile manifest fingerprints, holds no scope name)."""
    proc = home_batches["host"].processor
    lowered = proc._step.lower(*proc._step_input_avals())
    plain = lowered.as_text()
    assert "dx." not in plain
    text = lowered.as_text(debug_info=True)
    locs = dict(re.findall(r"^(#loc\d+) = (.*)$", text, flags=re.M))

    def resolve(ref, depth=0):
        body = locs.get(ref, ref)
        if depth < 8:
            for inner in re.findall(r"#loc\d+", body):
                body += resolve(inner, depth + 1)
        return body

    # an operation's location stands at the end of its last line (a
    # scatter or sort closes its region there); constants are hoisted
    # out of their scope and arguments have none
    # (helpers jax lowers once and calls from several stages, ``_where``
    # or ``cumsum``, keep one body: the call in @main carries the scope)
    main = text[text.index("func.func public @main"):]
    main = main[:main.index("func.func private")]
    ops = [ln for ln in main.splitlines()
           if re.search(r"loc\(#loc\d*\)$", ln)
           and re.match(r"\s+(%\S+ = |\}\) |\"?stablehlo\.)", ln)
           and not re.search(r"stablehlo\.(constant|return)|func\.", ln)]
    assert len(ops) > 50
    bare = [ln.strip()[:120] for ln in ops
            if "dx." not in resolve(ln[ln.rfind("loc(") + 4:-1])]
    assert not bare, bare[:5]
    scopes = set(re.findall(r"dx\.[A-Za-z]+(?:\.[A-Za-z0-9_]+)?", text))
    # the sample's window is held as per-slot partial aggregates (its
    # one reader is a decomposable GROUP BY): no ring, the fold and the
    # combine under their own scopes
    scopes |= set(re.findall(r"dx\.window\.[a-z]+", text))
    assert {"dx.project.default", "dx.window.partial", "dx.window.combine",
            "dx.view.HeatAvg", "dx.view.OpenDoors", "dx.compact.OpenDoors",
            "dx.compact.HeatAvg", "dx.counts"} <= scopes
    assert "dx.ring" not in scopes


@pytest.fixture(scope="module")
def v5e_chip():
    """One described (not attached) v5e chip to compile for; made inside
    the fixture, so collecting this file loads no TPU library."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_for(proc, v5e_chip) -> str:
    import jax

    assert jax.config.jax_traceback_in_locations_limit == 1
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip),
        proc._step_input_avals())
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return jax.jit(proc._step_fn).lower(*avals).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


@pytest.fixture(scope="module")
def v5e_step_text(home_batches, v5e_chip):
    """The HomeAutomation step (2,048-row width, 4,096 group slots; the
    window held as 6 slots of partial aggregates) compiled for the v5e
    under the settings the host runs with; the optimized program's
    text."""
    return _compiled_for(home_batches["host"].processor, v5e_chip)


@pytest.fixture(scope="module")
def raw_ring_processor(home_batches, tmp_path_factory):
    """The same deployment with one more statement that reads the
    window's rows: the planner then keeps the raw-row ring (a 12,288-row
    ring at this width) and ``HeatAvg`` is the sort-based GROUP BY over
    it."""
    from data_accelerator_tpu.core.config import SettingDictionary
    from data_accelerator_tpu.runtime.processor import FlowProcessor

    conf = dict(home_batches["host"].processor.dict.dict)
    with open(conf["datax.job.process.transform"], encoding="utf-8") as f:
        text = f.read()
    path = tmp_path_factory.mktemp("rawring") / "flow.transform"
    path.write_text(
        text + "--DataXQuery--\nWarmRows = SELECT deviceId, temperature "
        "FROM DataXProcessedInput_5seconds WHERE temperature > 99\n")
    conf["datax.job.process.transform"] = str(path)
    proc = FlowProcessor(SettingDictionary(conf))
    assert "DataXProcessedInput" in proc.ring_slots
    assert not proc.window_states
    return proc


@pytest.fixture(scope="module")
def v5e_raw_ring_step_text(raw_ring_processor, v5e_chip):
    return _compiled_for(raw_ring_processor, v5e_chip)


def test_the_tpu_compiler_keeps_the_stage_in_op_name(v5e_step_text):
    """What a device trace shows is the compiled program's ``op_name``.
    Compiled for the v5e under the settings the host runs with
    (``PersistentCompileCache.enable``: metadata in the cache key, one
    frame a location), the step's instructions still carry their
    ``dx.<stage>`` scope. (``jax_include_full_tracebacks_in_locations``
    off loses it: ``op_name`` then reads ``scatter-add`` alone.)"""
    text = v5e_step_text
    named = re.findall(r'op_name="([^"]*)"', text)
    assert len(named) > 100
    scoped = [n for n in named if "/dx." in n]
    assert len(scoped) >= 0.75 * len(named), (len(scoped), len(named))
    custom = [n for n in re.findall(
        r'fusion\([^\n]*kind=kCustom[^\n]*op_name="([^"]*)"', text)]
    assert custom and all("/dx." in n for n in custom), custom[:3]
    assert any("dx.window.partial" in n for n in custom)


def test_the_window_partials_sort_a_batch_not_the_window(
        home_batches, v5e_step_text):
    """The sample's window held as per-slot partial aggregates: in the
    program the v5e runs, the largest sort is the one over the batch's
    rows (its width, once to group them and once to pack their groups to
    the front); the merge with the key directory and the view's key order
    sort groups, not rows. Nothing sorts the window (slots x width) and
    nothing under the view's own scope sorts at all."""
    text = v5e_step_text
    proc = home_batches["host"].processor
    state = proc.window_states["HeatAvg"]
    width, slots, groups = 2048, 6, 4096
    assert (state.slots, state.groups) == (slots, groups)
    sorts = [(int(n), ln) for ln in text.splitlines()
             for n in re.findall(r"= \(?\w+\[(\d+)\][^=]* sort\(%", ln)[:1]]
    in_window = [(n, ln) for n, ln in sorts
                 if "dx.window.partial" in ln or "dx.window.combine" in ln]
    assert len(in_window) >= 4, sorts
    by_rows = {n for n, _ln in in_window}
    # the batch (2,048), the directory (4,096), directory + batch groups
    assert width in by_rows
    assert by_rows <= {width, groups, groups + min(width, groups)}, by_rows
    assert max(n for n, _ln in sorts) < slots * width
    assert not [ln for _n, ln in sorts if "dx.view.HeatAvg" in ln]
    # at the deployment's shapes (groups <= width / 2) every one of them
    # is at most the batch's width: the 262,144 rows of a batch against
    # 131,072 + 131,072
    assert 131_072 + min(262_144, 131_072) <= 262_144


@pytest.fixture(scope="module")
def eventtime_processor(tmp_path_factory):
    """The benchmark's event-time deployment (PR 34: the sample's
    5-minute window on the sensors' own clock, 10 s watermark) at a
    2,048-row width and 4,096 group slots: 312 slots of partial
    aggregates, a slot a second of event time."""
    from benchmark import run as bench, served
    from data_accelerator_tpu.core.config import SettingDictionary
    from data_accelerator_tpu.runtime.processor import FlowProcessor

    run_dir = str(tmp_path_factory.mktemp("eventtime"))
    cell = bench.load_cell("homeautomation-5m-eventtime.paced")
    conf_path = served.write_conf(run_dir, cell["config"], 2048,
                                  served.free_port(), None)
    with open(conf_path, encoding="utf-8") as f:
        conf = dict(ln.rstrip("\n").split("=", 1) for ln in f if "=" in ln)
    conf["datax.job.process.projection"] = conf[
        "datax.job.process.projection"].replace("\\n", "\n")
    conf["datax.job.process.maxgroups"] = "4096"
    proc = FlowProcessor(SettingDictionary(conf))
    state = proc.window_states["HeatAvg"]
    assert (state.slots, state.groups, state.clock.lag) == (312, 4096, 11)
    assert not proc.ring_slots
    return proc


def test_the_event_time_fold_sorts_a_batch_and_writes_twelve_slot_rows(
        eventtime_processor, v5e_chip):
    """An event-time window's fold in the program the v5e runs: the
    sorts are the batch's rows (by key and second) and the key
    directory's groups, as for a processing-time window; nothing sorts,
    gathers or scatters the state's 312 x 4,096 cells: what is indexed by
    row is the batch (each (second, key) total to its place in the 12 x
    4,096 block of the seconds a batch can reach: its own, the one
    before and the watermark's 10), and those 12 slot rows are read and
    written as rows."""
    text = _compiled_for(eventtime_processor, v5e_chip)
    width, slots, groups, touched = 2048, 312, 4096, 12
    sorts = [(int(n), ln) for ln in text.splitlines()
             for n in re.findall(r"= \(?\w+\[(\d+)\][^=]* sort\(%", ln)[:1]]
    in_window = {n for n, ln in sorts
                 if "dx.window.partial" in ln or "dx.window.combine" in ln}
    assert width in in_window
    assert in_window <= {width, groups, groups + min(width, groups)}
    shapes = {name: [int(d) for d in dims.split(",") if d]
              for name, dims in re.findall(
                  r"^\s*(?:ROOT )?(%[\w.-]+) = \w+\[([\d,]*)\]", text, re.M)}
    indexed = []
    for ln in text.splitlines():
        m = re.search(r" (gather|scatter)\((%[\w.-]+), (%[\w.-]+)", ln)
        if m and "dx.window.partial" in ln:
            indexed.append((m.group(1), shapes[m.group(2)],
                            shapes[m.group(3)][0]))
    assert indexed
    # no operand a gather or scatter walks is the whole state, and none
    # takes more indices than the batch has rows or the directory groups
    assert all(operand != [slots, groups] or n <= touched
               for _k, operand, n in indexed), indexed
    assert max(n for _k, _o, n in indexed) <= max(width, groups), indexed
    assert ("scatter", [touched * groups], width) in indexed


def test_the_group_by_gathers_and_scatters_by_group_not_by_row(
        raw_ring_processor, v5e_raw_ring_step_text):
    """Over a raw-row ring ``dx.view.HeatAvg`` groups the whole ring (6
    slots x the batch width) into 4,096 slots. In the program the v5e
    runs, the ring's rows go through the view's sort, and no gather or
    scatter under the view's scope takes an index a row: the sort carries
    the columns, segments are reduced in place and read at 4,096 (+ 1)
    positions."""
    text = v5e_raw_ring_step_text
    proc = raw_ring_processor
    rows = 6 * 2048
    slots = 4096
    view = {v.name: v for v in proc.pipeline.views}["HeatAvg"]
    assert (view.plan.input_rows, view.capacity) == (rows, slots)
    shapes = {name: [int(d) for d in dims.split(",") if d]
              for name, dims in re.findall(
                  r"^\s*(?:ROOT )?(%[\w.-]+) = \w+\[([\d,]*)\]", text, re.M)}
    in_view = [ln for ln in text.splitlines()
               if 'op_name="' in ln and "dx.view.HeatAvg" in ln]
    sorts = [ln for ln in in_view if re.search(r"[\])] sort\(%", ln)]
    assert sorts and all(f"[{rows}]" in ln for ln in sorts), sorts
    indexed = []
    for ln in in_view:
        m = re.search(r" (gather|scatter)\((%[\w.-]+), (%[\w.-]+)", ln)
        if m:
            n_indices = shapes[m.group(3)][0]
            indexed.append((m.group(1), n_indices))
    # the view still reads its groups' rows and writes nothing by index
    assert {k for k, _n in indexed} == {"gather"}
    assert max(n for _k, n in indexed) <= slots + 1 < rows, indexed
    # the new operations are the view's own: the scan's passes carry
    # its scope like the sort does
    fusions = [ln for ln in text.splitlines() if re.search(r" fusion\(", ln)
               and 'op_name="' in ln]
    assert sum("dx.view.HeatAvg" in ln for ln in fusions) >= 10
