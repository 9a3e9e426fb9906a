"""Compile-surface analyzer (DX6xx) + AOT manifest tests.

- golden fixtures per DX6xx code under tests/data/flows/ (DX602/DX603
  are comparison codes: their fixtures are clean flows the tests tamper
  a freshly derived manifest against)
- manifest == lowering byte-exactness (the ``test_deviceplan.py``
  pattern): the statically emitted manifest equals the entries a REAL
  ``FlowProcessor`` derives from its live device state — entry set,
  aval signatures, donation patterns AND StableHLO lowering digests
- warm-vs-cold ``FlowProcessor`` init through the FULL generation path
  (designer gui → S100–S900 → flat conf → processor): a warm start
  performs zero first-dispatch step compiles; a post-warm signature the
  manifest never promised fires the DX604 runtime counterpart
  (``Compile_WarmMiss_Count``)
- persistent compilation cache: misses on first start, hits on
  restart, shared through a real ``objstore://`` store
- CLI ``--compile``/``--all`` + REST ``"compile"``/``"all"`` parity
- tier-1 self-lint: every shipped scenario/baseline flow passes
  ``--compile`` clean, and its manifest holds one entry, the step
"""

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from data_accelerator_tpu.analysis import (
    CODES,
    SEV_ERROR,
    SEV_WARNING,
    analyze_flow,
    analyze_flow_compile,
    analyze_processor_compile,
)
from data_accelerator_tpu.analysis.compilecheck import check_manifest
from data_accelerator_tpu.core.config import SettingDictionary
from data_accelerator_tpu.runtime.processor import FlowProcessor, pack_raw
from data_accelerator_tpu.serve.scenarios import shipped_flow_guis

FLOWS_DIR = os.path.join(os.path.dirname(__file__), "data", "flows")


def load_flow(name: str) -> dict:
    with open(os.path.join(FLOWS_DIR, name + ".json")) as f:
        return json.load(f)


def clean_flow_paths():
    return sorted(
        os.path.join(FLOWS_DIR, f)
        for f in os.listdir(FLOWS_DIR)
        if f.startswith("clean_") and f.endswith(".json")
    )


def conf_for_gui(gui: dict, extra: dict = None) -> SettingDictionary:
    """A runnable flat conf equivalent to a single-source fixture gui —
    the same lowering inputs config generation would produce, so the
    static (gui) and runtime (conf) analysis paths must agree."""
    from data_accelerator_tpu.compile.codegen import CodegenEngine
    from data_accelerator_tpu.serve.flowbuilder import RuleDefinitionGenerator

    proc = gui["process"]
    rc = CodegenEngine().generate_code(
        "\n".join(proc["queries"]),
        RuleDefinitionGenerator().generate(gui.get("rules") or [],
                                           gui["name"]),
        gui["name"],
        windowable_tables={"DataXProcessedInput"},
    )
    conf = {
        "datax.job.name": gui["name"],
        "datax.job.input.default.blobschemafile":
            gui["input"]["properties"]["inputSchemaFile"],
        "datax.job.input.default.streaming.intervalinseconds": "1",
        "datax.job.process.timestampcolumn": proc.get("timestampColumn", ""),
        "datax.job.process.watermark": proc.get("watermark", "0 second"),
        "datax.job.process.projection":
            gui["input"]["properties"].get("normalizationSnippet", "Raw.*"),
        "datax.job.process.transform": rc.code,
        "datax.job.process.batchcapacity": str(
            (proc.get("jobconfig") or {}).get("jobBatchCapacity") or 65536
        ),
    }
    for wname, dur in rc.time_windows.items():
        conf[f"datax.job.process.timewindow.{wname}.windowduration"] = dur
    for tables, _sink in rc.outputs:
        for t in tables.split(","):
            conf[f"datax.job.output.{t.strip()}.metric"] = "enabled"
    conf.update(extra or {})
    return SettingDictionary(conf)


# ---------------------------------------------------------------------------
# golden fixtures (imported by test_analysis's registry-coverage test)
# ---------------------------------------------------------------------------
COMPILE_GOLDEN = [
    ("dx600_open_surface", "DX600", SEV_WARNING),
    ("dx602_manifest_donation", "DX602", SEV_ERROR),
    ("dx603_manifest_drift", "DX603", SEV_ERROR),
    ("dx690_lowering_failure", "DX690", SEV_ERROR),
    ("dx691_unavailable", "DX691", SEV_WARNING),
]

# codes that need a shipped manifest to compare against — their
# fixtures are clean flows; the golden test tampers the manifest
_COMPARISON_CODES = {"DX602", "DX603"}


@pytest.mark.parametrize("fixture,code,severity", COMPILE_GOLDEN,
                         ids=[g[0] for g in COMPILE_GOLDEN])
def test_golden_compile_diagnostic(fixture, code, severity):
    flow = load_flow(fixture)
    # compile-tier-only findings: the semantic tier stays clean
    assert analyze_flow(flow).errors == []
    if code in _COMPARISON_CODES:
        fresh = analyze_flow_compile(flow)
        assert fresh.ok and fresh.manifest is not None
        tampered = copy.deepcopy(fresh.manifest)
        if code == "DX602":
            # donation pattern lies: step claims nothing donated
            tampered["entries"][0]["donate"] = []
        else:
            # aval drift: one leaf shape altered
            tampered["entries"][0]["avals"]["leaves"][0][0][0] += 1
        report = analyze_flow_compile(flow, manifest=tampered)
    else:
        report = analyze_flow_compile(flow)
    hits = [d for d in report.diagnostics if d.code == code]
    assert hits, f"expected {code}, got {report.codes()}"
    assert hits[0].severity == severity
    assert hits[0].severity == CODES[code][0]
    assert report.ok == (severity != SEV_ERROR)


def test_golden_compile_clean_twins():
    """Each bad fixture's minimal fix analyzes clean/stable again."""
    # DX600's twin: the same flow without the interval-refreshing UDF
    flow = load_flow("dx600_open_surface")
    twin = copy.deepcopy(flow)
    twin["process"]["functions"] = []
    twin["process"]["queries"] = [
        "--DataXQuery--\nScaled = SELECT deviceId, temperature AS t2 "
        "FROM DataXProcessedInput;\nOUTPUT Scaled TO Metrics;"
    ]
    report = analyze_flow_compile(twin)
    assert report.diagnostics == [] and report.stable


def test_dx600_message_names_the_refresh_udf():
    report = analyze_flow_compile(load_flow("dx600_open_surface"))
    hits = [d for d in report.diagnostics if d.code == "DX600"]
    assert hits and "scaleby" in hits[0].message
    assert not report.stable
    assert report.manifest is not None  # initial surface still ships
    assert report.manifest["stable"] is False


# ---------------------------------------------------------------------------
# manifest == lowering byte-exactness (the DX603 contract)
# ---------------------------------------------------------------------------
def test_manifest_matches_runtime_lowering_byte_exact():
    """The statically emitted manifest equals what a real FlowProcessor
    derives from its live device state — entries, avals, donation AND
    lowering digests — because both sides share build_step_fn and
    step_compile_entry. Asserted on the DX603 fixture flow (a
    windowed group-by, i.e. donated rings in play)."""
    flow = load_flow("dx603_manifest_drift")
    static = analyze_flow_compile(flow)
    assert static.ok and static.stable

    proc = FlowProcessor(conf_for_gui(flow))
    runtime = analyze_processor_compile(proc)
    s = {e["entry"]: e for e in static.entries}
    r = {e["entry"]: e for e in runtime.entries}
    assert set(s) == set(r) == {"step"}
    for name in s:
        for field in ("donate", "static", "avals", "loweringDigest"):
            assert s[name][field] == r[name][field], (name, field)

    # the static manifest checks drift-free against the runtime surface
    assert analyze_processor_compile(proc, manifest=static.manifest).ok

    # ...and a capacity change IS drift (DX603), caught both ways
    changed = copy.deepcopy(flow)
    changed["process"]["jobconfig"]["jobBatchCapacity"] = "8192"
    drifted = analyze_flow_compile(changed, manifest=static.manifest)
    assert "DX603" in drifted.codes() and not drifted.ok
    diags = []
    check_manifest(static.manifest, analyze_flow_compile(changed).entries,
                   diags)
    assert any(d.code == "DX603" for d in diags)


@pytest.mark.parametrize("chips", [None, 4], ids=["one-chip", "mesh4"])
def test_a_socket_flows_manifest_carries_the_packed_matrix_on_both_layouts(
    chips
):
    """The raw form follows the input type alone (``source_raw_form``,
    the one definition the analyzer and the runtime share): a socket
    flow's manifest entry carries the PackedRaw aval, and a processor of
    that flow derives the same entry on one chip and under a mesh — no
    DX603 drift for a four-chip job."""
    flow = copy.deepcopy(load_flow("dx603_manifest_drift"))
    flow["input"]["type"] = "socket"
    static = analyze_flow_compile(flow)
    assert static.ok and static.stable
    (entry,) = static.entries
    assert "PackedRaw" in entry["avals"]["tree"]
    extra = {"datax.job.input.default.inputtype": "socket"}
    if chips:
        extra["datax.job.process.numchips"] = str(chips)
    proc = FlowProcessor(conf_for_gui(flow, extra))
    assert (proc.mesh.size if chips else proc.mesh) == chips
    (runtime,) = proc.derive_compile_entries()
    assert runtime["avals"] == entry["avals"]
    assert runtime["donate"] == entry["donate"]
    assert analyze_processor_compile(proc, manifest=static.manifest).ok
    # the local twin of the flow keeps a leaf a column
    local = analyze_flow_compile(load_flow("dx603_manifest_drift"))
    assert "PackedRaw" not in local.entries[0]["avals"]["tree"]


def test_step_entry_records_ring_donation_contract():
    from data_accelerator_tpu.runtime.processor import STEP_DONATE_ARGNUMS

    report = analyze_flow_compile(load_flow("dx603_manifest_drift"))
    (step,) = report.entries
    assert step["entry"] == "step"
    assert step["donate"] == list(STEP_DONATE_ARGNUMS)
    # the entry carries the deployable coordinates
    assert step["cacheKey"] and step["loweringDigest"]
    assert step["avals"]["leaves"]


# ---------------------------------------------------------------------------
# tier-1 self-lint: shipped flows must ship precompilable
# ---------------------------------------------------------------------------
def _baseline_flow(name: str) -> dict:
    if name.endswith(".json"):
        with open(os.path.join(FLOWS_DIR, name)) as f:
            return json.load(f)
    return next(g for g in shipped_flow_guis() if g.get("name") == name)


@pytest.mark.parametrize("flow", [
    "probe-deploy", *map(os.path.basename, clean_flow_paths()),
])
def test_compile_surface_is_the_step_alone(flow):
    """Every shipped scenario flow AND every clean baseline-mirror
    fixture passes ``--compile`` with zero error diagnostics, and the
    manifest it emits holds one entry: a flow dispatches its step and
    no other program, so there is nothing else to warm or to miss."""
    report = analyze_flow_compile(_baseline_flow(flow))
    assert report.errors == [], [d.render() for d in report.errors]
    assert [e["entry"] for e in report.manifest["entries"]] == ["step"]
    assert report.compile_dict()["entries"] == 1


# ---------------------------------------------------------------------------
# runtime half: warm-vs-cold init through the FULL generation path
# ---------------------------------------------------------------------------
@pytest.fixture
def generated_conf(tmp_path):
    """gui → S100–S900 → flat conf (with the S630 compile block) →
    parsed SettingDictionary + the raw text."""
    from test_serve_generation import make_gui

    from data_accelerator_tpu.core.config import parse_conf_lines
    from data_accelerator_tpu.serve.generation import RuntimeConfigGeneration
    from data_accelerator_tpu.serve.storage import (
        LocalDesignTimeStorage,
        LocalRuntimeStorage,
    )

    design = LocalDesignTimeStorage(str(tmp_path / "design"))
    runtime = LocalRuntimeStorage(str(tmp_path / "runtime"))
    gen = RuntimeConfigGeneration(design, runtime)
    gui = make_gui("CompileWarm")
    design.save({"name": gui["name"], "gui": gui})
    res = gen.generate(gui["name"])
    assert res.ok, res.errors
    text = open(res.conf_paths[0]).read()
    return SettingDictionary(parse_conf_lines(text.splitlines())), text


def test_generation_embeds_manifest_and_cache_conf(generated_conf):
    conf, text = generated_conf
    mpath = conf.get("datax.job.process.compile.manifest")
    assert mpath and os.path.exists(mpath)
    manifest = json.loads(open(mpath).read())
    assert manifest["flow"] == "CompileWarm"
    assert [e["entry"] for e in manifest["entries"]].count("step") == 1
    # the cache directory is not conf: every host resolves it itself
    assert "compile.cachedir" not in text


def test_warm_init_performs_no_first_dispatch_compile(generated_conf):
    """The acceptance bit: with the generated manifest present, init
    AOT-compiles everything; the first REAL dispatch adds no step
    trace, no warm-miss, no manifest drift. Cold (manifest stripped),
    the same conf pays its first step compile at dispatch."""
    conf, _text = generated_conf
    rows = [{
        "deviceDetails": {"deviceId": 1, "deviceType": "DoorLock",
                          "homeId": 150, "status": 0,
                          "temperature": 20.0},
        "eventTimeStamp": 1_700_000_000_000,
    }]

    cold_dict = {
        k: v for k, v in conf.dict.items()
        if not k.startswith("datax.job.process.compile.")
    }
    cold = FlowProcessor(SettingDictionary(cold_dict))
    assert not cold._aot_warmed and cold._step_cache_size() == 0
    cold.process_batch(
        cold.encode_rows(rows, 1_700_000_000_000),
        batch_time_ms=1_700_000_000_000,
    )
    assert cold._step_cache_size() == 1  # first dispatch compiled

    warm = FlowProcessor(SettingDictionary(dict(conf.dict)))
    assert warm._aot_warmed and warm.compile_manifest is not None
    mark = warm._warm_step_mark
    assert mark and mark >= 1  # init compiled the step
    _d, m = warm.process_batch(
        warm.encode_rows(rows, 1_700_000_000_000),
        batch_time_ms=1_700_000_000_000,
    )
    assert warm._step_cache_size() == mark  # zero dispatch compiles
    assert "Compile_WarmMiss_Count" not in m
    assert "Compile_ManifestDrift_Count" not in m
    assert m["Compile_ColdStart_Ms"] > 0


def test_warm_miss_fires_dx604_counter(generated_conf):
    """A post-warm dispatch with a trace signature the manifest never
    promised (the packed raw form on a local-input flow) compiles at
    dispatch — the missed warm promise surfaces as
    Compile_WarmMiss_Count (DX604's runtime face)."""
    conf, _text = generated_conf
    warm = FlowProcessor(SettingDictionary(dict(conf.dict)))
    spec = warm.specs[warm.primary]
    np_cols = {
        c: np.zeros(
            spec.capacity,
            {"double": np.float32, "boolean": np.bool_}.get(t, np.int32),
        )
        for c, t in spec.raw_schema.types.items()
    }
    packed = pack_raw(np_cols, np.zeros(spec.capacity, np.bool_))
    _d, m = warm.process_batch(packed, batch_time_ms=1_700_000_000_000)
    assert m.get("Compile_WarmMiss_Count", 0) >= 1


@pytest.fixture
def fresh_cache_dir(tmp_path, monkeypatch):
    """Point this process's compile cache at an empty directory for one
    test (the suite's own cache is warm from earlier runs, so nothing
    would miss), and put jax back afterwards. Moving the directory is
    what the engine itself never does; a test of cold-vs-warm has to."""
    import jax
    from jax._src import compilation_cache

    from data_accelerator_tpu.compile.aotcache import CACHE_DIR_ENV

    before = jax.config.jax_compilation_cache_dir

    def point_at(path):
        monkeypatch.setenv(CACHE_DIR_ENV, path)
        jax.config.update("jax_compilation_cache_dir", path)
        compilation_cache.reset_cache()
        return path

    yield lambda name: point_at(str(tmp_path / name))
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_persistent_cache_hits_across_restarts(
    generated_conf, fresh_cache_dir,
):
    """A second init against the same cache directory deserializes
    instead of compiling: jax's own cache events (the
    Compile_Cache_{Hit,Miss}_Count metrics) show misses on the first
    start, hits and no miss on the restart."""
    conf, _text = generated_conf
    fresh_cache_dir("cc")
    rows = [{
        "deviceDetails": {"deviceId": 1, "deviceType": "Heating",
                          "homeId": 150, "status": 1,
                          "temperature": 50.0},
        "eventTimeStamp": 1_700_000_000_000,
    }]
    p1 = FlowProcessor(SettingDictionary(dict(conf.dict)))
    _d, m1 = p1.process_batch(
        p1.encode_rows(rows, 1_700_000_000_000),
        batch_time_ms=1_700_000_000_000,
    )
    assert m1["Compile_Cache_Miss_Count"] > 0
    p2 = FlowProcessor(SettingDictionary(dict(conf.dict)))
    _d, m2 = p2.process_batch(
        p2.encode_rows(rows, 1_700_000_000_000),
        batch_time_ms=1_700_000_000_000,
    )
    assert m2["Compile_Cache_Hit_Count"] >= m1["Compile_Cache_Miss_Count"]
    assert m2["Compile_Cache_Miss_Count"] == 0
    assert m2["Compile_ColdStart_Ms"] < m1["Compile_ColdStart_Ms"]


def test_compile_cache_routes_through_objstore(tmp_path, fresh_cache_dir):
    """cacheurl = objstore:// prefix: the first processor pushes its
    compiles to the shared store; a replica whose local directory is
    EMPTY pulls them back (the preemption-recovery / scale-out path)
    and compiles nothing. The replica resolves the SAME directory path
    (as every host of one deployment does): jax hashes the directory
    into its cache key, so entries carried to another path never hit."""
    import shutil

    from jax._src import compilation_cache

    from data_accelerator_tpu.serve.objectstore import (
        ObjectStoreClient,
        ObjectStoreServer,
    )

    store = ObjectStoreServer(port=0, root=str(tmp_path / "store")).start()
    try:
        client = ObjectStoreClient(store.endpoint)
        url = client.url_for("flows/CacheFlow/compilecache")
        flow = load_flow("dx602_manifest_donation")
        manifest = analyze_flow_compile(flow, digests=False).manifest
        extra = {
            "datax.job.process.compile.manifest": json.dumps(manifest),
            "datax.job.process.compile.cacheurl": url,
        }
        cache_dir = fresh_cache_dir("cc")
        p1 = FlowProcessor(conf_for_gui(flow, extra))
        assert p1._aot_warmed
        keys = client.list("flows/CacheFlow/compilecache")
        assert keys, "warm pushed no cache entries to the store"
        shutil.rmtree(cache_dir)  # the replica's disk starts empty
        compilation_cache.reset_cache()
        p2 = FlowProcessor(conf_for_gui(flow, extra))
        pulled = [
            f for f in os.listdir(cache_dir) if not f.endswith("-atime")
        ]
        assert len(pulled) >= len(keys)
        hits, misses = p2._compile_cache.take_counts()
        assert hits > 0 and misses == 0
    finally:
        store.stop()


def test_cache_dir_comes_from_the_environment_or_the_checkout(monkeypatch):
    """One resolver: JAX_COMPILATION_CACHE_DIR when set — and then no
    code of ours touches jax's cache-directory config — else
    <checkout>/.jax_cache. No path to it goes through tempfile."""
    import inspect

    import jax

    from data_accelerator_tpu.compile import aotcache

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv(aotcache.CACHE_DIR_ENV)
    assert aotcache.resolve_cache_dir() == os.path.join(checkout, ".jax_cache")
    assert aotcache.PersistentCompileCache().dir == aotcache.resolve_cache_dir()

    armed = jax.config.jax_compilation_cache_dir  # conftest's env value
    monkeypatch.setenv(aotcache.CACHE_DIR_ENV, armed)
    assert aotcache.resolve_cache_dir() == armed
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updates.append(k), real_update(k, v)),
    )
    aotcache.PersistentCompileCache().enable()
    assert "jax_compilation_cache_dir" not in updates
    assert jax.config.jax_compilation_cache_dir == armed

    assert "tempfile" not in inspect.getsource(aotcache)
    with open(os.path.join(checkout, "tests/conftest.py"),
              encoding="utf-8") as f:
        src = f.read()
    assert "compile.cachedir" not in src and "dxtpu-jax-cache" not in src


# ---------------------------------------------------------------------------
# CLI + REST surfaces
# ---------------------------------------------------------------------------
def _run_cli(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("PYTHONPATH", os.path.dirname(os.path.dirname(__file__)))
    return subprocess.run(
        [sys.executable, "-m", "data_accelerator_tpu.analysis", *args],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(__file__)),
    )


def test_cli_compile_zero_exit_on_clean_config():
    path = os.path.join(FLOWS_DIR, "dx603_manifest_drift.json")
    r = _run_cli(["--compile", path])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "compile surface:" in r.stdout and "stable" in r.stdout


def test_cli_compile_nonzero_on_lowering_error():
    path = os.path.join(FLOWS_DIR, "dx690_lowering_failure.json")
    r = _run_cli(["--compile", path])
    assert r.returncode == 1
    assert "DX690" in r.stdout


def test_cli_compile_manifest_roundtrip(tmp_path):
    """--manifest-out writes the artifact; --manifest= checks it
    drift-free (exit 0) and a tampered copy drifts (exit 1, DX602)."""
    path = os.path.join(FLOWS_DIR, "dx602_manifest_donation.json")
    out = str(tmp_path / "m.json")
    assert _run_cli(["--compile", f"--manifest-out={out}", path]).returncode == 0
    manifest = json.loads(open(out).read())
    assert manifest["manifestVersion"] >= 1
    assert _run_cli(["--compile", f"--manifest={out}", path]).returncode == 0
    manifest["entries"][0]["donate"] = []
    bad = str(tmp_path / "bad.json")
    json.dump(manifest, open(bad, "w"))
    r = _run_cli(["--compile", f"--manifest={bad}", path])
    assert r.returncode == 1 and "DX602" in r.stdout


def test_cli_all_runs_every_tier_merged():
    path = os.path.join(FLOWS_DIR, "dx603_manifest_drift.json")
    r = _run_cli(["--all", "--json", path])
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(r.stdout)
    # fleet wraps per-file reports; one schemaVersion at top level
    assert out["schemaVersion"] >= 1
    f = out["files"][0]
    assert {"device", "udfs", "compile", "diagnostics"} <= set(f)
    assert f["compile"]["entries"] == len(f["compile"]["manifest"]["entries"])


def test_cli_unknown_flag_still_rejected():
    path = os.path.join(FLOWS_DIR, "dx603_manifest_drift.json")
    assert _run_cli(["--compiel", path]).returncode == 2


@pytest.fixture
def flow_ops(tmp_path):
    from test_serve_jobs import FakeJobClient

    from data_accelerator_tpu.serve.flowservice import FlowOperation
    from data_accelerator_tpu.serve.storage import (
        LocalDesignTimeStorage,
        LocalRuntimeStorage,
    )

    return FlowOperation(
        LocalDesignTimeStorage(str(tmp_path / "design")),
        LocalRuntimeStorage(str(tmp_path / "runtime")),
        job_client=FakeJobClient(),
    )


def test_validate_endpoint_compile_and_all(flow_ops):
    from data_accelerator_tpu.serve.restapi import DataXApi

    api = DataXApi(flow_ops)
    flow = load_flow("dx603_manifest_drift")
    status, out = api.dispatch(
        "POST", "api/flow/validate", body={"flow": flow, "compile": True}
    )
    assert status == 200
    r = out["result"]
    assert r["ok"] and r["compile"]["stable"]
    # endpoint == CLI: same manifest for the same flow
    cli = analyze_flow_compile(flow)
    assert r["compile"]["manifest"]["entries"] == [
        e for e in cli.manifest["entries"]
    ]
    # a tampered shipped manifest reaches DX603 through the endpoint
    bad = copy.deepcopy(cli.manifest)
    bad["entries"][0]["avals"]["leaves"][0][0][0] += 1
    status, out = api.dispatch(
        "POST", "api/flow/validate",
        body={"flow": flow, "compile": True, "compileManifest": bad},
    )
    assert status == 200 and not out["result"]["ok"]
    codes = {d["code"] for d in out["result"]["diagnostics"]}
    assert "DX603" in codes
    # "all": true merges every tier into one report
    status, out = api.dispatch(
        "POST", "api/flow/validate", body={"flow": flow, "all": True}
    )
    assert status == 200
    assert {"device", "udfs", "fleet", "compile"} <= set(out["result"])
