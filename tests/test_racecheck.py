"""Buffer-lifetime/concurrency analyzer tests (the --race tier, DX8xx)
and the runtime buffer sanitizer (DX805).

- golden fixtures: one bad/clean twin pair per DX80x code under
  tests/data/race/ — tiny modules written in the engine's idioms, each
  bad twin emitting EXACTLY its code, each clean twin silent
- dynamic ground truth: the DX800 bad twin poison-hits under a real
  PackedBufferPool with the sanitizer armed; the clean twin runs silent
- self-lint (the standing CI race gate): every ``runtime/``, ``lq/``
  and ``pilot/`` module analyzes DX8xx-clean
- the seeded PR 13 regression: dropping ``copy=True`` in
  ``snapshot_window_state`` (in a sandboxed copy) is caught by BOTH
  detectors — DX800/DX801 statically, a sanitizer poison-hit
  (snapshot-alias) dynamically
- sanitizer e2e: an armed FlowProcessor runs batches sanitizer-silent
  and exports Sanitizer_GuardedViews_Count
- CLI/REST contract: --race under the 0/1/2 exit contract (incl.
  exit-2 typo rejection), folded into --all, REST ``race: true``
  parity with the CLI
"""

import glob
import json
import os
import pathlib
import subprocess
import sys
import types

import numpy as np
import pytest

from data_accelerator_tpu.analysis import (
    CODES,
    ENGINE_PACKAGES,
    REPORT_SCHEMA_VERSION,
    SEV_ERROR,
    analyze_flow_race,
    analyze_modules,
    engine_module_paths,
)
from data_accelerator_tpu.runtime.sanitizer import (
    MIN_RUN,
    SENTINEL,
    BufferSanitizer,
)

HERE = os.path.dirname(__file__)
RACE_DIR = os.path.join(HERE, "data", "race")
FLOWS_DIR = os.path.join(HERE, "data", "flows")
PKG_ROOT = os.path.dirname(HERE)


# ---------------------------------------------------------------------------
# golden bad/clean twins
# ---------------------------------------------------------------------------
RACE_CODES = ["DX800", "DX801", "DX802", "DX804"]


@pytest.mark.parametrize("code", RACE_CODES)
def test_golden_race_twins(code):
    bad = os.path.join(RACE_DIR, code.lower() + "_bad.py")
    clean = os.path.join(RACE_DIR, code.lower() + "_clean.py")
    bad_report = analyze_modules([bad])
    codes = {d.code for d in bad_report.diagnostics}
    assert codes == {code}, (
        f"{bad}: expected exactly {code}, got "
        f"{[d.render() for d in bad_report.diagnostics]}"
    )
    assert not bad_report.ok
    assert all(d.severity == SEV_ERROR for d in bad_report.diagnostics)
    assert CODES[code][0] == SEV_ERROR
    clean_report = analyze_modules([clean])
    assert clean_report.diagnostics == [], (
        f"{clean}: {[d.render() for d in clean_report.diagnostics]}"
    )
    assert clean_report.ok


def test_every_dx80x_code_has_a_twin_pair():
    fixtures = {os.path.basename(p) for p in
                glob.glob(os.path.join(RACE_DIR, "*.py"))}
    for code in RACE_CODES:
        assert code.lower() + "_bad.py" in fixtures
        assert code.lower() + "_clean.py" in fixtures
    # and the registry carries every code the fixtures exercise
    for code in RACE_CODES:
        assert code in CODES


def test_clean_twin_markers_are_counted():
    report = analyze_modules(
        [os.path.join(RACE_DIR, "dx801_clean.py")]
    )
    assert report.allowed_zero_copy_sites == 1


# ---------------------------------------------------------------------------
# dynamic ground truth: the DX800 twins against a REAL pool + sanitizer
# ---------------------------------------------------------------------------
def _import_fixture(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(RACE_DIR, name + ".py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _drive_snapshotter(mod):
    """Acquire a pool matrix, 'checkpoint' it through the fixture,
    release (=> poison) the matrix, then scan the checkpoint."""
    from data_accelerator_tpu.native.decoder import PackedBufferPool

    san = BufferSanitizer()
    pool = PackedBufferPool(4, 64)
    pool.sanitizer = san
    mat = pool.acquire()
    mat[:] = 7
    snap = mod.WindowSnapshotter().snapshot(mat)
    pool.release(mat)  # poisons the slot
    assert san.poison_count == 1
    table = types.SimpleNamespace(cols={"rows": snap["rows"]}, valid=None)
    return san.scan_table("ckpt", table), san


def test_dx800_bad_twin_poison_hits_dynamically():
    hits, san = _drive_snapshotter(_import_fixture("dx800_bad"))
    assert hits >= 1
    events = san.drain_events()
    assert events and events[0]["code"] == "DX805"
    assert events[0]["kind"] == "sentinel-run"
    assert events[0]["runLength"] >= MIN_RUN


def test_dx800_clean_twin_runs_sanitizer_silent():
    hits, san = _drive_snapshotter(_import_fixture("dx800_clean"))
    assert hits == 0
    assert san.poison_hits == 0
    assert san.drain_events() == []


# ---------------------------------------------------------------------------
# the standing CI race gate: the engine self-lints DX8xx-clean
# ---------------------------------------------------------------------------
def test_engine_self_lint_is_race_clean():
    paths = engine_module_paths()
    assert len(paths) >= 15  # runtime/ + lq/ + pilot/
    assert ENGINE_PACKAGES == ("runtime", "lq", "pilot")
    report = analyze_modules(paths)
    assert report.diagnostics == [], (
        "engine race gate violated:\n"
        + "\n".join(d.render() for d in report.diagnostics)
    )
    # the engine's deliberate zero-copy/handoff sites stay pinned: a
    # new one must be a conscious, annotated decision
    assert report.allowed_zero_copy_sites == 2
    assert report.owner_handoff_sites == 2


def test_analyze_flow_race_caches_per_engine_state():
    flow = {"gui": {"name": "f1"}}
    r1 = analyze_flow_race(flow)
    r2 = analyze_flow_race({"gui": {"name": "f2"}})
    assert r1.ok and r2.ok
    assert r1.flow == "f1" and r2.flow == "f2"
    # same engine source => the cached module analysis is shared
    assert r1.modules is r2.modules
    d = r1.race_dict()
    assert set(d) == {
        "flow", "analyzedFiles", "modules", "allowedZeroCopySites",
        "ownerHandoffSites",
    }
    assert d["analyzedFiles"] == len(engine_module_paths())


# ---------------------------------------------------------------------------
# the seeded PR 13 regression: BOTH detectors must catch it
# ---------------------------------------------------------------------------
PROCESSOR_PY = os.path.join(
    PKG_ROOT, "data_accelerator_tpu", "runtime", "processor.py"
)


def _seeded_source():
    src = pathlib.Path(PROCESSOR_PY).read_text()
    bad = src.replace(
        "c: np.array(a, copy=True)", "c: np.asarray(a)"
    ).replace(
        '"valid": np.array(buf.valid, copy=True)',
        '"valid": np.asarray(buf.valid)',
    )
    assert bad != src, "seed target moved: update the regression test"
    return bad


def test_seeded_pr13_bug_caught_statically(tmp_path):
    """Re-apply the PR 13 bug (drop copy=True in snapshot_window_state)
    in a sandboxed copy: the race pass must fail the self-lint."""
    p = tmp_path / "processor.py"
    p.write_text(_seeded_source())
    report = analyze_modules([str(p)])
    codes = {d.code for d in report.diagnostics}
    assert "DX800" in codes, (
        f"static detector missed the seeded bug: "
        f"{[d.render() for d in report.diagnostics]}"
    )
    assert not report.ok  # self-lint exit 1
    snap_hits = [
        d for d in report.diagnostics
        if "snapshot_window_state" in d.message
    ]
    assert snap_hits


def test_seeded_pr13_bug_caught_dynamically(tmp_path):
    """The same seeded bug, executed: bind the patched (copy-dropping)
    snapshot method onto a LIVE processor — the armed sanitizer's
    checkpoint guard must see the snapshot aliasing the rings."""
    import ast

    from data_accelerator_tpu.core.config import SettingDictionary
    from data_accelerator_tpu.runtime.processor import FlowProcessor

    t = tmp_path / "flow.transform"
    t.write_text(
        "--DataXQuery--\n"
        "WinAgg = SELECT deviceId, COUNT(*) AS Cnt "
        "FROM DataXProcessedInput_10seconds GROUP BY deviceId\n"
        # a reader of the window's rows: the planner keeps the raw-row
        # ring, whose snapshot the seeded bug aliases
        "--DataXQuery--\n"
        "Rows = SELECT deviceId FROM DataXProcessedInput_10seconds "
        "WHERE deviceId > 100\n"
    )
    schema = json.dumps({"type": "struct", "fields": [
        {"name": "deviceId", "type": "long", "nullable": False,
         "metadata": {}},
        {"name": "eventTimeStamp", "type": "timestamp",
         "nullable": False, "metadata": {}},
    ]})
    conf = SettingDictionary({
        "datax.job.name": "SeededRace",
        "datax.job.input.default.blobschemafile": schema,
        "datax.job.process.transform": str(t),
        "datax.job.process.timestampcolumn": "eventTimeStamp",
        "datax.job.process.watermark": "0 second",
        "datax.job.process.batchcapacity": "16",
        "datax.job.process.timewindow.DataXProcessedInput_10seconds"
        ".windowduration": "10 seconds",
        "datax.job.process.debug.buffersanitizer": "true",
    })
    proc = FlowProcessor(conf, output_datasets=["WinAgg"])
    assert proc.buffer_sanitizer is not None
    base = 1_700_000_000_000
    proc.process_batch(
        proc.encode_rows(
            [{"deviceId": 5, "eventTimeStamp": base}], base
        ),
        base,
    )

    # the SHIPPED snapshot is a real copy: the guard stays silent
    good = proc.snapshot_window_state()
    assert proc.buffer_sanitizer.check_snapshot(
        good, proc.window_buffers
    ) == 0

    # extract + exec the seeded method, bind it over the live processor
    tree = ast.parse(_seeded_source())
    cls = next(
        n for n in tree.body
        if isinstance(n, ast.ClassDef) and n.name == "FlowProcessor"
    )
    fn = next(
        n for n in cls.body
        if isinstance(n, ast.FunctionDef)
        and n.name == "snapshot_window_state"
    )
    from typing import Optional

    from data_accelerator_tpu.runtime import processor as processor_mod

    ns = {"np": np, "Dict": dict, "Optional": Optional,
          "WindowPartials": processor_mod.WindowPartials,
          "_snapshot_partials": processor_mod._snapshot_partials}
    exec(  # noqa: S102 — sandboxed regression seed, sources from this repo
        compile(ast.Module(body=[fn], type_ignores=[]), "<seed>", "exec"),
        ns,
    )
    proc.snapshot_window_state = types.MethodType(
        ns["snapshot_window_state"], proc
    )

    bad_snap = proc.snapshot_window_state()
    hits = proc.buffer_sanitizer.check_snapshot(
        bad_snap, proc.window_buffers
    )
    assert hits >= 1, "sanitizer missed the seeded aliasing snapshot"
    events = proc.buffer_sanitizer.drain_events()
    assert any(e["kind"] == "snapshot-alias" for e in events)
    assert all(e["code"] == "DX805" for e in events)


# ---------------------------------------------------------------------------
# sanitizer unit + armed-processor e2e
# ---------------------------------------------------------------------------
def test_sentinel_scan_thresholds():
    san = BufferSanitizer()
    ok = np.arange(64, dtype=np.int32)
    ok[10] = int(SENTINEL)  # an isolated honest collision
    t = types.SimpleNamespace(cols={"c": ok}, valid=None)
    assert san.scan_table("t", t) == 0
    bad = np.arange(64, dtype=np.int32)
    bad[8:8 + MIN_RUN] = int(SENTINEL)
    t2 = types.SimpleNamespace(cols={"c": bad}, valid=None)
    assert san.scan_table("t", t2) == 1
    d = san.drain_metric_deltas()
    assert d["Sanitizer_PoisonHit_Count"] == 1.0
    assert d["Sanitizer_GuardedViews_Count"] == 2.0
    # drained: a second drain reports nothing new
    assert san.drain_metric_deltas() == {}


def test_armed_processor_runs_sanitizer_silent(tmp_path):
    """An armed FlowProcessor processes batches with zero poison hits
    and exports the guarded-views metric — the tier-1 face of the
    depth-2/4 recovery+chaos arming."""
    from data_accelerator_tpu.core.config import SettingDictionary
    from data_accelerator_tpu.runtime.processor import FlowProcessor

    t = tmp_path / "flow.transform"
    t.write_text(
        "--DataXQuery--\n"
        "Out = SELECT deviceId, temperature FROM DataXProcessedInput\n"
    )
    schema = json.dumps({"type": "struct", "fields": [
        {"name": "deviceId", "type": "long", "nullable": False,
         "metadata": {}},
        {"name": "temperature", "type": "double", "nullable": False,
         "metadata": {}},
        {"name": "eventTimeStamp", "type": "timestamp",
         "nullable": False, "metadata": {}},
    ]})
    conf = SettingDictionary({
        "datax.job.name": "SanE2E",
        "datax.job.input.default.blobschemafile": schema,
        "datax.job.process.transform": str(t),
        "datax.job.process.timestampcolumn": "eventTimeStamp",
        "datax.job.process.watermark": "0 second",
        "datax.job.process.batchcapacity": "16",
        "datax.job.process.debug.buffersanitizer": "true",
    })
    proc = FlowProcessor(conf, output_datasets=["Out"])
    base = 1_700_000_000_000
    seen_guarded = 0.0
    for i in range(3):
        rows = [
            {"deviceId": d, "temperature": 1.0 * d,
             "eventTimeStamp": base + i * 1000}
            for d in range(4)
        ]
        datasets, metrics = proc.process_batch(
            proc.encode_rows(rows, base + i * 1000), base + i * 1000
        )
        assert len(datasets["Out"]) == 4
        assert "Sanitizer_PoisonHit_Count" not in metrics
        seen_guarded += metrics.get("Sanitizer_GuardedViews_Count", 0.0)
    assert seen_guarded > 0
    assert proc.buffer_sanitizer.poison_hits == 0
    assert proc.buffer_sanitizer.drain_events() == []


def test_unarmed_processor_has_no_sanitizer(tmp_path):
    from data_accelerator_tpu.core.config import SettingDictionary
    from data_accelerator_tpu.runtime.processor import FlowProcessor

    t = tmp_path / "flow.transform"
    t.write_text(
        "--DataXQuery--\n"
        "Out = SELECT deviceId FROM DataXProcessedInput\n"
    )
    schema = json.dumps({"type": "struct", "fields": [
        {"name": "deviceId", "type": "long", "nullable": False,
         "metadata": {}},
        {"name": "eventTimeStamp", "type": "timestamp",
         "nullable": False, "metadata": {}},
    ]})
    conf = SettingDictionary({
        "datax.job.name": "SanOff",
        "datax.job.input.default.blobschemafile": schema,
        "datax.job.process.transform": str(t),
        "datax.job.process.timestampcolumn": "eventTimeStamp",
        "datax.job.process.watermark": "0 second",
        "datax.job.process.batchcapacity": "16",
    })
    proc = FlowProcessor(conf, output_datasets=["Out"])
    assert proc.buffer_sanitizer is None


# ---------------------------------------------------------------------------
# CLI contract (the 0/1/2 exit contract covers --race)
# ---------------------------------------------------------------------------
def _run_cli(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("PYTHONPATH", PKG_ROOT)
    return subprocess.run(
        [sys.executable, "-m", "data_accelerator_tpu.analysis", *args],
        capture_output=True, text=True, env=env, cwd=PKG_ROOT,
    )


def test_cli_race_zero_exit_and_gate_summary():
    path = os.path.join(FLOWS_DIR, "clean_config2_window_agg.json")
    proc = _run_cli(["--race", path])
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "race gate:" in proc.stdout
    assert "engine module(s) analyzed" in proc.stdout


def test_cli_race_json_and_all_fold_in():
    path = os.path.join(FLOWS_DIR, "clean_config2_window_agg.json")
    proc = _run_cli(["--race", "--json", path])
    assert proc.returncode == 0, proc.stderr
    report = json.loads(proc.stdout)
    assert report["schemaVersion"] == REPORT_SCHEMA_VERSION == 5
    assert report["race"]["analyzedFiles"] >= 15
    assert report["race"]["modules"]
    # --all includes the race block (one CI call, every tier); the
    # fleet tier nests the per-file reports under "files"
    proc2 = _run_cli(["--all", "--json", path])
    assert proc2.returncode == 0, proc2.stderr
    merged = json.loads(proc2.stdout)["files"][0]
    assert merged["race"] == report["race"]
    for block in ("device", "udfs", "compile", "mesh", "race"):
        assert block in merged


def test_cli_usage_exit_2_covers_race_flag():
    path = os.path.join(FLOWS_DIR, "clean_config2_window_agg.json")
    typo = _run_cli(["--rcae", path])
    assert typo.returncode == 2
    assert "unknown flag" in typo.stderr
    usage = _run_cli([])
    assert usage.returncode == 2
    assert "--race" in usage.stderr


# ---------------------------------------------------------------------------
# REST parity: flow/validate {"race": true} == the CLI --race
# ---------------------------------------------------------------------------
def test_validate_endpoint_race_parity(tmp_path):
    from test_serve_jobs import FakeJobClient

    from data_accelerator_tpu.serve.flowservice import FlowOperation
    from data_accelerator_tpu.serve.restapi import DataXApi
    from data_accelerator_tpu.serve.storage import (
        LocalDesignTimeStorage,
        LocalRuntimeStorage,
    )

    with open(os.path.join(
        FLOWS_DIR, "clean_config2_window_agg.json"
    )) as f:
        flow = json.load(f)
    api = DataXApi(FlowOperation(
        LocalDesignTimeStorage(str(tmp_path / "design")),
        LocalRuntimeStorage(str(tmp_path / "runtime")),
        job_client=FakeJobClient(),
    ))
    status, out = api.dispatch(
        "POST", "api/flow/validate", body={"flow": flow, "race": True},
    )
    assert status == 200
    result = out["result"]
    assert result["ok"] is True
    assert result["schemaVersion"] == REPORT_SCHEMA_VERSION
    cli = _run_cli([
        "--race", "--json",
        os.path.join(FLOWS_DIR, "clean_config2_window_agg.json"),
    ])
    cli_report = json.loads(cli.stdout)
    assert result["race"] == cli_report["race"]
