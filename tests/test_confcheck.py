"""Configuration-lattice analyzer tests (the --conf tier, DX10xx) and
the runtime conf audit (DX1006).

- golden fixtures: one bad/clean twin pair per DX100x code under
  tests/data/conf/ — DX1000-DX1003 as tiny .py modules in the
  engine's conf idioms, DX1004/DX1005 as flat .conf files; each bad
  twin emits EXACTLY its code, each clean twin is silent
- self-lint (the standing CI conf gate): the full engine+serve tree
  scans DX10xx-clean with the read-site/produced-key/token inventory
  pinned by exact count, and registry coverage of runtime read sites
  pinned at 100%
- seeded designer-chain regression: renaming one S650 key in a copy of
  serve/generation.py is caught statically by DX1002 and dynamically
  by exactly one DX1006 at service boot
- ConfAudit unit semantics: fail-open, unknown/out-of-bounds counting,
  DX1006 event shape, telemetry/metric emission
- CLI/REST contract: --conf under the 0/1/2 exit contract (incl.
  exit-2 typo rejection), folded into --all, REST ``conf: true``
  parity with the CLI
"""

import glob
import json
import os
import subprocess
import sys

import pytest

from data_accelerator_tpu.analysis import (
    CODES,
    CONF_REGISTRY,
    REPORT_SCHEMA_VERSION,
    SEV_ERROR,
    SEV_WARNING,
    analyze_conf_modules,
    analyze_flow_conf,
    conf_module_paths,
)
from data_accelerator_tpu.analysis.confspec import (
    CONSTRAINTS,
    match_key,
    rows_matching_family,
)
from data_accelerator_tpu.constants import MetricName
from data_accelerator_tpu.runtime.confaudit import ConfAudit, audit_conf

HERE = os.path.dirname(__file__)
CONF_DIR = os.path.join(HERE, "data", "conf")
FLOWS_DIR = os.path.join(HERE, "data", "flows")
PKG_ROOT = os.path.dirname(HERE)
GENERATION = os.path.join(
    PKG_ROOT, "data_accelerator_tpu", "serve", "generation.py"
)

# ---------------------------------------------------------------------------
# golden bad/clean twins
# ---------------------------------------------------------------------------
# code -> (fixture extension, severity of the bad twin's finding)
CONF_CODES = {
    "DX1000": (".py", SEV_ERROR),
    "DX1001": (".py", SEV_WARNING),
    "DX1002": (".py", SEV_ERROR),
    "DX1003": (".py", SEV_WARNING),
    "DX1004": (".conf", SEV_ERROR),
    "DX1005": (".conf", SEV_ERROR),
}


@pytest.mark.parametrize("code", sorted(CONF_CODES))
def test_golden_conf_twins(code):
    ext, sev = CONF_CODES[code]
    bad = os.path.join(CONF_DIR, code.lower() + "_bad" + ext)
    clean = os.path.join(CONF_DIR, code.lower() + "_clean" + ext)
    bad_report = analyze_conf_modules([bad])
    codes = {d.code for d in bad_report.diagnostics}
    assert codes == {code}, (
        f"{bad}: expected exactly {code}, got "
        f"{[d.render() for d in bad_report.diagnostics]}"
    )
    assert all(d.severity == sev for d in bad_report.diagnostics)
    assert CODES[code][0] == sev
    assert bad_report.ok == (sev != SEV_ERROR)
    clean_report = analyze_conf_modules([clean])
    assert clean_report.diagnostics == [], (
        f"{clean}: {[d.render() for d in clean_report.diagnostics]}"
    )
    assert clean_report.ok


def test_every_dx100x_code_has_a_twin_pair():
    fixtures = {os.path.basename(p) for p in
                glob.glob(os.path.join(CONF_DIR, "*"))}
    for code, (ext, _sev) in CONF_CODES.items():
        assert code.lower() + "_bad" + ext in fixtures
        assert code.lower() + "_clean" + ext in fixtures
    # the diagnostics table carries the whole family, runtime half too
    for code in list(CONF_CODES) + ["DX1006"]:
        assert code in CODES


# ---------------------------------------------------------------------------
# self-lint: the engine holds its own conf lattice (a standing CI
# gate: a new read site, produced key or gui token must land in the
# registry — and adjust these pins — before any runtime test runs)
# ---------------------------------------------------------------------------
def test_engine_conf_lattice_clean_with_pinned_inventory():
    paths = conf_module_paths()
    report = analyze_conf_modules(paths)
    assert report.diagnostics == [], (
        [d.render() for d in report.diagnostics]
    )
    cd = report.conf_dict()
    # the inventory is PINNED: a new conf read site, generated key,
    # ``# dx-conf:`` marker or registry row must adjust these numbers
    # consciously (and justify itself in review)
    assert cd["analyzedFiles"] == 93
    assert cd["readSites"] == 97
    assert cd["readKeys"] == 92
    assert cd["producedKeys"] == 51
    assert cd["knobTokens"] == 5
    assert cd["registryKeys"] == len(CONF_REGISTRY) == 104
    assert cd["constraints"] == len(CONSTRAINTS) == 1


def test_registry_covers_every_runtime_read_site_exactly():
    """100% read-site coverage, by exact count: every one of the 97
    scanned read sites resolves to a registry row (a DX1000 would also
    fail the self-lint above — this pins the count the other way)."""
    report = analyze_conf_modules(conf_module_paths())
    covered = [
        r for r in report.read_sites
        if (rows_matching_family(r.key) if "*" in r.key
            else match_key(r.key) is not None)
    ]
    assert len(covered) == len(report.read_sites) == 97


def test_registry_parity_rows_are_exactly_the_azurefunction_family():
    """read=False rows exist only for reference-parity keys the engine
    intentionally does not consume (the azure-function extension
    family) — pinned so parity rows cannot hide dead conf."""
    parity = [e for e in CONF_REGISTRY if not e.read]
    assert len(parity) == 5
    assert all(e.key.startswith("azurefunction.") for e in parity)


# ---------------------------------------------------------------------------
# seeded designer-chain regression (the PR 6 bug class, both halves)
# ---------------------------------------------------------------------------
def _seed_renamed_generation(tmp_path):
    """A copy of serve/generation.py with one S650 key renamed — the
    knob is still read, its registered key is never written."""
    with open(GENERATION, "r", encoding="utf-8") as f:
        src = f.read()
    seeded = src.replace(
        '"datax.job.process.ingest.decoderthreads"',
        '"datax.job.process.ingest.decoderthread"',
    )
    assert seeded != src
    out = tmp_path / "generation.py"
    out.write_text(seeded)
    return str(out)


def test_seeded_chain_break_is_caught_statically_by_dx1002(tmp_path):
    report = analyze_conf_modules([_seed_renamed_generation(tmp_path)])
    by_code = {}
    for d in report.diagnostics:
        by_code.setdefault(d.code, []).append(d)
    assert "DX1002" in by_code, (
        [d.render() for d in report.diagnostics]
    )
    assert any(
        "jobDecoderThreads" in d.message for d in by_code["DX1002"]
    )
    # the renamed key itself is flagged as dead conf alongside
    assert set(by_code) == {"DX1001", "DX1002"}
    assert not report.ok


def test_seeded_chain_break_is_caught_dynamically_by_one_dx1006():
    """The dynamic half: a service booted with the conf the broken
    generation would have emitted flight-records EXACTLY one DX1006."""
    from data_accelerator_tpu.lq.service import LiveQueryService

    conf = {
        "datax.job.process.batchcapacity": "8",
        "datax.job.process.pipeline.depth": "2",
        # the seeded rename: what generation writes after the break
        "datax.job.process.ingest.decoderthread": "2",
        "datax.job.process.lq.maxfanin": "4",
    }
    svc = LiveQueryService(conf=conf)
    audit = svc.conf_audit
    events = audit.events()
    assert len(events) == 1
    ev = events[0]
    assert ev["code"] == "DX1006"
    assert ev["kind"] == "unknown"
    assert ev["key"] == "ingest.decoderthread"
    assert "DX1006" in ev["message"]
    assert audit.metric_deltas() == {
        MetricName.CONF_AUDITED: 4.0,
        MetricName.CONF_UNKNOWN: 1.0,
        MetricName.CONF_OUT_OF_BOUNDS: 0.0,
    }


# ---------------------------------------------------------------------------
# ConfAudit: the dynamic half, unit semantics
# ---------------------------------------------------------------------------
class _FakeTelemetry:
    def __init__(self, fail=False):
        self.events = []
        self.fail = fail

    def track_event(self, name, props):
        if self.fail:
            raise RuntimeError("telemetry down")
        self.events.append((name, props))


class _FakeMetricLogger:
    def __init__(self):
        self.detail = []
        self.points = []

    def send_metric_events(self, metric, events, uts_ms=None):
        self.detail.append((metric, list(events)))

    def send_batch_metrics(self, metrics, uts_ms=None):
        self.points.append(dict(metrics))


def test_audit_clean_conf_is_silent_but_counted():
    audit = audit_conf({
        "datax.job.process.batchcapacity": "8",
        "datax.job.other.key": "ignored",
    })
    assert audit.ok
    assert audit.audited == 1
    assert audit.events() == []
    deltas = audit.metric_deltas()
    assert deltas[MetricName.CONF_AUDITED] == 1.0
    assert deltas[MetricName.CONF_UNKNOWN] == 0.0
    assert deltas[MetricName.CONF_OUT_OF_BOUNDS] == 0.0


def test_audit_counts_unknown_value_and_constraint_findings():
    audit = audit_conf({
        "datax.job.process.bogus.key": "1",          # unknown
        "datax.job.process.pipeline.depth": "0",     # bounds
        "datax.job.process.state.filteringest": "true",  # constraint
        "datax.job.process.numchips": "4",
    })
    assert not audit.ok
    assert audit.audited == 4
    assert audit.unknown == 1
    assert audit.out_of_bounds == 2  # one value + one constraint
    kinds = sorted(e["kind"] for e in audit.events())
    assert kinds == ["constraint", "unknown", "value"]


def test_audit_accepts_setting_dictionary():
    from data_accelerator_tpu.core.config import SettingDictionary

    audit = audit_conf(SettingDictionary(
        {"datax.job.process.batchcapacity": "8"}
    ))
    assert audit.ok and audit.audited == 1


def test_audit_emit_flight_records_and_is_fail_open():
    audit = audit_conf({"datax.job.process.bogus.key": "1"})
    tele, ml = _FakeTelemetry(), _FakeMetricLogger()
    audit.emit(telemetry=tele, metric_logger=ml)
    assert [n for n, _ in tele.events] == ["conf/violation"]
    assert tele.events[0][1]["code"] == "DX1006"
    (metric, evs), = ml.detail
    assert metric == "Conf_Violation"
    assert evs[0]["key"] == "bogus.key"
    assert ml.points == [audit.metric_deltas()]
    # a broken telemetry sink must never block boot
    audit.emit(telemetry=_FakeTelemetry(fail=True), metric_logger=ml)
    # nor a pathological conf object
    assert audit_conf(object()).audited == 0


def test_conf_metric_names_are_registered_runtime_patterns():
    for name in (MetricName.CONF_AUDITED, MetricName.CONF_UNKNOWN,
                 MetricName.CONF_OUT_OF_BOUNDS):
        assert MetricName.is_runtime_metric(name)


# ---------------------------------------------------------------------------
# flow-level gate: every shipped flow fixture's conf passes clean
# ---------------------------------------------------------------------------
def test_flow_conf_gate_clean_on_shipped_flows():
    for path in sorted(
        glob.glob(os.path.join(FLOWS_DIR, "clean_*.json"))
    ):
        with open(path) as f:
            flow = json.load(f)
        report = analyze_flow_conf(flow)
        assert report.diagnostics == [], (
            path, [d.render() for d in report.diagnostics]
        )


# ---------------------------------------------------------------------------
# CONF.md: the generated configuration reference cannot go stale
# ---------------------------------------------------------------------------
def test_conf_md_reference_is_not_stale():
    from data_accelerator_tpu.analysis.confspec import render_conf_md

    with open(os.path.join(PKG_ROOT, "CONF.md")) as f:
        on_disk = f.read()
    assert on_disk == render_conf_md(), (
        "CONF.md is stale — regenerate with: "
        "python -m data_accelerator_tpu.analysis.confspec > CONF.md"
    )


# ---------------------------------------------------------------------------
# CLI contract (the 0/1/2 exit contract covers --conf)
# ---------------------------------------------------------------------------
def _run_cli(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("PYTHONPATH", PKG_ROOT)
    return subprocess.run(
        [sys.executable, "-m", "data_accelerator_tpu.analysis", *args],
        capture_output=True, text=True, env=env, cwd=PKG_ROOT,
    )


def test_cli_conf_zero_exit_and_gate_summary():
    path = os.path.join(FLOWS_DIR, "clean_config2_window_agg.json")
    proc = _run_cli(["--conf", path])
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "conf gate:" in proc.stdout
    assert "read site(s)" in proc.stdout


def test_cli_conf_json_and_all_fold_in():
    path = os.path.join(FLOWS_DIR, "clean_config2_window_agg.json")
    proc = _run_cli(["--conf", "--json", path])
    assert proc.returncode == 0, proc.stderr
    report = json.loads(proc.stdout)
    assert report["schemaVersion"] == REPORT_SCHEMA_VERSION == 5
    assert report["conf"]["readSites"] == 97
    assert report["conf"]["registryKeys"] == 104
    # --all includes the conf block (one CI call, every tier)
    proc2 = _run_cli(["--all", "--json", path])
    assert proc2.returncode == 0, proc2.stderr
    merged = json.loads(proc2.stdout)["files"][0]
    assert merged["conf"] == report["conf"]
    for block in ("device", "udfs", "compile", "mesh", "race",
                  "protocol", "conf"):
        assert block in merged


def test_cli_usage_exit_2_covers_conf_flag():
    path = os.path.join(FLOWS_DIR, "clean_config2_window_agg.json")
    typo = _run_cli(["--cnof", path])
    assert typo.returncode == 2
    assert "unknown flag" in typo.stderr
    usage = _run_cli([])
    assert usage.returncode == 2
    assert "--conf" in usage.stderr


# ---------------------------------------------------------------------------
# REST parity: flow/validate {"conf": true} == the CLI --conf
# ---------------------------------------------------------------------------
def test_validate_endpoint_conf_parity(tmp_path):
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
        "POST", "api/flow/validate",
        body={"flow": flow, "conf": True},
    )
    assert status == 200
    result = out["result"]
    assert result["ok"] is True
    assert result["schemaVersion"] == REPORT_SCHEMA_VERSION
    cli = _run_cli([
        "--conf", "--json",
        os.path.join(FLOWS_DIR, "clean_config2_window_agg.json"),
    ])
    cli_report = json.loads(cli.stdout)
    assert result["conf"] == cli_report["conf"]
