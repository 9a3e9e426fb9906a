"""Exactly-once protocol analyzer tests (the --protocol tier, DX90x)
and the runtime protocol monitor (DX906).

- golden fixtures: one bad/clean twin pair per DX90x code under
  tests/data/proto/ — tiny modules written in the engine's batch-tail
  idioms, each bad twin emitting EXACTLY its code, each clean twin
  silent
- self-lint (the standing CI protocol gate): every engine module plus
  the rescale handoff analyzes DX90x-clean, with the ``# dx-proto:``
  marker inventory pinned by count
- ProtocolMonitor unit semantics: a well-ordered batch seals silent;
  an ack-before-flip FAILED batch fires exactly one DX906 citing
  DX900; metric drains are delta-based and violation-silent-on-health
- CLI/REST contract: --protocol under the 0/1/2 exit contract (incl.
  exit-2 typo rejection), folded into --all, REST ``protocol: true``
  parity with the CLI

(The seeded ack-before-checkpoint regression — the SAME reorder caught
by both the static pass and the armed monitor under sink failure —
lives in tests/test_recovery.py beside the recovery drills it
subverts.)
"""

import glob
import json
import os
import subprocess
import sys

import pytest

from data_accelerator_tpu.analysis import (
    CODES,
    REPORT_SCHEMA_VERSION,
    RULES,
    RULES_BY_CODE,
    SEV_ERROR,
    analyze_proto_modules,
    check_sequence,
    proto_module_paths,
)
from data_accelerator_tpu.runtime.protocolmonitor import (
    ProtocolMonitor,
    from_conf,
)

HERE = os.path.dirname(__file__)
PROTO_DIR = os.path.join(HERE, "data", "proto")
FLOWS_DIR = os.path.join(HERE, "data", "flows")
PKG_ROOT = os.path.dirname(HERE)


# ---------------------------------------------------------------------------
# golden bad/clean twins
# ---------------------------------------------------------------------------
PROTO_CODES = ["DX900", "DX901", "DX902", "DX903", "DX904", "DX905"]


@pytest.mark.parametrize("code", PROTO_CODES)
def test_golden_proto_twins(code):
    bad = os.path.join(PROTO_DIR, code.lower() + "_bad.py")
    clean = os.path.join(PROTO_DIR, code.lower() + "_clean.py")
    bad_report = analyze_proto_modules([bad])
    codes = {d.code for d in bad_report.diagnostics}
    assert codes == {code}, (
        f"{bad}: expected exactly {code}, got "
        f"{[d.render() for d in bad_report.diagnostics]}"
    )
    assert not bad_report.ok
    assert all(d.severity == SEV_ERROR for d in bad_report.diagnostics)
    assert CODES[code][0] == SEV_ERROR
    clean_report = analyze_proto_modules([clean])
    assert clean_report.diagnostics == [], (
        f"{clean}: {[d.render() for d in clean_report.diagnostics]}"
    )
    assert clean_report.ok


def test_every_dx90x_code_has_a_twin_pair():
    fixtures = {os.path.basename(p) for p in
                glob.glob(os.path.join(PROTO_DIR, "*.py"))}
    for code in PROTO_CODES:
        assert code.lower() + "_bad.py" in fixtures
        assert code.lower() + "_clean.py" in fixtures
    # and both registries carry every code the fixtures exercise: the
    # diagnostics table AND the shared static/runtime rule table
    for code in PROTO_CODES:
        assert code in CODES
        assert code in RULES_BY_CODE
    assert [r.code for r in RULES] == PROTO_CODES


def test_clean_twin_markers_are_counted():
    report = analyze_proto_modules(
        [os.path.join(PROTO_DIR, "dx904_clean.py")]
    )
    assert report.post_commit_sites == 1


# ---------------------------------------------------------------------------
# self-lint: the engine holds its own delivery protocol (a standing CI
# gate: any reorder of the batch tail, checkpoint fence or rescale
# handoff fails HERE before any runtime test runs)
# ---------------------------------------------------------------------------
def test_engine_is_protocol_clean_with_pinned_inventory():
    paths = proto_module_paths()
    report = analyze_proto_modules(paths)
    assert report.ok, [d.render() for d in report.diagnostics]
    pd = report.protocol_dict()
    # the inventory is PINNED: a new ack/commit/checkpoint site, a new
    # ``# dx-proto:`` marker, or a dropped one must adjust these
    # numbers consciously (and justify itself in review)
    assert pd["analyzedFiles"] == len(paths) >= 24
    # 30 since the window checkpoint writes per-slot partial aggregates
    # a slot at a time: the slot file's fsync and its durable replace
    # (runtime/checkpoint.py _save_slots) are two DURABLE_WRITE sites
    assert pd["effectEvents"] == 30
    assert pd["postCommitSites"] == 3
    assert pd["requeueUpstreamSites"] == 1
    # the rescale handoff rides along with the engine set
    rels = {m["path"] for m in pd["modules"]}
    assert any(r.endswith("serve/jobs.py") for r in rels)
    assert any(r.endswith("runtime/host.py") for r in rels)


# ---------------------------------------------------------------------------
# ProtocolMonitor: the dynamic half, unit semantics
# ---------------------------------------------------------------------------
def _well_ordered_batch(pm):
    pm.record("SINK_EMIT", detail="dispatcher.dispatch")
    pm.record("POINTER_FLIP", detail="processor.commit")
    pm.record("FIFO_ACK", source="default")
    pm.record("DURABLE_WRITE", detail="window_checkpointer.save")
    pm.record("STATE_PUSH", detail="push_window_partitions")
    pm.record("OFFSET_COMMIT", detail="checkpoint_batch")


def test_monitor_well_ordered_batch_seals_silent():
    pm = ProtocolMonitor()
    _well_ordered_batch(pm)
    assert pm.seal_batch(batch_time_ms=12.5) == 0
    assert pm.violations == 0
    assert pm.batches_sealed == 1
    assert pm.drain_events() == []
    deltas = pm.drain_metric_deltas()
    # events flow every drain; the violation counter stays SILENT on
    # health (same posture as the sanitizer's poison-hit counter)
    assert deltas == {"Protocol_Events_Count": 6.0}
    assert pm.drain_metric_deltas() == {}


def test_monitor_ack_before_flip_on_failed_batch_fires_one_dx906():
    pm = ProtocolMonitor()
    pm.record("FIFO_ACK", source="default")
    pm.record("REQUEUE", source="default")
    assert pm.seal_batch(batch_time_ms=3.0, failed=True) == 1
    assert pm.violations == 1
    events = pm.drain_events()
    assert len(events) == 1
    ev = events[0]
    assert ev["code"] == "DX906"
    assert ev["rule"] == "DX900"
    assert ev["failed"] is True
    assert ev["sequence"] == ["FIFO_ACK", "REQUEUE"]
    assert "DX906" in ev["message"] and "DX900" in ev["message"]
    # drained means drained
    assert pm.drain_events() == []
    deltas = pm.drain_metric_deltas()
    assert deltas["Protocol_Violation_Count"] == 1.0
    assert deltas["Protocol_Events_Count"] == 2.0


def test_monitor_double_ack_same_source_is_dx902():
    pm = ProtocolMonitor()
    pm.record("POINTER_FLIP")
    pm.record("FIFO_ACK", source="default")
    pm.record("FIFO_ACK", source="default")
    assert pm.seal_batch() == 1
    (ev,) = pm.drain_events()
    assert ev["rule"] == "DX902"


def test_monitor_history_ring_keeps_sealed_linearizations():
    pm = ProtocolMonitor()
    _well_ordered_batch(pm)
    pm.seal_batch(batch_time_ms=1.0)
    recent = pm.recent_sequences()
    assert len(recent) == 1
    assert recent[0]["violations"] == []
    assert [e["kind"] for e in recent[0]["sequence"]][0] == "SINK_EMIT"
    # an empty tail (no events) seals to nothing — no phantom batches
    assert pm.seal_batch() == 0
    assert pm.batches_sealed == 1


def test_check_sequence_is_the_shared_rule_table():
    # the monitor and the static pass validate the SAME spec: a bare
    # event list through protospec.check_sequence reproduces the
    # monitor's verdicts
    ok = [{"kind": "SINK_EMIT"}, {"kind": "POINTER_FLIP"},
          {"kind": "FIFO_ACK", "source": "a"}]
    assert check_sequence(ok) == []
    bad = [{"kind": "FIFO_ACK", "source": "a"}, {"kind": "REQUEUE"}]
    found = check_sequence(bad, failed=True)
    assert [c for c, _ in found] == ["DX900"]


def test_from_conf_arms_only_on_true():
    class _Dbg:
        def __init__(self, v):
            self.v = v

        def get_or_else(self, key, default):
            return self.v if key == "protocolmonitor" else default

    assert isinstance(from_conf(_Dbg("true")), ProtocolMonitor)
    assert isinstance(from_conf(_Dbg("True")), ProtocolMonitor)
    assert from_conf(_Dbg("false")) is None
    assert from_conf(_Dbg(None)) is None


# ---------------------------------------------------------------------------
# CLI contract (the 0/1/2 exit contract covers --protocol)
# ---------------------------------------------------------------------------
def _run_cli(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("PYTHONPATH", PKG_ROOT)
    return subprocess.run(
        [sys.executable, "-m", "data_accelerator_tpu.analysis", *args],
        capture_output=True, text=True, env=env, cwd=PKG_ROOT,
    )


def test_cli_protocol_zero_exit_and_gate_summary():
    path = os.path.join(FLOWS_DIR, "clean_config2_window_agg.json")
    proc = _run_cli(["--protocol", path])
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "protocol gate:" in proc.stdout
    assert "engine module(s) analyzed" in proc.stdout


def test_cli_protocol_json_and_all_fold_in():
    path = os.path.join(FLOWS_DIR, "clean_config2_window_agg.json")
    proc = _run_cli(["--protocol", "--json", path])
    assert proc.returncode == 0, proc.stderr
    report = json.loads(proc.stdout)
    assert report["schemaVersion"] == REPORT_SCHEMA_VERSION == 5
    assert report["protocol"]["analyzedFiles"] >= 24
    assert report["protocol"]["modules"]
    # --all includes the protocol block (one CI call, every tier)
    proc2 = _run_cli(["--all", "--json", path])
    assert proc2.returncode == 0, proc2.stderr
    merged = json.loads(proc2.stdout)["files"][0]
    assert merged["protocol"] == report["protocol"]
    for block in ("device", "udfs", "compile", "mesh", "race",
                  "protocol"):
        assert block in merged


def test_cli_usage_exit_2_covers_protocol_flag():
    path = os.path.join(FLOWS_DIR, "clean_config2_window_agg.json")
    typo = _run_cli(["--protocl", path])
    assert typo.returncode == 2
    assert "unknown flag" in typo.stderr
    usage = _run_cli([])
    assert usage.returncode == 2
    assert "--protocol" in usage.stderr


# ---------------------------------------------------------------------------
# REST parity: flow/validate {"protocol": true} == the CLI --protocol
# ---------------------------------------------------------------------------
def test_validate_endpoint_protocol_parity(tmp_path):
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
        body={"flow": flow, "protocol": True},
    )
    assert status == 200
    result = out["result"]
    assert result["ok"] is True
    assert result["schemaVersion"] == REPORT_SCHEMA_VERSION
    cli = _run_cli([
        "--protocol", "--json",
        os.path.join(FLOWS_DIR, "clean_config2_window_agg.json"),
    ])
    cli_report = json.loads(cli.stdout)
    assert result["protocol"] == cli_report["protocol"]
