"""Mesh-sharding analyzer tests (the --mesh tier, DX7xx).

- golden fixtures: one bad/clean twin pair per DX7xx code under
  tests/data/flows/ (DX702/DX703 judge against a deliberately tiny
  fleet spec, the fleet-tier DX40x pattern)
- self-lint (tier-1 CI + the acceptance gate): every shipped scenario
  flow AND every clean baseline-mirror fixture passes --mesh --chips=8
  with zero errors, a validated partition plan, and the closed-form
  collective byte model matching the real Mesh lowering EXACTLY
- CLI contract: --mesh exit codes (0 clean incl. warnings, 1 on
  mesh-tier errors, 2 on bad --chips / unknown flags), plan rendering
- endpoint parity: flow/validate {"mesh": true} returns the same
  diagnostics and sharding plan as the CLI (one shared implementation)
- the shared chip-count parser (analysis/chipcount.py): one typed
  error for every surface
- generation S660: mesh jobs' confs embed datax.job.process.mesh.model;
  single-chip jobs and jobMeshModel:"false" skip it
"""

import json
import os
import subprocess
import sys

import pytest

from data_accelerator_tpu.analysis import (
    CODES,
    ChipCountError,
    FleetSpec,
    SEV_ERROR,
    SEV_WARNING,
    analyze_flow,
    analyze_flow_mesh,
    parse_chip_count,
)
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


# tiny fleet specs the DX702/DX703 fixtures are judged against (their
# flows are modest; the spec makes the bound bite — the DX40x pattern)
_TINY_HBM = FleetSpec(hbm_per_chip_bytes=1 << 20)
_TINY_ICI = FleetSpec(ici_bytes_per_sec_per_chip=125_000.0)

# (fixture, code, severity, spec override or None)
MESH_GOLDEN = [
    ("dx700_unshardable_order", "DX700", SEV_WARNING, None),
    ("dx701_repeated_reshard", "DX701", SEV_WARNING, None),
    ("dx702_perchip_hbm", "DX702", SEV_ERROR, _TINY_HBM),
    ("dx703_ici_budget", "DX703", SEV_WARNING, _TINY_ICI),
    ("dx704_scaling_cliff", "DX704", SEV_WARNING, None),
    ("dx790_mesh_lowering", "DX790", SEV_ERROR, None),
    ("dx791_mesh_unavailable", "DX791", SEV_WARNING, None),
]


@pytest.mark.parametrize("fixture,code,severity,spec", MESH_GOLDEN,
                         ids=[g[0] for g in MESH_GOLDEN])
def test_golden_mesh_diagnostic(fixture, code, severity, spec):
    flow = load_flow(fixture)
    # mesh-tier-only findings: the semantic tier stays clean on them
    assert analyze_flow(flow).errors == []
    report = analyze_flow_mesh(flow, chips=8, spec=spec, lower=False)
    hits = [d for d in report.diagnostics if d.code == code]
    assert hits, f"expected {code}, got {report.codes()}"
    assert hits[0].severity == severity
    assert hits[0].severity == CODES[code][0]
    assert report.ok == (severity != SEV_ERROR)
    # the clean twin (same shape, the fix applied) drops the code
    twin = load_flow(fixture + "_clean")
    twin_report = analyze_flow_mesh(twin, chips=8, spec=spec, lower=False)
    assert code not in twin_report.codes(), (
        f"{fixture}_clean still reports {code}: "
        f"{[d.render() for d in twin_report.diagnostics]}"
    )
    assert twin_report.ok


def test_dx700_and_dx704_share_the_pallas_origin():
    """A Pallas-kernel UDF stage is both structurally unshardable
    (DX700) and the scaling cliff (DX704) — one origin, two lenses."""
    report = analyze_flow_mesh(
        load_flow("dx704_scaling_cliff"), chips=8, lower=False
    )
    assert {"DX700", "DX704"} <= set(report.codes())
    scored = next(s for s in report.stages if s.name == "Scored")
    assert scored.axis == "replicated"
    assert scored.scaling == "replicated"
    # the jnp twin shards clean
    twin = analyze_flow_mesh(
        load_flow("dx704_scaling_cliff_clean"), chips=8, lower=False
    )
    scored = next(s for s in twin.stages if s.name == "Scored")
    assert scored.axis == "data"


# ---------------------------------------------------------------------------
# self-lint: the acceptance gate — every shipped/baseline flow at
# --chips=8 analyzes clean AND the byte model equals the Mesh lowering
# ---------------------------------------------------------------------------
def test_mesh_self_lint_shipped_and_baseline_flows_exact():
    flows = [(g.get("name"), g) for g in shipped_flow_guis()]
    for path in clean_flow_paths():
        with open(path) as f:
            flows.append((os.path.basename(path), json.load(f)))
    assert len(flows) >= 6
    for name, flow in flows:
        report = analyze_flow_mesh(flow, chips=8)
        assert report.errors == [], (
            f"{name}: {[d.render() for d in report.errors]}"
        )
        assert report.validated, f"{name}: plan not cross-checked"
        assert report.stages, f"{name}: no partition plan"
        for s in report.stages:
            if s.lowered_bytes is None:
                continue
            assert s.lowered_bytes == s.ici_result_bytes, (
                f"{name}/{s.name}: model {s.ici_result_bytes} != "
                f"lowered {s.lowered_bytes} collective bytes"
            )
        t = report.totals()
        assert t["chips"] == 8
        assert t["iciWireBytesPerBatch"] >= t["iciResultBytesPerBatch"]


def test_partition_plan_axes_follow_the_mesh_layout():
    """The inferred plan mirrors dist/mesh.py's documented layout:
    rows/rings/windows shard, state replicates, group outputs
    replicate with a modeled gather at the window boundary."""
    # (a flow whose window a plain SELECT reads too, so the planner keeps
    # the raw-row ring; clean_config2_window_agg's GROUP BY alone is held
    # as replicated partial aggregates: the next test)
    report = analyze_flow_mesh(
        load_flow("dx702_perchip_hbm"), chips=8, lower=False
    )
    by = {s.name: s for s in report.stages}
    assert by["input:default"].axis == "data"
    assert by["DataXProcessedInput"].axis == "data"
    assert by["ring:DataXProcessedInput"].axis == "data"
    agg = next(s for s in report.stages if s.kind == "group")
    assert agg.axis == "replicated"
    assert agg.scaling == "collective"
    assert len(agg.reshards) == 1
    edge = agg.reshards[0]
    # closed form: the gathered window table's bytes, exactly
    win = next(s for s in report.stages if s.kind == "window")
    assert edge.result_bytes == win.hbm_bytes
    assert edge.wire_bytes == edge.result_bytes * 7  # ring all-gather, N=8
    # per-chip residency of sharded stages is 1/N of the table
    assert by["ring:DataXProcessedInput"].per_chip_bytes == (
        -(-by["ring:DataXProcessedInput"].hbm_bytes // 8)
    )


def test_a_socket_sources_batch_is_priced_as_one_matrix_not_a_leaf_a_column():
    """The raw form follows the input type (``source_raw_form``): a
    non-local source's batch crosses as one int32 matrix, a row a
    column and one for the validity, sharded on its capacity axis under
    a mesh. The device plan and the mesh plan price that matrix; the
    local twin of the flow keeps a column a leaf."""
    import copy

    from data_accelerator_tpu.analysis import analyze_flow_device

    local = load_flow("dx702_perchip_hbm")
    socketed = copy.deepcopy(local)
    socketed["input"]["type"] = "socket"
    stages = {}
    for name, flow in (("local", local), ("socket", socketed)):
        mesh = {s.name: s for s in
                analyze_flow_mesh(flow, chips=4, lower=False).stages}
        device = {s.name: s for s in analyze_flow_device(flow).stages}
        stages[name] = (mesh["input:default"], device["input:default"])
        # both tiers price the same bytes, sharded on their rows
        assert mesh["input:default"].hbm_bytes \
            == device["input:default"].hbm_bytes \
            == device["input:default"].model_bytes
        assert mesh["input:default"].axis == "data"
        assert mesh["input:default"].per_chip_bytes \
            == mesh["input:default"].hbm_bytes // 4
    m_local, d_local = stages["local"]
    m_socket, d_socket = stages["socket"]
    rows = d_socket.rows
    # every raw column is 4 bytes in this flow, so the matrix differs
    # from the columns by the validity alone: an int32 row for a bool
    assert m_socket.hbm_bytes % (4 * rows) == 0
    assert m_socket.hbm_bytes - m_local.hbm_bytes == 3 * rows
    assert "one packed matrix" in m_socket.detail
    assert "one packed matrix" in d_socket.detail
    assert "packed" not in m_local.detail and "packed" not in d_local.detail
    # nothing downstream moves: the step splits the matrix into the
    # table the projections read
    def rest(flow):
        return [(s.name, s.hbm_bytes)
                for s in analyze_flow_device(flow).stages if s.kind != "input"]

    assert rest(socketed) == rest(local)


def test_a_window_held_as_partial_aggregates_is_replicated():
    """A windowed GROUP BY alone, over a payload time column: per-slot
    partial aggregates of an event-time window, replicated on every chip
    (no ring, no window stage to gather)."""
    report = analyze_flow_mesh(
        load_flow("clean_config2_window_agg"), chips=8, lower=False
    )
    by = {s.name: s for s in report.stages}
    assert not [n for n in by if n.startswith("ring:")]
    state = by["window-state:WinAgg"]
    assert (state.axis, state.scaling) == ("replicated", "replicated")
    assert state.per_chip_bytes == state.hbm_bytes


def test_state_join_right_side_replicates_without_reshard():
    """A join against an accumulation table is a broadcast join: the
    state side is already replicated, so only the stream side pays a
    gather."""
    report = analyze_flow_mesh(
        load_flow("clean_config3_state_join"), chips=8, lower=False
    )
    for s in report.stages:
        for e in s.reshards:
            assert not e.table.startswith("state:"), (
                f"{s.name} gathers replicated state {e.table}"
            )
    assert any(s.kind == "state" and s.axis == "replicated"
               for s in report.stages)


def test_processor_mesh_parity_with_flow_analysis():
    """analyze_processor_mesh over a live mesh FlowProcessor produces
    the same stage axes and collective model the flow-config path
    derives — one inference, two entry points."""
    from test_dist import make_conf

    from data_accelerator_tpu.analysis import analyze_processor_mesh
    from data_accelerator_tpu.dist import make_mesh
    from data_accelerator_tpu.runtime.processor import FlowProcessor

    import tempfile
    from pathlib import Path

    with tempfile.TemporaryDirectory() as td:
        conf = make_conf(Path(td))
        proc = FlowProcessor(
            conf, batch_capacity=256, mesh=make_mesh(8),
            output_datasets=["Hot", "PerDevice"],
        )
        report = analyze_processor_mesh(proc)
    assert report.chips == 8
    assert report.validated
    assert report.errors == []
    by = {s.name: s for s in report.stages}
    assert by["Hot"].axis == "data"
    assert by["PerDevice"].axis == "replicated"
    # the sharded output gathers at the step boundary
    assert any(
        e.table.endswith("(output boundary)") for e in by["Hot"].reshards
    )
    for s in report.stages:
        if s.lowered_bytes is not None:
            assert s.lowered_bytes == s.ici_result_bytes


# ---------------------------------------------------------------------------
# shared chip-count parser (satellite): one typed error everywhere
# ---------------------------------------------------------------------------
def test_parse_chip_count_contract():
    assert parse_chip_count(None) is None
    assert parse_chip_count("") is None
    assert parse_chip_count("8") == 8
    assert parse_chip_count(16) == 16
    for bad in ("0", "-2", 0, -1, "eight", 2.5, True):
        with pytest.raises(ChipCountError):
            parse_chip_count(bad)
    # the typed error names the offending surface
    with pytest.raises(ChipCountError, match="--chips"):
        parse_chip_count("0", "--chips")
    with pytest.raises(ChipCountError, match="fleet"):
        parse_chip_count(-3, "fleet spec 'chips'")
    # and is a ValueError, so existing surface handlers keep catching it
    assert issubclass(ChipCountError, ValueError)


def test_fleet_spec_chips_use_shared_parser():
    assert FleetSpec.from_dict({"chips": 4}).chips == 4
    with pytest.raises(ChipCountError):
        FleetSpec.from_dict({"chips": 0})
    with pytest.raises(ChipCountError):
        FleetSpec.from_dict({"chips": "many"})


# ---------------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------------
def _run_cli(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("PYTHONPATH", os.path.dirname(os.path.dirname(__file__)))
    return subprocess.run(
        [sys.executable, "-m", "data_accelerator_tpu.analysis", *args],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(__file__)),
    )


def test_cli_mesh_zero_exit_on_clean_configs(tmp_path):
    paths = clean_flow_paths()
    for i, gui in enumerate(shipped_flow_guis()):
        p = tmp_path / f"scenario{i}.json"
        p.write_text(json.dumps(gui))
        paths.append(str(p))
    proc = _run_cli(["--mesh", "--chips=8", *paths])
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "mesh plan (8 chips, validated)" in proc.stdout


def test_cli_mesh_nonzero_on_lowering_error():
    proc = _run_cli([
        "--mesh", os.path.join(FLOWS_DIR, "dx790_mesh_lowering.json"),
    ])
    assert proc.returncode == 1, proc.stdout
    assert "DX790" in proc.stdout
    # without --mesh the same flow exits clean: mesh-tier-only finding
    proc2 = _run_cli([
        os.path.join(FLOWS_DIR, "dx790_mesh_lowering.json"),
    ])
    assert proc2.returncode == 0, proc2.stdout


def test_cli_mesh_warning_keeps_zero_exit():
    proc = _run_cli([
        "--mesh", os.path.join(FLOWS_DIR, "dx700_unshardable_order.json"),
    ])
    assert proc.returncode == 0, proc.stdout
    assert "DX700" in proc.stdout


def test_cli_usage_exit_2_covers_mesh_flags():
    """The usage/exit-2 contract covers the new flags: a bad --chips is
    a typed usage error, a --mesh typo cannot silently skip the tier,
    and the usage text documents --mesh."""
    path = os.path.join(FLOWS_DIR, "clean_config2_window_agg.json")
    bad_chips = _run_cli(["--mesh", "--chips=0", path])
    assert bad_chips.returncode == 2
    assert "chip count must be >= 1" in bad_chips.stderr
    bad_chips2 = _run_cli(["--mesh", "--chips=abc", path])
    assert bad_chips2.returncode == 2
    assert "invalid chip count" in bad_chips2.stderr
    typo = _run_cli(["--mehs", path])
    assert typo.returncode == 2
    assert "unknown flag" in typo.stderr
    usage = _run_cli([])
    assert usage.returncode == 2
    assert "--mesh" in usage.stderr


def test_cli_mesh_json_matches_validate_endpoint():
    """The REST ``mesh: true`` path and the CLI ``--mesh --json`` path
    share one implementation — identical diagnostics AND identical
    sharding plans for the same flow JSON."""
    from test_serve_jobs import FakeJobClient

    from data_accelerator_tpu.serve.flowservice import FlowOperation
    from data_accelerator_tpu.serve.restapi import DataXApi
    from data_accelerator_tpu.serve.storage import (
        LocalDesignTimeStorage,
        LocalRuntimeStorage,
    )

    path = os.path.join(FLOWS_DIR, "dx700_unshardable_order.json")
    proc = _run_cli(["--mesh", "--chips=8", "--json", path])
    assert proc.returncode == 0, proc.stderr  # DX700 is a warning
    cli_report = json.loads(proc.stdout)
    assert cli_report["mesh"]["stages"]
    assert cli_report["mesh"]["validated"] is True

    import tempfile

    with tempfile.TemporaryDirectory() as td:
        api = DataXApi(FlowOperation(
            LocalDesignTimeStorage(os.path.join(td, "design")),
            LocalRuntimeStorage(os.path.join(td, "runtime")),
            job_client=FakeJobClient(),
        ))
        status, out = api.dispatch(
            "POST", "api/flow/validate",
            body={"flow": load_flow("dx700_unshardable_order"),
                  "mesh": True, "chips": 8},
        )
    assert status == 200
    assert out["result"]["diagnostics"] == cli_report["diagnostics"]
    assert out["result"]["mesh"] == cli_report["mesh"]


def test_validate_endpoint_rejects_bad_chips():
    from test_serve_jobs import FakeJobClient

    from data_accelerator_tpu.serve.flowservice import FlowOperation
    from data_accelerator_tpu.serve.restapi import DataXApi
    from data_accelerator_tpu.serve.storage import (
        LocalDesignTimeStorage,
        LocalRuntimeStorage,
    )

    import tempfile

    with tempfile.TemporaryDirectory() as td:
        api = DataXApi(FlowOperation(
            LocalDesignTimeStorage(os.path.join(td, "design")),
            LocalRuntimeStorage(os.path.join(td, "runtime")),
            job_client=FakeJobClient(),
        ))
        status, out = api.dispatch(
            "POST", "api/flow/validate",
            body={"flow": load_flow("clean_config2_window_agg"),
                  "mesh": True, "chips": 0},
        )
    assert status == 400
    assert "chip count" in out["error"]["message"]


# ---------------------------------------------------------------------------
# generation S660: the sharding plan as a deployment artifact
# ---------------------------------------------------------------------------
def _flow_ops(tmp_path):
    from data_accelerator_tpu.serve.flowservice import FlowOperation
    from data_accelerator_tpu.serve.storage import (
        LocalDesignTimeStorage,
        LocalRuntimeStorage,
    )

    return FlowOperation(
        LocalDesignTimeStorage(str(tmp_path / "d")),
        LocalRuntimeStorage(str(tmp_path / "r")),
        fleet_admission=False,
    )


def _conf_dict(conf_path):
    conf = {}
    for line in open(conf_path, encoding="utf-8"):
        if "=" in line:
            k, _, v = line.partition("=")
            conf[k] = v.rstrip("\n")
    return conf


def test_generation_embeds_mesh_model_for_mesh_jobs(tmp_path):
    gui = load_flow("clean_config2_window_agg")
    gui["name"] = "mesh-embed"
    gui.setdefault("process", {}).setdefault("jobconfig", {})[
        "jobNumChips"] = "8"
    fo = _flow_ops(tmp_path)
    fo.save_flow(gui)
    res = fo.generate_configs("mesh-embed")
    assert res.ok, res.errors
    conf = _conf_dict(res.conf_paths[0])
    model = json.loads(conf["datax.job.process.mesh.model"])
    assert model["totals"]["chips"] == 8
    assert model["totals"]["iciWireBytesPerBatch"] > 0
    assert model["totals"]["reshardCount"] >= 1
    assert any(s["axis"] == "replicated" for s in model["stages"])
    # the model round-trips through the conf parser the host uses
    from data_accelerator_tpu.core.config import parse_conf_lines

    props = parse_conf_lines(
        open(res.conf_paths[0], encoding="utf-8").readlines()
    )
    assert json.loads(props["datax.job.process.mesh.model"]) == model


def test_generation_skips_mesh_model_for_single_chip(tmp_path):
    gui = load_flow("clean_config2_window_agg")
    gui["name"] = "mesh-single"
    fo = _flow_ops(tmp_path)
    fo.save_flow(gui)
    res = fo.generate_configs("mesh-single")
    assert res.ok, res.errors
    assert "mesh.model" not in open(res.conf_paths[0]).read()


def test_generation_mesh_model_opt_out(tmp_path):
    gui = load_flow("clean_config2_window_agg")
    gui["name"] = "mesh-optout"
    gui.setdefault("process", {}).setdefault("jobconfig", {}).update(
        {"jobNumChips": "8", "jobMeshModel": "false"}
    )
    fo = _flow_ops(tmp_path)
    fo.save_flow(gui)
    res = fo.generate_configs("mesh-optout")
    assert res.ok, res.errors
    assert "mesh.model" not in open(res.conf_paths[0]).read()
