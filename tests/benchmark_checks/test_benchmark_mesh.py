"""The four-chip cell (``homeautomation-mesh4.paced``) at a tiny width
against a CPU child laid over four of the eight forced CPU devices: engine
and plain reference agree row for row, the low-precision control does not,
the mesh's span and counter are there under a mesh and nowhere else, a
step that leaves the exchange between the chips out is seen, and the
readers of the mesh's capture give hand-computed numbers."""

import os
import shutil
import sys

import pytest

from benchmark import mesh, readers, run as bench
from benchmark.served import INPUT_ROWS

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "homeautomation-mesh4.paced"
TINY = {"traffic": {"declared_width": 2048, "rate_events_per_s": 1500},
        "warmup": {"min_batches": 7}}


def spans_of(run):
    rec = run["rec"]
    return [rec.spans(t) for t, _m, _ts in rec.batches]


@pytest.fixture(scope="module")
def ran(tmp_path_factory):
    run_dir = str(tmp_path_factory.mktemp("mesh") / "run")
    return bench.execute(CELL, 4_294_967_387, 5, False, run_dir=run_dir,
                         require_tpu=False, overrides=TINY)


def test_the_mesh_and_the_reference_agree_row_for_row(ran):
    cell, run, m = ran
    assert cell["chips"] == 4
    assert cell["config"]["conf"]["datax.job.process.numchips"] == "4"
    dev = run["rec"].device
    assert dev["stepDevices"] == 4 and dev["deviceCount"] == 8
    assert dev["ringDevices"] == {"DataXProcessedInput": 4}
    assert dev["rawDevices"] == {"default": 4}
    verdict = bench.decide(run, cell, m)
    assert verdict["correct"], (verdict["compared"], verdict["notes"])
    assert verdict["compared"]["rows_differ"] == {"value": 0, "limit": 0}
    assert verdict["compared"]["avg_rel_gap"]["value"] < 1e-5
    assert verdict["compared"]["window_snapshot_missing"]["value"] == 0
    assert verdict["rows_compared"] > 100
    assert verdict["committed_offset"] in m["bounds"][1:]
    line = bench.result_line(cell, run, m, verdict, None)
    assert set(line["metrics"]) == {
        "events_per_s", "alert_latency_p50_ms", "alert_latency_p95_ms",
        "setup_s"}
    assert line["device"]["count"] == 8  # what the child's jax saw


def test_the_low_precision_control_is_not_correct_on_the_mesh(ran):
    cell, run, m = ran
    verdict = bench.decide(run, cell, m, got=cell["flow"].control)
    assert verdict["correct"] is False
    gap = verdict["compared"]["avg_rel_gap"]
    assert gap["value"] > 3 * gap["limit"]
    assert verdict["compared"]["rows_differ"]["value"] == 0


def test_the_meshs_span_and_counters_are_on_every_batch(ran):
    _cell, run, _m = ran
    for _t, measurements, _ts in run["rec"].batches:
        assert measurements["Mesh_Chips"] == 4.0
        assert measurements["Mesh_ICI_Bytes"] > 0
        assert measurements["Mesh_Reshard_Count"] >= 1
    for spans in spans_of(run):
        assert "shard-put" in spans
        start, ms = spans["shard-put"]
        lo, whole = spans["decode"]
        assert lo <= start and start + ms / 1e3 <= lo + whole / 1e3 + 1e-3


def test_without_a_mesh_there_is_no_such_span_or_counter(tmp_path):
    _cell, run, _m = bench.execute(
        "homeautomation.paced", 4_294_967_389, 5, False,
        run_dir=str(tmp_path / "run"), require_tpu=False, overrides=TINY)
    assert run["rec"].device["stepDevices"] == 1
    for _t, measurements, _ts in run["rec"].batches:
        assert not [k for k in measurements if k.startswith("Mesh_")]
    assert not any("shard-put" in spans for spans in spans_of(run))


def test_a_step_that_leaves_the_exchange_out_is_not_correct(tmp_path):
    """Each shard's partial ``HeatAvg`` landed as if whole: the counts of
    a key are a quarter of the window's, and the rows differ."""
    line = bench.run_cell(
        CELL, 24_000_000_019, 5, False, run_dir=str(tmp_path / "run"),
        child_argv=[sys.executable,
                    os.path.join(HERE, "broken_host_mesh.py")],
        require_tpu=False, overrides=TINY)
    assert line["correct"] is False
    assert line["compared"]["rows_differ"]["value"] >= 1
    assert line["compared"]["offset_off_boundary"]["value"] == 0


def test_a_four_chip_conf_on_fewer_chips_is_an_error(tmp_path):
    """No smaller mesh, no fallback: the child sees two devices, the conf
    asks for four, and the run has no result."""
    import subprocess

    from benchmark import served

    cell = bench.load_cell(CELL)
    conf = served.write_conf(str(tmp_path / "run"), cell["config"], 2048,
                             served.free_port(), None)
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_NUM_CPU_DEVICES="2",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    done = subprocess.run(
        [sys.executable, "-m", "data_accelerator_tpu.runtime.host",
         f"conf={conf}", "batches=1"],
        cwd=bench.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode != 0
    assert "process.numchips=4" in done.stderr
    assert "only 2 available" in done.stderr


# ---------------------------------------------------------------------------
# the readers of the mesh's capture, on hand-made planes
# ---------------------------------------------------------------------------
def plane(collective, lo, hi):
    """Three runs of the step 1 s apart, 0.1 s each: a fusion, then one
    collective from ``lo`` to ``hi`` into the run, then a fusion whose
    operand list names a collective (which is none itself)."""
    ops, modules = [], []
    for k in (0.0, 1.0, 2.0):
        modules.append(("jit_step(77)", k, k + 0.1))
        ops += [
            ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p.0), kind=kLoop",
             k, k + lo),
            (collective, k + lo, k + hi),
            ("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %all-gather.3), "
             "kind=kLoop", k + hi, k + 0.1),
        ]
    return {"ops": ops, "async": [], "modules": modules}


def test_collective_time_and_busy_chips_on_hand_made_planes():
    """Two planes ran the step, one collective a run each: 20 ms of
    ``all-gather`` on the first, 40 ms of ``all-reduce-done`` on the
    second; a third plane ran nothing. Mean over the two planes that ran
    anything: (20 + 40) / 2 = 30 ms a batch; two chips busy."""
    planes = {
        "/device:TPU:0": plane(
            "%all-gather.3 = f32[6,8]{1,0} all-gather(f32[6,2]{1,0} %c.1), "
            "channel_id=1", 0.06, 0.08),
        "/device:TPU:1": plane(
            "%all-reduce-done.2 = f32[8]{0} all-reduce-done(%all-reduce-"
            "start.2)", 0.05, 0.09),
        "/device:TPU:2": {"ops": [], "async": [], "modules": []},
    }
    numbers, people = mesh.reduce(planes)
    assert numbers["mesh_chips_busy"] == 2.0
    assert numbers["mesh_collective_ms_per_batch"] == pytest.approx(30.0)
    assert people["batches"] == 3 and people["window_s"] == pytest.approx(3.0)
    assert people["planes_that_ran_the_step"] == ["/device:TPU:0",
                                                  "/device:TPU:1"]
    kinds = people["by_kind"]
    assert set(kinds) == {"all-gather", "all-reduce"}
    assert kinds["all-gather"]["ops_ms_per_batch"] == pytest.approx(10.0)
    assert kinds["all-reduce"]["ops_ms_per_batch"] == pytest.approx(20.0)
    assert kinds["all-gather"]["events_per_batch"] == pytest.approx(0.5)
    # a plane that ran another program alone does not count as busy
    planes["/device:TPU:2"] = {
        "ops": [("%copy.1 = f32[8]{0} copy(%p.0)", 0.5, 0.6)], "async": [],
        "modules": [("jit_convert(5)", 0.5, 0.6)]}
    numbers, _people = mesh.reduce(planes)
    assert numbers["mesh_chips_busy"] == 2.0
    assert numbers["mesh_collective_ms_per_batch"] == pytest.approx(20.0)


@pytest.mark.parametrize("name,kind", [
    ("%all-gather.16 = pred[6,262144]{1,0:T(8,128)(4,1)S(1)} all-gather("
     "%copy-done.9), channel_id=1", "all-gather"),
    ("%all-reduce.4 = s32[16]{0:T(128)S(1)} all-reduce(%dus.11)",
     "all-reduce"),
    ("%collective-permute-start = (s32[3,1]{1,0}, s32[3,1]{1,0}) "
     "collective-permute-start(%slice.101)", "collective-permute"),
    ("%collective-permute-done.1 = s32[1]{0} collective-permute-done("
     "%collective-permute-start.1)", "collective-permute"),
    ("all-to-all.2", "all-to-all"),
    ("%reduce-scatter.5 = f32[2]{0} reduce-scatter(%p.1)", "reduce-scatter"),
    ("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %all-gather.3), kind=kLoop",
     None),
    ("%all-gather_fusion = f32[8]{0} fusion(%p.0), kind=kLoop", None),
    ("%reduce-window.3 = s32[96,128]{1,0} reduce-window(%p.2)", None),
])
def test_a_collective_is_told_by_the_instructions_own_name(name, kind):
    assert mesh.kind_of(name) == kind


def test_a_one_chip_capture_reads_one_plane_and_no_collective(tmp_path):
    """The recorded one-chip capture through the helper process, as the
    readers under ``layers/`` call it; parsed once a run."""
    shutil.copy(os.path.join(os.path.dirname(mesh.__file__), "trace_sample",
                             "sample_annotated.xplane.pb"), tmp_path)
    run = {"profile": {"path": str(tmp_path)}, "run_dir": str(tmp_path)}
    cell = {"chips": 1}
    assert readers.read_one("mesh_chips_busy", cell, run, {}, {}) == 1.0
    os.remove(tmp_path / "sample_annotated.xplane.pb")  # kept on the run
    assert readers.read_one("mesh_collective_ms_per_batch", cell, run, {},
                            {}) == 0.0
    assert os.path.exists(tmp_path / "mesh_collectives.json")


class _Rec:
    device = {"deviceKind": "TPU v5 lite"}


def test_the_four_chip_roofline_share_is_a_quarter_of_the_one_chip_share():
    cell = bench.load_cell(CELL)
    run = {"rec": _Rec()}
    m = {"measurements": [{INPUT_ROWS: 196_608.0,
                           "Output_OpenDoors_Events_Count": 1_966.0,
                           "Output_HeatAvg_Events_Count": 8.0}]}
    trace = {"device_busy_ms_per_batch": 108.0}
    one = readers.read_one("step_roofline_pct", cell, run, m, trace)
    four = readers.read_one("step_roofline_pct.mesh4", cell, run, m, trace)
    assert one == pytest.approx(0.0196, rel=0.02)
    assert four == pytest.approx(one / 4)
    assert readers.read_one("step_roofline_pct.mesh4", cell, run, m, {}) \
        is None


@pytest.mark.parametrize("name,key,values,want", [
    ("mesh_ici_bytes_per_batch", "Mesh_ICI_Bytes", [5e7, 5e7, 6e7], 5e7),
    ("mesh_shard_put_ms", "shard-put", [4.0, 9.0, 5.0], 5.0),
])
def test_the_declared_mesh_readers_and_a_program_without_them(
        name, key, values, want):
    """The median over the window's batches; a program that has no such
    span or counter (the parent of the PR that added them) gives nothing,
    and the line leaves the metric out."""
    m = {"measurements": [{key: v} for v in values],
         "spans": [{key: (0.0, v)} for v in values]}
    assert readers.read_one(name, {}, {}, m, {}) == want
    bare = {"measurements": [{"Latency-Batch": 1.0}],
            "spans": [{"decode": (0.0, 1.0)}]}
    assert readers.read_one(name, {}, {}, bare, {}) is None
    cell = {"per_layer": [{"name": name, "unit": "x"}]}
    assert readers.read_all(cell, {}, bare, {}) == {}
