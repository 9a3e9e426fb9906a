"""``benchmark/xplane.py`` on a capture taken on the chip with PR 25's
program (``sample_annotated.xplane.pb``: TPU v5 lite, ``homeautomation.paced``,
host plane kept, no Python frames) gives the numbers ``expected_annotated.json``
holds, which were worked out apart from it; on the older sample, whose
program named nothing, every new reader finds nothing to read."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmark import trace, xplane

BENCH = os.path.dirname(os.path.abspath(trace.__file__))
SAMPLE = os.path.join(BENCH, "trace_sample")
ANNOTATED = os.path.join(SAMPLE, "sample_annotated.xplane.pb")
NEW_READERS = ["device_query_ms_per_batch", "device_window_ms_per_batch",
               "device_egress_ms_per_batch", "host_serial_ms_per_batch",
               "idle_unattributed_pct"]


def load(name):
    with open(os.path.join(SAMPLE, name), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced():
    host = load("batches_annotated.json")
    return xplane.reduce(xplane.load_space(ANNOTATED), host["batches"],
                         host["posted_at"])


def test_the_sample_is_small_and_keeps_its_host_plane():
    assert os.path.getsize(ANNOTATED) < 2 * 1024 * 1024
    space = xplane.load_space(ANNOTATED)
    assert xplane.HOST_PLANE in [p.name for p in space.planes]
    names = {n[0] for n in xplane.annotations(space)}
    assert {"dx/decode", "dx/source-poll", "dx/native-decode", "dx/dispatch",
            "dx/sync", "dx/collect", "dx/sinks", "dx/emit", "dx/checkpoint",
            "dx/pace"} <= names


def test_stage_times_add_up_to_the_busy_time(reduced):
    numbers, stages, _host = reduced
    want = load("expected_annotated.json")
    parts = [numbers[f"device_{k}_ms_per_batch"]
             for k in ("query", "window", "egress", "unscoped")]
    assert sum(parts) == pytest.approx(numbers["device_busy_ms_per_batch"],
                                       abs=1e-9)
    # ... which is trace.py's own, from the same file through ProfileData
    from jax.profiler import ProfileData

    outside = trace.reduce(
        trace.device_planes(ProfileData.from_file(ANNOTATED)), [], None)
    assert numbers["device_busy_ms_per_batch"] == pytest.approx(
        outside["device_busy_ms_per_batch"], abs=0.1)
    assert stages["window_s"] == pytest.approx(outside["window_s"], abs=1e-6)
    for key in ("device_busy_ms_per_batch", "device_query_ms_per_batch",
                "device_window_ms_per_batch", "device_egress_ms_per_batch",
                "device_unscoped_ms_per_batch"):
        assert numbers[key] == pytest.approx(want[key], abs=2e-3), key
    got = {k: v["ms_per_batch"] for k, v in stages["scopes"].items()}
    assert set(got) == set(want["scope_ms_per_batch"])
    for scope, ms in want["scope_ms_per_batch"].items():
        assert got[scope] == pytest.approx(ms, abs=2e-3), scope
    # the acceptance line: scoped device time >= 95 % of the busy time
    assert sum(parts[:3]) >= 0.95 * numbers["device_busy_ms_per_batch"]
    assert stages["scopes"]["dx.view.HeatAvg"]["top_source"].endswith(
        "ops/groupby.py:56")


def test_idle_by_annotation_adds_up_to_the_idle_time(reduced):
    numbers, _stages, host = reduced
    want = load("expected_annotated.json")
    assert host["periods"] == want["host_periods"]
    assert sum(host["idle_s_by_annotation"].values()) == pytest.approx(
        host["idle_s"], abs=1e-9)
    assert host["idle_s"] == pytest.approx(want["host_idle_s"], abs=1e-5)
    for name, t in want["idle_s_by_annotation"].items():
        assert host["idle_s_by_annotation"][name] == pytest.approx(
            t, abs=1e-5), name
    assert numbers["host_serial_ms_per_batch"] == pytest.approx(
        want["host_serial_ms_per_batch"], abs=0.01)
    assert numbers["idle_unattributed_pct"] == pytest.approx(
        want["idle_unattributed_pct"], abs=0.01)
    assert numbers["idle_unattributed_pct"] < 5.0
    # a nested annotation's share is part of its parent's, not of the sum
    inner = host["idle_s_by_inner_annotation"]
    assert inner["dx/source-poll"] + inner["dx/native-decode"] \
        <= host["idle_s_by_annotation"]["dx/decode"]


def test_both_planes_share_one_clock(reduced):
    """The offset read inside the trace (``dx/dispatch`` against the
    recorder's ``dispatch`` span) and ``trace.clock_offset`` (step starts
    against ``dispatch`` ends, from outside) differ by the stretch of
    ``dispatch`` that follows the step's start on the device, which the
    trace itself shows: what is left is under 2 ms (it reads 0.003)."""
    _numbers, _stages, host = reduced
    want = load("expected_annotated.json")
    assert host["offset_in_trace_s"] == pytest.approx(
        want["offset_in_trace_s"], abs=1e-6)
    assert host["dispatch_after_step_start_ms"] == pytest.approx(
        want["dispatch_after_step_start_ms"], abs=1e-6)
    assert host["outside_minus_in_trace_ms"] == pytest.approx(
        1000.0 * (host["offset_outside_s"] - host["offset_in_trace_s"]))
    assert host["clocks_differ_ms"] < 2.0
    assert abs(host["outside_minus_in_trace_ms"]) < 1000.0 * trace.ALIGN_S


def test_by_hand_on_two_threads():
    """Two idle stretches; the loop's thread holds ``dx/collect`` with a
    nested ``dx/materialize`` and then ``dx/pace``, a sink's thread
    ``dx/sink/file`` under the tail of ``dx/collect`` and beyond it."""
    notes = [("dx/collect", 1.0, 2.0, True, 7),
             ("dx/materialize", 1.2, 1.8, False, 7),
             ("dx/sink/file", 1.9, 2.3, True, 7),
             ("dx/pace", 2.5, 3.5, True, None)]
    idle = [(0.5, 2.4), (2.6, 3.0)]
    got = xplane.idle_by_annotation(idle, notes)
    assert got == pytest.approx({"dx/collect": 1.0, "dx/sink/file": 0.3,
                                 "dx/pace": 0.4, "unattributed": 0.6})
    assert sum(got.values()) == pytest.approx(xplane.total(idle))
    assert xplane.inner_idle(idle, notes) == pytest.approx(
        {"dx/materialize": 0.6})
    assert xplane.left_by([(0.0, 1.0), (2.0, 3.0)], [(0.5, 2.5)]) == [
        (0.0, 0.5), (2.5, 3.0)]
    assert xplane.dispatch_tail(
        [("dx/dispatch", 0.9, 1.05, True, 7)], [(1.0, 1.4)]) \
        == pytest.approx(0.05)
    # device time is exclusive: a nested operation adds nothing, one that
    # starts outside every run of the step is the egress helpers'
    planes = {"/device:TPU:0": {"modules": [], "ops": [
        ("while", 1.0, 1.4, "dx.view.A", "a.py:1", 10),
        ("body", 1.1, 1.2, "dx.view.A", "a.py:2", 5),
        ("copy", 1.4, 1.5, None, "", 1),
        ("slice", 1.6, 1.7, "dx.compact.X", "p.py:3", 2)]}}
    by = xplane.device_stages(planes, 1.0, 2.0, [(1.0, 1.5)])
    assert {k: v["s"] for k, v in by.items()} == pytest.approx(
        {"dx.view.A": 0.4, "unscoped": 0.1, "outside_step": 0.1})
    assert by["dx.view.A"]["bytes"] == 15


def test_a_capture_without_names_gives_every_new_reader_nothing():
    """The older sample (PR 24's program: no scope, no annotation, device
    planes only): every new reader returns ``None`` and none raises, which
    is what a parent commit gives the driver under this PR's files."""
    host = load("spans.json")
    batches = [[0, spans] for spans in host["batches"]]
    numbers, stages, idle = xplane.reduce(
        xplane.load_space(os.path.join(SAMPLE, "sample.xplane.pb")),
        batches, host["posted_at"])
    assert all(numbers[name] is None for name in NEW_READERS)
    assert set(stages["scopes"]) == {"unscoped", "outside_step"}
    assert idle["offset_in_trace_s"] is None
    assert idle["idle_s_by_annotation"] == {
        "unattributed": pytest.approx(idle["idle_s"])}
    run = {"xplane": numbers}
    for name in NEW_READERS:
        code = os.path.join(BENCH, "layers", name + ".py")
        spec = importlib.util.spec_from_file_location("layer_" + name, code)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        assert module.read({}, run, {}, {}) is None


def test_the_helper_process_parses_once_for_all_readers(tmp_path):
    """``stages`` runs the file as a helper (the parent stays off
    protobuf), writes the two files for people into the run directory and
    keeps the numbers on the run: a second reader costs nothing."""
    capture = tmp_path / "capture" / "plugins" / "profile" / "x"
    capture.mkdir(parents=True)
    os.symlink(ANNOTATED, capture / "vm.xplane.pb")
    host = load("batches_annotated.json")

    class Rec:
        batches = [(t, {}, 0.0) for t, _spans in host["batches"]]

        def spans(self, t):
            return dict(host["batches"])[t]

    run = {"rec": Rec(), "run_dir": str(tmp_path),
           "profile": {"path": str(tmp_path / "capture")},
           "profile_posted_at": host["posted_at"]}
    before = set(sys.modules)
    got = xplane.stages(run)
    assert not {m for m in set(sys.modules) - before
                if m.startswith(("tensorflow", "google.protobuf"))}
    want = load("expected_annotated.json")
    for name in NEW_READERS:
        assert got[name] == pytest.approx(want[name], abs=0.01), name
    assert xplane.stages(run) is got
    for name in ("device_stages.json", "host_idle.json"):
        with open(tmp_path / name, encoding="utf-8") as f:
            assert json.load(f)
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "xplane.py"), "missing.pb",
         str(tmp_path / "xplane_batches.json"), str(tmp_path)],
        capture_output=True, text=True, timeout=120)
    assert done.returncode != 0
