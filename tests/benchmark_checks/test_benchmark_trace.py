"""``benchmark/trace.py`` on the small recorded trace kept beside it gives
the busy time, idle share and op table worked out by hand;
``benchmark/roofline.py`` on the two flows' shapes gives the byte counts
its docstring describes, and an unknown device kind raises."""

import json
import os

import pytest

from benchmark import roofline, trace

BENCH = os.path.dirname(os.path.abspath(trace.__file__))
SAMPLE = os.path.join(BENCH, "trace_sample")


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json"),
              encoding="utf-8") as f:
        return json.load(f)


def test_union_and_gaps_by_hand():
    spans = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)]
    assert trace.union_s(spans) == pytest.approx(3.0)
    assert trace.gaps(spans, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]
    assert trace.gaps(spans, 0.5, 3.5) == [(2.0, 3.0)]


def test_reduce_on_hand_made_planes():
    """Two bursts of device work 1 s apart on one plane; the host's spans
    (wall clock = trace clock + 1000 s) cover the gap with 0.3 s of
    ``collect``, 0.1 s of ``sinks`` and 0.5 s of pacing sleep."""
    ops = [("fusion.1", 0.000, 0.040), ("sort.2", 0.040, 0.100),
           ("fusion.1", 1.000, 1.030), ("sort.2", 1.030, 1.100)]
    batches = [
        {"streaming/batch": [999.9, 600.0], "dispatch": [999.995, 5.0],
         "collect": [1000.1, 300.0], "sinks": [1000.4, 100.0]},
        {"streaming/batch": [1000.95, 400.0], "dispatch": [1000.995, 5.0]},
        {"streaming/batch": [1001.95, 400.0], "dispatch": [1001.995, 5.0]},
    ]
    modules = [("jit_step(1)", 0.0, 0.1), ("jit_convert(2)", 0.5, 0.5001),
               ("jit_step(1)", 1.0, 1.1)]
    out = trace.reduce({"/device:TPU:0": {"ops": ops, "modules": modules}},
                       batches)
    assert out["busy_s"] == pytest.approx(0.2)
    # two whole periods of 1 s, from the first step's start
    assert out["window_s"] == pytest.approx(2.0)
    assert out["device_idle_pct"] == pytest.approx(90.0)
    assert out["batches"] == 2
    assert out["device_busy_ms_per_batch"] == pytest.approx(100.0)
    assert out["breakdown"]["device_ops"] == [
        ["sort.2", pytest.approx(0.13)], ["fusion.1", pytest.approx(0.07)]]
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert gaps["collect"] == pytest.approx(0.3)
    assert gaps["sinks"] == pytest.approx(0.1)
    # 1000.5 -> 1000.95 in the first gap, 1001.35 -> 1001.95 in the second
    assert gaps["pacing_sleep"] == pytest.approx(0.45 + 0.6)
    assert sum(gaps.values()) == pytest.approx(1.8)
    with pytest.raises(ValueError):
        trace.reduce({}, batches)


def test_reduce_on_the_recorded_trace():
    """A capture taken on the chip (TPU v5 lite) during this PR's first
    traced run, cut to its device planes; ``expected.json`` beside it
    holds the numbers worked out by hand from its event list."""
    from jax.profiler import ProfileData

    with open(os.path.join(SAMPLE, "expected.json"), encoding="utf-8") as f:
        want = json.load(f)
    with open(os.path.join(SAMPLE, "spans.json"), encoding="utf-8") as f:
        batches = json.load(f)["batches"]
    planes = trace.device_planes(ProfileData.from_file(
        os.path.join(SAMPLE, "sample.xplane.pb")))
    assert sorted(planes) == want["planes"]
    out = trace.reduce(planes, batches)
    for key in ("busy_s", "window_s", "device_idle_pct",
                "device_busy_ms_per_batch"):
        assert out[key] == pytest.approx(want[key], rel=1e-6), key
    assert out["batches"] == want["batches"]
    top = out["breakdown"]["device_ops"][:3]
    assert [n for n, _s in top] == [n for n, _s in want["top_ops"]]
    for (_n, got), (_m, exp) in zip(top, want["top_ops"]):
        assert got == pytest.approx(exp, rel=1e-6)


def test_roofline_bytes_of_the_two_flows():
    home = config("homeautomation-5s")["roofline"]
    rows = 262_144
    w = roofline.work(home, rows, {"OpenDoors": 2_621, "HeatAvg": 8})
    values = (
        (5 + 1) * rows      # packed input, read once, with its validity row
        + 2 * rows          # the rule reads deviceType and status
        + 6 * rows * 2      # the window's live rows x (deviceId, temperature)
        + rows * 2          # the slot written
        + 2_621 * 2 + 8 * 3)  # output rows
    assert w["bytes"] == 4 * values == 23_089_736
    assert w["ops"] == 2 * rows + 2 * 6 * rows
    least = roofline.least_time(home, rows, {"OpenDoors": 2_621, "HeatAvg": 8},
                                "TPU v5 lite")
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(23_089_736 / 819e9)
    q1 = config("nexmark-q1")["roofline"]
    w = roofline.work(q1, 30_000, {"Q1": 30_000})
    assert w["bytes"] == 4 * ((4 + 1) * 30_000 + 4 * 30_000)
    assert w["ops"] == 30_000


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_the_recorded_traces_idle_gaps_are_attributed():
    """The recorder's wall clock is aligned with the trace's from outside
    (step starts against dispatch ends): most of the idle time of a paced
    cell is the loop's pacing sleep."""
    from jax.profiler import ProfileData

    with open(os.path.join(SAMPLE, "expected.json"), encoding="utf-8") as f:
        want = json.load(f)
    with open(os.path.join(SAMPLE, "spans.json"), encoding="utf-8") as f:
        host = json.load(f)
    planes = trace.device_planes(ProfileData.from_file(
        os.path.join(SAMPLE, "sample.xplane.pb")))
    out = trace.reduce(planes, host["batches"], host["posted_at"])
    got = out["breakdown"]["idle_gaps"]
    assert [n for n, _t in got] == [n for n, _t in want["idle_gaps"]]
    assert got[0][0] == "pacing_sleep"
    idle_s = out["window_s"] - out["busy_s"]
    assert sum(t for _n, t in got) == pytest.approx(idle_s, rel=0.01)
    # no alignment, no attribution
    out = trace.reduce(planes, [], None)
    assert out["breakdown"]["idle_gaps"][0][0] == "unattributed"


def test_cut_keeps_the_device_planes_alone():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "trace_sample_cut", os.path.join(SAMPLE, "cut.py"))
    cut = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cut)
    with open(os.path.join(SAMPLE, "sample.xplane.pb"), "rb") as f:
        data = f.read()
    assert cut.cut(data) == data  # already cut: nothing more goes
    assert len(cut.cut(data, prefix="/host:")) < 100  # the host names


def test_readers_declared_and_coded(tmp_path, monkeypatch):
    """A per-layer metric is a file of its own: a declaration or, for
    what no declaration covers, code; one that finds nothing to read
    returns nothing and the metric is left out of the line."""
    from benchmark import readers

    monkeypatch.setattr(readers, "LAYERS", str(tmp_path))
    (tmp_path / "a_ms.json").write_text(json.dumps(
        {"reader": {"from": "span", "key": "decode", "stat": "median"}}))
    (tmp_path / "b.json").write_text(json.dumps(
        {"reader": {"from": "measurement", "key": "Missing", "stat": "mean"}}))
    (tmp_path / "c.x.py").write_text(
        "def read(cell, run, m, trace):\n    return trace['busy_s'] * 2\n")
    (tmp_path / "d_pct.json").write_text(json.dumps(
        {"reader": {"from": "trace", "key": "device_idle_pct"}}))
    m = {"spans": [{"decode": (0.0, 10.0)}, {"decode": (1.0, 30.0)}, {}],
         "measurements": [{}, {}], "send_late_ms": []}
    cell = {"per_layer": [{"name": n, "unit": "ms"}
                          for n in ("a_ms", "b", "c.x", "d_pct")]}
    out = readers.read_all(cell, {}, m, {"busy_s": 0.25})
    assert out == {"a_ms": {"value": 20.0, "unit": "ms"},
                   "c.x": {"value": 0.5, "unit": "ms"}}
