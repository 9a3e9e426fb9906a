"""The benchmark's harness at a tiny width against a CPU child of each
configuration: engine and plain reference agree row for row, the
low-precision control and a seeded wrong row do not, a run that finds no
TPU has no result, and the latency arithmetic gives hand-computed numbers.

One child a configuration, shared by the tests of this module (each costs
a jax start-up, a compile and a few 1 s batches)."""

import copy
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark import compare, control, run as bench, traffic, window

ROOT = bench.ROOT
TINY = {"traffic": {"declared_width": 2048, "rate_events_per_s": 1500},
        "warmup": {"min_batches": 7}}
CELLS = ["homeautomation.paced", "nexmark-q1.paced"]
GAP = {"homeautomation.paced": "avg_rel_gap",
       "nexmark-q1.paced": "price_rel_gap"}


@pytest.fixture(scope="module", params=CELLS)
def ran(request, tmp_path_factory):
    """The run the tests read is the SECOND in its directory: what the
    first left there (checkpoint, recorder, sink files) must not reach it."""
    run_dir = str(tmp_path_factory.mktemp("bench") / "run")
    bench.execute(request.param, 5, 4, False, run_dir=run_dir,
                  require_tpu=False, overrides=TINY)
    assert os.path.exists(os.path.join(run_dir, "checkpoint", "offsets.txt"))
    cell, run, m = bench.execute(
        request.param, 4_294_967_311, 5, False, run_dir=run_dir,
        require_tpu=False, overrides=TINY)
    return request.param, cell, run, m


def test_engine_and_reference_agree_row_for_row(ran):
    name, cell, run, m = ran
    verdict = bench.decide(run, cell, m)
    assert verdict["correct"], (verdict["compared"], verdict["notes"])
    assert verdict["compared"]["rows_differ"] == {"value": 0, "limit": 0}
    assert verdict["compared"][GAP[name]]["value"] < 1e-5
    assert verdict["rows_compared"] > 100
    assert verdict["committed_offset"] in m["bounds"][1:]
    # the sink's rename keeps the temp file's mtime: never after the span
    assert verdict["sink_mtime_after_span_ms"] <= 5.0
    assert run["rec"].device["platform"] == "cpu"
    assert m["e2e"]["events_per_s"] == pytest.approx(1500, rel=0.02)
    assert 0 < m["e2e"]["alert_latency_p50_ms"] < \
        m["e2e"]["alert_latency_p95_ms"] < 2500
    line = bench.result_line(cell, run, m, verdict, None)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == {e["name"] for e in cell["end_to_end"]}
    json.dumps(line)


def test_the_low_precision_control_is_not_correct(ran):
    name, cell, run, m = ran
    verdict = bench.decide(run, cell, m, got=cell["flow"].control)
    assert verdict["correct"] is False
    c = verdict["compared"][GAP[name]]
    assert c["value"] > 3 * c["limit"]
    assert verdict["compared"]["rows_differ"]["value"] == 0


def test_one_seeded_wrong_row_is_not_correct(ran):
    name, cell, run, m = ran
    wrong = dict(m, events=copy.deepcopy(m["events"]))
    at = int(m["bounds"][2]) + 3  # an event of the third batch
    if name == "homeautomation.paced":
        wrong["events"]["type"][at] = 0
        wrong["events"]["status"][at] = 0  # the reference now sees an alert
    else:
        wrong["events"]["bidder"][at] += 1
    verdict = bench.decide(run, cell, wrong)
    assert verdict["correct"] is False
    assert verdict["compared"]["rows_differ"]["value"] >= 1
    assert any("batch 2" in n for n in verdict["notes"])


def test_a_run_that_finds_no_tpu_has_no_result(ran, capsys, monkeypatch,
                                               tmp_path):
    name = ran[0]
    real = bench.execute

    def tiny(*a, **k):
        return real(*a, run_dir=str(tmp_path / "run"), overrides=TINY, **k)

    monkeypatch.setattr(bench, "execute", tiny)
    rc = bench.main(["--workload", name, "--seed", "9", "--seconds", "2"])
    out = capsys.readouterr()
    assert rc == 1 and out.out == ""
    assert "not a TPU" in out.err


def test_no_result_in_a_directory_with_the_benchmark_alone(tmp_path):
    """BENCHMARK.json and the files under ``paths`` and nothing else of
    the repo: the child cannot start; exit code 1, nothing on stdout."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for top in ("benchmark", "tests/benchmark_checks"):
        shutil.copytree(os.path.join(ROOT, top), tmp_path / top,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "2", "--trace", "0"],
        cwd=str(tmp_path), env=env, capture_output=True, timeout=120)
    assert done.returncode == 1
    assert done.stdout == b""
    assert b"no result" in done.stderr


def test_a_source_that_always_has_more(tmp_path):
    """The backlog mode (``homeautomation.saturated``: a mix with its file
    and no manifest entry yet): the parent keeps 3 declared widths ahead
    of what landed, every batch is as wide as the host lets it be, and
    the rows still agree."""
    cell, run, m = bench.execute(
        "homeautomation.saturated", 31, 4, False,
        run_dir=str(tmp_path / "run"), require_tpu=False,
        overrides={"traffic": {"declared_width": 2048,
                               "max_chunk_events": 512},
                   "warmup": {"min_batches": 7}})
    assert cell["end_to_end"] == [] and cell["per_layer"] == []
    assert all(m["rows"][k] == 2048 for k in m["window"])
    assert m["backlog_close"] <= 3 * 2048
    assert bench.decide(run, cell, m)["correct"]


def test_control_readings_come_from_one_run(tmp_path):
    got = control.readings(CELLS[1], 77, 4, run_dir=str(tmp_path / "run"),
                           require_tpu=False, overrides=TINY)
    assert got["program_correct"] and not got["control_correct"]
    assert got["control"]["price_rel_gap"] > 1e-3 > \
        got["program"]["price_rel_gap"]


# ---------------------------------------------------------------------------
# the arithmetic, on a hand-made schedule
# ---------------------------------------------------------------------------
def test_latency_arithmetic_on_a_hand_made_schedule():
    """Ten events due at 0.0, 0.1 .. 0.9 s (one a chunk). Batch 0 takes
    the first 4 and lands at 1.0 s, batch 1 the next 6 and lands at
    2.5 s. Alerts: events 1 and 3 (batch 0), 4, 7 and 9 (batch 1)."""
    lookup = bench.DueLookup(np.arange(11), np.arange(10) / 10.0)
    alerts = {0: np.array([1, 3]), 1: np.array([4, 7, 9])}
    lat = window.alert_latencies_ms(lookup, alerts, [1.0, 2.5])
    assert lat == pytest.approx([900, 700, 2100, 1800, 1600])
    assert window.percentile(lat, 50) == pytest.approx(1600)
    # numpy's linear rule: rank 0.95 * 4 = 3.8 between 1800 and 2100
    assert window.percentile(lat, 95) == pytest.approx(2040)
    assert list(window.batch_bounds([4, 6])) == [0, 4, 10]
    # chunks of several events: an event is due when its chunk is
    lookup = bench.DueLookup(np.array([0, 4, 8, 10]), np.array([0., .5, 1.]))
    assert list(lookup[np.array([0, 3, 4, 9])]) == [0.0, 0.0, 0.5, 1.0]


def test_window_and_rate_on_hand_made_landings():
    """Landings at 10, 11, 12.1, 13, 14.2 s; the window opens at the
    landing at 10 s and lasts 4 s: batches 1-3 are inside, the rate is
    their rows over the 3 s between the landings at 10 and 13."""
    landed = [10.0, 11.0, 12.1, 13.0, 14.2]
    win = window.in_window(landed, 10.0, 4.0)
    assert win == [1, 2, 3]
    assert window.events_per_s([5, 100, 110, 90, 7], landed, 10.0, win) \
        == pytest.approx(100.0)


QUIET, HIT, MISS = {}, {"Compile_Cache_Hit_Count": 1.0}, \
    {"Compile_Cache_Hit_Count": 0.0, "Compile_Cache_Miss_Count": 2.0}
WARM = {"min_batches": 7, "quiet_batches": 3, "steady_batches": 2,
        "steady_tolerance": 0.1, "max_behind_intervals": 1.5,
        "max_batches": 20}


@pytest.mark.parametrize("measurements,rows,behind,over", [
    # seven batches, the newest three quiet and steady: over
    ([MISS, MISS, QUIET, HIT] + [QUIET] * 3, [1000] * 7, 1200, True),
    # six batches: the ring is not full yet
    ([MISS, MISS, QUIET] + [QUIET] * 3, [1000] * 6, 1200, False),
    # a program loaded two batches ago (a transfer bucket, a checkpoint)
    ([MISS] + [QUIET] * 4 + [HIT, QUIET], [1000] * 7, 1200, False),
    # a compilation in the newest batch, long after the start: the sized
    # transfer's boost ran out
    ([MISS] + [QUIET] * 10 + [MISS], [1000] * 12, 1200, False),
    ([MISS] + [QUIET] * 10 + [MISS] + [QUIET] * 3, [1000] * 15, 1200, True),
    # quiet, and the newest batch is still half a batch (the ramp)
    ([MISS] + [QUIET] * 6, [1000] * 6 + [500], 1200, False),
    # quiet and steady, two intervals of arrivals not landed yet
    ([MISS] + [QUIET] * 6, [1000] * 7, 2000, False),
    # never steady: measured as it is after max_batches ...
    ([MISS] + [QUIET] * 19, [1000] * 19 + [500], 5000, True),
    # ... but a host that still compiles is waited for
    ([MISS] + [QUIET] * 18 + [HIT], [1000] * 20, 1200, False),
])
def test_warm_up_is_over_when_the_host_is_quiet_and_steady(
        measurements, rows, behind, over):
    sched = traffic.Schedule({"rate_events_per_s": 1000, "chunk_ms": 20})
    assert bench._warm(rows, behind, WARM, sched, 1.0, measurements) is over


def test_warm_up_without_a_quiet_rule_counts_batches_alone():
    """A mix whose file names ``min_batches`` only (the backlog mode)."""
    assert bench._warm([5] * 7, 0, {"min_batches": 7}, None, 1.0, [HIT] * 7)
    assert not bench._warm([5] * 6, 0, {"min_batches": 7}, None, 1.0,
                           [QUIET] * 6)
    assert bench.compiled(MISS) and bench.compiled(HIT)
    assert not bench.compiled({"Compile_Cache_Hit_Count": 0.0})


def test_paced_schedule_holds_its_mean_and_its_bursts():
    steady = traffic.Schedule({"rate_events_per_s": 1234, "chunk_ms": 20})
    assert steady.lo(0) == 0 and steady.lo(50) == 1234
    assert steady.lo(50 * 7) == 1234 * 7 and steady.due(50) == 1.0
    bursty = traffic.Schedule({
        "rate_events_per_s": 1000, "chunk_ms": 20,
        "profile": [[2.0, 3.0], [4.0, 0.0]]})
    assert bursty.lo(100) == 6000 and bursty.lo(300) == 6000
    assert bursty.lo(350) == 6000 + 3000 and not bursty.steady


def test_the_stream_depends_on_the_seed_alone():
    flow = bench.load_flow("nexmark_q1")
    a, b = traffic.EventStream(flow, 2**31 + 5), traffic.EventStream(
        flow, 2**31 + 5)
    whole = a.render(0, 40_000, 1790000000.0)
    parts = b.render(0, 777, 1790000000.0) + b.render(777, 40_000,
                                                      1790000000.0)
    assert whole == parts and len(whole) == 40_000 * flow.LINE_BYTES
    other = traffic.EventStream(flow, 2**31 + 6).render(0, 100, 1790000000.0)
    assert other != whole[:len(other)]
    row = json.loads(whole.splitlines()[39_999])
    ev = a.events(40_000)
    assert row["auction"] == ev["auction"][-1]
    assert row["dateTime"] == 1790000000000 == ev["due_ms"][-1]


def test_compare_counts_rows_and_takes_the_widest_gap():
    cols = {"T": {"k": "key", "n": "exact", "x": "x_gap"}}
    want = {"T": [{"k": np.array([1, 2]), "n": np.array([5, 6]),
                   "x": np.array([1.0, 2.0])}]}
    got = {"T": [{"k": np.array([2, 1]), "n": np.array([6, 5]),
                  "x": np.array([2.002, 1.0])}]}
    numbers, compared, notes = compare.compare_rows(cols, got, want)
    assert numbers == {"rows_differ": 0, "x_gap": pytest.approx(1e-3)}
    assert compared == 2 and notes == []
    got["T"][0]["n"] = np.array([6, 4])
    numbers, _c, notes = compare.compare_rows(cols, got, want)
    assert numbers["rows_differ"] == 1 and "row 0" in notes[0]
    numbers, _c, _n = compare.compare_rows(cols, {"T": [None]}, want)
    assert numbers["rows_differ"] == 2
    assert compare.offset_off_boundary(10, [0, 4, 10]) == 0
    assert compare.offset_off_boundary(7, [0, 4, 10]) == 1
    assert compare.offset_off_boundary(None, [0, 4, 10]) == 1
    ok, c = compare.verdict({"rows_differ": 0, "x_gap": 2e-3},
                            {"rows_differ": 0, "x_gap": 1e-3})
    assert not ok and c["x_gap"] == {"value": 2e-3, "limit": 1e-3}
