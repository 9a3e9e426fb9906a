"""``homeautomation-5m.paced`` (PR 32): the sample at its published
window against its plain reference on a CPU child at a 2,048-row width,
the window held as 301 slots of per-slot partial aggregates over 131,072
groups. The engine and the reference agree row for row, the bfloat16
control does not, the one fault this window state can have (the combine
skips a live slot) is seen, the program's new counters ride every batch,
and the window's roofline counts the bytes worked out here by hand."""

import json
import os
import sys

import numpy as np
import pytest

from benchmark import roofline, run as bench, window_roofline

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "homeautomation-5m.paced"
TINY = {"traffic": {"declared_width": 2048, "rate_events_per_s": 1500},
        "warmup": {"min_batches": 7}}
GROUPS, SLOTS = 131_072, 301


@pytest.fixture(scope="module")
def ran(tmp_path_factory):
    run_dir = str(tmp_path_factory.mktemp("bench5m") / "run")
    cell, run, m = bench.execute(CELL, 4_294_967_311, 5, False,
                                 run_dir=run_dir, require_tpu=False,
                                 overrides=TINY)
    return cell, run, m


def test_the_cell_is_the_sample_at_its_published_window():
    cell = bench.load_cell(CELL)
    config, flow = cell["config"], cell["flow"]
    assert config["window_seconds"] == 300
    assert "window_seconds" not in config["reduced"]
    assert "DataXProcessedInput_5minutes" in config["transform"]
    assert config["conf"]["datax.job.process.maxgroups"] == str(GROUPS)
    assert (flow.WINDOW_MS, flow.RING_SLOTS, flow.DEVICES) == (
        300_000, SLOTS, GROUPS)
    assert config["roofline"]["window"]["slots"] == SLOTS
    assert {m["name"] for m in cell["per_layer"]} >= {
        "window_partial_ms_per_batch", "window_combine_ms_per_batch",
        "window_combine_roofline_pct", "window_state_bytes",
        "checkpoint_window_bytes", "batch_busy_ms.5m"}
    ev = flow.make_events(3, 4096)
    assert ev["device"].min() >= 1 and ev["device"].max() <= GROUPS
    rows = flow.lines(ev, 0, 4096).splitlines()
    assert len(rows[0]) + 1 == flow.LINE_BYTES
    assert [json.loads(r)["deviceDetails"]["deviceId"] for r in rows] == \
        ev["device"].tolist()


def test_engine_and_reference_agree_row_for_row(ran):
    cell, run, m = ran
    verdict = bench.decide(run, cell, m)
    assert verdict["correct"], (verdict["compared"], verdict["notes"])
    assert verdict["compared"]["rows_differ"] == {"value": 0, "limit": 0}
    assert verdict["compared"]["avg_rel_gap"]["value"] < 1e-5
    assert verdict["compared"]["window_snapshot_missing"]["value"] == 0
    # HeatAvg lands a row a device the window has seen: more every batch
    assert verdict["rows_compared"] > 20_000
    assert verdict["committed_offset"] in m["bounds"][1:]
    line = bench.result_line(cell, run, m, verdict, None)
    assert set(line["metrics"]) == {
        "events_per_s", "alert_latency_p50_ms", "alert_latency_p95_ms",
        "setup_s"}


def test_the_low_precision_control_is_not_correct(ran):
    cell, run, m = ran
    verdict = bench.decide(run, cell, m, got=cell["flow"].control)
    assert verdict["correct"] is False
    # (``served.read_checkpoint`` deletes the snapshot it has sized, so a
    # second verdict on one run finds none: not the control's doing)
    over = {n for n, c in verdict["compared"].items()
            if c["value"] > c["limit"]} - {"window_snapshot_missing"}
    assert over == {"avg_rel_gap"}
    c = verdict["compared"]["avg_rel_gap"]
    assert c["value"] > 3 * c["limit"]


def test_the_window_state_counters_ride_every_batch(ran):
    _cell, run, _m = ran
    batches = [meas for _t, meas, _ts in run["rec"].batches]
    assert len(batches) >= 10
    cells = GROUPS * SLOTS
    for k, meas in enumerate(batches):
        # two [slots, groups] partials (row count, float32 sum), the key
        # directory (key + used a group), the slots' times and flags
        assert meas["Window_State_Bytes"] == cells * 8 + GROUPS * 5 + SLOTS * 5
        # a run is shorter than the window: every batch so far is live
        assert meas["Window_Slots_Live"] == k + 1
    # a checkpoint (every 5 s) writes the head and the slots written
    # since the one before: a few slots, never the state
    written = [meas["Checkpoint_Window_Bytes"] for meas in batches[1:]]
    head = GROUPS * 5
    assert all(head < b <= head + 8 * GROUPS * 8 + 65_536 for b in written), \
        sorted(set(written))
    assert max(written) < 0.03 * batches[0]["Window_State_Bytes"]
    slot_files = os.listdir(os.path.join(run["run_dir"], "checkpoint",
                                         "window-slots"))
    assert slot_files and all(f.startswith("HeatAvg.") for f in slot_files)


def test_a_combine_that_skips_a_live_slot_is_not_correct(tmp_path):
    line = bench.run_cell(
        CELL, 24_000_000_007, 4, False, run_dir=str(tmp_path / "run"),
        child_argv=[sys.executable, os.path.join(HERE, "broken_host_5m.py")],
        require_tpu=False, overrides=TINY)
    assert line["correct"] is False
    over = {n for n, c in line["compared"].items() if c["value"] > c["limit"]}
    assert "rows_differ" in over, line["compared"]


def test_the_windows_least_bytes_by_hand():
    shapes = bench.load_cell(CELL)["config"]["roofline"]
    need = window_roofline.least_bytes(shapes, GROUPS, 196_608, 131_000)
    assert need == {
        "batch_read": 196_608 * 2 * 4.0,          # key and temperature
        "slot_written": GROUPS * 2 * 4.0,         # count and sum a group
        "leaving_slot_read": GROUPS * 2 * 4.0,
        "running_state_read": GROUPS * 2 * 4.0,
        "output_written": 131_000 * 3 * 4.0,      # deviceId, Cnt, AvgT
    }
    assert sum(need.values()) == 6_290_592.0
    peak = roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    # 7.7 us at 819 GB/s: a step that spends 5 ms there reads 0.15 %
    assert 1000.0 * sum(need.values()) / peak == pytest.approx(0.007681, rel=1e-3)


def test_the_scope_readers_read_nothing_from_a_program_without_them(tmp_path):
    """The parent commit has neither scope: the readers return nothing
    and do not raise (``device_stages.json`` of a raw-row ring)."""
    run_dir = str(tmp_path)
    with open(os.path.join(run_dir, "device_stages.json"), "w",
              encoding="utf-8") as f:
        json.dump({"scopes": {"dx.ring": {"ms_per_batch": 0.24},
                              "dx.window": {"ms_per_batch": 0.02}}}, f)
    run = {"run_dir": run_dir, "xplane": {}}
    assert window_roofline.scope_ms(run, window_roofline.PARTIAL) is None
    assert window_roofline.scope_ms(run, window_roofline.COMBINE) is None
    assert window_roofline.roofline_pct({}, run, {}) is None
    with open(os.path.join(run_dir, "device_stages.json"), "w",
              encoding="utf-8") as f:
        json.dump({"scopes": {
            "dx.window.partial": {"ms_per_batch": 3.0},
            "dx.window.combine": {"ms_per_batch": 1.0}}}, f)
    assert window_roofline.scope_ms(run, window_roofline.PARTIAL) == 3.0
    cell = bench.load_cell(CELL)
    m = {"measurements": [
        {"Input_DataXProcessedInput_Events_Count": 196_608.0,
         "Output_HeatAvg_Events_Count": 131_000.0}]}
    run["rec"] = type("Rec", (), {"device": {"deviceKind": "TPU v5 lite"}})()
    assert window_roofline.roofline_pct(cell, run, m) == pytest.approx(
        100.0 * 0.007681 / 4.0, rel=1e-3)
    assert np.isfinite(window_roofline.roofline_pct(cell, run, m))
