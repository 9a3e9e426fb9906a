"""``homeautomation-5m-eventtime.paced`` (PR 34): the sample on the
sensors' own clock against its plain reference on a CPU child at a
2,048-row width. One event in ten is stamped up to 3 s before it is sent,
one in a thousand 3-30 s, under a 10 s watermark; the window is held as
312 slots of per-slot partial aggregates, a slot a second of event time.
The engine and the reference agree row for row with the window in either
state (partials, and the raw-row ring a join forces), the bfloat16
control does not, the one fault an event-time window can have (late rows
counted in their batch's own second) is seen, the program's new counters
ride every batch, and the window's roofline counts the bytes worked out
here by hand."""

import json
import os
import sys

import numpy as np
import pytest

from benchmark import roofline, run as bench, window_roofline_eventtime

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "homeautomation-5m-eventtime.paced"
TINY = {"traffic": {"declared_width": 2048, "rate_events_per_s": 1500},
        "warmup": {"min_batches": 7}}
GROUPS, SLOTS = 131_072, 312


@pytest.fixture(scope="module")
def ran(tmp_path_factory):
    run_dir = str(tmp_path_factory.mktemp("bench5mev") / "run")
    return bench.execute(CELL, 4_294_967_311, 14, False, run_dir=run_dir,
                         require_tpu=False, overrides=TINY)


def test_the_cell_is_the_sample_on_the_sensors_own_clock():
    cell = bench.load_cell(CELL)
    config, flow = cell["config"], cell["flow"]
    sibling = bench.load_cell("homeautomation-5m.paced")
    assert cell["mix"]["traffic"] == sibling["mix"]["traffic"]
    assert cell["mix"]["warmup"] == sibling["mix"]["warmup"]
    assert config["transform"] == sibling["config"]["transform"]
    assert config["reduced"] == sibling["config"]["reduced"]
    assert config["window_seconds"] == 300
    assert config["conf"]["datax.job.process.watermark"] == "10 second" == \
        config["guarantees"]["watermark"]
    assert config["conf"]["datax.job.process.projection"].startswith(
        "deviceDetails.eventTime AS eventTimeStamp")
    assert config["conf"]["datax.job.process.maxgroups"] == str(GROUPS)
    assert "runtime/timewindow.py" in config["guarantees"]["window_contents"]
    fields = config["schema"]["fields"][0]["type"]["fields"]
    assert fields[-1]["name"] == "eventTime" and \
        fields[-1]["type"] == "timestamp"
    assert fields[:-1] == sibling["config"]["schema"]["fields"][0]["type"][
        "fields"]
    assert (flow.WINDOW_MS, flow.WATERMARK_MS, flow.RING_SLOTS,
            flow.DEVICES) == (300_000, 10_000, SLOTS, GROUPS)
    assert config["roofline"]["window"]["slots"] == SLOTS
    assert {m["name"] for m in cell["per_layer"]} == {
        "window_partial_ms_per_batch.et", "window_combine_ms_per_batch.et",
        "window_fold_roofline_pct.et", "window_late_rows.et",
        "window_slots_touched.et", "window_state_bytes.et",
        "checkpoint_window_bytes.et", "checkpoint_ms.et", "batch_busy_ms.et",
        "decode_ms.et", "device_wait_ms.et", "sinks_ms.et",
        "device_busy_ms_per_batch.et", "device_idle_pct.et",
        "step_roofline_pct.et",
        # the namesakes of the layers the cell runs beside the window's
        "device_egress_ms_per_batch.et", "device_window_ms_per_batch.et",
        "device_query_ms_per_batch.et", "collect_ms.et",
        "source_poll_ms.et", "emit_ms.et", "host_serial_ms_per_batch.et",
        "idle_unattributed_pct.et", "generator_late_p95_ms.et"}
    ev = flow.make_events(3, 1 << 16)
    ev["due_ms"] = np.full(1 << 16, 1_700_000_000_123, np.int64)
    rows = flow.lines(ev, 0, 4096).splitlines()
    assert len(rows[0]) + 1 == flow.LINE_BYTES == 134
    parsed = [json.loads(r)["deviceDetails"] for r in rows]
    assert [p["deviceId"] for p in parsed] == ev["device"][:4096].tolist()
    assert [p["eventTime"] for p in parsed] == \
        (ev["due_ms"] - ev["delay_ms"])[:4096].tolist()
    delay = ev["delay_ms"]
    assert (delay > 0).mean() == pytest.approx(0.101, abs=0.006)
    assert (delay > 3000).mean() == pytest.approx(0.001, abs=0.0006)
    assert delay.max() <= 30_000 and delay.min() == 0
    # about 0.07 % of events arrive past a 10 s watermark (stamped more
    # than 11-12 s before they are sent)
    assert 0.0002 < (delay > 11_500).mean() < 0.0015


def test_the_reference_is_the_rule():
    """The flow module's reference on a hand-made stream: a row of second
    n - 11 is kept, one of n - 12 is dropped; a kept row is read from
    batch b + 12 on; OpenDoors shows every arrival with its own stamp."""
    flow = bench.load_cell(CELL)["flow"]
    t0 = 1_700_000_000_000
    n_ev = 4
    ev = {
        "device": np.array([7, 7, 8, 9], np.int32),
        "type": np.array([0, 1, 1, 0], np.int8),
        "status": np.array([0, 1, 1, 0], np.int8),
        "home": np.zeros(n_ev, np.int8),
        "milli": np.array([20_000, 30_000, 40_000, 50_000], np.int32),
        "due_ms": np.full(n_ev, t0 + 500, np.int64),
        # on time; 11 s back (the edge, kept); 12 s back (dropped); ahead
        "delay_ms": np.array([0, 11_000, 12_000, -2_000], np.int64),
    }
    batches = [(t0 + 600, 4)] + [(t0 + 600 + k * 1000, 0)
                                 for k in range(1, 15)]
    got = flow.reference(ev, batches)
    assert got["OpenDoors"][0]["deviceId"].tolist() == [7, 9]
    assert got["OpenDoors"][0]["eventTimeStamp"].tolist() == \
        [t0 + 500, t0 + 2500]
    heat = [dict(zip(h["deviceId"].tolist(), h["Cnt"].tolist()))
            for h in got["HeatAvg"]]
    # the 11 s old row (second n - 11) is in the window from batch 1 on,
    # the on-time and the early one (both second n) from batch 12 on
    assert heat[0] == {} and heat[1] == {7: 1} and heat[11] == {7: 1}
    assert heat[12] == {7: 2, 9: 1} and heat[14] == {7: 2, 9: 1}
    assert got["HeatAvg"][12]["AvgT"].tolist() == [25.0, 50.0]
    bucket, accepted, n_of = flow.accepted_buckets(ev, batches)
    assert accepted.tolist() == [True, True, False, True]
    assert (n_of - bucket).tolist() == [0, 11, 12, 0]


def test_engine_and_reference_agree_row_for_row(ran):
    cell, run, m = ran
    verdict = bench.decide(run, cell, m)
    assert verdict["correct"], (verdict["compared"], verdict["notes"])
    assert verdict["compared"]["rows_differ"] == {"value": 0, "limit": 0}
    assert verdict["compared"]["avg_rel_gap"]["value"] < 1e-5
    assert verdict["compared"]["window_snapshot_missing"]["value"] == 0
    assert verdict["rows_compared"] > 20_000
    assert verdict["committed_offset"] in m["bounds"][1:]
    line = bench.result_line(cell, run, m, verdict, None)
    assert set(line["metrics"]) == {
        "events_per_s", "alert_latency_p50_ms", "alert_latency_p95_ms",
        "setup_s"}
    # the window trails the batch by the watermark and one interval:
    # HeatAvg is empty for the first eleven seconds and counts devices
    # from then on
    heat = [len(h["deviceId"]) for h in cell["flow"].reference(
        m["events"], [(t, n) for (t, _m, _ts), n in
                      zip(run["rec"].batches, m["rows"])])["HeatAvg"]]
    assert heat[0] == 0 and heat[-1] > 5_000


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


def test_the_event_time_counters_ride_every_batch(ran):
    cell, run, m = ran
    batches = [meas for _t, meas, _ts in run["rec"].batches]
    assert len(batches) >= 18
    bucket, accepted, n_of = cell["flow"].accepted_buckets(
        m["events"], [(t, n) for (t, _m, _ts), n in
                      zip(run["rec"].batches, m["rows"])])
    bounds = m["bounds"]
    cells = GROUPS * SLOTS
    stamp = cell["flow"].event_time_ms(m["events"], 0, bounds[-1])
    t_of = np.repeat([t for t, _m, _ts in run["rec"].batches], m["rows"])
    for k, meas in enumerate(batches):
        # two [slots, groups] partials (row count, float32 sum), the key
        # directory (key + used a group), the slots' times, flags and
        # generations
        assert meas["Window_State_Bytes"] == cells * 8 + GROUPS * 5 + SLOTS * 9
        # the rows the watermark refused, as the reference has them
        assert meas["Window_TooLate_Rows_Dropped"] == \
            int((~accepted[bounds[k]:bounds[k + 1]]).sum()), k
        # and the accepted ones stamped over a second before their batch
        late = accepted & (t_of - stamp > 1000)
        assert meas["Window_Late_Rows"] == \
            int(late[bounds[k]:bounds[k + 1]].sum()), k
        # a fold writes the batch's own second and up to eleven before it
        assert 0 <= meas["Window_Slots_Touched"] <= 12
        # the window holds seconds up to n - 12; the oldest a row can
        # have been accepted for is the first batch's less eleven
        if m["rows"][k]:
            assert meas["Window_Slots_Live"] <= \
                n_of[bounds[k + 1] - 1] - n_of[0]
    assert sum(b["Window_Late_Rows"] for b in batches) > 100
    assert sum(b["Window_TooLate_Rows_Dropped"] for b in batches) > 0
    assert max(b["Window_Slots_Touched"] for b in batches) >= 4
    # a checkpoint (every 5 s) writes the head and the slots changed
    # since the one before: the new seconds and the older ones late rows
    # were added to, never the state
    wrote = [(b["Checkpoint_Window_Slots"], b["Checkpoint_Window_Bytes"])
             for b in batches if "Checkpoint_Window_Slots" in b]
    head = GROUPS * 5
    assert wrote and max(s for s, _b in wrote) > 5
    assert all(s <= 5 + 11 + 2 for s, _b in wrote), wrote
    assert all(head + s * GROUPS * 8 < b <= head + s * GROUPS * 8 + 131_072
               for s, b in wrote), wrote
    slot_files = os.listdir(os.path.join(run["run_dir"], "checkpoint",
                                         "window-slots"))
    assert slot_files and all(f.startswith("HeatAvg.") for f in slot_files)


def test_the_raw_row_ring_gives_the_same_rows(tmp_path):
    """The window held the other way (a join reads its rows, so the
    planner keeps the ring): the same rule, the same answers."""
    small = {"traffic": {"declared_width": 512, "rate_events_per_s": 300},
             "warmup": {"min_batches": 7}}
    cell, run, m = bench.execute(
        CELL, 24_000_000_011, 14, False, run_dir=str(tmp_path / "run"),
        child_argv=[sys.executable,
                    os.path.join(HERE, "ring_host_eventtime.py")],
        require_tpu=False, overrides=small)
    batches = [meas for _t, meas, _ts in run["rec"].batches]
    # the ring: 312 slots of 512 rows, no partial aggregates
    assert all("Window_Slots_Live" not in b for b in batches)
    assert batches[0]["Window_State_Bytes"] < SLOTS * 512 * 64
    assert all(b["Window_Slots_Touched"] == 1 for b in batches)
    assert sum(b["Window_Late_Rows"] for b in batches) > 20
    verdict = bench.decide(run, cell, m)
    assert verdict["correct"], (verdict["compared"], verdict["notes"])
    assert verdict["compared"]["rows_differ"] == {"value": 0, "limit": 0}
    assert verdict["rows_compared"] > 4_000


def test_late_rows_counted_in_their_batchs_second_are_not_correct(tmp_path):
    line = bench.run_cell(
        CELL, 24_000_000_007, 14, False, run_dir=str(tmp_path / "run"),
        child_argv=[sys.executable,
                    os.path.join(HERE, "broken_host_eventtime.py")],
        require_tpu=False, overrides=TINY)
    assert line["correct"] is False
    over = {n for n, c in line["compared"].items() if c["value"] > c["limit"]}
    assert "rows_differ" in over, line["compared"]


def test_the_windows_least_bytes_by_hand():
    shapes = bench.load_cell(CELL)["config"]["roofline"]
    need = window_roofline_eventtime.least_bytes(
        shapes, GROUPS, 196_608, 131_000, 11)
    assert need == {
        "batch_read": 196_608 * 3 * 4.0,        # key, temperature, time
        "touched_slots_read": 11 * GROUPS * 2 * 4.0,  # count and sum
        "touched_slots_written": 11 * GROUPS * 2 * 4.0,
        "leaving_slot_read": GROUPS * 2 * 4.0,
        "running_state_read": GROUPS * 2 * 4.0,
        "output_written": 131_000 * 3 * 4.0,    # deviceId, Cnt, AvgT
    }
    assert sum(need.values()) == 29_097_120.0
    peak = roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    # 35.5 us at 819 GB/s: a step that spends 10 ms there reads 0.36 %
    assert 1000.0 * sum(need.values()) / peak == pytest.approx(
        0.035528, rel=1e-3)


def test_the_readers_read_nothing_from_a_program_without_them(tmp_path):
    """The parent commit has the scopes and not the counter: the roofline
    reader returns nothing and does not raise; with both it reads."""
    run_dir = str(tmp_path)
    with open(os.path.join(run_dir, "device_stages.json"), "w",
              encoding="utf-8") as f:
        json.dump({"scopes": {
            "dx.window.partial": {"ms_per_batch": 9.0},
            "dx.window.combine": {"ms_per_batch": 1.0}}}, f)
    cell = bench.load_cell(CELL)
    run = {"run_dir": run_dir, "xplane": {},
           "rec": type("Rec", (), {"device": {"deviceKind": "TPU v5 lite"}})()}
    rows = {"Input_DataXProcessedInput_Events_Count": 196_608.0,
            "Output_HeatAvg_Events_Count": 131_000.0}
    assert window_roofline_eventtime.roofline_pct(
        cell, run, {"measurements": [rows]}) is None
    got = window_roofline_eventtime.roofline_pct(
        cell, run, {"measurements": [dict(rows, Window_Slots_Touched=11.0)]})
    assert got == pytest.approx(100.0 * 0.035528 / 10.0, rel=1e-3)
    with open(os.path.join(run_dir, "device_stages.json"), "w",
              encoding="utf-8") as f:
        json.dump({"scopes": {"dx.ring": {"ms_per_batch": 0.24}}}, f)
    assert window_roofline_eventtime.roofline_pct(
        cell, run, {"measurements": [dict(rows, Window_Slots_Touched=1.0)]}
    ) is None
