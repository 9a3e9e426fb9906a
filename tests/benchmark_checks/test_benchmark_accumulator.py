"""``homeautomation-5m-accumulator.paced``: the sample with its
``--DataXStates--`` accumulator against its plain reference on a CPU child
at a 2,048-row width, across the whole 131,072-device fleet (the table
holds ``process.maxgroups`` rows). The engine and the reference agree row
for row in all three outputs, the bfloat16 control does not, the one fault
a carried accumulator can have (a batch that starts from an empty table)
is seen, the program's new spans and counters ride every batch, the
readers read nothing from a program without them, and the state's
roofline counts the bytes worked out here by hand."""

import json
import os
import sys

import pytest

from benchmark import readers, roofline, run as bench, state_roofline

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "homeautomation-5m-accumulator.paced"
TINY = {"traffic": {"declared_width": 2048, "rate_events_per_s": 1500},
        "warmup": {"min_batches": 7}}
GROUPS = 131_072
STATE_KEY = "datax.job.process.statetable.DeviceTotals."


@pytest.fixture(scope="module")
def ran(tmp_path_factory):
    run_dir = str(tmp_path_factory.mktemp("bench5macc") / "run")
    return bench.execute(CELL, 4_294_967_311, 5, False, run_dir=run_dir,
                         require_tpu=False, overrides=TINY)


def test_the_cell_is_the_5m_sample_with_its_accumulator():
    cell = bench.load_cell(CELL)
    config, flow = cell["config"], cell["flow"]
    sibling = bench.load_cell("homeautomation-5m.paced")
    assert cell["mix"]["traffic"]["declared_width"] == \
        sibling["mix"]["traffic"]["declared_width"]
    assert cell["mix"]["warmup"] == sibling["mix"]["warmup"]
    # the sibling's transform unchanged, the accumulator after it
    assert config["transform"].startswith(sibling["config"]["transform"])
    assert "DeviceTotals = SELECT deviceId, SUM(Events)" in \
        config["transform"]
    assert config["conf"]["datax.job.process.maxgroups"] == str(GROUPS)
    assert config["conf"][STATE_KEY + "schema"] == \
        "deviceId long, Events long, MinStatus long, MaxT double"
    assert STATE_KEY + "location" not in config["conf"]
    assert config["outputs"] == ["OpenDoors", "HeatAvg", "DeviceTotals"]
    assert config["reduced"] == ["reference_data_join"]
    assert config["schema"] == sibling["config"]["schema"]
    assert flow.DEVICES == GROUPS
    assert set(flow.COLUMNS["DeviceTotals"].values()) <= {"key", "exact"}
    assert flow.LIMITS == sibling["flow"].LIMITS
    assert {m["name"] for m in cell["per_layer"]} == {
        "state_write_ms", "state_commit_ms", "state_write_bytes",
        "device_state_ms_per_batch", "state_fold_roofline_pct",
        "batch_busy_ms.acc"}


def test_engine_and_reference_agree_row_for_row(ran):
    cell, run, m = ran
    verdict = bench.decide(run, cell, m)
    assert verdict["correct"], (verdict["compared"], verdict["notes"])
    assert verdict["compared"]["rows_differ"] == {"value": 0, "limit": 0}
    assert verdict["committed_offset"] in m["bounds"][1:]
    want = cell["flow"].reference(
        m["events"], [(t, n) for (t, _m, _ts), n in zip(run["rec"].batches,
                                                        m["rows"])])
    # the table grows by the devices each batch sees for the first time
    sizes = [len(b["deviceId"]) for b in want["DeviceTotals"]]
    assert sizes == sorted(sizes) and sizes[-1] > 10_000
    assert sum(int(b["Events"].sum()) for b in want["DeviceTotals"][-1:]) \
        == int(m["bounds"][-1])
    line = bench.result_line(cell, run, m, verdict, None)
    assert set(line["metrics"]) == {
        "events_per_s", "alert_latency_p50_ms", "alert_latency_p95_ms",
        "setup_s"}


def test_the_low_precision_control_is_not_correct(ran):
    cell, run, m = ran
    verdict = bench.decide(run, cell, m, got=cell["flow"].control)
    assert verdict["correct"] is False
    over = {n for n, c in verdict["compared"].items()
            if c["value"] > c["limit"]} - {"window_snapshot_missing"}
    # bfloat16 temperatures: HeatAvg's averages drift, DeviceTotals' maxima
    # differ outright
    assert over == {"avg_rel_gap", "rows_differ"}
    assert verdict["compared"]["rows_differ"]["value"] > 1000


def test_the_state_spans_and_counters_ride_every_batch(ran):
    cell, run, m = ran
    rec = run["rec"]
    assert len(rec.batches) >= 10
    want = cell["flow"].totals(
        m["events"], [(t, n) for (t, _x, _ts), n in zip(rec.batches,
                                                        m["rows"])])
    for (t, meas, _ts), table in zip(rec.batches, want):
        assert meas["State_Rows"] == len(table["deviceId"])
        assert meas["State_GroupsDropped"] == 0
        # a partition's .npz (four int32/float32 columns of its rows) and
        # its sidecar, for the 16 partitions the fleet's keys hash onto
        assert 16 * len(table["deviceId"]) < meas["State_Write_Bytes"] \
            < 16 * len(table["deviceId"]) + 16 * 2_000
        spans = rec.spans(t)
        assert "state-write" in spans and "state-commit" in spans
        # the standby write starts as the batch lands, runs beside its
        # sinks, and ends before the pointers are committed
        w0, w_ms = spans["state-write"]
        c0, c_ms = spans["state-commit"]
        assert spans["collect"][0] <= w0
        assert w0 + w_ms / 1000.0 <= c0 + c_ms / 1000.0 + 1e-3
        # every span of the chain is counted: what none holds stays small
        assert meas["Batch_Unspanned_Ms"] < 50.0
    # the table persists under the job's checkpoint directory, nowhere
    # else: one A/B pair and a committed pointer a partition
    state = os.path.join(run["run_dir"], "checkpoint", "state",
                         "DeviceTotals")
    parts = sorted(os.listdir(state))
    assert parts == [f"p{p:02d}" for p in range(16)]
    assert all(os.path.exists(os.path.join(state, p, "pointer"))
               for p in parts)


def test_a_host_that_starts_every_batch_empty_is_not_correct(tmp_path):
    line = bench.run_cell(
        CELL, 24_000_000_011, 4, False, run_dir=str(tmp_path / "run"),
        child_argv=[sys.executable,
                    os.path.join(HERE, "broken_host_accumulator.py")],
        require_tpu=False, overrides=TINY)
    assert line["correct"] is False
    over = {n for n, c in line["compared"].items() if c["value"] > c["limit"]}
    # the window and the alert are the sibling's: only the table is wrong
    assert over == {"rows_differ"}, line["compared"]


def test_the_states_least_bytes_by_hand():
    shapes = bench.load_cell(CELL)["config"]["roofline"]
    need = state_roofline.least_bytes(shapes, 196_608, GROUPS)
    assert need == {
        "batch_read": 196_608 * 3 * 4.0,     # deviceId, status, temperature
        "table_read": GROUPS * 4 * 4.0,      # deviceId, Events, MinStatus,
        "table_written": GROUPS * 4 * 4.0,   # MaxT
    }
    assert sum(need.values()) == 6_553_600.0
    peak = roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    # 8.0 us at 819 GB/s: a fold that takes 4 ms there reads 0.2 %
    assert 1000.0 * sum(need.values()) / peak == pytest.approx(0.008002,
                                                              rel=1e-3)


def test_the_state_readers_read_nothing_from_a_program_without_them(
        tmp_path):
    """The parent commit runs the accumulator under ``dx.view.*`` and
    counts no ``State_Rows``: the readers return nothing and do not
    raise."""
    run_dir = str(tmp_path)
    cell = bench.load_cell(CELL)
    with open(os.path.join(run_dir, "device_stages.json"), "w",
              encoding="utf-8") as f:
        json.dump({"scopes": {
            "dx.view.DeviceMerged": {"ms_per_batch": 1.5},
            "dx.view.DeviceTotals": {"ms_per_batch": 2.5}}}, f)
    run = {"run_dir": run_dir, "xplane": {}}
    m = {"measurements": [{"Input_DataXProcessedInput_Events_Count": 196_608.0}]}
    assert state_roofline.state_ms(cell, run) is None
    assert state_roofline.roofline_pct(cell, run, m) is None
    for name in ("device_state_ms_per_batch", "state_fold_roofline_pct"):
        assert readers.read_one(name, cell, run, m, {}) is None
    for name in ("state_write_ms", "state_commit_ms", "state_write_bytes"):
        assert readers.read_one(
            name, cell, run, {"spans": [{"collect": (0.0, 3.0)}],
                              **m}, {}) is None
    with open(os.path.join(run_dir, "device_stages.json"), "w",
              encoding="utf-8") as f:
        json.dump({"scopes": {
            "dx.state.DeviceMerged": {"ms_per_batch": 1.5},
            "dx.state.DeviceTotals": {"ms_per_batch": 2.5}}}, f)
    assert state_roofline.state_ms(cell, run) == 4.0
    m["measurements"][0]["State_Rows"] = float(GROUPS)
    run["rec"] = type("Rec", (), {"device": {"deviceKind": "TPU v5 lite"}})()
    assert state_roofline.roofline_pct(cell, run, m) == pytest.approx(
        100.0 * 0.008002 / 4.0, rel=1e-3)
