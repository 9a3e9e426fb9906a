"""Window state as per-slot partial aggregates (PR 32).

A GROUP BY of COUNT / SUM / AVG / MIN / MAX over a ``TIMEWINDOW`` table
is held as K slots of per-group partial aggregates
(``runtime/timewindow.py WindowPartials``), not as K batches of rows;
here the processing-time windows, whose rows carry their batch's one
time (tests/test_window_eventtime.py has the event-time ones). The raw-row ring is the reference: the same events
through both give the same rows. Which of the two a window keeps is the
planner's choice from the statements alone; the state is checkpointed a
slot at a time and laid out over a mesh like the ring is.

CPU, small sizes. (The compile of the sample's step for a described v5e,
with its sorts counted, is in tests/test_observability.py: the tests that
load the TPU compiler stay in one file.)"""

import json
import os

import jax
import numpy as np
import pytest

from data_accelerator_tpu.compile.planner import TableData
from data_accelerator_tpu.core.config import SettingDictionary
from data_accelerator_tpu.runtime.checkpoint import WindowStateCheckpointer
from data_accelerator_tpu.runtime.processor import FlowProcessor
from data_accelerator_tpu.runtime.timewindow import (
    WindowBuffers,
    WindowPartials,
)

import jax.numpy as jnp

SCHEMA = json.dumps({"type": "struct", "fields": [
    {"name": "deviceId", "type": "long", "nullable": False, "metadata": {}},
    {"name": "temperature", "type": "double", "nullable": False,
     "metadata": {}},
    {"name": "level", "type": "long", "nullable": False, "metadata": {}},
]})
AGGS = {
    "COUNT": "COUNT(*) AS Cnt",
    "SUM": "SUM(level) AS SumL",
    "AVG": "AVG(temperature) AS AvgT",
    "MIN": "MIN(temperature) AS MinT",
    "MAX": "MAX(level) AS MaxL",
}
COLUMN = {"COUNT": "Cnt", "SUM": "SumL", "AVG": "AvgT", "MIN": "MinT",
          "MAX": "MaxL"}
PER_DEVICE = (
    "--DataXQuery--\n"
    "PerDevice = SELECT deviceId, " + ", ".join(AGGS.values())
    + " FROM DataXProcessedInput_W GROUP BY deviceId\n"
)
# a statement that reads the window's rows: the planner then keeps the
# raw-row ring, and PerDevice is the sort-based GROUP BY over it
ROW_READER = (
    "--DataXQuery--\n"
    "Rows = SELECT deviceId, level FROM DataXProcessedInput_W "
    "WHERE level > 1000\n"
)
CAP = 32
T0 = 1_700_000_000_000


def conf(tmp_path, transform, seconds, **more):
    path = tmp_path / f"t{abs(hash(transform)) % 10**8}.transform"
    path.write_text(transform)
    d = {
        "datax.job.name": "WindowPartials",
        "datax.job.input.default.inputtype": "local",
        "datax.job.input.default.blobschemafile": SCHEMA,
        "datax.job.input.default.streaming.intervalinseconds": "1",
        "datax.job.process.timestampcolumn": "eventTimeStamp",
        "datax.job.process.watermark": "0 second",
        "datax.job.process.transform": str(path),
        "datax.job.process.timewindow.DataXProcessedInput_W.windowduration":
            f"{seconds} seconds",
        "datax.job.process.projection":
            "current_timestamp() AS eventTimeStamp\nRaw.*",
    }
    d.update(more)
    return SettingDictionary(d)


def batches_for(slots, seed=3):
    """3 x K batches: (time step ms, device ids, temperatures, levels).
    Times step by 1 s mostly, 0.5 s now and then (the far edge drops no
    slot) and by 2 and 3 s (it drops two and three at once); batch 4 is
    empty; device 9 reports in batches 0-1, vanishes for longer than the
    window and returns."""
    rng = np.random.default_rng(seed)
    out = []
    for b in range(3 * slots):
        step = [1000, 1000, 500, 1000, 2000, 1000, 3000, 500][b % 8]
        n = 0 if b == 4 else int(rng.integers(1, CAP + 1))
        ids = rng.integers(1, 7, n)
        if b in (0, 1, 2 * slots + 1, 2 * slots + 2) and n:
            ids[0] = 9
        out.append((step, ids.astype(np.int32),
                    rng.uniform(-50, 50, n).astype(np.float32),
                    rng.integers(-9, 10, n).astype(np.int32)))
    return out


def feed(proc, batches, t0=T0):
    """Run ``batches`` through ``proc``; per batch (rows of PerDevice in
    the order they landed, the batch's metrics)."""
    got, t = [], t0
    for step, ids, temp, level in batches:
        t += step
        cols = {c: np.zeros(CAP, np.float32 if ty == "double" else np.int32)
                for c, ty in proc.raw_schema.types.items()}
        n = len(ids)
        cols["deviceId"][:n], cols["temperature"][:n] = ids, temp
        cols["level"][:n] = level
        raw = TableData({k: jnp.asarray(v) for k, v in cols.items()},
                        jnp.arange(CAP) < n)
        datasets, metrics = proc.process_batch(raw, batch_time_ms=t)
        got.append((datasets["PerDevice"], metrics))
    return got


@pytest.fixture(scope="module", params=[4, 7], ids=["K4", "K7"])
def both(request, tmp_path_factory):
    """The same events through both window states: K slots of partial
    aggregates, and the K-slot raw-row ring."""
    slots = request.param
    tmp = tmp_path_factory.mktemp(f"k{slots}")
    partial = FlowProcessor(conf(tmp, PER_DEVICE, slots - 1),
                            batch_capacity=CAP,
                            output_datasets=["PerDevice"])
    raw = FlowProcessor(conf(tmp, PER_DEVICE + ROW_READER, slots - 1),
                        batch_capacity=CAP, output_datasets=["PerDevice"])
    assert isinstance(partial.window_buffers["PerDevice"], WindowPartials)
    assert partial.window_buffers["PerDevice"].slots == slots
    assert not partial.ring_slots
    assert isinstance(raw.window_buffers["DataXProcessedInput"],
                      WindowBuffers)
    assert raw.ring_slots == {"DataXProcessedInput": slots}
    batches = batches_for(slots)
    return slots, batches, feed(partial, batches), feed(raw, batches)


@pytest.mark.parametrize("agg", list(AGGS))
def test_partials_equal_the_raw_ring_row_for_row(both, agg):
    slots, batches, partial, raw = both
    assert len(partial) == len(raw) == 3 * slots
    col = COLUMN[agg]
    seen_nine = []
    for k, ((p_rows, _pm), (r_rows, _rm)) in enumerate(zip(partial, raw)):
        assert [r["deviceId"] for r in p_rows] == \
            [r["deviceId"] for r in r_rows], k
        seen_nine.append(any(r["deviceId"] == 9 for r in r_rows))
        for p, r in zip(p_rows, r_rows):
            if agg in ("AVG",):
                assert p[col] == pytest.approx(r[col], rel=1e-5, abs=1e-5), k
            else:
                assert p[col] == r[col], (k, p, r)
    # the key that vanished left the window and came back
    assert seen_nine[0] and not all(seen_nine) and seen_nine[-1]
    # the far edge dropped several slots at once somewhere, and the
    # window was full somewhere
    live = [pm["Window_Slots_Live"] for _rows, pm in partial]
    assert max(live) <= slots and min(live[slots:]) < max(live)


def test_the_window_counters(both):
    slots, _batches, partial, raw = both
    groups = slots * CAP  # the raw ring's bound: under maxgroups here
    for _rows, m in partial:
        # count + 2 int32 partials + 2 float32 partials, [slots, groups];
        # a key and a flag a group; a time and a flag a slot
        assert m["Window_State_Bytes"] == \
            slots * groups * 5 * 4 + groups * 5 + slots * 5
        assert 1 <= m["Window_Slots_Live"] <= slots
        assert m["Output_PerDevice_GroupsDropped"] == 0.0
    for _rows, m in raw:
        assert "Window_Slots_Live" not in m
        # the ring: the projected table's six 4-byte columns (the three
        # fields, the timestamp, the two properties ids) and a validity
        # byte a row
        assert m["Window_State_Bytes"] == slots * CAP * (6 * 4 + 1)


def test_groups_over_maxgroups_drop_and_are_counted_the_same(tmp_path):
    """Six devices report in every batch and the flow bounds its groups
    at four: both window states land the four smallest keys and count two
    dropped, every batch."""
    more = {"datax.job.process.maxgroups": "4"}
    partial = FlowProcessor(conf(tmp_path, PER_DEVICE, 3, **more),
                            batch_capacity=CAP, output_datasets=["PerDevice"])
    raw = FlowProcessor(conf(tmp_path, PER_DEVICE + ROW_READER, 3, **more),
                        batch_capacity=CAP, output_datasets=["PerDevice"])
    assert partial.window_buffers["PerDevice"].groups == 4
    rng = np.random.default_rng(1)
    batches = [(1000, np.arange(1, 7, dtype=np.int32).repeat(2),
                rng.uniform(0, 9, 12).astype(np.float32),
                rng.integers(0, 9, 12).astype(np.int32)) for _ in range(6)]
    for (p_rows, pm), (r_rows, rm) in zip(feed(partial, batches),
                                          feed(raw, batches)):
        assert [r["deviceId"] for r in p_rows] == [1, 2, 3, 4]
        assert [(r["deviceId"], r["Cnt"], r["SumL"]) for r in p_rows] == \
            [(r["deviceId"], r["Cnt"], r["SumL"]) for r in r_rows]
        assert pm["Output_PerDevice_GroupsDropped"] == 2.0 == \
            rm["Output_PerDevice_GroupsDropped"]


# ---------------------------------------------------------------------------
# the planner's choice
# ---------------------------------------------------------------------------
SAMPLE = (
    "--DataXQuery--\n"
    "HeatAvg = SELECT deviceId, COUNT(*) AS Cnt, AVG(temperature) AS AvgT "
    "FROM DataXProcessedInput_W GROUP BY deviceId\n"
)
RAW_ROW_READERS = {
    "window_join": (
        "--DataXQuery--\n"
        "Joined = SELECT a.deviceId, b.level FROM DataXProcessedInput a "
        "JOIN DataXProcessedInput_W b ON a.deviceId = b.deviceId\n"),
    "plain_select": ROW_READER,
    "distinct_aggregate": (
        "--DataXQuery--\n"
        "Levels = SELECT deviceId, COUNT(DISTINCT level) AS L "
        "FROM DataXProcessedInput_W GROUP BY deviceId\n"),
}


@pytest.mark.parametrize("why", list(RAW_ROW_READERS))
def test_the_planner_keeps_raw_rows_for(tmp_path, why):
    proc = FlowProcessor(
        conf(tmp_path, SAMPLE + RAW_ROW_READERS[why], 3,
             **{"datax.job.process.joincapacity": "64"}),
        batch_capacity=CAP)
    assert proc.pipeline.partial_windows == ()
    assert not proc.window_states
    assert proc.ring_slots == {"DataXProcessedInput": 4}


def test_the_planner_keeps_partials_for_a_payload_timestamp_column(tmp_path):
    """The rows of a batch carry times of their own: an event-time window
    (PR 34), whose slot is a second of event time, not a batch. It is held
    as partial aggregates all the same (tests/test_window_eventtime.py
    holds both states to the rule)."""
    schema = json.loads(SCHEMA)
    schema["fields"].append({"name": "eventTimeStamp", "type": "timestamp",
                             "nullable": False, "metadata": {}})
    c = dict(conf(tmp_path, SAMPLE, 3).dict)
    c["datax.job.input.default.blobschemafile"] = json.dumps(schema)
    del c["datax.job.process.projection"]
    proc = FlowProcessor(SettingDictionary(c), batch_capacity=CAP)
    assert proc.pipeline.partial_windows == ("DataXProcessedInput_W",)
    assert not proc.ring_slots
    plan = proc.window_states["HeatAvg"]
    assert plan.slots == 5 and plan.clock is not None
    assert (plan.clock.interval_ms, plan.clock.watermark_ms) == (1000, 0)
    # the current_timestamp() projection is the other kind
    uniform = FlowProcessor(conf(tmp_path, SAMPLE, 3), batch_capacity=CAP)
    assert uniform.window_states["HeatAvg"].clock is None


def test_the_planner_keeps_partials_for_the_sample(tmp_path):
    proc = FlowProcessor(conf(tmp_path, SAMPLE, 300), batch_capacity=CAP)
    assert proc.pipeline.partial_windows == ("DataXProcessedInput_W",)
    assert not proc.ring_slots
    state = proc.window_buffers["HeatAvg"]
    # 5 minutes of 1 s batches, and the raw ring's group bound
    assert (state.slots, state.groups) == (301, 4096)
    assert set(state.parts) == {"n", "agg1"}
    view = proc.pipeline.view_by_name("HeatAvg")
    assert view.plan.input_rows == CAP  # one batch is sorted, not 301
    assert view.plan.window_state_bytes == proc.window_state_bytes()
    # nothing chooses it but the statements: no conf key names it
    from data_accelerator_tpu.analysis import confspec
    keys = list(confspec.registry_index())
    assert not [k for k in keys if "partial" in k or "windowstate" in k]


# ---------------------------------------------------------------------------
# checkpoint: a slot at a time
# ---------------------------------------------------------------------------
def simple_batches(n, seed):
    rng = np.random.default_rng(seed)
    return [(1000, rng.integers(1, 9, 20).astype(np.int32),
             rng.uniform(0, 50, 20).astype(np.float32),
             rng.integers(0, 9, 20).astype(np.int32)) for _ in range(n)]


def test_a_checkpoint_writes_the_slots_since_the_last_and_restores(tmp_path):
    c = conf(tmp_path, PER_DEVICE, 7)  # 8 slots
    proc = FlowProcessor(c, batch_capacity=CAP, output_datasets=["PerDevice"])
    ck = WindowStateCheckpointer(str(tmp_path / "ck"))
    assert ck.landed_counter is None
    feed(proc, simple_batches(5, 1))
    ck.save(proc.snapshot_window_state(since=ck.landed_counter))
    first_bytes = ck.last_bytes
    feed(proc, simple_batches(5, 2), t0=T0 + 5000)
    snap = proc.snapshot_window_state(since=ck.landed_counter)
    assert snap["partials"]["PerDevice"]["first_gen"] == 5
    slot_bytes = sum(a.nbytes for a in
                     snap["partials"]["PerDevice"]["parts"].values())
    groups = 8 * CAP
    assert slot_bytes == 5 * groups * 5 * 4  # five slots of five partials
    ck.save(snap)
    assert ck.landed_counter == 10
    # head + five slots, not the eight the state holds
    head = os.path.getsize(ck.path)
    assert slot_bytes < ck.last_bytes - head < slot_bytes + 4096
    assert first_bytes == pytest.approx(ck.last_bytes, rel=0.02)
    assert ck.last_bytes < 0.75 * proc.window_state_bytes()
    assert len(os.listdir(ck.slots_dir)) == 2

    # a restart restores all eight slots from the head and its files:
    # the next batch's rows are the ones the first process lands
    again = FlowProcessor(c, batch_capacity=CAP,
                          output_datasets=["PerDevice"])
    ck2 = WindowStateCheckpointer(str(tmp_path / "ck"))
    assert again.restore_window_state(ck2.load())
    assert ck2.landed_counter == 10
    nxt = simple_batches(3, 3)
    want = [rows for rows, _m in feed(proc, nxt, t0=T0 + 10_000)]
    got = [rows for rows, _m in feed(again, nxt, t0=T0 + 10_000)]
    assert got == want and len(want[0]) == 8
    # and its own next checkpoint builds on what it loaded: three slots
    ck2.save(again.snapshot_window_state(since=ck2.landed_counter))
    assert ck2.last_bytes - os.path.getsize(ck2.path) < \
        3 * groups * 20 + 4096
    # the new head names the files of the generations the window holds
    # (13 - 8 = 5 on); the head now in ``.old`` (counter 10) still names
    # 0-4, so it stays until that head is replaced
    ranges = lambda: sorted(  # noqa: E731
        f.split(".")[1] for f in os.listdir(ck2.slots_dir))
    assert ranges() == ["0-4", "10-12", "5-9"]
    feed(again, simple_batches(1, 4), t0=T0 + 13_000)
    ck2.save(again.snapshot_window_state(since=ck2.landed_counter))
    assert ranges() == ["10-12", "13-13", "5-9"]


@pytest.mark.parametrize("torn", ["head", "slot_tmp", "newest_slot_file"])
def test_a_torn_checkpoint_falls_back_to_the_previous_one(tmp_path, torn):
    c = conf(tmp_path, PER_DEVICE, 7)
    proc = FlowProcessor(c, batch_capacity=CAP, output_datasets=["PerDevice"])
    ck = WindowStateCheckpointer(str(tmp_path / "ck"))
    feed(proc, simple_batches(3, 1))
    ck.save(proc.snapshot_window_state(since=ck.landed_counter))
    feed(proc, simple_batches(4, 2), t0=T0 + 3000)
    ck.save(proc.snapshot_window_state(since=ck.landed_counter))
    newest = max(os.listdir(ck.slots_dir),
                 key=lambda f: int(f.split(".")[1].split("-")[0]))
    if torn == "head":
        with open(ck.path, "r+b") as f:
            f.truncate(os.path.getsize(ck.path) // 3)
    elif torn == "slot_tmp":
        # a save that died writing its slot file: the head never moved
        with open(os.path.join(ck.slots_dir, "PerDevice.7-9.dead.npz.tmp"),
                  "wb") as f:
            f.write(b"PK\x03\x04 torn")
    else:
        os.remove(os.path.join(ck.slots_dir, newest))
    snap = WindowStateCheckpointer(str(tmp_path / "ck")).load()
    assert snap is not None
    # a state some completed checkpoint described: the newest (7) where
    # only a temp file is torn, else the one before (3)
    assert snap["slot_counter"] == (7 if torn == "slot_tmp" else 3)
    again = FlowProcessor(c, batch_capacity=CAP,
                          output_datasets=["PerDevice"])
    assert again.restore_window_state(snap)
    state = again.window_buffers["PerDevice"]
    assert int(np.asarray(state.slot_live).sum()) == snap["slot_counter"]


def test_a_head_written_before_slots_had_generations_restores(tmp_path):
    """A checkpoint of PR 32's form (the head names [first generation,
    last generation, file] a file and carries no ``slot_gen``: a slot was
    the batch of counter g, in row g - first of its file) restores after
    an upgrade: the accepted 5-minute deployment does not restart with an
    empty window."""
    import json

    c = conf(tmp_path, PER_DEVICE, 7)
    proc = FlowProcessor(c, batch_capacity=CAP, output_datasets=["PerDevice"])
    ck = WindowStateCheckpointer(str(tmp_path / "ck"))
    stream = simple_batches(10, 1)
    feed(proc, stream[:3])
    ck.save(proc.snapshot_window_state(since=ck.landed_counter))
    feed(proc, stream[3:7], t0=T0 + 3000)
    ck.save(proc.snapshot_window_state(since=ck.landed_counter))
    # the same checkpoint as PR 32 wrote it
    named = []
    for path in (ck.path, ck.backup_path):
        with np.load(path) as z:
            arrays = {k: z[k] for k in z.files}
        del arrays["partial/PerDevice/slot_gen"]
        head = json.loads(
            arrays["partial/PerDevice/files_json"].tobytes().decode())
        by_file = {}
        for slot, gen, name, row in head["files"]:
            assert slot == gen % head["slots"]
            by_file.setdefault(name, []).append((gen, row))
        head["files"] = []
        for name, held in sorted(by_file.items(), key=lambda e: min(e[1])):
            first = min(g for g, _r in held)
            assert sorted(held) == [(first + i, i) for i in range(len(held))]
            head["files"].append([first, first + len(held) - 1, name])
        arrays["partial/PerDevice/files_json"] = np.frombuffer(
            json.dumps(head).encode(), np.uint8)
        with open(path, "wb") as f:
            np.savez(f, **arrays)
        named.append([e[:2] for e in head["files"]])
    assert named == [[[0, 2], [3, 6]], [[0, 2]]]

    ck2 = WindowStateCheckpointer(str(tmp_path / "ck"))
    snap = ck2.load()
    assert snap["slot_counter"] == 7
    again = FlowProcessor(c, batch_capacity=CAP,
                          output_datasets=["PerDevice"])
    assert again.restore_window_state(snap)
    assert sorted(snap["partials"]["PerDevice"]["slot_gen"]) == \
        [-1, *range(7)]
    first = [rows for rows, _m in feed(proc, stream[7:], t0=T0 + 7000)]
    got = [rows for rows, _m in feed(again, stream[7:], t0=T0 + 7000)]
    assert got == first and got[-1]
    # and its next checkpoint builds on the files the old head named
    ck2.save(again.snapshot_window_state(since=ck2.landed_counter))
    assert ck2.last_slots == 3
    assert WindowStateCheckpointer(str(tmp_path / "ck")).load()[
        "slot_counter"] == 10


def test_a_snapshot_the_processor_did_not_take_is_forgotten(tmp_path):
    """A checkpoint of another flow shape is refused, and the next
    snapshot builds on none of its slot files."""
    small = FlowProcessor(conf(tmp_path, PER_DEVICE, 3), batch_capacity=CAP,
                          output_datasets=["PerDevice"])
    ck = WindowStateCheckpointer(str(tmp_path / "ck"))
    feed(small, simple_batches(2, 1))
    ck.save(small.snapshot_window_state(since=ck.landed_counter))
    wide = FlowProcessor(conf(tmp_path, PER_DEVICE, 7), batch_capacity=CAP,
                         output_datasets=["PerDevice"])
    ck2 = WindowStateCheckpointer(str(tmp_path / "ck"))
    assert not wide.restore_window_state(ck2.load())
    ck2.forget()
    feed(wide, simple_batches(3, 2))
    ck2.save(wide.snapshot_window_state(since=ck2.landed_counter))
    assert WindowStateCheckpointer(str(tmp_path / "ck")).load()[
        "slot_counter"] == 3


# ---------------------------------------------------------------------------
# under a mesh
# ---------------------------------------------------------------------------
def test_under_a_four_device_mesh_the_rows_are_the_one_device_rows(tmp_path):
    one = FlowProcessor(conf(tmp_path, PER_DEVICE, 3), batch_capacity=CAP,
                        output_datasets=["PerDevice"])
    four = FlowProcessor(
        conf(tmp_path, PER_DEVICE, 3,
             **{"datax.job.process.numchips": "4"}),
        batch_capacity=CAP, output_datasets=["PerDevice"])
    assert four.mesh is not None and four.mesh.size == 4
    # the partial aggregates lie replicated on every chip from the start
    for leaf in jax.tree_util.tree_leaves(four.window_buffers["PerDevice"]):
        assert len(leaf.sharding.device_set) == 4
    batches = batches_for(4, seed=11)
    for (rows1, _m1), (rows4, m4) in zip(feed(one, batches),
                                         feed(four, batches)):
        assert [r["deviceId"] for r in rows1] == \
            [r["deviceId"] for r in rows4]
        for a, b in zip(rows1, rows4):
            assert (a["Cnt"], a["SumL"], a["MaxL"]) == \
                (b["Cnt"], b["SumL"], b["MaxL"])
            assert a["AvgT"] == pytest.approx(b["AvgT"], rel=1e-5, abs=1e-5)
        assert m4["Mesh_Chips"] == 4.0
    assert four.placement()["ringDevices"] == {"DataXProcessedInput": 4}


def test_the_planner_keeps_raw_rows_for_a_state_handed_off_by_key(tmp_path):
    """A job whose window state is shipped to a snapshot mirror by key
    partition (the rescale handoff re-packs the rows of a partition)
    needs the rows."""
    proc = FlowProcessor(
        conf(tmp_path, SAMPLE, 3, **{
            "datax.job.process.state.snapshoturl":
                "objstore://" + str(tmp_path / "mirror")}),
        batch_capacity=CAP)
    assert proc.state_mirror is not None
    assert not proc.window_states
    assert proc.ring_slots == {"DataXProcessedInput": 4}
