"""Event-time windows (PR 34): the timestamp column comes from the
payload, rows arrive out of order under a watermark.

The rule (``runtime/timewindow.py``): I the batch interval, w the
watermark's intervals, d the window's; a batch at time t has n =
floor(t / I), a row stamped ts has b = floor(ts / I). A batch's rows came
in over the interval before t, so on-time ones read b = n - 1 or n: the
row is accepted iff b >= n - w - 1, else dropped from every window and
counted; the window read at batch n holds the accepted rows with
n - w - 1 - d <= b < n - w - 1. Both window states keep it: per-slot
partial aggregates (a slot an interval of event time) and the raw-row
ring. A plain Python reference written from the rule alone is what both
are held to.

CPU, small sizes."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from data_accelerator_tpu.compile.planner import TableData
from data_accelerator_tpu.core.config import SettingDictionary
from data_accelerator_tpu.runtime.checkpoint import WindowStateCheckpointer
from data_accelerator_tpu.runtime.processor import FlowProcessor
from data_accelerator_tpu.runtime.timewindow import (
    WindowBuffers,
    WindowPartials,
    num_slots,
)

SCHEMA = json.dumps({"type": "struct", "fields": [
    {"name": "deviceId", "type": "long", "nullable": False, "metadata": {}},
    {"name": "temperature", "type": "double", "nullable": False,
     "metadata": {}},
    {"name": "level", "type": "long", "nullable": False, "metadata": {}},
    {"name": "eventTimeStamp", "type": "timestamp", "nullable": False,
     "metadata": {}},
]})
PER_DEVICE = (
    "--DataXQuery--\n"
    "PerDevice = SELECT deviceId, COUNT(*) AS Cnt, SUM(level) AS SumL, "
    "AVG(temperature) AS AvgT, MIN(temperature) AS MinT, MAX(level) AS MaxL "
    "FROM DataXProcessedInput_W GROUP BY deviceId\n"
)
# a statement that reads the window's rows: the planner then keeps the
# raw-row ring, and PerDevice is the sort-based GROUP BY over it
JOIN = (
    "--DataXQuery--\n"
    "Joined = SELECT a.deviceId, b.level FROM DataXProcessedInput a "
    "JOIN DataXProcessedInput_W b ON a.deviceId = b.deviceId "
    "WHERE b.level > 1000\n"
)
CAP = 32
T0 = 1_700_000_000_000
I = 1000


def conf(tmp_path, transform, window_s, watermark_s, **more):
    path = tmp_path / f"t{abs(hash(transform)) % 10**8}.transform"
    path.write_text(transform)
    d = {
        "datax.job.name": "WindowEventTime",
        "datax.job.input.default.inputtype": "local",
        "datax.job.input.default.blobschemafile": SCHEMA,
        "datax.job.input.default.streaming.intervalinseconds": "1",
        "datax.job.process.timestampcolumn": "eventTimeStamp",
        "datax.job.process.watermark": f"{watermark_s} second",
        "datax.job.process.transform": str(path),
        "datax.job.process.timewindow.DataXProcessedInput_W.windowduration":
            f"{window_s} seconds",
        "datax.job.process.joincapacity": "64",
    }
    d.update(more)
    return SettingDictionary(d)


def processors(tmp_path, window_s, watermark_s, **more):
    """The same flow with its window in each state."""
    partial = FlowProcessor(
        conf(tmp_path, PER_DEVICE, window_s, watermark_s, **more),
        batch_capacity=CAP, output_datasets=["PerDevice"])
    ring = FlowProcessor(
        conf(tmp_path, PER_DEVICE + JOIN, window_s, watermark_s, **more),
        batch_capacity=CAP, output_datasets=["PerDevice"])
    assert isinstance(partial.window_buffers["PerDevice"], WindowPartials)
    assert not partial.ring_slots
    assert isinstance(ring.window_buffers["DataXProcessedInput"],
                      WindowBuffers)
    assert not ring.window_states
    return partial, ring


def feed(proc, batches):
    """``batches``: (batch time ms, [(stamp ms, device, temperature,
    level)]). Per batch (PerDevice rows as landed, metrics)."""
    got = []
    for t, rows in batches:
        base = (t // 1000) * 1000
        cols = {c: np.zeros(CAP, np.float32 if ty == "double" else np.int32)
                for c, ty in proc.raw_schema.types.items()}
        for i, (ts, dev, temp, level) in enumerate(rows):
            cols["eventTimeStamp"][i] = ts - base
            cols["deviceId"][i], cols["temperature"][i] = dev, temp
            cols["level"][i] = level
        raw = TableData({k: jnp.asarray(v) for k, v in cols.items()},
                        jnp.arange(CAP) < len(rows))
        datasets, metrics = proc.process_batch(raw, batch_time_ms=t)
        got.append((datasets["PerDevice"], metrics))
    return got


def by_the_rule(batches, window_s, watermark_s):
    """Per batch (PerDevice rows in key order, late rows, too-late rows)
    from the rule alone, in plain Python."""
    lag, d = watermark_s * 1000 // I + 1, window_s * 1000 // I
    accepted = []  # (b, device, temperature, level)
    out = []
    for t, rows in batches:
        n = t // I
        late = too_late = 0
        for ts, dev, temp, level in rows:
            b = min(ts // I, n)
            if b < n - lag:
                too_late += 1
                continue
            late += t - ts > I
            accepted.append((b, dev, temp, level))
        groups = {}
        for b, dev, temp, level in accepted:
            if n - lag - d <= b < n - lag:
                groups.setdefault(dev, []).append((temp, level))
        out.append(([
            {"deviceId": dev, "Cnt": len(g),
             "SumL": sum(lv for _t, lv in g),
             "AvgT": float(np.mean([np.float32(tp) for tp, _l in g])),
             "MinT": float(min(np.float32(tp) for tp, _l in g)),
             "MaxL": max(lv for _t, lv in g)}
            for dev, g in sorted(groups.items())], late, too_late))
    return out


def assert_rows(got, want, where):
    assert [r["deviceId"] for r in got] == [r["deviceId"] for r in want], \
        where
    for g, r in zip(got, want):
        assert (g["Cnt"], g["SumL"], g["MaxL"]) == \
            (r["Cnt"], r["SumL"], r["MaxL"]), (where, g, r)
        assert g["AvgT"] == pytest.approx(r["AvgT"], rel=1e-5, abs=1e-5)
        assert g["MinT"] == pytest.approx(r["MinT"], rel=1e-6)


def late_stream(n_batches, window_s, watermark_s, seed):
    """A seeded stream: batches a second apart (now and then two in one
    second, now and then a gap of two or three seconds); each row stamped
    up to ``watermark_s`` + 3 s before its batch's time, a few a little
    ahead of it; device 9 reports early, vanishes for longer than window +
    watermark and returns."""
    rng = np.random.default_rng(seed)
    out, t = [], T0 + 137
    gone = range(2, 2 + window_s + watermark_s + 5)
    for k in range(n_batches):
        t += [1000, 1000, 300, 1000, 2000, 1000, 700, 3000][k % 8]
        n = 0 if k == 4 else int(rng.integers(1, CAP + 1))
        rows = []
        for i in range(n):
            back = int(rng.integers(0, (watermark_s + 3) * 1000))
            if rng.random() < 0.6:
                back = int(rng.integers(0, 400))
            if rng.random() < 0.05:
                back = -int(rng.integers(1, 1500))  # a clock that runs fast
            dev = 9 if i == 0 and k not in gone else int(rng.integers(1, 7))
            rows.append((t - back, dev, float(rng.integers(-400, 400)) / 8,
                         int(rng.integers(-9, 10))))
        out.append((t, rows))
    return out


@pytest.fixture(scope="module", params=[(3, 2), (5, 0), (4, 3)],
                ids=["D3W2", "D5W0", "D4W3"])
def both(request, tmp_path_factory):
    window_s, watermark_s = request.param
    tmp = tmp_path_factory.mktemp(f"d{window_s}w{watermark_s}")
    partial, ring = processors(tmp, window_s, watermark_s)
    slots = num_slots(window_s, watermark_s, 1, event_time=True)
    assert slots == window_s + watermark_s + 2
    assert partial.window_buffers["PerDevice"].slots == slots
    assert ring.ring_slots == {"DataXProcessedInput": slots}
    batches = late_stream(4 * slots, window_s, watermark_s, seed=window_s)
    return (request.param, batches, feed(partial, batches),
            feed(ring, batches), by_the_rule(batches, window_s, watermark_s))


def test_both_window_states_hold_the_rules_rows(both):
    _shape, batches, partial, ring, want = both
    seen_nine = []
    for k, (w_rows, _late, _too_late) in enumerate(want):
        assert_rows(partial[k][0], w_rows, ("partials", k))
        assert_rows(ring[k][0], w_rows, ("ring", k))
        seen_nine.append(any(r["deviceId"] == 9 for r in w_rows))
    assert any(rows for rows, _l, _t in want)
    # the key that vanished left the window and came back
    assert True in seen_nine and False in seen_nine[seen_nine.index(True):] \
        and seen_nine[-1]


def test_the_late_and_dropped_rows_are_counted(both):
    (window_s, watermark_s), _batches, partial, ring, want = both
    for k, (_rows, late, too_late) in enumerate(want):
        for state in (partial, ring):
            m = state[k][1]
            assert m["Window_Late_Rows"] == late, k
            assert m["Window_TooLate_Rows_Dropped"] == too_late, k
        # a fold writes the slots of the intervals rows fell into, at most
        # the two of the batch's on-time rows and the watermark's; the
        # ring its one slot
        assert 0 <= partial[k][1]["Window_Slots_Touched"] <= watermark_s + 2
        assert ring[k][1]["Window_Slots_Touched"] == 1
        assert partial[k][1]["Window_Slots_Live"] <= window_s
    assert sum(t for _r, _l, t in want) > 0
    assert sum(l for _r, l, _t in want) > 0


def rows_at(t, stamps, dev=1):
    return (t, [(ts, dev, 1.0, 1) for ts in stamps])


def test_the_drop_rule_on_both_sides_of_the_edge(tmp_path):
    """w = 2: a row of interval n - 3 is kept, one of n - 4 is dropped and
    counted; the kept one is in the window from batch b + 4 on, for d
    batches."""
    partial, ring = processors(tmp_path, 3, 2)
    n = T0 // I
    batches = [rows_at(T0 + 50, [(n - 3) * I + 999, (n - 4) * I + 999])]
    batches += [rows_at(T0 + 50 + k * I, []) for k in range(1, 6)]
    want = by_the_rule(batches, 3, 2)
    assert [len(r) for r, _l, _t in want] == [0, 1, 1, 1, 0, 0]
    assert want[0][1:] == (1, 1)
    for state in feed(partial, batches), feed(ring, batches):
        assert [len(rows) for rows, _m in state] == [0, 1, 1, 1, 0, 0]
        assert [r["Cnt"] for rows, _m in state for r in rows] == [1, 1, 1]
        assert state[0][1]["Window_Late_Rows"] == 1
        assert state[0][1]["Window_TooLate_Rows_Dropped"] == 1
        assert state[1][1]["Window_TooLate_Rows_Dropped"] == 0


@pytest.mark.parametrize("case", ["empty_second", "two_batches_one_second",
                                  "gap_of_two_seconds", "stamped_ahead"])
def test_the_grid(tmp_path, case):
    """Seconds no batch fell into are dead, not stale; two batches of one
    second add into the same slots and read the same window; a gap brings
    two intervals into the window at once; a row stamped ahead of its
    batch counts in the batch's own interval."""
    partial, ring = processors(tmp_path, 3, 1)
    n = T0 // I
    at = lambda k, ms=10: T0 + k * I + ms  # noqa: E731
    if case == "empty_second":
        # nothing is stamped in second n + 1: K = 6 batches later its slot
        # is still not read as the second's that once lived there
        batches = [rows_at(at(0), [at(0)]), rows_at(at(1), [at(0, 900)]),
                   rows_at(at(2), [at(2)])]
        batches += [rows_at(at(k), [at(k)]) for k in range(3, 12)]
    elif case == "two_batches_one_second":
        batches = [rows_at(at(0, 10), [at(0)]), rows_at(at(0, 600), [at(0, 5)]),
                   rows_at(at(1), [at(0, 990), at(1)], dev=2)]
        batches += [rows_at(at(k), []) for k in range(2, 7)]
    elif case == "gap_of_two_seconds":
        batches = [rows_at(at(0), [at(0)]), rows_at(at(1), [at(1)]),
                   rows_at(at(4), [at(3, 500), at(4)])]
        batches += [rows_at(at(k), []) for k in range(5, 10)]
    else:
        batches = [rows_at(at(0), [at(0, 700), at(1, 300), at(5)]),
                   rows_at(at(1), [at(1)])]
        batches += [rows_at(at(k), []) for k in range(2, 8)]
    want = by_the_rule(batches, 3, 1)
    got_p, got_r = feed(partial, batches), feed(ring, batches)
    for k, (w_rows, late, too_late) in enumerate(want):
        assert_rows(got_p[k][0], w_rows, (case, "partials", k))
        # the ring is a ring of batches: K = 6 of them are fewer than 6
        # seconds where two fell into one, so from its seventh batch on it
        # has lost the first (the bound processing-time windows have too;
        # partials of an event-time window, a slot a second, do not)
        if case != "two_batches_one_second" or k < 6:
            assert_rows(got_r[k][0], w_rows, (case, "ring", k))
        assert got_p[k][1]["Window_TooLate_Rows_Dropped"] == too_late == 0
    counts = [[r["Cnt"] for r in rows] for rows, _l, _t in want]
    if case == "two_batches_one_second":
        # both batches of second n read the same (empty) window; second
        # n's three rows (two of device 1, a late one of device 2) are
        # read together from n + 3 on, and the ring, K = 6 batches, has
        # lost the first of them where the partials still hold it
        assert counts[:5] == [[], [], [], [], [2, 1]] and n == at(0) // I
        assert counts[6] == [2, 2] and \
            [r["Cnt"] for r in got_r[6][0]] == [1, 2]
    elif case == "gap_of_two_seconds":
        # at n + 4 the window (w = 1, d = 3) holds n - 1 .. n + 1
        assert counts[2] == [2]
    elif case == "stamped_ahead":
        # all three rows of the first batch count in its own second
        assert counts[3] == [3] and counts[4] == [4]


def test_the_order_of_arrival_does_not_change_a_window(tmp_path):
    """The same accepted events in order and shuffled within the
    watermark give identical rows, every batch (temperatures are eighths:
    float32 sums of them are exact in any order)."""
    window_s, watermark_s = 4, 3
    rng = np.random.default_rng(7)
    n_batches = 30
    times = [T0 + 40 + k * I for k in range(n_batches)]
    events = []  # (stamp, device, temperature, level): six a second
    for k in range(n_batches - watermark_s):
        for _ in range(6):
            events.append((times[k] - int(rng.integers(0, 30)),
                           int(rng.integers(1, 6)),
                           float(rng.integers(-80, 80)) / 8,
                           int(rng.integers(-9, 10))))
    in_order = [(t, [e for e in events if e[0] // I == t // I])
                for t in times]
    # each event arrives 0 .. watermark_s batches after its own second
    delay = rng.integers(0, watermark_s + 1, len(events))
    shuffled = [(t, [e for e, by in zip(events, delay)
                     if e[0] // I + by == t // I]) for t in times]
    assert sorted(e for _t, rows in shuffled for e in rows) == sorted(events)
    assert [rows for _t, rows in shuffled] != [rows for _t, rows in in_order]
    want = by_the_rule(in_order, window_s, watermark_s)
    assert [r for r, _l, _t in want] == \
        [r for r, _l, _t in by_the_rule(shuffled, window_s, watermark_s)]
    for name, (one, other) in {
        "partials": [processors(tmp_path, window_s, watermark_s)[0]
                     for _ in range(2)],
        "ring": [processors(tmp_path, window_s, watermark_s)[1]
                 for _ in range(2)],
    }.items():
        a, b = feed(one, in_order), feed(other, shuffled)
        for k in range(n_batches):
            assert a[k][0] == b[k][0], (name, k)
            assert_rows(a[k][0], want[k][0], (name, k))
            assert a[k][1]["Window_TooLate_Rows_Dropped"] == 0 == \
                b[k][1]["Window_TooLate_Rows_Dropped"]
        assert sum(m["Window_Late_Rows"] for _r, m in b) > 0


# ---------------------------------------------------------------------------
# the planner's choice
# ---------------------------------------------------------------------------
def test_the_planner_decides_from_the_statements_alone(tmp_path):
    partial, ring = processors(tmp_path, 300, 10)
    assert partial.pipeline.partial_windows == ("DataXProcessedInput_W",)
    assert ring.pipeline.partial_windows == ()
    state = partial.window_buffers["PerDevice"]
    assert (state.slots, state.groups) == (312, 4096)
    plan = partial.window_states["PerDevice"]
    assert (plan.clock.interval_ms, plan.clock.watermark_ms,
            plan.clock.lag, plan.clock.span(plan.duration_ms)) == \
        (1000, 10_000, 11, 300)
    assert set(partial.pipeline.event_tables) == {"DataXProcessedInput"}
    assert not [w for w in partial.pipeline.windows.values()
                if w.slot_uniform_time]
    # no conf key, environment variable or switch names the choice
    from data_accelerator_tpu.analysis import confspec
    assert not [k for k in confspec.registry_index()
                if "partial" in k or "windowstate" in k or "eventtime" in k]
    view = partial.pipeline.view_by_name("PerDevice")
    assert view.plan.input_rows == CAP
    assert view.plan.window_state_bytes == partial.window_state_bytes()


# ---------------------------------------------------------------------------
# checkpoint: a slot is written again when a late row changed it
# ---------------------------------------------------------------------------
def steady(first, count, seed, watermark_s=3):
    """One batch a second from second ``first``: four on-time rows and two
    late ones (up to ``watermark_s`` s back), devices 1-8."""
    rng = np.random.default_rng(seed)
    out = []
    for k in range(first, first + count):
        t = T0 + 20 + k * I
        rows = [(t - int(rng.integers(0, 15)), int(rng.integers(1, 9)),
                 float(rng.integers(0, 400)) / 8, int(rng.integers(0, 9)))
                for _ in range(4)]
        rows += [(t - int(rng.integers(1000, watermark_s * 1000)),
                  int(rng.integers(1, 9)), float(rng.integers(0, 400)) / 8,
                  int(rng.integers(0, 9))) for _ in range(2)]
        out.append((t, rows))
    return out


def seconds_of(batches):
    """The seconds (from T0's) the rows of ``batches`` are stamped in."""
    return {ts // I - T0 // I for _t, rows in batches for ts, *_row in rows}


def test_a_checkpoint_writes_the_slots_late_rows_changed_and_restores(
        tmp_path):
    c = conf(tmp_path, PER_DEVICE, 8, 3)  # 13 slots
    proc = FlowProcessor(c, batch_capacity=CAP, output_datasets=["PerDevice"])
    ck = WindowStateCheckpointer(str(tmp_path / "ck"))
    stream = steady(0, 5, 1) + steady(5, 5, 2) + steady(10, 4, 3)
    feed(proc, stream[:5])
    ck.save(proc.snapshot_window_state(since=ck.landed_counter))
    # every second a row has been stamped in so far (late ones reach back
    # before second 0)
    first_seconds = seconds_of(stream[:5])
    assert ck.last_slots == len(first_seconds) >= 5
    feed(proc, stream[5:10])
    snap = proc.snapshot_window_state(since=ck.landed_counter)
    part = snap["partials"]["PerDevice"]
    # batches 5-9 wrote the five new seconds and, of those the first
    # checkpoint already held, the ones late rows changed since: exactly
    # the seconds their rows are stamped in (the state's own generations,
    # not a guess at what a batch may reach)
    seconds = lambda rows: sorted(  # noqa: E731
        (int(part["slot_ts"][s]) + snap["base_ms"] - T0) // I for s in rows)
    changed = seconds_of(stream[5:10])
    assert seconds(part["rows"]) == sorted(changed)
    assert set(range(5, 10)) < changed <= set(range(2, 10))
    assert changed & first_seconds
    assert part["first_gen"] == 5
    assert set(part["slot_gen"][part["rows"]]) <= set(range(5, 10))
    groups = 13 * CAP
    assert sum(a.nbytes for a in part["parts"].values()) == \
        len(changed) * groups * 5 * 4
    ck.save(snap)
    assert (ck.landed_counter, ck.last_slots) == (10, len(changed))
    assert ck.last_bytes < 0.8 * proc.window_state_bytes()
    # the head names for each slot still held (second 9 - 4 - 8 on) the
    # newest file that holds it: the seconds batches 5-9 left alone in the
    # first file, the others in the second
    named = [name.split(".")[1] for _g, name, _r
             in ck._slot_files["PerDevice"].values()]
    older = {b for b in first_seconds - changed if b >= -3}
    assert sorted(named) == ["0-4"] * len(older) + ["5-9"] * len(changed)
    assert len(older) >= 2
    assert len(os.listdir(ck.slots_dir)) == 2

    # a restart restores every slot, late rows included: the next
    # batches' rows are the ones the first process lands
    again = FlowProcessor(c, batch_capacity=CAP,
                          output_datasets=["PerDevice"])
    ck2 = WindowStateCheckpointer(str(tmp_path / "ck"))
    assert again.restore_window_state(ck2.load())
    assert ck2.landed_counter == 10
    want = by_the_rule(stream, 8, 3)[10:]
    first = [rows for rows, _m in feed(proc, stream[10:])]
    got = [rows for rows, _m in feed(again, stream[10:])]
    assert got == first
    for k, rows in enumerate(got):
        assert_rows(rows, want[k][0], k)
    assert len(got[-1]) == 8
    # its own next checkpoint builds on what it loaded; the first file
    # goes once neither head nor ``.old`` names it: once the newest second
    # only it holds has left what the older of the two heads keeps (the
    # window's 8 seconds and the 4 before them)
    ck2.save(again.snapshot_window_state(since=ck2.landed_counter))
    ranges = lambda: sorted(  # noqa: E731
        f.split(".")[1] for f in os.listdir(ck2.slots_dir))
    assert ranges() == ["0-4", "10-13", "5-9"]
    only_first = max(first_seconds - changed - seconds_of(stream[10:]))
    for j in range(14, 19):
        feed(again, steady(j, 1, j))
        ck2.save(again.snapshot_window_state(since=ck2.landed_counter))
        assert ("0-4" in ranges()) == (only_first >= j - 1 - 12), j
        assert f"{j}-{j}" in ranges()
    assert "0-4" not in ranges() and "5-9" in ranges()


@pytest.mark.parametrize("torn", ["head", "slot_tmp", "newest_slot_file"])
def test_a_torn_checkpoint_of_a_late_stream_falls_back(tmp_path, torn):
    c = conf(tmp_path, PER_DEVICE, 8, 3)
    proc = FlowProcessor(c, batch_capacity=CAP, output_datasets=["PerDevice"])
    ck = WindowStateCheckpointer(str(tmp_path / "ck"))
    stream = steady(0, 3, 1) + steady(3, 4, 2) + steady(7, 3, 3)
    feed(proc, stream[:3])
    ck.save(proc.snapshot_window_state(since=ck.landed_counter))
    feed(proc, stream[3:7])
    ck.save(proc.snapshot_window_state(since=ck.landed_counter))
    newest = max(os.listdir(ck.slots_dir),
                 key=lambda f: int(f.split(".")[1].split("-")[0]))
    if torn == "head":
        with open(ck.path, "r+b") as f:
            f.truncate(os.path.getsize(ck.path) // 3)
    elif torn == "slot_tmp":
        with open(os.path.join(ck.slots_dir, "PerDevice.7-9.dead.npz.tmp"),
                  "wb") as f:
            f.write(b"PK\x03\x04 torn")
    else:
        os.remove(os.path.join(ck.slots_dir, newest))
    snap = WindowStateCheckpointer(str(tmp_path / "ck")).load()
    assert snap is not None
    # a state some completed checkpoint described: the newest (7) where
    # only a temp file is torn, else the one before (3), whose slots are
    # as they were before the late rows of batches 3-6 changed them
    landed = 7 if torn == "slot_tmp" else 3
    assert snap["slot_counter"] == landed
    again = FlowProcessor(c, batch_capacity=CAP,
                          output_datasets=["PerDevice"])
    assert again.restore_window_state(snap)
    # replaying from there lands what the rule gives for the whole stream
    want = by_the_rule(stream, 8, 3)
    for k, (rows, _m) in enumerate(feed(again, stream[landed:]), landed):
        assert_rows(rows, want[k][0], (torn, k))


# ---------------------------------------------------------------------------
# under a mesh
# ---------------------------------------------------------------------------
def test_under_a_four_device_mesh_the_rows_are_the_one_device_rows(tmp_path):
    four = FlowProcessor(
        conf(tmp_path, PER_DEVICE, 3, 2,
             **{"datax.job.process.numchips": "4"}),
        batch_capacity=CAP, output_datasets=["PerDevice"])
    assert four.mesh is not None and four.mesh.size == 4
    for leaf in jax.tree_util.tree_leaves(four.window_buffers["PerDevice"]):
        assert len(leaf.sharding.device_set) == 4
    batches = late_stream(20, 3, 2, seed=11)
    one = FlowProcessor(conf(tmp_path, PER_DEVICE, 3, 2), batch_capacity=CAP,
                        output_datasets=["PerDevice"])
    want = by_the_rule(batches, 3, 2)
    for k, ((rows1, m1), (rows4, m4)) in enumerate(
            zip(feed(one, batches), feed(four, batches))):
        assert_rows(rows4, want[k][0], k)
        assert [(r["deviceId"], r["Cnt"], r["SumL"]) for r in rows1] == \
            [(r["deviceId"], r["Cnt"], r["SumL"]) for r in rows4]
        assert m4["Mesh_Chips"] == 4.0
        for name in ("Window_Late_Rows", "Window_TooLate_Rows_Dropped",
                     "Window_Slots_Touched"):
            assert m1[name] == m4[name]
