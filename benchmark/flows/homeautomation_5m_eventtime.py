"""HomeAutomation sample on the sensors' own clock: the events, their wire
form and the plain reference of the flow (DoorLock rule + COUNT/AVG per
deviceId over a 5-minute window by event time, under a 10 s watermark)
for a fleet of 131,072 devices. numpy only; shares no code with the
engine.

The distribution is ``flows/homeautomation_5m.py``'s (2 % DoorLock events,
half of them open, so ~1 % of rows trip the rule; three homes;
temperatures in thousandths of a degree, 0-100; the device id uniform
over 1..131,072 as a six-digit field) with one more field: ``eventTime``,
the epoch ms the device stamped the reading with, 13 digits. An event's
stamp is the moment it is due to be sent less its delay: none for 89.9 %
of events, uniform over 0-3,000 ms for 10 % (Beam NEXmark's
``probDelayedEvent`` 0.1, ``occasionalDelaySec`` 3), uniform over 3-30 s
for 0.1 % (a device that reconnects and flushes its buffer). A line is
134 bytes, fixed width, so a chunk renders as one uint8 matrix with no
per-row Python.

The rule the reference is written from (I = 1,000 ms the batch interval,
w = 10 intervals of watermark, d = 300 of window): a batch the host
recorded at time t has n = floor(t / I); an event stamped ts has b =
floor(ts / I), taken as n when it is stamped ahead of its batch. t is the
moment the host polled the batch's events, which came in over the
interval before it, so the ones stamped as they were sent read b = n - 1
or n: an event is accepted iff b >= n - w - 1. ``HeatAvg`` at batch n is
COUNT and AVG per device over the accepted events with n - w - 1 - d <=
b < n - w - 1. ``OpenDoors`` is the batch's own arrivals, every one, each
with its own stamp."""

import os
from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmark import wire

# This deployment is served only by an engine that holds an event-time
# window as per-slot partial aggregates (``runtime/timewindow.py``: a
# slot an interval of event time, ``event_rows`` puts a batch on the
# grid; PR 34). An engine whose partials need the batch's one time keeps
# the raw rows for a payload time column: 311 slots x 262,144 rows (2.0
# GB) and an 81 M-row sort a batch, behind its 1 s interval from the
# first batch, and its window is not the rule's. The harness cannot ask
# the child before it starts it, and this module imports nothing of the
# engine, so it reads the engine's source and refuses to load beside a
# tree without it: the run then has no result at once (exit code 1).
_ENGINE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), "data_accelerator_tpu", "runtime", "timewindow.py")
with open(_ENGINE, encoding="utf-8") as _f:
    if "def event_rows" not in _f.read():
        raise RuntimeError(
            "homeautomation-5m-eventtime needs an event-time window held "
            f"as per-slot partial aggregates (event_rows in {_ENGINE}); "
            "this engine keeps the raw rows of a payload time column and "
            "cannot serve TIMEWINDOW('5 minutes') over a 262,144-row batch "
            "in its interval")

DEVICES = 131_072
DEVICE_TYPES = (b'"DoorLock" ', b'"Heating"  ', b'"WindSpeed"')
HOME_IDS = (b"150", b" 32", b" 88")
INTERVAL_MS = 1_000
WINDOW_MS = 300_000
WATERMARK_MS = 10_000
# the window's 300 intervals, the watermark's 10, the two a batch's on-time
# events fall in
RING_SLOTS = 312
# whole intervals the window trails its batch by, and an event may lie
# behind it and count
LAG = WATERMARK_MS // INTERVAL_MS + 1
# lateness: the share of events delayed, and by how much at most (ms)
DELAYED_SHARE, DELAYED_MS = 0.1, 3_000
FLUSHED_SHARE, FLUSHED_MS = 0.001, 30_000

_TEMPLATE = (
    b'{"deviceDetails":{"deviceId":DDDDDD,"deviceType":TTTTTTTTTTT,'
    b'"homeId":HHH,"status":S,"temperature":WW.FFF,'
    b'"eventTime":EEEEEEEEEEEEE}}\n'
)


_DEVICE, _STATUS = (wire.field(_TEMPLATE, b"DDDDDD"),
                    wire.field(_TEMPLATE, b":S", 1))
_TYPE, _HOME = (wire.field(_TEMPLATE, b"TTTTTTTTTTT"),
                wire.field(_TEMPLATE, b"HHH"))
_WHOLE, _FRAC = wire.field(_TEMPLATE, b"WW"), wire.field(_TEMPLATE, b"FFF")
_TIME = wire.field(_TEMPLATE, b"E" * 13)
_TYPE_TABLE = np.frombuffer(b"".join(DEVICE_TYPES), np.uint8).reshape(3, -1)
_HOME_TABLE = np.frombuffer(b"".join(HOME_IDS), np.uint8).reshape(3, -1)
LINE_BYTES = len(_TEMPLATE)


def make_events(seed, n: int, first: int = 0) -> Dict[str, np.ndarray]:
    """``n`` events from ``seed`` (an int or a SeedSequence); events are
    alike all along the stream, so ``first`` (where the block starts) is
    not used. ``delay_ms``: how long before it is due the event was
    stamped."""
    rng = np.random.default_rng(seed)
    u = rng.random(n, dtype=np.float32)
    bits = rng.integers(0, 1 << 32, n, dtype=np.uint32)
    ids = rng.integers(0, DEVICES, n, dtype=np.uint32)
    late = rng.random(n)
    by = rng.integers(0, 1 << 32, n, dtype=np.uint32).astype(np.int64)
    is_door = u < 0.02
    return {
        "device": (1 + ids).astype(np.int32),
        "type": np.where(is_door, 0, 1 + ((bits >> 3) & 1)).astype(np.int8),
        "home": (((bits >> 4) & 0xFF) % 3).astype(np.int8),
        "status": np.where(u < 0.01, 0, 1).astype(np.int8),
        "milli": ((bits >> 12) % 100_000).astype(np.int32),
        "delay_ms": np.where(
            late < FLUSHED_SHARE,
            DELAYED_MS + by % (FLUSHED_MS - DELAYED_MS + 1),
            np.where(late < FLUSHED_SHARE + DELAYED_SHARE,
                     by % (DELAYED_MS + 1), 0)).astype(np.int64),
    }


def event_time_ms(ev: Dict[str, np.ndarray], lo: int, hi: int) -> np.ndarray:
    """The stamp each event carries: ``ev["due_ms"]`` (epoch ms the event
    is due to be sent, set by the harness) less its delay."""
    return ev["due_ms"][lo:hi] - ev["delay_ms"][lo:hi]


def lines(ev: Dict[str, np.ndarray], lo: int, hi: int) -> bytes:
    out = np.tile(np.frombuffer(_TEMPLATE, np.uint8), (hi - lo, 1))
    milli = ev["milli"][lo:hi]
    out[:, _DEVICE] = wire.digits(ev["device"][lo:hi], 6, 32)
    out[:, _TYPE] = _TYPE_TABLE[ev["type"][lo:hi]]
    out[:, _HOME] = _HOME_TABLE[ev["home"][lo:hi]]
    out[:, _STATUS] = (ev["status"][lo:hi] + 48)[:, None]
    out[:, _WHOLE] = wire.digits(milli // 1000, 2, 32)
    out[:, _FRAC] = wire.digits(milli % 1000, 3, 48)
    out[:, _TIME] = wire.digits(event_time_ms(ev, lo, hi), 13, 48)
    return out.tobytes()


def alert_events(ev: Dict[str, np.ndarray], lo: int, hi: int) -> np.ndarray:
    """Stream indices in [lo, hi) of the events that land an alert row
    (an OpenDoors row), in stream order."""
    door = (ev["type"][lo:hi] == 0) & (ev["status"][lo:hi] == 0)
    return lo + np.flatnonzero(door)


def temperature(milli: np.ndarray) -> np.ndarray:
    """The float32 the decoder stores for the text ``<milli/1000>``."""
    return (milli / 1000.0).astype(np.float32)


def accepted_buckets(ev: Dict[str, np.ndarray],
                     batches: Sequence[Tuple[int, int]]
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per event of the batches: the interval it counts in (its own, or
    its batch's when stamped ahead), whether the watermark accepted it,
    and its batch's interval."""
    rows = np.array([n for _t, n in batches], np.int64)
    n_of = np.repeat(np.array([t for t, _n in batches], np.int64)
                     // INTERVAL_MS, rows)
    b = np.minimum(event_time_ms(ev, 0, int(rows.sum())) // INTERVAL_MS, n_of)
    return b, b >= n_of - LAG, n_of


def reference(
    ev: Dict[str, np.ndarray], batches: Sequence[Tuple[int, int]],
    cast=lambda x: x,
) -> Dict[str, List[Dict[str, np.ndarray]]]:
    """``batches``: (batch time ms, valid rows) as the host recorded
    them; batch k consumed the next ``rows`` events of the stream.
    Per batch: OpenDoors as (deviceId, eventTimeStamp) columns in stream
    order, the stamp the event's own; HeatAvg as (deviceId, Cnt, AvgT)
    over the accepted events of the 300 intervals the window covers at
    that batch. An interval's per-device counts and float64 sums (over
    the float32 inputs) are one bincount each, taken when the interval
    enters the window: by then every event the watermark accepts for it
    has arrived. The window's are kept by adding the interval that
    enters and taking off the one that leaves (float64: exact for the
    counts, ~1e-16 relative for the sums). ``cast`` narrows the inputs
    and the average (the low-precision control)."""
    bounds = np.concatenate([[0], np.cumsum([n for _t, n in batches])])
    total = int(bounds[-1])
    temp = cast(temperature(ev["milli"][:total])).astype(np.float64)
    stamp = event_time_ms(ev, 0, total)
    bucket, accepted, _n_of = accepted_buckets(ev, batches)
    size = DEVICES + 1
    lag, span = LAG, WINDOW_MS // INTERVAL_MS
    # accepted events by interval
    order = np.flatnonzero(accepted)
    order = order[np.argsort(bucket[order], kind="stable")]
    which, start, count = np.unique(
        bucket[order], return_index=True, return_counts=True)
    edges = {int(b): (lo, lo + c) for b, lo, c in zip(which, start, count)}

    def sums(b: int):
        lo, hi = edges.get(b, (0, 0))
        at = order[lo:hi]
        return (np.bincount(ev["device"][at], minlength=size),
                np.bincount(ev["device"][at], temp[at], minlength=size))

    doors, heat = [], []
    cnt = np.zeros(size, np.int64)
    tot = np.zeros(size, np.float64)
    held: List[int] = []  # the intervals in the running totals
    for k, (t, _n) in enumerate(batches):
        at = alert_events(ev, bounds[k], bounds[k + 1])
        doors.append({
            "deviceId": ev["device"][at].astype(np.int64),
            "eventTimeStamp": stamp[at].astype(np.int64),
        })
        n = t // INTERVAL_MS
        want = [b for b in range(n - lag - span, n - lag) if b in edges]
        for b in held:
            if b not in want:
                c, s = sums(b)
                cnt -= c
                tot -= s
        for b in want:
            if b not in held:
                c, s = sums(b)
                cnt += c
                tot += s
        held = want
        live = np.flatnonzero(cnt)
        heat.append({
            "deviceId": live.astype(np.int64), "Cnt": cnt[live].copy(),
            "AvgT": cast(tot[live] / cnt[live]).astype(np.float64),
        })
    return {"OpenDoors": doors, "HeatAvg": heat}


def control(ev, batches):
    """The reference in the nearest precision below the float32 the
    configuration states: bfloat16 inputs and result."""
    return reference(ev, batches, cast=wire.bfloat16)


# how each output's columns are held to the reference: "exact", or the
# name of the relative-gap number the comparison reports for it
COLUMNS = {
    "OpenDoors": {"deviceId": "exact", "eventTimeStamp": "exact"},
    "HeatAvg": {"deviceId": "key", "Cnt": "exact", "AvgT": "avg_rel_gap"},
}

# each number compared and its limit; PERF.md section 2 gives the
# readings each was set from. ``rows_differ`` 0 holds both sides of the
# drop rule through ``Cnt``: a row kept that should go, or gone that
# should stay, is a differing count
LIMITS = {
    "rows_differ": 0, "offset_off_boundary": 0, "window_snapshot_missing": 0,
    # float32 sums of a device's few rows an interval, combined over up to
    # 300 slots on the device, against float64
    "avg_rel_gap": 1e-4,
}
