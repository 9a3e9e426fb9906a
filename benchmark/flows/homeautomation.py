"""HomeAutomation sample: the events, their wire form and the plain
reference of the flow (DoorLock rule + COUNT/AVG per deviceId over the
last 5 seconds). numpy only; shares no code with the engine.

The distribution is ``chip_smoke.py``'s (2 % DoorLock events, half of
them open, so ~1 % of rows trip the rule; 8 device ids uniform; three
homes; temperatures in thousandths of a degree, 0-100). The draw and the
wire form differ from the smoke's for speed: one bit field per event, and
fixed-width lines (numbers padded on the left, the device type on the
right, with spaces JSON allows) so a chunk renders as one uint8 matrix
with no per-row Python."""

from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmark import wire

DEVICE_TYPES = (b'"DoorLock" ', b'"Heating"  ', b'"WindSpeed"')
HOME_IDS = (b"150", b" 32", b" 88")
WINDOW_MS = 5_000
RING_SLOTS = 6  # ceil(5 s / 1 s) + 1 batches the engine's ring holds

_TEMPLATE = (
    b'{"deviceDetails":{"deviceId":D,"deviceType":TTTTTTTTTTT,'
    b'"homeId":HHH,"status":S,"temperature":WW.FFF}}\n'
)


_DEVICE, _STATUS = (wire.field(_TEMPLATE, b":D", 1),
                    wire.field(_TEMPLATE, b":S", 1))
_TYPE, _HOME = (wire.field(_TEMPLATE, b"TTTTTTTTTTT"),
                wire.field(_TEMPLATE, b"HHH"))
_WHOLE, _FRAC = wire.field(_TEMPLATE, b"WW"), wire.field(_TEMPLATE, b"FFF")
_TYPE_TABLE = np.frombuffer(b"".join(DEVICE_TYPES), np.uint8).reshape(3, -1)
_HOME_TABLE = np.frombuffer(b"".join(HOME_IDS), np.uint8).reshape(3, -1)
LINE_BYTES = len(_TEMPLATE)


def make_events(seed, n: int, first: int = 0) -> Dict[str, np.ndarray]:
    """``n`` events from ``seed`` (an int or a SeedSequence); events are
    alike all along the stream, so ``first`` (where the block starts) is
    not used."""
    rng = np.random.default_rng(seed)
    u = rng.random(n, dtype=np.float32)
    bits = rng.integers(0, 1 << 32, n, dtype=np.uint32)
    is_door = u < 0.02
    return {
        "device": (1 + (bits & 7)).astype(np.int8),
        "type": np.where(is_door, 0, 1 + ((bits >> 3) & 1)).astype(np.int8),
        "home": (((bits >> 4) & 0xFF) % 3).astype(np.int8),
        "status": np.where(u < 0.01, 0, 1).astype(np.int8),
        "milli": ((bits >> 12) % 100_000).astype(np.int32),
    }


def lines(ev: Dict[str, np.ndarray], lo: int, hi: int) -> bytes:
    out = np.tile(np.frombuffer(_TEMPLATE, np.uint8), (hi - lo, 1))
    milli = ev["milli"][lo:hi]
    out[:, _DEVICE] = (ev["device"][lo:hi] + 48)[:, None]
    out[:, _TYPE] = _TYPE_TABLE[ev["type"][lo:hi]]
    out[:, _HOME] = _HOME_TABLE[ev["home"][lo:hi]]
    out[:, _STATUS] = (ev["status"][lo:hi] + 48)[:, None]
    out[:, _WHOLE] = wire.digits(milli // 1000, 2, 32)
    out[:, _FRAC] = wire.digits(milli % 1000, 3, 48)
    return out.tobytes()


def alert_events(ev: Dict[str, np.ndarray], lo: int, hi: int) -> np.ndarray:
    """Stream indices in [lo, hi) of the events that land an alert row
    (an OpenDoors row), in stream order."""
    door = (ev["type"][lo:hi] == 0) & (ev["status"][lo:hi] == 0)
    return lo + np.flatnonzero(door)


def temperature(milli: np.ndarray) -> np.ndarray:
    """The float32 the decoder stores for the text ``<milli/1000>``."""
    return (milli / 1000.0).astype(np.float32)


def reference(
    ev: Dict[str, np.ndarray], batches: Sequence[Tuple[int, int]],
    cast=lambda x: x,
) -> Dict[str, List[Dict[str, np.ndarray]]]:
    """``batches``: (batch time ms, valid rows) as the host recorded
    them; batch k consumed the next ``rows`` events of the stream.
    Per batch: OpenDoors as (deviceId, eventTimeStamp) columns in stream
    order; HeatAvg as (deviceId, Cnt, AvgT) over the batches the ring
    still holds (the last RING_SLOTS) whose time lies in [t - 5 s, t].
    Sums are float64 over the float32 inputs. ``cast`` narrows the
    inputs and the average (the low-precision control)."""
    bounds = np.concatenate([[0], np.cumsum([n for _t, n in batches])])
    temp = cast(temperature(ev["milli"][:bounds[-1]])).astype(np.float64)
    doors, heat = [], []
    for k, (t, _n) in enumerate(batches):
        at = alert_events(ev, bounds[k], bounds[k + 1])
        doors.append({
            "deviceId": ev["device"][at].astype(np.int64),
            "eventTimeStamp": np.full(len(at), t, np.int64),
        })
        cnt = np.zeros(9, np.int64)
        tot = np.zeros(9, np.float64)
        for j in range(max(0, k - RING_SLOTS + 1), k + 1):
            if not t - WINDOW_MS <= batches[j][0] <= t:
                continue
            a, b = bounds[j], bounds[j + 1]
            cnt += np.bincount(ev["device"][a:b], minlength=9)
            tot += np.bincount(ev["device"][a:b], temp[a:b], minlength=9)
        live = np.flatnonzero(cnt)
        heat.append({
            "deviceId": live.astype(np.int64), "Cnt": cnt[live],
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
# readings each was set from
LIMITS = {
    "rows_differ": 0, "offset_off_boundary": 0, "window_snapshot_missing": 0,
    # float32 sums of up to 6 x width / 8 rows a key against float64
    "avg_rel_gap": 1e-4,
}
