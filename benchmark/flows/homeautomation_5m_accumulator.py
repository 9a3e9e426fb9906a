"""HomeAutomation sample with its ``--DataXStates--`` accumulator at fleet
size: ``homeautomation_5m.py``'s events, wire form and reference (DoorLock
rule + COUNT/AVG per deviceId over the last 5 minutes, 131,072 devices),
loaded from that module and not copied, plus the plain reference of the
accumulator. numpy only; shares no code with the engine.

The accumulator follows the sample's pattern (a new batch's rows UNION ALL
the table, then GROUP BY the key): ``DeviceTotals`` keeps, for every
device that has reported since the run's first batch, its event count,
the least status it reported and the highest temperature. The table lands
whole every batch as a third output, so the comparison sees the state
itself, row for row. Every column is exact: a count, a min of small
integers, a max of the float32 values the decoder stored."""

import importlib.util
import os
from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchmark import wire

_HERE = os.path.dirname(os.path.abspath(__file__))

# This deployment is served only by an engine whose state table holds the
# key bound (``process.maxgroups``) and persists under the job's own
# checkpoint directory (``runtime/statetable.py state_capacity``). An
# engine without them keeps the table under /tmp, outside the run
# directory the harness empties, so every run resumes the totals of the
# run before it (and the parent's and the change's runs resume each
# other's), where the reference starts from nothing; and it sizes the
# table at four batch widths, 1,048,576 rows, so its first batch unions
# and sorts 1,310,720. The harness cannot ask the child before it starts
# it, and this module imports nothing of the engine, so it reads the
# engine's source and refuses to load beside a tree without the rule: the
# run then has no result at once (exit code 1).
_ENGINE = os.path.join(os.path.dirname(os.path.dirname(_HERE)),
                       "data_accelerator_tpu", "runtime", "statetable.py")
with open(_ENGINE, encoding="utf-8") as _f:
    if "def state_capacity" not in _f.read():
        raise RuntimeError(
            "homeautomation-5m-accumulator needs a state table sized by "
            f"process.maxgroups and kept under the job's checkpoint "
            f"directory (state_capacity in {_ENGINE}); this engine keeps "
            "it under /tmp at four batch widths")


def _load(name: str):
    spec = importlib.util.spec_from_file_location(
        f"benchmark_flow_{name}", os.path.join(_HERE, f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_base = _load("homeautomation_5m")

DEVICES = _base.DEVICES
LINE_BYTES = _base.LINE_BYTES
make_events = _base.make_events
lines = _base.lines
alert_events = _base.alert_events
temperature = _base.temperature


def totals(ev: Dict[str, np.ndarray], batches: Sequence[Tuple[int, int]],
           cast=lambda x: x) -> List[Dict[str, np.ndarray]]:
    """``DeviceTotals`` as it stands after each batch: one row a device
    seen in any batch so far, by deviceId, with its event count, least
    status and highest temperature over every event of those batches
    (the run's first batch on, in batch order). ``cast`` narrows the
    temperatures (the low-precision control)."""
    size = DEVICES + 1
    count = np.zeros(size, np.int64)
    least = np.full(size, np.iinfo(np.int64).max, np.int64)
    most = np.full(size, -np.inf, np.float64)
    out, lo = [], 0
    for _t, n in batches:
        dev = ev["device"][lo:lo + n]
        count += np.bincount(dev, minlength=size)
        np.minimum.at(least, dev, ev["status"][lo:lo + n].astype(np.int64))
        np.maximum.at(most, dev, cast(
            temperature(ev["milli"][lo:lo + n])).astype(np.float64))
        lo += n
        live = np.flatnonzero(count)
        out.append({"deviceId": live.astype(np.int64),
                    "Events": count[live].copy(),
                    "MinStatus": least[live].copy(),
                    "MaxT": most[live].copy()})
    return out


def reference(ev: Dict[str, np.ndarray], batches: Sequence[Tuple[int, int]],
              cast=lambda x: x) -> Dict[str, List[Dict[str, np.ndarray]]]:
    """``homeautomation_5m.reference`` (OpenDoors, HeatAvg) and the
    accumulator's table after every batch."""
    return {**_base.reference(ev, batches, cast),
            "DeviceTotals": totals(ev, batches, cast)}


def control(ev, batches):
    """The reference in the nearest precision below the float32 the
    configuration states: bfloat16 inputs and results."""
    return reference(ev, batches, cast=wire.bfloat16)


COLUMNS = {
    **_base.COLUMNS,
    "DeviceTotals": {"deviceId": "key", "Events": "exact",
                     "MinStatus": "exact", "MaxT": "exact"},
}

# the window's limits (PERF.md section 2); the accumulator's columns are
# exact, so they add nothing but rows to ``rows_differ``
LIMITS = dict(_base.LIMITS)
