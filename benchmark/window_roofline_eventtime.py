"""The least time any exact implementation of an event-time window state
needs for one batch: ``window_roofline.py``'s reckoning for a window whose
slot is an interval of event time, which a batch's rows fall into several
of (late arrivals) and add to, where a processing-time window writes its
one slot whole.

The least bytes a batch (4 a value), from the configuration's shapes and
the slots the program's own counter says the fold wrote
(``Window_Slots_Touched``), whatever implements the window:
  the batch's rows x the columns the window reads (key, argument and the
    time that says which interval a row belongs to), read once;
  each slot rows fell into, groups x aggregates, read and written (a
    late row is added to partials that are there already);
  the slot that leaves the window and one running state, each groups x
    aggregates, read (as ``window_roofline.py``: an implementation that
    reduces all live slots reads slots x as much and sits low here);
  the output: the rows landed x its columns, written.
Least time = bytes / peak HBM bytes a second. The share is that over the
measured time of ``dx.window.partial`` and ``dx.window.combine``, in %:
the same work whatever implements it, so it cannot pass 100. A program
without the scopes or the counter (a commit before PR 34) gives ``None``."""

from typing import Dict, Optional

import numpy as np

from benchmark import roofline, window_roofline

TOUCHED = "Window_Slots_Touched"


def least_bytes(shapes: dict, groups: int, rows: float, rows_out: float,
                slots_touched: float) -> Dict[str, float]:
    """Bytes one batch of ``rows`` valid rows, fallen into
    ``slots_touched`` intervals, needs of the window state."""
    win = shapes["window"]
    cell = groups * win["aggregates"]
    values = {
        "batch_read": rows * (win["columns_read"] + 1),
        "touched_slots_read": slots_touched * cell,
        "touched_slots_written": slots_touched * cell,
        "leaving_slot_read": cell,
        "running_state_read": cell,
        "output_written": rows_out * shapes["output_columns"]["HeatAvg"],
    }
    return {k: float(v * roofline.VALUE_BYTES) for k, v in values.items()}


def roofline_pct(cell: dict, run: dict, m: dict) -> Optional[float]:
    times = [window_roofline.scope_ms(run, window_roofline.PARTIAL),
             window_roofline.scope_ms(run, window_roofline.COMBINE)]
    touched = [ms[TOUCHED] for ms in m["measurements"] if TOUCHED in ms]
    if None in times or not sum(times) or not touched:
        return None
    config = cell["config"]
    median = lambda key: float(np.median(  # noqa: E731
        [ms.get(key, 0.0) for ms in m["measurements"]]))
    need = least_bytes(
        config["roofline"], int(config["conf"][window_roofline.GROUPS_KEY]),
        median("Input_DataXProcessedInput_Events_Count"),
        median("Output_HeatAvg_Events_Count"), float(np.median(touched)))
    peak = roofline.peaks(run["rec"].device["deviceKind"])
    least_ms = 1000.0 * sum(need.values()) / peak["hbm_bytes_per_s"]
    return 100.0 * least_ms / sum(times)
