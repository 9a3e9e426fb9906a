"""The accumulator's own kernels: their device time by scope, and the
least time any exact accumulator needs for what they do.

The program runs every statement that reads or writes a state table
under ``dx.state.<statement>`` (``compile/pipeline.py``), where other
statements run under ``dx.view.<statement>``. The configuration's
``roofline.state.statements`` names the accumulator's statements (the
UNION ALL of the batch's rows and the table, and the GROUP BY that folds
them). A program without those scopes (one that runs them under
``dx.view.*``) gives ``None``.

The least bytes a batch (4 a value), from the configuration's shapes and
the program's own count of the table's rows (``State_Rows``), whatever
implements the accumulator:
  the batch's rows x the columns the accumulator reads (the key and the
    values it folds), read once;
  the table's valid rows x its columns, read once (the state before the
    batch) and written once (the state after it).
Least time = bytes / peak HBM bytes a second. The share is that over the
measured time of the accumulator's scopes, in %: the same work whatever
implements it, so it cannot pass 100."""

from typing import Dict, Optional

import numpy as np

from benchmark import roofline
from benchmark.window_roofline import scope_ms

STATE_ROWS = "State_Rows"
INPUT_ROWS = "Input_DataXProcessedInput_Events_Count"


def state_ms(cell: dict, run: dict) -> Optional[float]:
    """Device ms a batch under the accumulator's ``dx.state.*`` scopes,
    None where the program has none of them."""
    names = cell["config"]["roofline"]["state"]["statements"]
    times = [scope_ms(run, f"dx.state.{n}") for n in names]
    if all(t is None for t in times):
        return None
    return float(sum(t for t in times if t is not None))


def least_bytes(shapes: dict, rows: float, state_rows: float
                ) -> Dict[str, float]:
    """Bytes one batch of ``rows`` valid rows needs of an accumulator
    whose table holds ``state_rows`` valid rows."""
    st = shapes["state"]
    table = state_rows * st["table_columns"]
    values = {
        "batch_read": rows * st["columns_read"],
        "table_read": table,
        "table_written": table,
    }
    return {k: float(v * roofline.VALUE_BYTES) for k, v in values.items()}


def roofline_pct(cell: dict, run: dict, m: dict) -> Optional[float]:
    took = state_ms(cell, run)
    held = [ms[STATE_ROWS] for ms in m["measurements"] if STATE_ROWS in ms]
    if not took or not held:
        return None
    rows = float(np.median([ms.get(INPUT_ROWS, 0.0)
                            for ms in m["measurements"]]))
    need = least_bytes(cell["config"]["roofline"], rows,
                       float(np.median(held)))
    peak = roofline.peaks(run["rec"].device["deviceKind"])
    least_ms = 1000.0 * sum(need.values()) / peak["hbm_bytes_per_s"]
    return 100.0 * least_ms / took
