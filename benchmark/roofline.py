"""The least time one chip needs for one batch of a flow, reckoned from
the flow's shapes alone: the work the flow needs whatever implements it.
It never reads the step's HLO, so a later PR that replaces the sort with
slot partials raises the share without making the count stale.

Bytes (4 a value: the engine keeps int32/float32 columns):
  the batch's packed input, read once: (input columns + 1 validity row)
    x rows;
  each column the rules read, once a row;
  for a windowed GROUP BY: the window's live rows (slots x rows) x the
    columns it reads, plus the slot written (rows x those columns);
  the output rows written: rows out x their columns.
Operations: one compare a rule a row, one multiply-or-add a projected
arithmetic column a row, one add an aggregate a window row.
Least time = max(bytes / peak bytes/s, operations / peak operations/s);
``bound`` says which."""

import json
import os
from typing import Dict

VALUE_BYTES = 4
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    with open(PEAKS, encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS}: add a "
            "row with its source")
    return table[device_kind]


def work(shapes: dict, rows: float, rows_out: Dict[str, float]) -> Dict[str, float]:
    """Bytes and operations one batch of ``rows`` valid rows needs;
    ``rows_out``: rows each output lands."""
    values = (shapes["input_columns"] + 1) * rows
    values += shapes["rule_columns"] * rows
    ops = shapes["rules"] * rows + shapes.get("arithmetic_per_row", 0) * rows
    win = shapes.get("window")
    if win:
        live = win["slots"] * rows
        values += live * win["columns_read"] + rows * win["columns_read"]
        ops += live * win["aggregates"]
    for out, cols in shapes["output_columns"].items():
        values += rows_out.get(out, 0) * cols
    return {"bytes": float(values * VALUE_BYTES), "ops": float(ops)}


def least_time(shapes: dict, rows: float, rows_out: Dict[str, float],
               device_kind: str) -> Dict[str, object]:
    peak = peaks(device_kind)
    w = work(shapes, rows, rows_out)
    by_bytes = w["bytes"] / peak["hbm_bytes_per_s"]
    by_ops = w["ops"] / peak["ops_per_s"]
    return {"seconds": max(by_bytes, by_ops),
            "bound": "memory" if by_bytes >= by_ops else "compute", **w}
