"""The window state's own kernels: their device time by scope, and the
least time any exact implementation needs for what they do.

The program (PR 32 on) folds a batch into per-slot partial aggregates
under the scope ``dx.window.partial`` and reduces the live slots to the
view's groups under ``dx.window.combine`` (``benchmark/xplane.py`` writes
every scope's time a batch into ``device_stages.json``). A program that
has neither scope (the raw-row ring of a commit before PR 32) gives
``None`` for all three numbers.

The least bytes a batch (4 a value), from the configuration's shapes
alone, whatever implements the window:
  the batch's rows x the columns the GROUP BY reads (key and argument),
    read once;
  one slot of groups x aggregates written (the batch's partials);
  the slot that leaves the window and one running state, each groups x
    aggregates, read (an implementation that keeps running totals reads
    no more; one that reduces all live slots, as the program does, reads
    slots x as much and so sits low on this share);
  the output: the rows landed x its columns, written.
Least time = bytes / peak HBM bytes a second. The share is that over the
measured time of the two scopes, in %: the same work whatever implements
it, so it cannot pass 100."""

import json
import os
from typing import Dict, Optional

import numpy as np

from benchmark import roofline, xplane

PARTIAL, COMBINE = "dx.window.partial", "dx.window.combine"
GROUPS_KEY = "datax.job.process.maxgroups"


def scope_ms(run: dict, scope: str) -> Optional[float]:
    """Device ms a batch under one scope of the step, None without it."""
    xplane.stages(run)  # parses the capture once, writes the file
    path = os.path.join(run["run_dir"], "device_stages.json")
    with open(path, encoding="utf-8") as f:
        row = json.load(f)["scopes"].get(scope)
    return None if row is None else float(row["ms_per_batch"])


def least_bytes(shapes: dict, groups: int, rows: float,
                rows_out: float) -> Dict[str, float]:
    """Bytes one batch of ``rows`` valid rows needs of the window state."""
    win = shapes["window"]
    cell = groups * win["aggregates"]
    values = {
        "batch_read": rows * win["columns_read"],
        "slot_written": cell,
        "leaving_slot_read": cell,
        "running_state_read": cell,
        "output_written": rows_out * shapes["output_columns"]["HeatAvg"],
    }
    return {k: float(v * roofline.VALUE_BYTES) for k, v in values.items()}


def roofline_pct(cell: dict, run: dict, m: dict) -> Optional[float]:
    times = [scope_ms(run, PARTIAL), scope_ms(run, COMBINE)]
    if None in times or not sum(times):
        return None
    config = cell["config"]
    median = lambda key: float(np.median(  # noqa: E731
        [ms.get(key, 0.0) for ms in m["measurements"]]))
    need = least_bytes(
        config["roofline"], int(config["conf"][GROUPS_KEY]),
        median("Input_DataXProcessedInput_Events_Count"),
        median("Output_HeatAvg_Events_Count"))
    peak = roofline.peaks(run["rec"].device["deviceKind"])
    least_ms = 1000.0 * sum(need.values()) / peak["hbm_bytes_per_s"]
    return 100.0 * least_ms / sum(times)
