"""Per-layer metrics: one small reader file a metric under
``benchmark/layers/``, found by the metric's name in BENCHMARK.json.

``<name>.json`` declares where the number comes from:

- ``{"from": "span", "key": "decode", "stat": "median"}``: a recorder
  span's duration in ms over the window's batches that have it;
- ``{"from": "measurement", "key": "Latency-Batch", "stat": "median"}``:
  a metric on the ``streaming/batch/end`` events of the window's batches;
- ``{"from": "generator", "stat": "p95"}``: how late the parent's sends
  ran, ms, over the window's chunks;
- ``{"from": "trace", "key": "device_idle_pct"}``: a named reduction of
  ``benchmark/trace.py``;
- ``{"from": "roofline"}``: the least time the chip needs for one batch's
  work (``benchmark/roofline.py``, from the flow's shapes) over the
  device's busy time a batch in the trace, in %.

``<name>.py`` is for what no declaration covers: ``read(cell, run, m,
trace)`` returns the number or ``None``. A reader that finds nothing to
read returns nothing, and the metric is left out of the line."""

import importlib.util
import json
import os
from typing import Dict, Optional

import numpy as np

from benchmark import roofline
from benchmark.served import INPUT_ROWS

LAYERS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "layers")

STATS = {
    "median": lambda v: float(np.median(v)),
    "mean": lambda v: float(np.mean(v)),
    "p95": lambda v: float(np.percentile(v, 95)),
    "max": lambda v: float(np.max(v)),
}


def read_declared(spec: dict, cell: dict, run: dict, m: dict, trace: dict
                  ) -> Optional[float]:
    src = spec["from"]
    if src == "trace":
        return trace.get(spec["key"])
    if src == "roofline":
        busy_ms = trace.get("device_busy_ms_per_batch")
        if not busy_ms:
            return None
        median = lambda key: float(np.median(  # noqa: E731
            [ms.get(key, 0.0) for ms in m["measurements"]]))
        shapes = cell["config"]["roofline"]
        least = roofline.least_time(
            shapes, median(INPUT_ROWS),
            {o: median(f"Output_{o}_Events_Count")
             for o in shapes["output_columns"]},
            run["rec"].device["deviceKind"])
        return 100.0 * least["seconds"] * 1000.0 / busy_ms
    if src == "span":
        values = [sp[spec["key"]][1] for sp in m["spans"] if spec["key"] in sp]
    elif src == "measurement":
        values = [ms[spec["key"]] for ms in m["measurements"]
                  if spec["key"] in ms]
    elif src == "generator":
        values = list(m["send_late_ms"])
    else:
        raise ValueError(f"reader: unknown source {src!r}")
    return STATS[spec["stat"]](values) if values else None


def read_one(name: str, cell: dict, run: dict, m: dict, trace: dict
             ) -> Optional[float]:
    code = os.path.join(LAYERS, f"{name}.py")
    if os.path.exists(code):
        spec = importlib.util.spec_from_file_location(
            "benchmark_layer_" + name.replace(".", "_").replace("-", "_"),
            code)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read(cell, run, m, trace)
    with open(os.path.join(LAYERS, f"{name}.json"), encoding="utf-8") as f:
        return read_declared(json.load(f)["reader"], cell, run, m, trace)


def read_all(cell: dict, run: dict, m: dict, trace: dict
             ) -> Dict[str, Dict[str, object]]:
    out = {}
    for metric in cell["per_layer"]:
        value = read_one(metric["name"], cell, run, m, trace)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out
