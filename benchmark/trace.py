"""From a device trace (``.xplane.pb``) to numbers: busy time, idle share,
the operations that took most time, and the idle gaps by what the host
was doing in them.

Only the process that holds the chip can trace it, so the capture is the
child's own (``POST /profile`` on its observability port); the reduction
runs afterwards in a helper process under ``JAX_PLATFORMS=cpu``
(``jax.profiler.ProfileData`` reads the file with nothing but jax), which
keeps the parent off jax:

    python benchmark/trace.py <file.xplane.pb> <spans.json>

``spans.json``: ``{"batches": [{"name": [start s, duration ms], ...}]}``,
the recorder's spans of the batches around the capture, on the host's
wall clock, and ``posted_at``, when the parent asked for the capture. The
trace has its own clock (ns since the capture began); ``clock_offset``
aligns the two from outside.

Device planes are those named ``/device:TPU:<n>``; the operations are the
events of the plane's ``XLA Ops`` line, the program runs those of its
``XLA Modules`` line. ``trace_sample/cut.py`` cuts a capture down to its
device planes (the host plane's Python events are nearly all of a file)."""

import glob
import json
import os
import subprocess
import sys
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
OP_NAME_CHARS = 120  # an operation's name in the trace is its whole HLO line
ALIGN_S = 0.02
HOST_SPANS = ("decode", "dispatch", "collect", "sinks", "checkpoint")
GAP_S = 0.001  # idle stretches shorter than this are not looked at one by one


def union_s(intervals: Sequence[Tuple[float, float]]) -> float:
    """Total length of the union of (start, end) intervals."""
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def gaps(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] that no interval covers."""
    out, at = [], lo
    for a, b in sorted(intervals):
        if a > at:
            out.append((at, min(a, hi)))
        at = max(at, b)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(a, b) for a, b in out if b > a]


def device_planes(profile) -> Dict[str, Dict[str, list]]:
    """plane name -> {"ops": [(operation, start s, end s)], "modules":
    [(program, start s, end s)]} from a ProfileData."""
    planes = {}
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        found = {"ops": [], "modules": []}
        for line in plane.lines:
            kind = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
            if kind:
                found[kind] = [
                    (e.name, e.start_ns / 1e9,
                     (e.start_ns + e.duration_ns) / 1e9)
                    for e in line.events]
        planes[plane.name] = found
    return planes


def step_runs(modules: Sequence[Tuple[str, float, float]]
              ) -> List[Tuple[float, float]]:
    """(start, end) of every run of the step program: the program that
    takes most of the device's time, one run a batch."""
    total: Dict[str, float] = {}
    for name, s, e in modules:
        total[name] = total.get(name, 0.0) + (e - s)
    if not total:
        return []
    step = max(total, key=total.get)
    return sorted((s, e) for name, s, e in modules if name == step)


def reduce(planes: Dict[str, Dict[str, list]],
           batches: List[Dict[str, List[float]]],
           posted_at: float = None) -> dict:
    """Busy time, idle share, the op table and the idle gaps. The window
    looked at is whole batch periods: from the first run of the step
    program to one mean period past the last one's start (the capture's
    own edges cut batches in two). Busy is the union of the operations'
    intervals inside it, averaged over the device planes."""
    planes = {n: p for n, p in planes.items() if p["ops"]}
    if not planes:
        raise ValueError("the trace has no device operations")
    first = planes[sorted(planes)[0]]
    runs = step_runs(first["modules"])
    if not runs:
        raise ValueError("the trace has no program runs on its device plane")
    n = len(runs)
    period = (runs[-1][0] - runs[0][0]) / (n - 1) if n > 1 else None
    lo = min(s for _n, s, _e in first["ops"] if s >= runs[0][0] - GAP_S)
    hi = runs[-1][0] + period if period else max(
        e for _n, _s, e in first["ops"])
    busy, per_op = 0.0, {}
    for p in planes.values():
        inside = [(name, max(s, lo), min(e, hi)) for name, s, e in p["ops"]
                  if e > lo and s < hi]
        busy += union_s([(s, e) for _n, s, e in inside]) / len(planes)
        for name, s, e in inside:
            name = name[:OP_NAME_CHARS]
            per_op[name] = per_op.get(name, 0.0) + (e - s) / len(planes)
    idle = [(a, b) for a, b in gaps(
        [(s, e) for _n, s, e in first["ops"]], lo, hi) if b - a >= GAP_S]
    return {
        "busy_s": busy, "window_s": hi - lo,
        "device_idle_pct": 100.0 * (1.0 - busy / (hi - lo)),
        "batches": n,
        "device_busy_ms_per_batch": 1000.0 * busy / n,
        "breakdown": {
            "device_ops": [[name, t] for name, t in sorted(
                per_op.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": attribute(idle, runs, batches, posted_at),
        },
    }


def attribute(idle, runs, batches, posted_at) -> List[List[object]]:
    """Idle seconds by what the host was doing: each gap's time goes to
    the host spans that cover it (``pacing_sleep``: between one batch's
    end and the next one's start), the rest to ``other``. Without an
    alignment everything is ``unattributed``."""
    offset = clock_offset(
        [s for s, _e in runs],
        sorted(b["dispatch"][0] + b["dispatch"][1] / 1e3
               for b in batches if "dispatch" in b), posted_at)
    if offset is None or not idle:
        return [["unattributed", sum(b - a for a, b in idle)]]
    host: List[Tuple[str, float, float]] = []
    roots = sorted((b["streaming/batch"][0],
                    b["streaming/batch"][0] + b["streaming/batch"][1] / 1e3)
                   for b in batches if "streaming/batch" in b)
    for b in batches:
        for name in HOST_SPANS:
            if name in b:
                host.append((name, b[name][0], b[name][0] + b[name][1] / 1e3))
    for (_s0, e0), (s1, _e1) in zip(roots, roots[1:]):
        host.append(("pacing_sleep", e0, s1))
    totals: Dict[str, float] = {}
    for a, b in idle:
        a, b = a + offset, b + offset
        covered = 0.0
        for name, s, e in host:
            part = min(b, e) - max(a, s)
            if part > 0:
                totals[name] = totals.get(name, 0.0) + part
                covered += part
        if b - a - covered > 0:
            totals["other"] = totals.get("other", 0.0) + (b - a - covered)
    return [[name, t] for name, t in sorted(
        totals.items(), key=lambda kv: -kv[1])[:10]]


def clock_offset(starts: List[float], dispatched: List[float],
                 posted_at: float = None):
    """Wall clock minus trace clock, from outside the trace: a batch's
    step starts on the device as its ``dispatch`` span ends on the host
    (well under a millisecond apart). The capture holds a run of
    consecutive batches: every shift of the step starts against the
    dispatch ends gives an offset; those whose differences agree within
    ``ALIGN_S`` are candidates, and the one nearest the moment the
    capture was asked for (the trace clock starts about then) wins, or
    without that moment the one that agrees best."""
    best = None
    for shift in range(len(dispatched) - len(starts) + 1):
        diffs = sorted(d - s for d, s in
                       zip(dispatched[shift:shift + len(starts)], starts))
        spread, offset = diffs[-1] - diffs[0], diffs[len(diffs) // 2]
        if spread > ALIGN_S:
            continue
        rank = abs(offset - posted_at) if posted_at else spread
        if best is None or rank < best[0]:
            best = (rank, offset)
    return None if best is None else best[1]


def find_xplane(capture_dir: str) -> str:
    found = glob.glob(os.path.join(capture_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {capture_dir}")
    return max(found, key=os.path.getmtime)


def reduce_in_helper(capture_dir: str, run: dict, m: dict) -> dict:
    """Run this file as a helper process on the capture. Called by the
    parent, which stays off jax."""
    rec = run["rec"]
    spans_path = os.path.join(run["run_dir"], "trace_spans.json")
    with open(spans_path, "w", encoding="utf-8") as f:
        json.dump({"batches": [rec.spans(t) for t, _m, _ts in rec.batches],
                   "posted_at": run["profile_posted_at"]}, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.abspath(__file__), find_xplane(capture_dir),
         spans_path],
        env=env, capture_output=True, text=True, timeout=300)
    if done.returncode != 0:
        raise RuntimeError(f"trace reduction failed: {done.stderr[-2000:]}")
    return json.loads(done.stdout.strip().splitlines()[-1])


def main(argv: List[str]) -> int:
    from jax.profiler import ProfileData

    with open(argv[1], encoding="utf-8") as f:
        host = json.load(f)
    profile = ProfileData.from_file(argv[0])
    print(json.dumps(reduce(device_planes(profile), host["batches"],
                            host.get("posted_at"))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
