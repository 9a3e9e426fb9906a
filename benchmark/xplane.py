"""From a capture's ``XSpace`` to what ``trace.py`` cannot see: the device's
time by the step's named stages, and the device's idle time by what the
host was doing, read inside the one trace.

The program (PR 25 on) names the step's stages on the device with
``jax.named_scope`` (``dx.project.<source>``, ``dx.ring``, ``dx.window``,
``dx.view.<view>``, ``dx.compact.<output>``, ``dx.counts``: they arrive as
the ``tf_op`` stat of an operation's metadata, ``jit(step)/dx.ring/...``)
and opens a ``jax.profiler.TraceAnnotation`` ``dx/<span>`` for every host
span and ``dx/pace`` for the loop's sleep (events of the ``/host:CPU``
plane, one line a thread, on the same clock as the device's lines).

``jax.profiler.ProfileData`` does not expose an event's metadata stats, so
the capture is parsed as the protobuf it is (``xplane_pb2`` ships with
tensorflow; its file is loaded by path, which costs milliseconds where
``import tensorflow`` costs ten seconds). The parse runs in a helper
process, as ``trace.py``'s does, so the parent stays off it:

    python benchmark/xplane.py <file.xplane.pb> <batches.json> <out dir>

``batches.json``: ``{"batches": [[batch time ms, {"span": [start s,
duration ms]}]], "posted_at": s}``. The helper writes, for people,
``device_stages.json`` (per scope: ms a batch, bytes a batch, the source
line that took most) and ``host_idle.json`` (idle seconds by annotation,
both clocks' offsets) into the out directory, and prints the numbers the
readers under ``layers/`` hand out; ``stages`` caches them on the run, so
five readers cost one parse.

The window, the busy union, the gaps and the step's runs are ``trace.py``'s
own (imported): the stage times and ``unscoped`` add up to its busy time by
construction, and the helper fails if they do not. The host's share is read
over the whole periods from the first step's start to the last one's: that
stretch the capture holds on both planes, while ``trace.py``'s window runs
one mean period past the last start, where a 5 s capture has ended (an
annotation still open then, the last ``dx/pace``, is never written). A
capture of a program without scopes or annotations gives ``None`` for what
it cannot read."""

import importlib.util
import json
import os
import re
import subprocess
import sys
from typing import Dict, List, Optional, Sequence, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    # started as a script, sys.path[0] is benchmark/: its trace.py would
    # then shadow the standard library's
    sys.path[0] = ROOT

from benchmark import trace  # noqa: E402

HOST_PLANE = "/host:CPU"
NOTE = "dx/"
PACE = "dx/pace"
SCOPE = re.compile(r"(?:^|/)(dx\.[A-Za-z0-9_.\-]+)")
OUTSIDE_STEP = "outside_step"  # the sized-transfer helpers, conversions
UNSCOPED = "unscoped"
# stage -> the scopes it sums (by prefix)
STAGES = {
    "query": ("dx.project", "dx.view."),
    "window": ("dx.ring", "dx.window"),
    "egress": ("dx.compact", "dx.counts", OUTSIDE_STEP),
}

Interval = Tuple[float, float]


def load_space(path: str):
    """The capture as an ``XSpace`` message."""
    found = importlib.util.find_spec("tensorflow")
    if found is None or not found.submodule_search_locations:
        raise ImportError("xplane_pb2 ships with tensorflow, which is not "
                          "installed")
    pb2 = os.path.join(list(found.submodule_search_locations)[0], "tsl",
                       "profiler", "protobuf", "xplane_pb2.py")
    spec = importlib.util.spec_from_file_location("dx_xplane_pb2", pb2)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    space = module.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def stats_of(plane, stats) -> Dict[str, object]:
    """name -> value of a list of ``XStat`` (a reference resolved)."""
    out = {}
    for s in stats:
        kind = s.WhichOneof("value")
        value = getattr(s, kind)
        if kind == "ref_value":
            value = plane.stat_metadata[value].name
        out[plane.stat_metadata[s.metadata_id].name] = value
    return out


def seconds(line, event) -> Interval:
    start = line.timestamp_ns / 1e9 + event.offset_ps / 1e12
    return start, start + event.duration_ps / 1e12


def device_planes(space) -> Dict[str, Dict[str, list]]:
    """plane name -> ``ops`` [(name, start s, end s, scope or None, source
    line, bytes accessed)] and ``modules`` [(program, start s, end s)]."""
    planes = {}
    for plane in space.planes:
        if not plane.name.startswith(trace.DEVICE_PLANE):
            continue
        found = {"ops": [], "modules": []}
        about: Dict[int, tuple] = {}
        for line in plane.lines:
            if line.name == trace.MODULES_LINE:
                found["modules"] = [
                    (plane.event_metadata[e.metadata_id].name,
                     *seconds(line, e)) for e in line.events]
            elif line.name == trace.OPS_LINE:
                for e in line.events:
                    if e.metadata_id not in about:
                        md = plane.event_metadata[e.metadata_id]
                        st = stats_of(plane, md.stats)
                        scope = SCOPE.search(str(st.get("tf_op", "")))
                        about[e.metadata_id] = (
                            md.name, scope.group(1) if scope else None,
                            str(st.get("source", "")),
                            int(st.get("bytes_accessed", 0)))
                    name, scope, source, nbytes = about[e.metadata_id]
                    found["ops"].append(
                        (name, *seconds(line, e), scope, source, nbytes))
        planes[plane.name] = found
    return planes


def annotations(space) -> List[Tuple[str, float, float, bool, object]]:
    """Every ``dx/*`` event of the host plane: (name, start s, end s,
    whether no other ``dx/*`` event of its thread encloses it, its
    ``batch`` stat)."""
    out = []
    for plane in space.planes:
        if plane.name != HOST_PLANE:
            continue
        ours = {i: md.name for i, md in plane.event_metadata.items()
                if md.name.startswith(NOTE)}
        for line in plane.lines:
            events = sorted(
                ((*seconds(line, e), ours[e.metadata_id],
                  stats_of(plane, e.stats).get("batch"))
                 for e in line.events if e.metadata_id in ours),
                key=lambda ev: (ev[0], -ev[1]))
            open_until = float("-inf")
            for start, end, name, batch in events:
                out.append((name, start, end, start >= open_until, batch))
                open_until = max(open_until, end)
    return out


def window_of(first: Dict[str, list]) -> Tuple[float, float, List[Interval]]:
    """``trace.reduce``'s window: whole batch periods, from the first run
    of the step program to one mean period past the last one's start."""
    runs = trace.step_runs(first["modules"])
    if not runs:
        raise ValueError("the trace has no program runs on its device plane")
    n = len(runs)
    period = (runs[-1][0] - runs[0][0]) / (n - 1) if n > 1 else None
    lo = min(op[1] for op in first["ops"] if op[1] >= runs[0][0] - trace.GAP_S)
    hi = runs[-1][0] + period if period else max(op[2] for op in first["ops"])
    return lo, hi, runs


def device_stages(planes: Dict[str, Dict[str, list]], lo: float, hi: float,
                  runs: Sequence[Interval]) -> Dict[str, Dict[str, object]]:
    """scope -> seconds, bytes and seconds by source line inside the
    window, mean over the device planes. Time is exclusive: every instant
    of the busy union goes to the operation that covers it first, so the
    scopes add up to ``trace.union_s`` of the operations."""
    out: Dict[str, Dict[str, object]] = {}
    for p in planes.values():
        covered = float("-inf")
        at = 0
        for _name, s, e, scope, source, nbytes in sorted(
                p["ops"], key=lambda op: (op[1], op[2])):
            if e <= lo or s >= hi:
                continue
            while at < len(runs) and runs[at][1] < s:
                at += 1
            in_step = at < len(runs) and runs[at][0] <= s
            scope = (scope or UNSCOPED) if in_step else OUTSIDE_STEP
            s, e = max(s, lo), min(e, hi)
            took = max(0.0, e - max(s, covered))
            covered = max(covered, e)
            row = out.setdefault(scope, {"s": 0.0, "bytes": 0, "sources": {}})
            row["s"] += took / len(planes)
            row["bytes"] += nbytes / len(planes)
            row["sources"][source] = row["sources"].get(source, 0.0) \
                + took / len(planes)
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def left_by(idle: Sequence[Interval], cover: Sequence[Interval]
            ) -> List[Interval]:
    """The parts of the (disjoint) ``idle`` stretches no ``cover``
    interval covers."""
    cover = sorted(cover)
    return [g for a, b in idle for g in trace.gaps(
        [c for c in cover if c[1] > a and c[0] < b], a, b)]


def idle_by_annotation(idle: Sequence[Interval], notes) -> Dict[str, float]:
    """Idle seconds by the outermost ``dx/*`` annotation that covers them
    (names in the order they first appear; an instant two threads'
    annotations cover goes to the earlier name), the rest to
    ``unattributed``. Adds up to the idle time."""
    by_name: Dict[str, List[Interval]] = {}
    for name, s, e, top, _batch in sorted(notes, key=lambda n: n[1]):
        if top:
            by_name.setdefault(name, []).append((s, e))
    out, left = {}, list(idle)
    for name, cover in by_name.items():
        rest = left_by(left, cover)
        out[name] = total(left) - total(rest)
        left = rest
    out["unattributed"] = total(left)
    return out


def dispatch_tail(notes, runs: Sequence[Interval]) -> Optional[float]:
    """Seconds a ``dx/dispatch`` annotation goes on after its batch's step
    has started on the device (median): ``trace.clock_offset`` takes the
    two for one moment, so its offset reads high by this much."""
    tails = [e - start for start, _end in runs
             for name, s, e, _top, _batch in notes
             if name == NOTE + "dispatch" and s <= start <= e]
    return sorted(tails)[len(tails) // 2] if tails else None


def offset_in_trace(notes, batches) -> Optional[float]:
    """Wall clock minus trace clock from inside the trace: a
    ``dx/dispatch`` annotation carries its batch's time, and starts with
    the recorder's ``dispatch`` span of that batch (same call site, a few
    microseconds apart). Median over the batches the capture holds."""
    by_time = {int(t): spans for t, spans in batches}
    diffs = []
    for name, s, _e, _top, batch in notes:
        spans = by_time.get(batch) if isinstance(batch, int) else None
        if name == NOTE + "dispatch" and spans and "dispatch" in spans:
            diffs.append(spans["dispatch"][0] - s)
    return sorted(diffs)[len(diffs) // 2] if diffs else None


def reduce(space, batches, posted_at=None) -> Tuple[dict, dict, dict]:
    """(the numbers for the readers, device_stages.json, host_idle.json)."""
    planes = {n: p for n, p in device_planes(space).items() if p["ops"]}
    if not planes:
        raise ValueError("the trace has no device operations")
    first = planes[sorted(planes)[0]]
    lo, hi, runs = window_of(first)
    n = len(runs)
    outside = trace.reduce(
        {name: {"ops": [op[:3] for op in p["ops"]], "modules": p["modules"]}
         for name, p in planes.items()}, [], None)
    by_scope = device_stages(planes, lo, hi, runs)
    busy = sum(row["s"] for row in by_scope.values())
    if abs(busy - outside["busy_s"]) > 1e-9 * max(1.0, busy) \
            or abs((hi - lo) - outside["window_s"]) > 1e-9:
        raise ValueError(
            f"stage times add up to {busy} s over {hi - lo} s, trace.py's "
            f"busy time is {outside['busy_s']} s over {outside['window_s']}")
    ms = lambda s: 1000.0 * s / n  # noqa: E731
    numbers: Dict[str, Optional[float]] = {}
    scoped = [k for k in by_scope if k.startswith("dx.")]
    for stage, prefixes in STAGES.items():
        mine = [k for k in by_scope if k.startswith(prefixes)]
        numbers[f"device_{stage}_ms_per_batch"] = ms(sum(
            by_scope[k]["s"] for k in mine)) \
            if scoped and any(k.startswith("dx.") for k in mine) else None
    numbers["device_unscoped_ms_per_batch"] = ms(
        by_scope.get(UNSCOPED, {"s": 0.0})["s"]) if scoped else None
    numbers["device_busy_ms_per_batch"] = ms(busy)
    stages = {
        "batches": n, "window_s": hi - lo, "busy_s": busy,
        "scopes": {k: {
            "ms_per_batch": ms(row["s"]), "bytes_per_batch": row["bytes"] / n,
            "top_source": max(row["sources"], key=row["sources"].get)}
            for k, row in sorted(by_scope.items(), key=lambda kv: -kv[1]["s"])},
    }
    notes = annotations(space)
    periods = n - 1
    idle = trace.gaps([op[1:3] for op in first["ops"]], lo, runs[-1][0])
    by_note = idle_by_annotation(idle, notes)
    idle_s = total(idle)
    inside = offset_in_trace(notes, batches)
    from_outside = trace.clock_offset(
        [s for s, _e in runs],
        sorted(sp["dispatch"][0] + sp["dispatch"][1] / 1e3
               for _t, sp in batches if "dispatch" in sp), posted_at)
    tail = dispatch_tail(notes, runs)
    differ = None if None in (inside, from_outside) \
        else 1000.0 * (from_outside - inside)
    host = {
        "periods": periods, "window_s": runs[-1][0] - lo, "idle_s": idle_s,
        "idle_s_by_annotation": dict(sorted(by_note.items(),
                                            key=lambda kv: -kv[1])),
        # what the nested annotations cover of it (not part of the sum)
        "idle_s_by_inner_annotation": inner_idle(idle, notes),
        "offset_in_trace_s": inside, "offset_outside_s": from_outside,
        "outside_minus_in_trace_ms": differ,
        "dispatch_after_step_start_ms": None if tail is None
        else 1000.0 * tail,
        # what is left of the difference once the tail is taken off
        "clocks_differ_ms": None if None in (differ, tail)
        else abs(differ - 1000.0 * tail),
    }
    if notes and periods and idle_s > 0:
        numbers["host_serial_ms_per_batch"] = 1000.0 * sum(
            t for name, t in by_note.items()
            if name not in (PACE, "unattributed")) / periods
        numbers["idle_unattributed_pct"] = \
            100.0 * by_note["unattributed"] / idle_s
    else:
        numbers["host_serial_ms_per_batch"] = None
        numbers["idle_unattributed_pct"] = None
    return numbers, stages, host


def inner_idle(idle: Sequence[Interval], notes) -> Dict[str, float]:
    by_name: Dict[str, List[Interval]] = {}
    for name, s, e, top, _batch in notes:
        if not top:
            by_name.setdefault(name, []).append((s, e))
    return {name: total(idle) - total(left_by(idle, cover))
            for name, cover in sorted(by_name.items())}


def stages(run: dict) -> Dict[str, Optional[float]]:
    """The numbers of this run's capture, parsed once in a helper process
    and kept on the run. Called by the readers under ``layers/``, in the
    parent, which stays off protobuf and tensorflow."""
    if "xplane" in run:
        return run["xplane"]
    rec = run["rec"]
    batches_path = os.path.join(run["run_dir"], "xplane_batches.json")
    with open(batches_path, "w", encoding="utf-8") as f:
        json.dump({"batches": [[t, rec.spans(t)] for t, _m, _ts in rec.batches],
                   "posted_at": run["profile_posted_at"]}, f)
    done = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         trace.find_xplane(run["profile"]["path"]), batches_path,
         run["run_dir"]],
        capture_output=True, text=True, timeout=300)
    if done.returncode != 0:
        raise RuntimeError(f"xplane reduction failed: {done.stderr[-2000:]}")
    run["xplane"] = json.loads(done.stdout.strip().splitlines()[-1])
    return run["xplane"]


def main(argv: List[str]) -> int:
    with open(argv[1], encoding="utf-8") as f:
        host = json.load(f)
    numbers, device, idle = reduce(load_space(argv[0]), host["batches"],
                                   host.get("posted_at"))
    for name, body in (("device_stages.json", device),
                       ("host_idle.json", idle)):
        with open(os.path.join(argv[2], name), "w", encoding="utf-8") as f:
            json.dump(body, f, indent=1)
    print(json.dumps(numbers))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
