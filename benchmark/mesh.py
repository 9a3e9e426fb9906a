"""What the capture of a step laid over a mesh shows and one chip's does
not: which device planes ran the step, and the device's time in the
collectives the partitioner placed.

Collectives are GSPMD's, not the tracer's: no ``jax.named_scope`` holds
them, so they are told apart by operation name. An operation's name in the
capture is its HLO line (``%all-gather.16 = pred[6,262144]... all-gather(``),
so the name before the ``=`` says what it is: ``all-gather``,
``all-reduce``, ``all-to-all``, ``collective-permute``, ``reduce-scatter``
and their ``-start`` / ``-done`` forms. (An operand list may name a
collective too; only the instruction's own name counts.)

Time in them is read off the ``XLA Ops`` line, the core's own timeline,
like every busy time of ``trace.py`` and ``xplane.py``: an operation there
holds the core, so this is the *exposed* collective time (a ``-done``
waiting for its transfer included), inside the same window as the busy
time it is a part of. What the TPU runs in flight beside other operations
is on the plane's ``Async XLA Ops`` line and goes to ``mesh_collectives.json``
for people, not into the metric.

    python benchmark/mesh.py <file.xplane.pb> <out dir>

The parse runs in a helper process, as ``xplane.py``'s does; ``numbers``
caches its result on the run, so two readers cost one parse. A capture of
one chip has one plane and no collective: 1 and 0.0."""

import json
import os
import re
import subprocess
import sys
from typing import Dict, List, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    # started as a script, sys.path[0] is benchmark/: its trace.py would
    # then shadow the standard library's
    sys.path[0] = ROOT

from benchmark import trace, xplane  # noqa: E402

ASYNC_LINE = "Async XLA Ops"
COLLECTIVE = re.compile(
    r"\s*%?(all-gather|all-reduce|all-to-all|collective-permute"
    r"|reduce-scatter)(?:-start|-done)?(?:\.\d+)?\s*(?:=|$)")

Op = Tuple[str, float, float]


def kind_of(name: str):
    """The collective an operation's name says it is, or ``None``."""
    found = COLLECTIVE.match(name)
    return found.group(1) if found else None


def device_planes(space) -> Dict[str, Dict[str, List[Op]]]:
    """plane name -> ``ops``, ``async`` [(name, start s, end s)] and
    ``modules`` [(program, start s, end s)], every device plane of the
    capture, one that ran nothing too."""
    planes = {}
    for plane in space.planes:
        if not plane.name.startswith(trace.DEVICE_PLANE):
            continue
        found = {"ops": [], "async": [], "modules": []}
        for line in plane.lines:
            kind = {trace.OPS_LINE: "ops", ASYNC_LINE: "async",
                    trace.MODULES_LINE: "modules"}.get(line.name)
            if kind:
                found[kind] = [
                    (plane.event_metadata[e.metadata_id].name,
                     *xplane.seconds(line, e)) for e in line.events]
        planes[plane.name] = found
    return planes


def reduce(planes: Dict[str, Dict[str, List[Op]]]) -> Tuple[dict, dict]:
    """(the numbers for the readers, mesh_collectives.json). The window
    and the step's runs are ``xplane.window_of``'s, read off the first
    plane that ran anything; a plane counts as busy when the step program
    (by name) ran on it inside that window. Collective time: the union of
    the collective-named operations of a plane's ``XLA Ops`` line inside
    the window, a batch, mean over the planes that ran anything
    (``trace.reduce``'s divisor for the busy time this is a part of)."""
    ran = {n: p for n, p in planes.items() if p["ops"]}
    if not ran:
        raise ValueError("the trace has no device operations")
    first = ran[sorted(ran)[0]]
    lo, hi, runs = xplane.window_of(first)
    took: Dict[str, float] = {}
    for name, s, e in first["modules"]:
        took[name] = took.get(name, 0.0) + (e - s)
    step = max(took, key=took.get)  # as trace.step_runs picks it
    busy = sorted(n for n, p in ran.items()
                  if any(name == step and e > lo and s < hi
                         for name, s, e in p["modules"]))
    n = len(runs)
    by_kind: Dict[str, Dict[str, float]] = {}
    exposed = 0.0
    for name in ran:
        inside = []
        for line in ("ops", "async"):
            for op, s, e in planes[name][line]:
                kind = kind_of(op)
                if kind is None or e <= lo or s >= hi:
                    continue
                s, e = max(s, lo), min(e, hi)
                row = by_kind.setdefault(kind, {
                    "ops_ms_per_batch": 0.0, "async_ms_per_batch": 0.0,
                    "events_per_batch": 0.0})
                row[f"{line}_ms_per_batch"] += 1e3 * (e - s) / len(ran) / n
                if line == "ops":
                    row["events_per_batch"] += 1.0 / len(ran) / n
                    inside.append((s, e))
        exposed += trace.union_s(inside) / len(ran)
    numbers = {
        "mesh_chips_busy": float(len(busy)),
        "mesh_collective_ms_per_batch": 1e3 * exposed / n,
    }
    return numbers, {
        "batches": n, "window_s": hi - lo, "step_program": step,
        "planes": sorted(planes), "planes_that_ran_the_step": busy,
        "collective_ms_per_batch": numbers["mesh_collective_ms_per_batch"],
        "by_kind": dict(sorted(by_kind.items())),
    }


def numbers(run: dict) -> Dict[str, float]:
    """The numbers of this run's capture, parsed once in a helper process
    and kept on the run. Called by the readers under ``layers/``, in the
    parent, which stays off protobuf and tensorflow."""
    if "mesh" in run:
        return run["mesh"]
    done = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         trace.find_xplane(run["profile"]["path"]), run["run_dir"]],
        capture_output=True, text=True, timeout=300)
    if done.returncode != 0:
        raise RuntimeError(f"mesh reduction failed: {done.stderr[-2000:]}")
    run["mesh"] = json.loads(done.stdout.strip().splitlines()[-1])
    return run["mesh"]


def main(argv: List[str]) -> int:
    got, for_people = reduce(device_planes(xplane.load_space(argv[0])))
    with open(os.path.join(argv[1], "mesh_collectives.json"), "w",
              encoding="utf-8") as f:
        json.dump(for_people, f, indent=1)
    print(json.dumps(got))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
