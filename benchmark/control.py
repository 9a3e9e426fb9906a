#!/usr/bin/env python3
"""The control of a cell's comparison, and the readings its limits are set
from. For every seed: one short run of the cell at its own size and load;
the numbers the comparison reads from the program (the lower readings),
and the same numbers with the low-precision control in the program's
place (the upper readings: the flow module's ``control``, the reference
computed in bfloat16, the nearest precision below the float32 the
configuration states). The control has to come out as not correct. The
benchmark's own runs never run this.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 \\
        [--seconds 10] [--out FILE]
"""

import argparse
import json
import os
import sys

if __name__ == "__main__":
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark.run import BenchFailure, decide, execute  # noqa: E402


def readings(name: str, seed: int, seconds: float, **how) -> dict:
    cell, run, m = execute(name, seed, seconds, False, **how)
    program = decide(run, cell, m)
    control = decide(run, cell, m, got=cell["flow"].control)
    return {
        "seed": seed, "rows_compared": program["rows_compared"],
        "program_correct": program["correct"],
        "program": {n: c["value"] for n, c in program["compared"].items()},
        "control_correct": control["correct"],
        "control": {n: c["value"] for n, c in control["compared"].items()},
        "end_to_end": m["e2e"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    out = []
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out.append(readings(args.workload, seed, args.seconds))
        except BenchFailure as e:
            out.append({"seed": seed, "failed": str(e)})
        print(json.dumps(out[-1]), flush=True)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
