#!/usr/bin/env python3
"""Find the highest rate a paced cell sustains: one run a fixed rate, and
for each the backlog (events due less events landed) at the window's
opening and at its close. A rate is sustained when the backlog at the
close is no larger than one batch (one interval's arrivals) more than at
the opening. Run once when a cell is defined; the cell's file keeps the
readings and offers 0.8 x the highest sustained rate from then on.

    python3 benchmark/sweep.py --workload <cell> --rates 120000,160000 \\
        [--seconds 20] [--seed N] [--out FILE]
"""

import argparse
import json
import os
import sys

if __name__ == "__main__":
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark.run import BenchFailure, run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=2_400_000_001)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    readings = []
    for i, rate in enumerate(int(r) for r in args.rates.split(",")):
        over = {"traffic": {"rate_events_per_s": rate}}
        try:
            line = run_cell(args.workload, args.seed + i, args.seconds, False,
                            overrides=over)
        except BenchFailure as e:
            readings.append({"rate": rate, "failed": str(e)})
            print(json.dumps(readings[-1]), flush=True)
            continue
        w = line["window"]
        readings.append({
            "rate": rate, "correct": line["correct"],
            "backlog_open": w["backlog_open"],
            "backlog_close": w["backlog_close"],
            "sustained": w["backlog_close"] - w["backlog_open"] <= rate,
            "send_late_p95_ms": w["send_late_p95_ms"],
            "valid_rows": w["valid_rows"], **w["end_to_end"],
        })
        print(json.dumps(readings[-1]), flush=True)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(readings, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
