"""The comparison that decides ``correct``: every row of every sink file
of every landed batch against the plain reference, and the committed
offset against the batch boundaries. Each number compared has a limit of
its own (the flow module's ``LIMITS``; PERF.md gives the readings each
was set from)."""

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


def compare_rows(columns: Dict[str, Dict[str, str]],
                 got: Dict[str, List[Optional[Dict[str, np.ndarray]]]],
                 want: Dict[str, List[Dict[str, np.ndarray]]],
                 ) -> Tuple[Dict[str, float], int, List[str]]:
    """``got[dataset][k]`` / ``want[dataset][k]``: batch k's rows as one
    array a column (``None``: no sink file). ``columns`` says how each
    column is held: ``exact``, ``key`` (rows are matched by it, then it
    is exact) or the name of the number that takes the widest relative
    gap of that column. Returns (numbers, rows compared, the first few
    differences spelled out)."""
    numbers: Dict[str, float] = {"rows_differ": 0}
    notes: List[str] = []
    compared = 0
    for dataset, how in columns.items():
        gaps = {c: n for c, n in how.items() if n not in ("exact", "key")}
        for n in gaps.values():
            numbers.setdefault(n, 0.0)
        key = next((c for c, n in how.items() if n == "key"), None)
        for k, w in enumerate(want[dataset]):
            g = got[dataset][k]
            n_want = len(next(iter(w.values())))
            n_got = 0 if g is None else len(next(iter(g.values())))
            compared += max(n_want, n_got)
            if n_got != n_want:
                numbers["rows_differ"] += max(n_want, n_got)
                notes.append(f"{dataset} batch {k}: {n_got} rows, "
                             f"reference {n_want}")
                continue
            if not n_want:
                continue
            if key is not None:
                order_g, order_w = np.argsort(g[key]), np.argsort(w[key])
                g = {c: v[order_g] for c, v in g.items()}
                w = {c: v[order_w] for c, v in w.items()}
            bad = np.zeros(n_want, bool)
            for c, rule in how.items():
                if c in gaps:
                    gap = np.abs(g[c] - w[c]) / np.maximum(
                        np.abs(w[c]), np.finfo(np.float32).tiny)
                    numbers[gaps[c]] = max(numbers[gaps[c]], float(gap.max()))
                else:
                    bad |= g[c] != w[c]
            if bad.any():
                numbers["rows_differ"] += int(bad.sum())
                i = int(np.flatnonzero(bad)[0])
                notes.append(
                    f"{dataset} batch {k} row {i}: got "
                    f"{ {c: g[c][i].item() for c in how} }, reference "
                    f"{ {c: w[c][i].item() for c in how} }")
    return numbers, compared, notes[:5]


def offset_off_boundary(committed: Optional[int],
                        bounds: Sequence[int]) -> int:
    """0 when the committed offset ends on a batch boundary the host
    reached (every event at or below it is then in the compared
    results), else 1."""
    return 0 if committed is not None and committed in set(
        int(b) for b in bounds[1:]) else 1


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Each number beside its limit; correct when none is over."""
    compared = {n: {"value": v, "limit": limits[n]}
                for n, v in numbers.items()}
    ok = all(c["value"] <= c["limit"] for c in compared.values())
    return ok, compared
