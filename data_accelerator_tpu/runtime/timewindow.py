"""Time windows as device-resident ring buffers.

The reference implements ``TIMEWINDOW('5 minutes')`` by caching each
batch's filtered RDD in driver memory, evicting stale ones, and
re-unioning per batch (CommonProcessorFactory.scala:156-236,
TimeWindowHandler.scala:23-68) — recompute-by-union, O(window/batch)
cached RDDs. TPU-native instead: a fixed ring of K batch slots lives on
device as [K, capacity] column arrays; each batch overwrites one slot
in-jit, timestamps are kept relative to the current batch base (shifted
by the base delta each step), and a window table is just the flattened
ring masked by ``ts >= now - duration`` — no host round-trips, no
recompute, O(1) per batch.

Windowed views (``DataXProcessedInput_5minutes``) are exposed to the
pipeline as plain input tables of capacity K*capacity.

That is the raw-row ring, and it serves whatever reads the window's rows
(a join, a plain SELECT, DISTINCT aggregates, UDAFs, a payload time
column). A GROUP BY of COUNT / SUM / AVG / MIN / MAX over a window whose
rows all carry their batch's one time does not need the rows: the planner
(``compile/planner.py``) keeps ``WindowPartials`` for it instead, K slots
of per-group partial aggregates, ``[K, groups]`` a partial. Each batch is
folded into its slot once (one sort over ``capacity`` rows) and the view
is a masked reduce over the slots the window still covers, so the cost
follows K x groups, not K x capacity. Nothing is added to and subtracted
from a running total: float sums would drift and MIN / MAX have no
inverse.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..compile.planner import TableData, ViewSchema


@jax.tree_util.register_pytree_node_class
@dataclass
class WindowBuffers:
    """Ring of K batch slots: cols are [K, capacity]."""

    cols: Dict[str, jnp.ndarray]
    valid: jnp.ndarray  # [K, capacity]

    def tree_flatten(self):
        names = tuple(sorted(self.cols))
        return tuple(self.cols[n] for n in names) + (self.valid,), names

    @classmethod
    def tree_unflatten(cls, names, children):
        return cls(dict(zip(names, children[:-1])), children[-1])

    @property
    def slots(self) -> int:
        return int(self.valid.shape[0])

    @property
    def capacity(self) -> int:
        return int(self.valid.shape[1])


def num_slots(max_window_s: float, watermark_s: float, interval_s: float) -> int:
    """Slots needed to retain max_window + watermark of history
    (the eviction horizon at CommonProcessorFactory.scala:185-194)."""
    return max(1, math.ceil((max_window_s + watermark_s) / max(interval_s, 1e-9))) + 1


def make_buffers(schema: ViewSchema, capacity: int, slots: int) -> WindowBuffers:
    dtypes = {"double": jnp.float32, "boolean": jnp.bool_}
    cols = {
        c: jnp.zeros((slots, capacity), dtype=dtypes.get(t, jnp.int32))
        for c, t in schema.types.items()
    }
    return WindowBuffers(cols, jnp.zeros((slots, capacity), dtype=jnp.bool_))


def update_buffers(
    buf: WindowBuffers,
    batch: TableData,
    slot: jnp.ndarray,  # scalar int32
    delta_ms: jnp.ndarray,  # scalar int32: new_base_ms - old_base_ms
    ts_col: str,
) -> WindowBuffers:
    """Rebase stored timestamps to the new batch base, then overwrite the
    ring slot with the new batch. Traced; runs inside the step jit."""
    new_cols = {}
    for c, arr in buf.cols.items():
        if c == ts_col:
            arr = arr - delta_ms
        new_cols[c] = jax.lax.dynamic_update_index_in_dim(
            arr, batch.cols[c], slot, axis=0
        )
    new_valid = jax.lax.dynamic_update_index_in_dim(
        buf.valid, batch.valid, slot, axis=0
    )
    return WindowBuffers(new_cols, new_valid)


def window_table(
    buf: WindowBuffers,
    duration_ms: int,
    now_rel_ms: jnp.ndarray,
    ts_col: str,
) -> TableData:
    """Flattened ring masked to the window span [now - duration, now]."""
    k, cap = buf.valid.shape
    ts = buf.cols[ts_col].reshape(k * cap)
    valid = buf.valid.reshape(k * cap)
    in_window = (ts >= (now_rel_ms - jnp.int32(duration_ms))) & (ts <= now_rel_ms)
    cols = {c: a.reshape(k * cap) for c, a in buf.cols.items()}
    return TableData(cols, valid & in_window)


# ---------------------------------------------------------------------------
# per-slot partial aggregates
# ---------------------------------------------------------------------------
# the row count of a group in a slot: every partial state keeps it (COUNT,
# the divisor of AVG, and which columns still hold a live key)
ROWS = "n"


_PART = "part."


@jax.tree_util.register_pytree_node_class
@dataclass
class WindowPartials:
    """K slots of per-group partial aggregates for one windowed GROUP BY,
    laid out as the ring is (named arrays and one validity vector), so
    whatever handles window state by ``cols`` / ``valid`` handles both.

    A group key owns one column index for as long as a live slot holds
    rows of it: ``cols["key<i>"][c]`` is column c's key (one array a key
    column) and ``valid[c]`` whether it has one. ``cols["part.<name>"]``
    is [K, groups]: slot k's partial of every column's group (the op's
    identity where the slot has no row of it). ``cols["slot_ts"]`` is each
    slot's batch time relative to the current batch base (rebased every
    step like the ring's timestamps); a slot that left the window is not
    in ``cols["slot_live"]`` again until it is overwritten."""

    cols: Dict[str, jnp.ndarray]
    valid: jnp.ndarray  # [groups] bool

    @classmethod
    def of(cls, keys, used, parts, slot_ts, slot_live) -> "WindowPartials":
        cols = {f"key{i}": a for i, a in enumerate(keys)}
        cols.update({_PART + n: a for n, a in parts.items()})
        cols["slot_ts"], cols["slot_live"] = slot_ts, slot_live
        return cls(cols, used)

    def tree_flatten(self):
        names = tuple(sorted(self.cols))
        return tuple(self.cols[n] for n in names) + (self.valid,), names

    @classmethod
    def tree_unflatten(cls, names, children):
        return cls(dict(zip(names, children[:-1])), children[-1])

    @property
    def keys(self) -> Tuple[jnp.ndarray, ...]:
        n = sum(1 for c in self.cols if c.startswith("key"))
        return tuple(self.cols[f"key{i}"] for i in range(n))

    @property
    def used(self) -> jnp.ndarray:
        return self.valid

    @property
    def parts(self) -> Dict[str, jnp.ndarray]:
        return {c[len(_PART):]: a for c, a in self.cols.items()
                if c.startswith(_PART)}

    @property
    def slot_ts(self) -> jnp.ndarray:
        return self.cols["slot_ts"]

    @property
    def slot_live(self) -> jnp.ndarray:
        return self.cols["slot_live"]

    @property
    def slots(self) -> int:
        return int(self.slot_ts.shape[0])

    @property
    def groups(self) -> int:
        return int(self.valid.shape[0])


def make_partials(
    key_dtypes: Sequence, part_dtypes: Dict[str, Tuple[str, object]],
    slots: int, groups: int,
) -> WindowPartials:
    """Empty state. ``part_dtypes``: name -> (op, dtype); a slot no batch
    has been folded into reads the op's identity everywhere."""
    from ..ops.groupby import _identity

    return WindowPartials.of(
        tuple(jnp.zeros((groups,), dt) for dt in key_dtypes),
        jnp.zeros((groups,), jnp.bool_),
        {
            name: jnp.full((slots, groups), _identity(op, dt), dt)
            for name, (op, dt) in part_dtypes.items()
        },
        jnp.zeros((slots,), jnp.int32),
        jnp.zeros((slots,), jnp.bool_),
    )


def _front(flag: jnp.ndarray, carry: Sequence[jnp.ndarray], n: int):
    """The rows where ``flag`` holds, packed to the front in their order,
    cut to ``n``: a stable sort on one bit, the columns riding along (no
    gather by a row-count index)."""
    out = jax.lax.sort(
        (jnp.where(flag, 0, 1).astype(jnp.int32), *carry),
        num_keys=1, is_stable=True,
    )
    return [a[:n] for a in out[1:]]


def fold_partials(
    state: WindowPartials,
    ops: Dict[str, str],  # partial name -> "sum" | "min" | "max"
    keys: Sequence[jnp.ndarray],  # the batch's key columns, [capacity]
    valid: jnp.ndarray,  # [capacity] rows that count
    args: Dict[str, jnp.ndarray],  # partial name -> [capacity] values
    slot: jnp.ndarray,  # scalar int32: the slot this batch overwrites
    delta_ms: jnp.ndarray,  # scalar int32: new_base_ms - old_base_ms
    now_rel_ms: jnp.ndarray,
    duration_ms: int,
) -> Tuple[WindowPartials, jnp.ndarray, jnp.ndarray]:
    """Fold one batch into its slot. Returns (new state, [groups] rows a
    column holds over the live slots, groups that found no column).

    One sort over the batch's rows; everything after it is sized by the
    group bound: the batch's groups (its smallest ``groups`` keys) are
    merged with the key directory by a sort of 2 x groups entries, a key
    the directory lacks takes the lowest free column in key order, and
    the slot's row of every partial is one scatter of ``groups``
    updates."""
    from ..ops.groupby import _identity, _shifted, segmented_scan, sort_groups

    state_parts = state.parts
    k, d = state_parts[ROWS].shape
    names = sorted(state_parts)
    nk = len(keys)
    cap = valid.shape[0]
    gb = min(cap, d)

    # 1. the batch's groups: sorted runs, each run's total at its last row
    sg = sort_groups(keys, valid, [*keys, *(args[n] for n in names if n != ROWS)])
    keys_s = sg.carried[:nk]
    vals_s = dict(zip((n for n in names if n != ROWS), sg.carried[nk:]))
    run = {ROWS: segmented_scan(sg.valid_s.astype(jnp.int32), sg.seg, "sum")}
    for n, v in vals_s.items():
        ident = _identity(ops[n], v.dtype)
        run[n] = segmented_scan(
            jnp.where(sg.valid_s, v, ident), sg.seg, ops[n]
        )
    last = sg.valid_s & jnp.concatenate(
        [sg.seg[1:] != sg.seg[:-1], jnp.ones((1,), jnp.bool_)]
    )
    n_batch = jnp.sum(last.astype(jnp.int32))
    packed = _front(last, [*keys_s, *(run[n] for n in names)], gb)
    bkeys, bvals = packed[:nk], dict(zip(names, packed[nk:]))
    bvalid = jnp.arange(gb) < n_batch
    dropped = jnp.maximum(n_batch - gb, 0)

    # 2. the slots: rebase their times, retire what left the window (a
    # slot is wholly inside or outside: its rows share the batch's time)
    at = jnp.arange(k) == slot
    ts = jnp.where(at, now_rel_ms, state.slot_ts - delta_ms)
    in_window = (ts >= now_rel_ms - jnp.int32(duration_ms)) & (ts <= now_rel_ms)
    live = (state.slot_live | at) & in_window
    rows_old = jnp.sum(
        jnp.where((live & ~at)[:, None], state_parts[ROWS], 0), axis=0
    )
    used = state.used & (rows_old > 0)

    # 3. merge the batch's groups with the directory: in key order a
    # batch entry right behind its directory twin takes that column
    merged = sort_groups(
        [jnp.concatenate([dk, bk]) for dk, bk in zip(state.keys, bkeys)],
        jnp.concatenate([used, bvalid]),
        [
            *(jnp.concatenate([dk, bk]) for dk, bk in zip(state.keys, bkeys)),
            *(jnp.concatenate([jnp.zeros((d,), bvals[n].dtype), bvals[n]])
              for n in names),
        ],
    )
    from_batch = (merged.order >= d) & merged.valid_s
    known = from_batch & ~merged.first
    fresh = from_batch & merged.first
    twin = _shifted(merged.order, 1, 0)
    # known first, then fresh in key order, then the rest: all that can
    # land lies in the first ``groups`` places
    klass = jnp.where(known, 0, jnp.where(fresh, 1, 2)).astype(jnp.int32)
    placed = [a[:d] for a in jax.lax.sort(
        (klass, twin, *merged.carried), num_keys=1, is_stable=True
    )]
    klass, twin, carried = placed[0], placed[1], placed[2:]
    n_known = jnp.sum(known.astype(jnp.int32))
    n_fresh = jnp.sum(fresh.astype(jnp.int32))
    n_free = d - jnp.sum(used.astype(jnp.int32))
    # the free columns, lowest first, lined up with the fresh entries
    free = jax.lax.sort(
        (used.astype(jnp.int32), jnp.arange(d, dtype=jnp.int32)),
        num_keys=1, is_stable=True,
    )[1]
    place = jnp.arange(d)
    free_at = jnp.roll(free, n_known)
    col = jnp.where(
        klass == 0, twin,
        # what does not land: out of range, each index its own
        jnp.where((klass == 1) & (place - n_known < n_free), free_at,
                  d + place),
    )
    dropped = dropped + jnp.maximum(n_fresh - n_free, 0)

    # 4. the slot's row of every partial, the directory's new keys
    def put(target, updates):
        return target.at[col].set(
            updates, mode="drop", unique_indices=True
        )

    new_keys = tuple(put(dk, c) for dk, c in zip(state.keys, carried[:nk]))
    new_used = put(used, jnp.ones((d,), jnp.bool_))
    parts = {}
    for n, c in zip(names, carried[nk:]):
        row = put(jnp.full((d,), _identity(ops[n], c.dtype), c.dtype), c)
        parts[n] = jax.lax.dynamic_update_index_in_dim(
            state_parts[n], row, slot, axis=0
        )
        if n == ROWS:
            rows = rows_old + row
    return (
        WindowPartials.of(new_keys, new_used, parts, ts, live),
        rows, dropped.astype(jnp.int32),
    )


def combine_partials(
    state: WindowPartials,
    ops: Dict[str, str],
    rows: jnp.ndarray,  # [groups] ``fold_partials``' live row counts
    dropped: jnp.ndarray,
) -> TableData:
    """The window's groups in key order: every partial reduced over the
    live slots (one masked pass over [K, groups]), the columns that hold
    rows sorted by key to the front. Columns ``key<i>``, one a partial,
    and the dropped-group count on every row."""
    from ..ops.groupby import _identity, sort_groups

    d = state.groups
    live = state.slot_live[:, None]
    total = {ROWS: rows}
    for n, a in state.parts.items():
        if n == ROWS:
            continue
        ident = _identity(ops[n], a.dtype)
        reduce = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}[ops[n]]
        total[n] = reduce(jnp.where(live, a, ident), axis=0)
    names = sorted(total)
    held = state.used & (rows > 0)
    sg = sort_groups(
        list(state.keys), held, [*state.keys, *(total[n] for n in names)]
    )
    nk = len(state.keys)
    cols = {f"key{i}": c for i, c in enumerate(sg.carried[:nk])}
    cols.update(zip(names, sg.carried[nk:]))
    cols["__overflow.groups"] = jnp.broadcast_to(dropped, (d,))
    return TableData(cols, sg.valid_s)
