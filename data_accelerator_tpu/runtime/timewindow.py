"""Time windows as device-resident state.

The reference implements ``TIMEWINDOW('5 minutes')`` by caching each
batch's filtered RDD in driver memory, evicting stale ones, and
re-unioning per batch (CommonProcessorFactory.scala:156-236,
TimeWindowHandler.scala:23-68) — recompute-by-union, O(window/batch)
cached RDDs. TPU-native instead: a fixed ring of K batch slots lives on
device as [K, capacity] column arrays; each batch overwrites one slot
in-jit, timestamps are kept relative to the current batch base (shifted
by the base delta each step), and a window table is just the flattened
ring under a mask — no host round-trips, no recompute, O(1) per batch.

Windowed views (``DataXProcessedInput_5minutes``) are exposed to the
pipeline as plain input tables of capacity K*capacity.

That is the raw-row ring, and it serves whatever reads the window's rows
(a join, a plain SELECT, DISTINCT aggregates, UDAFs). A GROUP BY of
COUNT / SUM / AVG / MIN / MAX over a window does not need the rows: the
planner (``compile/planner.py``) keeps ``WindowPartials`` for it
instead, K slots of per-group partial aggregates, ``[K, groups]`` a
partial. Each batch is folded into its slots once (one sort over
``capacity`` rows) and the view is a masked reduce over the slots the
window covers, so the cost follows K x groups, not K x capacity. Nothing
is added to and subtracted from a running total: float sums would drift
and MIN / MAX have no inverse.

Which rows a window holds (the ONE statement of it; ``CONF.md`` quotes
it). I = the batch interval, D = the window's duration, W =
``process.watermark``, all in ms; w = ceil(W / I), d = ceil(D / I); a
batch whose time the host records as t has n = floor(t / I); a row whose
timestamp column reads ts has b = floor(ts / I).

- **Processing-time window**: the timestamp column is the
  ``current_timestamp()`` projection, so every row of a batch carries
  the batch's one time t. The window a statement reads at a batch of
  time t holds the rows of the last K batches with t - D <= ts <= t. A
  slot is a batch. The watermark only sizes K.
- **Event-time window**: the timestamp column comes from the payload. A
  batch's time t is the moment the host polled its rows, and they came
  in over the interval before t, which does not lie on the grid: rows
  stamped as they were sent read b = n - 1 or b = n. So a row that
  arrives in batch n is accepted iff b >= n - w - 1 (the watermark's w
  intervals behind the two a batch's on-time rows fall in: under
  ``process.watermark`` 0, upstream's default, every on-time row
  counts); else it is too late: it is in no window, and
  ``Window_TooLate_Rows_Dropped`` counts it. The window a statement
  reads at batch n holds the accepted rows with n - w - 1 - d <= b <
  n - w - 1: a window's contents are final when first emitted and do
  not depend on the order in which the accepted rows arrived
  (upstream's rule: CommonProcessorFactory.scala:185-233 keeps past
  batches for watermark + window, filters the union by the timestamp
  column and lags the window by the watermark; its batch times are
  Spark's, the grid-aligned end of the collection interval, where
  on-time rows all read b = n - 1). A row is on time or not by the
  clock, not the grid: ``Window_Late_Rows`` counts the accepted rows
  with t - ts > I. A slot is an interval of event time: bucket b lives
  in slot b mod K, K = ``num_slots(D, W, I, event_time=True)`` >= d +
  w + 2. A row stamped ahead of its batch (ts > t: a sensor's clock
  runs fast) is taken as stamped t in window state, so b = n; the
  batch's own table (``DataXProcessedInput``) shows every row as it
  came, too-late and early ones included: a rule over it is not held
  back by the watermark. Both window states keep this one rule, so
  which of the two the planner picks does not change an answer. (One
  bound apart, which a processing-time window has in either state: the
  raw-row ring holds the last K batches, so a host that ran more than K
  batches within K intervals has lost the oldest rows from it; partial
  aggregates, a slot an interval, have not.) The clock is the batch's:
  rows replayed after a restart that took longer than the watermark
  are too late for the windows, and counted; they still reach the
  batch's own table, so delivery stays at-least-once while a window is
  at-most-once for them.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..compile.planner import EventClock, TableData, ViewSchema


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


def num_slots(
    max_window_s: float, watermark_s: float, interval_s: float,
    event_time: bool = False,
) -> int:
    """Slots needed to retain max_window + watermark of history
    (the eviction horizon at CommonProcessorFactory.scala:185-194): the
    d intervals of the window, the w of the watermark, and the batch's
    own (K = d + w + 1 whenever the interval divides both); an
    event-time window one more, the interval before the batch's own
    that its on-time rows fall in too."""
    i = max(interval_s, 1e-9)
    return max(
        max(1, math.ceil((max_window_s + watermark_s) / i)),
        max(1, math.ceil(max_window_s / i)) + math.ceil(watermark_s / i),
    ) + 1 + int(event_time)


# ---------------------------------------------------------------------------
# event time: a batch's rows on the clock's grid
# ---------------------------------------------------------------------------
class EventRows(NamedTuple):
    """One batch's rows of an event-time table on its ``EventClock``'s
    grid (the module docstring has the rule). Lives inside one trace."""

    clock: EventClock
    # [capacity] int32: n - min(b, n), the whole intervals a row's stamp
    # lies behind its batch's (0: on time, or stamped ahead)
    age: jnp.ndarray
    accepted: jnp.ndarray  # [capacity] bool: valid and age <= w + 1
    base_s: jnp.ndarray  # the batch base, whole seconds
    phase_ms: jnp.ndarray  # base_ms mod I
    n_rel: jnp.ndarray  # n - floor(base_ms / I)
    late: jnp.ndarray  # accepted rows stamped over an interval before t
    too_late: jnp.ndarray  # valid rows the watermark refused

    @property
    def now_start_ms(self) -> jnp.ndarray:
        """Start of the batch's own interval, ms relative to the base."""
        return self.n_rel * jnp.int32(self.clock.interval_ms) - self.phase_ms

    def slot_of_now(self, slots: int) -> jnp.ndarray:
        """n mod ``slots``: the slot of the batch's own interval. In
        int32: base_ms = 1000 base_s does not fit; base_s = a I + r, so
        floor(base_ms / I) = 1000 a + floor(1000 r / I)."""
        i, m = jnp.int32(self.clock.interval_ms), jnp.int32(slots)
        a, r = self.base_s // i, self.base_s % i
        return ((a % m) * (1000 % slots) + (r * 1000) // i + self.n_rel) % m


def _bucket(ts: jnp.ndarray, phase_ms: jnp.ndarray, interval_ms: int):
    """floor((base_ms + ts) / I) - floor(base_ms / I)."""
    return jnp.floor_divide(ts + phase_ms, jnp.int32(interval_ms))


def event_rows(
    ts: jnp.ndarray,  # [capacity] int32 ms relative to the batch base
    valid: jnp.ndarray,
    base_s: jnp.ndarray,
    now_rel_ms: jnp.ndarray,
    clock: EventClock,
) -> EventRows:
    i = jnp.int32(clock.interval_ms)
    phase = ((base_s % i) * 1000) % i  # base_ms mod I, in int32
    n_rel = _bucket(now_rel_ms, phase, clock.interval_ms)
    age = jnp.maximum(n_rel - _bucket(ts, phase, clock.interval_ms), 0)
    accepted = valid & (age <= clock.lag)
    count = lambda m: jnp.sum(m.astype(jnp.int32))  # noqa: E731
    return EventRows(
        clock, age, accepted, base_s, phase, n_rel,
        count(accepted & (now_rel_ms - ts > i)), count(valid & ~accepted),
    )


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
    now_rel_ms: Optional[jnp.ndarray] = None,
    event: Optional[EventRows] = None,
) -> WindowBuffers:
    """Rebase stored timestamps to the new batch base, then overwrite the
    ring slot with the new batch. Traced; runs inside the step jit. An
    event-time table (``event``) stores the rows the watermark accepted,
    a stamp ahead of the batch as the batch's time."""
    new_cols = {}
    for c, arr in buf.cols.items():
        col = batch.cols[c]
        if c == ts_col:
            arr = arr - delta_ms
            if event is not None:
                col = jnp.minimum(col, now_rel_ms)
        new_cols[c] = jax.lax.dynamic_update_index_in_dim(
            arr, col, slot, axis=0
        )
    new_valid = jax.lax.dynamic_update_index_in_dim(
        buf.valid, batch.valid if event is None else event.accepted,
        slot, axis=0,
    )
    return WindowBuffers(new_cols, new_valid)


def window_table(
    buf: WindowBuffers,
    duration_ms: int,
    now_rel_ms: jnp.ndarray,
    ts_col: str,
    event: Optional[EventRows] = None,
) -> TableData:
    """Flattened ring masked to the window: [now - duration, now], or for
    an event-time table the intervals n - w - 1 - d <= b < n - w - 1."""
    k, cap = buf.valid.shape
    ts = buf.cols[ts_col].reshape(k * cap)
    valid = buf.valid.reshape(k * cap)
    if event is None:
        in_window = (ts >= (now_rel_ms - jnp.int32(duration_ms))) \
            & (ts <= now_rel_ms)
    else:
        clock = event.clock
        age = event.n_rel - _bucket(ts, event.phase_ms, clock.interval_ms)
        in_window = (age > clock.lag) \
            & (age <= clock.lag + clock.span(duration_ms))
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
    identity where the slot has no row of it). What a slot is follows
    the window's kind (the module docstring): a batch of a
    processing-time window, written once, whole; an interval of event
    time of an event-time window (bucket b in slot b mod K), which every
    batch that brings rows of that interval adds to. ``cols["slot_ts"]``
    is each slot's time relative to the current batch base (its batch's
    time, or its interval's start; rebased every step like the ring's
    timestamps); a slot that left the window is not in
    ``cols["slot_live"]`` again until a batch (an interval) takes it
    over, which resets its row to the ops' identities first.
    An event-time window also keeps ``cols["slot_gen"]``, each slot's
    generation: the counter of the last batch that changed its row (-1:
    none yet), which is what a checkpoint goes by
    (``runtime/checkpoint.py``). A processing-time window has no need
    of it: the batch of counter g wrote slot g mod K, once."""

    cols: Dict[str, jnp.ndarray]
    valid: jnp.ndarray  # [groups] bool

    @classmethod
    def of(
        cls, keys, used, parts, slot_ts, slot_live, slot_gen=None
    ) -> "WindowPartials":
        cols = {f"key{i}": a for i, a in enumerate(keys)}
        cols.update({_PART + n: a for n, a in parts.items()})
        cols["slot_ts"], cols["slot_live"] = slot_ts, slot_live
        if slot_gen is not None:
            cols["slot_gen"] = slot_gen
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
    def slot_gen(self) -> Optional[jnp.ndarray]:
        return self.cols.get("slot_gen")

    @property
    def slots(self) -> int:
        return int(self.slot_ts.shape[0])

    @property
    def groups(self) -> int:
        return int(self.valid.shape[0])


def make_partials(
    key_dtypes: Sequence, part_dtypes: Dict[str, Tuple[str, object]],
    slots: int, groups: int, event_time: bool = False,
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
        jnp.full((slots,), -1, jnp.int32) if event_time else None,
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


def _merge_directory(
    dir_keys: Sequence[jnp.ndarray],  # [groups] a key column
    used: jnp.ndarray,  # [groups] columns that still hold a live key
    bkeys: Sequence[jnp.ndarray],  # the batch's distinct keys, key order
    bvalid: jnp.ndarray,
    payload: Sequence[jnp.ndarray],  # what rides with a batch key
):
    """Give every key of the batch its column: in key order a batch entry
    right behind its directory twin takes that column, a key the
    directory lacks the lowest free column in key order. Returns (col
    [groups]: where each placed entry lands, out of range when it does
    not; the placed entries' keys; their payload; which of them came
    from the batch; groups that found no column). A sort of 2 x groups
    entries."""
    from ..ops.groupby import _shifted, sort_groups

    d = used.shape[0]
    nk = len(dir_keys)
    merged = sort_groups(
        [jnp.concatenate([dk, bk]) for dk, bk in zip(dir_keys, bkeys)],
        jnp.concatenate([used, bvalid]),
        [
            *(jnp.concatenate([dk, bk]) for dk, bk in zip(dir_keys, bkeys)),
            *(jnp.concatenate([jnp.zeros((d,), v.dtype), v])
              for v in payload),
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
    return (col, carried[:nk], carried[nk:], klass < 2,
            jnp.maximum(n_fresh - n_free, 0))


def fold_partials(
    state: WindowPartials,
    ops: Dict[str, str],  # partial name -> "sum" | "min" | "max"
    keys: Sequence[jnp.ndarray],  # the batch's key columns, [capacity]
    valid: jnp.ndarray,  # [capacity] rows that count
    args: Dict[str, jnp.ndarray],  # partial name -> [capacity] values
    counter: jnp.ndarray,  # scalar int32: the batch counter
    delta_ms: jnp.ndarray,  # scalar int32: new_base_ms - old_base_ms
    now_rel_ms: jnp.ndarray,
    duration_ms: int,
    event: Optional[EventRows] = None,
):
    """Fold one batch into the state. Returns (new state, [slots] the
    slots the window reads this batch, [groups] the rows a column holds
    over them, groups that found no column, slots written).

    One sort over the batch's rows, and the batch's groups (its smallest
    ``groups`` keys) merged with the key directory (``_merge_directory``).
    A processing-time window (``event`` None) writes the batch's groups
    as the whole row of its one slot, ``counter`` mod K: everything after
    the sort is sized by the group bound. An event-time window sorts by (key,
    interval), and adds each (interval, key) total to the row of the
    interval's slot: the w + 2 rows a batch can touch are read, reset
    where a new interval takes the slot over, added to and written back.
    The branch is taken at trace time; the state is the same."""
    from ..ops.groupby import _identity, segmented_scan, sort_groups

    state_parts = state.parts
    k, d = state_parts[ROWS].shape
    names = sorted(state_parts)
    nk = len(keys)
    cap = valid.shape[0]
    gb = min(cap, d)
    by = [] if event is None else [event.age]

    # 1. the batch's groups: sorted runs, each run's total at its last row
    sg = sort_groups(
        [*keys, *by], valid,
        [*keys, *by, *(args[n] for n in names if n != ROWS)],
    )
    keys_s = sg.carried[:nk]
    vals_s = dict(zip((n for n in names if n != ROWS),
                      sg.carried[nk + len(by):]))
    run = {ROWS: segmented_scan(sg.valid_s.astype(jnp.int32), sg.seg, "sum")}
    for n, v in vals_s.items():
        ident = _identity(ops[n], v.dtype)
        run[n] = segmented_scan(
            jnp.where(sg.valid_s, v, ident), sg.seg, ops[n]
        )
    last = sg.valid_s & jnp.concatenate(
        [sg.seg[1:] != sg.seg[:-1], jnp.ones((1,), jnp.bool_)]
    )
    if event is not None:
        return _fold_event(
            state, ops, names, sg, keys_s, run, last, counter, delta_ms,
            duration_ms, event,
        )
    n_batch = jnp.sum(last.astype(jnp.int32))
    packed = _front(last, [*keys_s, *(run[n] for n in names)], gb)
    bkeys, bvals = packed[:nk], dict(zip(names, packed[nk:]))
    bvalid = jnp.arange(gb) < n_batch
    dropped = jnp.maximum(n_batch - gb, 0)

    # 2. the slots: rebase their times, retire what left the window (a
    # slot is wholly inside or outside: its rows share the batch's time)
    slot = jax.lax.rem(counter, jnp.asarray(k, jnp.int32))
    at = jnp.arange(k) == slot
    ts = jnp.where(at, now_rel_ms, state.slot_ts - delta_ms)
    in_window = (ts >= now_rel_ms - jnp.int32(duration_ms)) & (ts <= now_rel_ms)
    live = (state.slot_live | at) & in_window
    rows_old = jnp.sum(
        jnp.where((live & ~at)[:, None], state_parts[ROWS], 0), axis=0
    )
    used = state.used & (rows_old > 0)

    # 3. merge the batch's groups with the directory
    col, carried_keys, carried, _from_batch, overflow = _merge_directory(
        state.keys, used, bkeys, bvalid, [bvals[n] for n in names]
    )
    dropped = dropped + overflow

    # 4. the slot's row of every partial, the directory's new keys
    def put(target, updates):
        return target.at[col].set(
            updates, mode="drop", unique_indices=True
        )

    new_keys = tuple(put(dk, c) for dk, c in zip(state.keys, carried_keys))
    new_used = put(used, jnp.ones((d,), jnp.bool_))
    parts = {}
    for n, c in zip(names, carried):
        row = put(jnp.full((d,), _identity(ops[n], c.dtype), c.dtype), c)
        parts[n] = jax.lax.dynamic_update_index_in_dim(
            state_parts[n], row, slot, axis=0
        )
        if n == ROWS:
            rows = rows_old + row
    return (
        WindowPartials.of(new_keys, new_used, parts, ts, live),
        live, rows, dropped.astype(jnp.int32), jnp.asarray(1, jnp.int32),
    )


def _fold_event(
    state: WindowPartials, ops, names, sg, keys_s, run, last, counter,
    delta_ms, duration_ms: int, event: EventRows,
):
    """``fold_partials`` for an event-time window, from the batch sorted
    by (key, interval): ``run`` holds each (key, interval) total at the
    run's ``last`` row."""
    from ..ops.groupby import _COMBINE, _identity

    state_parts = state.parts
    k, d = state_parts[ROWS].shape
    nk = len(keys_s)
    cap = last.shape[0]
    gb = min(cap, d)
    clock = event.clock
    i_ms = jnp.int32(clock.interval_ms)
    reach = clock.lag + clock.span(duration_ms)  # the oldest interval held
    nb = clock.lag + 1  # the intervals a batch's accepted rows fall in
    age_s = sg.carried[nk]

    # 1. the batch's distinct keys: a (key, interval) run that starts
    # where the key changes; every row knows its key's rank among them
    changed = jnp.zeros((cap - 1,), jnp.bool_)
    for ks in keys_s:
        changed = changed | (ks[1:] != ks[:-1])
    key_first = sg.first & jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), changed])
    rank = jnp.cumsum(key_first.astype(jnp.int32)) - 1
    n_batch = jnp.sum(key_first.astype(jnp.int32))
    bkeys = _front(key_first, keys_s, gb)
    bvalid = jnp.arange(gb) < n_batch
    dropped = jnp.maximum(n_batch - gb, 0)

    # 2. the slots: rebase their times, retire the intervals older than
    # the window's far edge; a key keeps its column while any interval
    # still held (the window's and the watermark's) has rows of it
    ts_old = state.slot_ts - delta_ms
    age_old = jnp.floor_divide(event.now_start_ms - ts_old, i_ms)
    keep = state.slot_live & (age_old >= 0) & (age_old <= reach)
    rows_kept = jnp.sum(
        jnp.where(keep[:, None], state_parts[ROWS], 0), axis=0
    )
    used = state.used & (rows_kept > 0)

    # 3. merge the batch's keys with the directory; which column each
    # got, by its rank
    col, carried_keys, (bidx,), from_batch, overflow = _merge_directory(
        state.keys, used, bkeys, bvalid, [jnp.arange(gb, dtype=jnp.int32)]
    )
    dropped = dropped + overflow
    col_of = jnp.full((gb,), d, jnp.int32).at[
        jnp.where(from_batch, bidx, gb + jnp.arange(d))
    ].set(col.astype(jnp.int32), mode="drop", unique_indices=True)
    new_keys = tuple(
        dk.at[col].set(c, mode="drop", unique_indices=True)
        for dk, c in zip(state.keys, carried_keys)
    )
    new_used = used.at[col].set(
        jnp.ones((d,), jnp.bool_), mode="drop", unique_indices=True
    )

    # 4. each (interval, key) total to its place in [w + 2, groups], and
    # those rows into their slots: interval n - j lives in slot (n - j)
    # mod K; a slot that held another interval starts from the identity
    run_col = col_of[jnp.clip(rank, 0, gb - 1)]
    lands = last & (rank < gb) & (run_col < d)
    flat = jnp.where(lands, age_s * d + run_col, nb * d + jnp.arange(cap))
    at = jnp.mod(event.slot_of_now(k) - jnp.arange(nb, dtype=jnp.int32), k)
    start = event.now_start_ms - jnp.arange(nb, dtype=jnp.int32) * i_ms
    sums = {
        n: jnp.full((nb * d,), _identity(ops[n], run[n].dtype), run[n].dtype)
        .at[flat].set(run[n], mode="drop", unique_indices=True)
        .reshape(nb, d)
        for n in names
    }
    touched = jnp.any(sums[ROWS] > 0, axis=1)
    taken_over = touched & ~(keep[at] & (ts_old[at] == start))
    parts = {}
    for n in names:
        held = jnp.where(
            taken_over[:, None], _identity(ops[n], sums[n].dtype),
            state_parts[n][at],
        )
        parts[n] = state_parts[n].at[at].set(
            _COMBINE[ops[n]](held, sums[n]), unique_indices=True
        )
    ts = ts_old.at[at].set(jnp.where(touched, start, ts_old[at]))
    live = keep.at[at].set(keep[at] | touched)
    gen = state.slot_gen.at[at].set(
        jnp.where(touched, counter, state.slot_gen[at]))
    # the window trails the batch by the watermark and the interval its
    # own rows came in over: what this batch brought is not in it yet
    age = jnp.floor_divide(event.now_start_ms - ts, i_ms)
    window = live & (age > clock.lag)
    rows = jnp.sum(jnp.where(window[:, None], parts[ROWS], 0), axis=0)
    return (
        WindowPartials.of(new_keys, new_used, parts, ts, live, gen),
        window, rows, dropped.astype(jnp.int32),
        jnp.sum(touched.astype(jnp.int32)),
    )


def combine_partials(
    state: WindowPartials,
    ops: Dict[str, str],
    window: jnp.ndarray,  # [slots] ``fold_partials``' window slots
    rows: jnp.ndarray,  # [groups] and its row counts over them
    dropped: jnp.ndarray,
) -> TableData:
    """The window's groups in key order: every partial reduced over the
    window's slots (one masked pass over [K, groups]), the columns that
    hold rows sorted by key to the front. Columns ``key<i>``, one a
    partial, and the dropped-group count on every row."""
    from ..ops.groupby import _identity, sort_groups

    d = state.groups
    live = window[:, None]
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
