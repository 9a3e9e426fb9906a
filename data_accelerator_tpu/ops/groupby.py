"""Sort-based GROUP BY for fixed-capacity masked batches.

Replaces Spark's hash-exchange + aggregate for ``GROUP BY`` queries
(reference: implicit in spark.sql, CommonProcessorFactory.scala:257) with
an XLA-friendly static-shape pipeline that makes no gather and no scatter
whose index count is the row count:

  1. one stable ``lax.sort`` by (invalid-last, key columns) that carries
     the row index and every column a consumer reads in sorted order
     (aggregate arguments) as payload operands: the sorted keys, validity
     and values come out of the sort, nothing is gathered by ``order``
  2. flag segment boundaries on the sorted keys, prefix-sum into dense
     group ids
  3. segment starts by a binary search of the ids (``capacity + 1``
     queries); COUNT is the difference of consecutive starts, SUM / MIN /
     MAX / ANY / ALL a blocked segmented scan over the sorted values read
     at each segment's last row (``capacity``-index gathers); a group's
     representative row is the row at its start

All shapes are static; invalid rows sort to the end and land in a dummy
trailing segment that the output mask hides. Group count <= row count, so
output capacity == input capacity is always sufficient.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp

def _flip_negative(bits: jnp.ndarray) -> jnp.ndarray:
    """Sign-magnitude int32 bits <-> two's-complement order (its own
    inverse): float bit patterns then compare like the floats."""
    return jnp.where(bits < 0, jnp.int32(-2147483648) - bits, bits)


def _as_sortable(col: jnp.ndarray) -> jnp.ndarray:
    """Make a column usable as a sort key (bool/float -> int bits)."""
    if col.dtype == jnp.bool_:
        return col.astype(jnp.int32)
    if jnp.issubdtype(col.dtype, jnp.floating):
        # total order on floats via sign-magnitude bit trick
        return _flip_negative(
            jax.lax.bitcast_convert_type(col.astype(jnp.float32), jnp.int32)
        )
    return col.astype(jnp.int32)


def _key_changes(sorted_key: jnp.ndarray, like: jnp.ndarray) -> jnp.ndarray:
    """[n] bool: sorted position i holds another key value than i - 1,
    by the ``!=`` of the key's own dtype (0.0 equals -0.0, NaN differs
    from NaN), which for floats means undoing ``_as_sortable``."""
    ks = sorted_key
    if jnp.issubdtype(like.dtype, jnp.floating):
        ks = jax.lax.bitcast_convert_type(_flip_negative(ks), jnp.float32)
    return jnp.concatenate([jnp.ones((1,), jnp.bool_), ks[1:] != ks[:-1]])


class SortedGroups(NamedTuple):
    """What one GROUP BY sort gives, everything in sorted order."""

    order: jnp.ndarray  # [n] permutation sorting rows by (valid desc, keys)
    seg: jnp.ndarray  # [n] dense group id; invalid rows get ``num_groups``
    num_groups: jnp.ndarray  # scalar count of real groups
    first: jnp.ndarray  # [n] bool, True at the first row of each group
    valid_s: jnp.ndarray  # [n] bool, the rows' validity
    carried: Tuple[jnp.ndarray, ...]  # the ``carry`` columns


def sort_groups(
    keys: Sequence[jnp.ndarray],
    valid: jnp.ndarray,
    carry: Sequence[jnp.ndarray] = (),
) -> SortedGroups:
    """Sort the masked rows into groups; ``carry`` columns ([n], any
    dtype) ride through the sort instead of being gathered by ``order``
    after it."""
    n = valid.shape[0]
    keys = list(keys)
    # primary key: invalid rows last; stable, so rows of one group keep
    # their input order and the row index is a payload, not a key
    operands = (
        jnp.where(valid, 0, 1).astype(jnp.int32),
        *(_as_sortable(k) for k in keys),
        jnp.arange(n, dtype=jnp.int32),
        *carry,
    )
    invalid_s, *rest = jax.lax.sort(
        operands, num_keys=1 + len(keys), is_stable=True
    )
    keys_s, order, carried = rest[: len(keys)], rest[len(keys)], rest[len(keys) + 1:]
    valid_s = invalid_s == 0

    boundary = jnp.zeros((n,), dtype=jnp.bool_)
    for k, ks in zip(keys, keys_s):
        boundary = boundary | _key_changes(ks, k)
    # only valid rows start groups; the first invalid row starts the dummy
    first_invalid = jnp.concatenate(
        [valid_s[:1] == False, valid_s[1:] != valid_s[:-1]]  # noqa: E712
    )
    boundary = (boundary & valid_s) | (first_invalid & ~valid_s)
    # make sure position 0 is a boundary (group 0 or dummy)
    boundary = boundary.at[0].set(True)

    seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1  # dense ids in sorted order
    first = boundary & valid_s
    num_groups = jnp.sum(first.astype(jnp.int32))
    return SortedGroups(order, seg, num_groups, first, valid_s, tuple(carried))


def group_ids(
    keys: Sequence[jnp.ndarray], valid: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Compute dense group ids for the masked rows.

    Returns (order, gids_sorted, num_groups, first_in_group), the first
    four fields of ``sort_groups``.
    """
    return sort_groups(keys, valid)[:4]


def segment_starts(seg: jnp.ndarray, capacity: int) -> jnp.ndarray:
    """[capacity + 1] sorted position at which each segment id starts
    (``n`` for an id no row has): ``seg`` is non-decreasing, so a binary
    search finds it, log2(n) gathers of ``capacity + 1`` indices (unrolled:
    as a loop the chip spends as long on each turn as on its gather).
    Segment ``g`` is rows ``starts[g]:starts[g + 1]``."""
    return jnp.searchsorted(
        seg, jnp.arange(capacity + 1, dtype=seg.dtype), side="left",
        method="scan_unrolled",
    )


def _identity(op: str, dtype) -> jnp.ndarray:
    if op == "sum":
        return jnp.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf if op == "min" else -jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if op == "min" else info.min, dtype)


_COMBINE = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}

# rows of a block: one vector register's lanes
_BLOCK = 128


def _shifted(x: jnp.ndarray, k: int, axis: int) -> jnp.ndarray:
    """``x`` moved ``k`` places up ``axis`` (the first ``k`` read 0)."""
    head = list(x.shape)
    head[axis] = k
    body = jax.lax.slice_in_dim(x, 0, x.shape[axis] - k, axis=axis)
    return jnp.concatenate([jnp.zeros(head, x.dtype), body], axis=axis)


def _scan_runs(x, seg, axis, combine):
    """Inclusive scan along ``axis`` that restarts where ``seg`` changes:
    log2(length) doubling steps, each one elementwise pass. Ids do not
    decrease along the axis, so the row ``k`` places back lies in the same
    run exactly when it holds the same id: no reset flag is carried."""
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    k = 1
    while k < x.shape[axis]:
        same_run = (pos >= k) & (_shifted(seg, k, axis) == seg)
        x = jnp.where(same_run, combine(_shifted(x, k, axis), x), x)
        k *= 2
    return x


def segmented_scan(values: jnp.ndarray, seg: jnp.ndarray, op: str) -> jnp.ndarray:
    """[n] inclusive ``op`` ("sum" | "min" | "max") of ``values`` over the
    rows of its run of equal ``seg`` ids (non-decreasing) up to each row.

    Blocked: runs are scanned inside blocks of ``_BLOCK`` rows, the
    blocks' last rows are scanned the same way one level up, and a block
    whose first run began before it takes that run's total so far. A
    group's sum is built pairwise inside the group: nothing is summed
    across groups and subtracted again, so nothing cancels.
    """
    combine = _COMBINE[op]
    n = seg.shape[0]
    if n <= _BLOCK:
        return _scan_runs(values, seg, 0, combine)
    blocks = -(-n // _BLOCK)
    pad = blocks * _BLOCK - n  # rows after the last: never read back
    x = jnp.pad(values, (0, pad)).reshape(blocks, _BLOCK)
    ids = jnp.pad(seg, (0, pad), mode="edge").reshape(blocks, _BLOCK)
    x = _scan_runs(x, ids, 1, combine)
    # the run a block ends in, up to the block's end
    run_so_far = segmented_scan(x[:, -1], ids[:, -1], op)
    before = _shifted(run_so_far, 1, 0)[:, None]
    id_before = _shifted(ids[:, -1], 1, 0)[:, None]
    first_block = jax.lax.broadcasted_iota(jnp.int32, ids.shape, 0) == 0
    x = jnp.where((ids == id_before) & ~first_block, combine(before, x), x)
    return x.reshape(-1)[:n]


def segment_aggregate(
    values: jnp.ndarray,
    seg: jnp.ndarray,
    capacity: int,
    op: str,
    valid_s: jnp.ndarray,
) -> jnp.ndarray:
    """Aggregate sorted ``values`` per segment id into [capacity] output.

    op: "sum" | "min" | "max" | "count" | "any" | "all"
    ``seg`` is ``sort_groups``' (non-decreasing). Rows that are not
    ``valid_s`` do not count. For a reduction of values they may sit
    anywhere, in the middle of a segment too (string MIN/MAX masks nulls
    that way): they are given the op's identity here. "count" takes
    ``sort_groups``' own ``valid_s``, whose invalid rows are the last
    ones (count a narrower mask as the "sum" of its 0/1 values). A
    segment no live row falls in reads the identity (0; the dtype's
    largest / smallest value for min / max).
    """
    if op == "count":
        # rows between consecutive segment starts; the rows that are not
        # valid are the last ones (``sort_groups``' order), so a start is
        # clipped to the number of valid rows: exact, and nothing is summed
        starts = jnp.minimum(
            segment_starts(seg, capacity), jnp.sum(valid_s.astype(jnp.int32))
        )
        return starts[1:] - starts[:-1]
    if op in ("any", "all"):
        as_int = segment_aggregate(
            values.astype(jnp.bool_).astype(jnp.int32), seg, capacity,
            "max" if op == "any" else "min", valid_s,
        )
        return as_int > 0
    if op not in _COMBINE:
        raise ValueError(f"unknown aggregate op {op!r}")
    # no scatter: the segmented scan, read at each segment's last row
    identity = _identity(op, values.dtype)
    scanned = segmented_scan(jnp.where(valid_s, values, identity), seg, op)
    starts = segment_starts(seg, capacity)
    lo, hi = starts[:-1], starts[1:]
    at_last = scanned[jnp.clip(hi - 1, 0, seg.shape[0] - 1)]
    return jnp.where(hi > lo, at_last, identity)


def distinct_mask(keys: Sequence[jnp.ndarray], valid: jnp.ndarray) -> jnp.ndarray:
    """Mask keeping one representative row per distinct key combination.

    Used for SELECT DISTINCT: rows stay in place (no reordering); the
    first occurrence in sort order survives.
    """
    order, _seg, _num, first = group_ids(keys, valid)
    n = valid.shape[0]
    keep = jnp.zeros((n,), dtype=jnp.bool_).at[order].set(first)
    return keep & valid
