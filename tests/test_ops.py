"""Numerical kernel tests vs numpy reference implementations — the layer
the reference lacks entirely (SURVEY.md section 4 takeaway)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from data_accelerator_tpu.ops import (
    compact_indices,
    distinct_mask,
    group_ids,
    inner_join_indices,
    segment_aggregate,
)
from data_accelerator_tpu.ops.join import left_join_indices


def _np_groupby(keys, values, valid):
    """Reference group-by using plain python."""
    groups = {}
    for i in range(len(valid)):
        if not valid[i]:
            continue
        k = tuple(np.asarray(col)[i] for col in keys)
        groups.setdefault(k, []).append(values[i])
    return groups


def test_group_ids_and_sum():
    keys = [jnp.array([3, 1, 3, 2, 1, 9, 3, 0], dtype=jnp.int32)]
    valid = jnp.array([1, 1, 1, 1, 1, 0, 1, 0], dtype=bool)
    vals = jnp.array([10.0, 20, 30, 40, 50, 60, 70, 80], dtype=jnp.float32)

    order, seg, num, first = group_ids(keys, valid)
    assert int(num) == 3  # {1, 2, 3}
    vals_s = vals[order]
    valid_s = valid[order]
    out = segment_aggregate(vals_s, seg, 8, "sum", valid_s)
    # groups sorted by key: 1 -> 70, 2 -> 40, 3 -> 110
    np.testing.assert_allclose(np.asarray(out[:3]), [70.0, 40.0, 110.0])


def test_group_min_max_count():
    k = jnp.array([1, 2, 1, 2, 1], dtype=jnp.int32)
    valid = jnp.ones(5, dtype=bool)
    v = jnp.array([5, 1, 3, 9, 4], dtype=jnp.int32)
    order, seg, num, _ = group_ids([k], valid)
    v_s, valid_s = v[order], valid[order]
    assert int(num) == 2
    np.testing.assert_array_equal(
        np.asarray(segment_aggregate(v_s, seg, 5, "min", valid_s)[:2]), [3, 1]
    )
    np.testing.assert_array_equal(
        np.asarray(segment_aggregate(v_s, seg, 5, "max", valid_s)[:2]), [5, 9]
    )
    np.testing.assert_array_equal(
        np.asarray(segment_aggregate(v_s, seg, 5, "count", valid_s)[:2]), [3, 2]
    )


def test_group_by_multiple_keys_and_floats():
    k1 = jnp.array([1, 1, 2, 2, 1], dtype=jnp.int32)
    k2 = jnp.array([-1.5, -1.5, 0.5, 0.5, 2.5], dtype=jnp.float32)
    valid = jnp.ones(5, dtype=bool)
    order, seg, num, _ = group_ids([k1, k2], valid)
    assert int(num) == 3


def test_group_all_invalid():
    k = jnp.array([1, 2], dtype=jnp.int32)
    valid = jnp.zeros(2, dtype=bool)
    _, _, num, first = group_ids([k], valid)
    assert int(num) == 0
    assert not np.asarray(first).any()


def test_empty_keys_single_group():
    # global aggregation: GROUP BY ()
    valid = jnp.array([1, 1, 0, 1], dtype=bool)
    v = jnp.array([1.0, 2, 99, 3], dtype=jnp.float32)
    order, seg, num, _ = group_ids([], valid)
    assert int(num) == 1
    out = segment_aggregate(v[order], seg, 4, "sum", valid[order])
    assert float(out[0]) == 6.0


def test_distinct_mask():
    k = jnp.array([7, 7, 8, 7, 8, 9], dtype=jnp.int32)
    valid = jnp.array([1, 1, 1, 1, 1, 0], dtype=bool)
    keep = distinct_mask([k], valid)
    kept_keys = sorted(np.asarray(k)[np.asarray(keep)].tolist())
    assert kept_keys == [7, 8]
    assert int(np.asarray(keep).sum()) == 2


def test_inner_join_basic():
    lk = jnp.array([1, 2, 3, 4], dtype=jnp.int32)
    rk = jnp.array([2, 3, 2], dtype=jnp.int32)
    lv = jnp.ones(4, dtype=bool)
    rv = jnp.array([1, 1, 1], dtype=bool)
    li, ri, valid, dropped = inner_join_indices([lk], [rk], lv, rv, out_capacity=8)
    pairs = {
        (int(lk[li[i]]), int(rk[ri[i]]))
        for i in range(8)
        if bool(valid[i])
    }
    # key 2 matches right rows 0 and 2; key 3 matches right row 1
    assert pairs == {(2, 2), (3, 3)}
    assert int(np.asarray(valid).sum()) == 3  # (2,r0), (2,r2), (3,r1)


def test_inner_join_residual_condition():
    lk = jnp.array([1, 1], dtype=jnp.int32)
    rk = jnp.array([1, 1], dtype=jnp.int32)
    lval = jnp.array([10, 20], dtype=jnp.int32)
    rval = jnp.array([15, 25], dtype=jnp.int32)
    lv = jnp.ones(2, dtype=bool)
    rv = jnp.ones(2, dtype=bool)
    li, ri, valid, _dropped = inner_join_indices(
        [lk], [rk], lv, rv, 8,
        residual=lambda i, j: lval[i] > rval[j],
    )
    got = {(int(li[i]), int(ri[i])) for i in range(8) if bool(valid[i])}
    assert got == {(1, 0)}  # only 20 > 15


def test_join_overflow_drops():
    lk = jnp.zeros(4, dtype=jnp.int32)
    rk = jnp.zeros(4, dtype=jnp.int32)
    lv = jnp.ones(4, dtype=bool)
    rv = jnp.ones(4, dtype=bool)
    _, _, valid, dropped = inner_join_indices([lk], [rk], lv, rv, out_capacity=5)
    assert int(np.asarray(valid).sum()) == 5  # 16 matches capped at 5
    assert int(dropped) == 11  # and the overflow is counted, not silent


def test_left_join_unmatched():
    lk = jnp.array([1, 2], dtype=jnp.int32)
    rk = jnp.array([2], dtype=jnp.int32)
    lv = jnp.ones(2, dtype=bool)
    rv = jnp.ones(1, dtype=bool)
    li, ri, valid, is_null, dropped = left_join_indices([lk], [rk], lv, rv, 4)
    rows = [
        (int(lk[li[i]]), bool(is_null[i]))
        for i in range(4)
        if bool(valid[i])
    ]
    assert sorted(rows) == [(1, True), (2, False)]


def test_compact():
    valid = jnp.array([0, 1, 0, 1, 1], dtype=bool)
    idx, out_valid = compact_indices(valid, 5)
    assert np.asarray(idx)[:3].tolist() == [1, 3, 4]
    assert np.asarray(out_valid).tolist() == [True, True, True, False, False]


def test_ops_jit_compatible():
    @jax.jit
    def fn(k, valid, v):
        order, seg, num, _ = group_ids([k], valid)
        return segment_aggregate(v[order], seg, k.shape[0], "sum", valid[order]), num

    out, num = fn(
        jnp.array([1, 1, 2], dtype=jnp.int32),
        jnp.ones(3, dtype=bool),
        jnp.array([1.0, 2, 3], dtype=jnp.float32),
    )
    assert int(num) == 2
    np.testing.assert_allclose(np.asarray(out[:2]), [3.0, 3.0])


def test_sort_join_matches_matrix_join():
    """Sort-merge and match-matrix joins agree pair-for-pair (values,
    validity, drop count, and ORDER) on random multi-key data."""
    from data_accelerator_tpu.ops.join import sort_join_indices

    rng = np.random.RandomState(5)
    n, m, cap = 64, 48, 256
    lk1 = jnp.asarray(rng.randint(0, 8, n), jnp.int32)
    lk2 = jnp.asarray(rng.randint(0, 3, n), jnp.int32)
    rk1 = jnp.asarray(rng.randint(0, 8, m), jnp.int32)
    rk2 = jnp.asarray(rng.randint(0, 3, m), jnp.int32)
    lv = jnp.asarray(rng.rand(n) > 0.2)
    rv = jnp.asarray(rng.rand(m) > 0.2)

    li_a, ri_a, va, da = inner_join_indices([lk1, lk2], [rk1, rk2], lv, rv, cap)
    li_b, ri_b, vb, nb, db = sort_join_indices([lk1, lk2], [rk1, rk2], lv, rv, cap)
    pa = [(int(li_a[i]), int(ri_a[i])) for i in range(cap) if bool(va[i])]
    pb = [(int(li_b[i]), int(ri_b[i])) for i in range(cap) if bool(vb[i])]
    assert pa == pb  # identical pairs in identical order
    assert int(da) == int(db) == 0
    assert not bool(np.asarray(nb).any())


def test_sort_join_overflow_and_left_outer():
    from data_accelerator_tpu.ops.join import sort_join_indices

    lk = jnp.asarray([1, 1, 2, 3], jnp.int32)
    rk = jnp.asarray([1, 1, 1, 9], jnp.int32)
    lv = jnp.ones(4, bool)
    rv = jnp.ones(4, bool)
    # inner with overflow: 2 left rows x 3 matches = 6 pairs, cap 4
    _, _, valid, _nul, dropped = sort_join_indices([lk], [rk], lv, rv, 4)
    assert int(np.asarray(valid).sum()) == 4
    assert int(dropped) == 2
    # left outer: unmatched lefts (2, 3) emit one null row each
    li, ri, valid, is_null, dropped = sort_join_indices(
        [lk], [rk], lv, rv, 16, left_outer=True
    )
    rows = [(int(li[i]), bool(is_null[i])) for i in range(16) if bool(valid[i])]
    assert rows == [(0, False)] * 3 + [(1, False)] * 3 + [(2, True), (3, True)]
    assert int(dropped) == 0


# -- the sorted GROUP BY against numpy: every op over every shape of input


def _groupby_case(name):
    """(k1, k2, valid, live, capacity): ``live`` is what the aggregate is
    told is valid, in input order (a subset of ``valid``)."""
    rng = np.random.RandomState(11)
    n, capacity = 64, 64
    if name == "odd_length":
        n = capacity = 333  # blocks of 128 rows, the last one padded
    elif name == "three_levels":
        n, capacity = 128 * 128 + 1000, 32  # blocks of blocks of blocks
    k1 = rng.randint(0, 4, n)
    k2 = rng.randint(-2, 1, n)
    valid = np.ones(n, bool)
    live = None
    if name == "invalid_interleaved":
        valid = rng.rand(n) < 0.6
    elif name == "not_live_mid_segment":
        live = rng.rand(n) < 0.7
    elif name == "zero_valid":
        valid = np.zeros(n, bool)
    elif name == "more_groups_than_capacity":
        k1 = rng.randint(0, 40, n)
        capacity = 8
    elif name == "one_group":
        k1 = np.full(n, 3)
        k2 = np.full(n, -1)
    return k1, k2, valid, valid if live is None else valid & live, capacity


_GROUPBY_CASES = [
    "all_valid", "invalid_interleaved", "not_live_mid_segment", "zero_valid",
    "more_groups_than_capacity", "one_group", "odd_length", "three_levels",
]
_NP_REDUCE = {
    "sum_int": np.sum, "min": np.min, "max": np.max, "any": np.any,
    "all": np.all,
}


@pytest.mark.parametrize("case", _GROUPBY_CASES)
@pytest.mark.parametrize(
    "op", ["count", "sum", "sum_int", "min", "max", "any", "all"])
def test_segment_aggregate_equals_numpy(op, case):
    from data_accelerator_tpu.ops.groupby import sort_groups

    k1, k2, valid, live, capacity = _groupby_case(case)
    n = len(valid)
    rng = np.random.RandomState(3)
    if op in ("any", "all"):
        vals = rng.rand(n) < 0.5
    elif op == "sum_int":
        vals = rng.randint(-1000, 1000, n).astype(np.int32)
    else:
        vals = rng.normal(70.0, 10.0, n).astype(np.float32)

    g = sort_groups(
        [jnp.asarray(k1, jnp.int32), jnp.asarray(k2, jnp.int32)],
        jnp.asarray(valid), [jnp.asarray(vals), jnp.asarray(live)],
    )
    vals_s, live_s = g.carried
    order = np.asarray(g.order)
    # what rode through the sort is what a gather by `order` would give
    np.testing.assert_array_equal(np.asarray(vals_s), vals[order])
    np.testing.assert_array_equal(np.asarray(g.valid_s), valid[order])
    if op == "count":
        # COUNT takes the sort's own validity (its invalid rows are last)
        live, live_s = valid, g.valid_s
    out = np.asarray(segment_aggregate(
        None if op == "count" else vals_s, g.seg, capacity,
        op.split("_")[0], live_s,
    ))
    assert out.shape == (capacity,)

    groups = sorted({(a, b) for a, b, v in zip(k1, k2, valid) if v})
    assert int(g.num_groups) == len(groups)
    for i, (a, b) in enumerate(groups[:capacity]):
        rows = vals[(k1 == a) & (k2 == b) & live]
        if op == "count":
            assert out[i] == len(rows)
        elif len(rows) == 0:
            continue  # a group of no live row reads the op's identity
        elif op == "sum":
            np.testing.assert_allclose(
                out[i], np.sum(rows.astype(np.float64)), rtol=1e-6)
        else:
            assert out[i] == _NP_REDUCE[op](rows), (op, case, i)
    if op in ("count", "sum", "sum_int"):
        # the dummy segment of invalid rows and the unused slots read 0
        assert not out[len(groups):].any()


@pytest.mark.parametrize("case", _GROUPBY_CASES)
def test_group_order_is_numpys_stable_lexsort(case):
    k1, k2, valid, _live, _capacity = _groupby_case(case)
    order, seg, num, first = group_ids(
        [jnp.asarray(k1, jnp.int32), jnp.asarray(k2, jnp.int32)],
        jnp.asarray(valid),
    )
    # np.lexsort: last key is primary, and it is stable
    expect = np.lexsort((k2, k1, ~valid))
    np.testing.assert_array_equal(np.asarray(order), expect)
    seg, first = np.asarray(seg), np.asarray(first)
    assert (np.diff(seg) >= 0).all() and (np.diff(seg) <= 1).all()
    n_valid = int(valid.sum())
    assert first.sum() == int(num) and not first[n_valid:].any()
    # dense ids: each group's first row opens the next id
    np.testing.assert_array_equal(seg[:n_valid], np.cumsum(first)[:n_valid] - 1)
    assert (seg[n_valid:] == int(num)).all()


@pytest.mark.parametrize("n", [1, 100, 128, 129, 1000, 128 * 128 + 77])
@pytest.mark.parametrize("op", ["sum", "min", "max"])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
def test_segmented_scan_equals_numpy(dtype, op, n):
    """Runs from one row to thousands, crossing blocks and blocks of
    blocks; integers exact, float sums to 1e-6 of the float64 scan."""
    from data_accelerator_tpu.ops.groupby import segmented_scan

    rng = np.random.RandomState(n)
    seg = np.cumsum(rng.rand(n) < (0.3 if n < 2000 else 0.002)).astype(np.int32)
    vals = (rng.normal(0, 100, n) if dtype == np.float32
            else rng.randint(-1000, 1000, n)).astype(dtype)
    got = np.asarray(segmented_scan(jnp.asarray(vals), jnp.asarray(seg), op))
    acc = {"sum": np.add, "min": np.minimum, "max": np.maximum}[op].accumulate
    expect = np.concatenate([
        acc(vals[seg == g].astype(np.float64 if dtype == np.float32 else dtype))
        for g in np.unique(seg)
    ])
    if dtype == np.float32 and op == "sum":
        scale = np.concatenate([
            np.add.accumulate(np.abs(vals[seg == g]).astype(np.float64))
            for g in np.unique(seg)
        ])
        assert (np.abs(got - expect) <= 1e-6 * scale).all()
    else:
        np.testing.assert_array_equal(got, expect.astype(dtype))


def test_group_float_keys_keep_their_equality():
    """The boundary is read from the sorted key by the key's own ``!=``:
    0.0 and -0.0 are one group, and a NaN equals nothing, itself neither."""
    nan = float("nan")
    k = jnp.asarray([0.0, -0.0, 1.5, nan, -1.5, 1.5, nan], jnp.float32)
    valid = jnp.ones(7, bool)
    order, seg, num, _ = group_ids([k], valid)
    assert int(num) == 5
    assert np.asarray(order).tolist() == [4, 0, 1, 2, 5, 3, 6]
    assert np.asarray(seg).tolist() == [0, 1, 1, 2, 2, 3, 4]
