"""Mask compaction: valid rows to the front with a static size."""

from __future__ import annotations

import math
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp


def compact_front(
    valid: jnp.ndarray, cols: Sequence[jnp.ndarray]
) -> Tuple[List[jnp.ndarray], jnp.ndarray]:
    """The rows where ``valid`` holds, packed to the front in their order.

    Returns (cols_front, valid_front): one stable sort on the validity
    bit with the columns riding along as its operands, so no column is
    gathered by a row index. Valid rows keep their order, so the first
    ``count`` rows are those of ``nonzero`` + a gather, bit for bit.
    Rows past the count hold the invalid rows' values, in their order;
    ``valid_front`` is False there and nothing should read them (the
    host slices ``[:count]``).

    A column whose leading dimension is ``valid``'s rides the sort; one
    of shape ``[n, k, ...]`` rides as ``k...`` 1-D operands and is put
    back after. Any other column (a scalar, another length) is returned
    as it came.
    """
    n = valid.shape[0]
    # operands each column rides as (0: it does not ride)
    widths = [
        math.prod(c.shape[1:]) if c.shape[:1] == (n,) else 0 for c in cols
    ]
    flat = []
    for c, k in zip(cols, widths):
        if c.ndim == 1 and k:
            flat.append(c)
        elif k:
            m = c.reshape(n, k)
            flat.extend(m[:, j] for j in range(k))
    key, *front = jax.lax.sort(
        (jnp.where(valid, 0, 1).astype(jnp.int32), *flat),
        num_keys=1, is_stable=True,
    )
    out, at = [], 0
    for c, k in zip(cols, widths):
        if not k:
            out.append(c)
        elif c.ndim == 1:
            out.append(front[at])
        else:
            out.append(jnp.stack(front[at:at + k], axis=1).reshape(c.shape))
        at += k
    return out, key == 0
