"""Grouped matrix product: rows sorted by group, each times its group's
matrix, on the TPU's grouped-matmul kernel.

``grouped_matmul(lhs, rhs, sizes)`` computes, for each group ``g``, the
rows ``[off_g, off_g + sizes[g])`` of ``lhs`` (m, k) times ``rhs[g]``
(k, n), where ``off_g`` is the sum of the sizes before it. It runs JAX's
megablox kernels (``jax.experimental.pallas.ops.tpu.megablox``): ``gmm``
for the product and for its data gradient (the product with each group's
matrix transposed), ``tgmm`` for its weight gradient. Their grid visits
only the row tiles that hold a group's rows (one more per group that
starts inside a tile), so the time follows the rows in the groups and
not the static size of ``lhs``. The weight gradient reads the groups'
rows alone (a select, NaN included). The rows past the last group are
not written, in the result and in the data gradient: they hold whatever
the output buffer held, and the caller masks them.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

from repro.kernels.backend import resolve_interpret

#: row, contraction and column tile sizes; a dimension below its tile is
#: one whole tile. A ragged last tile of the contraction or the columns is
#: masked by the kernel.
TM, TK, TN = 512, 1024, 1024


def tiling(m: int, k: int, n: int):
    """The tiles of one (m, k, n) product: forward, data gradient and weight
    gradient each look theirs up (megablox calls this with its own
    shapes)."""
    return min(TM, m), min(TK, k), min(TN, n)


def grouped_matmul(lhs, rhs, sizes, *, interpret: Optional[bool] = None):
    """lhs (m, k) rows sorted by group, rhs (G, k, n), sizes (G,) int ->
    (m, n) in ``lhs.dtype``; differentiable in ``lhs`` and ``rhs``. Rows
    are padded up to a whole row tile and the padding sliced off again."""
    m = lhs.shape[0]
    tm = min(TM, -(-m // 16) * 16)
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = megablox.gmm(lhs, rhs.astype(lhs.dtype), sizes.astype(jnp.int32),
                       lhs.dtype, tiling, None, None, False,
                       resolve_interpret(interpret))
    return out[:m] if pad else out
