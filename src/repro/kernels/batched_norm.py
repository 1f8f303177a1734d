"""Batched multi-tensor squared-norm Pallas kernel (paper §III-B.2).

GPU motivation: one small tensor cannot occupy the CUDA cores, so the paper
batches all layers' norm computations into one kernel launch. TPU
adaptation (DESIGN.md §2): many tiny HLO reduces each pay an HBM round trip
and launch overhead; here ONE kernel streams the bucket-packed parameter
buffer through VMEM once, 8×128-aligned, and accumulates each tensor's
partial sums into its output row as the (sequential) grid walks the chunks.

Layout (produced by ``repro.core.bucketing``):
  flat     : (n_chunks * CHUNK,)  — tensors flattened, zero-padded to CHUNK
  seg_ids  : (n_chunks,) int32    — which tensor each chunk belongs to
                                     (scalar-prefetched: it drives the output
                                     block index_map)
  out      : (n_tensors * 8, 128) f32 — one (8, 128) tile per tensor,
                                     every element of which holds its sum
                                     of squares (a tile is the smallest
                                     block the TPU stores unmasked)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bucketing import CHUNK
from repro.kernels.backend import resolve_interpret

SUB = 8
LANE = 128
assert CHUNK == SUB * LANE


def _kernel(seg_ref, x_ref, out_ref):
    i = pl.program_id(0)
    first = jnp.logical_or(i == 0, seg_ref[i] != seg_ref[jnp.maximum(i - 1, 0)])
    x = x_ref[...].astype(jnp.float32)
    s = jnp.sum(x * x)

    @pl.when(first)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += s


def batched_sumsq(flat, seg_ids, n_tensors: int, *,
                  interpret: Optional[bool] = None):
    """See module docstring. Returns (n_tensors,) f32."""
    n_chunks = seg_ids.shape[0]
    assert flat.size == n_chunks * CHUNK
    x = flat.reshape(n_chunks * SUB, LANE)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_chunks,),
            in_specs=[pl.BlockSpec((SUB, LANE), lambda i, seg: (i, 0))],
            out_specs=pl.BlockSpec((SUB, LANE), lambda i, seg: (seg[i], 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_tensors * SUB, LANE), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(seg_ids, x)
    return out[::SUB, 0]
