"""Pallas ring-step kernel: the fused add-and-shift inner loop of the ring
schedules (paper §III-C engineering, TPU form).

One reduce-scatter step folds the partial sum received from the ring
neighbour into the local chunk ``k``:  ``acc = recv + chunks[k]``. The jnp
form materializes ``chunks[k]`` (a dynamic gather) in HBM before the add;
this kernel instead streams both operands through VMEM once, with the
(traced) chunk index ``k`` scalar-prefetched so it drives the input block
index_map directly — the same prefetch idiom as
``repro.kernels.batched_norm``.

Layout contract (enforced by the ring schedules via ``pad_to=CHUNK``):
  chunks : (n, c) with c % CHUNK == 0  — zero-padded chunk rows
  recv   : (c,)                        — partial sum from the neighbour
  k      : int32                       — which local chunk to fold in
Grid: one program per (SUB, LANE) tile of the chunk.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bucketing import CHUNK
from repro.kernels.backend import resolve_interpret

SUB = 8
LANE = 128
assert CHUNK == SUB * LANE


def _kernel(k_ref, recv_ref, chunk_ref, out_ref):
    del k_ref  # only consumed by the index_map
    out_ref[...] = recv_ref[...] + chunk_ref[...]


def ring_add_step(recv, chunks, k, *, interpret: bool = None):
    """``recv + chunks[k]`` as one fused VMEM pass. See module docstring."""
    n, c = chunks.shape
    assert c % CHUNK == 0 and recv.shape == (c,), (chunks.shape, recv.shape)
    tiles = c // CHUNK
    recv2 = recv.reshape(tiles * SUB, LANE)
    chunks2 = chunks.reshape(n * tiles * SUB, LANE)
    k_arr = jnp.asarray(k, jnp.int32).reshape(1)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tiles,),
            in_specs=[
                pl.BlockSpec((SUB, LANE), lambda i, k: (i, 0)),
                pl.BlockSpec((SUB, LANE), lambda i, k: (k[0] * tiles + i, 0)),
            ],
            out_specs=pl.BlockSpec((SUB, LANE), lambda i, k: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((tiles * SUB, LANE), recv.dtype),
        interpret=resolve_interpret(interpret),
    )(k_arr, recv2, chunks2)
    return out.reshape(c)


def kernel_step_fn(interpret: bool = None):
    """Adapter matching ``primitives.default_step_fn``'s signature."""
    return lambda recv, chunks, k: ring_add_step(recv, chunks, k,
                                                 interpret=interpret)
