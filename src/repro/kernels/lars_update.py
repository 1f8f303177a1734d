"""Fused LARS weight-update Pallas kernel.

Companion to ``batched_norm``: once per-tensor trust ratios are known, the
whole update (wd add, momentum, scaled step) runs as one kernel over the
bucket-packed fp32 master buffers — one HBM read/write per operand instead
of per-tensor op streams. The per-tensor trust ratio rides in as one
(8, 128) tile per tensor whose block index is driven by the scalar-prefetched
segment map (same trick as batched_norm's output); lr, momentum and weight
decay ride in SMEM, since lr is traced (it follows the schedule).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret
from repro.kernels.batched_norm import LANE, SUB


def _kernel(seg_ref, p_ref, g_ref, m_ref, t_ref, hp_ref,
            p_out, m_out):
    lr, mu, wd = hp_ref[0], hp_ref[1], hp_ref[2]
    p = p_ref[...]
    g = g_ref[...].astype(jnp.float32) + wd * p
    m2 = mu * m_ref[...] + (lr * t_ref[...]) * g
    p_out[...] = p - m2
    m_out[...] = m2


def lars_packed_update(p, g, m, trust, seg_ids, *, lr, momentum, wd,
                       interpret: Optional[bool] = None):
    """p/g/m: (n_chunks*CHUNK,) f32 packed; trust: (n_tensors,) f32.
    Returns (new_p, new_m) with the same packing."""
    n_chunks = seg_ids.shape[0]
    shape2d = (n_chunks * SUB, LANE)
    n_tensors = trust.shape[0]
    t2 = jnp.broadcast_to(trust.astype(jnp.float32)[:, None, None],
                          (n_tensors, SUB, LANE)).reshape(-1, LANE)
    hp = jnp.stack([jnp.asarray(x, jnp.float32) for x in (lr, momentum, wd)])
    blk = pl.BlockSpec((SUB, LANE), lambda i, seg: (i, 0))
    tblk = pl.BlockSpec((SUB, LANE), lambda i, seg: (seg[i], 0))
    hblk = pl.BlockSpec(memory_space=pltpu.SMEM)
    p2, m2 = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_chunks,),
            in_specs=[blk, blk, blk, tblk, hblk],
            out_specs=[blk, blk],
        ),
        out_shape=[jax.ShapeDtypeStruct(shape2d, jnp.float32),
                   jax.ShapeDtypeStruct(shape2d, jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(seg_ids, p.reshape(shape2d), g.reshape(shape2d), m.reshape(shape2d),
      t2, hp)
    return p2.reshape(-1), m2.reshape(-1)
