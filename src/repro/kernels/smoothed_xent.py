"""Label-smoothed cross-entropy Pallas kernel (online logsumexp over vocab).

The LLM configs in the pool have vocabularies up to 152k: materializing
softmax intermediates for (tokens × vocab) dominates loss-layer HBM traffic.
This kernel streams the logits row-block through VMEM once per vocab tile,
keeping running (max, sumexp, target-logit, mean) statistics in f32 VMEM
scratch, and emits the per-row smoothed NLL on the last tile — the fused
TPU analogue of what the paper's framework-level fusions do for small ops.

Grid: (cdiv(T, bT), cdiv(V, bV)), vocab innermost (sequential on TPU).
``bV`` is the whole vocab when it fits one tile, else a multiple of 128
(the TPU's lane width); the columns of the last tile past V are masked out
of every statistic, and rows past T are computed and dropped.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

NEG = -1e30


def _kernel(lab_ref, x_ref, out_ref, m_ref, l_ref, t_ref, s_ref, *,
            bV: int, nV: int, V: int, smoothing: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        t_ref[...] = jnp.zeros_like(t_ref)
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[...].astype(jnp.float32)              # (bT, bV)
    labels = lab_ref[...]                           # (bT, 1) int32
    cols = j * bV + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = cols < V
    m_old = m_ref[...]                              # (bT, 1)
    m_new = jnp.maximum(m_old, jnp.where(valid, x, NEG).max(
        axis=1, keepdims=True))
    corr = jnp.exp(m_old - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.where(
        valid, jnp.exp(x - m_new), 0.0).sum(axis=1, keepdims=True)
    m_ref[...] = m_new
    hit = (cols == labels)
    t_ref[...] += jnp.where(hit, x, 0.0).sum(axis=1, keepdims=True)
    s_ref[...] += jnp.where(valid, x, 0.0).sum(axis=1, keepdims=True)

    @pl.when(j == nV - 1)
    def _():
        lse = m_ref[...] + jnp.log(l_ref[...])
        nll = lse - ((1.0 - smoothing) * t_ref[...]
                     + smoothing * s_ref[...] / V)
        out_ref[...] = nll


def smoothed_xent_rows(logits, labels, *, smoothing: float = 0.1,
                       bT: int = 256, bV: int = 2048,
                       interpret: Optional[bool] = None):
    """logits: (T, V); labels: (T,) int32 in [0, V). Returns (T,) f32.
    ``bT`` must be a multiple of 8 and ``bV`` of 128 (see module
    docstring)."""
    T, V = logits.shape
    bT = T if T <= bT else bT
    bV = V if V <= bV else bV
    nT, nV = pl.cdiv(T, bT), pl.cdiv(V, bV)
    out = pl.pallas_call(
        functools.partial(_kernel, bV=bV, nV=nV, V=V, smoothing=smoothing),
        grid=(nT, nV),
        in_specs=[
            pl.BlockSpec((bT, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bT, bV), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bT, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bT, 1), jnp.float32)] * 4,
        interpret=resolve_interpret(interpret),
    )(labels[:, None].astype(jnp.int32), logits)
    return out[:, 0]
