"""Flash attention (Pallas TPU): fused QK^T → online-softmax → PV with
VMEM-resident running (m, l, acc) — none of the score-sized intermediates
that dominate the §Roofline memory term of the pure-JAX chunked attention
ever touch HBM.

Layout: q (BH, Sq, Dk), k/v (BK, Sk, Dk/Dv) with BH = B·H and BK = B·K
(GQA: the kv block index map folds the head-group mapping, so no kv
replication is materialized). Grid (BH, nQ, nK), kv innermost; per-(bh,i)
scratch carries the online-softmax state across kv blocks. Causal/window
masking is applied inside the kernel; fully-visible blocks skip the mask
(same optimization as the jnp path's §Perf-1 H4).

The kernel name encodes causality ("flash_attention_causal") so the HLO
cost walker can count its FLOPs analytically from the custom-call shapes.
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


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            bq: int, bk: int, nk: int, causal: bool, window: int,
            scale: float):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = i * bq
    k_lo = j * bk

    def do_block():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, Dk)
        k = k_ref[0].astype(jnp.float32)                  # (bk, Dk)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        need_mask = False
        if causal:
            need_mask = True
            mask = kpos <= qpos
        if window:
            wmask = kpos > qpos - window
            mask = jnp.logical_and(mask, wmask) if need_mask else wmask
            need_mask = True
        if need_mask:
            s = jnp.where(mask, s, NEG)
        m_old = m_ref[...]                                # (bq, 1)
        m_new = jnp.maximum(m_old, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_old - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        m_ref[...] = m_new
        v = v_ref[0].astype(jnp.float32)                  # (bk, Dv)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))

    # skip kv blocks entirely outside the causal/window range
    if causal or window:
        visible = jnp.bool_(True)
        if causal:
            visible = k_lo <= q_lo + bq - 1
        if window:
            visible = jnp.logical_and(visible,
                                      k_lo + bk - 1 > q_lo - window)
        pl.when(visible)(do_block)
    else:
        do_block()

    @pl.when(j == nk - 1)
    def _():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    n_q_heads: int = None, n_kv_heads: int = None,
                    bq: int = 512, bk: int = 512,
                    interpret: Optional[bool] = None):
    """q: (BH, Sq, Dk); k/v: (BK, Sk, Dk/Dv) with BH = B*H, BK = B*K.
    Returns (BH, Sq, Dv)."""
    BH, Sq, Dk = q.shape
    BK, Sk, Dv = v.shape
    H = n_q_heads or BH
    K = n_kv_heads or BK
    G = H // K
    assert BH % H == 0 and (BH // H) * K == BK

    def _fit(s, c):
        c = min(c, s)
        while s % c:
            c -= 1
        return c

    bq = _fit(Sq, bq)
    bk = _fit(Sk, bk)
    nq, nk = Sq // bq, Sk // bk

    def kv_head(bh):
        b, h = bh // H, bh % H
        return b * K + h // G

    name = "flash_attention" + ("_causal" if causal else "") \
        + (f"_win{window}" if window else "")
    kern = functools.partial(_kernel, bq=bq, bk=bk, nk=nk, causal=causal,
                             window=window, scale=Dk ** -0.5)
    return pl.pallas_call(
        kern,
        name=name,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, Dk), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, Dk), lambda bh, i, j: (kv_head(bh), j, 0)),
            pl.BlockSpec((1, bk, Dv), lambda bh, i, j: (kv_head(bh), j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dv), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, Dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, Dv), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
