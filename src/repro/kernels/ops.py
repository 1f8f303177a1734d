"""Jit'd public wrappers around the Pallas kernels.

``interpret=None`` (the default) compiles the kernel on a TPU backend and
interprets it elsewhere (each kernel resolves it through
``backend.resolve_interpret``). Each wrapper has the identical signature
pure-jnp oracle in ``repro.kernels.ref``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import bucketing
from repro.kernels import batched_norm as _bn
from repro.kernels import flash_attention as _fa
from repro.kernels import lars_update as _lu
from repro.kernels import smoothed_xent as _sx


@functools.partial(jax.jit, static_argnames=("n_tensors", "interpret"))
def batched_sumsq(flat, seg_ids, n_tensors: int,
                  interpret: Optional[bool] = None):
    return _bn.batched_sumsq(flat, seg_ids, n_tensors, interpret=interpret)


def tree_norms(tree, *, plan=None, interpret: Optional[bool] = None):
    """Per-tensor L2 norms of a pytree via ONE batched-norm kernel launch
    (paper §III-B.2). Returns a pytree of scalars matching ``tree``."""
    if plan is None:
        plan = bucketing.make_plan(tree)
    bufs = bucketing.pack(tree, plan, dtype=jnp.float32)
    flat = bucketing.concat_buckets(bufs)
    seg = jnp.asarray(bucketing.segment_ids(plan))
    sumsq = batched_sumsq(flat, seg, plan.n_tensors, interpret=interpret)
    norms = jnp.sqrt(sumsq)
    # scatter the scalars back into tree structure (packing order is the
    # reverse flatten order)
    leaves = list(norms)
    leaves.reverse()
    return jax.tree_util.tree_unflatten(plan.treedef, leaves)


@functools.partial(jax.jit,
                   static_argnames=("lr", "momentum", "wd", "interpret"))
def lars_packed_update(p, g, m, trust, seg_ids, *, lr, momentum, wd,
                       interpret: Optional[bool] = None):
    return _lu.lars_packed_update(p, g, m, trust, seg_ids, lr=lr,
                                  momentum=momentum, wd=wd,
                                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("smoothing", "interpret"))
def smoothed_xent_rows(logits, labels, smoothing: float = 0.1,
                       interpret: Optional[bool] = None):
    return _sx.smoothed_xent_rows(logits, labels, smoothing=smoothing,
                                  interpret=interpret)


def flash_attention_bshd(q, k, v, *, causal=True, window=0,
                         interpret: Optional[bool] = None):
    """(B,S,H,Dk)/(B,S,K,D*) layout wrapper around the flash kernel."""
    B, Sq, H, Dk = q.shape
    K, Dv = k.shape[2], v.shape[-1]
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, Dk)
    kf = k.transpose(0, 2, 1, 3).reshape(B * K, k.shape[1], Dk)
    vf = v.transpose(0, 2, 1, 3).reshape(B * K, v.shape[1], Dv)
    o = _fa.flash_attention(qf, kf, vf, causal=causal, window=window,
                            n_q_heads=H, n_kv_heads=K,
                            interpret=interpret)
    return o.reshape(B, H, Sq, Dv).transpose(0, 2, 1, 3)
