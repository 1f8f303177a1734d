"""Optimizers: momentum-SGD (the paper's base solver and comparison
baseline) and LARS [You et al., arXiv:1708.03888] — the paper's §III-A.1
layer-wise adaptive rate scaling.

LARS per tensor w with gradient g:
    trust = η · ||w|| / (||g|| + wd·||w|| + ε)
    v    ← μ·v + lr·trust·(g + wd·w)
    w    ← w − v
1-D tensors (biases, norm scales) and the classifier head are excluded from
trust scaling, as in the paper/MLPerf reference.

Per-tensor norms are computed either the plain-jnp way or via the
``batched_norm`` Pallas kernel (paper §III-B.2) over the bucket-packed
buffer — selected with ``use_kernel``.

``sharded_update_from_shards`` is the ZeRO-1 path (docs/comm.md §Sharded
update): trust ratios come from psum'd per-tensor *partial* norms over
each device's bucket shard, and the packed update runs on the local 1/n
persistent master shard only (``TrainState.shards``) — through the fused
``kernels/lars_update`` Pallas kernel or its packed-jnp oracle — so
optimizer FLOPs, fp32 optimizer-state memory, and every update stream
shrink by the shard count.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class OptConfig:
    kind: str = "lars"            # lars | sgdm | lamb
    momentum: float = 0.9         # beta1 for lamb
    beta2: float = 0.999          # lamb second-moment decay
    weight_decay: float = 5e-5
    trust_coef: float = 0.001     # η (lars); lamb uses ratio directly
    eps: float = 1e-9
    nesterov: bool = False
    use_kernel: bool = False      # batched-norm Pallas kernel for the norms


def init_momentum(params, kind: str = "lars"):
    zeros = lambda: jax.tree.map(
        lambda p: jnp.zeros_like(p, jnp.float32), params)
    if kind == "lamb":
        # LAMB carries Adam's two moments; packed into one pytree so the
        # TrainState shape is optimizer-agnostic
        return {"m": zeros(), "v": zeros(), "count": jnp.zeros((), jnp.int32)}
    return zeros()


def _is_scaled(p) -> bool:
    """Trust-ratio scaling applies to >=2-D tensors only."""
    return p.ndim >= 2


def tensor_norms(tree):
    """Per-tensor L2 norms, plain jnp (the per-layer baseline the paper's
    batched kernel replaces)."""
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def _batched_norms(params, grads, cfg):
    """All per-tensor norms in one pass (kernel or packed-jnp path)."""
    if cfg.use_kernel:
        from repro.kernels import ops
        return (ops.tree_norms(params), ops.tree_norms(grads))
    return tensor_norms(params), tensor_norms(grads)


def update(params, grads, mom, lr, cfg: OptConfig):
    """One optimizer step (all fp32; caller owns mixed-precision casts).
    Returns (new_params, new_mom)."""
    if cfg.kind == "sgdm":
        def upd(p, g, v):
            g = g.astype(jnp.float32) + cfg.weight_decay * p
            v2 = cfg.momentum * v + lr * g
            step = (cfg.momentum * v2 + lr * g) if cfg.nesterov else v2
            return p - step, v2
        out = jax.tree.map(upd, params, grads, mom)
    elif cfg.kind == "lars":
        wn, gn = _batched_norms(params, grads, cfg)

        def upd(p, g, v, pw, gw):
            g = g.astype(jnp.float32)
            if _is_scaled(p):
                trust = cfg.trust_coef * pw / (gw + cfg.weight_decay * pw
                                               + cfg.eps)
                trust = jnp.where(pw > 0, trust, 1.0)
            else:
                trust = 1.0
            g = g + cfg.weight_decay * p
            v2 = cfg.momentum * v + (lr * trust) * g
            return p - v2, v2
        out = jax.tree.map(upd, params, grads, mom, wn, gn)
    elif cfg.kind == "lamb":
        # You et al. 2020 (LAMB): Adam statistics + per-tensor trust ratio
        # ||w|| / ||update||. The paper's LARS lineage, known to work
        # better for the transformer pool (DESIGN.md §3).
        t = mom["count"] + 1
        b1, b2 = cfg.momentum, cfg.beta2

        def moments(g, m, v):
            g = g.astype(jnp.float32)
            m2 = b1 * m + (1 - b1) * g
            v2 = b2 * v + (1 - b2) * g * g
            return m2, v2

        mv = jax.tree.map(moments, grads, mom["m"], mom["v"])
        new_m = jax.tree.map(lambda x: x[0], mv,
                             is_leaf=lambda x: isinstance(x, tuple))
        new_v = jax.tree.map(lambda x: x[1], mv,
                             is_leaf=lambda x: isinstance(x, tuple))
        c1 = 1 - b1 ** t.astype(jnp.float32)
        c2 = 1 - b2 ** t.astype(jnp.float32)

        def upd(p, m, v):
            u = (m / c1) / (jnp.sqrt(v / c2) + cfg.eps)
            u = u + cfg.weight_decay * p
            if _is_scaled(p):
                wn = jnp.sqrt(jnp.sum(jnp.square(p)))
                un = jnp.sqrt(jnp.sum(jnp.square(u)))
                ratio = jnp.where((wn > 0) & (un > 0), wn / un, 1.0)
            else:
                ratio = 1.0
            return p - lr * ratio * u

        new_params = jax.tree.map(upd, params, new_m, new_v)
        return new_params, {"m": new_m, "v": new_v, "count": t}
    else:
        raise ValueError(cfg.kind)
    new_params = jax.tree.map(lambda t: t[0], out,
                              is_leaf=lambda x: isinstance(x, tuple))
    new_mom = jax.tree.map(lambda t: t[1], out,
                           is_leaf=lambda x: isinstance(x, tuple))
    return new_params, new_mom


# --------------------------------------------------------------------------
# ZeRO-1 sharded update (explicit-DP path; see core/ddp.py + docs/comm.md)

def shard_trust_ratios(param_shards, grad_shards, segs, plan, cfg: OptConfig,
                       *, shard_axis):
    """Per-tensor LARS trust ratios from psum'd partial norms.

    Each device holds one contiguous shard per bucket; a tensor's squared
    norm is the psum (over the shard axis) of each shard's per-CHUNK
    partial sums, routed to the tensor via the shard-aware segment map —
    no device ever touches a full gradient. Split-leaf plans need no
    special casing: the segment maps key on ``plan.slot_tensor_ids``, so a
    tensor's spans (even across buckets) accumulate into one segment
    before the psum. Returns a ``(n_tensors,)`` f32 trust vector indexed
    by tensor id (1.0 for <2-D tensors and for sgdm, matching ``update``'s
    per-tensor rules)."""
    from repro.core import bucketing
    from repro.kernels.ref import batched_sumsq
    if cfg.kind != "lars":
        return jnp.ones((plan.n_tensors,), jnp.float32)
    w_sq = jnp.zeros((plan.n_tensors,), jnp.float32)
    g_sq = jnp.zeros((plan.n_tensors,), jnp.float32)
    for p_s, g_s, seg in zip(param_shards, grad_shards, segs):
        w_sq = w_sq + batched_sumsq(p_s, seg, plan.n_tensors)
        g_sq = g_sq + batched_sumsq(g_s, seg, plan.n_tensors)
    w_sq = jax.lax.psum(w_sq, shard_axis)
    g_sq = jax.lax.psum(g_sq, shard_axis)
    wn, gn = jnp.sqrt(w_sq), jnp.sqrt(g_sq)
    raw = cfg.trust_coef * wn / (gn + cfg.weight_decay * wn + cfg.eps)
    scaled = jnp.asarray(bucketing.trust_scaled_mask(plan))
    return jnp.where(scaled & (wn > 0), raw, 1.0)


def sharded_update_from_shards(p_shards, grad_shards, mom_shards, lr,
                               cfg: OptConfig, plan, *, shard_axis,
                               n_shards: int, update_kernel: bool = False,
                               interpret: bool = None):
    """One ZeRO-1 optimizer step on this device's PERSISTENT bucket shards
    (must run inside shard_map).

    ``p_shards``/``grad_shards``/``mom_shards``: per-bucket local fp32
    buffers of ``bucketing.shard_elems`` length — the persistent master
    shards carried in ``TrainState.shards``, the reduce-scatter output,
    and the sharded momentum leaves. Every stream here is O(N/n): unlike
    the transitional PR-4 path, no repack of the full masters happens, so
    the reference implementation now matches what
    ``comm.cost.lars_update_time_s`` prices. Returns ``(param_shards,
    mom_shards)`` — the caller persists both and all-gathers the params
    when the next forward needs them (``ddp.gather_ahead_params``)."""
    from repro.comm.primitives import shard_index
    from repro.core import bucketing
    assert cfg.kind in ("lars", "sgdm"), \
        f"sharded_update supports lars/sgdm, not {cfg.kind!r}"
    assert not cfg.nesterov, "nesterov momentum unsupported on shards"
    k = shard_index(shard_axis)
    seg_maps = bucketing.shard_segment_ids(plan, n_shards)
    segs = [jnp.take(jnp.asarray(m), k, axis=0) for m in seg_maps]
    trust = shard_trust_ratios(p_shards, grad_shards, segs, plan, cfg,
                               shard_axis=shard_axis)
    if update_kernel:
        from repro.kernels.lars_update import lars_packed_update
        upd = lambda *a, **kw: lars_packed_update(*a, interpret=interpret,
                                                  **kw)
    else:
        from repro.kernels.ref import lars_packed_update as upd
    new_p, new_m = [], []
    for p_s, g_s, m_s, seg in zip(p_shards, grad_shards, mom_shards, segs):
        p2, m2 = upd(p_s, g_s, m_s, trust, seg, lr=lr,
                     momentum=cfg.momentum, wd=cfg.weight_decay)
        new_p.append(p2)
        new_m.append(m2)
    return tuple(new_p), tuple(new_m)
