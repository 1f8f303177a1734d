"""Attention: GQA (+QKV bias, qk_norm, sliding window), MLA, KV caches.

Train/prefill use a chunked online-softmax implementation (no S×S score
tensor): a static python loop over query chunks, `lax.scan` over only the
key chunks a causal/windowed query chunk can see (true block skipping, so
HLO FLOPs reflect the causal halving).

Decode is a single-token step against a (B, S_max, ...) cache updated with
`dynamic_update_slice`. MLA decodes in the *absorbed* form, caching only the
512-d latent + rope key (DeepSeek-V2's contribution).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.compat import shard_map
from repro.models.common import PD, constrain, dense_pd, dp_axes, \
    rms_norm, rope, yarn_mscale

NEG_INF = -1e30


def _attend(q, k, v, cfg, mesh, *, causal: bool, window: int = 0):
    """Dispatch: Pallas flash kernel (cfg.flash_attention) or the pure-JAX
    chunked online-softmax path. The flash path runs inside shard_map so
    each device launches one kernel over its local (batch, head) slice."""
    if cfg.flash_attention:
        from repro.kernels.ops import flash_attention_bshd
        tp = mesh.shape.get("model", 1) if mesh is not None else 1
        if mesh is None or tp == 1:
            return flash_attention_bshd(q, k, v, causal=causal,
                                        window=window)
        if cfg.n_heads % tp == 0 and cfg.n_kv_heads % tp == 0:
            dp = dp_axes(mesh)
            from jax.sharding import PartitionSpec as P
            spec = P(dp, None, "model", None)
            fn = lambda ql, kl, vl: flash_attention_bshd(
                ql, kl, vl, causal=causal, window=window)
            return shard_map(fn, mesh=mesh,
                             in_specs=(spec, spec, spec),
                             out_specs=spec)(q, k, v)
        # uneven heads: fall through to the chunked path
    return chunked_attention(q, k, v, q_offset=0, causal=causal,
                             window=window, chunk=cfg.attn_chunk)


# ---------------------------------------------------------------------------
# parameter descriptors


def gqa_pd(cfg):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, K = cfg.n_heads, cfg.n_kv_heads
    dp = "data" if cfg.fsdp else None
    p = {
        "wq": dense_pd(d, H * hd, spec=P(dp, "model")),
        "wk": dense_pd(d, K * hd, spec=P(dp, "model")),
        "wv": dense_pd(d, K * hd, spec=P(dp, "model")),
        "wo": dense_pd(H * hd, d, spec=P("model", dp),
                       scale=(H * hd) ** -0.5 / math.sqrt(2 * max(cfg.n_layers, 1))),
    }
    if cfg.qkv_bias:
        p["bq"] = PD((H * hd,), spec=P("model"), init="zeros")
        p["bk"] = PD((K * hd,), spec=P("model"), init="zeros")
        p["bv"] = PD((K * hd,), spec=P("model"), init="zeros")
    if cfg.qk_norm:
        p["q_norm"] = PD((hd,), init="ones")
        p["k_norm"] = PD((hd,), init="ones")
    return p


def mla_pd(cfg):
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    dp = "data" if cfg.fsdp else None
    qd = m.nope_head_dim + m.rope_head_dim
    return {
        "wq": dense_pd(d, H * qd, spec=P(dp, "model")),
        "wkv_a": dense_pd(d, m.kv_lora_rank + m.rope_head_dim, spec=P(dp, None)),
        "ckv_norm": PD((m.kv_lora_rank,), init="ones"),
        "wk_b": dense_pd(m.kv_lora_rank, H * m.nope_head_dim, spec=P(dp, "model")),
        "wv_b": dense_pd(m.kv_lora_rank, H * m.v_head_dim, spec=P(dp, "model")),
        "wo": dense_pd(H * m.v_head_dim, d, spec=P("model", dp),
                       scale=(H * m.v_head_dim) ** -0.5 / math.sqrt(2 * cfg.n_layers)),
    }


# ---------------------------------------------------------------------------
# chunked online-softmax attention (parallel form)


def chunked_attention(q, k, v, *, q_offset, causal: bool, window: int = 0,
                      chunk: int = 1024):
    """q: (B,Sq,H,Dh) k,v: (B,Sk,K,Dh) with H = K*G. Positions of q are
    q_offset + arange(Sq); k positions are arange(Sk). Returns (B,Sq,H,Dh)."""
    B, Sq, H, Dh = q.shape
    Sk, K = k.shape[1], k.shape[2]
    Dv = v.shape[-1]                  # MLA: value head dim != qk head dim
    G = H // K
    scale = Dh ** -0.5

    def _fit(s, c):                   # largest divisor of s that is <= c
        c = min(c, s)
        while s % c:
            c -= 1
        return c

    cq, ck = _fit(Sq, chunk), _fit(Sk, chunk)
    nq, nk = Sq // cq, Sk // ck

    qr = q.reshape(B, nq, cq, K, G, Dh)
    # (nk, B, ck, K, Dh): the scan over a query chunk's visible key blocks
    # reads block j in place
    kr = jnp.moveaxis(k.reshape(B, nk, ck, K, Dh), 1, 0)
    vr = jnp.moveaxis(v.reshape(B, nk, ck, K, Dv), 1, 0)

    outs = []
    for i in range(nq):  # static python loop -> true causal block skipping
        qi = qr[:, i] * jnp.asarray(scale, q.dtype)
        qpos = q_offset + i * cq + jnp.arange(cq)
        if causal:
            hi = min(nk, -(-(q_offset + (i + 1) * cq) // ck))
        else:
            hi = nk
        lo = 0
        if window:
            lo = max(0, (q_offset + i * cq - window) // ck)
        hi = max(hi, lo + 1)
        # blocks strictly below the causal diagonal and strictly inside the
        # window need NO mask: skipping the (cq,ck) select there removes
        # most score-sized mask traffic (§Perf-1 H4)
        full_hi = hi
        if causal:
            full_hi = min(hi, (q_offset + i * cq) // ck)
        full_lo = lo
        if window:
            first_fully_inside = -(-(q_offset + i * cq + 1 - window) // ck)
            full_lo = max(lo, max(first_fully_inside, 0))
        full_lo = min(full_lo, full_hi)

        def body(masked):
            def run(carry, j):
                m, l, acc = carry
                kj = jax.lax.dynamic_index_in_dim(kr, j, keepdims=False)
                vj = jax.lax.dynamic_index_in_dim(vr, j, keepdims=False)
                s = jnp.einsum("bqkgd,bckd->bkgqc", qi, kj,
                               preferred_element_type=jnp.float32)
                if masked:
                    kpos = j * ck + jnp.arange(ck)
                    mask = jnp.ones((cq, ck), bool)
                    if causal:
                        mask &= kpos[None, :] <= qpos[:, None]
                    if window:
                        mask &= kpos[None, :] > (qpos[:, None] - window)
                    s = jnp.where(mask[None, None, None], s, NEG_INF)
                m_new = jnp.maximum(m, s.max(-1))
                p = jnp.exp(s - m_new[..., None])
                corr = jnp.exp(m - m_new)
                l = l * corr + p.sum(-1)
                acc = acc * corr[..., None] + jnp.einsum(
                    "bkgqc,bckd->bkgqd", p.astype(vj.dtype), vj,
                    preferred_element_type=jnp.float32)
                return (m_new, l, acc), None
            return run

        m0 = jnp.full((B, K, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, cq), jnp.float32)
        a0 = jnp.zeros((B, K, G, cq, Dv), jnp.float32)
        carry = (m0, l0, a0)
        for mlo, mhi, masked in ((lo, full_lo, True),
                                 (full_lo, full_hi, False),
                                 (full_hi, hi, True)):
            if mhi <= mlo:
                continue
            js = jnp.arange(mlo, mhi)
            # each key block's scores are recomputed in the backward pass
            # (flash attention's trade): the scan keeps its carries, never
            # a (cq, ck) probability block per step
            carry, _ = jax.lax.scan(jax.checkpoint(body(masked)), carry, js)
        m, l, acc = carry
        oi = acc / jnp.maximum(l, 1e-30)[..., None]
        outs.append(jnp.moveaxis(oi, 3, 1))        # (B,cq,K,G,Dh)
    out = jnp.concatenate(outs, axis=1) if nq > 1 else outs[0]
    return out.reshape(B, Sq, H, Dv).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0):
    """q: (B,1,H,Dh); caches: (B,Smax,K,Dh); pos: scalar index of the new
    token (its k/v must already be written into the cache)."""
    B, _, H, Dh = q.shape
    K = k_cache.shape[2]
    G = H // K
    qh = q.reshape(B, K, G, Dh) * jnp.asarray(Dh ** -0.5, q.dtype)
    s = jnp.einsum("bkgd,bskd->bkgs", qh, k_cache,
                   preferred_element_type=jnp.float32)
    kpos = jnp.arange(k_cache.shape[1])
    mask = kpos <= pos
    if window:
        mask &= kpos > (pos - window)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, H * Dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA block


def _split_heads(x, n, hd):
    return x.reshape(*x.shape[:-1], n, hd)


def gqa_parallel(p, x, positions, cfg, *, cache_len: int = 0,
                 cross_x: Optional[jax.Array] = None, mesh=None):
    """Train/prefill attention. Returns (out, cache|None); cache holds k/v
    written into a (B, cache_len, K, Dh) buffer when cache_len > 0.
    cross_x: encoder states for cross-attention (keys/values source)."""
    hd = cfg.resolved_head_dim
    B, S = x.shape[:2]
    kv_src = cross_x if cross_x is not None else x
    q = x @ p["wq"]
    k = kv_src @ p["wk"]
    v = kv_src @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = _split_heads(q, cfg.n_heads, hd)
    k = _split_heads(k, cfg.n_kv_heads, hd)
    v = _split_heads(v, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    if cfg.rope_theta and cross_x is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    tp = mesh.shape.get("model", 1) if mesh is not None else 1
    pin = (cfg.n_kv_heads % tp == 0) or (cfg.n_kv_heads == cfg.n_heads)
    if mesh is not None and pin:
        # pin head-parallel attention: without this GSPMD picks either a
        # contraction-sharded score einsum (per-chunk all-reduce of scores,
        # §Perf-1 H1) or head replication (score tensors blow up, §Perf-3).
        # MHA with uneven heads pads (40->48: measured -18% dominant);
        # but GQA with kv < tp (8/16) measured 5-6x WORSE when pinned
        # (§Perf sweep) — those fall through to GSPMD's choice.
        dp = dp_axes(mesh)
        from jax.sharding import PartitionSpec as P
        q = constrain(q, mesh, P(dp, None, "model", None))
        k = constrain(k, mesh, P(dp, None, "model", None))
        v = constrain(v, mesh, P(dp, None, "model", None))
    causal = cross_x is None
    o = _attend(q, k, v, cfg, mesh, causal=causal,
                window=cfg.sliding_window)
    out = o.reshape(B, S, cfg.n_heads * hd) @ p["wo"]
    cache = None
    if cache_len:
        K = cfg.n_kv_heads
        kc = jnp.zeros((B, cache_len, K, hd), k.dtype)
        vc = jnp.zeros((B, cache_len, K, hd), v.dtype)
        kc = jax.lax.dynamic_update_slice(kc, k, (0, 0, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v, (0, 0, 0, 0))
        cache = {"k": kc, "v": vc}
    return out, cache


def gqa_decode_inplace(p, x, pos, cfg, ck_all, cv_all, layer):
    """Unrolled-serving decode: ck_all/cv_all are the full stacked
    (L,B,Smax,K,Dh) caches (donated by the caller); writes the ONE new
    token in place and attends over this layer's slice. Avoids the
    full-slice copy-through that a scan-carried cache pays (§Perf-2 H1)."""
    hd = cfg.resolved_head_dim
    B = x.shape[0]
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = _split_heads(q, cfg.n_heads, hd)
    k = _split_heads(k, cfg.n_kv_heads, hd)
    v = _split_heads(v, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    if cfg.rope_theta:
        pp = jnp.full((B, 1), pos, jnp.int32)
        q = rope(q, pp, cfg.rope_theta)
        k = rope(k, pp, cfg.rope_theta)
    ck_all = jax.lax.dynamic_update_slice(ck_all, k[None],
                                          (layer, 0, pos, 0, 0))
    cv_all = jax.lax.dynamic_update_slice(cv_all, v[None],
                                          (layer, 0, pos, 0, 0))
    kc = jax.lax.dynamic_index_in_dim(ck_all, layer, 0, keepdims=False)
    vc = jax.lax.dynamic_index_in_dim(cv_all, layer, 0, keepdims=False)
    o = decode_attention(q, kc, vc, pos, window=cfg.sliding_window)
    return o @ p["wo"], ck_all, cv_all


def mla_decode_inplace(p, x, pos, cfg, ckv_all, kr_all, layer):
    """Absorbed MLA decode against the stacked latent cache, in place."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    kv_a = x @ p["wkv_a"]
    ckv_new = rms_norm(kv_a[..., :m.kv_lora_rank], p["ckv_norm"], cfg.rms_eps)
    kr_new = kv_a[..., m.kv_lora_rank:].reshape(B, 1, 1, m.rope_head_dim)
    pp = jnp.full((B, 1), pos, jnp.int32)
    kr_new = rope(kr_new, pp, cfg.rope_theta, cfg.rope_yarn)
    ckv_all = jax.lax.dynamic_update_slice(ckv_all, ckv_new[None],
                                           (layer, 0, pos, 0))
    kr_all = jax.lax.dynamic_update_slice(kr_all, kr_new[:, :, 0][None],
                                          (layer, 0, pos, 0))
    ckv_c = jax.lax.dynamic_index_in_dim(ckv_all, layer, 0, keepdims=False)
    kr_c = jax.lax.dynamic_index_in_dim(kr_all, layer, 0, keepdims=False)
    q = (x @ p["wq"]).reshape(B, 1, H, m.nope_head_dim + m.rope_head_dim)
    q_nope, q_rope = q[..., :m.nope_head_dim], q[..., m.nope_head_dim:]
    q_rope = rope(q_rope, pp, cfg.rope_theta, cfg.rope_yarn)
    wk_b = p["wk_b"].reshape(m.kv_lora_rank, H, m.nope_head_dim)
    q_abs = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0].astype(jnp.float32),
                       wk_b.astype(jnp.float32))
    scale = mla_softmax_scale(cfg)
    s = (jnp.einsum("bhr,bsr->bhs", q_abs.astype(ckv_c.dtype), ckv_c,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhr,bsr->bhs", q_rope[:, 0], kr_c,
                      preferred_element_type=jnp.float32)) * scale
    mask = jnp.arange(ckv_c.shape[1]) <= pos
    s = jnp.where(mask[None, None], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", prob.astype(ckv_c.dtype), ckv_c,
                       preferred_element_type=jnp.float32)
    wv_b = p["wv_b"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, wv_b.astype(jnp.float32))
    out = o.reshape(B, 1, H * m.v_head_dim).astype(x.dtype) @ p["wo"]
    return out, ckv_all, kr_all


def gqa_decode(p, x, pos, cfg, cache, *, cross: bool = False):
    """One-token decode. x: (B,1,d); pos: scalar int32; cache: {'k','v'}
    (B,Smax,K,Dh). cross=True: read-only cross-attention cache."""
    hd = cfg.resolved_head_dim
    B = x.shape[0]
    q = x @ p["wq"]
    if cfg.qkv_bias:
        q = q + p["bq"]
    q = _split_heads(q, cfg.n_heads, hd)
    if not cross:
        k = x @ p["wk"]
        v = x @ p["wv"]
        if cfg.qkv_bias:
            k, v = k + p["bk"], v + p["bv"]
        k = _split_heads(k, cfg.n_kv_heads, hd)
        v = _split_heads(v, cfg.n_kv_heads, hd)
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"], cfg.rms_eps)
            k = rms_norm(k, p["k_norm"], cfg.rms_eps)
        if cfg.rope_theta:
            pp = jnp.full((B, 1), pos, jnp.int32)
            q = rope(q, pp, cfg.rope_theta)
            k = rope(k, pp, cfg.rope_theta)
        kc = jax.lax.dynamic_update_slice(cache["k"], k, (0, pos, 0, 0))
        vc = jax.lax.dynamic_update_slice(cache["v"], v, (0, pos, 0, 0))
        cache = dict(cache, k=kc, v=vc)
        o = decode_attention(q, kc, vc, pos, window=cfg.sliding_window)
    else:
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        # cross attention: attend over the full (precomputed) cache
        o = decode_attention(q, cache["k"], cache["v"],
                             cache["k"].shape[1] - 1)
    out = o @ p["wo"]
    return out, cache


# ---------------------------------------------------------------------------
# MLA block (DeepSeek-V2)


def mla_softmax_scale(cfg) -> float:
    """(qk_nope + qk_rope)^-1/2, times m(factor, mscale_all_dim)^2 under
    YaRN: DeepSeek-V2's 192^-1/2 * 1.5897."""
    m = cfg.mla
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5
    y = cfg.rope_yarn
    if y is not None:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def mla_parallel(p, x, positions, cfg, *, cache_len: int = 0, mesh=None):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1),
    train/prefill form, under the ``attention`` scope. Queries are full
    rank (``wq``: d -> H * (nope + rope)); keys and values come from the
    ``kv_lora_rank`` latent (RMS-normed) through ``wk_b`` and ``wv_b``; one
    rope key of ``rope_head_dim`` is shared by every head. The rope is the
    rotate-half form (``common.rope``, YaRN's frequencies under
    ``cfg.rope_yarn``): DeepSeek's interleaved layout only permutes the
    rope columns of ``wq`` and ``wkv_a``. The softmax scale is
    ``mla_softmax_scale``; the queries carry its factor over the chunked
    attention's own head_dim^-1/2."""
    with jax.named_scope("attention"):
        return _mla_parallel(p, x, positions, cfg, cache_len=cache_len,
                             mesh=mesh)


def _mla_parallel(p, x, positions, cfg, *, cache_len: int, mesh):
    m = cfg.mla
    B, S = x.shape[:2]
    H = cfg.n_heads
    kv_a = x @ p["wkv_a"]
    ckv = rms_norm(kv_a[..., :m.kv_lora_rank], p["ckv_norm"], cfg.rms_eps)
    krope = kv_a[..., m.kv_lora_rank:].reshape(B, S, 1, m.rope_head_dim)
    krope = rope(krope, positions, cfg.rope_theta, cfg.rope_yarn)
    q = (x @ p["wq"]).reshape(B, S, H, m.nope_head_dim + m.rope_head_dim)
    q_nope, q_rope = q[..., :m.nope_head_dim], q[..., m.nope_head_dim:]
    q_rope = rope(q_rope, positions, cfg.rope_theta, cfg.rope_yarn)
    k_nope = (ckv @ p["wk_b"]).reshape(B, S, H, m.nope_head_dim)
    v = (ckv @ p["wv_b"]).reshape(B, S, H, m.v_head_dim)
    # fold the shared rope key in as extra head dims (standard MLA trick)
    q_eff = jnp.concatenate([q_nope, q_rope], axis=-1)
    gain = mla_softmax_scale(cfg) * (m.nope_head_dim + m.rope_head_dim) ** 0.5
    if gain != 1.0:
        q_eff = q_eff * jnp.asarray(gain, q_eff.dtype)
    k_eff = jnp.concatenate(
        [k_nope, jnp.broadcast_to(krope, (B, S, H, m.rope_head_dim))], axis=-1)
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        dp = dp_axes(mesh)
        q_eff = constrain(q_eff, mesh, P(dp, None, "model", None))
        k_eff = constrain(k_eff, mesh, P(dp, None, "model", None))
        v = constrain(v, mesh, P(dp, None, "model", None))
    o = _attend(q_eff, k_eff, v, cfg, mesh, causal=True)
    out = o.reshape(B, S, H * m.v_head_dim) @ p["wo"]
    cache = None
    if cache_len:
        c = jnp.zeros((B, cache_len, m.kv_lora_rank), ckv.dtype)
        r = jnp.zeros((B, cache_len, m.rope_head_dim), krope.dtype)
        c = jax.lax.dynamic_update_slice(c, ckv, (0, 0, 0))
        r = jax.lax.dynamic_update_slice(r, krope[:, :, 0], (0, 0, 0))
        cache = {"ckv": c, "krope": r}
    return out, cache


def mla_decode(p, x, pos, cfg, cache):
    """Absorbed-form MLA decode: score against the cached latent directly;
    only (ckv, krope) are cached — DeepSeek-V2's KV-cache reduction."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    kv_a = x @ p["wkv_a"]
    ckv_new = rms_norm(kv_a[..., :m.kv_lora_rank], p["ckv_norm"], cfg.rms_eps)
    kr_new = kv_a[..., m.kv_lora_rank:].reshape(B, 1, 1, m.rope_head_dim)
    pp = jnp.full((B, 1), pos, jnp.int32)
    kr_new = rope(kr_new, pp, cfg.rope_theta, cfg.rope_yarn)
    ckv_c = jax.lax.dynamic_update_slice(cache["ckv"], ckv_new, (0, pos, 0))
    kr_c = jax.lax.dynamic_update_slice(cache["krope"], kr_new[:, :, 0],
                                        (0, pos, 0))
    q = (x @ p["wq"]).reshape(B, 1, H, m.nope_head_dim + m.rope_head_dim)
    q_nope, q_rope = q[..., :m.nope_head_dim], q[..., m.nope_head_dim:]
    q_rope = rope(q_rope, pp, cfg.rope_theta, cfg.rope_yarn)
    wk_b = p["wk_b"].reshape(m.kv_lora_rank, H, m.nope_head_dim)
    # absorb W^UK into q:   q̃ = q_nope · W^UK   (B,H,r)
    q_abs = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0].astype(jnp.float32),
                       wk_b.astype(jnp.float32))
    scale = mla_softmax_scale(cfg)
    s = (jnp.einsum("bhr,bsr->bhs", q_abs.astype(ckv_c.dtype), ckv_c,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhr,bsr->bhs", q_rope[:, 0], kr_c,
                      preferred_element_type=jnp.float32)) * scale
    mask = jnp.arange(ckv_c.shape[1]) <= pos
    s = jnp.where(mask[None, None], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", prob.astype(ckv_c.dtype), ckv_c,
                       preferred_element_type=jnp.float32)
    wv_b = p["wv_b"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    o = jnp.einsum("bhr,rhv->bhv", o_lat, wv_b.astype(jnp.float32))
    out = o.reshape(B, 1, H * m.v_head_dim).astype(x.dtype) @ p["wo"]
    return out, {"ckv": ckv_c, "krope": kr_c}
