"""Language-model composition: decoder stacks (dense / MoE / MLA / hybrid /
xLSTM), encoder-decoder (whisper) and prefix-VLM (internvl2).

Layer parameters are *stacked* along a leading layer axis and the stack is
traversed with ``lax.scan`` (optionally wrapped in ``jax.checkpoint``) so the
HLO stays small enough to compile for 512 devices. Heterogeneous stacks
(xLSTM patterns, zamba2's shared-attention interleave) use static python
grouping instead (documented in DESIGN.md §5).

Three entry points per model, matching the assigned input-shape kinds:
``forward_train`` (full logits + MoE aux), ``forward_prefill`` (logits of the
last position + a filled cache), ``forward_decode`` (one token against the
cache).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import attention as attn
from repro.models import mamba as mb
from repro.models import mlp as mlpm
from repro.models import moe as moem
from repro.models import xlstm as xl
from repro.models.common import (PD, constrain, dense_pd, dp_axes, layer_norm,
                                 pd_stack, rms_norm)

MAX_POS = 32_768   # learned-position table size (whisper-style decoders)


# ---------------------------------------------------------------------------
# parameter descriptor trees


def _attn_pd(cfg):
    return attn.mla_pd(cfg) if cfg.mla is not None else attn.gqa_pd(cfg)


def _dense_layer_pd(cfg, dense: bool = False):
    """One decoder layer; ``dense``: a MoE model's leading dense layer."""
    d = cfg.d_model
    p = {"ln1": PD((d,), init="ones"), "attn": _attn_pd(cfg),
         "ln2": PD((d,), init="ones")}
    if dense:
        p["mlp"] = mlpm.swiglu_pd(cfg, d_ff=cfg.moe.d_dense)
    elif cfg.moe is not None:
        p["moe"] = moem.moe_pd(cfg)
        if cfg.moe.n_held:
            # the held experts live in ``experts``, outside the layer stack
            p["moe"] = {"router": p["moe"]["router"]}
        if cfg.moe.n_shared:
            p["shared"] = mlpm.swiglu_pd(
                cfg, d_ff=cfg.moe.n_shared * cfg.moe.d_expert)
    else:
        p["mlp"] = mlpm.swiglu_pd(cfg)
    return p


def _whisper_layer_pd(cfg, cross: bool):
    d = cfg.d_model
    p = {"ln1": PD((d,), init="ones"), "ln1b": PD((d,), init="zeros"),
         "attn": attn.gqa_pd(cfg),
         "ln2": PD((d,), init="ones"), "ln2b": PD((d,), init="zeros"),
         "mlp": mlpm.gelu_mlp_pd(cfg)}
    if cross:
        p["lnx"] = PD((d,), init="ones")
        p["lnxb"] = PD((d,), init="zeros")
        p["cross"] = attn.gqa_pd(cfg)
    return p


def lm_pd(cfg) -> Dict[str, Any]:
    d, V = cfg.d_model, cfg.vocab_size
    dp = "data" if cfg.fsdp else None
    tree: Dict[str, Any] = {"final_norm": PD((d,), init="ones")}
    if cfg.tie_embeddings:
        tree["embed"] = PD((V, d), spec=P("model", dp), scale=0.02)
    else:
        tree["embed"] = PD((V, d), spec=P(dp, "model"), scale=0.02)
        tree["lm_head"] = dense_pd(d, V, spec=P(dp, "model"), scale=d ** -0.5)

    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        k = cfg.moe.first_k_dense if fam == "moe" else 0
        if k:
            tree["dense_layers"] = pd_stack(
                _dense_layer_pd(cfg, dense=True), k)
        tree["layers"] = pd_stack(_dense_layer_pd(cfg), cfg.n_layers - k)
        if fam == "moe" and cfg.moe.n_held:
            tree["experts"] = moem.held_pd(cfg, cfg.n_layers - k)
        if fam == "vlm":
            tree["proj"] = dense_pd(d, d, spec=P(None, None))  # stub projector
    elif fam == "ssm" and cfg.xlstm is not None:
        for i in range(cfg.n_layers):
            kind = xl.block_kind(cfg, i)
            blk = xl.mlstm_pd(cfg) if kind == "m" else xl.slstm_pd(cfg)
            tree[f"layer_{i:02d}"] = {"ln": PD((d,), init="ones"), "blk": blk}
    elif fam == "hybrid":
        tree["layers"] = pd_stack(
            {"ln": PD((d,), init="ones"), "mamba": mb.mamba_pd(cfg)},
            cfg.n_layers)
        tree["shared_attn"] = {
            "ln1": PD((d,), init="ones"), "attn": attn.gqa_pd(cfg),
            "ln2": PD((d,), init="ones"), "mlp": mlpm.swiglu_pd(cfg)}
    elif fam == "audio":
        enc = cfg.encoder
        tree["enc_pos"] = PD((enc.n_frames, d), scale=0.02)
        tree["enc_layers"] = pd_stack(_whisper_layer_pd(cfg, cross=False),
                                      enc.n_layers)
        tree["enc_norm"] = PD((d,), init="ones")
        tree["enc_norm_b"] = PD((d,), init="zeros")
        tree["dec_pos"] = PD((MAX_POS, d), scale=0.02)
        tree["layers"] = pd_stack(_whisper_layer_pd(cfg, cross=True),
                                  cfg.n_layers)
        tree["final_norm_b"] = PD((d,), init="zeros")
    else:
        raise ValueError(fam)
    return tree


# ---------------------------------------------------------------------------
# shared pieces


def _embed(params, cfg, tokens):
    e = params["embed"][tokens]
    return e.astype(jnp.bfloat16)


def _logits(params, cfg, x):
    x = rms_norm(x, params["final_norm"], cfg.rms_eps) \
        if cfg.family != "audio" else \
        layer_norm(x, params["final_norm"], params["final_norm_b"])
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (x @ w.astype(x.dtype)).astype(jnp.float32)


def _maybe_remat(fn, cfg):
    return jax.checkpoint(fn) if cfg.remat else fn


def _scan_layers(body, x, layers, cfg, extra=None):
    """Scan a homogeneous stacked-layer tree. body(x, layer_p, extra)->x, aux."""
    def f(carry, layer_p):
        x, aux = carry
        x, a = body(x, layer_p)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(_maybe_remat(f, cfg), (x, jnp.float32(0)),
                               layers)
    return x, aux


# ---------------------------------------------------------------------------
# dense / moe / vlm decoder


def _dense_block(p, x, positions, cfg, mesh, *, decode=False, cache=None,
                 pos=None, cache_len=0, experts=None):
    """One decoder layer: a dense FFN where ``p`` has ``mlp``, else a MoE
    layer (``experts``: this layer's held experts, under ``n_held``).
    Returns (x, aux, new_cache, MoE counters {})."""
    h = rms_norm(x, p["ln1"], cfg.rms_eps)
    new_cache = {}
    if cfg.mla is not None:
        if decode:
            a, new_cache = attn.mla_decode(p["attn"], h, pos, cfg, cache)
        else:
            a, new_cache = attn.mla_parallel(p["attn"], h, positions, cfg,
                                             cache_len=cache_len, mesh=mesh)
    else:
        if decode:
            a, new_cache = attn.gqa_decode(p["attn"], h, pos, cfg, cache)
        else:
            a, new_cache = attn.gqa_parallel(p["attn"], h, positions, cfg,
                                             cache_len=cache_len, mesh=mesh)
    x = x + a
    h = rms_norm(x, p["ln2"], cfg.rms_eps)
    aux, stats = jnp.float32(0), {}
    if "mlp" in p:
        m = mlpm.swiglu_apply(p["mlp"], h)
    else:
        with jax.named_scope("moe"):
            if experts is not None:
                m, aux, stats = moem.moe_held(p["moe"]["router"], experts,
                                              h, cfg)
            else:
                m, aux = moem.moe_apply(p["moe"], h, cfg, mesh,
                                        decode=decode)
            if cfg.moe.n_shared:
                m = m + mlpm.swiglu_apply(p["shared"], h)
    return x + m, aux, new_cache, stats


def _held_view(params, cfg):
    """The held experts as (MoE layers, n_held, ...) for the layer scan,
    or None without held experts."""
    if "experts" not in params:
        return None
    return jax.tree.map(
        lambda a: a.reshape(-1, cfg.moe.n_held, *a.shape[1:]),
        params["experts"])


def _stacks(params, cfg):
    """(layer stack, its held experts or None) in order: the leading
    dense layers, then the rest."""
    out = []
    if "dense_layers" in params:
        out.append(("dense_layers", params["dense_layers"], None))
    out.append(("layers", params["layers"], _held_view(params, cfg)))
    return out


def _dense_forward(params, cfg, mesh, x, positions, *, mode, cache=None,
                   pos=None, cache_len=0):
    """mode: train | prefill | decode. x: embedded inputs (B,S,d).
    Train returns (x, aux, MoE counters); prefill and decode (x, aux,
    cache), the cache {stack name: its cache} where the model has
    leading dense layers."""
    dp = dp_axes(mesh) if mesh is not None else ()
    x = constrain(x, mesh, P(dp, None, None))

    if mode == "train":
        aux, stats = jnp.float32(0), {}
        for _, layers, held in _stacks(params, cfg):
            def f(carry, xs):
                x, aux, stats = carry
                layer_p, ex = xs
                x, a, _, st = _dense_block(layer_p, x, positions, cfg, mesh,
                                           experts=ex)
                if st:
                    # rows add up over the layers; the load is the worst
                    st = {"moe_rows_here": stats["moe_rows_here"]
                          + st["moe_rows_here"],
                          "moe_load_max_over_mean": jnp.maximum(
                              stats["moe_load_max_over_mean"],
                              st["moe_load_max_over_mean"])}
                return (x, aux + a, st), None

            st0 = ({k: jnp.float32(0) for k in moem.HELD_STATS}
                   if held is not None else {})
            (x, aux, st), _ = jax.lax.scan(_maybe_remat(f, cfg),
                                           (x, aux, st0), (layers, held))
            stats.update(st)
        return x, aux, stats

    stacks = _stacks(params, cfg)
    caches = {}
    aux = jnp.float32(0)
    for name, layers, held in stacks:
        if mode == "prefill":
            def f(carry, xs):
                x, aux = carry
                layer_p, ex = xs
                x, a, c, _ = _dense_block(layer_p, x, positions, cfg, mesh,
                                          cache_len=cache_len, experts=ex)
                return (x, aux + a), c
            (x, aux), caches[name] = jax.lax.scan(f, (x, aux),
                                                  (layers, held))
            continue
        # decode: cache scanned through xs/ys. Two alternatives were
        # measured and REFUTED (§Perf-2): a fully-unrolled in-place loop
        # (XLA materialized per-layer full-cache copies: 0.10s -> 11.0s
        # memory term) and cache-as-scan-carry (loop double-buffering
        # copies the whole cache every iteration: -> 0.94s). XLA's xs/ys
        # loop aliasing is already the best layout for a layer-scanned
        # cache.
        def f(carry, xs):
            x, aux = carry
            layer_p, ex, c = xs
            x, a, c2, _ = _dense_block(layer_p, x, positions, cfg, mesh,
                                       decode=True, cache=c, pos=pos,
                                       experts=ex)
            return (x, aux + a), c2
        layer_cache = cache[name] if len(stacks) > 1 else cache
        (x, aux), caches[name] = jax.lax.scan(f, (x, aux),
                                              (layers, held, layer_cache))
    return x, aux, caches if len(stacks) > 1 else caches["layers"]


# ---------------------------------------------------------------------------
# hybrid (zamba2): scanned mamba groups + shared attention block


def _shared_attn_block(p, x, positions, cfg, *, decode=False, cache=None,
                       pos=None, cache_len=0):
    h = rms_norm(x, p["ln1"], cfg.rms_eps)
    if decode:
        a, c = attn.gqa_decode(p["attn"], h, pos, cfg, cache)
    else:
        a, c = attn.gqa_parallel(p["attn"], h, positions, cfg,
                                 cache_len=cache_len)
    x = x + a
    h = rms_norm(x, p["ln2"], cfg.rms_eps)
    return x + mlpm.swiglu_apply(p["mlp"], h), c


def _hybrid_groups(cfg):
    k = cfg.attn_every
    n = cfg.n_layers
    return [(g * k, min((g + 1) * k, n)) for g in range(-(-n // k))]


def _hybrid_forward(params, cfg, mesh, x, positions, *, mode, cache=None,
                    pos=None, cache_len=0):
    dp = dp_axes(mesh) if mesh is not None else ()
    x = constrain(x, mesh, P(dp, None, None))
    groups = _hybrid_groups(cfg)
    take = lambda t, lo, hi: jax.tree.map(lambda a: a[lo:hi], t)
    aux = jnp.float32(0)
    attn_caches, mamba_caches = [], []

    for gi, (lo, hi) in enumerate(groups):
        if mode == "decode":
            x, ac = _shared_attn_block(
                params["shared_attn"], x, positions, cfg, decode=True,
                cache=jax.tree.map(lambda a, g=gi: a[g], cache["attn"]),
                pos=pos)
        else:
            x, ac = _shared_attn_block(
                params["shared_attn"], x, positions, cfg,
                cache_len=cache_len)
        attn_caches.append(ac)

        layers = take(params["layers"], lo, hi)
        if mode == "train":
            def body(x, layer_p):
                h = rms_norm(x, layer_p["ln"], cfg.rms_eps)
                o, _ = mb.mamba_parallel(layer_p["mamba"], h, cfg)
                return x + o, jnp.float32(0)
            x, _ = _scan_layers(body, x, layers, cfg)
        elif mode == "prefill":
            def f(carry, layer_p):
                x = carry
                h = rms_norm(x, layer_p["ln"], cfg.rms_eps)
                o, c = mb.mamba_parallel(layer_p["mamba"], h, cfg,
                                         return_cache=True)
                return x + o, c
            x, mc = jax.lax.scan(f, x, layers)
            mamba_caches.append(mc)
        else:
            def f(carry, xs):
                x = carry
                layer_p, c = xs
                h = rms_norm(x, layer_p["ln"], cfg.rms_eps)
                o, c2 = mb.mamba_decode(layer_p["mamba"], h, cfg, c)
                return x + o, c2
            x, mc = jax.lax.scan(
                f, x, (layers, take(cache["mamba"], lo, hi)))
            mamba_caches.append(mc)

    new_cache = None
    if mode != "train":
        stack0 = lambda ts: jax.tree.map(lambda *a: jnp.stack(a), *ts)
        cat0 = lambda ts: jax.tree.map(
            lambda *a: jnp.concatenate(a, axis=0), *ts)
        new_cache = {"attn": stack0(attn_caches), "mamba": cat0(mamba_caches)}
    return x, aux, new_cache


# ---------------------------------------------------------------------------
# xLSTM stack (heterogeneous python loop; 12 small layers)


def _xlstm_forward(params, cfg, mesh, x, *, mode, cache=None):
    dp = dp_axes(mesh) if mesh is not None else ()
    x = constrain(x, mesh, P(dp, None, None))
    new_cache = {}
    for i in range(cfg.n_layers):
        p = params[f"layer_{i:02d}"]
        kind = xl.block_kind(cfg, i)
        h = rms_norm(x, p["ln"], cfg.rms_eps)
        key = f"layer_{i:02d}"
        if mode == "decode":
            fn = xl.mlstm_decode if kind == "m" else xl.slstm_decode
            o, c = fn(p["blk"], h, cfg, cache[key])
        else:
            fn = xl.mlstm_parallel if kind == "m" else xl.slstm_parallel
            o, c = fn(p["blk"], h, cfg, return_cache=(mode == "prefill"))
        new_cache[key] = c
        x = x + o
    return x, jnp.float32(0), (new_cache if mode != "train" else None)


# ---------------------------------------------------------------------------
# whisper enc-dec


def _whisper_layer(p, x, positions, cfg, enc_out, *, decode=False,
                   cache=None, pos=None, cache_len=0):
    h = layer_norm(x, p["ln1"], p["ln1b"])
    if decode:
        a, sc = attn.gqa_decode(p["attn"], h, pos, cfg, cache["self"])
    else:
        a, sc = attn.gqa_parallel(p["attn"], h, positions, cfg,
                                  cache_len=cache_len)
    x = x + a
    h = layer_norm(x, p["lnx"], p["lnxb"])
    if decode:
        a, _ = attn.gqa_decode(p["cross"], h, pos, cfg, cache["cross"],
                               cross=True)
        xc = cache["cross"]
    else:
        a, xc = attn.gqa_parallel(p["cross"], h, positions, cfg,
                                  cross_x=enc_out,
                                  cache_len=enc_out.shape[1] if cache_len else 0)
    x = x + a
    h = layer_norm(x, p["ln2"], p["ln2b"])
    x = x + mlpm.gelu_mlp_apply(p["mlp"], h)
    c = {"self": sc, "cross": xc} if (cache_len or decode) else None
    return x, c


def _whisper_encode(params, cfg, frames):
    """frames: stub (B, n_frames, d) embeddings."""
    x = frames.astype(jnp.bfloat16) + params["enc_pos"].astype(jnp.bfloat16)

    def body(x, layer_p):
        h = layer_norm(x, layer_p["ln1"], layer_p["ln1b"])
        a, _ = attn.gqa_parallel(layer_p["attn"], h, None, cfg, cross_x=h)
        x = x + a
        h = layer_norm(x, layer_p["ln2"], layer_p["ln2b"])
        return x + mlpm.gelu_mlp_apply(layer_p["mlp"], h), jnp.float32(0)

    x, _ = _scan_layers(body, x, params["enc_layers"], cfg)
    return layer_norm(x, params["enc_norm"], params["enc_norm_b"])


def _whisper_forward(params, cfg, mesh, tokens, frames, *, mode, cache=None,
                     pos=None, cache_len=0):
    dp = dp_axes(mesh) if mesh is not None else ()
    if mode == "decode":
        x = _embed(params, cfg, tokens) \
            + params["dec_pos"][pos].astype(jnp.bfloat16)
        def f(carry, xs):
            x = carry
            layer_p, c = xs
            x, c2 = _whisper_layer(layer_p, x, None, cfg, None, decode=True,
                                   cache=c, pos=pos)
            return x, c2
        x, cache = jax.lax.scan(f, x, (params["layers"], cache))
        return x, jnp.float32(0), cache

    enc_out = _whisper_encode(params, cfg, frames)
    enc_out = constrain(enc_out, mesh, P(dp, None, None))
    S = tokens.shape[1]
    x = _embed(params, cfg, tokens) \
        + params["dec_pos"][:S].astype(jnp.bfloat16)
    x = constrain(x, mesh, P(dp, None, None))
    positions = jnp.arange(S)[None]
    if mode == "train":
        def body(x, layer_p):
            x, _ = _whisper_layer(layer_p, x, positions, cfg, enc_out)
            return x, jnp.float32(0)
        x, aux = _scan_layers(body, x, params["layers"], cfg)
        return x, aux, None

    def f(carry, layer_p):
        x = carry
        x, c = _whisper_layer(layer_p, x, positions, cfg, enc_out,
                              cache_len=cache_len)
        return x, c
    x, cache = jax.lax.scan(f, x, params["layers"])
    return x, jnp.float32(0), cache


# ---------------------------------------------------------------------------
# public entry points


def forward_train(params, cfg, mesh, batch):
    """batch: {'tokens': (B,S)[, 'frames': (B,P,d)]}. Returns (logits, aux,
    counters): the counters are the held experts' (``moe.HELD_STATS``),
    {} for every other model."""
    tokens = batch["tokens"]
    stats = {}
    if cfg.family == "audio":
        x, aux, _ = _whisper_forward(params, cfg, mesh, tokens,
                                     batch["frames"], mode="train")
        return _logits(params, cfg, x), aux, stats
    x = _embed(params, cfg, tokens)
    if cfg.family == "vlm":
        pre = (batch["frames"].astype(jnp.bfloat16)
               @ params["proj"].astype(jnp.bfloat16))
        x = jnp.concatenate([pre, x], axis=1)
    S = x.shape[1]
    positions = jnp.arange(S)[None]
    if cfg.family in ("dense", "moe", "vlm"):
        x, aux, stats = _dense_forward(params, cfg, mesh, x, positions,
                                       mode="train")
    elif cfg.family == "hybrid":
        x, aux, _ = _hybrid_forward(params, cfg, mesh, x, positions,
                                    mode="train")
    else:
        x, aux, _ = _xlstm_forward(params, cfg, mesh, x, mode="train")
    return _logits(params, cfg, x), aux, stats


def forward_prefill(params, cfg, mesh, batch, cache_len: int):
    """Returns (last-position logits, cache)."""
    tokens = batch["tokens"]
    if cfg.family == "audio":
        x, _, cache = _whisper_forward(params, cfg, mesh, tokens,
                                       batch["frames"], mode="prefill",
                                       cache_len=cache_len)
    else:
        x = _embed(params, cfg, tokens)
        if cfg.family == "vlm":
            pre = (batch["frames"].astype(jnp.bfloat16)
                   @ params["proj"].astype(jnp.bfloat16))
            x = jnp.concatenate([pre, x], axis=1)
        positions = jnp.arange(x.shape[1])[None]
        if cfg.family in ("dense", "moe", "vlm"):
            x, _, cache = _dense_forward(params, cfg, mesh, x, positions,
                                         mode="prefill", cache_len=cache_len)
        elif cfg.family == "hybrid":
            x, _, cache = _hybrid_forward(params, cfg, mesh, x, positions,
                                          mode="prefill", cache_len=cache_len)
        else:
            x, _, cache = _xlstm_forward(params, cfg, mesh, x, mode="prefill")
    return _logits(params, cfg, x[:, -1:]), cache


def forward_decode(params, cfg, mesh, cache, token, pos):
    """token: (B,1) int32; pos: scalar int32. Returns (logits, new cache)."""
    if cfg.family == "audio":
        x, _, cache = _whisper_forward(params, cfg, mesh, token, None,
                                       mode="decode", cache=cache, pos=pos)
        return _logits(params, cfg, x), cache
    x = _embed(params, cfg, token)
    if cfg.family in ("dense", "moe", "vlm"):
        x, _, cache = _dense_forward(params, cfg, mesh, x, None,
                                     mode="decode", cache=cache, pos=pos)
    elif cfg.family == "hybrid":
        x, _, cache = _hybrid_forward(params, cfg, mesh, x, None,
                                      mode="decode", cache=cache, pos=pos)
    else:
        x, _, cache = _xlstm_forward(params, cfg, mesh, x, mode="decode",
                                     cache=cache)
    return _logits(params, cfg, x), cache


# ---------------------------------------------------------------------------
# abstract cache descriptors (for dry-run input_specs)


def cache_pd(cfg, batch: int, max_seq: int, dp=("data",)):
    """Descriptor tree matching what forward_prefill produces (leading layer
    dim for scanned stacks). dp: mesh axes carrying the request batch."""
    hd = cfg.resolved_head_dim
    K = cfg.n_kv_heads
    dp = tuple(dp)

    def kv(seq, stack=None, kvheads=K):
        pd = {"k": PD((batch, seq, kvheads, hd), spec=P(dp, None, "model", None),
                      init="zeros", dtype=jnp.bfloat16),
              "v": PD((batch, seq, kvheads, hd), spec=P(dp, None, "model", None),
                      init="zeros", dtype=jnp.bfloat16)}
        return pd_stack(pd, stack) if stack else pd

    fam = cfg.family
    if fam in ("dense", "vlm", "moe") and cfg.mla is None:
        return kv(max_seq, stack=cfg.n_layers)
    if cfg.mla is not None:
        m = cfg.mla
        pd = {"ckv": PD((batch, max_seq, m.kv_lora_rank),
                        spec=P(dp, None, None), init="zeros",
                        dtype=jnp.bfloat16),
              "krope": PD((batch, max_seq, m.rope_head_dim),
                          spec=P(dp, None, None), init="zeros",
                          dtype=jnp.bfloat16)}
        k = cfg.moe.first_k_dense if cfg.moe is not None else 0
        if k:
            return {"dense_layers": pd_stack(pd, k),
                    "layers": pd_stack(pd, cfg.n_layers - k)}
        return pd_stack(pd, cfg.n_layers)
    if fam == "hybrid":
        n_groups = len(_hybrid_groups(cfg))
        return {
            "attn": pd_stack(kv(max_seq), n_groups),
            "mamba": pd_stack(mb.mamba_cache_pd(cfg, batch, dp=dp),
                              cfg.n_layers),
        }
    if fam == "ssm" and cfg.xlstm is not None:
        out = {}
        for i in range(cfg.n_layers):
            kind = xl.block_kind(cfg, i)
            out[f"layer_{i:02d}"] = (xl.mlstm_cache_pd(cfg, batch, dp=dp)
                                     if kind == "m"
                                     else xl.slstm_cache_pd(cfg, batch,
                                                            dp=dp))
        return out
    if fam == "audio":
        return pd_stack({"self": kv(max_seq),
                         "cross": kv(cfg.encoder.n_frames)}, cfg.n_layers)
    raise ValueError(fam)
