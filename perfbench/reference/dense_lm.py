"""A dense decoder-only language model in plain float32 ``jax.numpy``:
forward, label-smoothed next-token loss, gradient and LARS steps.

From the Mistral / Llama description (Touvron et al. 2023, Jiang et al.
2023; Mistral-NeMo's ``config.json``): token embedding; per layer,
pre-norm RMSNorm, grouped-query attention with rotary embeddings
(rotate-half form, frequencies theta^(-2i/head_dim)) and causal masking,
a residual, pre-norm RMSNorm and a SwiGLU feed-forward network, a
residual; a final RMSNorm and an untied output projection.

Computed one sequence at a time and, inside a sequence, one block of
queries at a time, each rematerialised in the backward pass, so that a
step at 4096 tokens fits the chip in float32.

The layers' parameters are held stacked along a leading layer axis, as
one array per kind of tensor; LARS still takes one trust ratio per
layer's tensor and leaves each layer's RMSNorm scales (1-D) unscaled, as
You et al. (arXiv:1708.03888) and arXiv:1903.12650 state it.

Departure from the published description, the configuration's own
statement: no sliding window; at the configuration's sequence lengths a
window of 131,072 positions masks nothing.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench.reference import common

QUERY_BLOCK = 512


def param_spec(c: dict) -> dict:
    """{path: (shape, init, scale)}; ``c`` holds d_model, n_heads,
    n_kv_heads, head_dim, d_ff, vocab_size and n_layers."""
    d, v, L = c["d_model"], c["vocab_size"], c["n_layers"]
    hq, hk, f = c["n_heads"] * c["head_dim"], c["n_kv_heads"] * c["head_dim"], c["d_ff"]
    out = math.sqrt(2 * L)
    return {
        "final_norm": ((d,), "ones", 0.0),
        "embed": ((v, d), "normal", 0.02),
        "lm_head": ((d, v), "normal", d ** -0.5),
        "layers/ln1": ((L, d), "ones", 0.0),
        "layers/attn/wq": ((L, d, hq), "normal", d ** -0.5),
        "layers/attn/wk": ((L, d, hk), "normal", d ** -0.5),
        "layers/attn/wv": ((L, d, hk), "normal", d ** -0.5),
        "layers/attn/wo": ((L, hq, d), "normal", hq ** -0.5 / out),
        "layers/ln2": ((L, d), "ones", 0.0),
        "layers/mlp/w_gate": ((L, d, f), "normal", d ** -0.5),
        "layers/mlp/w_up": ((L, d, f), "normal", d ** -0.5),
        "layers/mlp/w_down": ((L, f, d), "normal", f ** -0.5 / out),
    }


def stacked(spec: dict) -> frozenset:
    """The tensors that hold one tensor per layer along their first axis."""
    return frozenset(k for k in spec if k.startswith("layers/"))


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (S, heads, hd): rotate-half rotary embedding at positions 0..S-1."""
    s, hd = x.shape[0], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, compute):
    """q (S, H, hd), k and v (S, K, hd) -> (S, H*hd); causal, query head
    h reads key/value head h // (H/K)."""
    s, h, hd = q.shape
    group = h // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    kpos = jnp.arange(s)
    outs = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)

        def block(qb, k, v, lo=lo, hi=hi):
            sc = jnp.einsum("qhd,khd->hqk", common.operand(qb, compute),
                            common.operand(k, compute),
                            precision=common.HIGHEST) / math.sqrt(hd)
            mask = kpos[None, :] <= jnp.arange(lo, hi)[:, None]
            sc = jnp.where(mask[None], sc, -jnp.inf)
            p = jax.nn.softmax(sc, axis=-1)
            return jnp.einsum("hqk,khd->qhd", common.operand(p, compute),
                              common.operand(v, compute),
                              precision=common.HIGHEST)

        outs.append(jax.checkpoint(block)(q[lo:hi], k, v))
    return jnp.concatenate(outs, axis=0).reshape(s, h * hd)


def _layer(p, x, c, compute):
    s = x.shape[0]
    hd, eps = c["head_dim"], c["rms_norm_eps"]
    mm = lambda a, b: common.matmul(a, b, compute)
    h = _rms(x, p["ln1"], eps)
    q = _rope(mm(h, p["wq"]).reshape(s, c["n_heads"], hd), c["rope_theta"])
    k = _rope(mm(h, p["wk"]).reshape(s, c["n_kv_heads"], hd), c["rope_theta"])
    v = mm(h, p["wv"]).reshape(s, c["n_kv_heads"], hd)
    x = x + mm(_attention(q, k, v, compute), p["wo"])
    h = _rms(x, p["ln2"], eps)
    return x + mm(jax.nn.silu(mm(h, p["w_gate"])) * mm(h, p["w_up"]),
                  p["w_down"])


def _sequence_nll(params, tokens, labels, c, smoothing, compute):
    x = params["embed"][tokens]
    for i in range(c["n_layers"]):
        lp = {k.split("/")[-1]: v[i] for k, v in params.items()
              if k.startswith("layers/")}
        x = jax.checkpoint(lambda lp, x: _layer(lp, x, c, compute))(lp, x)
    x = _rms(x, params["final_norm"], c["rms_norm_eps"])
    out = common.matmul(x, params["lm_head"], compute)
    return common.smoothed_nll_sum(out, labels, smoothing)


def loss(params, batch, *, c: dict, smoothing: float, compute: str = "f32"):
    """(mean label-smoothed next-token loss over every labelled position of
    the batch (tokens and labels (B, S)), one sequence at a time; no batch
    statistics: {})."""
    def body(acc, xs):
        tot, n = jax.checkpoint(
            lambda p, t, l: _sequence_nll(p, t, l, c, smoothing, compute))(
                params, *xs)
        return (acc[0] + tot, acc[1] + n), None

    (total, n), _ = jax.lax.scan(body, (jnp.float32(0), jnp.int32(0)),
                                 (batch["tokens"], batch["labels"]))
    return total / n, {}
