"""DeepSeek-V2-Lite's decoder, a chip's share of its experts, in plain
float32 ``jax.numpy``: forward, next-token loss with the balance loss,
gradient and LARS steps.

From DeepSeek-V2 (arXiv:2405.04434) and DeepSeek-V2-Lite's
``config.json``: token embedding; per layer, pre-norm RMSNorm, multi-head
latent attention (MLA), a residual, pre-norm RMSNorm, a feed-forward
network, a residual; a final RMSNorm and an untied output projection.

- MLA: queries without LoRA, ``x @ wq`` split per head into a no-rope
  part (``qk_nope_head_dim``) and a rope part (``qk_rope_head_dim``); a
  latent ``c = RMSNorm(x @ wkv_a[:, :kv_lora_rank])``, keys' no-rope part
  ``c @ wk_b`` and values ``c @ wv_b`` per head, one rope key
  ``x @ wkv_a[:, kv_lora_rank:]`` shared by every head. Score
  ``q_nope . k_nope + q_rope . k_rope`` times the softmax scale, causal.
- YaRN rope (arXiv:2309.00071), rotate-half form, at positions 0..S-1:
  ``extra_i = theta^(-2i/d)``, ``inter_i = extra_i / factor``,
  ``corr(r) = d ln(orig / (2 pi r)) / (2 ln theta)``, ``low =
  floor(corr(beta_fast))``, ``high = ceil(corr(beta_slow))``, ``ramp_i =
  clip((i - low) / (high - low), 0, 1)``, ``inv_freq_i = inter_i ramp_i +
  extra_i (1 - ramp_i)``; cos and sin times m(factor, mscale) /
  m(factor, mscale_all_dim), ``m(s, mu) = 0.1 mu ln s + 1``; the softmax
  scale ``(nope + rope)^-1/2 m(factor, mscale_all_dim)^2``.
- The first ``first_k_dense_replace`` layers: a SwiGLU of width
  ``intermediate_size``. The others: a softmax router over all
  ``n_routed_experts``, the top ``num_experts_per_tok`` probabilities as
  gates, not renormalised, times ``routed_scaling_factor``; the held
  experts ``[held_offset, held_offset + experts_held)``, each a SwiGLU of
  width ``moe_intermediate_size`` computed on every token of the sequence
  and weighted by that token's gate for it (0 where the token did not
  choose it): a plain loop over the held experts, no sort and no grouped
  product. What the experts that are not held would add is left out, as
  the chip's share leaves it out. Plus ``n_shared_experts`` shared
  experts, one SwiGLU of width ``n_shared * moe_intermediate_size``, on
  every token.
- The balance loss (``seq_aux``): per sequence and MoE layer, E sum_e
  f_e P_e, f_e the share of the sequence's S k routed slots that chose
  expert e and P_e the mean of the router's probabilities for it; the
  mean over sequences, summed over the MoE layers, times
  ``aux_loss_alpha``. Its gradient is taken; its value is not in the loss
  returned, as the program reports the cross entropy alone.

Computed one sequence at a time (the balance loss is per sequence, so
the sum of the sequences' gradients is exact), each layer and each block
of queries rematerialised, so that a step at the cell's size fits the
chip in float32. The layers' parameters are stacked along a leading axis
as the program stores them: per layer, and the held experts per (layer,
expert), layer-major; LARS takes one trust ratio per layer's tensor and
per expert's matrix, and leaves rank-1 tensors (norm scales) unscaled.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import common

QUERY_BLOCK = 512


def param_spec(c: dict) -> dict:
    """{path: (shape, init, scale)}; ``c`` from ``dims``."""
    d, v, L, k = c["d_model"], c["vocab_size"], c["n_layers"], c["k_dense"]
    H, r, rope = c["n_heads"], c["kv_lora_rank"], c["rope_dim"]
    hq, hv = H * (c["nope_dim"] + rope), H * c["v_dim"]
    f, fe, fs = c["d_dense"], c["d_expert"], c["n_shared"] * c["d_expert"]
    out = math.sqrt(2 * L)

    def attn(prefix, n):
        return {
            f"{prefix}/ln1": ((n, d), "ones", 0.0),
            f"{prefix}/ln2": ((n, d), "ones", 0.0),
            f"{prefix}/attn/wq": ((n, d, hq), "normal", d ** -0.5),
            f"{prefix}/attn/wkv_a": ((n, d, r + rope), "normal", d ** -0.5),
            f"{prefix}/attn/ckv_norm": ((n, r), "ones", 0.0),
            f"{prefix}/attn/wk_b": ((n, r, H * c["nope_dim"]), "normal",
                                    r ** -0.5),
            f"{prefix}/attn/wv_b": ((n, r, hv), "normal", r ** -0.5),
            f"{prefix}/attn/wo": ((n, hv, d), "normal", hv ** -0.5 / out),
        }

    def swiglu(prefix, n, width):
        return {f"{prefix}/w_gate": ((n, d, width), "normal", d ** -0.5),
                f"{prefix}/w_up": ((n, d, width), "normal", d ** -0.5),
                f"{prefix}/w_down": ((n, width, d), "normal",
                                     width ** -0.5 / out)}

    m = L - k
    return {
        "final_norm": ((d,), "ones", 0.0),
        "embed": ((v, d), "normal", 0.02),
        "lm_head": ((d, v), "normal", d ** -0.5),
        **attn("dense_layers", k), **swiglu("dense_layers/mlp", k, f),
        **attn("layers", m),
        "layers/moe/router": ((m, d, c["n_routed"]), "normal", d ** -0.5),
        **swiglu("layers/shared", m, fs),
        **swiglu("experts", m * c["n_held"], fe),
    }


def stacked(spec: dict) -> frozenset:
    """The tensors that hold one published tensor per index of their first
    axis: a layer's, or a (layer, expert)'s."""
    return frozenset(k for k in spec
                     if k.split("/")[0] in ("dense_layers", "layers",
                                            "experts"))


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _m(scale, mscale):
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_inv_freq(c: dict) -> np.ndarray:
    """The rope's frequencies, YaRN's equations (module docstring)."""
    d, theta, y = c["rope_dim"], c["rope_theta"], c["yarn"]
    extra = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    inter = extra / y["factor"]

    def corr(r):
        return (d * math.log(y["original_max_position_embeddings"]
                             / (2 * math.pi * r)) / (2 * math.log(theta)))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def softmax_scale(c: dict) -> float:
    y = c["yarn"]
    return ((c["nope_dim"] + c["rope_dim"]) ** -0.5
            * _m(y["factor"], y["mscale_all_dim"]) ** 2)


def _rope(x, c):
    """x (S, heads, rope_dim): rotate-half YaRN rope at positions 0..S-1."""
    s, half = x.shape[0], x.shape[-1] // 2
    y = c["yarn"]
    gain = _m(y["factor"], y["mscale"]) / _m(y["factor"], y["mscale_all_dim"])
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None]
           * jnp.asarray(yarn_inv_freq(c), jnp.float32))
    cos = gain * jnp.cos(ang)[:, None, :]
    sin = gain * jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, scale, compute):
    """q, k (S, H, dq), v (S, H, dv) -> (S, H*dv); causal, one block of
    queries at a time (a loop over the blocks, each recomputed in the
    backward pass)."""
    s, h, _ = q.shape
    blk = math.gcd(s, QUERY_BLOCK)
    kpos = jnp.arange(s)

    def block(i):
        lo = i * blk
        qb = jax.lax.dynamic_slice_in_dim(q, lo, blk)
        sc = jnp.einsum("qhd,khd->hqk", common.operand(qb, compute),
                        common.operand(k, compute),
                        precision=common.HIGHEST) * scale
        mask = kpos[None, :] <= (lo + jnp.arange(blk))[:, None]
        sc = jnp.where(mask[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", common.operand(p, compute),
                          common.operand(v, compute),
                          precision=common.HIGHEST)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(s // blk))
    return out.reshape(s, -1)


def _mla(p, x, c, compute):
    s, H = x.shape[0], c["n_heads"]
    nope, rope, r = c["nope_dim"], c["rope_dim"], c["kv_lora_rank"]
    mm = lambda a, b: common.matmul(a, b, compute)
    q = mm(x, p["wq"]).reshape(s, H, nope + rope)
    kv = mm(x, p["wkv_a"])
    latent = _rms(kv[:, :r], p["ckv_norm"], c["rms_norm_eps"])
    k_rope = _rope(kv[:, r:].reshape(s, 1, rope), c)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], c)], -1)
    k = jnp.concatenate(
        [mm(latent, p["wk_b"]).reshape(s, H, nope),
         jnp.broadcast_to(k_rope, (s, H, rope))], -1)
    v = mm(latent, p["wv_b"]).reshape(s, H, c["v_dim"])
    return mm(_attention(q, k, v, softmax_scale(c), compute), p["wo"])


def _swiglu(p, x, compute):
    mm = lambda a, b: common.matmul(a, b, compute)
    return mm(jax.nn.silu(mm(x, p["w_gate"])) * mm(x, p["w_up"]),
              p["w_down"])


def _moe(p, experts, x, c, compute):
    """(the held experts' part plus the shared experts' on every token of
    x (S, d), the sequence's balance loss)."""
    s = x.shape[0]
    E, k = c["n_routed"], c["top_k"]
    logits = common.matmul(x, p["moe"]["router"], compute)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, k)
    gates = gates * c["routed_scale"]
    def expert(out, xs):
        j, ej = xs
        chose = ids == c["held_offset"] + j                    # (S, k)
        weight = jnp.sum(jnp.where(chose, gates, 0.0), axis=-1)
        # recomputed in the backward pass, one expert at a time
        y = jax.checkpoint(lambda ej, x: _swiglu(ej, x, compute))(ej, x)
        return out + weight[:, None] * y, None

    out, _ = jax.lax.scan(expert, _swiglu(p["shared"], x, compute),
                          (jnp.arange(c["n_held"]), experts))
    count = jnp.sum(jax.nn.one_hot(ids, E), axis=(0, 1))
    aux = E * jnp.sum(count / (s * k) * jnp.mean(probs, axis=0))
    return out, aux


def _layer(p, x, c, compute, experts=None):
    x = x + _mla(p["attn"], _rms(x, p["ln1"], c["rms_norm_eps"]), c, compute)
    h = _rms(x, p["ln2"], c["rms_norm_eps"])
    if experts is None:
        return x + _swiglu(p["mlp"], h, compute), jnp.float32(0)
    m, aux = _moe(p, experts, h, c, compute)
    return x + m, aux


def _nest(flat: dict, prefix: str) -> dict:
    """The stack ``prefix``'s tensors, nested by path."""
    out = {}
    for key, v in flat.items():
        if key.startswith(prefix + "/"):
            node = out
            parts = key[len(prefix) + 1:].split("/")
            for part in parts[:-1]:
                node = node.setdefault(part, {})
            node[parts[-1]] = v
    return out


def logits(params, tokens, c: dict, compute: str = "f32"):
    """(the output logits of one sequence ``tokens`` (S,), its balance
    loss summed over the MoE layers). The layers run one after another
    (a scan over each stack), each rematerialised in the backward pass."""
    x = params["embed"][tokens]

    def dense(x, lp):
        x, _ = jax.checkpoint(lambda lp, x: _layer(lp, x, c, compute))(lp, x)
        return x, None

    def moe(x, xs):
        lp, ex = xs
        return jax.checkpoint(
            lambda lp, ex, x: _layer(lp, x, c, compute, ex))(lp, ex, x)

    x, _ = jax.lax.scan(dense, x, _nest(params, "dense_layers"))
    experts = {n: w.reshape(-1, c["n_held"], *w.shape[1:])
               for n, w in _nest(params, "experts").items()}
    x, aux = jax.lax.scan(moe, x, (_nest(params, "layers"), experts))
    x = _rms(x, params["final_norm"], c["rms_norm_eps"])
    return common.matmul(x, params["lm_head"], compute), jnp.sum(aux)


def _sequence(params, tokens, labels, c, smoothing, compute):
    """(summed label-smoothed NLL, labelled positions, balance loss summed
    over the MoE layers) of one sequence."""
    out, aux = logits(params, tokens, c, compute)
    nll, n = common.smoothed_nll_sum(out, labels, smoothing)
    return nll, n, aux


def loss(params, batch, *, c: dict, smoothing: float, compute: str = "f32"):
    """(the batch's mean label-smoothed next-token loss over its labelled
    positions, {}), one sequence at a time; its gradient also holds the
    balance loss's, aux_loss_alpha times the sum over MoE layers of the
    mean over sequences."""
    def body(acc, xs):
        nll, n, aux = jax.checkpoint(
            lambda p, t, l: _sequence(p, t, l, c, smoothing, compute))(
                params, *xs)
        return (acc[0] + nll, acc[1] + n, acc[2] + aux), None

    zero = (jnp.float32(0), jnp.int32(0), jnp.float32(0))
    (nll, n, aux), _ = jax.lax.scan(body, zero,
                                    (batch["tokens"], batch["labels"]))
    balance = c["aux_alpha"] * aux / batch["tokens"].shape[0]
    return nll / n + balance - jax.lax.stop_gradient(balance), {}
