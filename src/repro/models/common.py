"""Shared model building blocks.

Parameters are described *abstractly* first (``PD`` descriptors carrying
shape/dtype/PartitionSpec/initializer) and materialized by
``repro.core.pinit`` — this is what makes the paper's §III-B.1
broadcast-free parallel initialization possible: every process derives the
same per-leaf key from the tree path and a shared seed, and ``jit`` with
sharded ``out_shardings`` materializes only the local shard.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class PD:
    """Abstract parameter descriptor (a pytree leaf)."""
    shape: Tuple[int, ...]
    spec: Any = P()                  # PartitionSpec
    init: str = "normal"             # normal | zeros | ones
    scale: float = 0.02
    dtype: Any = jnp.float32
    # leading axes along which the array stacks published tensors (a
    # layer stack, an expert bank): LARS scales each tensor on its own
    stack: int = 0


def pd_stack(tree, n: int):
    """Add a leading layer dim of size n to every descriptor (for scanned
    layer stacks); the leading dim is unsharded."""
    def f(pd):
        return dataclasses.replace(pd, shape=(n, *pd.shape),
                                   spec=P(None, *pd.spec),
                                   stack=pd.stack + 1)
    return jax.tree.map(f, tree, is_leaf=lambda x: isinstance(x, PD))


def dense_pd(d_in: int, d_out: int, *, spec=None,
             scale: Optional[float] = None, dtype=jnp.float32) -> PD:
    if spec is None:
        spec = P()
    if scale is None:
        scale = d_in ** -0.5
    return PD((d_in, d_out), spec=spec, init="normal", scale=scale, dtype=dtype)


# ---------------------------------------------------------------------------
# numerics


def rms_norm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention factor m(s, mu) = 0.1 * mu * ln s + 1 (1 for s <= 1)."""
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN's rotary frequencies (arXiv:2309.00071; DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``), float64, for i = 0 .. dim/2 - 1:

        extra_i    = theta^(-2i/dim)                 the published rope
        inter_i    = extra_i / factor                interpolated
        corr(r)    = dim * ln(orig / (2 pi r)) / (2 ln theta)
        low, high  = floor(corr(beta_fast)), ceil(corr(beta_slow)),
                     clipped to [0, dim - 1]
        ramp_i     = clip((i - low) / (high - low), 0, 1)
        inv_freq_i = inter_i * ramp_i + extra_i * (1 - ramp_i)

    The fast dimensions (i <= low) keep their frequency, the slow ones
    (i >= high) are divided by ``factor``. DeepSeek-V2 (dim 64, theta 1e4,
    orig 4096, beta 32 and 1): low 10, high 23."""
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / yarn.factor

    def corr(rotations):
        return (dim * math.log(yarn.original_max_position
                               / (2 * math.pi * rotations))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def rope(x, positions, theta: float, yarn=None):
    """Rotary embedding, rotate-half form. x: (..., S, H, Dh); positions:
    (..., S). With ``yarn`` (a ``configs.base.YarnConfig``) the
    frequencies are ``yarn_inv_freq``'s and cos and sin take the factor
    m(factor, mscale) / m(factor, mscale_all_dim), 1 for DeepSeek-V2."""
    dh = x.shape[-1]
    half = dh // 2
    if yarn is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        gain = 1.0
    else:
        freqs = jnp.asarray(yarn_inv_freq(dh, theta, yarn), jnp.float32)
        gain = (yarn_mscale(yarn.factor, yarn.mscale)
                / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., :, None, :]   # (..., S, 1, half)
    sin = jnp.sin(ang)[..., :, None, :]
    if gain != 1.0:
        cos, sin = cos * gain, sin * gain
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def dp_axes(mesh) -> tuple:
    """All mesh axes that carry the batch (everything but 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def constrain(x, mesh, spec):
    from jax.sharding import NamedSharding
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def causal_mask_block(qpos, kpos, window: int = 0):
    """(Q, K) boolean mask (True = attend) for absolute positions."""
    m = kpos[None, :] <= qpos[:, None]
    if window:
        m &= kpos[None, :] > (qpos[:, None] - window)
    return m
