"""What every reference shares: the initial weights from the seed, the
label-smoothed loss, the learning-rate schedule and the LARS step, each
written from its description.

Initial weights. Every parameter is drawn from its own key, the seed's
``PRNGKey`` folded with the CRC-32 of the parameter's path
(``"s0b0/conv1"``): a truncated normal on [-2, 2] times the parameter's
scale, or ones or zeros. This is the rule the training stack states for
its broadcast-free initialisation (paper §III-B.1); the reference draws
its own weights by it and takes none from the program.

Precision. ``compute`` is ``"f32"`` for the reference: every convolution
and matrix product at ``Precision.HIGHEST``. ``"fp8"`` is the control:
the operands of every convolution and matrix product are rounded to
float8 (e4m3 forward, e5m2 for the cotangents, each tensor scaled by its
largest magnitude first), the recipe a lower-precision training step
would use.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
IGNORE = -1


def leaf_key(seed: int, path: str):
    return jax.random.fold_in(jax.random.PRNGKey(seed), zlib.crc32(path.encode()))


def init_leaf(seed: int, path: str, shape, init: str = "normal",
              scale: float = 0.02):
    if init == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    draw = jax.random.truncated_normal(leaf_key(seed, path), -2.0, 2.0, shape)
    return (scale * draw).astype(jnp.float32)


def init_tree(spec: dict, seed):
    """``spec``: {path: (shape, init, scale)} -> {path: array}."""
    return {p: init_leaf(seed, p, s, i, c) for p, (s, i, c) in spec.items()}


# -- lower-precision control -------------------------------------------------

def _round_scaled(x, dtype):
    fmax = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, fmax / amax, 1.0)
    return ((x * scale).astype(dtype).astype(jnp.float32) / scale).astype(x.dtype)


@jax.custom_vjp
def to_fp8(x):
    return _round_scaled(x, jnp.float8_e4m3fn)


def _to_fp8_fwd(x):
    return to_fp8(x), None


def _to_fp8_bwd(_, ct):
    return (_round_scaled(ct, jnp.float8_e5m2),)


to_fp8.defvjp(_to_fp8_fwd, _to_fp8_bwd)


def operand(x, compute: str):
    """An operand of a convolution or matrix product, in ``compute``."""
    if compute == "f32":
        return x
    if compute == "fp8":
        return to_fp8(x)
    raise ValueError(compute)


def matmul(a, b, compute: str = "f32"):
    return jnp.matmul(operand(a, compute), operand(b, compute),
                      precision=HIGHEST)


# -- loss, schedule, optimizer ----------------------------------------------

def smoothed_nll_sum(logits, labels, smoothing: float):
    """Label-smoothed cross entropy (Szegedy et al. 2016):
    (1-e)·NLL(target) + e·mean over classes of NLL(class), summed over the
    positions whose label is not ``IGNORE``. Returns (sum, count)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    valid = labels != IGNORE
    safe = jnp.where(valid, labels, 0)
    tgt = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    nll = -((1.0 - smoothing) * tgt + smoothing * logp.mean(axis=-1))
    return jnp.where(valid, nll, 0.0).sum(), valid.sum()


def learning_rate(step: int, sched: dict) -> float:
    """Linear warm-up to ``base_lr`` over ``warmup`` steps (Goyal et al.),
    then the polynomial decay of power 2 to ``end_lr`` at ``total``."""
    base, warm = sched["base_lr"], sched["warmup"]
    total, end = sched["total"], sched["end_lr"]
    if step < warm:
        return base * (step + 1) / max(warm, 1)
    t = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return (base - end) * (1 - t) ** 2 + end


def _trust(w, g, opt: dict):
    """eta·||w|| / (||g|| + wd·||w|| + eps) for a tensor of two or more
    dimensions, 1 for the others and where ||w|| is 0."""
    if w.ndim < 2:
        return jnp.float32(1.0)
    eta, wd, eps = opt["trust_coef"], opt["weight_decay"], opt["eps"]
    wn = jnp.sqrt(jnp.sum(w * w))
    gn = jnp.sqrt(jnp.sum(g * g))
    return jnp.where(wn > 0, eta * wn / (gn + wd * wn + eps), 1.0)


def lars_step(params: dict, grads: dict, mom: dict, lr: float, opt: dict,
              stacked=frozenset()):
    """LARS (You et al. 2017) with momentum, one trust ratio per layer's
    tensor: v <- mu·v + lr·trust·(g + wd·w), w <- w - v. A tensor named in
    ``stacked`` holds one tensor per layer along its first axis, and each
    of them takes its own trust ratio (1 where a layer's tensor is 1-D,
    as a norm's scale is)."""
    wd, mu = opt["weight_decay"], opt["momentum"]
    new_p, new_v = {}, {}
    for k, w in params.items():
        g = grads[k]
        if k in stacked:
            trust = jax.vmap(lambda w, g: _trust(w, g, opt))(w, g)
            trust = jnp.reshape(trust, (-1,) + (1,) * (w.ndim - 1))
        else:
            trust = _trust(w, g, opt)
        v = mu * mom[k] + (lr * trust) * (g + wd * w)
        new_p[k], new_v[k] = w - v, v
    return new_p, new_v


def leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}
