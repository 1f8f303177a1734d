"""ResNet-50 in plain float32 ``jax.numpy``: forward, label-smoothed loss,
gradient and LARS steps.

From He et al., "Deep Residual Learning for Image Recognition"
(arXiv:1512.03385), Table 1: a 7x7/2 stem convolution with 64 channels,
3x3/2 max pooling, then 3, 4, 6 and 3 bottleneck blocks of widths 64,
128, 256 and 512 (expansion 4), global average pooling and a fully
connected layer. Batch normalisation (Ioffe and Szegedy 2015) after every
convolution, with batch statistics in training.

Departures from the paper, each the configuration's own statement:
- the stride of a downsampling block sits on its 3x3 convolution, not on
  the first 1x1 ("ResNet-50 v1.5", as in the MLPerf reference);
- convolutions and pooling pad as TensorFlow's ``SAME`` does (for a
  stride of 2, the extra row and column at the end);
- batch-norm statistics are taken per data-parallel group
  (``bn_groups`` contiguous blocks of the batch, one per chip of an
  explicit data-parallel step), as the paper's §III-A.2 does;
- the convolutions carry no bias (each feeds a batch norm), and batch
  norm uses the biased variance with eps 1e-5.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.reference import common

STAGES = ((3, 64), (4, 128), (6, 256), (3, 512))
EXPANSION = 4
BN_EPS = 1e-5


def param_spec(width: int = 64, n_classes: int = 1000) -> dict:
    """{path: (shape, init, scale)} in the order of the network."""
    spec = {}

    def conv(path, kh, cin, cout):
        spec[path] = ((kh, kh, cin, cout), "normal",
                      (2.0 / (kh * kh * cin)) ** 0.5)

    def bn(path, c):
        spec[f"{path}/scale"] = ((c,), "ones", 0.0)
        spec[f"{path}/bias"] = ((c,), "zeros", 0.0)

    conv("stem/conv", 7, 3, width)
    bn("stem/bn", width)
    cin = width
    for si, (blocks, base) in enumerate(STAGES):
        base = base * width // 64
        for bi in range(blocks):
            name, cout = f"s{si}b{bi}", base * EXPANSION
            conv(f"{name}/conv1", 1, cin, base)
            bn(f"{name}/bn1", base)
            conv(f"{name}/conv2", 3, base, base)
            bn(f"{name}/bn2", base)
            conv(f"{name}/conv3", 1, base, cout)
            bn(f"{name}/bn3", cout)
            if bi == 0:
                conv(f"{name}/proj", 1, cin, cout)
                bn(f"{name}/bn_proj", cout)
            cin = cout
    spec["head/w"] = ((cin, n_classes), "normal", cin ** -0.5)
    spec["head/b"] = ((n_classes,), "zeros", 0.0)
    return spec


def _conv(x, w, stride, compute):
    return jax.lax.conv_general_dilated(
        common.operand(x, compute), common.operand(w, compute),
        (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=common.HIGHEST)


def _bn(x, p, path, groups, stats):
    """Batch norm with the statistics of each of ``groups`` blocks of the
    batch; notes the batch statistics (over the groups, their mean) in
    ``stats[path]`` as (mean, variance) per channel."""
    b = x.shape[0]
    xg = x.reshape(groups, b // groups, *x.shape[1:])
    mean = xg.mean(axis=(1, 2, 3), keepdims=True)
    var = jnp.square(xg - mean).mean(axis=(1, 2, 3), keepdims=True)
    stats[path] = (mean.mean(axis=(0, 1, 2, 3)), var.mean(axis=(0, 1, 2, 3)))
    y = (xg - mean) / jnp.sqrt(var + BN_EPS)
    y = y.reshape(x.shape)
    return y * p[f"{path}/scale"] + p[f"{path}/bias"]


def _block(p, x, name, stride, groups, compute):
    st = {}
    h = jax.nn.relu(_bn(_conv(x, p[f"{name}/conv1"], 1, compute), p,
                        f"{name}/bn1", groups, st))
    h = jax.nn.relu(_bn(_conv(h, p[f"{name}/conv2"], stride, compute), p,
                        f"{name}/bn2", groups, st))
    h = _bn(_conv(h, p[f"{name}/conv3"], 1, compute), p, f"{name}/bn3",
            groups, st)
    if f"{name}/proj" in p:
        sc = _bn(_conv(x, p[f"{name}/proj"], stride, compute), p,
                 f"{name}/bn_proj", groups, st)
    else:
        sc = x
    return jax.nn.relu(h + sc), st


def _stem(p, x, groups, compute):
    st = {}
    x = _conv(x, p["stem/conv"], 2, compute)
    x = jax.nn.relu(_bn(x, p, "stem/bn", groups, st))
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1), "SAME"), st


def logits(params, images, *, groups: int = 1, compute: str = "f32"):
    """images (B, H, W, 3) float32 -> (logits (B, classes) float32, the
    batch statistics of every batch norm {path: (mean, variance)}). Each
    block is rematerialised in the backward pass, so that a whole batch
    fits the chip in float32."""
    x, stats = jax.checkpoint(functools.partial(
        _stem, groups=groups, compute=compute))(params, images)
    for si, (blocks, _) in enumerate(STAGES):
        for bi in range(blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            blk = functools.partial(_block, name=f"s{si}b{bi}", stride=stride,
                                    groups=groups, compute=compute)
            x, st = jax.checkpoint(blk)(params, x)
            stats.update(st)
    x = x.mean(axis=(1, 2))
    out = common.matmul(x, params["head/w"], compute) + params["head/b"]
    return out, stats


def loss(params, batch, *, smoothing: float, groups: int = 1,
         compute: str = "f32"):
    """(mean label-smoothed cross entropy over the batch, the batch
    statistics of every batch norm)."""
    out, stats = logits(params, batch["images"], groups=groups,
                        compute=compute)
    total, n = common.smoothed_nll_sum(out, batch["labels"], smoothing)
    return total / n, stats
