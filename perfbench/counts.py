"""Operations and bytes that a training step needs, from the shapes alone.

A FLOP is one floating-point addition or multiplication: a
multiply-accumulate is two. Recomputed operations are never counted.

- ResNet-50 (``resnet_convs``): every convolution of the network and the
  final fully connected layer, with its input and output sizes. The
  forward pass of one 224x224 image takes 4.09 G multiply-accumulates; a
  training step takes three times the forward FLOPs (the data gradient of
  the stem, whose input is the image, is not needed: 2.6% less).
- A dense decoder (``decoder_flops_per_token``): 6 N per token, N being the
  weights of the matrix products (attention and feed-forward projections
  and the output head; the embedding lookup is no product), plus the
  attention's score and value products: with causal masking a query at
  position i reads i + 1 keys, 4 * heads * head_dim FLOPs each forward.
"""
from __future__ import annotations

import dataclasses
from typing import List

STAGES = ((3, 64), (4, 128), (6, 256), (3, 512))


@dataclasses.dataclass(frozen=True)
class Conv:
    name: str
    k: int          # square kernel
    cin: int
    cout: int
    h_in: int
    h_out: int
    needs_dgrad: bool = True

    @property
    def macs(self) -> int:
        """Multiply-accumulates of one image's forward pass."""
        return self.k * self.k * self.cin * self.cout * self.h_out ** 2


def _out(h: int, stride: int) -> int:
    return -(-h // stride)      # "SAME" padding


def resnet_convs(width: int = 64, image: int = 224,
                 n_classes: int = 1000) -> List[Conv]:
    """The 53 convolutions of ResNet-50 v1.5 and its fully connected layer
    (a 1x1 "convolution" on a 1x1 map)."""
    convs = []
    h = _out(image, 2)
    convs.append(Conv("stem", 7, 3, width, image, h, needs_dgrad=False))
    h = _out(h, 2)                       # max pooling
    cin = width
    for si, (blocks, base) in enumerate(STAGES):
        base = base * width // 64
        for bi in range(blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            ho, cout = _out(h, stride), base * 4
            name = f"s{si}b{bi}"
            convs.append(Conv(f"{name}/conv1", 1, cin, base, h, h))
            convs.append(Conv(f"{name}/conv2", 3, base, base, h, ho))
            convs.append(Conv(f"{name}/conv3", 1, base, cout, ho, ho))
            if bi == 0:
                convs.append(Conv(f"{name}/proj", 1, cin, cout, h, ho))
            cin, h = cout, ho
    convs.append(Conv("head", 1, cin, n_classes, 1, 1))
    return convs


def resnet_forward_macs(**kw) -> int:
    return sum(c.macs for c in resnet_convs(**kw))


def resnet_train_flops_per_image(**kw) -> int:
    """Forward, data gradient and weight gradient of every convolution and
    of the fully connected layer."""
    return sum(2 * c.macs * (3 if c.needs_dgrad else 2)
               for c in resnet_convs(**kw))


@dataclasses.dataclass(frozen=True)
class Least:
    """The least time the chip could take for a set of passes."""
    seconds: float          # sum over passes of max(flops bound, bytes bound)
    flops_seconds: float    # sum of the flops bounds
    bytes_seconds: float    # sum of the bytes bounds
    compute_bound: int      # passes whose flops bound is the larger
    memory_bound: int


def conv_least_time(batch: int, peak_flops: float, hbm_bytes_per_s: float,
                    dtype_bytes: int = 2, **kw) -> Least:
    """Least time of one training step's convolution passes at ``batch``
    images: per convolution the forward (reads x and w, writes y), the data
    gradient (reads dy and w, writes dx) and the weight gradient (reads x
    and dy, writes dw), each bounded by the larger of its FLOPs over the
    peak and its bytes over the memory bandwidth."""
    total = fl = by = 0.0
    nc = nm = 0
    for c in resnet_convs(**kw):
        x = batch * c.h_in ** 2 * c.cin
        y = batch * c.h_out ** 2 * c.cout
        w = c.k * c.k * c.cin * c.cout
        passes = [x + w + y, x + y + w] + ([y + w + x] if c.needs_dgrad
                                          else [])
        for elems in passes:
            tf = 2 * c.macs * batch / peak_flops
            tb = elems * dtype_bytes / hbm_bytes_per_s
            total += max(tf, tb)
            fl += tf
            by += tb
            nc += tf >= tb
            nm += tf < tb
    return Least(total, fl, by, nc, nm)


def decoder_matmul_weights(c: dict) -> int:
    """N: weights of every matrix product of a dense decoder."""
    d, f = c["d_model"], c["d_ff"]
    hq = c["n_heads"] * c["head_dim"]
    hk = c["n_kv_heads"] * c["head_dim"]
    per_layer = d * hq + 2 * d * hk + hq * d + 3 * d * f
    return c["n_layers"] * per_layer + d * c["vocab_size"]


def decoder_attention_flops_per_token(c: dict, seq: int, *,
                                      causal: bool = True,
                                      passes: int = 3) -> float:
    """Score and value products per token, over ``passes`` (3: forward and
    the two backward products). Causal: position i reads i + 1 keys, a
    mean of (seq + 1) / 2."""
    hq = c["n_heads"] * c["head_dim"]
    keys = (seq + 1) / 2 if causal else seq
    return passes * c["n_layers"] * 4 * hq * keys


def decoder_flops_per_token(c: dict, seq: int) -> float:
    return (6 * decoder_matmul_weights(c)
            + decoder_attention_flops_per_token(c, seq))
