"""The one traffic generator: a pool of training batches made on the
device from the seed, at set-up, in one jitted call.

Kinds of input (``inputs`` in a traffic file):

- ``prototype_images``: class-prototype images, the arithmetic of the
  training stack's synthetic ImageNet: a label drawn uniformly, that
  class's prototype image (standard normal, fixed by the seed) plus
  ``noise`` times standard normal noise, flipped left to right with
  probability ``flip``. Images (B, size, size, 3) float32, labels (B,)
  int32. Each class's prototype is drawn from its own key, so that no
  table of all the classes is held.
- ``uniform_tokens``: token ids drawn uniformly from the vocabulary;
  labels are the next token, the last position unlabelled (-1). Tokens
  and labels (B, seq_len) int32.

Every batch of the pool is drawn from its own key, so all its rows
differ from every other batch's.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

IGNORE = -1
_POOL_TAG = zlib.crc32(b"perfbench/pool")


def _prototype_images(key, batch: int, size: int, classes: int,
                      noise: float, flip: float):
    k_lab, k_noise, k_flip, k_proto = jax.random.split(key, 4)
    labels = jax.random.randint(k_lab, (batch,), 0, classes)
    protos = jax.vmap(lambda c: jax.random.normal(
        jax.random.fold_in(k_proto, c), (size, size, 3)))(labels)
    imgs = protos + noise * jax.random.normal(k_noise, (batch, size, size, 3))
    flips = jax.random.bernoulli(k_flip, flip, (batch,))
    imgs = jnp.where(flips[:, None, None, None], imgs[:, :, ::-1], imgs)
    return {"images": imgs.astype(jnp.float32),
            "labels": labels.astype(jnp.int32)}


def _uniform_tokens(key, batch: int, seq: int, vocab: int):
    stream = jax.random.randint(key, (batch, seq), 0, vocab)
    labels = jnp.concatenate(
        [stream[:, 1:], jnp.full((batch, 1), IGNORE, stream.dtype)], 1)
    return {"tokens": stream.astype(jnp.int32),
            "labels": labels.astype(jnp.int32)}


def batch_fn(traffic: dict, sizes: dict, batch: int):
    """key -> one batch, for the traffic's ``inputs``; ``sizes`` are the
    configuration's (image_size and n_classes, or vocab_size)."""
    kind = traffic["inputs"]
    if kind == "prototype_images":
        return lambda key: _prototype_images(
            key, batch, sizes["image_size"], sizes["n_classes"],
            traffic["noise"], traffic["flip"])
    if kind == "uniform_tokens":
        return lambda key: _uniform_tokens(key, batch, traffic["seq_len"],
                                           sizes["vocab_size"])
    raise ValueError(f"unknown inputs {kind!r}")


def make_pool(traffic: dict, sizes: dict, batch: int, seed: int,
              sharding=None):
    """A tuple of ``traffic['pool_batches']`` batches, each laid out by
    ``sharding`` (rows over the data-parallel chips)."""
    one = batch_fn(traffic, sizes, batch)
    n = traffic["pool_batches"]

    def pool(seed):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), _POOL_TAG)
        return tuple(one(jax.random.fold_in(key, i)) for i in range(n))

    out = None if sharding is None else tuple(
        jax.tree.map(lambda _: sharding, jax.eval_shape(one,
                                                        jax.random.PRNGKey(0)))
        for _ in range(n))
    return jax.jit(pool, out_shardings=out)(jnp.int32(seed))
