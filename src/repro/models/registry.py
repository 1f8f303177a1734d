"""Model registry: uniform functional API over every architecture family."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.precision import cast_to_compute
from repro.models import resnet as rn
from repro.models import transformer as tf
from repro.models.common import PD


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    param_pd: Any                      # descriptor pytree
    bn_state_pd: Any = None            # resnet only
    # fns bound below
    train_fn: Callable = None
    prefill_fn: Callable = None
    decode_fn: Callable = None
    cache_pd_fn: Callable = None

    def forward_train(self, params, batch, mesh=None, bn_state=None,
                      counters: bool = False):
        """((logits, aux), new batch-norm state); with ``counters``
        ((logits, aux, counters), ...), the counters a dict of scalars
        (``moe.HELD_STATS`` for held experts, else {})."""
        out, new_bn = self.train_fn(params, batch, mesh, bn_state)
        return (out if counters else out[:2]), new_bn

    @property
    def stack_axes(self):
        """Per parameter, the leading axes along which it stacks published
        tensors (``PD.stack``), for LARS's one ratio per published tensor;
        None where the configuration takes one ratio per stored array
        (``ModelConfig.lars_per_published_tensor``)."""
        if not self.cfg.lars_per_published_tensor:
            return None
        return jax.tree.map(lambda pd: pd.stack, self.param_pd,
                            is_leaf=lambda x: isinstance(x, PD))

    def forward_prefill(self, params, batch, cache_len, mesh=None):
        return self.prefill_fn(params, batch, cache_len, mesh)

    def forward_decode(self, params, cache, token, pos, mesh=None):
        return self.decode_fn(params, cache, token, pos, mesh)

    def cache_pd(self, batch: int, max_seq: int, dp=("data",)):
        return self.cache_pd_fn(batch, max_seq, dp)


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family == "conv":
        params_pd, state_pd = rn.resnet_pd(cfg)

        def train_fn(params, batch, mesh, bn_state):
            logits, new_state = rn.resnet_forward(
                cast_to_compute(params), bn_state, cfg, batch["images"],
                train=True, mesh=mesh)
            return (logits, jnp.float32(0), {}), new_state

        return Model(cfg=cfg, param_pd=params_pd, bn_state_pd=state_pd,
                     train_fn=train_fn)

    pd = tf.lm_pd(cfg)

    def train_fn(params, batch, mesh, bn_state=None):
        return tf.forward_train(cast_to_compute(params), cfg, mesh,
                                batch), None

    def prefill_fn(params, batch, cache_len, mesh):
        return tf.forward_prefill(cast_to_compute(params), cfg, mesh, batch,
                                  cache_len)

    def decode_fn(params, cache, token, pos, mesh):
        return tf.forward_decode(cast_to_compute(params), cfg, mesh, cache,
                                 token, pos)

    def cache_pd_fn(batch, max_seq, dp=("data",)):
        return tf.cache_pd(cfg, batch, max_seq, dp=dp)

    return Model(cfg=cfg, param_pd=pd, train_fn=train_fn,
                 prefill_fn=prefill_fn, decode_fn=decode_fn,
                 cache_pd_fn=cache_pd_fn)
