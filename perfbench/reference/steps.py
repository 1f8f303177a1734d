"""Three LARS training steps of a reference model from the seed, in
float32 at ``Precision.HIGHEST`` (or in the control's precision), with
the readings the oracle compares."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import common


def change_norms(spec: dict):
    """Jitted (params {path: array}, seed) -> {path: ||w - w0||}, with w0
    drawn again from the seed tensor by tensor, so that no second copy of
    the weights is held."""
    def fn(params, seed):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            params[k].astype(jnp.float32)
            - common.init_leaf(seed, k, *spec[k])))) for k in spec}
    return jax.jit(fn)


class Reference:
    """A reference model's jitted initialisation, step and readings, built
    once and run for any seed. ``loss_fn(params, batch)`` -> (mean loss,
    batch statistics {layer: (mean, variance)}); ``stacked``: the tensors
    that hold one tensor per layer."""

    def __init__(self, loss_fn, spec: dict, opt: dict, stacked=frozenset()):
        self.spec = spec

        def one(params, mom, batch, lr):
            (loss, stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            new_p, new_v = common.lars_step(params, grads, mom, lr, opt,
                                            stacked)
            return (loss, stats, common.leaf_norms(grads), new_p, new_v,
                    common.leaf_norms(new_v))

        self.init = jax.jit(lambda s: common.init_tree(spec, s))
        self.step = jax.jit(one, donate_argnums=(0, 1))
        self.change = change_norms(spec)

    def run(self, seed: int, batches, sched: dict, device=None) -> dict:
        """The losses of three steps on ``batches``, the batch statistics of
        the first (``stats``, {layer: (mean, variance)} as host arrays), the
        raw gradient norms of step 0 (``grad0``), the momentum after one
        step over the first learning rate (``grad1``) and the weights'
        change over the three steps (``change3``), each {path: norm}."""
        with jax.default_matmul_precision("highest"), \
                jax.default_device(device):
            params = self.init(jnp.int32(seed))
            mom = jax.tree.map(jnp.zeros_like, params)
            out = {"losses": []}
            for k, batch in enumerate(batches[:3]):
                lr = common.learning_rate(k, sched)
                loss, stats, gnorm, params, mom, vnorm = self.step(
                    params, mom, batch, jnp.float32(lr))
                out["losses"].append(float(loss))
                if k == 0:
                    out["stats"] = {p: (np.asarray(m), np.asarray(v))
                                    for p, (m, v) in stats.items()}
                    out["grad0"] = {p: float(v) for p, v in gnorm.items()}
                    out["grad1"] = {p: float(v) / lr
                                    for p, v in vnorm.items()}
            del mom
            out["change3"] = {p: float(v) for p, v in self.change(
                params, jnp.int32(seed)).items()}
        return out
