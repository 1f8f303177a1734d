"""Broadcast-free parallel parameter initialization (paper §III-B.1).

The paper replaces the root-process-initializes-then-broadcast pattern with
"every process has the same seed and initializes weights in parallel". The
JAX/SPMD analogue implemented here: each parameter leaf derives a
deterministic PRNG key from (seed, tree-path), so every process computes the
identical initializer with **zero communication**; when a mesh is given the
whole init runs inside ``jit`` with sharded ``out_shardings`` so each device
materializes only its own shard.
"""
from __future__ import annotations

import functools
import zlib
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.models.common import PD

_is_pd = lambda x: isinstance(x, PD)


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _leaf_key(seed: int, path) -> jax.Array:
    h = zlib.crc32(_path_str(path).encode())
    return jax.random.fold_in(jax.random.PRNGKey(seed), h)


def _init_leaf(pd: PD, key) -> jax.Array:
    if pd.init == "zeros":
        return jnp.zeros(pd.shape, pd.dtype)
    if pd.init == "ones":
        return jnp.ones(pd.shape, pd.dtype)
    if pd.init == "const":   # constant fill with value = pd.scale
        return jnp.full(pd.shape, pd.scale, pd.dtype)
    if pd.init == "normal":
        # truncated normal, as in the paper's ResNet logs
        return (pd.scale * jax.random.truncated_normal(
            key, -2.0, 2.0, pd.shape)).astype(pd.dtype)
    raise ValueError(f"unknown init {pd.init!r}")


def specs(tree):
    """PartitionSpec pytree matching the descriptor tree."""
    return jax.tree.map(lambda pd: pd.spec, tree, is_leaf=_is_pd)


def abstract(tree):
    """ShapeDtypeStruct pytree (for .lower() without allocation)."""
    return jax.tree.map(lambda pd: jax.ShapeDtypeStruct(pd.shape, pd.dtype),
                        tree, is_leaf=_is_pd)


def abstract_compute(tree, dtype=jnp.bfloat16):
    """Abstract tree in serving precision (fp32 leaves -> bf16): inference
    holds bf16 weights; fp32 masters exist only in the train state."""
    def f(pd):
        dt = dtype if pd.dtype == jnp.float32 else pd.dtype
        return jax.ShapeDtypeStruct(pd.shape, dt)
    return jax.tree.map(f, tree, is_leaf=_is_pd)


def shardings(tree, mesh):
    return jax.tree.map(lambda pd: NamedSharding(mesh, pd.spec), tree,
                        is_leaf=_is_pd)


@functools.lru_cache(maxsize=16)
def _initializer(treedef, pds, mesh):
    """The jitted initializer of one descriptor tree on one mesh. The seed
    is its argument, so every seed and every later call reuses one
    compiled program."""
    tree = jax.tree_util.tree_unflatten(treedef, pds)

    def build(seed):
        return jax.tree_util.tree_map_with_path(
            lambda path, pd: _init_leaf(pd, _leaf_key(seed, path)),
            tree, is_leaf=_is_pd)

    if mesh is None:
        return jax.jit(build)
    return jax.jit(build, out_shardings=shardings(tree, mesh))


def materialize(tree, seed: int, mesh: Optional[Any] = None):
    """Initialize all parameters, communication-free (see module docstring)."""
    pds, treedef = jax.tree_util.tree_flatten(tree, is_leaf=_is_pd)
    return _initializer(treedef, tuple(pds), mesh)(jnp.int32(seed))
