"""Mesh construction: the one place a ``jax.sharding.Mesh`` is built.

Axes: ``data`` — pure data parallelism (the paper's axis: gradient
all-reduce), ``model`` — tensor/expert parallelism within a pod,
``pod`` — the cross-pod data-parallel axis of the 2-pod production job.

Every axis is ``AxisType.Auto``: the models place activations with
``with_sharding_constraint`` and the explicit-DP schedules run under
``shard_map``, both of which need Auto axes (``jax.make_mesh`` defaults to
Explicit ones).

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

from typing import Sequence, Union

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """Auto-typed mesh of ``shape`` over ``devices`` (default: the first
    ``prod(shape)`` devices jax reports)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1,
                    devices: Union[int, Sequence, None] = None):
    """(data, model) mesh over ``devices``: a list of devices, a count
    taken from the front of ``jax.devices()``, or None for all of them."""
    if devices is None or isinstance(devices, int):
        devices = jax.devices()[:devices]
    n = len(devices)
    if n % model_parallel:
        raise ValueError(f"{n} devices do not split into model_parallel="
                         f"{model_parallel}")
    return make_mesh((n // model_parallel, model_parallel),
                     ("data", "model"), devices=devices)


# TPU v5e hardware constants (roofline targets; see EXPERIMENTS.md §Roofline)
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link (intra-pod 'data'/'model' hops)
ICI_ALPHA = 1e-6              # per-message ICI latency, seconds

# Cross-pod ('pod' axis) data-center interconnect: ~order slower than ICI —
# the asymmetry the hierarchical/2d-torus schedules exploit (comm/cost.py).
DCI_BW = 6.25e9               # bytes/s per host link
DCI_ALPHA = 10e-6             # per-message DCI latency, seconds
