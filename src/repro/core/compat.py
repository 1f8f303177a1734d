"""``shard_map`` and mesh-axis helpers on the installed jax (0.9).

Everything under ``repro`` that needs them imports from here.

* ``shard_map`` — ``jax.shard_map`` with ``check_vma`` off by default: the
  comm schedules are built on ``ppermute``/dynamic indexing, whose
  per-device variance the checker cannot infer statically.
* ``axis_size`` / ``axes_size`` — static size of one or several named mesh
  axes inside ``shard_map`` tracing (Python ints, safe as loop bounds and
  reshape dims).
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def axis_size(axis_name) -> int:
    """Static size of a named mesh axis, inside shard_map/pmap tracing."""
    return jax.lax.axis_size(axis_name)


def axes_size(axes) -> int:
    """Product of the sizes of several named mesh axes."""
    n = 1
    for a in axes:
        n *= axis_size(a)
    return n
