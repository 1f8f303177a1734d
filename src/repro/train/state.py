"""Train state: fp32 master params + momentum (paper's mixed-precision
scheme keeps the update in fp32), BN statistics for the conv family."""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import lars, pinit


class TrainState(NamedTuple):
    step: jax.Array
    params: Any          # fp32 master; ZeRO-1: the gathered forward copy;
                         # ZeRO-3: None — params exist only transiently
                         # inside the step (ddp.jit_gather_params)
    mom: Any             # fp32 momentum buffers; sharded: packed shard bufs
    bn_state: Any = None # resnet only
    shards: Any = None   # ZeRO-1/3: persistent fp32 master shards, one flat
                         # buffer per bucket in the device-major rotated
                         # layout (bucketing.rotate_to_shards). When set,
                         # these are the authoritative masters; with
                         # gather='ahead' the ``params`` copy lags them by
                         # one update (it is what the last forward ran on).


def init_packed_momentum(plan, n_shards: int = 1):
    """ZeRO-1 sharded momentum (CommConfig.shard_update): one flat fp32
    buffer per bucket, global shape ``(n_shards * bucketing.shard_elems,)``,
    partitioned over the shard axis by the train step's shard_map specs.

    Layout is DEVICE-major, not bucket-linear: global rows
    ``[r*c, (r+1)*c)`` persist the momentum of whatever bucket chunk the
    device at shard-axis index r owns — chunk ``(r+1) % n_shards`` under
    the ring layout (``comm.primitives.shard_index``) — so the buffer is
    chunk-rotated relative to the packed param order. Self-consistent
    across steps; any tooling unpacking it by bucket offset must undo the
    rotation first."""
    from repro.core import bucketing
    return tuple(
        jnp.zeros((n_shards * bucketing.shard_elems(s, n_shards),),
                  jnp.float32) for s in plan.bucket_sizes)


def init_packed_shards(params, plan, n_shards: int = 1):
    """ZeRO-1 persistent master shards: pack the fp32 params into the
    bucket plan's flat buffers and rotate each into the device-major
    sharded layout (``bucketing.rotate_to_shards`` — same convention as
    ``init_packed_momentum``). Partitioned over the shard axis by the
    train step's shard_map specs; updated in place by the sharded step
    every step, so the fp32 masters never round-trip through the wire
    dtype."""
    from repro.core import bucketing
    bufs = bucketing.pack(params, plan, dtype=jnp.float32)
    return tuple(bucketing.rotate_to_shards(b, n_shards) for b in bufs)


def full_params_from_shards(shards, plan, n_shards: int = 1):
    """Reassemble the full fp32 master param pytree from the persistent
    shard buffers (host/global view, outside shard_map) — the exact
    inverse of ``init_packed_shards``. This is the authoritative read of a
    sharded ``TrainState``: with gather-ahead the ``params`` field lags
    the shards by one update."""
    from repro.core import bucketing
    bufs = [bucketing.unrotate_shards(b, n_shards)[:plan.bucket_sizes[i]]
            for i, b in enumerate(shards)]
    return bucketing.unpack(bufs, plan, dtype=jnp.float32)


def host_snapshot(state: TrainState) -> TrainState:
    """Full host-side copy of the state (numpy leaves) — what the guard's
    in-memory rollback ring stores (train/guard.py): cheap relative to a
    checkpoint commit (no serialization, no fsync) and layout-agnostic
    (shards/momentum/bn ride along as-is, ZeRO-3's ``params=None``
    included)."""
    return jax.device_get(state)


def restore_snapshot(host_state: TrainState) -> TrainState:
    """Inverse of :func:`host_snapshot`: the numpy leaves back onto
    devices. Placement is uncommitted — the jitted step's in_specs (or
    GSPMD) re-place them on the next dispatch, so a rollback never needs
    to know the mesh."""
    return jax.device_put(host_state)


def init_state(model, seed: int = 0, mesh=None, opt_kind: str = "lars",
               sharded_plan=None, n_shards: int = 1,
               materialize_params: bool = True,
               shard_params: bool = True) -> TrainState:
    """``sharded_plan`` (a ``BucketPlan``, typically
    ``train_step.bucket_plan``) switches the momentum leaves to the packed
    sharded layout expected by ``CommConfig.sharding='zero1'|'zero2'|
    'zero3'`` steps and materializes the persistent master shards.
    ``materialize_params=False`` (the ZeRO-3 state) drops the full
    ``params`` replica after packing the shards — every full-params read
    must then go through ``full_params_from_shards`` (or the loop's
    ``authoritative_params`` reader). ``shard_params=False`` (the ZeRO-2
    state) keeps the replicated fp32 ``params`` as the authoritative
    masters and packs only the momentum: ``shards`` stays None and the
    zero2 step slices its transient master shard per bucket itself."""
    params = pinit.materialize(model.param_pd, seed, mesh)
    shards = None
    if sharded_plan is not None:
        mom = init_packed_momentum(sharded_plan, n_shards)
        if shard_params:
            shards = init_packed_shards(params, sharded_plan, n_shards)
            if not materialize_params:
                params = None
        else:
            assert materialize_params, \
                "shard_params=False (ZeRO-2) keeps the replicated masters"
    else:
        assert materialize_params, \
            "materialize_params=False requires a sharded_plan (ZeRO-3)"
        mom = lars.init_momentum(params, opt_kind)
    bn = None
    if model.bn_state_pd is not None:
        bn = pinit.materialize(model.bn_state_pd, seed, mesh)
    state = TrainState(jnp.zeros((), jnp.int32), params, mom, bn, shards)
    if mesh is None:
        return state
    # place every leaf where the step on this mesh returns it (the spec
    # rules of step.sharded_call), so that the jitted step's second call
    # reuses its first call's executable
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.comm.cost import shard_axis_size
    rep = NamedSharding(mesh, P())
    per_param = pinit.shardings(model.param_pd, mesh)
    split = NamedSharding(mesh, P(shard_axis_size(mesh.axis_names,
                                                  mesh.devices.shape)[0]))
    if sharded_plan is not None:
        mom_sh = jax.tree.map(lambda _: split, mom)
    elif opt_kind == "lamb":
        mom_sh = {"m": per_param, "v": per_param, "count": rep}
    else:
        mom_sh = per_param
    return jax.device_put(state, TrainState(
        rep, None if params is None else per_param, mom_sh,
        None if bn is None else pinit.shardings(model.bn_state_pd, mesh),
        None if shards is None else jax.tree.map(lambda _: split, shards)))


def abstract_state(model) -> TrainState:
    """ShapeDtypeStruct state (for .lower() without allocation)."""
    params = pinit.abstract(model.param_pd)
    mom = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                       params)
    bn = (pinit.abstract(model.bn_state_pd)
          if model.bn_state_pd is not None else None)
    return TrainState(jax.ShapeDtypeStruct((), jnp.int32), params, mom, bn)


def state_specs(model) -> TrainState:
    """PartitionSpec pytree for the state."""
    from jax.sharding import PartitionSpec as P
    pspec = pinit.specs(model.param_pd)
    bn = (pinit.specs(model.bn_state_pd)
          if model.bn_state_pd is not None else None)
    return TrainState(P(), pspec, pspec, bn)
