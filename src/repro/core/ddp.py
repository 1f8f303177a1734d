"""Gradient all-reduce strategies (paper §III-C).

Used inside a ``shard_map`` train step over the data(/pod) mesh axes so the
collective pattern is explicit and controllable:

* ``naive``    — one psum per parameter tensor (the baseline whose overhead
                 the paper attacks: "allreduce per each layer leads to large
                 overhead ... if the data size of gradient is small").
* ``bucketed`` — the paper's optimization: gradients are packed into
                 several-MB flat buckets built in backward-completion order
                 (static layer groups, §III-C.2) and one collective is
                 issued per bucket as soon as its group's backward is done.
* any name in ``repro.comm.registry`` (``psum``, ``ring``, ``hierarchical``,
  ``2d_torus``, ``dbtree``) — same bucket plan, but the per-bucket
  collective is the named composable schedule instead of a fused psum
  (``bucketed`` is an alias for ``psum``). See docs/comm.md.

Two issue points for the bucket collectives: ``allreduce_grads`` runs them
after the full backward pass (PR-2 behaviour, ``CommConfig.overlap=False``),
while ``wrap_params_for_overlap`` plants them *inside* the backward via a
per-bucket ``custom_vjp`` so each group's all-reduce overlaps the rest of
the backward (paper §III-C.2, the default).
* ``xla``      — handled in train/step.py: no explicit collectives; GSPMD
                 inserts them (the tensor-parallel configs).
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import bucketing
from repro.core.compat import axes_size
from repro.core.precision import grads_to_comm, grads_to_master


def allreduce_grads(grads, *, strategy: str, axes: Sequence[str],
                    plan: "bucketing.BucketPlan" = None,
                    comm_dtype=jnp.bfloat16, use_kernel: bool = False,
                    interpret: bool = None):
    """Reduce-mean gradients over the data-parallel mesh axes.
    Must be called inside shard_map. Returns fp32 gradients.

    ``comm_dtype`` is the wire dtype (paper §IV: bf16; f32 reproduces the
    full-precision baseline); ``use_kernel`` swaps the ring schedules' inner
    fold for the Pallas ring-step kernel. Each bucket's collective runs
    under the named scope ``ar_b<k>``."""
    n = axes_size(axes)

    if strategy == "naive":
        comm = grads_to_comm(grads, dtype=comm_dtype)   # half on the wire
        red = jax.tree.map(lambda g: jax.lax.psum(g, tuple(axes)), comm)
        return jax.tree.map(lambda g: g.astype(jnp.float32) / n, red)

    from repro.comm import get_schedule
    schedule = get_schedule(strategy)
    assert plan is not None
    bufs = bucketing.pack(grads, plan, dtype=comm_dtype)
    # one collective per static bucket group, in backward-completion
    # order; payload is the paper's "several megabytes"
    out = []
    for b, buf in enumerate(bufs):
        with jax.named_scope(f"ar_b{b}"):
            out.append(schedule(buf, tuple(axes), use_kernel=use_kernel,
                                interpret=interpret))
    red = bucketing.unpack(out, plan, dtype=jnp.float32)
    return jax.tree.map(lambda g: g / n, red)


def _overlap_bucket_fn(gi, slots, schedule, axes, comm_dtype, use_kernel,
                       interpret):
    """custom_vjp identity over one bucket group's param leaves whose
    backward rule packs the group's cotangents, runs the collective, and
    returns the reduced-mean fp32 gradients — so the collective sits inside
    the backward graph, data-dependent only on this group's grads. The
    packing and the collective run under the named scope ``ar_b<gi>``."""
    @jax.custom_vjp
    def bucket_identity(leaves):
        return leaves

    def fwd(leaves):
        return leaves, None

    def bwd(_, gs):
        with jax.named_scope(f"ar_b{gi}"):
            buf = bucketing.pack_group(gs, slots, dtype=comm_dtype)
            buf = schedule(buf, axes, use_kernel=use_kernel,
                           interpret=interpret)
        n = axes_size(axes)
        pieces = bucketing.unpack_group(buf, slots, dtype=jnp.float32)
        outs = []
        for slot, g, piece in zip(slots, gs, pieces):
            if piece.shape == g.shape:          # slot covers the whole leaf
                outs.append(piece / n)
                continue
            # split span: scatter the reduced span back into the raw
            # cotangent — the leaf's other spans belong to other groups,
            # whose identities (chained) reduce them in turn
            flat = g.astype(jnp.float32).reshape(-1)
            flat = jax.lax.dynamic_update_slice(flat, piece / n,
                                                (slot.elem_offset,))
            outs.append(flat.reshape(g.shape))
        return (tuple(outs),)

    bucket_identity.defvjp(fwd, bwd)
    return bucket_identity


def _wrap_param_groups(params, plan: "bucketing.BucketPlan", make_group_fn,
                       extras=None):
    """Route each bucket group's param leaves through the identity built by
    ``make_group_fn(group_index, group_slots)`` — the shared scaffolding of
    the overlap and probe wraps, including the subtle slot-to-leaf mapping
    (slot i describes leaf ``n-1-slot_tensor_ids[i]``: the plan walks
    reverse flatten order, and a split tensor's spans all map to the one
    leaf). A leaf spanning several groups is CHAINED through their
    identities; groups are applied in DECREASING index order so the
    backward fires them in bucket order (group 0 — the backward-completion
    head — first), matching the overlap schedule. ``extras[gi]`` (e.g. a
    gradient sink) is passed as a second argument to group gi's identity
    when given."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    n_leaves = len(leaves)
    assert n_leaves == plan.n_tensors
    new_leaves = list(leaves)
    leaf_idx = {id(slot): n_leaves - 1 - t
                for t, slot in zip(plan.slot_tensor_ids, plan.slots)}
    for gi in range(len(plan.groups) - 1, -1, -1):
        group = plan.groups[gi]
        idxs = [leaf_idx[id(s)] for s in group]
        fn = make_group_fn(gi, group)
        args = (tuple(new_leaves[j] for j in idxs),)
        if extras is not None:
            args += (extras[gi],)
        outs = fn(*args)
        for j, o in zip(idxs, outs):
            new_leaves[j] = o
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


def _shard_bucket_fn(gi, slots, finals, rs, axes, comm_dtype, use_kernel,
                     interpret):
    """custom_vjp identity over one bucket group's ``(leaves, sink)`` whose
    backward rule packs the group's cotangents, runs the schedule's
    REDUCE-SCATTER-terminal form, and emits the reduced-mean fp32 local
    shard as the cotangent of the zero-valued ``sink`` (the flax
    ``perturb`` idiom: side outputs of the backward ride on auxiliary
    inputs). The leaves' own cotangents are zeros — the sharded path never
    materializes a full reduced gradient. EXCEPT: a split tensor threads
    through several group identities (chained in ``_wrap_param_groups``),
    and every group after this one in the chain still needs the raw local
    gradient to pack its own span — so only the group holding the tensor's
    FINAL span (``finals[j]``, the last identity to fire) zeroes the leaf
    cotangent; the others pass it through untouched. The packing, the
    reduce-scatter and the mean run under the named scope ``rs_b<gi>``."""
    @jax.custom_vjp
    def bucket_identity(leaves, sink):
        del sink
        return leaves

    def fwd(leaves, sink):
        del sink
        return leaves, None

    def bwd(_, gs):
        with jax.named_scope(f"rs_b{gi}"):
            buf = bucketing.pack_group(gs, slots, dtype=comm_dtype)
            shard = rs(buf, axes, use_kernel=use_kernel, interpret=interpret)
            shard = grads_to_master(shard) / axes_size(axes)
        outs = tuple(jnp.zeros(g.shape, g.dtype) if fin else g
                     for g, fin in zip(gs, finals))
        return (outs, shard)

    bucket_identity.defvjp(fwd, bwd)
    return bucket_identity


def make_shard_sinks(plan: "bucketing.BucketPlan", n_shards: int):
    """Zero-valued gradient sinks for the in-backward reduce-scatter: one
    fp32 ``(bucketing.shard_elems,)`` buffer per bucket. Differentiating a
    ``wrap_params_for_overlap(..., shard_sinks=sinks)``-wrapped loss with
    respect to these yields the per-bucket reduced-mean fp32 local
    gradient shards."""
    return tuple(jnp.zeros((c,), jnp.float32)
                 for c in bucketing.shard_sizes(plan, n_shards))


def wrap_params_for_overlap(params, plan: "bucketing.BucketPlan", *,
                            strategy: str, axes: Sequence[str],
                            comm_dtype=jnp.bfloat16, use_kernel: bool = False,
                            interpret: bool = None, shard_sinks=None):
    """Overlap-aware bucket scheduling (paper §III-C.2).

    Rebuilds ``params`` with each bucket group's leaves routed through an
    identity whose VJP performs that bucket's collective. Differentiating a
    loss of the wrapped params then yields *already reduced-mean* fp32
    gradients, and — unlike ``allreduce_grads``, which runs after the full
    backward pass — each bucket's collective is issued the moment its
    group's cotangents are produced, interleaved with the backward work of
    the earlier (in forward order) layers still to be differentiated. XLA's
    latency-hiding scheduler is then free to overlap collective and compute;
    on CPU the graphs are equivalent, on TPU the comm hides.

    ``shard_sinks`` (from ``make_shard_sinks``) switches each group's
    collective to the schedule's reduce-scatter-terminal form (the ZeRO-1
    in-backward scatter): the backward hands back only this device's
    reduced-mean fp32 shard, delivered as the cotangent of the matching
    sink — differentiate the wrapped loss w.r.t. the sinks to collect the
    per-bucket gradient shards. No full reduced gradient ever exists.

    Must be called on the primal params *inside* the differentiated
    function, itself inside ``shard_map`` over ``axes``."""
    if shard_sinks is not None:
        from repro.comm import get_reduce_scatter
        rs = get_reduce_scatter(strategy)
        final_map = {id(s): fin for s, fin in zip(plan.slots,
                                                  plan.slot_is_final_span)}

        def shard_fn(gi, group):
            finals = tuple(final_map[id(s)] for s in group)
            return _shard_bucket_fn(gi, group, finals, rs, tuple(axes),
                                    comm_dtype, use_kernel, interpret)

        return _wrap_param_groups(params, plan, shard_fn,
                                  extras=shard_sinks)
    from repro.comm import get_schedule
    schedule = get_schedule(strategy)
    return _wrap_param_groups(
        params, plan,
        lambda gi, group: _overlap_bucket_fn(gi, group, schedule,
                                             tuple(axes), comm_dtype,
                                             use_kernel, interpret))


# --------------------------------------------------------------------------
# ZeRO-1 sharded-update path (CommConfig.shard_update; docs/comm.md)

def reduce_scatter_grads(grads, *, strategy: str, axes: Sequence[str],
                         plan: "bucketing.BucketPlan",
                         comm_dtype=jnp.bfloat16, use_kernel: bool = False,
                         interpret: bool = None):
    """POST-backward scatter (the ``CommConfig.overlap=False`` sharded
    path; with overlap on, ``wrap_params_for_overlap(shard_sinks=...)``
    issues the same reduce-scatters from inside the backward instead):
    pack gradients into the bucket plan and stop each bucket's collective
    at the reduce-scatter. Returns one fp32 reduced-MEAN shard per bucket
    — this device's contiguous CHUNK-aligned 1/n slice
    (``comm.primitives.shard_index`` layout), already reduced over every
    non-shard axis. Each bucket's scatter runs under the named scope
    ``rs_b<k>``. Must be called inside shard_map."""
    from repro.comm import get_reduce_scatter
    rs = get_reduce_scatter(strategy)
    n = axes_size(axes)
    bufs = bucketing.pack(grads, plan, dtype=comm_dtype)
    shards = []
    for b, buf in enumerate(bufs):
        with jax.named_scope(f"rs_b{b}"):
            shards.append(grads_to_master(
                rs(buf, tuple(axes), use_kernel=use_kernel,
                   interpret=interpret)) / n)
    return shards


def all_gather_params(param_shards, plan: "bucketing.BucketPlan", *,
                      shard_axis: str, wire_dtype=jnp.bfloat16):
    """Gather phase: cast each fp32 master shard to the wire dtype once
    (bf16 by default — half the bytes of the fp32 grad all-gather the
    replicated path pays), ring all-gather along the shard axis, and unpack
    into the full param pytree. One independent collective per bucket, so
    a latency-hiding scheduler can slide each gather under surrounding
    compute. Must be called inside shard_map. Each bucket's gather runs
    under the named scope ``ag_b<k>``."""
    from repro.comm import primitives as prim
    bufs = []
    for b, shard in enumerate(param_shards):
        wire = grads_to_comm(shard, dtype=wire_dtype)
        with jax.named_scope(f"ag_b{b}"):
            bufs.append(prim.ring_all_gather(wire, shard_axis,
                                             plan.bucket_sizes[b]))
    return bucketing.unpack(bufs, plan, dtype=jnp.float32)


def gather_ahead_params(shards, plan: "bucketing.BucketPlan", *,
                        shard_axis: str, wire_dtype=jnp.bfloat16):
    """Gather-AHEAD: rebuild this step's forward params from the persistent
    master shards (``train.state.TrainState.shards``, updated by the
    previous step) at the START of the step. Each bucket's all-gather is an
    independent collective whose consumers are that bucket group's layers,
    so XLA's latency-hiding scheduler slides every gather under the forward
    compute of earlier layers — the AG leaves the step's critical path
    entirely (the timeline ``comm.autotune.simulate(shard_update=True,
    gather_ahead=True)`` prices). The fp32 masters never round-trip through
    the wire dtype: only this forward copy is quantized.

    Same collective schedule as ``all_gather_params`` — only the issue
    point (step start, from the persistent shards) differs. Must be called
    inside shard_map with the shards' local view."""
    return all_gather_params(shards, plan, shard_axis=shard_axis,
                             wire_dtype=wire_dtype)


# --------------------------------------------------------------------------
# ZeRO-3 just-in-time gather (CommConfig.sharding='zero3'; docs/comm.md)

def jit_gather_params(shards, plan: "bucketing.BucketPlan", *,
                      shard_axis: str, wire_dtype=jnp.bfloat16):
    """ZeRO-3 gather: rebuild the forward params from the persistent master
    shards with per-GROUP lifetimes — called *inside* the differentiated
    function, so no full replica ever lives in ``TrainState``.

    The memory contract is the difference from ``all_gather_params``: that
    path keeps every bucket's wire buffer live until one tree-wide unpack
    (a full wire image, O(N) scratch). Here each group's buffer is unpacked
    into its own fp32 leaves immediately, so a group's wire scratch dies as
    soon as its leaves exist, and the leaves themselves die once the last
    layer of that group has consumed them — XLA's liveness sees O(largest
    bucket group), not O(N). Each group's all-gather has only that group's
    layers as consumers, so the latency-hiding scheduler streams gather
    ``g`` under the forward compute of the groups already gathered (the
    forward walks groups in REVERSE packing order: bucket 0 holds the last
    layers). Each group's gather runs under the named scope ``ag_g<gi>``, a
    distinct name from the ZeRO-1 ``ag_b<k>`` step-boundary gathers. Must
    be called inside shard_map with the shards' local view."""
    from repro.comm import primitives as prim
    vals_slot_order = []
    for gi, group in enumerate(plan.groups):
        wire = grads_to_comm(shards[gi], dtype=wire_dtype)
        with jax.named_scope(f"ag_g{gi}"):
            buf = prim.ring_all_gather(wire, shard_axis,
                                       plan.bucket_sizes[gi])
        vals_slot_order.extend(
            bucketing.unpack_group(buf, group, dtype=jnp.float32))
    # groups concatenate back to plan.slots order (buckets are assigned in
    # packing order); reassemble split tensors from their flat span pieces
    leaves_slot_order, pieces = [], []
    for slot, fin, v in zip(plan.slots, plan.slot_is_final_span,
                            vals_slot_order):
        if slot.elem_offset == 0 and fin:       # unsplit: already reshaped
            leaves_slot_order.append(v)
            continue
        pieces.append(v)
        if fin:
            leaves_slot_order.append(
                jnp.concatenate(pieces).reshape(slot.shape))
            pieces = []
    return jax.tree_util.tree_unflatten(plan.treedef,
                                        list(reversed(leaves_slot_order)))


# --------------------------------------------------------------------------
# backward-profile probes (comm/autotune.measure_backward_profile)

def _probe_bucket_fn(group_idx: int, probe):
    @jax.custom_vjp
    def bucket_identity(leaves):
        return leaves

    def fwd(leaves):
        return leaves, None

    def bwd(_, gs):
        # tie the callback to the cotangent values so it fires exactly when
        # this group's gradients materialize, not at trace time
        dep = jnp.int32(0)
        for g in gs:
            dep = dep + (g.reshape(-1)[0] * 0).astype(jnp.int32)
        jax.debug.callback(probe, jnp.int32(group_idx) + dep)
        return (gs,)

    bucket_identity.defvjp(fwd, bwd)
    return bucket_identity


def wrap_params_for_probe(params, plan: "bucketing.BucketPlan", probe):
    """Measurement twin of ``wrap_params_for_overlap``: the same per-group
    custom-vjp identities, but the backward rule calls ``probe(group_idx)``
    on the host at the moment the group's cotangents exist (and passes them
    through unchanged) — the capture points for the measured backward
    profile. Runs anywhere (no collectives, no shard_map needed)."""
    return _wrap_param_groups(
        params, plan, lambda gi, group: _probe_bucket_fn(gi, probe))


def mark_backward_start(loss, probe, idx: int = -1):
    """Identity on the scalar loss whose VJP stamps ``probe(idx)`` when the
    backward pass begins (the cotangent of the loss is the first value the
    backward produces)."""
    @jax.custom_vjp
    def ident(v):
        return v

    def fwd(v):
        return v, None

    def bwd(_, ct):
        jax.debug.callback(probe, jnp.int32(idx) + (ct * 0).astype(jnp.int32))
        return (ct,)

    ident.defvjp(fwd, bwd)
    return ident(loss)


def mark_forward_start(params, probe, idx: int = -2):
    """Identity on the param pytree whose primal stamps ``probe(idx)`` when
    the first parameter leaf materializes — i.e. at program start, which on
    a compute-ordered backend is the start of the forward pass. Pairs with
    :func:`mark_backward_start`: the gap between the two stamps is the
    measured ``t_forward`` ``comm.autotune.measure_backward_profile``
    records (replacing the old t_backward/2 heuristic)."""
    leaves = jax.tree_util.tree_leaves(params)
    if not leaves:
        return params
    first = leaves[0]
    dep = (first.reshape(-1)[0] * 0).astype(jnp.int32)
    jax.debug.callback(probe, jnp.int32(idx) + dep)
    return params
