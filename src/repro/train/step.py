"""Train/eval steps.

Two distribution paths (DESIGN.md §2.2):

* ``comm='xla'`` — pjit/GSPMD: batch sharded over data axes, params sharded
  per their PartitionSpecs (tensor/expert-parallel over 'model', optional
  FSDP over 'data'); gradient reduction collectives are inserted by GSPMD.
  Used by every architecture, and the only path for TP/EP models.

* ``comm='bucketed' | 'naive'`` — the paper's §III-C explicit data-parallel
  communication, inside ``shard_map`` over ALL mesh axes (pure DP): grads
  are packed into static several-MB bucket groups in backward-completion
  order and one ``psum`` is issued per bucket ('bucketed'), or one per
  tensor ('naive' — the baseline the paper measures against). Restricted to
  replicated-parameter models (the paper's ResNet-50 and the small LMs).
  With ``CommConfig.overlap`` (the default) each bucket's collective is
  issued from *inside* the backward pass via a per-group custom-vjp
  (``core/ddp.wrap_params_for_overlap``) the moment its layer group's
  gradients are complete — §III-C.2's overlap — and
  ``CommConfig.bucket_mb='auto'`` sizes the buckets with
  ``repro.comm.autotune`` against the alpha-beta cost model.

The loss is label-smoothed cross entropy (paper §III-A.2) + MoE aux; the
optimizer is LARS or momentum-SGD (paper §III-A.1) on fp32 masters with
bf16 compute/communication (paper §IV).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import CommConfig
from repro.core import bucketing, compat, ddp, lars
from repro.core.label_smoothing import IGNORE, smoothed_xent, top1_accuracy
from repro.core.precision import cast_to_compute
from repro.obs import metrics as obs_metrics
from repro.train import guard as guard_lib
from repro.train.state import TrainState


def _lm_loss(logits, labels, *, smoothing):
    S_logits = logits.shape[1]
    S_lab = labels.shape[1] if labels.ndim > 1 else None
    if S_lab is not None and S_logits != S_lab:
        # VLM: image-prefix positions carry no labels
        pad = jnp.full((labels.shape[0], S_logits - S_lab), IGNORE,
                       labels.dtype)
        labels = jnp.concatenate([pad, labels], axis=1)
    return smoothed_xent(logits, labels, smoothing=smoothing)


def make_loss_fn(model, *, smoothing: float = 0.1, aux_coef: float = 0.01,
                 mesh=None):
    """The training loss: label-smoothed cross entropy plus the MoE balance
    loss times ``cfg.moe.router_aux_coef`` (``aux_coef`` for a model
    without experts, whose balance loss is 0). Its metrics hold ``loss``,
    ``aux``, ``acc`` and the model's counters (``held_stat_keys``)."""
    cfg = model.cfg
    if cfg.moe is not None:
        aux_coef = cfg.moe.router_aux_coef

    def loss_fn(params, batch, bn_state=None):
        # every op of the forward carries the ``forward`` scope in its HLO
        # ``op_name``; differentiating it puts the backward's ops under
        # ``transpose(jvp(forward))`` (docs/observability.md)
        with jax.named_scope("forward"):
            (logits, aux, counters), new_bn = model.forward_train(
                params, batch, mesh, bn_state, counters=True)
            loss, n = _lm_loss(logits, batch["labels"], smoothing=smoothing)
            total = loss + aux_coef * aux
            acc = top1_accuracy(logits, batch["labels"]
                                if logits.shape[:-1] == batch["labels"].shape
                                else jnp.full(logits.shape[:-1], IGNORE))
            metrics = {"loss": loss, "aux": aux, "acc": acc, **counters}
        return total, (metrics, new_bn)

    return loss_fn


def held_stat_keys(model) -> tuple:
    """The counters a model's train step reports beside its loss: the held
    experts' (``models.moe.HELD_STATS``), or none."""
    from repro.models.moe import HELD_STATS
    e = model.cfg.moe
    return HELD_STATS if e is not None and e.n_held else ()


def make_train_step(model, opt_cfg: lars.OptConfig, schedule, *,
                    smoothing: float = 0.1, mesh=None, comm: str = "xla",
                    bucket_mb: float = 4.0, comm_dtype: str = "bf16",
                    grad_accum: int = 1, profile_batch=None,
                    guard: bool = False):
    """Returns train_step(state, batch) -> (state, metrics). Not jitted —
    the caller owns jit/shardings (launcher, dryrun, tests).

    comm_dtype='bf16' (paper §IV): gradients are taken w.r.t. the bf16
    compute copy of the weights, so the data-parallel reduction GSPMD
    inserts runs on half-precision tensors; the fp32 upcast happens in the
    optimizer. 'f32' reproduces the fp32-wire baseline.

    ``comm`` is either a strategy name ('xla' | 'naive' | any schedule in
    ``repro.comm.registry``) or a full ``configs.base.CommConfig``, which
    then also carries the bucket_mb ('auto' = autotuned) / wire dtype /
    kernel / overlap / sharding policy / backward_profile knobs.
    With ``CommConfig.sharding='zero1'|'zero3'`` the state must carry the
    packed sharded momentum AND the persistent fp32 master shards
    (``train.state.init_state(..., sharded_plan=train_step.bucket_plan,
    n_shards=train_step.n_shards)``). Under 'zero1' the returned state's
    ``params`` is the gathered forward copy — with ``gather='ahead'``
    (default) it lags the authoritative ``shards`` by one update. Under
    'zero2' the state keeps the REPLICATED fp32 ``params`` as the
    authoritative masters (``shards=None``) and shards only the momentum
    (``init_state(..., sharded_plan=..., n_shards=..., shard_params=
    False)``): the forward runs on the replica with no gather at all, the
    backward reduce-scatters the grads exactly like zero1, the update
    runs on a transient 1/n slice of the packed masters, and one fp32
    step-end all-gather writes the replica back. Under
    'zero3' the state carries NO ``params`` (None): the forward rebuilds
    them per bucket group just-in-time (``ddp.jit_gather_params``) and
    ``gather='per_group'`` (default) re-gathers each group for its
    backward via rematerialization, while ``gather='ahead'`` retains the
    forward copies through the backward (faster, more peak memory). Full
    params are read through ``train.loop.make_params_reader``.
    ``profile_batch`` (one real batch) enables
    ``backward_profile='measured'`` for the autotuner.

    Every path names its phases with ``jax.named_scope`` (metadata only,
    no run-time cost): ``forward`` around the loss and the bf16 cast of
    the params feeding it, ``update`` around the optimizer application
    (with the guard's commit when armed); the backward carries
    ``transpose(jvp(forward))`` and each bucket's collective its
    ``ar_b<k>``/``rs_b<k>``/``ag_b<k>``/``ag_g<k>`` scope (core/ddp.py).
    A profiler trace maps each device op to its phase through the op's
    ``op_name`` (docs/observability.md).

    ``guard=True`` arms the numerical-integrity sentinel (train/guard.py,
    docs/elastic.md §Numerical faults) on every path (xla, replicated,
    zero1, zero3): the step signature becomes
    ``train_step(state, batch, guard_in)`` with
    ``guard_in = {'lr_scale', 'loss_scale'}`` f32 scalars (the loop's LR
    re-warmup and the ``spike@s:mag`` fault hook; 1.0 on the happy path),
    the metrics dict gains ``gnorm``/``nonfinite``/``skipped`` scalar rows,
    and a ``lax.cond`` commits the previous state unchanged whenever the
    loss or any gradient goes nonfinite. ``guard=False`` (default) leaves
    the step byte-identical to the unguarded graph. The returned step
    carries ``.guarded``."""
    comm_cfg = comm if isinstance(comm, CommConfig) else CommConfig(
        strategy=comm, bucket_mb=bucket_mb, wire_dtype=comm_dtype)
    comm, bucket_mb, comm_dtype = (comm_cfg.strategy, comm_cfg.bucket_mb,
                                   comm_cfg.wire_dtype)
    loss_fn = make_loss_fn(model, smoothing=smoothing, mesh=mesh)

    def sgd_update(state: TrainState, grads, metrics, new_bn,
                   guard_in=None):
        with jax.named_scope("update"):
            lr = schedule(state.step)
            if guard_in is not None:
                lr = lr * guard_in["lr_scale"]
            params, mom = lars.update(state.params, grads, state.mom, lr,
                                      opt_cfg, stack=model.stack_axes)
            metrics = dict(metrics, lr=lr)
            new_state = TrainState(state.step + 1, params, mom, new_bn)
            if guard_in is None:
                return new_state, metrics
            return guard_lib.apply_guard(state, new_state, metrics, grads)

    if comm == "xla":
        assert comm_cfg.sharding not in ("zero2", "zero3"), (
            f"sharding={comm_cfg.sharding!r} needs the explicit-DDP path "
            "(a schedule from repro.comm.registry), not comm='xla' — GSPMD "
            "owns the param layout there (use FSDP PartitionSpecs instead)")

        def xla_step(state: TrainState, batch, guard_in=None):
            lfn = (guard_lib.scale_loss(loss_fn, guard_in["loss_scale"])
                   if guard_in is not None else loss_fn)
            with jax.named_scope("forward"):
                p_in = (cast_to_compute(state.params)
                        if comm_dtype == "bf16" else state.params)
            if grad_accum == 1:
                (_, (metrics, new_bn)), grads = jax.value_and_grad(
                    lfn, has_aux=True)(p_in, batch, state.bn_state)
                return sgd_update(state, grads, metrics, new_bn, guard_in)

            # gradient accumulation: the paper's 81,920 global batch on a
            # smaller chip count = scan over microbatches, mean the grads
            micro = jax.tree.map(
                lambda x: x.reshape(grad_accum, x.shape[0] // grad_accum,
                                    *x.shape[1:]), batch)

            def acc_fn(carry, mb):
                g_acc, bn = carry
                (_, (metrics, new_bn)), g = jax.value_and_grad(
                    lfn, has_aux=True)(p_in, mb, bn)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (g_acc, new_bn), metrics

            g0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                              p_in)
            (grads, new_bn), ms = jax.lax.scan(
                acc_fn, (g0, state.bn_state), micro)
            grads = jax.tree.map(lambda g: g / grad_accum, grads)
            metrics = jax.tree.map(lambda m: m.mean(), ms)
            return sgd_update(state, grads, metrics, new_bn, guard_in)

        if guard:
            def train_step(state: TrainState, batch, guard_in):
                return xla_step(state, batch, guard_in)
        else:
            def train_step(state: TrainState, batch):
                return xla_step(state, batch)
        train_step.guarded = guard
        return train_step

    # ------ explicit-DDP path (paper §III-C), pure data parallelism ------
    assert mesh is not None
    axes = tuple(mesh.axis_names)          # every axis is data-parallel
    wire = jnp.bfloat16 if comm_dtype == "bf16" else jnp.float32
    wire_bytes = 2 if comm_dtype == "bf16" else 4
    # inside the shard_map region every axis is manual and every array is
    # device-local, so activation sharding constraints (models.common.
    # constrain) are both meaningless and rejected — run the forward
    # mesh-free. Values are unchanged: constraints only place data.
    loss_fn = make_loss_fn(model, smoothing=smoothing, mesh=None)

    # ZeRO-1/3 sharded update (docs/comm.md): shard over the innermost
    # non-trivial mesh axis — the same rule the scatter schedules
    # (comm.schedules.shard_axis) and the cost model apply. 'naive' has no
    # bucket plan to shard against, so it downgrades to replicated.
    from repro.comm.cost import shard_axis_size
    sharding = comm_cfg.sharding if comm != "naive" else "replicated"
    shard_update = sharding != "replicated"
    gather_mode = comm_cfg.gather if shard_update else "at_end"
    shard_axis, n_shards = shard_axis_size(
        axes, tuple(mesh.shape[a] for a in axes))
    if shard_update:
        assert opt_cfg.kind in ("lars", "sgdm") and not opt_cfg.nesterov, \
            f"sharding={sharding!r} supports lars/sgdm, not {opt_cfg.kind!r}"
        lars.check_unstacked(model.stack_axes, f"sharding={sharding!r}")

    profile = None
    if (bucket_mb == "auto" and comm != "naive"
            and comm_cfg.backward_profile == "measured"
            and profile_batch is not None):
        profile = _measure_profile(model, profile_batch,
                                   smoothing=smoothing,
                                   n_dp=mesh.devices.size)

    tuned = None
    if bucket_mb == "auto":
        if comm == "naive":
            bucket_mb = 4.0            # per-tensor psums: plan is unused
        else:
            from repro.comm.autotune import autotune
            tuned = autotune(
                model.param_pd, schedule=comm, axes=axes,
                sizes=tuple(mesh.shape[a] for a in axes),
                dtype_bytes=wire_bytes, family=model.cfg.family,
                profile=profile, sharding=sharding, gather=gather_mode,
                param_dtype_bytes=wire_bytes)
            bucket_mb = tuned.bucket_mb
    plan = bucketing.make_plan(jax.tree.map(
        lambda pd: pd, model.param_pd), bucket_mb=bucket_mb,
        dtype_bytes=wire_bytes)

    # overlap-aware scheduling (§III-C.2): wrap each bucket group's params
    # in a custom-vjp identity so its collective fires inside the backward
    # pass, as soon as the group's grads exist. 'naive' has no buckets.
    # With shard_update the in-backward collective is the reduce-scatter-
    # terminal form and the shards ride out as gradient-sink cotangents.
    overlap = comm_cfg.overlap and comm != "naive"
    # gather_ahead = the step-START full prefetch, a ZeRO-1-only notion:
    # zero3's 'ahead' means retain-through-backward inside the step
    gather_ahead = gather_mode == "ahead" and sharding == "zero1"

    def sharded_step(state: TrainState, batch, guard_in=None):
        lfn = (guard_lib.scale_loss(loss_fn, guard_in["loss_scale"])
               if guard_in is not None else loss_fn)
        # gather-ahead (the default): rebuild this step's forward params
        # from the persistent master shards updated by the PREVIOUS step —
        # each bucket's all-gather is consumed only by its own layer group,
        # so the gathers hide under the forward. Otherwise the forward
        # reuses state.params (gathered at the end of the previous step).
        params = (ddp.gather_ahead_params(state.shards, plan,
                                          shard_axis=shard_axis,
                                          wire_dtype=wire)
                  if gather_ahead else state.params)
        if overlap:
            # in-backward reduce-scatter: the wrapped loss's backward runs
            # each bucket's RS-terminal schedule the moment the group's
            # cotangents exist; the reduced-mean fp32 shards come back as
            # the gradients of the zero sinks — the params themselves are
            # not differentiated, so no full reduced gradient exists.
            sinks = ddp.make_shard_sinks(plan, n_shards)

            def sink_loss(sks, p, b, bn):
                p = ddp.wrap_params_for_overlap(
                    p, plan, strategy=comm, axes=axes, comm_dtype=wire,
                    use_kernel=comm_cfg.use_kernel, shard_sinks=sks)
                return lfn(p, b, bn)

            (_, (metrics, new_bn)), g_shards = jax.value_and_grad(
                sink_loss, has_aux=True)(sinks, params, batch,
                                         state.bn_state)
            g_shards = list(g_shards)
        else:
            (_, (metrics, new_bn)), grads = jax.value_and_grad(
                lfn, has_aux=True)(params, batch, state.bn_state)
            g_shards = ddp.reduce_scatter_grads(
                grads, strategy=comm, axes=axes, plan=plan, comm_dtype=wire,
                use_kernel=comm_cfg.use_kernel)
        if new_bn is not None:
            new_bn = jax.tree.map(lambda v: jax.lax.pmean(v, axes), new_bn)
        metrics = {k: jax.lax.pmean(v, axes) for k, v in metrics.items()}
        with jax.named_scope("update"):
            lr = schedule(state.step)
            if guard_in is not None:
                lr = lr * guard_in["lr_scale"]
            p_shards, m_shards = lars.sharded_update_from_shards(
                list(state.shards), g_shards, list(state.mom), lr, opt_cfg,
                plan, shard_axis=shard_axis, n_shards=n_shards,
                update_kernel=comm_cfg.update_kernel)
            new_params = (params if gather_ahead else
                          ddp.all_gather_params(p_shards, plan,
                                                shard_axis=shard_axis,
                                                wire_dtype=wire))
            metrics = dict(metrics, lr=lr)
            new_state = TrainState(state.step + 1, new_params, m_shards,
                                   new_bn, p_shards)
            if guard_in is None:
                return new_state, metrics
            # the sentinel reduces over the device-local shard chunks: psum
            # over the shard axis reassembles the global count/norm (the
            # chunks are replicated over the other mesh axes)
            return guard_lib.apply_guard(state, new_state, metrics, g_shards,
                                         psum_axis=shard_axis)

    def zero3_step(state: TrainState, batch, guard_in=None):
        lfn = (guard_lib.scale_loss(loss_fn, guard_in["loss_scale"])
               if guard_in is not None else loss_fn)
        # ZeRO-3: no persistent params anywhere — the forward re-creates
        # each bucket group's fp32 leaves from the master shards just in
        # time (ddp.jit_gather_params) and XLA's liveness frees them after
        # the group's last consumer. gather='per_group' additionally wraps
        # the whole gathered forward in jax.checkpoint, so the backward's
        # rematerialization re-runs the per-group gathers instead of
        # keeping the forward copies as residuals (FSDP semantics with
        # full activation checkpointing: 2x forward compute, O(largest
        # group) params live in the backward too); gather='ahead' retains
        # the forward copies as ordinary residuals.
        if overlap:
            sinks = ddp.make_shard_sinks(plan, n_shards)

            def sink_loss3(sks, shards, b, bn):
                params = ddp.jit_gather_params(
                    shards, plan, shard_axis=shard_axis, wire_dtype=wire)
                p = ddp.wrap_params_for_overlap(
                    params, plan, strategy=comm, axes=axes, comm_dtype=wire,
                    use_kernel=comm_cfg.use_kernel, shard_sinks=sks)
                return lfn(p, b, bn)

            inner = (jax.checkpoint(sink_loss3)
                     if gather_mode == "per_group" else sink_loss3)
            (_, (metrics, new_bn)), g_shards = jax.value_and_grad(
                inner, has_aux=True)(sinks, state.shards, batch,
                                     state.bn_state)
            g_shards = list(g_shards)
        else:
            # non-overlapped fallback: gather outside the differentiated
            # function (the full tree is a step-transient, still never in
            # TrainState) and scatter after the backward. Remat would not
            # cover the gathers here, so 'per_group' degrades to retain.
            params = ddp.jit_gather_params(
                state.shards, plan, shard_axis=shard_axis, wire_dtype=wire)
            (_, (metrics, new_bn)), grads = jax.value_and_grad(
                lfn, has_aux=True)(params, batch, state.bn_state)
            g_shards = ddp.reduce_scatter_grads(
                grads, strategy=comm, axes=axes, plan=plan, comm_dtype=wire,
                use_kernel=comm_cfg.use_kernel)
        if new_bn is not None:
            new_bn = jax.tree.map(lambda v: jax.lax.pmean(v, axes), new_bn)
        metrics = {k: jax.lax.pmean(v, axes) for k, v in metrics.items()}
        with jax.named_scope("update"):
            lr = schedule(state.step)
            if guard_in is not None:
                lr = lr * guard_in["lr_scale"]
            p_shards, m_shards = lars.sharded_update_from_shards(
                list(state.shards), g_shards, list(state.mom), lr, opt_cfg,
                plan, shard_axis=shard_axis, n_shards=n_shards,
                update_kernel=comm_cfg.update_kernel)
            metrics = dict(metrics, lr=lr)
            new_state = TrainState(state.step + 1, None, m_shards, new_bn,
                                   p_shards)
            if guard_in is None:
                return new_state, metrics
            return guard_lib.apply_guard(state, new_state, metrics, g_shards,
                                         psum_axis=shard_axis)

    def zero2_step(state: TrainState, batch, guard_in=None):
        lfn = (guard_lib.scale_loss(loss_fn, guard_in["loss_scale"])
               if guard_in is not None else loss_fn)
        # ZeRO-2: the replicated fp32 ``params`` ARE the authoritative
        # masters (shards=None, no start-of-step gather). Only the
        # gradient + optimizer lifetimes shard: the backward reduce-
        # scatters into 1/n fp32 gradient shards exactly like zero1, the
        # update runs on a TRANSIENT 1/n slice of the packed masters
        # against the persistent sharded momentum, and one fp32 step-end
        # all-gather (fp32: the masters must never round-trip through
        # the wire dtype) writes the updated replica back.
        params = state.params
        if overlap:
            sinks = ddp.make_shard_sinks(plan, n_shards)

            def sink_loss2(sks, p, b, bn):
                p = ddp.wrap_params_for_overlap(
                    p, plan, strategy=comm, axes=axes, comm_dtype=wire,
                    use_kernel=comm_cfg.use_kernel, shard_sinks=sks)
                return lfn(p, b, bn)

            (_, (metrics, new_bn)), g_shards = jax.value_and_grad(
                sink_loss2, has_aux=True)(sinks, params, batch,
                                          state.bn_state)
            g_shards = list(g_shards)
        else:
            (_, (metrics, new_bn)), grads = jax.value_and_grad(
                lfn, has_aux=True)(params, batch, state.bn_state)
            g_shards = ddp.reduce_scatter_grads(
                grads, strategy=comm, axes=axes, plan=plan, comm_dtype=wire,
                use_kernel=comm_cfg.use_kernel)
        if new_bn is not None:
            new_bn = jax.tree.map(lambda v: jax.lax.pmean(v, axes), new_bn)
        metrics = {k: jax.lax.pmean(v, axes) for k, v in metrics.items()}
        with jax.named_scope("update"):
            lr = schedule(state.step)
            if guard_in is not None:
                lr = lr * guard_in["lr_scale"]
            # transient local master shards: pack the replica into the
            # bucket buffers and slice this device's ring chunk (the same
            # chunk the reduce-scatter left here —
            # comm.primitives.shard_index); each slice is O(N/n) live and
            # dies once the packed update uses it
            from repro.comm.primitives import shard_index
            k = shard_index(shard_axis)
            p_shards = []
            for buf in bucketing.pack(params, plan, dtype=jnp.float32):
                padded = bucketing.pad_to_shards(buf, n_shards)
                c = padded.shape[0] // n_shards
                p_shards.append(
                    jax.lax.dynamic_slice(padded, (k * c,), (c,)))
            p_shards, m_shards = lars.sharded_update_from_shards(
                p_shards, g_shards, list(state.mom), lr, opt_cfg,
                plan, shard_axis=shard_axis, n_shards=n_shards,
                update_kernel=comm_cfg.update_kernel)
            new_params = ddp.all_gather_params(p_shards, plan,
                                               shard_axis=shard_axis,
                                               wire_dtype=jnp.float32)
            metrics = dict(metrics, lr=lr)
            new_state = TrainState(state.step + 1, new_params, m_shards,
                                   new_bn, None)
            if guard_in is None:
                return new_state, metrics
            return guard_lib.apply_guard(state, new_state, metrics, g_shards,
                                         psum_axis=shard_axis)

    def local_step(state: TrainState, batch, guard_in=None):
        if sharding == "zero3":
            return zero3_step(state, batch, guard_in)
        if sharding == "zero2":
            return zero2_step(state, batch, guard_in)
        if shard_update:
            return sharded_step(state, batch, guard_in)
        lfn = (guard_lib.scale_loss(loss_fn, guard_in["loss_scale"])
               if guard_in is not None else loss_fn)
        if overlap:
            def wrapped_loss(params, b, bn):
                p = ddp.wrap_params_for_overlap(
                    params, plan, strategy=comm, axes=axes, comm_dtype=wire,
                    use_kernel=comm_cfg.use_kernel)
                return lfn(p, b, bn)
            (_, (metrics, new_bn)), grads = jax.value_and_grad(
                wrapped_loss, has_aux=True)(state.params, batch,
                                            state.bn_state)
        else:
            (_, (metrics, new_bn)), grads = jax.value_and_grad(
                lfn, has_aux=True)(state.params, batch, state.bn_state)
            grads = ddp.allreduce_grads(grads, strategy=comm, axes=axes,
                                        plan=plan, comm_dtype=wire,
                                        use_kernel=comm_cfg.use_kernel)
        if new_bn is not None:
            # BN batch stats stay local (paper §III-A.2); only the moving-
            # average *buffers* are averaged so the SPMD state is replicated
            new_bn = jax.tree.map(lambda v: jax.lax.pmean(v, axes), new_bn)
        metrics = {k: jax.lax.pmean(v, axes) for k, v in metrics.items()}
        # guarded: the grads are the all-reduced means (identical on every
        # device), so the sentinel inside sgd_update needs no psum
        state, metrics = sgd_update(state, grads, metrics, new_bn, guard_in)
        return state, metrics

    metric_keys = ("loss", "aux", "acc") + held_stat_keys(model) + ("lr",)
    if guard:
        metric_keys = metric_keys + guard_lib.SENTINEL_KEYS

    def sharded_call(state: TrainState, batch, guard_in=None):
        batch_specs = {k: P(axes, *([None] * (v.ndim - 1)))
                       for k, v in batch.items()}
        state_spec = jax.tree.map(lambda _: P(), state)
        if sharding == "zero2":
            assert state.params is not None and state.shards is None, (
                "sharding='zero2' keeps the replicated params as masters "
                "with sharded momentum and NO shard field: init_state(..., "
                "sharded_plan=train_step.bucket_plan, "
                "n_shards=train_step.n_shards, shard_params=False)")
            # only the momentum persists sharded; params stay replicated
            state_spec = state_spec._replace(
                mom=jax.tree.map(lambda _: P(shard_axis), state.mom))
        elif shard_update:
            assert state.shards is not None, (
                f"sharding={sharding!r} needs the persistent-shard state: "
                "init_state(..., sharded_plan=train_step.bucket_plan, "
                "n_shards=train_step.n_shards)")
            # momentum + master shards persist sharded: dim 0 partitioned
            # over shard_axis
            state_spec = state_spec._replace(
                mom=jax.tree.map(lambda _: P(shard_axis), state.mom),
                shards=jax.tree.map(lambda _: P(shard_axis), state.shards))
        metric_specs = {k: P() for k in metric_keys}
        if guard_in is not None:
            return compat.shard_map(
                local_step, mesh=mesh,
                in_specs=(state_spec, batch_specs,
                          {"lr_scale": P(), "loss_scale": P()}),
                out_specs=(state_spec, metric_specs),
            )(state, batch, guard_in)
        return compat.shard_map(
            local_step, mesh=mesh,
            in_specs=(state_spec, batch_specs),
            out_specs=(state_spec, metric_specs),
        )(state, batch)

    if guard:
        def train_step(state: TrainState, batch, guard_in):
            return sharded_call(state, batch, guard_in)
    else:
        def train_step(state: TrainState, batch):
            return sharded_call(state, batch)

    # introspection for launch/dryrun/report: the resolved comm plan
    train_step.guarded = guard
    train_step.bucket_plan = plan
    train_step.bucket_mb = bucket_mb
    train_step.tuned = tuned
    train_step.overlap = overlap
    train_step.sharding = sharding
    train_step.gather = gather_mode
    train_step.shard_update = shard_update      # deprecated boolean views
    train_step.gather_ahead = gather_ahead
    train_step.shard_axis = shard_axis
    train_step.n_shards = n_shards
    train_step.backward_profile = profile
    # serializable CommPlan (docs/elastic.md): saved beside every
    # checkpoint; elastic resume rebuilds the packing layout from it and
    # re-autotunes/re-jits against the new mesh
    from repro import comm as comm_pkg
    train_step.comm_plan = comm_pkg.plan_for(
        comm_cfg, (axes, tuple(mesh.shape[a] for a in axes)),
        model.param_pd, resolved_bucket_mb=bucket_mb, strategy=comm,
        overlap=overlap, sharding=sharding, gather=gather_mode,
        n_shards=n_shards if shard_update else 1)
    return train_step


def _measure_profile(model, batch, *, smoothing: float, n_dp: int = 1):
    """Profiled warm-up step for ``backward_profile='measured'``: a
    single-device differentiation of the real loss with probing identities
    at the bucket-group boundaries (``ddp.wrap_params_for_probe``). The
    batch is pulled to host and cut to its 1/n_dp per-device share first,
    so the measured time matches the per-device backward the overlap
    timeline budgets against. Falls back to the FLOPs model (returns None)
    if capture fails — e.g. a forward that requires the mesh."""
    from repro.comm.autotune import measure_backward_profile
    from repro.core import pinit
    try:
        def per_device(x):
            x = jax.device_get(x)
            if getattr(x, "ndim", 0) == 0:
                return x
            return x[:max(x.shape[0] // max(n_dp, 1), 1)]
        batch = jax.tree.map(per_device, batch)
        params = pinit.materialize(model.param_pd, 0, None)
        bn = (pinit.materialize(model.bn_state_pd, 0, None)
              if model.bn_state_pd is not None else None)
        local_loss = make_loss_fn(model, smoothing=smoothing, mesh=None)
        prof = measure_backward_profile(
            lambda p: local_loss(p, batch, bn)[0], params)
        obs_metrics.event(
            "backward_profile_measured",
            {"groups": len(prof.cum_elems),
             "total_ms": round(prof.total_s * 1e3, 1),
             "forward_ms": (None if prof.t_forward_s is None
                            else round(prof.t_forward_s * 1e3, 1))},
            where="repro/train/step.py")
        return prof
    except Exception as e:  # noqa: BLE001 — profile is best-effort
        obs_metrics.event(
            "backward_profile_fallback",
            f"{type(e).__name__}: {e}; falling back to the FLOPs model",
            where="repro/train/step.py")
        return None


def make_eval_step(model, *, smoothing: float = 0.0, mesh=None):
    loss_fn = make_loss_fn(model, smoothing=smoothing, mesh=mesh)

    def eval_step(params, batch, bn_state=None):
        cfg = model.cfg
        if cfg.family == "conv":
            from repro.models.resnet import resnet_forward
            from repro.core.precision import cast_to_compute
            logits, _ = resnet_forward(cast_to_compute(params), bn_state,
                                       cfg, batch["images"], train=False,
                                       mesh=mesh)
            loss, _ = smoothed_xent(logits, batch["labels"], smoothing=0.0)
            return {"loss": loss,
                    "acc": top1_accuracy(logits, batch["labels"])}
        (logits, aux), _ = model.forward_train(params, batch, mesh, None)
        loss, _ = _lm_loss(logits, batch["labels"], smoothing=0.0)
        return {"loss": loss, "acc": jnp.float32(0)}

    return eval_step
