"""Training launcher.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \\
      --reduced --seq 128 --batch 8 --steps 100 --optimizer lars --lr 1.0
  PYTHONPATH=src python -m repro.launch.train --arch resnet50 --reduced \\
      --batch 32 --steps 200 --comm bucketed --warmup 20

Observability (docs/observability.md): ``--metrics out.jsonl`` mirrors the
tag stream to a JSONL artifact; ``--trace DIR`` runs the training loop
under the JAX profiler and writes its profile (``.xplane.pb`` plus a
Perfetto ``perfetto_trace.json.gz``) under ``DIR``: the device ops named by
the step's scopes and the loop's host spans, on one clock.
"""
from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os

import jax

from repro.configs import get_config
from repro.configs.shapes import InputShape
from repro.core import lars
from repro.core.schedule import ScheduleConfig, linear_scaled_lr, \
    make_schedule
from repro.data.synthetic import make_batch_fn
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models.registry import build_model
from repro.obs import metrics as obs_metrics
from repro.train import loop
from repro.train.state import init_state
from repro.train.step import make_eval_step, make_train_step

WHERE = "repro/launch/train.py"


def main(argv=None):
    """Parse ``argv`` and train. Returns (final state, history)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-sized variant of the same family")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--optimizer", default="lars",
                choices=["lars", "sgdm", "lamb"])
    ap.add_argument("--grad-accum", type=int, default=1)
    from repro.comm import available
    from repro.comm.registry import ALIASES
    ap.add_argument("--comm", default="xla",
                    choices=["xla", "naive"] + sorted(
                        set(available()) | set(ALIASES)))
    ap.add_argument("--bucket-mb", default=4.0, metavar="MB|auto",
                    type=lambda s: s if s == "auto" else float(s),
                    help="bucket size in MB, or 'auto' to autotune against "
                         "the comm cost model (repro/comm/autotune.py)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="post-backward collectives instead of issuing "
                         "each bucket's all-reduce inside the backward")
    ap.add_argument("--sharding", default=None,
                    choices=["replicated", "zero1", "zero2", "zero3"],
                    help="param/optimizer sharding policy: 'replicated' "
                         "(default) trains on a full replica; 'zero1' "
                         "reduce-scatters grads and shards the update; "
                         "'zero2' shards the gradient+optimizer lifetimes "
                         "but keeps the replicated fp32 masters in the "
                         "forward (no gather; fp32 step-end write-back); "
                         "'zero3' additionally drops the persistent param "
                         "replica and all-gathers each bucket group just "
                         "in time during the forward (docs/comm.md)")
    ap.add_argument("--gather", default=None,
                    choices=["ahead", "at_end", "per_group"],
                    help="param gather issue point: 'ahead' hides the "
                         "zero1 all-gather under the next forward (zero1 "
                         "default; under zero3 it retains the forward "
                         "copies for the backward), 'at_end' gathers at "
                         "step end, 'per_group' (zero3 default) re-gathers "
                         "each group for its backward via remat")
    ap.add_argument("--shard-update", action="store_true",
                    help="DEPRECATED: same as --sharding zero1")
    ap.add_argument("--update-kernel", action="store_true",
                    help="fused lars_update Pallas kernel for the sharded "
                         "update (interpret-mode on CPU)")
    ap.add_argument("--no-gather-ahead", action="store_true",
                    help="DEPRECATED: same as --gather at_end")
    ap.add_argument("--backward-profile", default="model",
                    choices=["model", "measured"],
                    help="bucket autotuner backward-time source: FLOPs "
                         "model, or one profiled warm-up step")
    ap.add_argument("--lr", type=float, default=None,
                    help="default: linear-scaling rule from batch size")
    ap.add_argument("--warmup", type=int, default=None)
    ap.add_argument("--decay", default="poly2")
    ap.add_argument("--smoothing", type=float, default=0.1)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--weight-decay", type=float, default=5e-5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--devices", type=int, default=None, metavar="N",
                    help="build the mesh over the first N devices "
                         "(default: every device jax reports)")
    ap.add_argument("--eval-every", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume-elastic", action="store_true",
                    help="resume from --ckpt-dir onto THIS mesh, resharding "
                         "the ZeRO-1 masters/momentum n->m if the device "
                         "count changed; the saved CommPlan drives the "
                         "packing layout and is re-autotuned/re-jitted for "
                         "the new mesh (docs/elastic.md)")
    ap.add_argument("--keep-last-k", type=int, default=0, metavar="K",
                    help="retention: prune step-tagged checkpoints beyond "
                         "the newest K (0 = keep everything)")
    ap.add_argument("--step-timeout-s", type=float, default=0.0,
                    help="step watchdog budget: a step exceeding this is "
                         "abandoned, the last good checkpoint restored, "
                         "and the step retried with backoff (0 = off; "
                         "disables buffer donation)")
    ap.add_argument("--max-step-retries", type=int, default=3)
    ap.add_argument("--inject-fault", default=None, metavar="SPEC",
                    help="fault-injection harness (train/faults.py): "
                         "comma-separated kind@step[:arg] — e.g. kill@7, "
                         "sigterm@5, stall@3:2.5, corrupt@4:manifest, "
                         "nan@3, spike@6:50")
    ap.add_argument("--guard", action="store_true",
                    help="numerical-integrity guard (train/guard.py, "
                         "docs/elastic.md §Numerical faults): in-graph "
                         "NaN sentinel with skip-update, divergence "
                         "detector, in-memory rollback ring escalating to "
                         "checkpoint restore")
    ap.add_argument("--rollback-ring", type=int, default=2, metavar="N",
                    help="guard rollback ring capacity: N in-memory "
                         "device_get snapshots (0 = skip straight to "
                         "checkpoint restore)")
    ap.add_argument("--rollback-every", type=int, default=1, metavar="K",
                    help="guard snapshot cadence in steps")
    ap.add_argument("--rewarmup-steps", type=int, default=0, metavar="R",
                    help="LR re-warmup window after a guard recovery, "
                         "composed with the run schedule (0 = off, the "
                         "trajectory-preserving setting)")
    ap.add_argument("--data", default="lcg", choices=["lcg", "uniform"])
    ap.add_argument("--log-every", type=int, default=10, metavar="K",
                    help="log (and keep in the history) every K-th step's "
                         "metrics, and the last step's")
    ap.add_argument("--history-out", default=None)
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="run the training loop under the JAX profiler and "
                         "write its profile (.xplane.pb and a Perfetto "
                         "trace) under DIR")
    ap.add_argument("--metrics", default=None, metavar="OUT.jsonl",
                    help="mirror every metrics event (the MLPerf tag "
                         "stream + obs.* rows) to a JSONL file")
    args = ap.parse_args(argv)

    enable_compile_cache()
    reg = obs_metrics.default_registry()
    sink = (reg.add_sink(obs_metrics.JsonlSink(args.metrics))
            if args.metrics else None)
    try:
        return _run(args, reg=reg)
    finally:
        if sink is not None:
            reg.remove_sink(sink)
            sink.close()


def _run(args, *, reg: obs_metrics.Registry):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_local_mesh(args.model_parallel, devices=args.devices)
    model = build_model(cfg)

    lr = args.lr if args.lr is not None else linear_scaled_lr(0.1, args.batch)
    warmup = args.warmup if args.warmup is not None else args.steps // 10
    sched = make_schedule(ScheduleConfig(
        base_lr=lr, warmup_steps=warmup, total_steps=args.steps,
        decay=args.decay))
    opt = lars.OptConfig(kind=args.optimizer, momentum=args.momentum,
                         weight_decay=args.weight_decay)

    shape = InputShape("cli", "train", args.seq, args.batch)
    batch_fn = make_batch_fn(cfg, shape, seed=args.seed, kind=args.data,
                             mesh=mesh)
    from repro.configs.base import CommConfig
    # deprecated boolean flags: warn and map onto the policy enum, exactly
    # like the CommConfig field shims (one release of compat)
    sharding, gather = args.sharding, args.gather
    if args.shard_update:
        reg.event("launch_deprecated",
                  "--shard-update is deprecated; use --sharding zero1",
                  where=WHERE)
        if sharding is None:
            sharding = "zero1"
        elif sharding == "replicated":
            raise SystemExit(
                "--shard-update conflicts with --sharding replicated — "
                "drop the deprecated flag")
    if args.no_gather_ahead:
        reg.event("launch_deprecated",
                  "--no-gather-ahead is deprecated; use --gather at_end",
                  where=WHERE)
        if gather is None:
            gather = "at_end"
        elif gather == "ahead":
            raise SystemExit(
                "--no-gather-ahead conflicts with --gather ahead — "
                "drop the deprecated flag")
    if (sharding in ("zero1", "zero2", "zero3")
            and args.comm in ("xla", "naive")):
        raise SystemExit(
            f"--sharding {sharding} needs an explicit-DP schedule "
            f"(--comm {{bucketed,psum,ring,hierarchical,2d_torus,dbtree}}), "
            f"not {args.comm!r} — it would silently train replicated")
    if args.backward_profile == "measured" and args.bucket_mb != "auto":
        reg.event("launch_note",
                  "--backward-profile measured only affects the bucket "
                  "autotuner; add --bucket-mb auto or the profile is unused",
                  where=WHERE)
    comm_cfg = CommConfig(strategy=args.comm, bucket_mb=args.bucket_mb,
                          overlap=not args.no_overlap,
                          update_kernel=args.update_kernel,
                          backward_profile=args.backward_profile,
                          sharding=sharding, gather=gather)
    saved_plan = None
    if args.resume_elastic:
        if not args.ckpt_dir:
            raise SystemExit("--resume-elastic needs --ckpt-dir")
        from repro.train import checkpoint as ckpt_mod
        try:
            saved_plan = ckpt_mod.load_comm_plan(args.ckpt_dir)
        except ckpt_mod.CheckpointError:
            saved_plan = None        # replicated/xla run: plain restore
        if saved_plan is not None:
            # the committed plan wins over the CLI comm flags: the resumed
            # run must keep the checkpoint's packing semantics;
            # bucket_mb='auto' re-autotunes below against THIS mesh when
            # make_train_step re-jits
            comm_cfg = saved_plan.comm_config(reautotune=True)
            reg.event(
                "elastic_resume_plan",
                f"resuming elastically from {args.ckpt_dir}: CommPlan "
                f"schedule={saved_plan.schedule} "
                f"bucket={saved_plan.bucket_mb:g}MB "
                f"(requested {saved_plan.requested_bucket_mb!r}), saved "
                f"on mesh "
                f"{dict(zip(saved_plan.mesh_axes, saved_plan.mesh_sizes))} "
                f"with n_shards={saved_plan.n_shards}", where=WHERE)
    from repro.train.faults import FaultInjector, parse_faults
    fault_list = parse_faults(args.inject_fault)
    if any(f.kind == "spike" for f in fault_list) and not args.guard:
        raise SystemExit(
            "spike@s:mag rides in through the guarded step's loss_scale "
            "input — add --guard")
    guard_cfg = None
    if args.guard:
        from repro.train.guard import GuardConfig
        guard_cfg = GuardConfig(ring_capacity=args.rollback_ring,
                                snapshot_every=max(args.rollback_every, 1),
                                rewarmup_steps=args.rewarmup_steps)
        reg.event("guard_armed",
                  f"numerical guard on: ring={args.rollback_ring} "
                  f"snapshots every {max(args.rollback_every, 1)} step(s), "
                  f"rewarmup={args.rewarmup_steps}", where=WHERE)
    train_step = make_train_step(model, opt, sched, smoothing=args.smoothing,
                                 mesh=mesh, comm=comm_cfg,
                                 grad_accum=args.grad_accum,
                                 profile_batch=(batch_fn(0) if
                                                args.backward_profile ==
                                                "measured" else None),
                                 guard=args.guard)
    if getattr(train_step, "tuned", None) is not None:
        t = train_step.tuned
        reg.event("autotune_plan",
                  f"autotuned bucket plan: {t.bucket_mb:g}MB x "
                  f"{t.n_buckets} buckets ({t.sim.mode}), predicted overlap "
                  f"eff {t.sim.overlap_eff:.2f}", where=WHERE)
    if getattr(train_step, "sharding", "replicated") != "replicated":
        rs_at = "in-backward" if train_step.overlap else "post-backward"
        ag_at = {"ahead": ("retained forward copies"
                           if train_step.sharding == "zero3" else
                           "gather-ahead (hidden under next forward)"),
                 "at_end": ("fp32 step-end (replica write-back)"
                            if train_step.sharding == "zero2"
                            else "step-end"),
                 "per_group": "per-group just-in-time (remat re-gather)",
                 }[train_step.gather]
        reg.event("shard_update_plan",
                  f"{train_step.sharding} sharded update: "
                  f"{train_step.n_shards} shards "
                  f"over '{train_step.shard_axis}', {rs_at} reduce-scatter, "
                  f"{ag_at} param all-gather", where=WHERE)
    eval_step = make_eval_step(model, mesh=mesh) if args.eval_every else None

    sharded = getattr(train_step, "shard_update", False)
    state = init_state(model, args.seed, mesh, opt_kind=args.optimizer,
                       sharded_plan=train_step.bucket_plan if sharded
                       else None,
                       n_shards=train_step.n_shards if sharded else 1,
                       materialize_params=getattr(train_step, "sharding",
                                                  "replicated") != "zero3",
                       shard_params=getattr(train_step, "sharding",
                                            "replicated") != "zero2")
    if args.resume_elastic:
        from repro.train import elastic
        new_n = train_step.n_shards if sharded else 1
        state = elastic.load_resharded(
            args.ckpt_dir, state, getattr(train_step, "bucket_plan", None),
            new_n, old_comm_plan=saved_plan)
        old_n = saved_plan.n_shards if saved_plan is not None else 1
        reg.event("elastic_resume",
                  f"elastic resume: restored step {int(state.step)}, "
                  f"resharded {old_n} -> {new_n} shards", where=WHERE)
    profile = (jax.profiler.trace(args.trace, create_perfetto_trace=True)
               if args.trace else contextlib.nullcontext())
    with profile:
        state, history = loop.train(
            state, train_step, batch_fn, steps=args.steps,
            eval_step=eval_step, eval_batch_fn=batch_fn,
            eval_every=args.eval_every, log_every=args.log_every,
            ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
            seed=args.seed, keep_last_k=args.keep_last_k,
            step_timeout_s=args.step_timeout_s,
            max_step_retries=args.max_step_retries,
            comm_plan=getattr(train_step, "comm_plan", None),
            faults=FaultInjector(fault_list), guard=guard_cfg)
    if args.trace:
        found = sorted(glob.glob(os.path.join(args.trace, "**",
                                              "*.xplane.pb"), recursive=True),
                       key=os.path.getmtime)
        reg.event("trace_written", {"path": found[-1] if found else None,
                                    "dir": args.trace}, where=WHERE)
    if args.history_out:
        with open(args.history_out, "w") as f:
            json.dump(history, f, indent=1)
    return state, history


if __name__ == "__main__":
    main()
