"""Training loop with MLPerf-v0.5.0-style tags (the paper's Appendix 1 log
format: run_start / train_epoch / eval_accuracy / run_stop) and the
elastic/fault-tolerance machinery (docs/elastic.md):

* **step watchdog** (``step_timeout_s``): each step runs under a bounded
  timeout; a hung collective / stalled device trips it, the loop restores
  the last good checkpoint and retries with exponential backoff, up to
  ``max_step_retries`` times. Watchdog mode disables buffer donation — the
  in-hand state must stay valid as a restore template.
* **SIGTERM preemption drain**: on the announced-preemption signal the loop
  finishes the in-flight step, commits a checkpoint, and returns early —
  the resumable exit an elastic scheduler expects.
* **checkpoint discipline**: periodic saves are step-tagged
  (``checkpoint.step_tag``) so retention (``keep_last_k``) has something to
  prune, the serialized CommPlan rides along with every save, and a final
  checkpoint is always committed at run_stop when ``ckpt_dir`` is set —
  a run whose ``steps`` is not a multiple of ``ckpt_every`` keeps its tail.
* **fault hooks** (``faults``): a ``train.faults.FaultInjector`` (or its
  spec string) fires kill/sigterm/stall/corrupt/nan/spike at the loop's
  hook points.
* **numerical-integrity guard** (``guard``, docs/elastic.md §Numerical
  faults): with a guarded step (``make_train_step(..., guard=True)``) the
  loop drives the recovery ladder — an in-graph sentinel skips nonfinite
  steps (replayed in place), a host-side EMA divergence detector trips an
  in-memory rollback ring (``device_get`` snapshots, no checkpoint IO)
  followed by an optional LR re-warmup window, escalating to checkpoint
  restore and then bounded-retry exhaustion exactly like the watchdog.

The jitted eval step and the authoritative-params gather are built once
per ``train()`` call (not re-jitted per eval), which also keeps eval
timing stable under the watchdog.
"""
from __future__ import annotations

import signal
import threading
import time
from typing import Callable, Optional

import jax
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.obs import metrics as obs_metrics
from repro.train import checkpoint as ckpt
from repro.train.faults import FaultInjector, parse_faults
from repro.train.guard import (DivergenceDetector, GuardConfig,
                               RollbackRing, rewarmup_scale_fn)
from repro.train.state import TrainState

_WHERE = "repro/train/loop.py"


class StepTimeoutError(RuntimeError):
    """A training step exceeded the watchdog budget."""


def mlperf_log(tag: str, value=None):
    """The Appendix-1 tag line, emitted through the ``obs.metrics``
    registry: the default ``StdoutSink`` prints the byte-identical
    ``:::MLPv0.5.0`` line (flush=True) the old inline print produced, and
    any attached sink (``--metrics`` JSONL, test MemorySink) sees the same
    event."""
    obs_metrics.event(tag, value, where="repro/train/loop.py")


def authoritative_params(state: TrainState, train_step: Callable):
    """The params evals must read. A sharded state
    (``sharding='zero1'|'zero3'``) carries its fp32 masters in
    ``state.shards``; under 'zero1' with gather-ahead (the default)
    ``state.params`` is the forward copy, one update BEHIND the masters,
    and under 'zero3' ``state.params`` is None — so reconstruct the full
    params from the shards instead of silently evaluating a stale (or
    absent) step. (``train()`` uses the jit-cached
    :func:`make_params_reader` form of this.)"""
    return make_params_reader(train_step)(state)


def make_params_reader(train_step: Callable) -> Callable:
    """Build the authoritative-params reader ONCE: for sharded steps
    (any non-replicated ``train_step.sharding``) a single jitted
    shards->params gather reused across every eval (the old per-eval
    retrace re-staged the full unpack each time); for replicated steps,
    plain attribute access."""
    if getattr(train_step, "sharding", "replicated") != "replicated" or \
            getattr(train_step, "shard_update", False):
        from repro.train.state import full_params_from_shards
        plan, n = train_step.bucket_plan, train_step.n_shards
        gather = jax.jit(
            lambda shards: full_params_from_shards(shards, plan, n))

        def read(state: TrainState):
            if state.shards is None:
                return state.params
            return gather(tuple(state.shards))
        return read
    return lambda state: state.params


def _call_with_timeout(fn: Callable, timeout_s: float):
    """Run ``fn`` with a bounded wall-clock budget. ``timeout_s <= 0``
    calls inline. The worker thread is daemonic: a genuinely hung step is
    abandoned (it cannot be killed), which is exactly the recover-by-
    restore situation the watchdog exists for."""
    if not timeout_s or timeout_s <= 0:
        return fn()
    box = {}

    def worker():
        try:
            box["ok"] = fn()
        except BaseException as e:  # noqa: BLE001 — re-raised on the caller
            box["err"] = e

    t = threading.Thread(target=worker, daemon=True,
                         name="repro-step-watchdog")
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        raise StepTimeoutError(
            f"step exceeded the {timeout_s:.1f}s watchdog budget (hung "
            f"collective / stalled device?)")
    if "err" in box:
        raise box["err"]
    return box["ok"]


def train(state: TrainState, train_step: Callable, batch_fn: Callable, *,
          steps: int, eval_step: Optional[Callable] = None,
          eval_batch_fn: Optional[Callable] = None, eval_every: int = 0,
          log_every: int = 10, ckpt_dir: Optional[str] = None,
          ckpt_every: int = 0, seed: int = 0, keep_last_k: int = 0,
          step_timeout_s: float = 0.0, max_step_retries: int = 3,
          retry_backoff_s: float = 0.5, comm_plan=None, faults=None,
          guard: Optional[GuardConfig] = None):
    """Runs optimizer steps up to global step ``steps`` (a resumed state
    continues from ``state.step``). Returns (state, history).

    ``guard`` (a ``train.guard.GuardConfig``) configures the numerical-
    integrity recovery ladder; it requires a guarded step
    (``make_train_step(..., guard=True)``). A guarded step with
    ``guard=None`` runs under the default ``GuardConfig()``.

    Without the watchdog or the guard, the loop keeps one step in flight:
    step i is dispatched on the not-yet-ready state step i-1 returned, and
    only then does the host wait on step i-1 and read its metrics back, so
    the host's work between two steps overlaps the device's. Never more
    than one: each batch fetch still follows exactly one more finished
    step. Checkpoints, evals, the preemption drain and the end of the run
    drain the step in flight first, so they see the state and the history
    they always did; the state returned is ready. The watchdog (each step
    under a timeout, no donation) and the guard (whose sentinel readback
    decides the next step) run each step to its end before the next.
    ``obs.loop.overlapped_total`` counts the steps dispatched with the
    previous one in flight, ``obs.loop.drain_total`` the drains; both are
    emitted on logged steps and at drains.

    Each step runs inside a ``jax.profiler.StepTraceAnnotation``
    (``train_step``, with its step number) holding the host spans
    ``loop.batch`` (the batch function), ``loop.dispatch`` (the jitted
    call until it returns), ``loop.release`` (dropping the state the step
    consumed, which destroys every array handle of it), ``loop.wait``
    (``block_until_ready`` on the previous step's metrics, or at a drain
    on this step's metrics and state), ``loop.readback`` (every
    device-to-host read of a logged step's metrics and the log line built
    from them, one step after that step's dispatch or at a drain),
    ``loop.checkpoint`` and ``loop.eval``. On the synchronous paths
    ``loop.release`` comes before ``loop.dispatch`` and ``loop.wait`` and
    ``loop.readback`` are the step's own. The spans cost well under a
    microsecond each unless a profiler is running, and then land on its
    host plane, on the device trace's clock (docs/observability.md)."""
    mlperf_log("run_start")
    mlperf_log("run_set_random_seed", seed)
    injector = (faults if isinstance(faults, FaultInjector)
                else FaultInjector(parse_faults(faults)))
    history = []
    t0 = time.time()
    watchdog = bool(step_timeout_s and step_timeout_s > 0)
    # donation frees the old state's buffers mid-step — incompatible with
    # keeping it as the watchdog's in-memory fallback restore point. The
    # guard is donation-safe on its own: the skip path's lax.cond returns
    # the old values as step OUTPUTS, and the rollback ring holds host
    # copies taken before dispatch.
    step_fn = (jax.jit(train_step) if watchdog
               else jax.jit(train_step, donate_argnums=(0,)))
    eval_fn = jax.jit(eval_step) if eval_step is not None else None
    params_reader = make_params_reader(train_step)
    last_saved_step = None

    guarded = bool(getattr(train_step, "guarded", False))
    if guard is not None and not guarded:
        raise ValueError(
            "loop.train(guard=...) needs a guarded step — build it with "
            "make_train_step(..., guard=True)")
    gcfg = guard if guard is not None else (GuardConfig() if guarded
                                            else None)
    detector = DivergenceDetector(gcfg) if guarded else None
    ring = RollbackRing(gcfg.ring_capacity) if guarded else None
    rewarm = rewarmup_scale_fn(gcfg.rewarmup_steps) if guarded else None
    rewarm_start = None       # step a recovery re-warmup window opened at
    skips = 0                 # consecutive sentinel skips
    rollbacks = 0             # ring rollbacks used
    restores = 0              # guard checkpoint restores used
    pipelined = not (watchdog or guarded)
    in_flight = None          # (step, metrics) dispatched, not waited on
    unsent = {"obs.loop.overlapped_total": 0, "obs.loop.drain_total": 0}

    def logged(step: int) -> bool:
        return bool(log_every) and (step % log_every == 0
                                    or step == steps - 1)

    def log_step(step: int, metrics) -> None:
        with TraceAnnotation("loop.readback"):
            m = {k: float(v) for k, v in metrics.items()}
            history.append({"step": step, **m})
            mlperf_log("train_step",
                       {"step": step, "loss": round(m["loss"], 4),
                        "lr": round(m.get("lr", 0.0), 6)})
            if guarded:
                obs_metrics.gauge("obs.guard.gnorm", m["gnorm"],
                                  where=_WHERE, step=step)

    def emit_loop_counters(step: int) -> None:
        # StdoutSink prints every event: counters go out on logged steps
        # and at drains, with what they gained since
        for name, inc in unsent.items():
            if inc:
                obs_metrics.counter(name, inc, where=_WHERE, step=step)
                unsent[name] = 0

    def settle(step: int, metrics, *ready) -> None:
        """Wait for a dispatched step (its metrics, and ``ready``), then
        read it back if it is a logged step."""
        with TraceAnnotation("loop.wait"):
            jax.block_until_ready((metrics, ready))
        if logged(step):
            log_step(step, metrics)
            emit_loop_counters(step)

    def drain() -> None:
        """A synchronous boundary: the step in flight finishes, and is
        read back, before anything reads the state."""
        nonlocal in_flight
        if in_flight is not None:
            step, metrics = in_flight
            in_flight = None
            unsent["obs.loop.drain_total"] += 1
            settle(step, metrics, state)
            emit_loop_counters(step)

    def save_ckpt(s: TrainState) -> None:
        nonlocal last_saved_step
        gstep = int(s.step)
        with TraceAnnotation("loop.checkpoint"):
            path = ckpt.save(s, ckpt_dir, tag=ckpt.step_tag(gstep),
                             comm_plan=comm_plan, keep_last_k=keep_last_k)
        last_saved_step = gstep
        mlperf_log("checkpoint_saved",
                   {"step": gstep, "tag": ckpt.step_tag(gstep)})
        injector.on_saved(path, gstep)

    preempted = threading.Event()

    def _on_sigterm(signum, frame):
        preempted.set()
        mlperf_log("sigterm_received")

    old_handler = None
    try:
        old_handler = signal.signal(signal.SIGTERM, _on_sigterm)
    except ValueError:      # loop driven from a non-main thread
        pass

    start = int(state.step)
    if watchdog and ckpt_dir and not ckpt.available_tags(ckpt_dir):
        # baseline restore point: the watchdog must always have somewhere
        # to roll back to, even if the very first step hangs
        save_ckpt(state)
    i = start
    retries = 0
    if guarded and ring is not None:
        # baseline snapshot: rung 2 must have a rollback target even if
        # the very first steps diverge
        ring.snapshot(state)
    try:
        while i < steps:
            with StepTraceAnnotation("train_step", step_num=i):
                with TraceAnnotation("loop.batch"):
                    batch = injector.poison_batch(batch_fn(state.step), i)
                if pipelined:
                    injector.on_step(i)
                    with TraceAnnotation("loop.dispatch"):
                        donated = state
                        state, metrics = step_fn(state, batch)
                    with TraceAnnotation("loop.release"):
                        # destroys every array handle of the donated state:
                        # host time the step in flight now covers
                        del donated
                    if in_flight is not None:
                        unsent["obs.loop.overlapped_total"] += 1
                        settle(*in_flight)
                    in_flight = (i, metrics)
                else:
                    with TraceAnnotation("loop.release"):
                        # the previous step's closure holds that step's input
                        # state: dropping it destroys every array handle of it,
                        # host time in which the device idles
                        run_step = None
                    guard_in = None
                    if guarded:
                        import numpy as np
                        scale = (1.0 if rewarm_start is None
                                 else rewarm(i - rewarm_start))
                        guard_in = {
                            "lr_scale": np.float32(scale),
                            "loss_scale": np.float32(injector.loss_scale(i))}

                    def run_step(state=state, batch=batch, i=i,
                                 guard_in=guard_in):
                        injector.on_step(i)
                        with TraceAnnotation("loop.dispatch"):
                            out = (step_fn(state, batch, guard_in) if guarded
                                   else step_fn(state, batch))
                        with TraceAnnotation("loop.wait"):
                            return jax.block_until_ready(out)

                    try:
                        state, metrics = _call_with_timeout(run_step,
                                                            step_timeout_s)
                        retries = 0
                    except StepTimeoutError as e:
                        retries += 1
                        obs_metrics.counter("obs.watchdog_timeout_total",
                                            where="repro/train/loop.py", step=i)
                        mlperf_log("watchdog_timeout",
                                   {"step": i, "attempt": retries,
                                    "timeout_s": step_timeout_s})
                        history.append({"step": i, "watchdog_timeout": retries})
                        if retries > max_step_retries:
                            raise RuntimeError(
                                f"step {i} timed out {retries} times "
                                f"(budget {step_timeout_s:.1f}s each) — giving "
                                f"up after bounded retries") from e
                        if ckpt_dir:
                            try:
                                state = ckpt.load(state, ckpt_dir, tag=None)
                                i = int(state.step)
                                mlperf_log("watchdog_restore",
                                           {"resume_step": i})
                                history.append({"step": i,
                                                "watchdog_restore": 1})
                            except ckpt.CheckpointError as err:
                                # used to be a bare print that bypassed the tag
                                # stream; now a first-class event on every sink
                                mlperf_log("watchdog_no_checkpoint",
                                           {"step": i, "error": str(err),
                                            "action": "retrying with the "
                                                      "in-memory state"})
                        time.sleep(min(retry_backoff_s * 2 ** (retries - 1),
                                       30.0))
                        continue
                    if guarded:
                        # recovery ladder (docs/elastic.md §Numerical faults)
                        with TraceAnnotation("loop.readback"):
                            g_loss = float(metrics["loss"])
                            g_gnorm = float(metrics["gnorm"])
                            g_skipped = float(metrics["skipped"])
                        reason = None
                        if g_skipped > 0:
                            # rung 1: the in-graph sentinel refused the update —
                            # state (and state.step) are unchanged, replay step i
                            skips += 1
                            obs_metrics.counter("obs.guard.skip_total",
                                                where=_WHERE, step=i)
                            with TraceAnnotation("loop.readback"):
                                nonfinite = int(float(metrics["nonfinite"]))
                            mlperf_log("guard_skip",
                                       {"step": i, "attempt": skips,
                                        "nonfinite": nonfinite})
                            history.append({"step": i, "guard_skip": skips})
                            if skips <= gcfg.max_skips:
                                if not preempted.is_set():
                                    continue
                                reason = "preempted mid-skip"
                            else:
                                reason = (f"{skips} consecutive nonfinite steps "
                                          f"at step {i}")
                        else:
                            skips = 0
                            if detector.observe(g_loss, g_gnorm) != "ok":
                                reason = (
                                    f"divergence at step {i}: loss "
                                    f"{g_loss:.4g}, grad-norm {g_gnorm:.4g} "
                                    f"vs EMA {detector.ema_gnorm or 0.0:.4g}")
                        if reason == "preempted mid-skip":
                            # a skipped step committed nothing; drain like the
                            # normal preemption path below
                            mlperf_log("preempt_drain", {"step": i})
                            if ckpt_dir and last_saved_step != int(state.step):
                                save_ckpt(state)
                            break
                        if reason is not None:
                            recovered = False
                            snap = ring.newest()
                            if snap is not None and \
                                    rollbacks < gcfg.max_rollbacks:
                                # rung 2: in-memory rollback, no checkpoint IO
                                rollbacks += 1
                                rstep, hstate = snap
                                state = RollbackRing.restore(hstate)
                                i = int(state.step)
                                if gcfg.rewarmup_steps:
                                    rewarm_start = i
                                obs_metrics.counter("obs.guard.rollback_total",
                                                    where=_WHERE, step=i)
                                mlperf_log("guard_rollback",
                                           {"resume_step": i, "used": rollbacks,
                                            "reason": reason})
                                history.append({"step": i,
                                                "guard_rollback": rollbacks})
                                if ckpt_dir:
                                    # guard-escalation save: step-tagged, so
                                    # keep_last_k retention can prune a spiky
                                    # run's trail (hand-named tags stay spared)
                                    save_ckpt(state)
                                recovered = True
                            elif ckpt_dir and restores < gcfg.max_restores:
                                # rung 3: checkpoint restore
                                try:
                                    state = ckpt.load(state, ckpt_dir, tag=None)
                                    restores += 1
                                    i = int(state.step)
                                    if gcfg.rewarmup_steps:
                                        rewarm_start = i
                                    obs_metrics.counter(
                                        "obs.guard.restore_total",
                                        where=_WHERE, step=i)
                                    mlperf_log("guard_ckpt_restore",
                                               {"resume_step": i,
                                                "reason": reason})
                                    history.append({"step": i,
                                                    "guard_restore": 1})
                                    recovered = True
                                except ckpt.CheckpointError as err:
                                    mlperf_log("guard_no_checkpoint",
                                               {"step": i, "error": str(err)})
                            if not recovered:
                                # rung 4: bounded-retry exhaustion
                                raise RuntimeError(
                                    f"numerical guard exhausted its recovery "
                                    f"ladder ({rollbacks} rollbacks, {restores} "
                                    f"checkpoint restores) — {reason}")
                            skips = 0
                            continue
                        if ring is not None and int(state.step) % max(
                                gcfg.snapshot_every, 1) == 0:
                            # snapshot only a state that passed sentinel AND
                            # detector: a spiked state is never a restore target
                            ring.snapshot(state)
                    if logged(i):
                        log_step(i, metrics)
                if eval_every and eval_fn is not None \
                        and (i + 1) % eval_every == 0:
                    drain()
                    with TraceAnnotation("loop.eval"):
                        mlperf_log("eval_start")
                        eb = eval_batch_fn(state.step + 100_000)
                        ep = params_reader(state)
                        em = {k: float(v) for k, v in
                              eval_fn(ep, eb, state.bn_state).items()}
                        mlperf_log("eval_accuracy",
                                   {"step": i, **{k: round(v, 4)
                                                  for k, v in em.items()}})
                        mlperf_log("eval_stop")
                        history.append({"step": i, **{f"eval_{k}": v
                                                      for k, v in em.items()}})
                i += 1
                if ckpt_dir and ckpt_every and i % ckpt_every == 0:
                    drain()
                    save_ckpt(state)
                if preempted.is_set():
                    # announced preemption: the in-flight step has drained
                    # — commit the tail and hand back a resumable state.
                    # Guarded by last_saved_step like the run-stop tail: a
                    # drained step that also landed on the ckpt_every
                    # cadence was saved two lines up and must not commit
                    # the same step twice.
                    drain()
                    mlperf_log("preempt_drain", {"step": i})
                    if ckpt_dir and last_saved_step != int(state.step):
                        save_ckpt(state)
                    break
                if i == steps:
                    # the run's end: the state returned is ready and the
                    # history holds every step
                    drain()
        if ckpt_dir and last_saved_step != int(state.step):
            # run_stop tail: steps not a multiple of ckpt_every (or no
            # periodic cadence at all) must still leave a final checkpoint
            save_ckpt(state)
    finally:
        if old_handler is not None:
            signal.signal(signal.SIGTERM, old_handler)
    dt = time.time() - t0
    mlperf_log("run_stop", {"steps": int(state.step),
                            "wall_s": round(dt, 2),
                            "preempted": preempted.is_set()})
    mlperf_log("run_final")
    return state, history
