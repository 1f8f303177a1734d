"""Numerical-integrity guard (docs/elastic.md §Numerical faults).

The large-batch recipe only holds the paper's 74.7 s headline together as
long as no step goes nonfinite and no loss spike knocks the trajectory off
the LARS/warmup rails — at batch 81,920 a single bad step is the dominant
*silent* failure mode (Akiba 1711.04325, Mikami 1811.05233 both report
spike/divergence episodes as the limiting factor). This module completes
the recovery ladder the step watchdog (PR 6) started, one rung per failure
class:

1. **in-graph sentinel** (:func:`apply_guard`) — nonfinite counts over the
   loss and the per-bucket grad buffers plus the global grad-norm, computed
   INSIDE the jitted step as cheap reductions that ride out on the existing
   metrics dict (no extra host sync on the happy path). A ``lax.cond``
   gates the state commit: a nonfinite step returns the *previous* state —
   step not advanced, params/momentum/shards/BN untouched — which is safe
   even under buffer donation because the cond's output aliases whichever
   branch wins. The loop sees ``metrics['skipped'] == 1`` and replays.
2. **host-side divergence detector** (:class:`DivergenceDetector`) — EMA of
   loss and grad-norm with hysteresis: trips when a committed step's values
   exceed ``spike_factor``× their EMA, then stays tripped (no rollback
   storm) until the run re-enters the ``rearm_factor``× band.
3. **in-memory rollback ring** (:class:`RollbackRing`) — bounded
   ``device_get`` snapshots of the full state every ``snapshot_every``
   steps; a detector trip rolls back to the newest snapshot WITHOUT
   checkpoint IO, optionally re-warming the LR over ``rewarmup_steps``
   (:func:`rewarmup_scale_fn`, composed from ``core/schedule.py``).
4. escalation: ring empty/exhausted → checkpoint restore → bounded-retry
   exhaustion (``RuntimeError``), exactly like the step watchdog.

The guard is opt-in per run (``make_train_step(..., guard=True)`` +
``loop.train(..., guard=GuardConfig(...))``); with it off the trained
graph is byte-identical to the unguarded one.
"""
from __future__ import annotations

import collections
import dataclasses
import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.schedule import ScheduleConfig, make_schedule


@dataclasses.dataclass(frozen=True)
class GuardConfig:
    """Knobs for the whole ladder. The defaults are deliberately
    conservative: a guard that trips on ordinary loss noise costs more
    replayed steps than it saves."""
    # rung 1 — sentinel skip
    max_skips: int = 3          # consecutive skips before escalating
    # rung 2 — divergence detector
    ema_beta: float = 0.9       # EMA decay for loss/grad-norm
    spike_factor: float = 10.0  # trip at value > spike_factor * EMA
    rearm_factor: float = 2.0   # re-arm once value <= rearm_factor * EMA
    min_history: int = 3        # ok steps observed before the detector arms
    # rung 3 — in-memory rollback ring
    ring_capacity: int = 2      # snapshots held (0 disables the ring)
    snapshot_every: int = 1     # device_get cadence in steps
    max_rollbacks: int = 2      # ring rollbacks before escalating further
    rewarmup_steps: int = 0     # LR re-warmup window after a recovery
    # rung 4 — checkpoint restore
    max_restores: int = 2       # checkpoint restores before giving up


# ------------------------------------------------------- in-graph sentinel


def nonfinite_count(tree) -> jax.Array:
    """int32 count of nonfinite entries over every leaf of ``tree``."""
    leaves = jax.tree.leaves(tree)
    total = jnp.int32(0)
    for leaf in leaves:
        total = total + jnp.sum(~jnp.isfinite(leaf)).astype(jnp.int32)
    return total


def sq_sum(tree) -> jax.Array:
    """f32 sum of squares over every leaf (grad-norm² before reduction)."""
    leaves = jax.tree.leaves(tree)
    total = jnp.float32(0)
    for leaf in leaves:
        total = total + jnp.sum(jnp.square(leaf.astype(jnp.float32)))
    return total


def scale_loss(loss_fn: Callable, scale) -> Callable:
    """Wrap a ``(total, aux)`` loss so the differentiated total is scaled —
    the spike-injection hook (``spike@s:mag`` rides in through the guarded
    step's ``loss_scale`` input; 1.0 on every un-faulted step). The metrics
    inside ``aux`` keep the UNscaled loss, so the detector sees the spike
    through the grad-norm, not a cosmetic loss blow-up."""
    def scaled(*args):
        total, aux = loss_fn(*args)
        return total * scale, aux
    return scaled


def apply_guard(prev_state, new_state, metrics, grads, *, psum_axis=None):
    """The sentinel + skip gate, called at the tail of a guarded step.

    ``grads`` is whatever the step differentiated into — the packed
    per-bucket shard buffers on the zero1/zero3 paths (device-local chunks:
    pass ``psum_axis=shard_axis`` so the count/norm reduce to the global
    value, replicated like the rest of the metrics) or the full reduced
    grad pytree on the replicated/xla paths (already identical everywhere;
    no psum). ``metrics['loss']`` must already be the replicated (pmean'd)
    loss. Returns ``(committed_state, metrics)`` where the metrics gain
    ``gnorm`` / ``nonfinite`` / ``skipped`` scalar rows and the state is
    ``new_state`` iff everything was finite, else ``prev_state`` untouched
    (step included — the loop replays)."""
    bad = nonfinite_count(grads)
    sq = sq_sum(grads)
    if psum_axis is not None:
        bad = jax.lax.psum(bad, psum_axis)
        sq = jax.lax.psum(sq, psum_axis)
    loss = jnp.asarray(metrics["loss"], jnp.float32)
    bad = bad + (~jnp.isfinite(loss)).astype(jnp.int32)
    gnorm = jnp.sqrt(sq)
    ok = (bad == 0) & jnp.isfinite(gnorm)
    committed = jax.lax.cond(ok, lambda: new_state, lambda: prev_state)
    metrics = dict(metrics, gnorm=gnorm,
                   nonfinite=bad.astype(jnp.float32),
                   skipped=jnp.where(ok, jnp.float32(0), jnp.float32(1)))
    return committed, metrics


#: metrics keys a guarded step appends (loop + shard_map out_specs use it)
SENTINEL_KEYS = ("gnorm", "nonfinite", "skipped")


def neutral_inputs():
    """The happy-path ``guard_in``: no LR rescale, no loss spike."""
    import numpy as np
    return {"lr_scale": np.float32(1.0), "loss_scale": np.float32(1.0)}


# -------------------------------------------------- host-side detector


class DivergenceDetector:
    """EMA of (loss, grad-norm) with hysteresis.

    ``observe`` returns ``'ok'`` or ``'diverged'``. The detector arms only
    after ``min_history`` ok steps (cold-start values are not a baseline),
    trips when either value exceeds ``spike_factor``× its EMA, and then
    holds (no repeated trips, no EMA absorption of suspicious values)
    until both values re-enter the ``rearm_factor``× band. A rolled-back
    run replaying clean steps therefore re-arms on its first normal
    observation instead of rolling back again on the same spike."""

    def __init__(self, cfg: GuardConfig):
        self.cfg = cfg
        self.ema_loss: Optional[float] = None
        self.ema_gnorm: Optional[float] = None
        self.n_ok = 0
        self.tripped = False

    def _update(self, loss: float, gnorm: float) -> None:
        b = self.cfg.ema_beta
        self.ema_loss = (loss if self.ema_loss is None
                         else b * self.ema_loss + (1 - b) * loss)
        self.ema_gnorm = (gnorm if self.ema_gnorm is None
                          else b * self.ema_gnorm + (1 - b) * gnorm)
        self.n_ok += 1

    def observe(self, loss: float, gnorm: float) -> str:
        if not (math.isfinite(loss) and math.isfinite(gnorm)):
            # should have been skipped in-graph; treat as divergence
            self.tripped = True
            return "diverged"
        if self.n_ok < self.cfg.min_history:
            self._update(loss, gnorm)
            return "ok"
        over = (gnorm > self.cfg.spike_factor * self.ema_gnorm
                or loss > self.cfg.spike_factor * self.ema_loss)
        if self.tripped:
            if (gnorm <= self.cfg.rearm_factor * self.ema_gnorm
                    and loss <= self.cfg.rearm_factor * self.ema_loss):
                self.tripped = False
                self._update(loss, gnorm)
            return "ok"        # hysteresis: already handled, don't re-trip
        if over:
            self.tripped = True
            return "diverged"
        self._update(loss, gnorm)
        return "ok"


# ------------------------------------------------- in-memory rollback ring


class RollbackRing:
    """Bounded ring of host-side state snapshots (``jax.device_get`` of the
    full TrainState — shards, momentum, bn_state, params, step). Rolling
    back is a pure host->device transfer: no checkpoint IO on the fast
    recovery rung. Snapshots are taken only AFTER a step passes both the
    sentinel and the detector, so a spiked state is never a restore
    target."""

    def __init__(self, capacity: int):
        self.capacity = int(capacity)
        self._ring = collections.deque(maxlen=max(self.capacity, 1))

    def __len__(self) -> int:
        return len(self._ring) if self.capacity > 0 else 0

    def snapshot(self, state) -> None:
        if self.capacity <= 0:
            return
        from repro.train.state import host_snapshot
        self._ring.append((int(state.step), host_snapshot(state)))

    def newest(self) -> Optional[Tuple[int, object]]:
        """Newest (step, host_state) snapshot, or None. Kept in the ring —
        a second trip can roll back to the same point (bounded by
        ``GuardConfig.max_rollbacks``)."""
        if not len(self):
            return None
        return self._ring[-1]

    @staticmethod
    def restore(host_state):
        """Host snapshot back onto devices (the jitted step's in_specs
        place it; nothing here depends on the mesh)."""
        from repro.train.state import restore_snapshot
        return restore_snapshot(host_state)


# ----------------------------------------------------------- LR re-warmup


def rewarmup_scale_fn(rewarmup_steps: int) -> Callable[[int], float]:
    """LR scale for the ``rewarmup_steps`` after a recovery, composed from
    ``core/schedule.py``: a unit-base-lr warmup whose output multiplies the
    run's real schedule, so the re-warmed LR ramps ``lr(step)/n .. lr(step)``
    over the window and is exactly ``lr(step)`` outside it. ``0`` disables
    (scale ≡ 1.0 — the trajectory-preserving setting the acceptance test
    relies on)."""
    if rewarmup_steps <= 0:
        return lambda k: 1.0
    sched = make_schedule(ScheduleConfig(
        base_lr=1.0, warmup_steps=rewarmup_steps,
        total_steps=rewarmup_steps + 1, decay="const"))

    def scale(k: int) -> float:
        if k < 0:
            return 1.0
        return float(sched(min(k, rewarmup_steps)))
    return scale
