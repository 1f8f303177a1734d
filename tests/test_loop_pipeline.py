"""The plain training loop keeps one step in flight.

Without the watchdog or the guard, ``loop.train`` dispatches step i
before it waits on step i-1 and reads step i-1's metrics back, and drains
the step in flight at every checkpoint, eval, preemption and at the end of
the run. The watchdog path (``step_timeout_s > 0``) runs each step to its
end, so it is the reference: both paths must give the same final state,
bit for bit, the same history and the same checkpoint, eval and drain
events. Fake ``TrainState`` steps only: nothing here compiles a model.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import metrics as obs_metrics
from repro.train import loop
from repro.train.state import TrainState

pytestmark = pytest.mark.tier1

COUNTERS = ("obs.loop.overlapped_total", "obs.loop.drain_total")
#: the events whose order the two paths share (a fault's own events and
#: ``sigterm_received`` come when the signal does)
ORDERED = ("train_step", "eval_accuracy", "checkpoint_saved", "preempt_drain")


def _state():
    return TrainState(jnp.int32(0), {"w": jnp.arange(4.0)},
                      {"w": jnp.zeros((4,))})


def _step(state, batch):
    mom = 0.9 * state.mom["w"] + jnp.cos(state.params["w"]) * batch["x"]
    w = state.params["w"] - 0.1 * mom
    return (TrainState(state.step + 1, {"w": w}, {"w": mom}),
            {"loss": jnp.sum(w * w), "lr": jnp.float32(0.1)})


def _batch(step):
    return {"x": jnp.sin(jnp.arange(4.0) + step)}


def _eval(params, batch, bn_state):
    return {"err": jnp.mean(jnp.abs(params["w"] - batch["x"]))}


def _guarded_step(state, batch, guard_in):
    new, m = _step(state, batch)
    return new, {**m, "gnorm": jnp.float32(1.0),
                 "skipped": jnp.float32(0.0)}


_guarded_step.guarded = True


def _run(tmp_path, side, steps, **kw):
    """(final state, history, ordered events, loop counter increments)."""
    if "ckpt_every" in kw or "faults" in kw:
        kw["ckpt_dir"] = str(tmp_path / side)
    before = {n: obs_metrics.counter(n, 0) for n in COUNTERS}
    with obs_metrics.default_registry().use_sink(
            obs_metrics.MemorySink()) as mem:
        state, history = loop.train(_state(), kw.pop("step", _step), _batch,
                                    steps=steps, **kw)
    counts = {n: (e[-1].value - before[n] if (e := mem.find(n)) else 0)
              for n in COUNTERS}
    events = [(e.name, e.value) for e in mem.events if e.name in ORDERED]
    return state, history, events, counts


@pytest.mark.parametrize("kw", [
    dict(log_every=1),
    dict(log_every=3),
    dict(log_every=1, ckpt_every=2),
    dict(log_every=1, eval_every=2, eval_step=_eval, eval_batch_fn=_batch),
    dict(log_every=1, faults="sigterm@1"),
], ids=["log1", "log3", "ckpt2_tail", "eval2", "sigterm1"])
def test_pipelined_loop_matches_the_synchronous_path(tmp_path, kw):
    plain = _run(tmp_path, "plain", 5, **kw)
    sync = _run(tmp_path, "sync", 5, step_timeout_s=60, **kw)
    (s, h, ev, _), (rs, rh, rev, _) = plain, sync
    assert int(s.step) == int(rs.step) == (2 if "faults" in kw else 5)
    assert jax.tree.structure(s) == jax.tree.structure(rs)
    for x, y in zip(jax.tree.leaves(s), jax.tree.leaves(rs)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert h == rh
    # the watchdog commits a baseline checkpoint of step 0 first
    if "ckpt_every" in kw or "faults" in kw:
        assert rev[0] == ("checkpoint_saved", {"step": 0,
                                               "tag": "step00000000"})
        rev = rev[1:]
    assert ev == rev
    if "ckpt_every" in kw:
        assert [v["step"] for n, v in ev if n == "checkpoint_saved"] == \
            [2, 4, 5]


def test_one_step_in_flight_order():
    """Depth one: the fetch of batch i+1 comes after step i-1's log line
    and before step i's; the last step is read at the final drain."""
    order = []

    class Record(obs_metrics.Sink):
        def emit(self, ev):
            if ev.name == "train_step":
                order.append(("log", ev.value["step"]))

    def batch_fn(step):
        order.append(("batch", sum(k == "batch" for k, _ in order)))
        return _batch(step)

    with obs_metrics.default_registry().use_sink(Record()):
        state, history = loop.train(_state(), _step, batch_fn, steps=4,
                                    log_every=1)
    assert order == [("batch", 0), ("batch", 1), ("log", 0),
                     ("batch", 2), ("log", 1), ("batch", 3), ("log", 2),
                     ("log", 3)]
    assert [h["step"] for h in history] == [0, 1, 2, 3]
    assert int(state.step) == 4


@pytest.mark.parametrize("kw,want", [
    (dict(), (5, 1)),
    (dict(ckpt_every=2), (3, 3)),
    (dict(step_timeout_s=60), (0, 0)),
    (dict(step=_guarded_step), (0, 0)),
], ids=["plain", "plain_ckpt2", "watchdog", "guarded"])
def test_loop_counters(tmp_path, kw, want):
    """Every step is either dispatched with the previous one in flight or
    waited on at a drain; the synchronous paths count neither."""
    _, history, _, counts = _run(tmp_path, "run", 6, log_every=2, **kw)
    assert [h["step"] for h in history] == [0, 2, 4, 5]
    assert (counts["obs.loop.overlapped_total"],
            counts["obs.loop.drain_total"]) == want
