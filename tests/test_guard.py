"""Numerical-integrity guard (docs/elastic.md §Numerical faults): the
in-graph NaN sentinel with its lax.cond skip gate, the host-side EMA
divergence detector, the in-memory rollback ring + LR re-warmup, the
nan/spike fault kinds, and the recovery-ladder escalation in the loop —
plus the 8-device subprocess acceptance run proving a faulted guarded run
lands within 1e-6 of the uninjected oracle."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import CommConfig
from repro.configs.shapes import InputShape
from repro.core import lars
from repro.core.schedule import ScheduleConfig, make_schedule
from repro.data.synthetic import make_batch_fn
from repro.models.registry import build_model
from repro.obs import metrics as obs_metrics
from repro.train import checkpoint as ckpt
from repro.train import faults, guard, loop
from repro.train import state as st
from repro.train.state import TrainState
from repro.train.step import make_train_step
from repro.launch.mesh import make_mesh

pytestmark = pytest.mark.tier1


# --------------------------------------------------------------- helpers

# the guarded reduced-ResNet ZeRO-1 step compiles once per process (~15s);
# every in-process test below shares this construction
_CACHE = {}


def _guarded_setup():
    if not _CACHE:
        cfg = get_config("resnet50").reduced()
        model = build_model(cfg)
        mesh = make_mesh((1, 1), ("data", "model"))
        sched = make_schedule(ScheduleConfig(base_lr=0.1, warmup_steps=2,
                                             total_steps=10))
        cc = CommConfig(strategy="ring", bucket_mb=0.25, sharding="zero1")
        step = make_train_step(model, lars.OptConfig(kind="lars"), sched,
                               mesh=mesh, comm=cc, guard=True)
        bf = make_batch_fn(cfg, InputShape("t", "train", 8, 4), seed=0,
                           mesh=mesh)
        _CACHE["v"] = (cfg, model, mesh, step, bf)
    return _CACHE["v"]


def _init():
    _, model, mesh, step, _ = _guarded_setup()
    return st.init_state(model, 0, mesh, sharded_plan=step.bucket_plan,
                         n_shards=step.n_shards)


def _scripted_guarded_step(spike_at=None, skip_from=None):
    """A cheap fake guarded step for loop-ladder tests: pure function of
    ``state.step`` (jit-safe), so a spike recurs on replay — the detector's
    hysteresis must carry the run past it — and a skip recurs forever,
    driving the ladder to escalation/exhaustion."""
    def step(state, batch, guard_in):
        s = state.step
        one = jnp.float32(1.0)
        gnorm = one
        if spike_at is not None:
            gnorm = jnp.where(s == spike_at, jnp.float32(1e6), one)
        skipped = jnp.float32(0)
        if skip_from is not None:
            skipped = jnp.where(s >= skip_from, one, jnp.float32(0))
        ok = skipped == 0
        p = {k: jnp.where(ok, v + 1.0, v) for k, v in state.params.items()}
        new = TrainState(jnp.where(ok, s + 1, s), p, state.mom, None, None)
        m = {"loss": one, "lr": jnp.float32(0.1), "gnorm": gnorm,
             "nonfinite": jnp.where(ok, jnp.float32(0), jnp.float32(4)),
             "skipped": skipped}
        return new, m
    step.guarded = True
    return step


def _fake_state():
    return TrainState(jnp.int32(0), {"w": jnp.zeros((4,))},
                      {"w": jnp.zeros((4,))}, None, None)


def _fake_batch(step):
    return {"x": jnp.zeros((2,))}


# --------------------------------------------------------- fault parsing


def test_parse_nan_spike_and_corrupt_targets():
    fs = faults.parse_faults("nan@3, spike@6:50, corrupt@4:manifest")
    assert fs == (faults.Fault("nan", 3),
                  faults.Fault("spike", 6, 50.0),
                  faults.Fault("corrupt", 4, target="manifest"))
    # payload is the default target and normalizes to ''
    assert faults.parse_faults("corrupt@4")[0].target == ""
    assert faults.parse_faults("corrupt@4:payload")[0].target == ""
    assert faults.parse_faults("corrupt@4:plan")[0].target == "plan"
    for bad in ("spike@3", "spike@3:0", "corrupt@4:bogus", "nan@x"):
        with pytest.raises(faults.FaultSpecError):
            faults.parse_faults(bad)


def test_poison_nan_hits_float_leaves_only():
    b = {"images": jnp.ones((2, 3)), "labels": jnp.zeros((2,), jnp.int32)}
    p = faults.poison_nan(b)
    assert np.isnan(np.asarray(p["images"]).reshape(-1)[0])
    assert np.isfinite(np.asarray(p["images"]).reshape(-1)[1:]).all()
    assert np.asarray(p["labels"]).dtype == np.int32
    with pytest.raises(faults.FaultSpecError, match="no float leaf"):
        faults.poison_nan({"tokens": jnp.zeros((4,), jnp.int32)})


def test_injector_faults_fire_once():
    inj = faults.FaultInjector(faults.parse_faults("nan@2,spike@5:8"))
    assert inj.loss_scale(1) == 1.0
    assert inj.loss_scale(5) == 8.0
    assert inj.loss_scale(5) == 1.0        # fired once: replay runs clean
    b = {"x": jnp.ones((2,))}
    assert np.isnan(np.asarray(inj.poison_batch(b, 2)["x"])[0])
    assert np.isfinite(np.asarray(inj.poison_batch(b, 2)["x"])).all()


# ------------------------------------------------------- detector + ring


def test_detector_arms_trips_and_rearms():
    d = guard.DivergenceDetector(guard.GuardConfig(
        min_history=3, spike_factor=10.0, rearm_factor=2.0))
    for _ in range(3):
        assert d.observe(1.0, 1.0) == "ok"
    assert d.observe(1.0, 100.0) == "diverged"       # gnorm spike trips
    assert d.tripped
    # hysteresis: while tripped, the same spike does not re-trip (no
    # rollback storm), and the suspicious value never enters the EMA
    assert d.observe(1.0, 100.0) == "ok"
    assert d.tripped and d.ema_gnorm == pytest.approx(1.0)
    # a normal observation re-arms
    assert d.observe(1.0, 1.0) == "ok"
    assert not d.tripped
    assert d.observe(1.0, 100.0) == "diverged"       # armed again
    # loss spikes trip too
    d2 = guard.DivergenceDetector(guard.GuardConfig(min_history=1))
    d2.observe(1.0, 1.0)
    assert d2.observe(1e3, 1.0) == "diverged"


def test_detector_nonfinite_is_divergence_even_cold():
    d = guard.DivergenceDetector(guard.GuardConfig())
    assert d.observe(float("nan"), 1.0) == "diverged"
    assert d.observe(1.0, float("inf")) == "diverged"


def test_rollback_ring_bounds_and_roundtrip():
    r = guard.RollbackRing(2)
    for i in range(3):
        s = TrainState(jnp.int32(i), {"w": jnp.full((4,), float(i))},
                       {"w": jnp.zeros((4,))}, None, None)
        r.snapshot(s)
    assert len(r) == 2                       # bounded: oldest evicted
    step_i, host = r.newest()
    assert step_i == 2
    back = guard.RollbackRing.restore(host)
    np.testing.assert_array_equal(np.asarray(back.params["w"]), 2.0)
    assert r.newest() is not None            # kept: a second trip can reuse
    # capacity 0 disables the ring entirely
    r0 = guard.RollbackRing(0)
    r0.snapshot(_fake_state())
    assert len(r0) == 0 and r0.newest() is None


def test_rewarmup_scale_composes_schedule():
    f = guard.rewarmup_scale_fn(4)
    assert f(0) == pytest.approx(0.25)       # lr/4 on the first replay
    assert f(3) == pytest.approx(1.0)
    assert f(10) == pytest.approx(1.0)       # clamped past the window
    assert f(-1) == 1.0
    off = guard.rewarmup_scale_fn(0)         # 0 disables: scale == 1.0
    assert all(off(k) == 1.0 for k in range(5))


# --------------------------------------------------- in-graph sentinel


def test_sentinel_commits_clean_and_skips_nonfinite():
    _, _, _, step, bf = _guarded_setup()
    s0 = _init()
    f = jax.jit(step)
    neutral = guard.neutral_inputs()
    s1, m1 = jax.block_until_ready(f(s0, bf(s0.step), neutral))
    assert int(s1.step) == 1 and float(m1["skipped"]) == 0.0
    assert float(m1["nonfinite"]) == 0.0
    assert np.isfinite(float(m1["gnorm"])) and float(m1["gnorm"]) > 0
    # poisoned batch: the cond gate refuses the commit — step NOT advanced,
    # every master shard bit-identical to the pre-step state
    s2, m2 = jax.block_until_ready(
        f(s1, faults.poison_nan(bf(s1.step)), neutral))
    assert int(s2.step) == 1 and float(m2["skipped"]) == 1.0
    assert float(m2["nonfinite"]) > 0
    for a, b in zip(s2.shards, s1.shards):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_spike_scales_grads_not_metrics_loss():
    """spike@s:mag must commit a finite-but-huge update (exercising the
    rollback rung, not the skip rung): the grad-norm scales by ~mag while
    the reported loss stays unscaled."""
    _, _, _, step, bf = _guarded_setup()
    s0 = _init()
    f = jax.jit(step)
    b = bf(s0.step)
    _, m1 = f(s0, b, guard.neutral_inputs())
    _, m2 = f(s0, b, {"lr_scale": np.float32(1.0),
                      "loss_scale": np.float32(50.0)})
    assert float(m2["skipped"]) == 0.0       # finite: commits
    assert float(m2["loss"]) == pytest.approx(float(m1["loss"]))
    ratio = float(m2["gnorm"]) / float(m1["gnorm"])
    assert ratio == pytest.approx(50.0, rel=0.05)


def test_guard_off_graph_is_unchanged():
    """The guard=False step stages NO sentinel ops (the happy-path graph is
    byte-identical to the pre-guard one) and its jaxpr is reproducible
    across constructions; the guarded step stages the is_finite sentinel."""
    cfg, model, mesh, gstep, bf = _guarded_setup()
    sched = make_schedule(ScheduleConfig(base_lr=0.1, warmup_steps=2,
                                         total_steps=10))
    cc = CommConfig(strategy="ring", bucket_mb=0.25, sharding="zero1")
    mk = lambda: make_train_step(model, lars.OptConfig(kind="lars"),  # noqa: E731
                                 sched, mesh=mesh, comm=cc)
    off_a, off_b = mk(), mk()
    assert not off_a.guarded and gstep.guarded
    s0 = _init()
    b = bf(s0.step)
    # the pretty-printer embeds function-object addresses in custom_vjp
    # eqn params; identical programs differ only there — normalize them
    import re
    addr = lambda t: re.sub(r"0x[0-9a-f]+", "0xADDR", t)  # noqa: E731
    jx_a = addr(str(jax.make_jaxpr(off_a)(s0, b)))
    jx_b = addr(str(jax.make_jaxpr(off_b)(s0, b)))
    assert jx_a == jx_b
    # log_softmax itself stages an is_finite (the max-shift guard), so the
    # sentinel's presence shows as strictly MORE is_finite ops, plus the
    # cond-gated commit
    jx_g = str(jax.make_jaxpr(gstep)(s0, b, guard.neutral_inputs()))
    assert jx_g.count("is_finite") > jx_a.count("is_finite")
    assert jx_g.count("cond[") > jx_a.count("cond[")


# ------------------------------------------- loop ladder: real train runs


def test_loop_nan_skip_replays_to_oracle():
    """nan@2 on a guarded ZeRO-1 run: one guard_skip event, the poisoned
    step replays clean (faults fire once), and the final masters are
    BIT-exact vs the uninjected oracle."""
    _, _, _, step, bf = _guarded_setup()
    mem = obs_metrics.MemorySink()
    with obs_metrics.default_registry().use_sink(mem):
        fin, hist = loop.train(_init(), step, bf, steps=4, log_every=0,
                               faults="nan@2")
        orc, _ = loop.train(_init(), step, bf, steps=4, log_every=0)
    assert len(mem.find("guard_skip")) == 1
    assert any("guard_skip" in h for h in hist)
    assert int(fin.step) == 4 == int(orc.step)
    for a, b in zip(fin.shards, orc.shards):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_loop_spike_rollback_replays_to_oracle(tmp_path):
    """spike@3:100 commits a finite bad update; the detector trips, the
    ring rolls back (no checkpoint IO), the replay runs unscaled, and the
    final masters are BIT-exact vs the oracle. The guard-escalation save
    is step-tagged so keep_last_k retention prunes it; a hand-named tag
    is spared (ISSUE 9 satellite: retention x guard tags)."""
    d = str(tmp_path)
    _, _, _, step, bf = _guarded_setup()
    ckpt.save(_init(), d, tag="best")        # hand-named: never pruned
    mem = obs_metrics.MemorySink()
    with obs_metrics.default_registry().use_sink(mem):
        fin, hist = loop.train(_init(), step, bf, steps=6, log_every=0,
                               ckpt_dir=d, keep_last_k=1,
                               faults=faults.FaultInjector(
                                   faults.parse_faults("spike@3:100")),
                               guard=guard.GuardConfig(spike_factor=5.0))
        orc, _ = loop.train(_init(), step, bf, steps=6, log_every=0)
    assert len(mem.find("guard_rollback")) == 1
    assert len(mem.find("obs.guard.rollback_total")) == 1
    assert any("guard_rollback" in h for h in hist)
    assert int(fin.step) == 6 == int(orc.step)
    for a, b in zip(fin.shards, orc.shards):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # retention: the step-tagged guard save (step 3) was pruned by the
    # run-stop tail save under keep_last_k=1; 'best' survived
    assert ckpt.available_tags(d) == ["best", "step00000006"]
    assert not os.path.exists(os.path.join(d, "ckpt_step00000003.npz"))


# --------------------------------------- loop ladder: escalation (scripted)


def test_ladder_ckpt_restore_rung(tmp_path):
    """Ring disabled -> a detector trip escalates straight to checkpoint
    restore; the replayed spike is held by hysteresis so the run converges.
    (The scripted spike is a pure function of step and so RECURS on
    replay — exactly the case hysteresis exists for.)"""
    d = str(tmp_path)
    mem = obs_metrics.MemorySink()
    with obs_metrics.default_registry().use_sink(mem):
        s, hist = loop.train(_fake_state(), _scripted_guarded_step(spike_at=3),
                             _fake_batch, steps=6, log_every=0,
                             ckpt_dir=d, ckpt_every=1,
                             guard=guard.GuardConfig(ring_capacity=0,
                                                     min_history=1))
    assert int(s.step) == 6
    assert len(mem.find("guard_ckpt_restore")) == 1
    assert len(mem.find("obs.guard.restore_total")) == 1
    assert len(mem.find("guard_rollback")) == 0
    assert any("guard_restore" in h for h in hist)


def test_ladder_exhaustion_is_bounded():
    """A step that skips every attempt (pure function of step) must walk
    skip -> rollback -> (no checkpoint) -> RuntimeError, never loop
    forever."""
    mem = obs_metrics.MemorySink()
    with obs_metrics.default_registry().use_sink(mem):
        with pytest.raises(RuntimeError, match="recovery ladder"):
            loop.train(_fake_state(), _scripted_guarded_step(skip_from=1),
                       _fake_batch, steps=6, log_every=0,
                       guard=guard.GuardConfig(max_skips=2, max_rollbacks=1,
                                               min_history=1))
    assert len(mem.find("guard_rollback")) == 1      # bounded rollbacks
    assert len(mem.find("guard_skip")) >= 3          # max_skips exceeded


def test_loop_guard_requires_guarded_step():
    def plain(state, batch):
        return state, {"loss": jnp.float32(1.0)}
    with pytest.raises(ValueError, match="guarded step"):
        loop.train(_fake_state(), plain, _fake_batch, steps=1,
                   log_every=0, guard=guard.GuardConfig())


# ------------------------------- subprocess: 8-device acceptance (tier 2)


def _run_cli(argv, timeout=900):
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.train"] + argv,
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "PYTHONPATH": "src",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             # room for every device's part (tests/test_comm.py)
             "NPROC": "32"})


@pytest.mark.tier2
def test_guard_8dev_faulted_run_matches_oracle(tmp_path):
    """The ISSUE 9 acceptance run: an 8-device guarded ZeRO-1 run with
    ``--inject-fault nan@3,spike@6:50`` finishes, emits guard_skip and
    guard_rollback on the metrics stream, and its final masters are within
    1e-6 of the uninjected oracle (the skipped/rolled-back steps were
    replayed, not dropped)."""
    d_f, d_o = str(tmp_path / "faulted"), str(tmp_path / "oracle")
    jsonl = str(tmp_path / "metrics.jsonl")
    base = ["--arch", "resnet50", "--reduced", "--batch", "32", "--seq", "0",
            "--steps", "8", "--warmup", "2", "--comm", "ring",
            "--bucket-mb", "0.25", "--sharding", "zero1", "--guard",
            "--rollback-ring", "4", "--rollback-every", "1",
            "--rewarmup-steps", "0"]
    r_f = _run_cli(base + ["--inject-fault", "nan@3,spike@6:50",
                           "--ckpt-dir", d_f, "--metrics", jsonl])
    assert r_f.returncode == 0, r_f.stderr[-3000:]
    r_o = _run_cli(base + ["--ckpt-dir", d_o])
    assert r_o.returncode == 0, r_o.stderr[-3000:]

    with open(jsonl) as f:
        names = [json.loads(line)["name"] for line in f]
    assert "guard_armed" in names
    assert "guard_skip" in names, names
    assert "guard_rollback" in names, names

    meta_f, data_f, _ = ckpt.load_arrays(d_f, tag=None)
    meta_o, data_o, _ = ckpt.load_arrays(d_o, tag=None)
    assert meta_f["step"] == 8 == meta_o["step"]
    shard_keys = sorted(k for k in data_o if k.startswith("shards"))
    assert shard_keys, sorted(data_o)[:5]
    worst = 0.0
    for k in shard_keys:
        worst = max(worst, float(np.abs(data_f[k] - data_o[k]).max()))
        np.testing.assert_allclose(data_f[k], data_o[k], rtol=0, atol=1e-6)
    print(f"max |faulted - oracle| over masters: {worst:.3g}")
    print("GUARD-OK")
