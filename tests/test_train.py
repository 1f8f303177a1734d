"""Integration tests: training makes progress; explicit-DDP paths agree;
checkpoint round-trips (incl. the ZeRO-1 sharded state); determinism of
seeded runs and of the overlap/gather-ahead graph variants."""
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
from repro.data.synthetic import make_batch_fn, token_batch
from repro.models.registry import build_model
from repro.train import checkpoint as ckpt
from repro.train import state as st
from repro.train.step import make_eval_step, make_train_step
from repro.launch.mesh import make_mesh

pytestmark = pytest.mark.tier1


def _train(arch, steps, *, opt="lars", lr=2.0, comm="xla", mesh=None,
           batch=8, seq=64, warmup=None):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    mesh = mesh or make_mesh((1, 1), ("data", "model"))
    sched = make_schedule(ScheduleConfig(
        base_lr=lr, warmup_steps=warmup if warmup is not None else steps // 8,
        total_steps=steps, decay="poly2"))
    step = jax.jit(make_train_step(model, lars.OptConfig(kind=opt), sched,
                                   mesh=mesh, comm=comm))
    bf = make_batch_fn(cfg, InputShape("t", "train", seq, batch), mesh=mesh)
    s = st.init_state(model, 0, mesh, opt_kind=opt)
    losses = []
    for _ in range(steps):
        s, m = step(s, bf(s.step))
        losses.append(float(m["loss"]))
    return losses, s


@pytest.mark.parametrize("extra", [[], ["--comm", "ring", "--sharding",
                                        "zero1"]], ids=["xla", "ring-zero1"])
def test_launcher_compiles_each_step_program_once(extra):
    """Steps 1.. reuse step 0's executables: ``init_state`` places the
    state where the step returns it, so neither the train step nor the
    batch function (fed ``state.step``) compiles a second time."""
    from repro.launch import train as launcher
    compiled = []

    def on_event(event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        _, history = launcher.main(
            ["--arch", "resnet50", "--reduced", "--batch", "8", "--steps",
             "3", "--devices", "1", "--log-every", "1", *extra])
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert [h["step"] for h in history] == [0, 1, 2]
    assert compiled.count("jit(train_step)") == 1, compiled
    assert compiled.count("jit(<lambda>)") == 1, compiled


def test_compile_cache_is_a_fixed_dir_in_the_checkout(monkeypatch):
    """Off the CPU the launcher caches compiled programs in
    ``<checkout>/.jax_cache``, unless ``JAX_COMPILATION_CACHE_DIR`` names
    a directory; on the CPU it caches nothing."""
    from repro.launch import compile_cache as cc
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert str(cc.CACHE_DIR) == os.path.join(checkout, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        cc.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/set/by/the/host")
        cc.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        cc.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(cc.CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_loss_decreases_lm():
    losses, _ = _train("qwen1.5-0.5b", 40)
    assert losses[-1] < losses[0] - 0.3, losses[::8]
    assert all(np.isfinite(l) for l in losses)


def test_loss_decreases_resnet():
    losses, _ = _train("resnet50", 30, lr=0.5, batch=16, seq=0)
    assert losses[-1] < losses[0] - 0.2, losses[::6]


def test_lars_stable_where_sgd_diverges_high_lr():
    """The paper's motivation: LARS keeps very-high-lr training finite."""
    lars_losses, _ = _train("qwen1.5-0.5b", 12, opt="lars", lr=30.0,
                            warmup=0)
    assert all(np.isfinite(l) for l in lars_losses)
    assert lars_losses[-1] < 3 * lars_losses[0] + 10


def test_checkpoint_roundtrip(tmp_path):
    _, s = _train("qwen1.5-0.5b", 3)
    ckpt.save(s, str(tmp_path))
    cfg = get_config("qwen1.5-0.5b").reduced()
    model = build_model(cfg)
    template = st.init_state(model, 123)
    restored = ckpt.load(template, str(tmp_path))
    assert int(restored.step) == int(s.step)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 s.params, restored.params)


def test_data_pipeline_deterministic_and_step_dependent():
    cfg = get_config("qwen1.5-0.5b").reduced()
    b1 = token_batch(cfg, batch=4, seq=32, step=jnp.int32(5), seed=0)
    b2 = token_batch(cfg, batch=4, seq=32, step=jnp.int32(5), seed=0)
    b3 = token_batch(cfg, batch=4, seq=32, step=jnp.int32(6), seed=0)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["labels"][:, :-1], b1["tokens"][:, 1:])


def test_lcg_stream_is_learnable_structure():
    cfg = get_config("qwen1.5-0.5b").reduced()
    b = token_batch(cfg, batch=2, seq=64, step=jnp.int32(0), seed=0,
                    kind="lcg")
    t = np.asarray(b["tokens"])
    pred = (5 * t[:, :-1] + 7) % cfg.vocab_size
    match = (pred == t[:, 1:]).mean()
    assert match > 0.85      # 5% noise


def test_eval_step_runs():
    cfg = get_config("resnet50").reduced()
    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    s = st.init_state(model, 0)
    from repro.data.synthetic import prototype_imagenet
    batch = prototype_imagenet(cfg, batch=8, step=jnp.int32(0))
    ev = jax.jit(make_eval_step(model, mesh=mesh))
    m = ev(s.params, batch, s.bn_state)
    assert 0.0 <= float(m["acc"]) <= 1.0


DDP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import get_config
from repro.configs.shapes import InputShape
from repro.models.registry import build_model
from repro.train import state as st
from repro.train.step import make_train_step
from repro.core import lars
from repro.core.schedule import ScheduleConfig, make_schedule
from repro.data.synthetic import make_batch_fn

mesh = make_mesh((8, 1), ("data", "model"))
cfg = get_config("resnet50").reduced()
model = build_model(cfg)
sched = make_schedule(ScheduleConfig(base_lr=0.2, warmup_steps=1,
                                     total_steps=20))
bf = make_batch_fn(cfg, InputShape("t", "train", 0, 16), mesh=mesh)
res = {}
for comm in ("naive", "bucketed"):
    s = st.init_state(model, 0, mesh)
    step = jax.jit(make_train_step(model, lars.OptConfig(kind="lars"),
                                   sched, mesh=mesh, comm=comm,
                                   bucket_mb=0.25))
    for i in range(3):
        s, m = step(s, bf(s.step))
    res[comm] = jax.tree.leaves(s.params)[0]
# naive and bucketed are separately-jitted graphs: XLA fuses the bf16
# forward/backward differently around the collectives, and 3 LARS steps
# amplify those ulp-level diffs — so this is a stability check, not a
# parity check (exact parity is asserted within one graph below and in
# tests/test_comm.py)
for v in res.values():
    assert np.isfinite(np.asarray(v)).all()
np.testing.assert_allclose(np.asarray(res["naive"]),
                           np.asarray(res["bucketed"]), atol=5e-2)

# one-graph gradient parity (paper SIII-C: bucketing is a pure comm-layout
# change): reduce the SAME grads both ways inside one jitted graph
from jax.sharding import PartitionSpec as P
from repro.core import bucketing, ddp
from repro.core.compat import shard_map
gtree = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32),
                     st.init_state(model, 1).params)
gplan = bucketing.make_plan(gtree, bucket_mb=0.25)
gspec = jax.tree.map(lambda _: P(), gtree)
def both(t):
    r = jax.lax.axis_index("data")
    t = jax.tree.map(lambda x: x * (1.0 + 0.1 * r), t)
    a = ddp.allreduce_grads(t, strategy="naive", axes=("data",), plan=gplan)
    b = ddp.allreduce_grads(t, strategy="bucketed", axes=("data",),
                            plan=gplan)
    return a, b
a, b = jax.jit(shard_map(both, mesh=mesh, in_specs=(gspec,),
                         out_specs=(gspec, gspec)))(gtree)
jax.tree.map(lambda x, y: np.testing.assert_allclose(
    np.asarray(x), np.asarray(y), rtol=1e-5), a, b)

# CommConfig routing: a composable schedule (f32 wire) must train
# identically to the fused psum baseline (f32 wire)
from repro.configs.base import CommConfig
res = {}
for strat in ("psum", "ring"):
    s = st.init_state(model, 0, mesh)
    cc = CommConfig(strategy=strat, bucket_mb=0.25, wire_dtype="f32")
    step = jax.jit(make_train_step(model, lars.OptConfig(kind="lars"),
                                   sched, mesh=mesh, comm=cc))
    for i in range(2):
        s, m = step(s, bf(s.step))
    res[strat] = jax.tree.leaves(s.params)[0]
np.testing.assert_allclose(np.asarray(res["psum"]),
                           np.asarray(res["ring"]), atol=1e-6)
print("DDP-OK")
"""


@pytest.mark.tier2
def test_bucketed_allreduce_equals_naive_8dev():
    """Paper §III-C on 8 host devices (subprocess: device count locks at
    jax init). Three claims: (1) naive and bucketed training are both
    stable and land close (loose atol — separately-jitted graphs differ at
    ulp level in the bf16 forward and LARS amplifies that); (2) reducing
    the SAME grads naive vs bucketed inside ONE graph is parity to 1e-5
    (the §III-C pure-comm-layout claim); (3) composable schedules routed
    via CommConfig train identically to fused psum at f32 wire."""
    # inherit the parent env: JAX_PLATFORMS=cpu must reach the child or
    # jax probes for TPUs for minutes at import
    r = subprocess.run([sys.executable, "-c", DDP_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert "DDP-OK" in r.stdout, r.stderr[-2000:]


def test_lamb_trains():
    """Beyond-paper: LAMB (LARS lineage) on the LM family."""
    losses, _ = _train("qwen1.5-0.5b", 25, opt="lamb", lr=0.01)
    assert losses[-1] < losses[0] - 0.2, losses[::5]


def test_grad_accum_matches_full_batch():
    """grad_accum=N over the same examples == one full-batch step."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    sched = make_schedule(ScheduleConfig(base_lr=0.1, warmup_steps=1,
                                         total_steps=10))
    bf = make_batch_fn(cfg, InputShape("t", "train", 32, 8), mesh=mesh)
    b = bf(jnp.int32(0))
    outs = []
    for ga in (1, 4):
        s = st.init_state(model, 0)
        step = jax.jit(make_train_step(model, lars.OptConfig(kind="lars"),
                                       sched, mesh=mesh, grad_accum=ga))
        s, _ = step(s, b)
        outs.append(s.params)
    # bf16 microbatch grads + LARS trust-ratio amplification leave a
    # small numerical gap vs the single full-batch step
    jax.tree.map(lambda a, c: np.testing.assert_allclose(a, c, atol=3e-4),
                 outs[0], outs[1])


def test_lamb_trust_ratio_is_norm_ratio():
    params = {"w": jnp.full((4, 4), 2.0)}
    grads = {"w": jnp.full((4, 4), 1.0)}
    mom = lars.init_momentum(params, "lamb")
    cfg = lars.OptConfig(kind="lamb", momentum=0.0, beta2=0.0,
                         weight_decay=0.0, eps=0.0)
    p2, m2 = lars.update(params, grads, mom, 0.5, cfg)
    # update u = g/|g| elementwise = 1; ratio = |w|/|u| = 2; step = lr*2*1
    np.testing.assert_allclose(p2["w"], 2.0 - 0.5 * 2.0, rtol=1e-5)
    assert int(m2["count"]) == 1


# -------------------- ZeRO-1 sharded state: determinism + checkpointing


def _train_sharded(comm_cfg, steps=3, seed=0):
    """Run ``steps`` sharded ResNet steps on the (1,1) mesh; returns
    (train_step, jitted fn, final state, losses)."""
    cfg = get_config("resnet50").reduced()
    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    sched = make_schedule(ScheduleConfig(base_lr=0.5, warmup_steps=1,
                                         total_steps=10))
    step = make_train_step(model, lars.OptConfig(kind="lars"), sched,
                           mesh=mesh, comm=comm_cfg)
    assert step.shard_update
    f = jax.jit(step)
    bf = make_batch_fn(cfg, InputShape("t", "train", 0, 8), mesh=mesh,
                       seed=seed)
    s = st.init_state(model, seed, mesh, sharded_plan=step.bucket_plan,
                      n_shards=step.n_shards)
    losses = []
    for _ in range(steps):
        s, m = f(s, bf(s.step))
        losses.append(float(m["loss"]))
    return step, f, s, losses


def test_sharded_runs_bit_identical():
    """Determinism: two identical seeded fully-overlapped sharded runs
    (in-backward RS + gather-ahead, the default bf16 wire) are
    bit-identical over 3 steps — losses, persistent master shards,
    momentum shards, and the forward params copy."""
    cc = CommConfig(strategy="ring", bucket_mb=0.25, shard_update=True)
    _, _, s1, l1 = _train_sharded(cc)
    _, _, s2, l2 = _train_sharded(cc)
    assert l1 == l2, (l1, l2)
    for a, b in [(s1.shards, s2.shards), (s1.mom, s2.mom),
                 (s1.params, s2.params)]:
        jax.tree.map(lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)), a, b)


def test_sharded_overlap_and_gather_variants_agree():
    """Overlap on/off (in-backward vs post-backward reduce-scatter) and
    gather-ahead on/off (step-start vs step-end all-gather) are the same
    math in different graphs: over 3 steps the persistent masters stay
    within fp32 tolerance of each other (cross-graph XLA fusion costs
    ulps; LARS amplifies them slightly)."""
    base_cc = CommConfig(strategy="ring", bucket_mb=0.25, wire_dtype="f32",
                         shard_update=True)
    step0, _, s0, l0 = _train_sharded(base_cc)
    p0 = st.full_params_from_shards(s0.shards, step0.bucket_plan,
                                    step0.n_shards)
    for variant in [CommConfig(strategy="ring", bucket_mb=0.25,
                               wire_dtype="f32", shard_update=True,
                               overlap=False),
                    CommConfig(strategy="ring", bucket_mb=0.25,
                               wire_dtype="f32", shard_update=True,
                               gather_ahead=False)]:
        stepv, _, sv, lv = _train_sharded(variant)
        pv = st.full_params_from_shards(sv.shards, stepv.bucket_plan,
                                        stepv.n_shards)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5), p0, pv)
        assert abs(l0[-1] - lv[-1]) <= 1e-4, (variant, l0, lv)


def test_checkpoint_roundtrip_sharded(tmp_path):
    """Checkpointing the ZeRO-1 state: save a shard_update=True state
    (persistent master shards + sharded momentum) after 2 steps, restore
    it into a freshly-initialized template, resume for 1 step, and land
    bit-identical to the uninterrupted 3-step run."""
    cc = CommConfig(strategy="ring", bucket_mb=0.25, shard_update=True)
    step, f, s2, _ = _train_sharded(cc, steps=2)
    ckpt.save(s2, str(tmp_path))

    cfg = get_config("resnet50").reduced()
    model = build_model(cfg)
    template = st.init_state(model, 123, sharded_plan=step.bucket_plan,
                             n_shards=step.n_shards)
    restored = ckpt.load(template, str(tmp_path))
    assert int(restored.step) == 2
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), tuple(s2.shards),
        tuple(restored.shards))

    # resume one step (same jitted fn => same executable) and compare to
    # the uninterrupted third step
    bf = make_batch_fn(cfg, InputShape("t", "train", 0, 8),
                       mesh=make_mesh((1, 1), ("data", "model")))
    s3, m3 = f(s2, bf(s2.step))
    r3, mr3 = f(restored, bf(restored.step))
    assert float(m3["loss"]) == float(mr3["loss"])
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), tuple(s3.shards), tuple(r3.shards))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), tuple(s3.mom), tuple(r3.mom))


def test_checkpoint_rejects_shard_mismatch(tmp_path):
    """Shard-layout mismatches must fail loudly in BOTH directions: a
    non-sharded checkpoint into a sharded template, and a sharded
    checkpoint (whose params copy may lag the masters) into a non-sharded
    template (the shard-unaware failure modes this PR fixes)."""
    _, s = _train("resnet50", 2, lr=0.5, batch=8, seq=0)
    ckpt.save(s, str(tmp_path))
    cfg = get_config("resnet50").reduced()
    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    sched = make_schedule(ScheduleConfig(base_lr=0.5, warmup_steps=1,
                                         total_steps=4))
    step = make_train_step(model, lars.OptConfig(kind="lars"), sched,
                           mesh=mesh,
                           comm=CommConfig(strategy="ring", bucket_mb=0.25,
                                           shard_update=True))
    template = st.init_state(model, 0, sharded_plan=step.bucket_plan,
                             n_shards=step.n_shards)
    with pytest.raises(ckpt.CheckpointMismatchError):
        ckpt.load(template, str(tmp_path))

    cc = CommConfig(strategy="ring", bucket_mb=0.25, shard_update=True)
    _, _, sh_state, _ = _train_sharded(cc, steps=1)
    ckpt.save(sh_state, str(tmp_path), tag="sharded")
    plain = st.init_state(model, 0)
    with pytest.raises(ckpt.CheckpointMismatchError):
        ckpt.load(plain, str(tmp_path), tag="sharded")


def test_loop_eval_reads_master_shards():
    """loop.authoritative_params must hand evals the masters rebuilt from
    the persistent shards, not the gather-ahead forward copy (which lags
    them by one update)."""
    from repro.train import loop
    cc = CommConfig(strategy="ring", bucket_mb=0.25, shard_update=True)
    step, _, s, _ = _train_sharded(cc, steps=1)
    ap = loop.authoritative_params(s, step)
    full = st.full_params_from_shards(s.shards, step.bucket_plan,
                                      step.n_shards)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), ap, full)
    # ...and it differs from the stale forward copy after one update
    diffs = jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()), ap, s.params))
    assert max(diffs) > 0.0
