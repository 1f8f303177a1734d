"""Elastic/fault-tolerance layer (docs/elastic.md): serializable CommPlans,
atomic checksum-manifested checkpoints with retention, n→m resharded
resume, the step watchdog, SIGTERM preemption drain, and the
fault-injection harness — plus subprocess kill/resume runs proving a
SIGKILLed training process resumes from its last committed checkpoint,
including onto a smaller mesh."""
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import plan as comm_plan_mod
from repro.configs import get_config
from repro.configs.base import CommConfig
from repro.configs.shapes import InputShape
from repro.core import bucketing, lars
from repro.core.schedule import ScheduleConfig, make_schedule
from repro.data.synthetic import make_batch_fn
from repro.models.registry import build_model
from repro.train import checkpoint as ckpt
from repro.train import elastic, faults, loop
from repro.train import state as st
from repro.train.state import TrainState
from repro.train.step import make_train_step
from repro.launch.mesh import make_mesh

pytestmark = pytest.mark.tier1


# --------------------------------------------------------------- helpers


def _mk_sharded_step(bucket_mb=0.25, wire="bf16", sharding=None):
    cfg = get_config("resnet50").reduced()
    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    sched = make_schedule(ScheduleConfig(base_lr=0.5, warmup_steps=1,
                                         total_steps=10))
    if sharding is None:
        # deliberately the deprecated boolean spelling: these tests keep
        # the shim path exercised under real use (maps to sharding='zero1')
        cc = CommConfig(strategy="ring", bucket_mb=bucket_mb,
                        wire_dtype=wire, shard_update=True)
    else:
        cc = CommConfig(strategy="ring", bucket_mb=bucket_mb,
                        wire_dtype=wire, sharding=sharding)
    step = make_train_step(model, lars.OptConfig(kind="lars"), sched,
                           mesh=mesh, comm=cc)
    return cfg, model, mesh, step


def _fake_state():
    return TrainState(jnp.int32(0), {"w": jnp.zeros((4,))},
                      {"w": jnp.zeros((4,))}, None, None)


def _fake_step(state, batch):
    p = {k: v + 1.0 for k, v in state.params.items()}
    return TrainState(state.step + 1, p, state.mom, None, None), \
        {"loss": jnp.float32(1.0) / (state.step + 1), "lr": jnp.float32(0.1)}


def _fake_batch(step):
    return {"x": jnp.zeros((2,))}


# ------------------------------------------------ CommPlan serialization


def test_commplan_json_roundtrip_and_rebuild():
    """loads(dumps(plan)) == plan by dataclass equality; the plan rebuilt
    from JSON reconstructs the exact BucketPlan from a template tree; a
    template of the wrong model fails loudly."""
    _, model, _, step = _mk_sharded_step()
    plan = step.comm_plan
    assert plan is not None and plan.shard_update
    again = comm_plan_mod.loads(comm_plan_mod.dumps(plan))
    assert again == plan

    params = st.init_state(model, 0).params
    rebuilt = again.bucket_plan(params)
    assert tuple(rebuilt.bucket_sizes) == tuple(step.bucket_plan.bucket_sizes)
    assert [s.path for s in rebuilt.slots] == \
        [s.path for s in step.bucket_plan.slots]

    wrong = build_model(get_config("qwen1.5-0.5b").reduced())
    with pytest.raises(comm_plan_mod.CommPlanError):
        again.bucket_plan(st.init_state(wrong, 0).params)


def test_commplan_version_and_schema_rejection():
    _, _, _, step = _mk_sharded_step()
    d = comm_plan_mod.to_dict(step.comm_plan)
    d["version"] = 99
    with pytest.raises(comm_plan_mod.CommPlanError):
        comm_plan_mod.from_dict(d)
    with pytest.raises(comm_plan_mod.CommPlanError):
        comm_plan_mod.loads("not json {")
    with pytest.raises(comm_plan_mod.CommPlanError):
        comm_plan_mod.from_dict({"version": comm_plan_mod.PLAN_VERSION})


def test_commplan_comm_config_requested_vs_resolved():
    """reautotune=True hands back the REQUESTED bucket size (so 'auto'
    re-autotunes on the new mesh); reautotune=False pins the resolved."""
    _, _, _, step = _mk_sharded_step()
    plan = step.comm_plan
    assert plan.requested_bucket_mb == 0.25
    assert plan.comm_config(reautotune=True).bucket_mb == 0.25
    assert plan.comm_config(reautotune=False).bucket_mb == plan.bucket_mb
    cc = plan.comm_config()
    assert cc.strategy == "ring" and cc.shard_update


def test_commplan_retarget_new_mesh():
    _, model, _, step = _mk_sharded_step()
    params = st.init_state(model, 0).params
    re = step.comm_plan.retarget(("data", "model"), (4, 1), params)
    assert re.n_shards == 4
    assert re.mesh_sizes == (4, 1)
    assert re.shard_axis == "data"
    # fixed bucket size: boundaries identical to the original plan
    assert re.bucket_sizes == step.comm_plan.bucket_sizes
    # retargeted plans serialize like any other
    assert comm_plan_mod.loads(comm_plan_mod.dumps(re)) == re


def test_commplan_v1_v2_payloads_upgrade_to_v3():
    """PLAN_VERSION 3 (split-leaf slots): a v1 payload — booleans only,
    no enum fields — and a v2 payload — enum pair, 6-element slot rows
    without the elem_offset column — both load compatibly and upgrade in
    place so a re-save writes native v3."""
    # bucket_mb=1.0 so no leaf splits: a legacy payload's 6-element slot
    # rows can only describe an unsplit layout, so the fixture must be
    # one (the split legacy case lives in
    # test_commplan_v2_oversized_leaf_layout_loads_and_reshards)
    _, _, _, step = _mk_sharded_step(bucket_mb=1.0)  # zero1, boolean shim
    assert all(s.elem_offset == 0 for s in step.comm_plan.slots)
    d = comm_plan_mod.to_dict(step.comm_plan)
    assert d["version"] == comm_plan_mod.PLAN_VERSION == 3
    v1 = dict(d)
    v1["version"] = 1
    del v1["sharding"], v1["gather"]          # v1 never had the enum pair
    v1["slots"] = [list(row)[:6] for row in v1["slots"]]  # nor elem_offset
    up = comm_plan_mod.from_dict(v1)
    assert up.version == comm_plan_mod.PLAN_VERSION
    assert (up.sharding, up.gather) == ("zero1", "ahead")
    assert up == step.comm_plan               # bit-identical upgrade
    # the other boolean spelling: gather_ahead=False -> 'at_end'
    v1["gather_ahead"] = False
    up2 = comm_plan_mod.from_dict(v1)
    assert (up2.sharding, up2.gather) == ("zero1", "at_end")
    # v2: enum pair present, slot rows still missing the elem_offset
    # column (every v2 slot is a whole tensor)
    v2 = dict(d)
    v2["version"] = 2
    v2["slots"] = [list(row)[:6] for row in v2["slots"]]
    up3 = comm_plan_mod.from_dict(v2)
    assert up3 == step.comm_plan
    assert all(s.elem_offset == 0 for s in up3.slots)
    # a round trip of the upgraded plan stays native v3
    again = comm_plan_mod.loads(comm_plan_mod.dumps(up))
    assert again.version == comm_plan_mod.PLAN_VERSION and again == up


def test_zero3_elastic_roundtrip_params_none(tmp_path):
    """A ZeRO-3 run (``state.params is None`` throughout) checkpoints
    through the same committed CommPlan and elastically resumes into a
    ZeRO-3 template across a bucket-boundary change — masters and
    momentum bit-exact — without ever materializing a full replica."""
    d = str(tmp_path)
    cfg, model, mesh, step_a = _mk_sharded_step(bucket_mb=0.25,
                                                sharding="zero3")
    assert step_a.sharding == "zero3"
    assert step_a.comm_plan.sharding == "zero3"
    assert step_a.comm_plan.gather == "per_group"
    bf = make_batch_fn(cfg, InputShape("t", "train", 0, 8), mesh=mesh)
    s = st.init_state(model, 0, mesh, sharded_plan=step_a.bucket_plan,
                      n_shards=step_a.n_shards, materialize_params=False)
    assert s.params is None
    f_a = jax.jit(step_a)
    for _ in range(2):
        s, _ = f_a(s, bf(s.step))
    assert s.params is None
    ckpt.save(s, d, tag=ckpt.step_tag(2), comm_plan=step_a.comm_plan)

    _, _, _, step_b = _mk_sharded_step(bucket_mb=0.5, sharding="zero3")
    assert tuple(step_b.bucket_plan.bucket_sizes) != \
        tuple(step_a.bucket_plan.bucket_sizes)
    tmpl = elastic.make_template(model, step_b.bucket_plan,
                                 step_b.n_shards, seed=9, mesh=mesh,
                                 materialize_params=False)
    assert tmpl.params is None
    r = elastic.load_resharded(d, tmpl, step_b.bucket_plan,
                               step_b.n_shards)
    assert r.params is None and int(r.step) == 2
    p_old = st.full_params_from_shards(s.shards, step_a.bucket_plan,
                                       step_a.n_shards)
    p_new = st.full_params_from_shards(r.shards, step_b.bucket_plan,
                                       step_b.n_shards)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), p_old, p_new)
    m_old = st.full_params_from_shards(s.mom, step_a.bucket_plan,
                                       step_a.n_shards)
    m_new = st.full_params_from_shards(r.mom, step_b.bucket_plan,
                                       step_b.n_shards)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), m_old, m_new)

    # the resumed run takes a live step under plan B, still replica-free
    s3, m3 = jax.jit(step_b)(r, bf(r.step))
    assert np.isfinite(float(m3["loss"]))
    assert int(s3.step) == 3 and s3.params is None


# --------------------------------------------------- n→m reshard (exact)


def _tree():
    k = jax.random.PRNGKey(0)
    mk = lambda key, shape: jax.random.normal(key, shape, jnp.float32)  # noqa: E731
    ks = jax.random.split(k, 4)
    return {"a": mk(ks[0], (97,)), "b": mk(ks[1], (33, 5)),
            "c": mk(ks[2], (4, 4, 3)), "d": mk(ks[3], (1,))}


@pytest.mark.parametrize("old_n,new_n", [(8, 4), (4, 8), (8, 2), (3, 5)])
def test_reshard_buffers_exact(old_n, new_n):
    """The n→m round trip is a pure fp32 relayout: resharded buffers are
    bit-identical to packing the original tree at the new count, even when
    the bucket boundaries change between plans."""
    tree = _tree()
    plan_a = bucketing.make_plan(tree, bucket_mb=0.0005)
    plan_b = bucketing.make_plan(tree, bucket_mb=0.002)
    old = st.init_packed_shards(tree, plan_a, old_n)
    new = elastic.reshard_buffers(old, plan_a, old_n, plan_b, new_n)
    want = st.init_packed_shards(tree, plan_b, new_n)
    assert len(new) == len(want)
    for got, exp in zip(new, want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    back = st.full_params_from_shards(new, plan_b, new_n)
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), tree, back)


def test_reshard_buffers_validates_layout():
    tree = _tree()
    plan = bucketing.make_plan(tree, bucket_mb=0.0005)
    old = st.init_packed_shards(tree, plan, 4)
    with pytest.raises(elastic.ElasticResumeError):
        elastic.reshard_buffers(old[:-1], plan, 4, plan, 2)
    with pytest.raises(elastic.ElasticResumeError):
        elastic.reshard_buffers(old, plan, 8, plan, 2)   # wrong old_n


def test_reshard_split_leaf_plans_exact():
    """8→4 reshard between two plans that both SPLIT the giant leaf — at
    different span boundaries — stays bit-exact for masters and momentum
    (the n→m relayout goes through unpack-to-tree, so span geometry never
    leaks into the restored values)."""
    chunk = bucketing.CHUNK
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    tree = {"giant": jax.random.normal(ks[0], (10 * chunk + 77,),
                                       jnp.float32),
            "w": jax.random.normal(ks[1], (33, 5), jnp.float32)}
    plan_a = bucketing.make_plan(tree, bucket_mb=3 * chunk * 2 / 2**20)
    plan_b = bucketing.make_plan(tree, bucket_mb=4 * chunk * 2 / 2**20)
    assert any(s.elem_offset for s in plan_a.slots)
    assert any(s.elem_offset for s in plan_b.slots)
    assert plan_a.bucket_sizes != plan_b.bucket_sizes
    for bufs in (st.init_packed_shards(tree, plan_a, 8),      # masters
                 st.init_packed_momentum(plan_a, 8)):         # momentum
        new = elastic.reshard_buffers(bufs, plan_a, 8, plan_b, 4)
        back = st.full_params_from_shards(new, plan_b, 4)
        want = st.full_params_from_shards(bufs, plan_a, 8)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), want, back)


def test_commplan_v2_oversized_leaf_layout_loads_and_reshards():
    """Acceptance: a v2 CommPlan saved BEFORE leaf splitting can carry an
    oversized own-bucket leaf. ``bucket_plan()`` must reconstruct that
    exact legacy layout (not re-pack it under the new packer, not trip
    the new budget guard), and its buffers must reshard onto a fresh
    split-leaf plan bit-exact."""
    chunk = bucketing.CHUNK
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    tree = {"giant": jax.random.normal(ks[0], (7 * chunk + 19,),
                                       jnp.float32),
            "w": jax.random.normal(ks[1], (40, 11), jnp.float32)}
    mb = 2 * chunk * 2 / 2**20
    legacy = bucketing.make_plan(tree, bucket_mb=mb, split_leaves=False)
    assert max(legacy.bucket_sizes) > 2 * chunk   # the oversized bucket
    cc = CommConfig(strategy="ring", bucket_mb=mb, sharding="zero1")
    cp = comm_plan_mod.make(cc, legacy, resolved_bucket_mb=mb,
                            mesh_axes=("data",), mesh_sizes=(8,),
                            shard_axis="data", n_shards=8)
    d = comm_plan_mod.to_dict(cp)
    d["version"] = 2
    d["slots"] = [list(row)[:6] for row in d["slots"]]
    loaded = comm_plan_mod.from_dict(d)
    lp = loaded.bucket_plan(tree)
    assert lp.bucket_sizes == legacy.bucket_sizes
    assert all(s.elem_offset == 0 for s in lp.slots)
    old = st.init_packed_shards(tree, lp, 8)
    new_plan = bucketing.make_plan(tree, bucket_mb=mb)    # splits today
    assert any(s.elem_offset for s in new_plan.slots)
    new = elastic.reshard_buffers(old, lp, 8, new_plan, 4)
    want = st.init_packed_shards(tree, new_plan, 4)
    for got, exp in zip(new, want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    back = st.full_params_from_shards(new, new_plan, 4)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), tree, back)


# ------------------------------------- atomic checkpoints + manifest


def test_checkpoint_manifest_checksum_and_fallback(tmp_path):
    """Corrupting the newest payload is caught by the sha256 manifest and
    tag=None falls back to the previous committed checkpoint — emitting a
    ``checkpoint_fallback`` metrics event that names the rejected tag
    (the skip must be observable, not a silent print)."""
    from repro.obs import metrics as obs_metrics
    d = str(tmp_path)
    s = _fake_state()
    s1 = TrainState(jnp.int32(1), {"w": jnp.ones((4,))}, s.mom, None, None)
    s2 = TrainState(jnp.int32(2), {"w": jnp.full((4,), 2.0)}, s.mom, None,
                    None)
    ckpt.save(s1, d, tag=ckpt.step_tag(1))
    ckpt.save(s2, d, tag=ckpt.step_tag(2))
    assert ckpt.available_tags(d) == ["step00000001", "step00000002"]
    assert ckpt.latest_tag(d) == "step00000002"

    faults.corrupt_file(os.path.join(d, "ckpt_step00000002.npz"))
    with pytest.raises(ckpt.CheckpointCorruptError, match="checksum"):
        ckpt.verify(d, "step00000002")
    with obs_metrics.default_registry().use_sink(
            obs_metrics.MemorySink()) as mem:
        restored = ckpt.load(_fake_state(), d, tag=None)
    assert int(restored.step) == 1
    np.testing.assert_array_equal(np.asarray(restored.params["w"]), 1.0)
    fb = mem.find("checkpoint_fallback")
    assert len(fb) == 1, [e.name for e in mem.events]
    assert fb[0].value["rejected_tag"] == "step00000002"
    assert "checksum" in fb[0].value["error"]

    # every entry corrupt -> CheckpointCorruptError, not a silent load
    faults.corrupt_file(os.path.join(d, "ckpt_step00000001.npz"))
    with pytest.raises(ckpt.CheckpointCorruptError):
        ckpt.load(_fake_state(), d, tag=None)


def test_checkpoint_retention_spares_hand_named_tags(tmp_path):
    d = str(tmp_path)
    for i in range(1, 5):
        s = TrainState(jnp.int32(i), {"w": jnp.full((4,), float(i))},
                       {"w": jnp.zeros((4,))}, None, None)
        ckpt.save(s, d, tag=ckpt.step_tag(i), keep_last_k=2)
    ckpt.save(_fake_state(), d, tag="best")
    tags = ckpt.available_tags(d)
    assert tags == ["step00000003", "step00000004", "best"]
    ckpt.prune(d, keep_last_k=1)
    assert ckpt.available_tags(d) == ["step00000004", "best"]
    # pruned files are gone from disk too
    assert not os.path.exists(os.path.join(d, "ckpt_step00000003.npz"))
    ckpt.load(_fake_state(), d, tag="step00000004")


def test_checkpoint_mismatch_messages_are_actionable(tmp_path):
    """Validation failures raise CheckpointMismatchError (never assert)
    and the shape-mismatch message points at the elastic-resume path."""
    d = str(tmp_path)
    ckpt.save(_fake_state(), d)
    bigger = TrainState(jnp.int32(0), {"w": jnp.zeros((9,))},
                        {"w": jnp.zeros((9,))}, None, None)
    with pytest.raises(ckpt.CheckpointMismatchError,
                       match="resume-elastic"):
        ckpt.load(bigger, d)
    other = TrainState(jnp.int32(0), {"v": jnp.zeros((4,))},
                       {"v": jnp.zeros((4,))}, None, None)
    with pytest.raises(ckpt.CheckpointMismatchError, match="lacks"):
        ckpt.load(other, d)


# ----------------------------------------------------- fault-spec parser


def test_parse_faults():
    fs = faults.parse_faults("stall@3:2.5, kill@7")
    assert fs == (faults.Fault("stall", 3, 2.5), faults.Fault("kill", 7))
    assert faults.parse_faults(None) == ()
    assert faults.parse_faults("") == ()
    for bad in ("explode@3", "stall@3", "kill@x", "stall@1:0"):
        with pytest.raises(faults.FaultSpecError):
            faults.parse_faults(bad)


# ------------------------------------------------- loop: ckpt discipline


def test_loop_final_save_step_tags_and_retention(tmp_path):
    """Periodic saves are step-tagged and pruned to keep_last_k; a steps
    count that is not a multiple of ckpt_every still commits the tail at
    run_stop; the resumable load lands on the final step."""
    d = str(tmp_path)
    s, _ = loop.train(_fake_state(), _fake_step, _fake_batch, steps=5,
                      ckpt_dir=d, ckpt_every=2, keep_last_k=2, log_every=0)
    assert int(s.step) == 5
    assert ckpt.available_tags(d) == ["step00000004", "step00000005"]
    r = ckpt.load(_fake_state(), d)
    assert int(r.step) == 5
    np.testing.assert_array_equal(np.asarray(r.params["w"]), 5.0)


def test_loop_resumes_from_restored_step(tmp_path):
    d = str(tmp_path)
    loop.train(_fake_state(), _fake_step, _fake_batch, steps=3,
               ckpt_dir=d, log_every=0)
    r = ckpt.load(_fake_state(), d)
    s, _ = loop.train(r, _fake_step, _fake_batch, steps=6, ckpt_dir=d,
                      log_every=0)
    assert int(s.step) == 6
    np.testing.assert_array_equal(np.asarray(s.params["w"]), 6.0)


def test_loop_corrupt_fault_rejected_at_load(tmp_path):
    """The corrupt-checkpoint fault (bit-rot after commit) must be caught
    by the checksum at load time, falling back to the previous save."""
    d = str(tmp_path)
    loop.train(_fake_state(), _fake_step, _fake_batch, steps=2, ckpt_dir=d,
               ckpt_every=1, log_every=0, faults="corrupt@2")
    with pytest.raises(ckpt.CheckpointCorruptError):
        ckpt.verify(d, "step00000002")
    r = ckpt.load(_fake_state(), d, tag=None)
    assert int(r.step) == 1


# ------------------------------------------- loop: watchdog + preemption


def test_loop_watchdog_restores_and_retries(tmp_path):
    """An injected stall trips the step watchdog; the loop restores the
    last good checkpoint, retries, and the run completes correctly."""
    d = str(tmp_path)
    s, h = loop.train(_fake_state(), _fake_step, _fake_batch, steps=4,
                      ckpt_dir=d, ckpt_every=1, step_timeout_s=0.5,
                      log_every=0, faults="stall@2:1.5")
    assert int(s.step) == 4
    np.testing.assert_array_equal(np.asarray(s.params["w"]), 4.0)
    assert any("watchdog_timeout" in e for e in h)
    assert any("watchdog_restore" in e for e in h)


def test_loop_watchdog_bounded_retries():
    """A step that hangs EVERY attempt exhausts max_step_retries and
    surfaces as a RuntimeError instead of retrying forever."""
    from jax.experimental import io_callback

    def _sleep(x):
        time.sleep(0.6)
        return x

    def slow_step(state, batch):
        w = io_callback(_sleep,
                        jax.ShapeDtypeStruct((4,), jnp.float32),
                        state.params["w"])
        return TrainState(state.step + 1, {"w": w + 1.0}, state.mom,
                          None, None), {"loss": jnp.float32(1.0)}

    with pytest.raises(RuntimeError, match="bounded retries"):
        loop.train(_fake_state(), slow_step, _fake_batch, steps=2,
                   step_timeout_s=0.2, max_step_retries=2,
                   retry_backoff_s=0.05, log_every=0)


def test_loop_sigterm_drains_and_saves(tmp_path):
    """The announced preemption: SIGTERM finishes the in-flight step,
    commits a checkpoint, and returns a resumable state early."""
    d = str(tmp_path)
    s, _ = loop.train(_fake_state(), _fake_step, _fake_batch, steps=10,
                      ckpt_dir=d, log_every=0, faults="sigterm@1")
    assert int(s.step) == 2          # step 1 drained, then early exit
    r = ckpt.load(_fake_state(), d)
    assert int(r.step) == 2


def test_loop_preempt_drain_saves_drained_step_once(tmp_path):
    """ISSUE 9 satellite: a drained step that also lands on the ckpt_every
    cadence must commit ONE checkpoint, not two — the drain save is guarded
    by last_saved_step (the old code re-saved the same step, doubling the
    commit fsync cost and churning retention)."""
    from repro.obs import metrics as obs_metrics
    d = str(tmp_path)
    mem = obs_metrics.MemorySink()
    with obs_metrics.default_registry().use_sink(mem):
        s, _ = loop.train(_fake_state(), _fake_step, _fake_batch, steps=10,
                          ckpt_dir=d, ckpt_every=1, log_every=0,
                          faults="sigterm@1")
    assert int(s.step) == 2
    saves = [e.value for e in mem.find("checkpoint_saved")]
    assert [v["step"] for v in saves].count(2) == 1, saves
    assert ckpt.available_tags(d) == ["step00000001", "step00000002"]


def test_corrupt_fault_manifest_target(tmp_path):
    """corrupt@s:manifest flips bytes in MANIFEST.json itself: every load
    through the manifest must refuse with CheckpointCorruptError (manual
    repair), never silently parse garbage."""
    d = str(tmp_path)
    loop.train(_fake_state(), _fake_step, _fake_batch, steps=2, ckpt_dir=d,
               ckpt_every=1, log_every=0, faults="corrupt@2:manifest")
    with pytest.raises(ckpt.CheckpointCorruptError, match="manifest"):
        ckpt.load(_fake_state(), d, tag=None)
    with pytest.raises(ckpt.CheckpointCorruptError, match="manifest"):
        ckpt.load(_fake_state(), d, tag="step00000002")


def test_corrupt_fault_plan_target(tmp_path):
    """corrupt@s:plan hits the commplan_<tag>.json committed with the
    checkpoint: the load must reject it as a corrupt checkpoint (the plan
    is outside the payload checksum), and arming the fault against a save
    with no CommPlan is a loud spec error, not a silent no-op."""
    d = str(tmp_path)
    _, model, _, step = _mk_sharded_step()
    s = st.init_state(model, 0, sharded_plan=step.bucket_plan,
                      n_shards=step.n_shards)
    inj = faults.FaultInjector(faults.parse_faults("corrupt@0:plan"))
    path = ckpt.save(s, d, tag=ckpt.step_tag(0), comm_plan=step.comm_plan)
    inj.on_saved(path, 0)
    with pytest.raises(ckpt.CheckpointCorruptError, match="CommPlan"):
        ckpt.load_arrays(d, tag="step00000000")
    with pytest.raises(comm_plan_mod.CommPlanError):
        ckpt.load_comm_plan(d, tag="step00000000")

    d2 = str(tmp_path / "noplan")
    p2 = ckpt.save(_fake_state(), d2, tag=ckpt.step_tag(0))
    inj2 = faults.FaultInjector(faults.parse_faults("corrupt@0:plan"))
    with pytest.raises(faults.FaultSpecError, match="CommPlan"):
        inj2.on_saved(p2, 0)


# ------------------------------------------------- elastic resume (1 dev)


def test_elastic_resume_across_bucket_plans(tmp_path):
    """Resume a sharded run under a DIFFERENT bucket plan: the fp32
    masters and momentum relayout bit-exact through the old plan's
    CommPlan into the new plan's buffers, and training continues."""
    d = str(tmp_path)
    cfg, model, mesh, step_a = _mk_sharded_step(bucket_mb=0.25)
    f_a = jax.jit(step_a)
    bf = make_batch_fn(cfg, InputShape("t", "train", 0, 8), mesh=mesh)
    s = st.init_state(model, 0, mesh, sharded_plan=step_a.bucket_plan,
                      n_shards=step_a.n_shards)
    for _ in range(2):
        s, _ = f_a(s, bf(s.step))
    ckpt.save(s, d, tag=ckpt.step_tag(2), comm_plan=step_a.comm_plan)

    _, _, _, step_b = _mk_sharded_step(bucket_mb=0.5)
    assert tuple(step_b.bucket_plan.bucket_sizes) != \
        tuple(step_a.bucket_plan.bucket_sizes)
    tmpl = st.init_state(model, 9, sharded_plan=step_b.bucket_plan,
                         n_shards=step_b.n_shards)
    r = elastic.load_resharded(d, tmpl, step_b.bucket_plan,
                               step_b.n_shards)
    assert int(r.step) == 2
    p_old = st.full_params_from_shards(s.shards, step_a.bucket_plan,
                                       step_a.n_shards)
    p_new = st.full_params_from_shards(r.shards, step_b.bucket_plan,
                                       step_b.n_shards)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), p_old, p_new)
    m_old = st.full_params_from_shards(s.mom, step_a.bucket_plan,
                                       step_a.n_shards)
    m_new = st.full_params_from_shards(r.mom, step_b.bucket_plan,
                                       step_b.n_shards)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), m_old, m_new)

    # the resumed run takes a live step under plan B
    s3, m3 = jax.jit(step_b)(r, bf(r.step))
    assert np.isfinite(float(m3["loss"]))
    assert int(s3.step) == 3


def test_elastic_resume_error_paths(tmp_path):
    d = str(tmp_path)
    cfg, model, mesh, step = _mk_sharded_step()
    plain = st.init_state(model, 0)
    sharded = st.init_state(model, 0, sharded_plan=step.bucket_plan,
                            n_shards=step.n_shards)

    # non-sharded checkpoint + sharded template
    ckpt.save(plain, d, tag="plain")
    with pytest.raises(elastic.ElasticResumeError):
        elastic.load_resharded(d, sharded, step.bucket_plan, step.n_shards,
                               tag="plain")
    # sharded checkpoint + plain template
    ckpt.save(sharded, d, tag="sharded", comm_plan=step.comm_plan)
    with pytest.raises(elastic.ElasticResumeError):
        elastic.load_resharded(d, plain, step.bucket_plan, step.n_shards,
                               tag="sharded")
    # sharded checkpoint saved WITHOUT a CommPlan: layout unknowable
    ckpt.save(sharded, d, tag="noplan")
    with pytest.raises(elastic.ElasticResumeError, match="CommPlan"):
        elastic.load_resharded(d, sharded, step.bucket_plan, step.n_shards,
                               tag="noplan")
    # non-sharded checkpoint + non-sharded template degrades to plain load
    r = elastic.load_resharded(d, st.init_state(model, 1), None, 1,
                               tag="plain")
    assert int(r.step) == 0


# ------------------------------------- subprocess: SIGKILL + CLI resume


def _run(argv, timeout=600):
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.train"] + argv,
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "PYTHONPATH": "src"})


def test_kill_resume_cli_smoke(tmp_path):
    """End-to-end through the launcher: a sharded run SIGKILLed mid-step
    leaves a committed checkpoint + CommPlan; --resume-elastic picks them
    up and finishes the run."""
    d = str(tmp_path / "ckpt")
    base = ["--arch", "resnet50", "--reduced", "--batch", "8", "--seq", "0",
            "--steps", "4", "--warmup", "1", "--comm", "ring",
            "--bucket-mb", "0.25", "--shard-update",
            "--ckpt-dir", d, "--ckpt-every", "1"]
    r1 = _run(base + ["--inject-fault", "kill@2"])
    assert r1.returncode == -9, (r1.returncode, r1.stderr[-2000:])
    assert "step00000002" in ckpt.available_tags(d)

    hist = str(tmp_path / "hist.json")
    r2 = _run(base + ["--resume-elastic", "--keep-last-k", "2",
                      "--history-out", hist])
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resuming elastically" in r2.stdout
    assert "elastic resume: restored step 2" in r2.stdout
    final = ckpt.load_arrays(d)[0]
    assert final["step"] == 4
    assert len(ckpt.available_tags(d)) <= 2    # retention applied


ELASTIC_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={ndev}"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import get_config
from repro.configs.base import CommConfig
from repro.configs.shapes import InputShape
from repro.core import lars
from repro.core.schedule import ScheduleConfig, make_schedule
from repro.data.synthetic import make_batch_fn
from repro.models.registry import build_model
from repro.train import checkpoint as ckpt
from repro.train import elastic, loop
from repro.train import state as st
from repro.train.step import make_train_step

ROLE, DIR, K = {role!r}, {d!r}, 2
NDEV = {ndev}
mesh = make_mesh((NDEV, 1), ("data", "model"))
# the LM family: LayerNorm is per-example, so the math is device-count
# invariant (ResNet's per-device BN batch stats are not)
cfg = get_config("qwen1.5-0.5b").reduced()
model = build_model(cfg)
# small lr: the only 8-dev-vs-4-dev residue is gradient-reduction order
# (~1e-6 relative on the grads), and LARS amplifies it in proportion to
# the update magnitude — the 1e-6 acceptance bound is on the params
sched = make_schedule(ScheduleConfig(base_lr=0.02, warmup_steps=1,
                                     total_steps=10))
bf = make_batch_fn(cfg, InputShape("t", "train", 32, 16), mesh=mesh)
opt = lars.OptConfig(kind="lars")

if ROLE == "victim":
    cc = CommConfig(strategy="ring", bucket_mb=0.25, wire_dtype="f32",
                    shard_update=True)
    step = make_train_step(model, opt, sched, mesh=mesh, comm=cc)
    s = st.init_state(model, 0, sharded_plan=step.bucket_plan,
                      n_shards=step.n_shards)
    loop.train(s, step, bf, steps=6, ckpt_dir=DIR, ckpt_every=1,
               log_every=0, comm_plan=step.comm_plan,
               faults="kill@%d" % K)
    raise SystemExit("unreachable: kill fault did not fire")

if ROLE == "oracle":
    cc = CommConfig(strategy="ring", bucket_mb=0.25, wire_dtype="f32",
                    shard_update=True)
    step = make_train_step(model, opt, sched, mesh=mesh, comm=cc)
    f = jax.jit(step)
    s = st.init_state(model, 0, sharded_plan=step.bucket_plan,
                      n_shards=step.n_shards)
    for _ in range(K):
        s, _ = f(s, bf(s.step))
    pk = st.full_params_from_shards(s.shards, step.bucket_plan,
                                    step.n_shards)
    np.savez(os.path.join(DIR, "oracle_k.npz"),
             *[np.asarray(x) for x in jax.tree.leaves(pk)])
    for _ in range(2):
        s, _ = f(s, bf(s.step))
    pk2 = st.full_params_from_shards(s.shards, step.bucket_plan,
                                     step.n_shards)
    np.savez(os.path.join(DIR, "oracle_k2.npz"),
             *[np.asarray(x) for x in jax.tree.leaves(pk2)])
    print("ORACLE-OK")
    raise SystemExit(0)

# ROLE == "resume" on the smaller mesh
saved = ckpt.load_comm_plan(DIR)
assert saved.n_shards == 8, saved.n_shards
step = make_train_step(model, opt, sched, mesh=mesh,
                       comm=saved.comm_config(reautotune=True))
assert step.n_shards == NDEV
tmpl = st.init_state(model, 7, sharded_plan=step.bucket_plan,
                     n_shards=step.n_shards)
s = elastic.load_resharded(DIR, tmpl, step.bucket_plan, step.n_shards,
                           old_comm_plan=saved)
assert int(s.step) == K, int(s.step)
pk = st.full_params_from_shards(s.shards, step.bucket_plan, step.n_shards)
ok = np.load(os.path.join(DIR, "oracle_k.npz"))
for got, want in zip(jax.tree.leaves(pk), ok.values()):
    np.testing.assert_array_equal(np.asarray(got), want)   # bit-exact
f = jax.jit(step)
for _ in range(2):
    s, _ = f(s, bf(s.step))
pk2 = st.full_params_from_shards(s.shards, step.bucket_plan, step.n_shards)
ok2 = np.load(os.path.join(DIR, "oracle_k2.npz"))
worst = 0.0
for got, want in zip(jax.tree.leaves(pk2), ok2.values()):
    worst = max(worst, float(np.abs(np.asarray(got) - want).max()))
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-6)
print("max |8dev - 4dev| after 2 resumed steps:", worst)
print("ELASTIC-OK")
"""


def _run_elastic(role, ndev, d, timeout=600):
    script = ELASTIC_SCRIPT.format(role=role, ndev=ndev, d=d)
    return subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=timeout,
                          env={**os.environ, "PYTHONPATH": "src"})


@pytest.mark.tier2
def test_elastic_8dev_kill_resume_4dev(tmp_path):
    """The acceptance run (ISSUE 6): an 8-device ZeRO-1 run is SIGKILLed
    mid-run; --resume-elastic-style restore onto 4 devices reshards the
    fp32 masters BIT-exactly (pure relayout), and two further LARS steps
    stay within 1e-6 of the uninterrupted 8-device oracle (the residue is
    only the device-count-dependent gradient-reduction order)."""
    d = str(tmp_path)
    victim = _run_elastic("victim", 8, d)
    assert victim.returncode == -9, (victim.returncode,
                                     victim.stderr[-2000:])
    assert "step00000002" in ckpt.available_tags(d)

    oracle = _run_elastic("oracle", 8, d)
    assert "ORACLE-OK" in oracle.stdout, oracle.stderr[-2000:]

    resume = _run_elastic("resume", 4, d)
    assert "ELASTIC-OK" in resume.stdout, \
        (resume.stdout[-2000:], resume.stderr[-3000:])
