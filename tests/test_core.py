"""Unit tests for the paper's core technique modules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.tier1  # fast, in-process

from repro.core import bucketing, lars, pinit
from repro.core.label_smoothing import IGNORE, smoothed_xent, top1_accuracy
from repro.core.precision import cast_to_compute
from repro.core.schedule import ScheduleConfig, linear_scaled_lr, \
    make_schedule
from repro.models.common import PD


# ---------------------------------------------------------------- schedule

def test_warmup_is_gradual_and_reaches_base():
    sc = ScheduleConfig(base_lr=1.0, warmup_steps=10, total_steps=100,
                        decay="const")
    lr = make_schedule(sc)
    vals = [float(lr(s)) for s in range(12)]
    assert vals[0] == pytest.approx(0.1)
    assert all(b > a for a, b in zip(vals[:10], vals[1:10]))
    assert vals[10] == pytest.approx(1.0)


@pytest.mark.parametrize("decay", ["const", "linear", "poly2", "cosine",
                                   "step"])
def test_decay_families(decay):
    sc = ScheduleConfig(base_lr=1.0, warmup_steps=5, total_steps=100,
                        decay=decay, end_lr=0.001)
    lr = make_schedule(sc)
    v_mid, v_end = float(lr(50)), float(lr(99))
    assert v_end <= v_mid + 1e-6
    assert v_end >= 0.0


def test_linear_scaling_rule():
    assert linear_scaled_lr(0.1, 256) == pytest.approx(0.1)
    # the paper's 81,920 batch
    assert linear_scaled_lr(0.1, 81920) == pytest.approx(32.0)


# ------------------------------------------------------------- smoothing

def test_smoothed_xent_matches_manual():
    logits = jnp.asarray([[2.0, 0.0, -1.0]])
    labels = jnp.asarray([0])
    loss, n = smoothed_xent(logits, labels, smoothing=0.0)
    want = -jax.nn.log_softmax(logits)[0, 0]
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    assert int(n) == 1


def test_smoothed_xent_ignore_mask():
    logits = jnp.zeros((4, 8))
    labels = jnp.asarray([1, IGNORE, 2, IGNORE])
    loss, n = smoothed_xent(logits, labels, smoothing=0.1)
    assert int(n) == 2
    assert float(loss) == pytest.approx(np.log(8.0), rel=1e-5)


def test_smoothing_penalizes_confidence():
    """With smoothing, an over-confident correct logit costs more than a
    calibrated one — the regularization the paper relies on at 81,920."""
    labels = jnp.asarray([0])
    confident = jnp.asarray([[30.0, 0.0, 0.0]])
    calibrated = jnp.asarray([[3.0, 0.0, 0.0]])
    lc, _ = smoothed_xent(confident, labels, smoothing=0.1)
    lk, _ = smoothed_xent(calibrated, labels, smoothing=0.1)
    assert float(lc) > float(lk)


def test_top1_accuracy():
    logits = jnp.asarray([[1.0, 2.0], [5.0, 0.0], [0.0, 1.0]])
    labels = jnp.asarray([1, 0, IGNORE])
    assert float(top1_accuracy(logits, labels)) == pytest.approx(1.0)


# ------------------------------------------------------------- bucketing

def _demo_tree():
    k = jax.random.PRNGKey(0)
    return {
        "layer0": {"w": jax.random.normal(k, (256, 256)),
                   "b": jnp.ones((256,))},
        "layer1": {"w": jax.random.normal(jax.random.fold_in(k, 1),
                                          (512, 128)),
                   "b": jnp.zeros((128,))},
        "head": jax.random.normal(jax.random.fold_in(k, 2), (128, 1000)),
    }


def test_pack_unpack_roundtrip():
    tree = _demo_tree()
    plan = bucketing.make_plan(tree, bucket_mb=0.25)
    bufs = bucketing.pack(tree, plan, dtype=jnp.float32)
    back = bucketing.unpack(bufs, plan, dtype=jnp.float32)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b), tree, back)


def test_bucket_sizes_respect_target():
    tree = _demo_tree()
    plan = bucketing.make_plan(tree, bucket_mb=0.25, dtype_bytes=4)
    target = 0.25 * 2**20 / 4
    for i, size in enumerate(plan.bucket_sizes):
        # a bucket may exceed the target only via a single huge tensor
        n_slots = sum(1 for s in plan.slots if s.bucket == i)
        assert size <= target or n_slots == 1


def test_packing_is_reverse_order():
    """Backward-completion order: the LAST tensor of the tree must be in
    bucket 0 (paper §III-C.2 static groups fire as backward finishes)."""
    tree = _demo_tree()
    plan = bucketing.make_plan(tree, bucket_mb=0.25)
    assert plan.slots[0].bucket == 0
    # the LAST leaf in flatten order (jax sorts dict keys) is packed first
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    last = "/".join(str(getattr(k, "key", k)) for k in leaves[-1][0])
    assert plan.slots[0].path == last


def test_segment_ids_cover_all_chunks():
    tree = _demo_tree()
    plan = bucketing.make_plan(tree)
    seg = bucketing.segment_ids(plan)
    assert seg.shape[0] == sum(s.padded for s in plan.slots) // bucketing.CHUNK
    assert seg.max() == plan.n_tensors - 1


# ------------------------------------------------------------------ LARS

def test_lars_trust_ratio_behaviour():
    """Small-gradient tensors get a LARGER effective lr than the raw ratio
    would suggest; 1-D tensors are excluded (trust == 1)."""
    params = {"w": jnp.full((4, 4), 1.0), "b": jnp.ones((4,))}
    grads = {"w": jnp.full((4, 4), 1e-4), "b": jnp.full((4,), 1e-4)}
    mom = jax.tree.map(jnp.zeros_like, params)
    cfg = lars.OptConfig(kind="lars", momentum=0.0, weight_decay=0.0)
    p2, _ = lars.update(params, grads, mom, 1.0, cfg)
    dw = float(jnp.abs(params["w"] - p2["w"]).max())
    db = float(jnp.abs(params["b"] - p2["b"]).max())
    # w step = lr * eta * |w|/|g| * g = 1 * 0.001 * (1/1e-4) * 1e-4 = 1e-3
    assert dw == pytest.approx(1e-3, rel=1e-3)
    # b step = plain lr * g = 1e-4 (no trust scaling for 1-D)
    assert db == pytest.approx(1e-4, rel=1e-3)


def test_sgdm_matches_manual():
    params = {"w": jnp.ones((2, 2))}
    grads = {"w": jnp.full((2, 2), 0.5)}
    mom = {"w": jnp.full((2, 2), 0.1)}
    cfg = lars.OptConfig(kind="sgdm", momentum=0.9, weight_decay=0.0)
    p2, m2 = lars.update(params, grads, mom, 0.1, cfg)
    want_m = 0.9 * 0.1 + 0.1 * 0.5
    np.testing.assert_allclose(m2["w"], want_m, rtol=1e-6)
    np.testing.assert_allclose(p2["w"], 1.0 - want_m, rtol=1e-6)


# --------------------------------------------------- parallel init / misc

def test_pinit_deterministic_and_path_dependent():
    tree = {"a": PD((32, 32)), "b": {"c": PD((32, 32))}}
    p1 = pinit.materialize(tree, seed=0)
    p2 = pinit.materialize(tree, seed=0)
    np.testing.assert_allclose(p1["a"], p2["a"])      # same seed -> same
    assert not np.allclose(p1["a"], p1["b"]["c"])     # different paths
    p3 = pinit.materialize(tree, seed=1)
    assert not np.allclose(p1["a"], p3["a"])          # different seeds


def test_pinit_compiles_once_for_every_seed():
    """The seed is an argument of the compiled initializer: new seeds and
    repeated calls reuse one program, which gives what the seed gives as
    a constant."""
    tree = {"a": PD((48, 24)), "b": {"c": PD((24,), init="zeros")}}
    compiled = []

    def on_event(event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        out = [pinit.materialize(tree, seed=s) for s in (0, 3, 3, 100000)]
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert compiled.count("jit(build)") == 1, compiled
    np.testing.assert_array_equal(out[1]["a"], out[2]["a"])
    want = pinit._init_leaf(tree["a"], pinit._leaf_key(
        100000, (jax.tree_util.DictKey("a"),)))
    np.testing.assert_array_equal(out[3]["a"], want)


def test_cast_to_compute_leaves_ints_alone():
    tree = {"w": jnp.ones((2,), jnp.float32), "i": jnp.ones((2,), jnp.int32)}
    out = cast_to_compute(tree)
    assert out["w"].dtype == jnp.bfloat16
    assert out["i"].dtype == jnp.int32
