"""DeepSeek-V2-Lite's pieces in the program: YaRN's rope, LARS per
published tensor, the cut to a chip's share and the launcher that trains
it. The comparison with the plain float32 reference is in
``perfbench/tests/test_bench_deepseek.py``."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import cut
from repro.core import lars

pytestmark = pytest.mark.tier1


def test_yarn_frequencies_and_softmax_scale():
    """DeepSeek-V2's YaRN (d 64, theta 1e4, factor 40, 4096 positions):
    dimensions up to low = 10 keep theta^(-2i/d), from high = 23 on they
    are divided by 40, the ramp between; the softmax scale is 192^-1/2
    times m(40, 0.707)^2 = 1.5897, and cos and sin keep their scale."""
    from repro.models.attention import mla_softmax_scale
    from repro.models.common import rope, yarn_inv_freq, yarn_mscale
    cfg = get_config("deepseek-v2-lite")
    y = cfg.rope_yarn
    extra = 1e4 ** (-np.arange(0, 64, 2) / 64)
    f = yarn_inv_freq(64, 1e4, y)
    np.testing.assert_allclose(f[:11], extra[:11], rtol=1e-12)
    np.testing.assert_allclose(f[23:], extra[23:] / 40, rtol=1e-12)
    ramp = (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(
        f[11:23], extra[11:23] / 40 * ramp + extra[11:23] * (1 - ramp),
        rtol=1e-12)
    assert yarn_mscale(40, 0.707) ** 2 == pytest.approx(1.5897, abs=1e-4)
    assert mla_softmax_scale(cfg) * math.sqrt(192) == pytest.approx(
        1.5897, abs=1e-4)
    # a rotation: norms are kept at every position
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 50, 2, 64))
    out = rope(x, jnp.arange(50)[None], 1e4, y)
    np.testing.assert_allclose(jnp.linalg.norm(out, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)


def _tree(key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 6)
    return {"layers": {"w": jax.random.normal(ks[0], (3, 8, 5)),
                       "ln": 1 + 0.1 * jax.random.normal(ks[1], (3, 8))},
            "experts": jax.random.normal(ks[2], (6, 4, 5)),
            "head": jax.random.normal(ks[3], (8, 10)),
            "bias": jax.random.normal(ks[4], (10,))}


STACK = {"layers": {"w": 1, "ln": 1}, "experts": 1, "head": 0, "bias": 0}


def _unstack(tree):
    """Each stacked array split into its published tensors."""
    out = {"head": tree["head"], "bias": tree["bias"]}
    for name, arr in (("w", tree["layers"]["w"]), ("ln", tree["layers"]["ln"]),
                      ("e", tree["experts"])):
        for i in range(arr.shape[0]):
            out[f"{name}{i}"] = arr[i]
    return out


@pytest.mark.parametrize("kind", ["lars", "lamb"])
def test_per_published_tensor_is_per_tensor_on_the_unstacked_tree(kind):
    """LARS (and LAMB's ratio) on stacked arrays with their stack axes
    equals the plain per-tensor rule on every layer's and expert's tensor
    as an array of its own: a ratio each, norm scales unscaled."""
    cfg = lars.OptConfig(kind=kind, weight_decay=1e-3)
    params, grads = _tree(0), _tree(1)
    mom = lars.init_momentum(params, kind)
    mom_u = lars.init_momentum(_unstack(params), kind)
    for _ in range(2):
        # a second step runs on the momentum of the first
        p2, mom = lars.update(params, grads, mom, 0.5, cfg, stack=STACK)
    pu, mu = lars.update(_unstack(params), _unstack(grads), mom_u, 0.5, cfg)
    pu, mu = lars.update(_unstack(params), _unstack(grads), mu, 0.5, cfg)
    for k, v in _unstack(p2).items():
        np.testing.assert_allclose(v, pu[k], rtol=1e-6, atol=1e-7,
                                   err_msg=k)
    if kind == "lars":
        # the norm scales took trust 1: their step is lr * (g + wd * w)
        g, w = grads["layers"]["ln"], params["layers"]["ln"]
        np.testing.assert_allclose(mom["layers"]["ln"] / 0.5,
                                   1.9 * (g + 1e-3 * w), rtol=1e-5)


def test_unstacked_trees_keep_the_old_rule_bit_for_bit():
    """Without stack axes, as for every ResNet leaf, the update is the
    rule LARS had before: one ratio per array of two or more dims."""
    from repro.core import pinit
    from repro.models.registry import build_model
    model = build_model(get_config("resnet50").reduced())
    assert model.stack_axes is None
    params = pinit.materialize(model.param_pd, 0)
    grads = jax.tree.map(lambda p: 0.01 * jnp.cos(p), params)
    mom = jax.tree.map(lambda p: 0.1 * jnp.sin(p), params)
    cfg = lars.OptConfig(kind="lars")
    upd = jax.jit(lambda p, g, m, s: lars.update(p, g, m, 0.3, cfg, stack=s),
                  static_argnums=3)
    new, new_m = upd(params, grads, mom, None)
    zeros = jax.tree.map(lambda _: 0, params)
    new0, _ = jax.jit(lambda p, g, m: lars.update(p, g, m, 0.3, cfg,
                                                  stack=zeros))(
        params, grads, mom)

    def old(p, g, v):
        if p.ndim >= 2:
            pw = jnp.sqrt(jnp.sum(jnp.square(p)))
            gw = jnp.sqrt(jnp.sum(jnp.square(g)))
            trust = jnp.where(pw > 0, cfg.trust_coef * pw / (
                gw + cfg.weight_decay * pw + cfg.eps), 1.0)
        else:
            trust = 1.0
        v2 = cfg.momentum * v + (0.3 * trust) * (g + cfg.weight_decay * p)
        return p - v2

    want = jax.jit(lambda p, g, m: jax.tree.map(old, p, g, m))(
        params, grads, mom)
    jax.tree.map(np.testing.assert_array_equal, new, want)
    jax.tree.map(np.testing.assert_array_equal, new0, want)


def test_packed_paths_refuse_the_published_rule(mesh11):
    """The sharded updates and the batched-norm kernel pack each array
    whole: for a model whose stacks hold published tensors they raise
    rather than take one ratio per stack."""
    from repro.configs.base import CommConfig
    from repro.core.schedule import ScheduleConfig, make_schedule
    from repro.models.registry import build_model
    from repro.train.step import make_train_step
    model = build_model(get_config("deepseek-v2-lite").reduced())
    stack = model.stack_axes
    assert stack["layers"]["ln1"] == 1 and stack["experts"]["w_gate"] == 1
    assert stack["embed"] == 0
    sched = make_schedule(ScheduleConfig(base_lr=0.1, warmup_steps=1,
                                         total_steps=4))
    with pytest.raises(NotImplementedError):
        make_train_step(model, lars.OptConfig(kind="lars"), sched,
                        mesh=mesh11, comm=CommConfig(strategy="ring",
                                                     sharding="zero1"))
    params, grads = _tree(0), _tree(1)
    with pytest.raises(NotImplementedError):
        lars.update(params, grads, lars.init_momentum(params), 0.1,
                    lars.OptConfig(kind="lars", use_kernel=True),
                    stack=STACK)


def test_loss_takes_the_configured_balance_coefficient(mesh11):
    import dataclasses
    from repro.core import pinit
    from repro.models.registry import build_model
    from repro.train.step import make_loss_fn
    cfg = get_config("deepseek-v2-lite").reduced()
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, router_aux_coef=0.25))
    model = build_model(cfg)
    params = pinit.materialize(model.param_pd, 0)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    total, (m, _) = make_loss_fn(model, smoothing=0.0, mesh=mesh11)(
        params, {"tokens": toks, "labels": toks})
    assert float(total) == pytest.approx(
        float(m["loss"]) + 0.25 * float(m["aux"]), rel=1e-6)
    assert float(m["moe_rows_here"]) > 0


def _unwritten_rows_grouped_matmul(fill):
    """``kernels.grouped_matmul`` as the TPU's kernel may leave it: the
    rows past the last group hold ``fill`` (whatever the buffer held) in
    the result and in the data gradient."""
    from repro.kernels.grouped_matmul import grouped_matmul as real

    def past(x, sizes):
        rows = jnp.arange(x.shape[0])[:, None]
        return jnp.where(rows < jnp.sum(sizes), x, jnp.asarray(fill, x.dtype))

    @jax.custom_vjp
    def grouped_matmul(lhs, rhs, sizes):
        return past(real(lhs, rhs, sizes), sizes)

    def fwd(lhs, rhs, sizes):
        return grouped_matmul(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        _, vjp = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)
        d_lhs, d_rhs = vjp(g)
        return past(d_lhs, sizes), d_rhs, None

    grouped_matmul.defvjp(fwd, bwd)
    return grouped_matmul


def test_rows_past_the_held_groups_reach_no_result(monkeypatch):
    """The held experts' buffer holds every routed slot, the held ones
    first: a grouped product that leaves NaN in the rows past the held
    groups, in its result and its data gradient, changes neither the loss
    nor any gradient (the TPU's kernel writes no such row)."""
    from repro.core import pinit
    from repro.models.registry import build_model
    from repro.train.step import make_loss_fn
    cfg = cut(get_config("deepseek-v2-lite").reduced(), n_layers=3,
              vocab_size=128, n_held=2, held_offset=1)
    model = build_model(cfg)
    params = pinit.materialize(model.param_pd, 0)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 128)
    batch = {"tokens": toks, "labels": toks}
    loss_fn = make_loss_fn(model, smoothing=0.0)

    def value_and_grads():
        (total, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        return total, grads

    want = value_and_grads()
    from repro.models import moe
    monkeypatch.setattr(moe, "grouped_matmul",
                        _unwritten_rows_grouped_matmul(jnp.nan))
    got = value_and_grads()
    assert float(got[0]) == float(want[0])
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            jax.tree.leaves(want[1])):
        assert bool(jnp.all(jnp.isfinite(g))), path
        np.testing.assert_array_equal(g, w, err_msg=str(path))


def test_cut_holds_a_share_of_the_experts():
    cfg = cut(get_config("deepseek-v2-lite"), n_layers=5, vocab_size=12_800,
              n_held=8, held_offset=16)
    assert (cfg.n_layers, cfg.vocab_size) == (5, 12_800)
    assert (cfg.moe.n_routed, cfg.moe.n_held, cfg.moe.held_offset) == \
        (64, 8, 16)
    assert cfg.d_model == 2048 and cfg.moe.d_expert == 1408
    with pytest.raises(ValueError):
        cut(get_config("deepseek-v2-lite"), n_held=8, held_offset=60)
    with pytest.raises(ValueError):
        cut(get_config("resnet50"), n_held=1)


def test_launcher_folds_a_large_seed():
    """A seed past int32, as the benchmark draws them, is folded as the
    benchmark folds it, and a small one is kept."""
    from repro.launch.train import SEED_MODULUS, seed_arg
    assert seed_arg("4315200001") == 4315200001 % SEED_MODULUS
    assert seed_arg("7") == 7 and 0 <= seed_arg("-3") < SEED_MODULUS


def test_launcher_trains_a_held_share():
    """``repro.launch.train`` trains the cut through its own flags and logs
    the held experts' counters on logged steps."""
    from repro.launch import train as launcher
    from repro.obs import metrics as obs_metrics
    with obs_metrics.default_registry().use_sink(
            obs_metrics.MemorySink()) as sink:
        state, hist = launcher.main([
            "--arch", "deepseek-v2-lite", "--reduced", "--layers", "2",
            "--vocab", "256", "--experts-held", "2", "--held-offset", "2",
            "--seq", "32", "--batch", "2", "--steps", "2", "--log-every",
            "1", "--data", "uniform", "--smoothing", "0", "--seed",
            "4315200001"])
    assert state.params["experts"]["w_gate"].shape[0] == 2
    assert all(np.isfinite(h["loss"]) for h in hist)
    rows = [e.value for e in sink.find("obs.moe.rows_here")]
    load = [e.value for e in sink.find("obs.moe.load_max_over_mean")]
    assert len(rows) == 2 and all(0 < r <= 2 * 32 * 2 for r in rows)
    assert all(v >= 1.0 for v in load)
