"""The DeepSeek-V2-Lite cell at a small size on the CPU (``small_mla_moe``):
the program against the plain float32 reference
(``perfbench/reference/mla_moe.py``), the held experts' shares, a router
that sends every slot to the held experts, whole harness runs, and the
cell's counts at its real size."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import faults, generator, model_scopes, scopes
from perfbench.kinds import train
from perfbench.reference import common, mla_moe
from perfbench.tests import small, small_mla_moe as sm

pytestmark = pytest.mark.tier1

SEED = 7


def _program(c):
    from repro.models.registry import build_model
    return build_model(c.config_module.program_config(c.config))


def _batch(c, key=3):
    return generator.batch_fn(c.traffic, c.config_module.sizes(c.config),
                              c.traffic["batch_per_chip"])(
        jax.random.PRNGKey(key))


def _rel(a, b):
    return float(jnp.linalg.norm((a - b).astype(jnp.float32))
                 / jnp.linalg.norm(b.astype(jnp.float32)))


def _program_grads(model, params, batch):
    from repro.train.step import make_loss_fn
    loss_fn = make_loss_fn(model, smoothing=0.0, mesh=None)
    (_, (metrics, _)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch)
    return metrics, train.flatten(grads)


def _reference_grads(c, batch, compute="f32"):
    spec, loss_fn, _ = c.config_module.reference(c.config, c.traffic, 1,
                                                 compute)
    params = common.init_tree(spec, SEED)
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
    return params, float(loss), grads


def test_program_matches_the_reference_in_float32():
    """In float32 at the highest matrix precision the program computes
    what the reference does, from the same draws: the same operations in
    another order (a grouped product where the reference loops over
    experts, a scan over layers, chunked attention), so the gaps are
    float32 rounding, 2e-6 and less here; 1e-5 leaves room for it."""
    from repro.core import pinit
    c = sm.cell()
    model, batch = _program(c), _batch(c)
    with small.program_in_f32():
        params = pinit.materialize(model.param_pd, SEED)
        metrics, grads = _program_grads(model, params, batch)
        logits, _, _ = model.forward_train(params, batch, None,
                                           counters=True)[0]
    ref_params, ref_loss, ref_grads = _reference_grads(c, batch)
    flat = train.flatten(params)
    assert set(flat) == set(ref_params)
    for k, v in ref_params.items():
        np.testing.assert_array_equal(flat[k], v, err_msg=k)
    assert float(metrics["loss"]) == pytest.approx(ref_loss, rel=1e-5)
    dims = c.config_module.dims(c.config)
    with jax.default_matmul_precision("highest"):
        for b in range(batch["tokens"].shape[0]):
            want, _ = mla_moe.logits(ref_params, batch["tokens"][b], dims)
            assert _rel(logits[b], want) < 1e-5
    for k, g in ref_grads.items():
        assert _rel(grads[k], g) < 1e-5, k


def test_program_in_bfloat16_stays_in_its_band():
    """The program as it runs (bfloat16 compute, float32 router and
    masters) against the float32 reference, on one batch from one seed:
    its loss within 4e-4 (it reads 8.2e-5), every tensor's gradient within
    20% of its norm (the worst, ``experts/w_gate``, reads 8.1%). The float8
    control falls outside both (8.5e-4; the router's gradient 60%)."""
    from repro.core import pinit
    c = sm.cell()
    model, batch = _program(c), _batch(c)
    params = pinit.materialize(model.param_pd, SEED)
    metrics, grads = _program_grads(model, params, batch)
    _, ref_loss, ref_grads = _reference_grads(c, batch)
    _, ctl_loss, ctl_grads = _reference_grads(c, batch, "fp8")

    def band(loss, grads):
        worst = max(_rel(grads[k], g) for k, g in ref_grads.items())
        return abs(loss - ref_loss) / ref_loss < 4e-4 and worst < 0.2

    assert band(float(metrics["loss"]), grads)
    assert not band(ctl_loss, train.flatten(ctl_grads))


def _moe_inputs(n_routed=16):
    key = jax.random.split(jax.random.PRNGKey(0), 5)
    d, f = 64, 32
    router = 0.3 * jax.random.normal(key[0], (d, n_routed))
    bank = {"w_gate": 0.1 * jax.random.normal(key[1], (n_routed, d, f)),
            "w_up": 0.1 * jax.random.normal(key[2], (n_routed, d, f)),
            "w_down": 0.1 * jax.random.normal(key[3], (n_routed, f, d))}
    x = jax.random.normal(key[4], (2, 32, d))
    return router, bank, x


def _moe_cfg(n_held, offset, top_k=6):
    import dataclasses
    c = sm.cell()
    cfg = c.config_module.program_config(c.config)
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_held=n_held, held_offset=offset, top_k=top_k))


def _reference_moe(router, bank, x, cfg, n_held, offset):
    """The reference's MoE layer over experts [offset, offset + n_held),
    less its shared experts (zero weights)."""
    d = x.shape[-1]
    dims = dict(n_routed=router.shape[1], top_k=cfg.moe.top_k,
                routed_scale=cfg.moe.routed_scale, n_held=n_held,
                held_offset=offset)
    p = {"moe": {"router": router},
         "shared": {"w_gate": jnp.zeros((d, 1)), "w_up": jnp.zeros((d, 1)),
                    "w_down": jnp.zeros((1, d))}}
    held = {k: v[offset:offset + n_held] for k, v in bank.items()}
    out = [mla_moe._moe(p, held, x[b], dims, "f32")[0]
           for b in range(x.shape[0])]
    return jnp.stack(out)


def test_held_shares_add_up_to_the_whole_layer():
    """Four chips' shares of 16 experts (offsets 0, 4, 8 and 12) add up to
    the layer that holds all 16, and each share is the reference's masked
    loop over its own experts."""
    from repro.models import moe
    router, bank, x = _moe_inputs()
    with jax.default_matmul_precision("highest"):
        whole, _, _ = moe.moe_held(router, bank, x, _moe_cfg(16, 0))
        total = 0
        for offset in (0, 4, 8, 12):
            cfg = _moe_cfg(4, offset)
            share = {k: v[offset:offset + 4] for k, v in bank.items()}
            out, _, stats = moe.moe_held(router, share, x, cfg)
            want = _reference_moe(router, bank, x, cfg, 4, offset)
            assert _rel(out, want) < 1e-5
            total = total + out
    assert _rel(total, whole) < 1e-5
    ref_whole = _reference_moe(router, bank, x, _moe_cfg(16, 0), 16, 0)
    assert _rel(whole, ref_whole) < 1e-5


def test_router_sending_every_slot_here_drops_nothing():
    """A router whose six largest logits are the held experts 0-5 for every
    token (a feature that is 1 on every token, weighted 100 for them): all T * 6 slots land on this chip (the static buffer's worst
    case), six experts take T rows each, and the result is the
    reference's, with nothing dropped."""
    from repro.models import moe
    router, bank, x = _moe_inputs()
    x = x.at[..., 0].set(1.0)
    router = router.at[0, :6].set(100.0 + jnp.arange(6.0))
    cfg = _moe_cfg(8, 0)
    held = {k: v[:8] for k, v in bank.items()}
    with jax.default_matmul_precision("highest"):
        out, _, stats = moe.moe_held(router, held, x, cfg)
        want = _reference_moe(router, bank, x, cfg, 8, 0)
    T = x.shape[0] * x.shape[1]
    assert float(stats["moe_rows_here"]) == T * 6
    assert float(stats["moe_load_max_over_mean"]) == pytest.approx(8 / 6)
    assert _rel(out, want) < 1e-5


@pytest.mark.parametrize("fault", [None, "half_batch", "state_unchanged"])
def test_small_cell_run(fault):
    c = sm.cell(sm.LIMITS)
    plant = faults.planted(fault) if fault else contextlib.nullcontext()
    with plant:
        result = small.run(c, seed=2 ** 31 + 20)
    assert result["correct"] is (fault is None), result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    # the CPU reports no device memory and runs too few steps for a p95
    assert set(result["metrics"]) <= {"setup_s", "step_ms_p95"}
    assert "setup_s" in result["metrics"]


def test_small_cell_control_fails():
    """The float8 control, read as the program, fails a compared reading
    of the small cell's limits."""
    from perfbench import oracle
    c = sm.cell()
    batches = generator.make_pool(c.traffic, c.config_module.sizes(c.config),
                                  2, SEED)
    ref = train.run_reference(c, train.reference(c, "f32"), batches, SEED)
    ctl = train.run_reference(c, train.reference(c, "fp8"), batches, SEED)
    values = oracle.gaps(ctl, ref)
    assert not oracle.passed(oracle.checks(values, sm.LIMITS)), values


def test_counts_at_the_cells_size():
    """2.18 GFLOP a training token; 24,576 rows routed here a step at a
    uniform router (T k n_held / n_routed); 36 grouped-product passes,
    each bound by its FLOPs."""
    c = sm.spec.Cell(sm.spec.benchmark(), sm.CELL)
    cm, t = c.config_module, c.traffic
    assert cm.flops_per_item(c.config, t) == pytest.approx(2.177e9,
                                                           rel=1e-3)
    assert cm.held_rows(c.config, t, 4) == 24_576
    passes = cm.expert_passes(c.config, t, 4)
    assert len(passes) == 4 * 3 * 3
    assert all(f / 197e12 > b / 819e9 for f, b in passes)


def test_program_config_is_the_published_cut():
    c = sm.spec.Cell(sm.spec.benchmark(), sm.CELL)
    cfg = c.config_module.program_config(c.config)
    assert (cfg.n_layers, cfg.vocab_size, cfg.d_model) == (5, 12_800, 2048)
    assert (cfg.moe.n_routed, cfg.moe.n_held, cfg.moe.held_offset) == \
        (64, 8, 0)
    assert cfg.moe.first_k_dense == 1 and cfg.moe.d_dense == 10_944
    assert cfg.lars_per_published_tensor and cfg.remat


def test_scope_ms_reads_the_model_scopes():
    """``model_scopes`` takes the scopes inside JAX's transformation names
    and in order, past a scan's and a checkpoint's own levels:
    ``moe/experts`` holds the forward's, the recompute's and the
    backward's expert products and the grouped-matmul kernels, not the
    router."""
    hlo = "\n".join([
        'ENTRY %main () -> f32[] {',
        '  %a = f32[] add(), metadata={op_name="jit(s)/forward/moe/experts/dot"}',
        '  %b = f32[] add(), metadata={op_name="jit(s)/transpose(jvp(forward))/'
        'transpose(jvp(moe))/transpose(jvp(experts))/dot"}',
        '  %c = f32[] add(), metadata={op_name="jit(s)/forward/moe/router/dot"}',
        '  %d = f32[] add(), metadata={op_name="jit(s)/forward/attention/dot"}',
        '  %e = f32[] add(), metadata={op_name="jit(s)/transpose(jvp(forward))/'
        'moe/while/body/closed_call/checkpoint/rematted_computation/experts/'
        'mul"}',
        '  %f = f32[] custom-call(), metadata={op_name="jit(s)/transpose(jvp('
        'forward))/while/body/closed_call/checkpoint/moe/while/body/'
        'closed_call/checkpoint/experts/jit(tgmm)/pallas_call"}',
        '  %h = f32[] custom-call(), metadata={op_name="ragged-dot-none"}',
        '  %g = f32[] add(), metadata={op_name="jit(s)/forward/experts/moe/mul"}',
        '}'])
    names = model_scopes.op_names(hlo)

    def under(*path):
        return {n for n, op in names.items()
                if model_scopes._holds(model_scopes._bases(op), path)}

    assert under("moe", "experts") == {"a", "b", "e", "f"}
    assert under("moe", "router") == {"c"}
    assert under("moe") == {"a", "b", "c", "e", "f", "g"}
    assert under("attention") == {"d"}
    # the phases still take the innermost scope they know: the model's
    # own scopes leave fwd/bwd to ``forward`` and its transpose
    phases = scopes.scope_map(hlo)
    assert (phases["a"], phases["b"], phases["d"]) == \
        ("forward", "backward", "forward")
