"""Model-component unit tests beyond the smoke level."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ModelConfig, SSMConfig, XLSTMConfig
from repro.core import pinit
from repro.models import mamba as mb
from repro.models import xlstm as xl
from repro.models.attention import chunked_attention
from repro.models.common import rms_norm, rope
from repro.launch.mesh import make_mesh

pytestmark = pytest.mark.tier1


def test_rope_rotation_preserves_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4, 16))
    pos = jnp.arange(8)[None]
    y = rope(x, pos, 10000.0)
    np.testing.assert_allclose(jnp.linalg.norm(x, axis=-1),
                               jnp.linalg.norm(y, axis=-1), rtol=1e-4)


def test_rope_relative_property():
    """<rope(q,i), rope(k,j)> depends only on i-j."""
    k = jax.random.PRNGKey(1)
    q = jax.random.normal(k, (1, 1, 1, 32))
    kk = jax.random.normal(jax.random.fold_in(k, 1), (1, 1, 1, 32))
    def dot_at(i, j):
        qi = rope(q, jnp.asarray([[i]]), 100.0)
        kj = rope(kk, jnp.asarray([[j]]), 100.0)
        return float(jnp.sum(qi * kj))
    assert dot_at(3, 1) == pytest.approx(dot_at(10, 8), rel=1e-4)
    assert dot_at(5, 5) == pytest.approx(dot_at(0, 0), rel=1e-4)


def test_sliding_window_blocks_distant_keys():
    B, S, H, Dh = 1, 32, 2, 8
    k = jax.random.PRNGKey(2)
    q = jax.random.normal(k, (B, S, H, Dh))
    kk = jax.random.normal(jax.random.fold_in(k, 1), (B, S, H, Dh))
    v = jnp.zeros((B, S, H, Dh)).at[:, 0].set(100.0)  # signal at position 0
    full = chunked_attention(q, kk, v, q_offset=0, causal=True, chunk=8)
    win = chunked_attention(q, kk, v, q_offset=0, causal=True, window=4,
                            chunk=8)
    # with window 4, queries past position 4 cannot see position 0
    assert float(jnp.abs(win[:, 8:]).max()) < 1e-3
    assert float(jnp.abs(full[:, 8:]).max()) > 1.0


def _mamba_cfg():
    return ModelConfig(
        arch_id="t", family="hybrid", source="", n_layers=1, d_model=64,
        ssm=SSMConfig(d_state=16, d_conv=4, expand=2, head_dim=32, chunk=8))


def test_mamba_parallel_equals_sequential_decode():
    """Chunked SSD (train path) == step-by-step recurrence (decode path)."""
    cfg = _mamba_cfg()
    pd = mb.mamba_pd(cfg)
    p = pinit.materialize(pd, seed=0)
    B, S = 2, 24
    x = (0.5 * jax.random.normal(jax.random.PRNGKey(0), (B, S, 64))
         ).astype(jnp.float32)
    y_par, cache = mb.mamba_parallel(p, x, cfg, return_cache=True)

    # sequential: feed tokens one by one
    c = {"conv_x": jnp.zeros((B, 3, 128)), "conv_B": jnp.zeros((B, 3, 16)),
         "conv_C": jnp.zeros((B, 3, 16)),
         "state": jnp.zeros((B, 4, 32, 16))}
    outs = []
    for t in range(S):
        o, c = mb.mamba_decode(p, x[:, t:t + 1], cfg, c)
        outs.append(o)
    y_seq = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y_par, np.float32),
                               np.asarray(y_seq, np.float32),
                               rtol=5e-2, atol=5e-2)
    # final states agree too
    np.testing.assert_allclose(np.asarray(cache["state"]),
                               np.asarray(c["state"]), rtol=5e-2, atol=5e-2)


def _xlstm_cfg():
    return ModelConfig(
        arch_id="t", family="ssm", source="", n_layers=1, d_model=64,
        n_heads=4, n_kv_heads=4, xlstm=XLSTMConfig(chunk=8))


def test_mlstm_parallel_equals_sequential_decode():
    cfg = _xlstm_cfg()
    pd = xl.mlstm_pd(cfg)
    p = pinit.materialize(pd, seed=0)
    B, S = 2, 16
    x = (0.5 * jax.random.normal(jax.random.PRNGKey(3), (B, S, 64))
         ).astype(jnp.float32)
    y_par, cache = xl.mlstm_parallel(p, x, cfg, return_cache=True)

    di = int(cfg.xlstm.proj_factor_m * 64)
    nh, hd = 4, di // 4
    c = {"C": jnp.zeros((B, nh, hd, hd)), "n": jnp.zeros((B, nh, hd)),
         "m": jnp.full((B, nh), -1e30)}
    outs = []
    for t in range(S):
        o, c = xl.mlstm_decode(p, x[:, t:t + 1], cfg, c)
        outs.append(o)
    y_seq = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y_par, np.float32),
                               np.asarray(y_seq, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_slstm_parallel_equals_sequential_decode():
    cfg = _xlstm_cfg()
    pd = xl.slstm_pd(cfg)
    p = pinit.materialize(pd, seed=0)
    B, S = 2, 12
    x = (0.5 * jax.random.normal(jax.random.PRNGKey(4), (B, S, 64))
         ).astype(jnp.float32)
    y_par, cache = xl.slstm_parallel(p, x, cfg, return_cache=True)
    c = {k: jnp.zeros((B, 64)) for k in ("c", "n", "h")}
    c["m"] = jnp.full((B, 64), -1e30)
    outs = []
    for t in range(S):
        o, c = xl.slstm_decode(p, x[:, t:t + 1], cfg, c)
        outs.append(o)
    y_seq = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y_par, np.float32),
                               np.asarray(y_seq, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_moe_capacity_drop_rate_reasonable():
    """At init (near-uniform router) the drop rate at cf=1.25 stays small."""
    from repro.models import moe as moem
    cfg = get_config("qwen2-moe-a2.7b").reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    pd = moem.moe_pd(cfg)
    p = pinit.materialize(pd, seed=0)
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(0), (4, 64, cfg.d_model))
    out, aux = moem.moe_apply(p, x, cfg, mesh, decode=False)
    assert out.shape == x.shape
    assert bool(jnp.isfinite(out).all())
    # aux loss near 1.0 for near-uniform routing (E * sum f*p ~= 1)
    assert 0.5 < float(aux) < 4.0


def test_bn_moving_average_update():
    from repro.models.resnet import _bn
    p = {"scale": jnp.ones((4,)), "bias": jnp.zeros((4,))}
    st = {"mean": jnp.zeros((4,)), "var": jnp.ones((4,))}
    x = 2.0 + jnp.zeros((8, 3, 3, 4))
    y, st2 = _bn(x, p, st, train=True, momentum=0.9)
    np.testing.assert_allclose(st2["mean"], 0.9 * 0 + 0.1 * 2.0, rtol=1e-5)
    # normalized output ~ 0 mean
    assert abs(float(y.mean())) < 1e-3
