"""Config-contract tests: the exact assigned hyperparameters, shape rules,
and the descriptor/abstract-state machinery."""
import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.tier1  # fast, in-process

from repro.configs import (ALL_ARCHS, ASSIGNED_ARCHS, active_param_count,
                           get_config, param_count, shapes_for)
from repro.core import pinit
from repro.models.registry import build_model

# the assignment block, verbatim
EXPECTED = {
    "xlstm-125m":       dict(L=12, d=768, H=4, kv=4, ff=0, V=50_304),
    "qwen1.5-32b":      dict(L=64, d=5120, H=40, kv=40, ff=27_392, V=152_064),
    "zamba2-7b":        dict(L=81, d=3584, H=32, kv=32, ff=14_336, V=32_000),
    "qwen3-14b":        dict(L=40, d=5120, H=40, kv=8, ff=17_408, V=151_936),
    "whisper-base":     dict(L=6, d=512, H=8, kv=8, ff=2048, V=51_865),
    "mistral-nemo-12b": dict(L=40, d=5120, H=32, kv=8, ff=14_336, V=131_072),
    "internvl2-2b":     dict(L=24, d=2048, H=16, kv=8, ff=8192, V=92_553),
    "qwen1.5-0.5b":     dict(L=24, d=1024, H=16, kv=16, ff=2816, V=151_936),
    "deepseek-v2-236b": dict(L=60, d=5120, H=128, kv=128, ff=1536,
                             V=102_400),
    "qwen2-moe-a2.7b":  dict(L=24, d=2048, H=16, kv=16, ff=1408, V=151_936),
    "deepseek-v2-lite": dict(L=27, d=2048, H=16, kv=16, ff=1408,
                             V=102_400),
}


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_exact_assigned_hyperparameters(arch):
    cfg = get_config(arch)
    e = EXPECTED[arch]
    assert cfg.n_layers == e["L"]
    assert cfg.d_model == e["d"]
    assert cfg.n_heads == e["H"]
    assert cfg.n_kv_heads == e["kv"]
    assert cfg.d_ff == e["ff"]
    assert cfg.vocab_size == e["V"]
    assert cfg.source    # every config cites its source


def test_feature_flags():
    assert get_config("qwen1.5-32b").qkv_bias
    assert get_config("qwen3-14b").qk_norm
    assert get_config("zamba2-7b").ssm.d_state == 64
    ds = get_config("deepseek-v2-236b")
    assert ds.mla.kv_lora_rank == 512
    assert ds.moe.n_routed == 160 and ds.moe.top_k == 6 and ds.moe.n_shared == 2
    qm = get_config("qwen2-moe-a2.7b")
    assert qm.moe.n_routed == 60 and qm.moe.top_k == 4 and qm.moe.n_shared == 4
    assert get_config("whisper-base").encoder.cross_attend
    assert not get_config("internvl2-2b").encoder.cross_attend


def test_param_counts_near_nameplates():
    # analytic counts should be within ~25% of the model names
    expect = {"qwen1.5-32b": 32e9, "qwen3-14b": 14e9, "mistral-nemo-12b":
              12e9, "deepseek-v2-236b": 236e9, "xlstm-125m": 0.125e9}
    for arch, n in expect.items():
        got = param_count(get_config(arch))
        assert 0.7 * n < got < 1.35 * n, (arch, got / 1e9)
    # MoE active << total
    ds = get_config("deepseek-v2-236b")
    assert active_param_count(ds) < 0.2 * param_count(ds)


def test_shape_skip_rules():
    # long_500k only for sub-quadratic archs (+ the sliding-window dense)
    runs_500k = {a for a in ASSIGNED_ARCHS
                 if "long_500k" in shapes_for(get_config(a))}
    assert runs_500k == {"xlstm-125m", "zamba2-7b", "mistral-nemo-12b"}
    # conv: only its own imagenet shape, no decode
    assert list(shapes_for(get_config("resnet50"))) == ["train_imagenet"]
    # everything else runs train/prefill/decode
    for a in ASSIGNED_ARCHS:
        s = shapes_for(get_config(a))
        assert {"train_4k", "prefill_32k", "decode_32k"} <= set(s)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_abstract_param_tree_has_specs(arch):
    model = build_model(get_config(arch))
    ab = pinit.abstract(model.param_pd)
    sp = pinit.specs(model.param_pd)
    na = len(jax.tree.leaves(ab))
    assert na > 0
    from jax.sharding import PartitionSpec
    leaves = jax.tree.leaves(sp, is_leaf=lambda x: isinstance(
        x, PartitionSpec))
    assert all(isinstance(l, PartitionSpec) for l in leaves)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_cache_pd_builds(arch):
    model = build_model(get_config(arch))
    cpd = model.cache_pd(4, 128)
    ab = pinit.abstract(cpd)
    assert len(jax.tree.leaves(ab)) > 0
