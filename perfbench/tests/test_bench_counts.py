"""The benchmark's own operation counts, against the published figure and
against the HLO of the program's forward compiled for the CPU, where
nothing is recomputed."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from perfbench import counts

pytestmark = pytest.mark.tier1

NEMO_L2 = dict(d_model=5120, d_ff=14336, n_heads=32, n_kv_heads=8,
               head_dim=128, n_layers=2, vocab_size=16384)


def test_resnet50_forward_is_4_09_gmac():
    assert len(counts.resnet_convs()) == 54          # 53 convolutions + fc
    assert counts.resnet_forward_macs() == pytest.approx(4.09e9, rel=0.01)


def test_resnet50_training_flops_are_three_forwards_less_the_stem_dgrad():
    convs = counts.resnet_convs()
    stem = convs[0]
    assert not stem.needs_dgrad and all(c.needs_dgrad for c in convs[1:])
    assert counts.resnet_train_flops_per_image() == \
        6 * counts.resnet_forward_macs() - 2 * stem.macs


def test_conv_least_time_takes_the_larger_bound():
    least = counts.conv_least_time(256, 197e12, 819e9)
    assert least.compute_bound + least.memory_bound == 3 * 54 - 1
    assert max(least.flops_seconds, least.bytes_seconds) <= least.seconds \
        <= least.flops_seconds + least.bytes_seconds


def test_decoder_count_is_6n_plus_causal_attention():
    n = counts.decoder_matmul_weights(NEMO_L2)
    assert n == 2 * 272_629_760 + 5120 * 16384      # 629.1 M
    attn = 3 * 2 * 4 * 4096 * (4096 + 1) / 2
    assert counts.decoder_flops_per_token(NEMO_L2, 4096) == 6 * n + attn


def _hlo_flops(fn, *args):
    from repro.launch import hlo_cost
    text = jax.jit(fn).lower(*args).compile().as_text()
    return hlo_cost.analyze_text(text).flops


def test_resnet_count_matches_the_compiled_forward():
    from repro.configs import get_config
    from repro.core import pinit
    from repro.models.resnet import resnet_forward, resnet_pd
    # at the published image size, so that no convolution reads a map
    # smaller than its kernel (XLA drops the taps that read only padding)
    cfg = dataclasses.replace(get_config("resnet50"), image_size=224,
                              n_classes=16, width=8)
    p_pd, s_pd = resnet_pd(cfg)
    params, bn = pinit.abstract(p_pd), pinit.abstract(s_pd)
    images = jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32)
    flops = _hlo_flops(lambda p, b, x: resnet_forward(p, b, cfg, x,
                                                      train=True)[0],
                       params, bn, images)
    want = 2 * counts.resnet_forward_macs(width=8, image=224, n_classes=16)
    assert flops == pytest.approx(want, rel=0.01)


def test_decoder_count_matches_the_compiled_forward():
    from repro.configs import get_config
    from repro.core import pinit
    from repro.models import transformer
    c = dict(d_model=64, d_ff=128, n_heads=4, n_kv_heads=2, head_dim=16,
             n_layers=2, vocab_size=256)
    seq = 64
    cfg = dataclasses.replace(
        get_config("mistral-nemo-12b"), n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256, remat=False,
        attn_chunk=seq)
    params = pinit.abstract_compute(transformer.lm_pd(cfg))
    tokens = jax.ShapeDtypeStruct((2, seq), jnp.int32)
    flops = _hlo_flops(lambda p, t: transformer.forward_train(
        p, cfg, None, {"tokens": t})[0], params, tokens)
    want = 2 * seq * (2 * counts.decoder_matmul_weights(c)
                      + counts.decoder_attention_flops_per_token(
                          c, seq, causal=False, passes=1))
    assert flops == pytest.approx(want, rel=0.01)
