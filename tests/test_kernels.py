"""Pallas-kernel validation: shape/dtype sweeps, allclose vs ref.py oracles
(interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.tier1  # fast, in-process

from repro.core import bucketing
from repro.kernels import ops, ref

CHUNK = bucketing.CHUNK


@pytest.mark.parametrize("n_chunks,n_tensors", [(1, 1), (4, 2), (16, 5),
                                                (7, 7), (32, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_batched_sumsq(n_chunks, n_tensors, dtype):
    seg = np.sort(np.arange(n_chunks) % n_tensors).astype(np.int32)
    flat = jax.random.normal(jax.random.PRNGKey(n_chunks),
                             (n_chunks * CHUNK,)).astype(dtype)
    got = ops.batched_sumsq(flat, jnp.asarray(seg), n_tensors,
                            interpret=True)
    want = ref.batched_sumsq(flat, jnp.asarray(seg), n_tensors)
    np.testing.assert_allclose(got, want, rtol=2e-3)


@pytest.mark.parametrize("n_chunks,n_tensors", [(2, 1), (8, 3), (16, 16)])
@pytest.mark.parametrize("lr,mu,wd", [(0.1, 0.9, 1e-4), (1.0, 0.0, 0.0)])
def test_lars_packed_update(n_chunks, n_tensors, lr, mu, wd):
    seg = np.sort(np.arange(n_chunks) % n_tensors).astype(np.int32)
    N = n_chunks * CHUNK
    k = jax.random.PRNGKey(0)
    p = jax.random.normal(k, (N,))
    g = jax.random.normal(jax.random.fold_in(k, 1), (N,))
    m = 0.1 * jax.random.normal(jax.random.fold_in(k, 2), (N,))
    trust = jnp.abs(jax.random.normal(jax.random.fold_in(k, 3),
                                      (n_tensors,)))
    got_p, got_m = ops.lars_packed_update(p, g, m, trust, jnp.asarray(seg),
                                          lr=lr, momentum=mu, wd=wd,
                                          interpret=True)
    want_p, want_m = ref.lars_packed_update(p, g, m, trust, jnp.asarray(seg),
                                            lr=lr, momentum=mu, wd=wd)
    np.testing.assert_allclose(got_p, want_p, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_m, want_m, rtol=1e-5, atol=1e-6)


def _ragged_layout_tree():
    """Real-model-shaped ragged layout: conv / BN scale / dense / head /
    scalar leaves whose per-tensor CHUNK padding and multi-bucket plan
    exercise the packed seg maps the way a real resnet plan does."""
    k = jax.random.PRNGKey(42)
    return {
        "conv1": jax.random.normal(k, (3, 3, 3, 24)),
        "bn": {"scale": jnp.full((24,), 1.5),
               "bias": 0.1 * jax.random.normal(jax.random.fold_in(k, 1),
                                               (24,))},
        "block": {"w1": jax.random.normal(jax.random.fold_in(k, 2),
                                          (129, 65)),
                  "w2": jax.random.normal(jax.random.fold_in(k, 3),
                                          (65, 200))},
        "head": jax.random.normal(jax.random.fold_in(k, 4), (200, 33)),
        "scalar": jnp.float32(0.7),
    }


def test_lars_packed_update_kernel_on_real_bucket_layout():
    """The fused Pallas kernel vs the UNPACKED per-tensor jnp update, on a
    plan-derived multi-bucket layout (per-tensor CHUNK padding, seg map
    from the plan) — the layout the ZeRO-1 path actually feeds it."""
    params = _ragged_layout_tree()
    k = jax.random.PRNGKey(7)
    grads = jax.tree.map(
        lambda x: 0.01 * jax.random.normal(k, x.shape), params)
    mom = jax.tree.map(lambda x: 0.05 * jnp.ones_like(x), params)
    plan = bucketing.make_plan(params, bucket_mb=0.05)
    assert plan.n_buckets >= 2
    trust_leaves = [0.1 + jnp.abs(jax.random.normal(
        jax.random.fold_in(k, i), ())) for i in range(plan.n_tensors)]
    trust = jnp.stack(trust_leaves)            # indexed like plan.slots
    lr, mu, wd = 0.1, 0.9, 1e-4

    p_buf = bucketing.concat_buckets(bucketing.pack(params, plan,
                                                    dtype=jnp.float32))
    g_buf = bucketing.concat_buckets(bucketing.pack(grads, plan,
                                                    dtype=jnp.float32))
    m_buf = bucketing.concat_buckets(bucketing.pack(mom, plan,
                                                    dtype=jnp.float32))
    seg = jnp.asarray(bucketing.segment_ids(plan))
    got_p, got_m = ops.lars_packed_update(p_buf, g_buf, m_buf, trust, seg,
                                          lr=lr, momentum=mu, wd=wd,
                                          interpret=True)
    sizes = list(plan.bucket_sizes)
    offs = np.concatenate([[0], np.cumsum(sizes)])
    got_p_tree = bucketing.unpack(
        [got_p[offs[b]:offs[b + 1]] for b in range(plan.n_buckets)], plan)
    got_m_tree = bucketing.unpack(
        [got_m[offs[b]:offs[b + 1]] for b in range(plan.n_buckets)], plan)

    # unpacked per-tensor reference (slot i describes leaf n-1-i)
    trust_tree = jax.tree_util.tree_unflatten(
        plan.treedef, list(reversed(list(trust))))

    def ref_upd(p, g, v, t):
        g = g + wd * p
        v2 = mu * v + (lr * t) * g
        return p - v2, v2

    want = jax.tree.map(ref_upd, params, grads, mom, trust_tree)
    want_p = jax.tree.map(lambda t: t[0], want,
                          is_leaf=lambda x: isinstance(x, tuple))
    want_m = jax.tree.map(lambda t: t[1], want,
                          is_leaf=lambda x: isinstance(x, tuple))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-5, atol=1e-6), got_p_tree, want_p)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-5, atol=1e-6), got_m_tree, want_m)


@pytest.mark.parametrize("n_shards", [2, 4])
def test_lars_packed_update_kernel_sharded_layout(n_shards):
    """Kernel on each CHUNK-aligned shard (shard-aware seg maps) ==
    kernel on the full padded bucket — the ZeRO-1 invariant."""
    params = _ragged_layout_tree()
    k = jax.random.PRNGKey(3)
    grads = jax.tree.map(
        lambda x: 0.01 * jax.random.normal(k, x.shape), params)
    plan = bucketing.make_plan(params, bucket_mb=0.05)
    trust = 0.1 + jnp.abs(jax.random.normal(k, (plan.n_tensors,)))
    seg_maps = bucketing.shard_segment_ids(plan, n_shards)
    p_bufs = bucketing.pack(params, plan, dtype=jnp.float32)
    g_bufs = bucketing.pack(grads, plan, dtype=jnp.float32)
    for b in range(plan.n_buckets):
        p = bucketing.pad_to_shards(p_bufs[b], n_shards)
        g = bucketing.pad_to_shards(g_bufs[b], n_shards)
        m = jnp.zeros_like(p)
        c = bucketing.shard_elems(plan.bucket_sizes[b], n_shards)
        full_p, full_m = ops.lars_packed_update(
            p, g, m, trust, jnp.asarray(seg_maps[b].reshape(-1)),
            lr=0.1, momentum=0.9, wd=1e-4, interpret=True)
        for s in range(n_shards):
            sh_p, sh_m = ops.lars_packed_update(
                p[s * c:(s + 1) * c], g[s * c:(s + 1) * c],
                m[s * c:(s + 1) * c], trust,
                jnp.asarray(seg_maps[b][s]), lr=0.1, momentum=0.9,
                wd=1e-4, interpret=True)
            np.testing.assert_allclose(sh_p, full_p[s * c:(s + 1) * c],
                                       rtol=1e-6, atol=1e-7)
            np.testing.assert_allclose(sh_m, full_m[s * c:(s + 1) * c],
                                       rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("T,V", [(8, 512), (64, 1000), (128, 4096),
                                 (256, 2048), (16, 333), (264, 5000)])
@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_smoothed_xent(T, V, smoothing):
    k = jax.random.PRNGKey(T + V)
    logits = 4.0 * jax.random.normal(k, (T, V))
    labels = jax.random.randint(jax.random.fold_in(k, 1), (T,), 0, V)
    got = ops.smoothed_xent_rows(logits, labels, smoothing, interpret=True)
    want = ref.smoothed_xent_rows(logits, labels, smoothing=smoothing)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_smoothed_xent_bf16_logits():
    k = jax.random.PRNGKey(9)
    logits = (4.0 * jax.random.normal(k, (32, 512))).astype(jnp.bfloat16)
    labels = jax.random.randint(jax.random.fold_in(k, 1), (32,), 0, 512)
    got = ops.smoothed_xent_rows(logits, labels, 0.1, interpret=True)
    want = ref.smoothed_xent_rows(logits.astype(jnp.float32), labels,
                                  smoothing=0.1)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_tree_norms_matches_per_tensor():
    k = jax.random.PRNGKey(3)
    tree = {"w": jax.random.normal(k, (300, 40)),
            "b": jnp.full((7,), 2.0),
            "nested": {"x": jax.random.normal(jax.random.fold_in(k, 1),
                                              (1025,))}}
    got = ops.tree_norms(tree, interpret=True)
    want = jax.tree.map(lambda x: jnp.linalg.norm(x.astype(jnp.float32)),
                        tree)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5),
                 got, want)


def test_kernel_lars_equals_jnp_lars_end_to_end():
    """Full optimizer step: packed-kernel LARS == tree-based jnp LARS."""
    from repro.core import lars
    k = jax.random.PRNGKey(0)
    params = {"w1": jax.random.normal(k, (64, 32)),
              "b1": jnp.zeros((32,)),
              "w2": jax.random.normal(jax.random.fold_in(k, 1), (32, 8))}
    grads = jax.tree.map(
        lambda x: 0.01 * jax.random.normal(jax.random.fold_in(k, 2),
                                           x.shape), params)
    mom = jax.tree.map(jnp.zeros_like, params)
    cfg_j = lars.OptConfig(kind="lars", use_kernel=False)
    cfg_k = lars.OptConfig(kind="lars", use_kernel=True)
    p1, m1 = lars.update(params, grads, mom, 0.1, cfg_j)
    p2, m2 = lars.update(params, grads, mom, 0.1, cfg_k)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                         atol=1e-6), p1, p2)


@pytest.mark.parametrize("B,S,H,K,Dk,Dv", [
    (2, 64, 4, 2, 32, 32), (1, 128, 2, 2, 16, 16), (2, 96, 4, 4, 32, 16)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 24),
                                           (False, 0)])
def test_flash_attention_vs_oracle(B, S, H, K, Dk, Dv, causal, window):
    """Pallas flash kernel == pure-jnp chunked online-softmax oracle."""
    from repro.kernels.ops import flash_attention_bshd
    from repro.models.attention import chunked_attention
    kq = jax.random.PRNGKey(S + H + Dk)
    q = jax.random.normal(kq, (B, S, H, Dk))
    k = jax.random.normal(jax.random.fold_in(kq, 1), (B, S, K, Dk))
    v = jax.random.normal(jax.random.fold_in(kq, 2), (B, S, K, Dv))
    got = flash_attention_bshd(q, k, v, causal=causal, window=window,
                               interpret=True)
    want = chunked_attention(q, k, v, q_offset=0, causal=causal,
                             window=window, chunk=32)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_flash_attention_bf16():
    from repro.kernels.ops import flash_attention_bshd
    from repro.models.attention import chunked_attention
    kq = jax.random.PRNGKey(7)
    q = jax.random.normal(kq, (2, 64, 4, 32)).astype(jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(kq, 1),
                          (2, 64, 2, 32)).astype(jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(kq, 2),
                          (2, 64, 2, 32)).astype(jnp.bfloat16)
    got = flash_attention_bshd(q, k, v, causal=True, interpret=True)
    want = chunked_attention(q, k, v, q_offset=0, causal=True, chunk=32)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)
