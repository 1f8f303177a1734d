"""Tests for the collective-schedule subsystem (repro/comm/).

Equivalence on a real 8-device mesh runs in a subprocess (jax locks the
host-device count at first init; conftest must keep the single real CPU
device). Everything else — registry, cost model, ring-step kernel,
degenerate 1-device meshes — runs in-process.
"""
import subprocess
import sys
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import comm
from repro.comm import cost
from repro.comm.ring_kernel import ring_add_step
from repro.core import bucketing, ddp
from repro.core.compat import shard_map
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh

pytestmark = pytest.mark.tier1


# ------------------------------------------------------------- registry

def test_registry_lists_all_schedules():
    assert set(comm.available()) == {"psum", "ring", "hierarchical",
                                     "2d_torus", "dbtree"}


def test_registry_every_schedule_has_reduce_scatter_form():
    """The ZeRO-1 path requires an RS-terminal form for every schedule
    (native or reduce-then-slice), plus the bucketed alias."""
    for s in comm.available() + ["bucketed"]:
        assert callable(comm.get_reduce_scatter(s))
    with pytest.raises(KeyError):
        comm.get_reduce_scatter("nope")


def test_registry_alias_and_unknown():
    assert comm.get_schedule("bucketed") is comm.get_schedule("psum")
    with pytest.raises(KeyError):
        comm.get_schedule("tree")


# ------------------------------------------------------------ cost model

MB = 2 ** 20


def test_cost_single_axis_ring_equals_psum():
    """On one axis the fused-psum model IS a ring — identical prediction."""
    a = cost.predict("psum", ("data",), (16,), 50 * MB)
    b = cost.predict("ring", ("data",), (16,), 50 * MB)
    assert a.time_s == pytest.approx(b.time_s)
    assert a.n_messages == b.n_messages == 2 * 15


def test_cost_hierarchical_cuts_cross_pod_traffic():
    """The point of the hierarchy: cross-pod (DCI) bytes shrink by the
    intra-axis size, so on the 2-pod mesh it beats flat ring and psum."""
    axes, sizes = ("pod", "data"), (2, 16)
    flat = {s: cost.predict(s, axes, sizes, 50 * MB) for s in
            ("psum", "ring", "hierarchical", "2d_torus")}
    assert flat["hierarchical"].time_s < flat["ring"].time_s
    assert flat["hierarchical"].time_s < flat["psum"].time_s
    # torus and hierarchical move the same bytes on this 2-axis mesh
    assert flat["2d_torus"].wire_bytes == pytest.approx(
        flat["hierarchical"].wire_bytes)
    dci_bytes = lambda r: sum(p.wire_bytes for p in r.phases
                              if p.link.bw == cost.DCI.bw)
    assert dci_bytes(flat["hierarchical"]) < dci_bytes(flat["ring"]) / 2


def test_cost_bucketing_scales_alpha_not_bytes():
    one = cost.predict("ring", ("data",), (16,), 50 * MB, n_buckets=1)
    many = cost.predict("ring", ("data",), (16,), 50 * MB, n_buckets=13)
    assert many.n_messages == 13 * one.n_messages
    assert many.wire_bytes == pytest.approx(one.wire_bytes)
    assert many.time_s > one.time_s         # extra latency, same bandwidth


def test_cost_degenerate_axes_are_free():
    for s in ("2d_torus", "dbtree"):
        r = cost.predict(s, ("pod", "data"), (1, 1), 50 * MB)
        assert r.time_s == 0 and r.n_messages == 0


def test_cost_dbtree_latency_vs_bandwidth_regimes():
    """The double binary tree is the logarithmic-latency point: it beats
    the ring for small (alpha-bound) payloads — 2*ceil(log2 n) messages vs
    2(n-1) — and loses for large (bandwidth-bound) ones."""
    small = 64 * 1024
    tree_s = cost.predict("dbtree", ("data",), (16,), small)
    ring_s = cost.predict("ring", ("data",), (16,), small)
    assert tree_s.n_messages == 2 * 4      # ceil(log2 16) up + down
    assert ring_s.n_messages == 2 * 15
    assert tree_s.time_s < ring_s.time_s
    big = 64 * MB
    assert cost.predict("dbtree", ("data",), (16,), big).time_s > \
        cost.predict("ring", ("data",), (16,), big).time_s


def test_cost_table_sorted():
    rows = cost.predict_table(("pod", "data"), (2, 16), 50 * MB,
                              n_buckets=13)
    assert [r.time_s for r in rows] == sorted(r.time_s for r in rows)
    assert len(rows) == len(comm.available())


# ---------------------------------------- sharded-update cost accounting

def test_cost_reduce_scatter_is_half_the_ring_allreduce():
    """RS(g) stops halfway: (n-1) messages of B/n vs the ring's 2(n-1),
    and RS + AG of the same payload reproduces the full all-reduce."""
    ar = cost.predict("ring", ("data",), (16,), 50 * MB)
    rs = cost.predict_reduce_scatter("ring", ("data",), (16,), 50 * MB)
    ag = cost.predict_all_gather(("data",), (16,), 50 * MB)
    assert rs.n_messages == ag.n_messages == 15
    assert rs.wire_bytes == pytest.approx(ar.wire_bytes / 2)
    assert rs.time_s + ag.time_s == pytest.approx(ar.time_s)


def test_cost_reduce_scatter_fallbacks_cost_full_reduce():
    """psum/dbtree have no scatter decomposition: reduce-then-slice costs
    exactly the full all-reduce (the slice is free)."""
    for s in ("psum", "dbtree"):
        full = cost.predict(s, ("data",), (16,), 50 * MB)
        rs = cost.predict_reduce_scatter(s, ("data",), (16,), 50 * MB)
        assert rs.time_s == pytest.approx(full.time_s)
        assert rs.wire_bytes == pytest.approx(full.wire_bytes)


def test_cost_rs_hierarchical_cuts_cross_pod_traffic():
    """The RS-terminal hierarchical form still shrinks DCI traffic by the
    intra-axis size — the shard crosses pods, not the full buffer."""
    rs = cost.predict_reduce_scatter("hierarchical", ("pod", "data"),
                                     (2, 16), 50 * MB)
    flat = cost.predict_reduce_scatter("psum", ("pod", "data"), (2, 16),
                                       50 * MB)
    dci = lambda r: sum(p.wire_bytes for p in r.phases
                        if p.link.bw == cost.DCI.bw)
    assert dci(rs) < dci(flat) / 2


def test_cost_update_time_scales_with_shards():
    full = cost.lars_update_time_s(25_600_000, 1)
    shard = cost.lars_update_time_s(25_600_000, 16)
    assert shard == pytest.approx(full / 16)


def test_shard_update_predicted_strictly_below_allreduce_ring():
    """Acceptance: for the ring schedule at the autotuned bucket size, the
    sharded path's predicted comm+update step cost is strictly below the
    all-reduce path's, on both production meshes."""
    from repro.comm.autotune import autotune
    from repro.configs import get_config
    from repro.models.registry import build_model
    model = build_model(get_config("resnet50"))
    for axes, sizes in [(("data",), (16,)), (("pod", "data"), (2, 16))]:
        ar = autotune(model.param_pd, schedule="ring", axes=axes,
                      sizes=sizes, family="conv")
        sh = autotune(model.param_pd, schedule="ring", axes=axes,
                      sizes=sizes, family="conv", shard_update=True)
        assert sh.sim.mode == "shard_update+gather_ahead"
        assert ar.sim.mode == "allreduce"
        assert sh.sim.t_step_s < ar.sim.t_step_s, (axes, sh.sim, ar.sim)
        assert sh.sim.t_update_s < ar.sim.t_update_s


def test_gather_ahead_pricing_hides_the_gather():
    """On one fixed plan, gather_ahead=True only moves the param
    all-gather off the exposed path: same serialized comm and gather
    time, exposure/step time never worse — and when the gather fits under
    the forward window, exactly t_gather disappears from the exposure."""
    from repro.comm.autotune import simulate
    from repro.configs import get_config
    from repro.models.registry import build_model
    pd = build_model(get_config("resnet50")).param_pd
    plan = bucketing.make_plan(pd, bucket_mb=4.0, dtype_bytes=2)
    for axes, sizes in [(("data",), (16,)), (("pod", "data"), (2, 16))]:
        kw = dict(t_backward_s=5e-3, shard_update=True)
        end = simulate(plan, "ring", axes, sizes, gather_ahead=False, **kw)
        ga = simulate(plan, "ring", axes, sizes, gather_ahead=True, **kw)
        assert end.mode == "shard_update"
        assert ga.mode == "shard_update+gather_ahead"
        assert ga.t_gather_s == end.t_gather_s > 0
        assert ga.t_comm_s == pytest.approx(end.t_comm_s)
        assert ga.t_step_s <= end.t_step_s
        assert ga.t_exposed_s <= end.t_exposed_s
        if ga.t_gather_s <= 0.5 * kw["t_backward_s"]:  # fits under fwd
            assert end.t_exposed_s - ga.t_exposed_s == pytest.approx(
                ga.t_gather_s, rel=1e-6)


# ------------------------------------------------ shard-aware bucketing

def test_shard_segment_ids_cover_plan():
    """Every shard row is CHUNK-aligned and the concatenated rows cover the
    bucket's tensors in offset order (padding repeats the last id)."""
    tree = {f"t{i}": jnp.zeros((300 + 11 * i, 17)) for i in range(9)}
    plan = bucketing.make_plan(tree, bucket_mb=0.05)
    for n_shards in (1, 4, 8):
        maps = bucketing.shard_segment_ids(plan, n_shards)
        assert len(maps) == plan.n_buckets
        for b, m in enumerate(maps):
            c = bucketing.shard_elems(plan.bucket_sizes[b], n_shards)
            assert m.shape == (n_shards, c // bucketing.CHUNK)
            flat = m.reshape(-1)
            want = [ti for ti, s in enumerate(plan.slots) if s.bucket == b
                    for _ in range(s.padded // bucketing.CHUNK)]
            assert list(flat[:len(want)]) == want
            assert all(flat[len(want):] == want[-1])


def test_shard_layout_roundtrip():
    """rotate_to_shards/unrotate_shards invert each other, shard_sizes
    matches shard_elems, and init_packed_shards -> full_params_from_shards
    reproduces a ragged param tree exactly for every shard count."""
    from repro.train import state as st
    tree = {f"t{i}": jnp.arange(300 + 77 * i, dtype=jnp.float32)
                     .reshape(-1) + 0.5 * i for i in range(7)}
    plan = bucketing.make_plan(tree, bucket_mb=0.01)
    assert plan.n_buckets >= 2
    for n_shards in (1, 3, 8):
        sizes = bucketing.shard_sizes(plan, n_shards)
        assert sizes == tuple(bucketing.shard_elems(s, n_shards)
                              for s in plan.bucket_sizes)
        assert all(c % bucketing.CHUNK == 0 for c in sizes)
        buf = jnp.arange(plan.bucket_sizes[0], dtype=jnp.float32)
        rot = bucketing.rotate_to_shards(buf, n_shards)
        assert rot.shape == (n_shards * sizes[0],)
        back = bucketing.unrotate_shards(rot, n_shards)
        np.testing.assert_array_equal(back[:buf.shape[0]], buf)
        np.testing.assert_array_equal(back[buf.shape[0]:], 0)
        shards = st.init_packed_shards(tree, plan, n_shards)
        assert tuple(s.shape[0] // n_shards for s in shards) == sizes
        full = st.full_params_from_shards(shards, plan, n_shards)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                     tree, full)


def test_shard_rotation_matches_ring_ownership():
    """Global row r of the rotated layout holds chunk (r+1)%n — the chunk
    the device at shard-axis index r ends up owning after a ring
    reduce-scatter (primitives.shard_index)."""
    n = 4
    c = bucketing.CHUNK
    buf = jnp.arange(n * c, dtype=jnp.float32)
    rot = bucketing.rotate_to_shards(buf, n).reshape(n, c)
    for r in range(n):
        np.testing.assert_array_equal(
            rot[r], np.arange(((r + 1) % n) * c, ((r + 1) % n) * c + c))


def test_make_shard_sinks_match_rs_output_shapes():
    """The gradient sinks' shapes must equal the reduce-scatter-terminal
    schedules' per-bucket output shard (bucketing.shard_elems) so the
    custom-vjp cotangents line up."""
    tree = {f"t{i}": jnp.zeros((123 + 7 * i, 13)) for i in range(6)}
    plan = bucketing.make_plan(tree, bucket_mb=0.02)
    for n_shards in (1, 2, 8):
        sinks = ddp.make_shard_sinks(plan, n_shards)
        assert len(sinks) == plan.n_buckets
        for s, c in zip(sinks, bucketing.shard_sizes(plan, n_shards)):
            assert s.shape == (c,) and s.dtype == jnp.float32
            assert not np.asarray(s).any()


def test_trust_scaled_mask_matches_lars_rule():
    tree = {"w": jnp.zeros((4, 4)), "b": jnp.zeros((7,)),
            "s": jnp.zeros(()), "c": jnp.zeros((2, 3, 3, 4))}
    plan = bucketing.make_plan(tree)
    mask = bucketing.trust_scaled_mask(plan)
    by_path = {s.path: m for s, m in zip(plan.slots, mask)}
    assert by_path == {"w": True, "c": True, "b": False, "s": False}


def test_backward_times_interpolates_measured_profile():
    """A measured profile reshapes the per-group apportionment: with a
    curve where the first half of the volume takes 90% of the time, the
    early groups get most of the backward budget."""
    from repro.comm.autotune import BackwardProfile, backward_times
    tree = {f"t{i}": jnp.zeros((256, 256)) for i in range(8)}
    plan = bucketing.make_plan(tree, bucket_mb=0.25, dtype_bytes=2)
    assert plan.n_buckets == 4
    total = sum(plan.bucket_sizes)
    prof = BackwardProfile((total // 2, total), (0.9, 1.0))
    bt = backward_times(plan, 1.0, prof)
    assert sum(bt) == pytest.approx(1.0)
    half = sum(t for t, s in zip(bt, np.cumsum(plan.bucket_sizes))
               if s <= total // 2)
    assert half > 0.8
    flat = backward_times(plan, 1.0)
    assert sum(flat) == pytest.approx(1.0)
    assert max(flat) < max(bt)          # volume model is flatter


# ------------------------------------------- 1-device degenerate meshes

def _roundtrip_1dev(strategy):
    mesh = make_mesh((1,), ("data",))
    tree = {"w": jnp.arange(5000, dtype=jnp.float32),
            "b": jnp.ones((3,), jnp.float32)}
    plan = bucketing.make_plan(tree, bucket_mb=0.01)
    fn = lambda t: ddp.allreduce_grads(t, strategy=strategy, axes=("data",),
                                       plan=plan, comm_dtype=jnp.float32)
    spec = jax.tree.map(lambda _: P(), tree)
    out = jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec,),
                            out_specs=spec))(tree)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-7),
                 tree, out)


@pytest.mark.parametrize("strategy", ["naive", "bucketed", "psum", "ring",
                                      "hierarchical", "2d_torus", "dbtree"])
def test_schedules_identity_on_1_device(strategy):
    _roundtrip_1dev(strategy)


@pytest.mark.parametrize("strategy", ["bucketed", "ring", "dbtree"])
def test_overlap_identity_on_1_device(strategy):
    """The custom-vjp overlap wrap is grad-transparent on a trivial mesh."""
    mesh = make_mesh((1,), ("data",))
    tree = {"w": jnp.arange(5000, dtype=jnp.float32),
            "b": jnp.ones((3,), jnp.float32)}
    plan = bucketing.make_plan(tree, bucket_mb=0.01)

    def fn(t):
        def loss(p):
            p = ddp.wrap_params_for_overlap(p, plan, strategy=strategy,
                                            axes=("data",),
                                            comm_dtype=jnp.float32)
            return sum(jnp.sum(x * x) for x in jax.tree.leaves(p)) / 2
        return jax.grad(loss)(t)

    spec = jax.tree.map(lambda _: P(), tree)
    out = jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec,),
                            out_specs=spec))(tree)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6),
                 tree, out)      # d/dx (x^2/2) = x


# ------------------------------------------------------ ring-step kernel

def test_ring_add_step_matches_jnp():
    k = jax.random.PRNGKey(0)
    n, c = 4, 2 * bucketing.CHUNK
    chunks = jax.random.normal(k, (n, c), jnp.float32)
    recv = jax.random.normal(jax.random.fold_in(k, 1), (c,), jnp.float32)
    for idx in (0, 3):
        out = ring_add_step(recv, chunks, jnp.int32(idx), interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(recv + chunks[idx]),
                                   rtol=1e-6)


def test_ring_add_step_bf16():
    chunks = jnp.ones((2, bucketing.CHUNK), jnp.bfloat16)
    recv = jnp.full((bucketing.CHUNK,), 0.5, jnp.bfloat16)
    out = ring_add_step(recv, chunks, jnp.int32(1), interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), 1.5)


@pytest.mark.parametrize("n,length", [(2, 1000), (3, 5000), (4, 4096),
                                      (8, 33000)])
def test_ring_kernel_parity_ragged_buckets(n, length):
    """Interpret-mode parity of the Pallas ring-step fold against the jnp
    reference on RAGGED bucket lengths — the ``_as_chunks(pad_to=CHUNK)``
    zero-padded chunk view the ring schedules actually feed it — at every
    chunk index."""
    from repro.comm import primitives as prim
    from repro.comm.ring_kernel import kernel_step_fn
    key = jax.random.PRNGKey(17 * n + length)
    x = jax.random.normal(key, (length,), jnp.float32)
    chunks = prim._as_chunks(x, n, pad_to=bucketing.CHUNK)
    c = chunks.shape[1]
    assert c % bucketing.CHUNK == 0 and n * c >= length
    recv = jax.random.normal(jax.random.fold_in(key, 1), (c,), jnp.float32)
    step = kernel_step_fn(interpret=True)
    for k in range(n):
        got = step(recv, chunks, jnp.int32(k))
        want = prim.default_step_fn(recv, chunks, jnp.int32(k))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)


# ------------------------------------------------------------- bucketing

def test_pack_stages_f32_keeps_bf16_wire():
    tree = {"w": jnp.full((100,), 0.1, jnp.float32)}
    plan = bucketing.make_plan(tree)
    bufs = bucketing.pack(tree, plan, dtype=jnp.bfloat16)
    assert all(b.dtype == jnp.bfloat16 for b in bufs)
    back = bucketing.unpack(bufs, plan, dtype=jnp.float32)
    np.testing.assert_allclose(back["w"], 0.1, rtol=1e-2)  # bf16 eps


# ------------------------------------- 8-device equivalence (subprocess)

EQUIV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from repro import comm
from repro.core import bucketing, ddp
from repro.core.compat import axis_size, shard_map

def demo_tree(seed=0):
    # deterministic, deliberately ragged shapes (nothing CHUNK-aligned)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {
        "conv": jax.random.normal(ks[0], (7, 7, 3, 17)),
        "blocks": [{"w": jax.random.normal(ks[1], (33, 65)),
                    "b": jax.random.normal(ks[2], (65,))},
                   {"w": jax.random.normal(ks[3], (129, 31))}],
        "head": jax.random.normal(ks[4], (200, 99)),
        "scalar": jax.random.normal(ks[5], ()),
    }

tree = demo_tree()
plan = bucketing.make_plan(tree, bucket_mb=0.02)   # several ragged buckets
assert plan.n_buckets >= 3, plan.bucket_sizes
spec = jax.tree.map(lambda _: P(), tree)

for shape, axes in [((8,), ("data",)), ((2, 4), ("pod", "data"))]:
    mesh = make_mesh(shape, axes)

    def run(strategy, **kw):
        def fn(t):
            # device-dependent contributions so per-chunk bookkeeping
            # errors cannot cancel out
            r = jnp.float32(0)
            for a in axes:
                r = r * axis_size(a) + jax.lax.axis_index(a)
            t = jax.tree.map(lambda x: x * (1.0 + 0.1 * r), t)
            return ddp.allreduce_grads(t, strategy=strategy, axes=axes,
                                       plan=plan,
                                       comm_dtype=jnp.float32, **kw)
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec,),
                                 out_specs=spec))(tree)

    base = run("naive")
    for s in comm.available() + ["bucketed"]:
        out = run(s)
        md = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.abs(a - b).max()), base, out)))
        assert md <= 1e-6, (shape, s, md)
        print(f"OK {shape} {s} maxdiff={md:.1e}")

# Pallas ring-step kernel path (small: interpret-mode kernels are slow)
mesh = make_mesh((8,), ("data",))
ktree = {"w": jax.random.normal(jax.random.PRNGKey(9), (2048,))}
kplan = bucketing.make_plan(ktree)
kspec = {"w": P()}

def krun(strategy, **kw):
    def fn(t):
        r = jax.lax.axis_index("data")
        t = jax.tree.map(lambda x: x * (1.0 + 0.1 * r), t)
        return ddp.allreduce_grads(t, strategy=strategy, axes=("data",),
                                   plan=kplan, comm_dtype=jnp.float32, **kw)
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=(kspec,),
                             out_specs=kspec))(ktree)

kb = krun("naive")
ko = krun("ring", use_kernel=True, interpret=True)
np.testing.assert_allclose(np.asarray(ko["w"]), np.asarray(kb["w"]),
                           atol=1e-6)
print("OK kernel-ring")

# Overlap-aware scheduling (SIII-C.2): differentiating a loss of the
# wrapped params must reproduce naive psum grads exactly, with the bucket
# plan coming from the autotuner ('auto' acceptance path). Every schedule,
# both meshes.
from repro.comm.autotune import autotune

for shape, axes in [((8,), ("data",)), ((2, 4), ("pod", "data"))]:
    mesh = make_mesh(shape, axes)
    tuned = autotune(tree, schedule="psum", axes=axes,
                     sizes=shape, dtype_bytes=4,
                     candidates=(0.02, 0.05, 0.1))
    oplan = tuned.plan
    assert oplan.n_buckets >= 2, (tuned.bucket_mb, oplan.bucket_sizes)

    def rank(axes):
        r = jnp.float32(0)
        for a in axes:
            r = r * axis_size(a) + jax.lax.axis_index(a)
        return r

    def local_loss(p, r):
        s = jnp.float32(0)
        for leaf in jax.tree.leaves(p):
            x = leaf * (1.0 + 0.1 * r)
            s = s + jnp.sum(jnp.sin(x) * x)
        return s

    def overlap_run(strategy):
        def fn(t):
            r = rank(axes)
            def loss(p):
                p = ddp.wrap_params_for_overlap(
                    p, oplan, strategy=strategy, axes=axes,
                    comm_dtype=jnp.float32)
                return local_loss(p, r)
            return jax.grad(loss)(t)
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec,),
                                 out_specs=spec))(tree)

    def naive_run(t):
        r = rank(axes)
        g = jax.grad(lambda p: local_loss(p, r))(t)
        return ddp.allreduce_grads(g, strategy="naive", axes=axes,
                                   comm_dtype=jnp.float32)

    obase = jax.jit(shard_map(naive_run, mesh=mesh, in_specs=(spec,),
                              out_specs=spec))(tree)
    for s in comm.available() + ["bucketed"]:
        out = overlap_run(s)
        md = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.abs(a - b).max()), obase, out)))
        assert md <= 1e-6, (shape, s, md)
        print(f"OK overlap {shape} {s} maxdiff={md:.1e}")
print("COMM-OK")
"""


@pytest.mark.tier2
def test_all_schedules_match_naive_8dev():
    """Acceptance: every registered schedule (+ the bucketed alias and the
    Pallas ring-step path) reproduces the naive psum gradients to <=1e-6
    fp32 on 8 host devices, on both a flat and a (pod, data) mesh — both
    post-backward (allreduce_grads) and overlap-aware (collectives issued
    inside the backward via wrap_params_for_overlap, bucket plan resolved
    by the autotuner)."""
    r = subprocess.run([sys.executable, "-c", EQUIV_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src"})
    assert "COMM-OK" in r.stdout, (r.stdout[-1000:], r.stderr[-3000:])


# --------------------------- ZeRO-1 sharded update (subprocess, 8 devices)

def _run_side_by_side(script, argvs, *, timeout):
    """Run ``script`` on 8 host devices once per argument list, all at
    once; returns each run's (stdout, stderr)."""
    procs = [subprocess.Popen([sys.executable, "-c", script, *argv],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True,
                              env={**os.environ, "PYTHONPATH": "src"})
             for argv in argvs]
    try:
        return [p.communicate(timeout=timeout) for p in procs]
    finally:
        for p in procs:
            p.kill()


SHARD_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
from repro import comm
from repro.core import bucketing, ddp, lars
from repro.core.compat import axis_size, shard_map
from repro.train import state as st

# ---- part A: update-level equivalence, every schedule, both meshes ----
# Persistent-shard path vs replicated path with the SAME schedule (so
# collective summation order matches and the comparison isolates the
# sharding machinery: RS-terminal form, persistent rotated master shards,
# psum'd partial norms, packed from-shards update, momentum shards, param
# all-gather). fp32 wire.

ks = jax.random.split(jax.random.PRNGKey(0), 6)
tree = {
    "conv": jax.random.normal(ks[0], (7, 7, 3, 17)),
    "blocks": [{"w": jax.random.normal(ks[1], (33, 65)),
                "b": jax.random.normal(ks[2], (65,))},
               {"w": jax.random.normal(ks[3], (129, 31))}],
    "head": jax.random.normal(ks[4], (200, 99)),
    "scalar": jax.random.normal(ks[5], ()),
}
plan = bucketing.make_plan(tree, bucket_mb=0.02)
assert plan.n_buckets >= 3, plan.bucket_sizes
spec = jax.tree.map(lambda _: P(), tree)
opt = lars.OptConfig(kind="lars")
STEPS = 2                       # second step exercises the momentum state

def rank(axes):
    r = jnp.float32(0)
    for a in axes:
        r = r * axis_size(a) + jax.lax.axis_index(a)
    return r

# the ((8, 1), ("data", "model")) mesh is the regression mesh: a trailing
# size-1 axis must not change which axis the hierarchical/2d_torus
# schedules scatter over (shard_axis = innermost NON-trivial), or the AR
# and RS-terminal forms sum in different orders and drift apart
# one mesh per process (argv[1] indexes MESHES), so the three can compile
# side by side on separate cores
MESHES = [((8,), ("data",)), ((2, 4), ("pod", "data")),
          ((8, 1), ("data", "model"))]
MESHES = [MESHES[int(i)] for i in sys.argv[1:]] or MESHES

for shape, axes in MESHES:
    mesh = make_mesh(shape, axes)
    n_sh = shape[axes.index("data")]
    sspec = tuple(P("data") for _ in range(plan.n_buckets))

    def repl(strategy):
        def fn(t, mom):
            g = jax.tree.map(lambda x: x * (1.0 + 0.1 * rank(axes)), t)
            g = ddp.allreduce_grads(g, strategy=strategy, axes=axes,
                                    plan=plan, comm_dtype=jnp.float32)
            return lars.update(t, g, mom, 0.1, opt)
        f = jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec, spec),
                              out_specs=(spec, spec)))
        p, m = tree, jax.tree.map(jnp.zeros_like, tree)
        for _ in range(STEPS):
            p, m = f(p, m)
        return p

    def shard(strategy, **kw):
        def fn(t, shards, mom):
            g = jax.tree.map(lambda x: x * (1.0 + 0.1 * rank(axes)), t)
            gs = ddp.reduce_scatter_grads(g, strategy=strategy, axes=axes,
                                          plan=plan,
                                          comm_dtype=jnp.float32)
            ps, ms = lars.sharded_update_from_shards(
                list(shards), gs, list(mom), 0.1, opt, plan,
                shard_axis="data", n_shards=n_sh, **kw)
            p2 = ddp.all_gather_params(ps, plan, shard_axis="data",
                                       wire_dtype=jnp.float32)
            return p2, ps, ms
        f = jax.jit(shard_map(fn, mesh=mesh,
                              in_specs=(spec, sspec, sspec),
                              out_specs=(spec, sspec, sspec)))
        p = tree
        shards = st.init_packed_shards(tree, plan, n_sh)
        m = st.init_packed_momentum(plan, n_sh)
        for _ in range(STEPS):
            p, shards, m = f(p, shards, m)
        # the persistent shards ARE the masters: the f32-wire gather and
        # the host-side unrotate/unpack must agree exactly
        full = st.full_params_from_shards(shards, plan, n_sh)
        md = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.abs(a - b).max()), p, full)))
        assert md == 0.0, ("shards vs gather", strategy, md)
        return p

    for s in comm.available() + ["bucketed"]:
        base, got = repl(s), shard(s)
        md = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.abs(a - b).max()), base, got)))
        assert md <= 1e-6, (shape, s, md)
        print(f"OK shard-update {shape} {s} maxdiff={md:.1e}")
    if shape == (8,):   # fused Pallas update kernel (interpret mode)
        got = shard("ring", update_kernel=True)
        base = repl("ring")
        md = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.abs(a - b).max()), base, got)))
        assert md <= 1e-6, ("update_kernel", md)
        print(f"OK shard-update kernel maxdiff={md:.1e}")

# ---- part B: in-backward RS == post-backward RS, per schedule/mesh ----
# Differentiating a loss of sink-wrapped params (the gradient-sink
# custom-vjp that plants each bucket's reduce-scatter inside the backward)
# must hand back exactly the shards reduce_scatter_grads produces after
# the backward — the tentpole mechanism in isolation.

def local_loss(p, r):
    s = jnp.float32(0)
    for leaf in jax.tree.leaves(p):
        x = leaf * (1.0 + 0.1 * r)
        s = s + jnp.sum(jnp.sin(x) * x)
    return s

for shape, axes in MESHES:
    mesh = make_mesh(shape, axes)
    n_sh = shape[axes.index("data")]
    sspec = tuple(P("data") for _ in range(plan.n_buckets))

    def in_backward(strategy):
        def fn(t):
            r = rank(axes)
            sinks = ddp.make_shard_sinks(plan, n_sh)
            def loss(sk, p):
                p = ddp.wrap_params_for_overlap(
                    p, plan, strategy=strategy, axes=axes,
                    comm_dtype=jnp.float32, shard_sinks=sk)
                return local_loss(p, r)
            return jax.grad(loss)(sinks, t)
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec,),
                                 out_specs=sspec))(tree)

    def post_backward(strategy):
        def fn(t):
            r = rank(axes)
            g = jax.grad(lambda p: local_loss(p, r))(t)
            return tuple(ddp.reduce_scatter_grads(
                g, strategy=strategy, axes=axes, plan=plan,
                comm_dtype=jnp.float32))
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=(spec,),
                                 out_specs=sspec))(tree)

    for s in comm.available() + ["bucketed"]:
        a, b = in_backward(s), post_backward(s)
        md = max(float(jnp.abs(x - y).max()) for x, y in zip(a, b))
        assert md <= 1e-6, (shape, s, md)
        print(f"OK in-bwd-rs {shape} {s} maxdiff={md:.1e}")
print("SHARD-OK")
"""


@pytest.mark.tier2
def test_shard_update_matches_replicated_8dev():
    """Acceptance: the persistent-shard ZeRO-1 update (reduce-scatter +
    packed LARS on the local shard straight from ``TrainState``-style
    shard buffers + param all-gather, sharded momentum) matches the
    same-schedule replicated update to <=1e-6 fp32 over two steps on 8
    host devices — every registered schedule + the bucketed alias on
    flat, (pod, data), and trailing-trivial-axis (data, model=1) meshes
    (the last is the shard_axis regression mesh), plus the fused Pallas
    update kernel — and the in-backward gradient-sink reduce-scatter
    hands back exactly the post-backward ``reduce_scatter_grads`` shards
    for every schedule on all three meshes."""
    for out, err in _run_side_by_side(SHARD_SCRIPT, [["0"], ["1"], ["2"]],
                                      timeout=900):
        assert "SHARD-OK" in out, (out[-2000:], err[-3000:])


# ------------- fully-overlapped ZeRO-1 train-step equivalence matrix
# (subprocess per mesh: 2 real ResNet steps, in-backward RS + gather-ahead
# vs the same-schedule replicated fp32 oracle, every registered schedule)

SHARD_STEP_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro import comm
from repro.configs import get_config
from repro.configs.base import CommConfig
from repro.configs.shapes import InputShape
from repro.core import lars
from repro.core.schedule import ScheduleConfig, make_schedule
from repro.data.synthetic import make_batch_fn
from repro.models.registry import build_model
from repro.train import state as st
from repro.train.step import make_train_step

MESH = sys.argv[1]
mesh = (make_mesh((8, 1), ("data", "model")) if MESH == "flat"
        else make_mesh((2, 4), ("pod", "data")))
cfg = get_config("resnet50").reduced()
model = build_model(cfg)
sched = make_schedule(ScheduleConfig(base_lr=0.1, warmup_steps=1,
                                     total_steps=10))
# batch 8 / 1 MB buckets: every run is a full ResNet-50 graph compile on
# the 8-device CPU mesh (~70 s each), so the matrix trims what it can
# without losing coverage — still 8 bucket groups on the reduced model
bf = make_batch_fn(cfg, InputShape("t", "train", 0, 8), mesh=mesh)

def run(comm_cfg):
    step = make_train_step(model, lars.OptConfig(kind="lars"), sched,
                           mesh=mesh, comm=comm_cfg)
    sharded = step.sharding != "replicated"
    if sharded:
        # the policy wiring must be active: RS issued from inside the
        # backward, the param gather at the policy's issue point — and
        # the deprecated boolean views must agree with the enum pair
        assert step.sharding == comm_cfg.sharding
        assert step.gather == comm_cfg.gather
        assert step.overlap == comm_cfg.overlap
        assert step.shard_update is True
        assert step.gather_ahead == (step.gather == "ahead"
                                     and step.sharding == "zero1")
    s = st.init_state(model, 0, mesh,
                      sharded_plan=step.bucket_plan if sharded else None,
                      n_shards=step.n_shards if sharded else 1,
                      materialize_params=step.sharding != "zero3",
                      shard_params=step.sharding != "zero2")
    f = jax.jit(step)
    for _ in range(2):
        s, m = f(s, bf(s.step))
    if step.sharding == "zero3":
        # ZeRO-3 contract: no persistent full replica, before or after
        assert s.params is None, "zero3 state rematerialized params"
    if step.sharding == "zero2":
        # ZeRO-2 contract: the replicated params ARE the masters — no
        # shard field ever materializes
        assert s.shards is None, "zero2 state grew master shards"
        return s, m, s.params
    if sharded:
        # authoritative masters live in the persistent shards
        full = st.full_params_from_shards(s.shards, step.bucket_plan,
                                          step.n_shards)
        return s, m, full
    return s, m, s.params

# ('bucketed' = psum alias: exercised at the update level in SHARD_SCRIPT,
# not worth two more ResNet compiles here)
schedules = comm.available()
assert "ring" in schedules          # the oracle of every non-schedule cell

# The cells, in order; this process runs those whose index is PART modulo
# NPARTS, so the parts can compile side by side on separate cores.
PART, NPARTS = int(sys.argv[2]), int(sys.argv[3])
cells = [(s, s, CommConfig(strategy=s, bucket_mb=1.0, wire_dtype="f32",
                           shard_update=True)) for s in schedules]
if MESH == "flat":
    # autotuned plan, Pallas update kernel, end-of-step gather issue point
    cells += [
        ("ring/auto", "ring",
         CommConfig(strategy="ring", bucket_mb="auto", wire_dtype="f32",
                    shard_update=True)),
        ("ring/kernel", "ring",
         CommConfig(strategy="ring", bucket_mb=1.0, wire_dtype="f32",
                    shard_update=True, update_kernel=True)),
        ("ring/gather-at-end", "ring",
         CommConfig(strategy="ring", bucket_mb=1.0, wire_dtype="f32",
                    shard_update=True, gather_ahead=False)),
    ]
# ZeRO-3 cells. The jit-gather machinery is schedule-independent (the
# per-group AG is prim.ring_all_gather regardless of the RS schedule, and
# the RS side is exactly the per-schedule-verified ZeRO-1 path), so one
# per-group cell per mesh covers it; flat adds the retained-gather and
# non-overlapped variants
cells.append(("zero3/per_group", "ring",
              CommConfig(strategy="ring", bucket_mb=1.0, wire_dtype="f32",
                         sharding="zero3")))
if MESH == "flat":
    cells += [
        ("zero3/retain", "ring",
         CommConfig(strategy="ring", bucket_mb=1.0, wire_dtype="f32",
                    sharding="zero3", gather="ahead")),
        ("zero3/no-overlap", "ring",
         CommConfig(strategy="ring", bucket_mb=1.0, wire_dtype="f32",
                    sharding="zero3", overlap=False)),
    ]
# ZeRO-2 + split-leaf cells (flat mesh). 0.25 MB f32 buckets split 7 of
# the reduced ResNet's conv leaves across bucket boundaries, so the
# split-aware packing, the tensor-id segment maps (LARS trust from
# cross-bucket partial norms), the chained in-backward collectives, and
# zero3's piece-wise jit gather all sit on the verified <=1e-6 path
if MESH == "flat":
    cells += [
        ("zero2", "ring",
         CommConfig(strategy="ring", bucket_mb=1.0, wire_dtype="f32",
                    sharding="zero2")),
        ("zero2-split", "ring",
         CommConfig(strategy="ring", bucket_mb=0.25, wire_dtype="f32",
                    sharding="zero2")),
        ("zero3-split", "ring",
         CommConfig(strategy="ring", bucket_mb=0.25, wire_dtype="f32",
                    sharding="zero3")),
    ]

oracles = {}

def oracle(strategy):
    # the same-schedule replicated fp32 run a sharded cell must match
    if strategy not in oracles:
        oracles[strategy] = run(CommConfig(strategy=strategy, bucket_mb=1.0,
                                           wire_dtype="f32"))
    return oracles[strategy]

for tag, strategy, cc in cells[PART::NPARTS]:
    if "split" in tag:
        import repro.core.bucketing as _bk
        _plan = _bk.make_plan(model.param_pd, bucket_mb=0.25, dtype_bytes=4)
        assert any(sl.elem_offset for sl in _plan.slots), \
            "split cell does not split any leaf"
    base_s, base_m, base_p = oracle(strategy)
    sh_s, sh_m, sh_p = run(cc)
    md = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()), base_p, sh_p)))
    ml = abs(float(base_m["loss"]) - float(sh_m["loss"]))
    assert md <= 1e-6 and ml <= 1e-6, (MESH, tag, md, ml)
    if tag == "ring/gather-at-end":
        # without gather-ahead the state's params copy is fresh (the
        # step-end gather): it must equal the shards exactly (f32 wire)
        pd = max(jax.tree.leaves(jax.tree.map(
            lambda a, b: float(jnp.abs(a - b).max()), sh_s.params, sh_p)))
        assert pd == 0.0, pd
    print(f"OK shard-step {MESH} {tag} maxdiff={md:.1e}")
print(f"STEP-MATRIX-OK {len(cells[PART::NPARTS])} of {len(cells)}")
"""


@pytest.mark.tier2
@pytest.mark.parametrize("mesh_tag", ["flat", "pod"])
def test_sharded_step_matrix_8dev(mesh_tag):
    """Acceptance matrix: two real ResNet train steps with the fully
    overlapped ZeRO-1 path (in-backward reduce-scatter via gradient
    sinks, persistent master shards, gather-ahead param all-gather) match
    the same-schedule replicated fp32 oracle to <=1e-6 — every registered
    schedule + the bucketed alias, on the flat 8-device and the
    (pod, data) production-shaped mesh, plus (flat) ``bucket_mb='auto'``,
    the Pallas ``lars_update`` kernel path, and the end-of-step gather
    issue point. The ZeRO-3 cells (per_group on both meshes; retained
    gather and non-overlapped on flat) hold the same <=1e-6 bar with NO
    persistent param replica — ``state.params is None`` throughout, the
    forward all-gathering each bucket group just-in-time and the
    per_group backward re-gathering via rematerialization. The flat mesh
    adds the ZeRO-2 middle rung (replicated fp32 masters, sharded
    grad+optimizer lifetimes, fp32 step-end write-back) and the
    split-leaf cells (0.25 MB buckets split 7 conv leaves across bucket
    boundaries) for both zero2 and zero3, all on the same <=1e-6 bar.
    Slow: every cell is a full ResNet compile on the 8-device CPU mesh
    (~45 s each; 19 compiles flat and 11 pod in one process), so the
    cells are split over concurrent subprocesses (5 flat, 3 pod), each
    compiling the oracles its cells need."""
    n_parts = 5 if mesh_tag == "flat" else 3
    outs = _run_side_by_side(
        SHARD_STEP_SCRIPT,
        [[mesh_tag, str(i), str(n_parts)] for i in range(n_parts)],
        timeout=2700)
    cells = 0
    for out, err in outs:
        assert "STEP-MATRIX-OK" in out, (out[-2000:], err[-3000:])
        cells += int(out.split("STEP-MATRIX-OK ")[1].split()[0])
    assert cells == int(out.split(" of ")[-1]), outs


# ------------------------------------------------------------- autotuner

def test_autotune_serialized_comm_monotone_in_bucket_count():
    """More buckets = more messages on the same bytes: with overlap
    disabled (t_backward=0) predicted comm time never improves as the
    bucket count grows."""
    from repro.comm import autotune as at
    tree = {f"t{i}": jnp.zeros((256, 256)) for i in range(24)}
    prev_nb, prev_t = None, None
    for mb in (8.0, 4.0, 2.0, 1.0, 0.5, 0.25):
        plan = bucketing.make_plan(tree, bucket_mb=mb, dtype_bytes=2)
        sim = at.simulate(plan, "ring", ("data",), (16,), t_backward_s=0.0)
        if prev_nb is not None and plan.n_buckets > prev_nb:
            assert sim.t_comm_s >= prev_t, (mb, sim.t_comm_s, prev_t)
        prev_nb, prev_t = plan.n_buckets, sim.t_comm_s


def test_autotune_overlap_only_helps():
    """Overlap can only hide comm: exposed <= serialized comm, eff in
    [0, 1], and a longer backward window never increases the exposure."""
    from repro.comm import autotune as at
    tree = {f"t{i}": jnp.zeros((512, 512)) for i in range(16)}
    plan = bucketing.make_plan(tree, bucket_mb=1.0)
    prev = None
    for tb in (0.0, 1e-4, 1e-3, 1e-2):
        sim = at.simulate(plan, "ring", ("data",), (16,), t_backward_s=tb)
        assert 0.0 <= sim.t_exposed_s <= sim.t_comm_s + 1e-12
        assert 0.0 <= sim.overlap_eff <= 1.0
        if prev is not None:
            assert sim.t_exposed_s <= prev + 1e-12
        prev = sim.t_exposed_s


def test_autotune_resolves_for_every_registered_config():
    """'auto' must produce a valid plan for every config in the pool, on
    both production meshes."""
    from repro.comm import autotune as at
    from repro.configs import ALL_ARCHS, get_config
    from repro.models.registry import build_model
    for arch in ALL_ARCHS:
        cfg = get_config(arch).reduced()
        pd = build_model(cfg).param_pd
        for axes, sizes in [(("data",), (16,)),
                            (("pod", "data"), (2, 16))]:
            t = at.best_plan(pd, axes=axes, sizes=sizes, family=cfg.family)
            assert t.bucket_mb in at.CANDIDATES_MB, (arch, t.bucket_mb)
            assert t.plan.n_tensors == len(jax.tree.leaves(pd))
            assert t.plan.n_buckets >= 1
            assert 0.0 <= t.sim.overlap_eff <= 1.0
            assert t.schedule in comm.available()


def test_shard_update_train_step_1_device():
    """The ZeRO-1 step degenerates cleanly on a trivial mesh (n_shards=1:
    the 'shard' is the whole buffer, collectives are identities)."""
    from repro.configs import get_config
    from repro.configs.base import CommConfig
    from repro.core import lars
    from repro.core.schedule import ScheduleConfig, make_schedule
    from repro.data.synthetic import make_batch_fn
    from repro.configs.shapes import InputShape
    from repro.models.registry import build_model
    from repro.train import state as st
    from repro.train.step import make_train_step

    cfg = get_config("resnet50").reduced()
    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    sched = make_schedule(ScheduleConfig(base_lr=0.1, warmup_steps=1,
                                         total_steps=4))
    step = make_train_step(model, lars.OptConfig(kind="lars"), sched,
                           mesh=mesh,
                           comm=CommConfig(strategy="ring", bucket_mb=0.25,
                                           wire_dtype="f32",
                                           shard_update=True))
    assert step.shard_update and step.n_shards == 1
    assert step.overlap and step.gather_ahead     # the default wiring
    s = st.init_state(model, 0, sharded_plan=step.bucket_plan, n_shards=1)
    assert len(s.mom) == step.bucket_plan.n_buckets
    assert len(s.shards) == step.bucket_plan.n_buckets
    bf = make_batch_fn(cfg, InputShape("t", "train", 0, 8), mesh=mesh)
    init_params = s.params
    s, m = jax.jit(step)(s, bf(s.step))
    assert np.isfinite(float(m["loss"]))
    # gather-ahead staleness semantics: params is the copy the forward ran
    # on (= the f32-wire gather of the pre-update shards, i.e. the initial
    # params), while the persistent shards carry the updated masters
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 init_params, s.params)
    full = st.full_params_from_shards(s.shards, step.bucket_plan, 1)
    diffs = jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a - b).max()), init_params, full))
    assert max(diffs) > 0.0     # the update actually moved the masters


def test_train_step_resolves_auto_bucket_mb():
    """CommConfig(bucket_mb='auto') builds and runs a real train step."""
    from repro.configs import get_config
    from repro.configs.base import CommConfig
    from repro.core import lars
    from repro.core.schedule import ScheduleConfig, make_schedule
    from repro.data.synthetic import make_batch_fn
    from repro.configs.shapes import InputShape
    from repro.models.registry import build_model
    from repro.train import state as st
    from repro.train.step import make_train_step

    cfg = get_config("resnet50").reduced()
    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    sched = make_schedule(ScheduleConfig(base_lr=0.1, warmup_steps=1,
                                         total_steps=4))
    step = make_train_step(model, lars.OptConfig(kind="lars"), sched,
                           mesh=mesh,
                           comm=CommConfig(strategy="bucketed",
                                           bucket_mb="auto"))
    assert isinstance(step.bucket_mb, float) and step.overlap
    assert step.tuned is not None and step.tuned.bucket_mb == step.bucket_mb
    bf = make_batch_fn(cfg, InputShape("t", "train", 0, 8), mesh=mesh)
    s = st.init_state(model, 0)
    s, m = jax.jit(step)(s, bf(s.step))
    assert np.isfinite(float(m["loss"]))


def test_comm_config_validates_bucket_mb():
    from repro.configs.base import CommConfig
    CommConfig(bucket_mb="auto")
    CommConfig(shard_update=True, update_kernel=True,
               backward_profile="measured")
    with pytest.raises(AssertionError):
        CommConfig(bucket_mb="foo")
    with pytest.raises(AssertionError):
        CommConfig(bucket_mb=-1.0)
    with pytest.raises(AssertionError):
        CommConfig(backward_profile="guessed")


def test_bucket_plan_groups_metadata():
    """Group boundaries cover every slot once, in packing order."""
    tree = {f"t{i}": jnp.zeros((300 + i, 17)) for i in range(9)}
    plan = bucketing.make_plan(tree, bucket_mb=0.05)
    groups = plan.groups
    assert len(groups) == plan.n_buckets
    flat = [s for g in groups for s in g]
    assert flat == list(plan.slots)
    for b, g in enumerate(groups):
        assert all(s.bucket == b for s in g)
        assert sum(s.padded for s in g) == plan.bucket_sizes[b]
    assert plan.bucket_bytes(2) == tuple(2 * s for s in plan.bucket_sizes)


# ------------------------------------------------- sharding= policy API

def test_resolve_policy_maps_booleans_and_defaults():
    """The single resolution point for the enum pair: old booleans map to
    their enum spellings; gather defaults per level."""
    from repro.comm.autotune import resolve_policy
    assert resolve_policy(None, None) == ("replicated", "ahead")
    assert resolve_policy(None, None, shard_update=True) == \
        ("zero1", "ahead")
    assert resolve_policy(None, None, shard_update=True,
                          gather_ahead=False) == ("zero1", "at_end")
    assert resolve_policy("zero3", None) == ("zero3", "per_group")
    assert resolve_policy("zero3", "ahead") == ("zero3", "ahead")
    assert resolve_policy("zero1", None) == ("zero1", "ahead")
    assert resolve_policy("zero2", None) == ("zero2", "at_end")


def test_comm_config_zero2_rejects_gather_ahead():
    """zero2 keeps the replica live through the forward, so there is no
    next-step gather to move ahead — 'ahead' is a config error, not a
    silent no-op."""
    from repro.configs.base import CommConfig
    cc = CommConfig(strategy="ring", bucket_mb=1.0, sharding="zero2")
    assert (cc.sharding, cc.gather) == ("zero2", "at_end")
    with pytest.raises(ValueError):
        CommConfig(strategy="ring", bucket_mb=1.0, sharding="zero2",
                   gather="ahead")


def test_comm_config_boolean_shims_warn_and_resolve_identically():
    """CommConfig(shard_update=True) must resolve — with a
    DeprecationWarning — to exactly CommConfig(sharding='zero1'), and
    gather_ahead=False to gather='at_end' (the acceptance bar: old
    spellings stay bit-identical)."""
    from repro.configs.base import CommConfig
    with pytest.warns(DeprecationWarning):
        old = CommConfig(strategy="ring", bucket_mb=1.0, shard_update=True)
    new = CommConfig(strategy="ring", bucket_mb=1.0, sharding="zero1")
    assert old == new
    assert (old.sharding, old.gather) == ("zero1", "ahead")
    assert old.shard_update is True and old.gather_ahead is True
    with pytest.warns(DeprecationWarning):
        old = CommConfig(strategy="ring", bucket_mb=1.0, shard_update=True,
                         gather_ahead=False)
    assert old == CommConfig(strategy="ring", bucket_mb=1.0,
                             sharding="zero1", gather="at_end")
    assert old.gather_ahead is False
    # the default stays fully replicated, no warning
    cc = CommConfig(strategy="ring", bucket_mb=1.0)
    assert (cc.sharding, cc.gather) == ("replicated", "ahead")
    assert cc.shard_update is False
    # conflicts are errors, not silent precedence
    with pytest.raises(ValueError):
        CommConfig(sharding="replicated", shard_update=True)
    with pytest.raises(ValueError):
        CommConfig(sharding="zero1", gather="ahead", gather_ahead=False)
    with pytest.raises(ValueError):
        CommConfig(sharding="mirrored")
    with pytest.raises(ValueError):
        CommConfig(sharding="zero3", gather="at_end")   # no step-end form


def test_zero3_simulate_modes_and_pricing():
    """The cost model's ZeRO-3 timelines: mode names, the forward gather
    pricing, and the per_group remat double-charge vs retain."""
    from repro.comm.autotune import simulate
    tree = {f"t{i}": jnp.zeros((160, 128)) for i in range(10)}
    plan = bucketing.make_plan(tree, bucket_mb=0.1)
    assert plan.n_buckets > 2
    kw = dict(schedule="ring", axes=("data",), sizes=(16,),
              t_backward_s=5e-3, t_forward_s=2.5e-3)
    z1 = simulate(plan, sharding="zero1", **kw)
    z3 = simulate(plan, sharding="zero3", gather="per_group", **kw)
    z3r = simulate(plan, sharding="zero3", gather="ahead", **kw)
    assert z1.mode == "shard_update+gather_ahead"
    assert z3.mode == "zero3_jit_gather"
    assert z3r.mode == "zero3_retain"
    # same AG volume: retain gathers once, per_group re-gathers in the
    # remat backward — exactly double
    assert z3.t_gather_s == pytest.approx(2 * z3r.t_gather_s)
    assert z3r.t_gather_s == pytest.approx(z1.t_gather_s)
    # retain can only be <= per_group (no re-gather, unstretched backward)
    assert z3r.t_step_s <= z3.t_step_s
    # the RS side is the shared zero1 machinery: identical update time
    assert z3.t_update_s == pytest.approx(z1.t_update_s)


def test_param_memory_accounting_clears_the_floor():
    """Peak-live-param-bytes accounting (``cost.param_memory``): zero1
    keeps the 4N fp32 replica plus the full wire image (every bucket
    buffer is live until the single tree-wide unpack in
    ``ddp.all_gather_params``); zero3 keeps one group's wire bucket plus
    its fp32 tensors. On ResNet-50 @ 1 MB buckets the reduction clears
    the (n-1)/n floor at n=8 — the shard count the 8-device equivalence
    matrix actually runs — and is n-independent."""
    from repro.configs import get_config
    from repro.models.registry import build_model

    model = build_model(get_config("resnet50"))
    plan = bucketing.make_plan(model.param_pd, bucket_mb=1.0)
    rep = cost.param_memory(plan, 8, sharding="replicated")
    z1 = cost.param_memory(plan, 8, sharding="zero1")
    z2 = cost.param_memory(plan, 8, sharding="zero2")
    z3 = cost.param_memory(plan, 8, sharding="zero3")
    assert rep.peak_bytes == 0           # baseline: the replica itself
    # the wire/transient image is the PADDED sharded layout
    # (n * shard_elems per bucket), not the raw bucket size — the bug the
    # padded_bucket_elems fix closes
    padded = cost.padded_bucket_elems(plan, 8)
    assert all(p >= b for p, b in zip(padded, plan.bucket_sizes))
    n_unpadded = sum(plan.group_elems)
    assert z1.persistent_bytes == 4 * n_unpadded
    assert z1.transient_bytes == 2 * sum(padded)
    # zero2 keeps the 4N replica persistent and pays the fp32 wire image
    assert z2.persistent_bytes == 4 * n_unpadded
    assert z2.transient_bytes == 4 * sum(padded)
    assert z3.persistent_bytes == 0
    # the 2M-elem fc kernel splits at 1 MB buckets; under the default
    # span-streaming accounting the peak is still per-group — splitting
    # is exactly what keeps it near the bucket budget
    assert any(s.elem_offset for s in plan.slots)
    assert cost._zero3_live_elems(plan) == plan.group_elems
    assert z3.peak_bytes == max(
        2 * b + 4 * g for b, g in zip(padded, plan.group_elems))
    red = cost.param_memory_reduction(plan, 8)
    assert red == pytest.approx(1 - z3.peak_bytes / z1.peak_bytes)
    assert red >= 7 / 8, f"zero3 peak-param reduction {red:.4f} < 7/8"
    # near-n-independence: only the CHUNK-level shard padding varies with
    # n, a vanishing fraction of the 25M-param plan
    assert cost.param_memory_reduction(plan, 16) == pytest.approx(red,
                                                                  rel=1e-2)


def test_param_memory_padding_regression():
    """Satellite regression for ``padded_bucket_elems``: a bucket whose
    size is NOT divisible by n_shards*CHUNK costs ``n * shard_elems``
    wire bytes — each device sends/receives its padded chunk — which is
    strictly more than the raw bucket size the old accounting charged."""
    tree = {"a": jnp.zeros((3 * bucketing.CHUNK + 7,)),
            "b": jnp.zeros((5, 5))}
    plan = bucketing.make_plan(tree, bucket_mb=1.0)
    n = 8
    padded = cost.padded_bucket_elems(plan, n)
    for p, b in zip(padded, plan.bucket_sizes):
        assert p == n * bucketing.shard_elems(b, n)
        assert p % (n * bucketing.CHUNK) == 0
    # 5 CHUNKs over 8 shards pad up to 8 CHUNKs — visible, not epsilon
    assert padded[0] > plan.bucket_sizes[0]
    z1 = cost.param_memory(plan, n, sharding="zero1")
    assert z1.transient_bytes == 2 * sum(padded)
    assert z1.transient_bytes > 2 * sum(plan.bucket_sizes)


def test_param_memory_split_leaf_bounds():
    """zero3 live accounting on a split leaf, both consumer models. The
    default (span-streaming) bound is per-group — splitting caps it near
    the bucket budget, so the reduction clears (n-1)/n on a giant-leaf
    tree; ``streaming_spans=False`` prices the assembled-tensor consumer,
    where a span's bucket also retains every EARLIER-gathered span of the
    same tensor (the whole tensor only dies once assembled) and the floor
    is the widest leaf."""
    chunk = bucketing.CHUNK
    tree = {"giant": jnp.zeros((12 * chunk, 3)),
            "small": jnp.zeros((64, 8))}
    mb = 4 * chunk * 2 / 2**20           # 4-CHUNK bucket budget (bf16)
    plan = bucketing.make_plan(tree, bucket_mb=mb, dtype_bytes=2)
    assert any(s.elem_offset for s in plan.slots)
    # default: streaming — live IS the per-group elems, and param_memory
    # uses it
    assert cost._zero3_live_elems(plan) == plan.group_elems
    z3 = cost.param_memory(plan, 8, sharding="zero3")
    padded = cost.padded_bucket_elems(plan, 8)
    assert z3.peak_bytes == max(2 * b + 4 * g for b, g in
                                zip(padded, plan.group_elems))
    spans = [s for s in plan.slots if s.path == "giant"]
    assert len(spans) > 2
    # assembled consumer: gather walks groups in DESCENDING bucket order
    # (forward order), so within the span chain the highest-bucket span
    # is gathered first and each lower bucket retains the suffix gathered
    # before it
    live = cost._zero3_live_elems(plan, streaming_spans=False)
    for i, s in enumerate(spans):
        suffix = sum(t.size for t in spans[i + 1:])
        assert live[s.bucket] >= plan.group_elems[s.bucket] + suffix - \
            s.size  # its own size is already in group_elems
    # the last-assembled span's bucket holds ~the whole tensor live
    assert max(live) >= sum(s.size for s in spans)
    z3a = cost.param_memory(plan, 8, sharding="zero3",
                            streaming_spans=False)
    assert z3a.peak_bytes >= 4 * sum(s.size for s in spans)
    assert z3a.peak_bytes > z3.peak_bytes


def test_plan_for_facade_assembles_commplan():
    """``comm.plan_for(config, mesh, tree)`` — the one-call packaging of
    autotune + bucketing + plan.make — carries the policy, resolves
    'auto' buckets, and accepts both a Mesh and an (axes, sizes) pair."""
    from repro.comm import plan_for
    from repro.configs.base import CommConfig

    tree = {f"t{i}": jnp.zeros((256, 64)) for i in range(6)}
    cc = CommConfig(strategy="ring", bucket_mb=0.25, sharding="zero3")
    p = plan_for(cc, (("data",), (8,)), tree)
    assert (p.sharding, p.gather) == ("zero3", "per_group")
    assert p.n_shards == 8 and p.schedule == "ring"
    assert p.bucket_plan(tree).n_buckets == len(p.bucket_sizes)
    # replicated plans don't shard
    pr = plan_for(CommConfig(strategy="ring", bucket_mb=0.25),
                  (("data",), (8,)), tree)
    assert (pr.sharding, pr.n_shards) == ("replicated", 1)
    # 'auto' resolves to a concrete bucket size
    pa = plan_for(CommConfig(strategy="ring", bucket_mb="auto",
                             sharding="zero1"), (("data",), (8,)), tree)
    assert isinstance(pa.bucket_mb, float)
    assert pa.requested_bucket_mb == "auto"
    # a real Mesh works too
    mesh = make_mesh((1, 1), ("data", "model"))
    pm = plan_for(cc, mesh, tree)
    assert pm.mesh_axes == ("data", "model") and pm.n_shards == 1
