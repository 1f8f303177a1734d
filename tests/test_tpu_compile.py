"""Every Pallas kernel the repo keeps, compiled (not interpreted) for a
described TPU v5e chip at the widths the models run it at.

Nothing runs: the TPU compiler that ships with jaxlib compiles for a
topology that is described, not attached, and refuses what the chip would
refuse (unaligned blocks, VMEM overflow). Interpret-mode tests cannot see
either. The topology is described inside a fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.comm.ring_kernel import ring_add_step
from repro.core.bucketing import CHUNK
from repro.kernels import batched_norm, flash_attention, lars_update, \
    smoothed_xent

pytestmark = pytest.mark.tier1

#: ResNet-50's 25.6M parameters, packed into CHUNK-aligned buffers
RESNET50_PACKED = 25_600_000 // CHUNK * CHUNK
RESNET50_TENSORS = 161


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _case(name):
    """(kernel fn, argument shapes) for one compile case."""
    n, nch = RESNET50_PACKED, RESNET50_PACKED // CHUNK
    f32, bf16, i32 = jnp.float32, jnp.bfloat16, jnp.int32
    if name.startswith("ring_add_step"):
        dt = f32 if name.endswith("f32") else bf16
        c = 64 * CHUNK                      # one 4-way ring chunk of 256K
        return (functools.partial(ring_add_step, interpret=False),
                [((c,), dt), ((4, c), dt), ((), i32)])
    if name == "batched_sumsq":
        return (functools.partial(batched_norm.batched_sumsq,
                                  n_tensors=RESNET50_TENSORS,
                                  interpret=False),
                [((n,), f32), ((nch,), i32)])
    if name == "lars_packed_update":
        def fn(p, g, m, trust, seg, lr):
            return lars_update.lars_packed_update(
                p, g, m, trust, seg, lr=lr, momentum=0.9, wd=5e-5,
                interpret=False)
        return fn, [((n,), f32)] * 3 + [((RESNET50_TENSORS,), f32),
                                         ((nch,), i32), ((), f32)]
    if name.startswith("smoothed_xent_rows"):
        T, V, dt = ((256, 1000, f32) if name.endswith("1000")
                    else (4096, 151936, bf16))
        return (functools.partial(smoothed_xent.smoothed_xent_rows,
                                  interpret=False),
                [((T, V), dt), ((T,), i32)])
    if name == "flash_attention":
        H, K, S, D = 40, 8, 4096, 128
        return (functools.partial(flash_attention.flash_attention,
                                  causal=True, n_q_heads=H, n_kv_heads=K,
                                  interpret=False),
                [((H, S, D), bf16), ((K, S, D), bf16), ((K, S, D), bf16)])
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "ring_add_step_f32", "ring_add_step_bf16", "batched_sumsq",
    "lars_packed_update", "smoothed_xent_rows_1000",
    "smoothed_xent_rows_151936", "flash_attention"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _case(name)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
