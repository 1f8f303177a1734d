"""Whole runs of the harness at a small size on the CPU, with the look for
a chip skipped: a sound program comes out correct, and each fault that a
training cell can have, planted in the timed path, comes out not correct.

On the small ResNet with the program computed in float32, which agrees
with the reference to rounding (its bfloat16 step does not at this size:
batch norm over a few values per channel makes its backward pass
chaotic), and on the small decoder in bfloat16 against a reference that
takes LARS per stacked tensor, as the program does. Against the
published per-layer LARS the decoder's program is not correct, which is
why it has no cell (PERF.md, Open questions)."""
import contextlib

import pytest

from perfbench import faults
from perfbench.tests import small

pytestmark = pytest.mark.tier1


@contextlib.contextmanager
def per_stack_lars(c):
    """The cell's reference with the program's LARS: one trust ratio per
    stacked tensor, norm scales included."""
    real = c.config_module.reference
    c.config_module.reference = lambda *a: real(*a)[:2] + (frozenset(),)
    try:
        yield
    finally:
        c.config_module.reference = real


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch"])
def test_decoder_run(fault):
    c = small.cell(small.DECODER, small.LM_LIMITS)
    plant = faults.planted(fault) if fault else contextlib.nullcontext()
    with per_stack_lars(c), plant:
        result = small.run(c, seed=2 ** 31 + 10)
    assert result["correct"] is (fault is None), result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    # the decoder has no cell, so no metric but those every cell reports;
    # the CPU reports no device memory, so no peak_hbm_gib
    assert set(result["metrics"]) == {"setup_s"}
    assert list(result)[-1] == "checks"


def test_decoder_departs_from_per_layer_lars():
    c = small.cell(small.DECODER, small.LM_LIMITS)
    result = small.run(c, seed=2 ** 31 + 10)
    assert result["correct"] is False
    assert result["checks"]["grad_gap"]["value"] > 0.1
    assert result["checks"]["loss_gap"]["value"] < \
        result["checks"]["loss_gap"]["limit"]


def test_resnet_run_reports_images_and_catches_a_stuck_state():
    c = small.cell("resnet50.b256.1chip", small.F32_LIMITS)
    with small.program_in_f32(), faults.planted("state_unchanged"):
        result = small.run(c, seed=5)
    assert result["correct"] is False
    assert result["checks"]["change_gap_median"]["value"] == \
        pytest.approx(1.0)
    assert {"img_per_s", "setup_s"} <= set(result["metrics"])
    # a percentile is reported from 20 steps up
    assert ("step_ms_p95" in result["metrics"]) is (result["attempted"] >= 20)


@pytest.mark.parametrize("fault", [None, "half_batch"])
def test_resnet_run(fault):
    c = small.cell("resnet50.b40.1chip", small.F32_LIMITS)
    plant = faults.planted(fault) if fault else contextlib.nullcontext()
    with small.program_in_f32(), plant:
        result = small.run(c, seed=2 ** 31 + 3)
    assert result["correct"] is (fault is None), result["checks"]
    assert result["checks"]["bn_stats_gap"]["value"] < 1e-4
