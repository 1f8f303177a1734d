"""The control: the reference itself, put in the program's place and
computed in float8 (the precision below the configuration's bfloat16),
must come out not correct. At a small size on the CPU; the ResNet cells
against their committed limits, the decoder (no cell: PERF.md) against
the limits of its small size. The readings at the cells' own sizes on
the chip are in PERF.md."""
import jax
import pytest

from perfbench import oracle
from perfbench.kinds import train
from perfbench.tests import small

pytestmark = pytest.mark.tier1


def _batches(c, seed):
    from perfbench import generator
    pool = generator.make_pool(c.traffic, c.config_module.sizes(c.config),
                               c.traffic["batch_per_chip"] * c.chips,
                               train.seed31(seed))
    return list(pool[:3])


@pytest.mark.parametrize("cell,limits,seed", [
    pytest.param(small.DECODER, small.LM_LIMITS, 11,
                 id="mistral-nemo-12b.l2.s4096-limits0-11"),
    pytest.param(small.DECODER, small.LM_LIMITS, 2 ** 31 + 40,
                 id="mistral-nemo-12b.l2.s4096-limits1-2147483688"),
    pytest.param("resnet50.b256.1chip", None, 7,
                 id="resnet50.b256.1chip-limits2-7"),
    pytest.param("resnet50.b40.1chip", None, 2 ** 31 + 7,
                 id="resnet50.b40.1chip-committed-2147483655")])
def test_control_is_not_correct(cell, limits, seed):
    c = small.cell(cell, limits)
    batches = _batches(c, seed)
    dev = jax.devices()[0]
    ref = train.run_reference(c, train.reference(c, "f32"), batches, seed,
                              dev)
    ctl = train.run_reference(c, train.reference(c, "fp8"), batches, seed,
                              dev)
    checks = oracle.checks(oracle.gaps(ctl, ref), c.limits["limits"])
    assert not oracle.passed(checks), checks
    if limits is None:
        # the committed limits fail it on the forward's batch statistics
        assert checks["bn_stats_gap"]["value"] > \
            checks["bn_stats_gap"]["limit"], checks
