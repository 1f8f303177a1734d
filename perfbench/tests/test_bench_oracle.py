"""The oracle's readings on hand-made norms."""
import math

import pytest

from perfbench import oracle

pytestmark = pytest.mark.tier1


def _ref():
    return {"losses": [7.0, 7.0, 7.0],
            "grad0": {"a": 1.0, "b": 2.0, "c": 4.0, "bias_k": 1e-6},
            "grad1": {"a": 1.0, "b": 2.0, "c": 4.0, "bias_k": 1e-6},
            "change3": {"a": 0.1, "b": 0.2, "c": 0.4, "bias_k": 1e-9}}


def test_gaps_are_taken_against_the_larger_of_the_tensor_and_the_median():
    prog = _ref()
    prog["losses"] = [7.0, 7.07, 7.0]
    prog["grad1"] = {"a": 1.5, "b": 2.0, "c": 4.4, "bias_k": 5.0}
    g = oracle.gaps(prog, _ref())
    assert g["loss_gap"] == pytest.approx(0.01)
    assert g["loss0_gap"] == 0.0
    # a: 0.5 over the median 2.0; c: 0.4 over its own 4.0
    assert g["grad_gap"] == pytest.approx(0.25)
    assert g["grad_gap_median"] == pytest.approx(0.1)
    assert g["change_gap"] == 0.0


def test_a_tensor_with_no_reference_gradient_is_left_out():
    assert oracle.kept_leaves(_ref()["grad0"]) == ["a", "b", "c"]


def test_a_missing_or_nonfinite_reading_fails():
    prog = _ref()
    prog["change3"] = dict(prog["change3"], b=math.inf)
    g = oracle.gaps(prog, _ref())
    chk = oracle.checks(g, {"change_gap": 0.1, "loss_gap": 0.01}, 0)
    assert set(chk) == {"change_gap", "loss_gap", "window_compiles"}
    assert not oracle.passed(chk)


def test_a_compile_inside_the_window_fails():
    g = oracle.gaps(_ref(), _ref())
    assert oracle.passed(oracle.checks(g, {"loss_gap": 1e-3}, 0))
    assert not oracle.passed(oracle.checks(g, {"loss_gap": 1e-3}, 1))


def test_batch_statistics_are_read_per_layer_and_the_median_layer_compared():
    import numpy as np
    ref = _ref()
    ref["stats"] = {f"l{i}": (np.zeros(4), np.full(4, 4.0)) for i in range(3)}
    prog = _ref()
    # l0: mean off by 0.2 std; l1: variance off by 10%; l2: exact
    prog["stats"] = {"l0": (np.full(4, 0.4), np.full(4, 4.0)),
                     "l1": (np.zeros(4), np.full(4, 4.4)),
                     "l2": (np.zeros(4), np.full(4, 4.0))}
    per = oracle.stats_gaps(prog["stats"], ref["stats"])
    assert per == pytest.approx({"l0": 0.2, "l1": 0.1, "l2": 0.0})
    assert oracle.gaps(prog, ref)["bn_stats_gap"] == pytest.approx(0.1)
    # a layer the program lacks reads infinity
    del prog["stats"]["l2"]
    assert oracle.gaps(prog, ref)["bn_stats_gap"] == pytest.approx(0.2)
    assert math.isinf(oracle.stats_gaps(prog["stats"], ref["stats"])["l2"])
