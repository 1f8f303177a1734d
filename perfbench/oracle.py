"""The comparison that decides ``correct`` for a training cell.

The program's first three steps, driven through the window's own call and
feed, are compared with the plain float32 reference's three steps from
the same seed and the same batches. The readings:

- ``loss_gap``: the largest relative gap between the program's and the
  reference's loss over the three steps; ``loss0_gap`` the first step's;
- ``grad_gap``: the first gradient as the optimizer took it, read from
  the momentum after one step (v1 / lr0 = trust * (g + wd * w)); for
  each parameter tensor the gap between the program's norm and the
  reference's, over the larger of the reference's norm of that tensor and
  of the median tensor; the worst tensor. ``grad_gap_median``: the
  median tensor's gap;
- ``change_gap`` and ``change_gap_median``: the same for the parameters'
  change over the three steps, ||w3 - w0||;
- ``bn_stats_gap``: the first step's batch statistics of every batch
  norm, as the program's state keeps them after one step; for each layer
  the larger of ||mean - mean_ref|| / ||std_ref|| and
  ||var - var_ref|| / ||var_ref|| (norms over the channels); the median
  layer. These are the forward pass's own readings, layer by layer, and
  the forward rounds without the amplification that the backward pass of
  a batch-normed network has at initialisation.

Tensors whose reference gradient at step 0 is below a thousandth of the
median tensor's are left out of the per-tensor readings: they move under
the optimizer by rounding alone. A cell's limits file names the readings
it compares, each with its limit; a reading that is not finite fails.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, List, Optional

#: a tensor whose reference gradient norm at step 0 is below this share of
#: the median tensor's moves by rounding alone
NEGLIGIBLE_GRAD = 1e-3


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              keep: List[str]) -> Dict[str, float]:
    """{tensor: gap of its norm over max(its reference norm, the median
    tensor's)}."""
    med = statistics.median(ref[k] for k in keep)
    out = {}
    for k in keep:
        denom = max(ref[k], med)
        ok = math.isfinite(prog[k]) and denom > 0
        out[k] = abs(prog[k] - ref[k]) / denom if ok else math.inf
    return out


def kept_leaves(ref_grad0: Dict[str, float]) -> List[str]:
    med = statistics.median(ref_grad0.values())
    return sorted(k for k, v in ref_grad0.items()
                  if v >= NEGLIGIBLE_GRAD * med)


def _rel(a, b, scale) -> float:
    d = math.sqrt(float(((a - b) ** 2).sum()))
    n = math.sqrt(float((scale ** 2).sum()))
    return d / n if n > 0 and math.isfinite(d) else math.inf


def stats_gaps(prog: dict, ref: dict) -> Dict[str, float]:
    """{layer: gap of its batch statistics}; ``prog`` and ``ref`` map a
    layer to (mean, variance) per channel. A layer the program lacks
    reads infinity."""
    out = {}
    for layer, (m_ref, v_ref) in ref.items():
        if layer not in prog or prog[layer][0].shape != m_ref.shape:
            out[layer] = math.inf
            continue
        m, v = prog[layer]
        out[layer] = max(_rel(m, m_ref, v_ref ** 0.5), _rel(v, v_ref, v_ref))
    return out


def gaps(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog`` and ``ref`` hold ``losses`` (three floats), ``grad1`` and
    ``change3`` ({tensor: norm}) and ``stats`` ({layer: (mean, variance)},
    empty for a model without batch norm); ``ref`` also ``grad0``."""
    keep = kept_leaves(ref["grad0"])
    loss = [abs(a - b) / abs(b) if math.isfinite(a) else math.inf
            for a, b in zip(prog["losses"], ref["losses"])]
    out = {"loss_gap": max(loss), "loss0_gap": loss[0]}
    for name, key in (("grad_gap", "grad1"), ("change_gap", "change3")):
        per = leaf_gaps(prog[key], ref[key], keep)
        out[name] = max(per.values())
        out[f"{name}_median"] = statistics.median(per.values())
    if ref.get("stats"):
        out["bn_stats_gap"] = statistics.median(
            stats_gaps(prog.get("stats", {}), ref["stats"]).values())
    return out


def checks(values: Dict[str, float], limits: Dict[str, float],
           window_compiles: Optional[int] = None) -> Dict[str, dict]:
    """The readings the cell compares, each beside its limit."""
    out = {k: {"value": values[k], "limit": lim} for k, lim in limits.items()}
    if window_compiles is not None:
        out["window_compiles"] = {"value": window_compiles, "limit": 0}
    return out


def passed(chk: Dict[str, dict]) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in chk.values())
