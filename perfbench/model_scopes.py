"""Device time under the model's own named scopes.

The model names its layers' parts with ``jax.named_scope``: ``attention``
around multi-head latent attention, ``moe`` around a MoE layer (its
router, held and shared experts), and inside it ``router``, ``dispatch``,
``experts`` and ``combine``. The compiled HLO keeps the scopes in each
instruction's ``op_name`` (a fusion carries its root's), in the forward
pass, in the backward (``transpose(jvp(moe))``) and in rematerialised
recompute alike, with JAX's own levels between them (a scan's
``while/body``, ``checkpoint``, ``rematted_computation``). ``scope_ms``
reads the device milliseconds per traced step of the operations whose
``op_name`` holds a given path of scopes in order, each scope unwrapped
from JAX's transformation names; None where no operation holds it, as a
program without those scopes gives. The held experts' grouped-matmul
kernels carry the scope of their call (``.../moe/.../experts/jit(gmm)/
pallas_call``).
"""
from __future__ import annotations

import functools
import statistics
from typing import Dict, Optional, Tuple

from perfbench import scopes, trace as tr


def _bases(op_name: str) -> Tuple[str, ...]:
    return tuple(scopes._WRAPPED.sub("", part).rstrip(")")
                 for part in op_name.split("/"))


def _holds(bases: Tuple[str, ...], path: Tuple[str, ...]) -> bool:
    """Whether ``path``'s scopes appear in ``bases`` in order, each inside
    the one before."""
    rest = iter(bases)
    return all(scope in rest for scope in path)


@functools.lru_cache(maxsize=2)
def op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction name: its ``op_name``} of a compiled HLO module."""
    out = {}
    for line in hlo_text.splitlines():
        m = scopes._METADATA.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def scope_ms(ctx, *path: str) -> Optional[float]:
    """Device milliseconds per traced step in which an operation under the
    scopes ``path`` runs: the union of those operations' intervals in the
    window on each chip, the mean over the chips."""
    hlo = scopes._hlo(ctx)
    if hlo is None or ctx.steps == 0:
        return None
    names = op_names(hlo)
    mine = {n for n, op in names.items() if _holds(_bases(op), path)}
    if not mine:
        return None
    per_chip = []
    for ops in ctx.trace.devices.values():
        spans = [(o.start, o.end) for o in ops if o.name in mine]
        per_chip.append(tr.length(tr.union(tr.clip(spans,
                                                   *ctx.trace.window))))
    if not any(per_chip):
        return None
    return 1e-6 * statistics.mean(per_chip) / ctx.steps
