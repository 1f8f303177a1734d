"""Pallas execution mode, decided by the platform alone.

Kernels compile on a TPU backend and run in interpret mode on every other
backend (the CPU has no Mosaic compiler). Every kernel wrapper takes
``interpret=None`` and resolves it here. A caller passes True/False only to
pin the mode: tests that run a kernel body on the CPU, or a compile for a
described TPU from a process whose backend is the CPU.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """``interpret`` if given, else True unless the default backend is a
    TPU."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"
