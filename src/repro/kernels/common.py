"""Shared helpers for Pallas TPU kernels.

All kernels in this package are written against the TPU backend
(``pl.pallas_call`` with explicit ``BlockSpec`` VMEM tiling). A kernel
called with ``interpret=None`` resolves the mode when it is called, from the
device the computation runs on (:func:`interpret_default`): compiled on a
TPU, the Pallas interpreter everywhere else (the CPU test suite). Nothing
here touches a JAX backend at import time.
"""
from __future__ import annotations

import jax

# TPU hardware constants (v5e) used for block-shape heuristics.
LANE = 128          # last-dim tiling (VREG lane count, MXU edge)
SUBLANE = 8         # second-to-last dim tiling for fp32
SUBLANE_I8 = 32     # second-to-last dim tiling for int8 (min tile 32x128)


def interpret_default(device=None) -> bool:
    """Whether a Pallas kernel placed on ``device`` runs in interpret mode.

    ``device`` defaults to JAX's default device. On a TPU the kernel is
    compiled (and a kernel the compiler refuses raises); on any other
    platform it runs in the Pallas interpreter.
    """
    if device is None:
        device = jax.devices()[0]
    return device.platform != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret`` as given, or :func:`interpret_default` for ``None``."""
    return interpret_default() if interpret is None else bool(interpret)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b
