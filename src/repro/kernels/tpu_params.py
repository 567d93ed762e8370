"""Mosaic compiler parameters shared by the Pallas TPU kernels.

Mosaic holds a kernel to a *scoped* VMEM limit (16 MiB on v5e) unless
the launch names a larger one.  Each kernel wrapper estimates its
per-grid-step footprint — every HBM-streamed block double-buffered, plus
VMEM scratch — and :func:`compiler_params` raises the limit only when
that estimate exceeds the default.  ``analysis/kernel_check.py`` audits
every tile plan against the same rule.
"""
from __future__ import annotations

import math

from jax.experimental.pallas import tpu as pltpu

# Mosaic's scoped-VMEM limit for a launch that names none (TPU v5e;
# Pallas TPU docs, "Memory spaces": 16 MiB by default on v4/v5).
SCOPED_VMEM_BYTES = 16 << 20
# Ceiling for an explicit request: v5e's 128 MiB of VMEM per core less
# headroom for Mosaic's own internal scratch.
MAX_VMEM_BYTES = 96 << 20


def block_bytes(*blocks) -> int:
    """VMEM bytes of HBM-streamed blocks, double-buffered: each block is
    ``(shape, itemsize)``."""
    return sum(2 * math.prod(shape) * size for shape, size in blocks)


def vmem_limit(need: int) -> int | None:
    """The ``vmem_limit_bytes`` a launch needing ``need`` bytes must name
    (``None`` = the scoped default suffices).  Raises when no legal limit
    fits — such a plan cannot launch at all."""
    if need <= SCOPED_VMEM_BYTES:
        return None
    if need > MAX_VMEM_BYTES:
        raise ValueError(f"kernel plan needs {need} B of VMEM, more than "
                         f"the {MAX_VMEM_BYTES} B a launch may request")
    return min(MAX_VMEM_BYTES, need + need // 4)


def compiler_params(dimension_semantics, vmem_need: int):
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=vmem_limit(vmem_need))
