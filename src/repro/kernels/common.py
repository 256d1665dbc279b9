"""Shared kernel plumbing.

TPU is the compile target.  On a TPU backend ``interpret`` resolves to
False and every kernel lowers to Mosaic; on any other backend (the CPU
test runs, ``JAX_PLATFORMS=cpu``) the Pallas interpreter executes the
kernel body in Python with the same blocking semantics.
"""
from __future__ import annotations

import functools

import jax

#: Precision of the LP engines' f32 matmuls.  A TPU runs a default-
#: precision f32 matmul as one bf16 pass (about three significant digits,
#: coarser than the σ the solvers converge to); HIGHEST keeps f32
#: accuracy there.  A CPU computes f32 either way.
F32_MATMUL = jax.lax.Precision.HIGHEST


@functools.lru_cache(maxsize=1)
def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def pick_block(dim: int, preferred: int, align: int = 8) -> int:
    """Largest hardware-friendly block ≤ preferred that keeps the grid
    covering ``dim`` without a ragged tail when possible."""
    if dim <= preferred:
        return round_up(dim, align) if dim % align else dim
    b = preferred
    while b > align and dim % b:
        b -= align
    return b if dim % b == 0 else preferred
