"""Public jit'd wrapper for the fused LP round kernel.

Chooses the kernel on TPU and falls back to the jnp reference when shapes
are too small to justify tiling overhead (or on platforms without Mosaic).
"""
from __future__ import annotations

import jax

from repro.kernels.common import default_interpret
from repro.kernels.lp_blockspmm.kernel import lp_round
from repro.kernels.lp_blockspmm.ref import lp_round_ref

_MIN_DIM_FOR_KERNEL = 128


def lp_round_op(
    A: jax.Array,
    F: jax.Array,
    base: jax.Array,
    *,
    c: float,
    bm: int = 256,
    bs: int = 256,
    bk: int = 512,
    use_kernel: bool | None = None,
) -> jax.Array:
    n, s = F.shape
    if use_kernel is None:
        use_kernel = n >= _MIN_DIM_FOR_KERNEL and s >= _MIN_DIM_FOR_KERNEL
    if not use_kernel:
        return lp_round_ref(A, F, base, c)
    return lp_round(A, F, base, c=c, bm=bm, bs=bs, bk=bk, interpret=default_interpret())
