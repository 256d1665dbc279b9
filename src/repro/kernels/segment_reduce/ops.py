"""Public wrappers: padded-CSR kernels with kernel-clock instrumentation.

Each wrapper always launches the Pallas kernel (Mosaic on TPU, the
interpreter elsewhere); there is no size-based fallback to the oracle.
A panel that does not fit the chip's VMEM fails to compile.  Callers that
want the jnp lowering call the ``*_ref`` oracles directly.
"""
from __future__ import annotations

import jax

from repro.kernels.segment_reduce.kernel import (
    csr_aggregate,
    csr_round,
    csr_round_residual,
)
from repro.obs.profiler import kernel_clock, kernel_time


def csr_aggregate_op(
    nbr: jax.Array,
    wgt: jax.Array,
    F: jax.Array,
    *,
    bn: int = 256,
    bs: int = 128,
    bd: int = 16,
) -> jax.Array:
    t0 = kernel_clock()
    out = csr_aggregate(nbr, wgt, F, bn=bn, bs=bs, bd=bd)
    return kernel_time("csr_aggregate.kernel", t0, out)


def csr_round_op(
    nbr: jax.Array,
    wgt: jax.Array,
    F: jax.Array,
    base: jax.Array,
    *,
    c: float,
    bn: int = 256,
    bs: int = 128,
    bd: int = 16,
) -> jax.Array:
    """Fused ``c·base + A_bucket @ F`` round for one blocked-CSR bucket."""
    t0 = kernel_clock()
    out = csr_round(nbr, wgt, F, base, c=c, bn=bn, bs=bs, bd=bd)
    return kernel_time("csr_round.kernel", t0, out)


def csr_round_residual_op(
    nbr: jax.Array,
    wgt: jax.Array,
    F: jax.Array,
    base: jax.Array,
    prev: jax.Array,
    *,
    c: float,
    bn: int = 256,
    bs: int = 128,
    bd: int = 16,
) -> tuple:
    """Fused superstep for one bucket: round plus max-|out − prev| partial.

    Returns ``(out, delta)``; ``delta`` has one max-partial row per row
    block (``(grid_m, S)``) — callers reduce with ``jnp.max(delta,
    axis=0)`` after concatenating buckets.
    """
    t0 = kernel_clock()
    out = csr_round_residual(nbr, wgt, F, base, prev, c=c, bn=bn, bs=bs, bd=bd)
    return kernel_time("csr_round_residual.kernel", t0, out)
