"""Pallas TPU kernel: one fused DHLP-2 round, ``out = c·base + A @ F``.

The LP hot loop is a repeated (N,N)×(N,S) matmul with an axpy epilogue.
Unfused, XLA emits matmul → HBM round-trip → elementwise; fusing the
epilogue into the matmul's final k-step keeps the (bm, bs) tile in VMEM
until it is complete — one HBM write per output tile per round instead of
write+read+write.

Blocking: grid = (N/bm, S/bs, N/bk), k innermost (``arbitrary`` semantics so
the fp32 VMEM accumulator survives across k-steps).  MXU alignment: all
block dims multiples of 128 where the problem allows; accumulation always
fp32 regardless of the storage dtype (bf16 storage mode of the LP engine).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, default_interpret


def _lp_round_kernel(base_ref, a_ref, f_ref, out_ref, acc_ref, *, c, k_steps):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = c * base_ref[...].astype(jnp.float32)

    acc_ref[...] += jnp.dot(
        a_ref[...],
        f_ref[...],
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == k_steps - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("c", "bm", "bs", "bk", "interpret"),
)
def lp_round(
    A: jax.Array,        # (N, N)
    F: jax.Array,        # (N, S)
    base: jax.Array,     # (N, S)
    *,
    c: float,
    bm: int = 256,
    bs: int = 256,
    bk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    n, s = F.shape
    if A.shape != (n, n) or base.shape != (n, s):
        raise ValueError(f"shape mismatch A={A.shape} F={F.shape} base={base.shape}")
    bm = min(bm, n)
    bs = min(bs, s)
    bk = min(bk, n)
    # Ragged trailing blocks read out-of-bounds garbage on TPU (and NaN in
    # the interpreter); zero-pad to block multiples — exact for this op —
    # and slice the result back.
    n_m = cdiv(n, bm) * bm
    n_k = cdiv(n, bk) * bk
    n_pad = max(n_m, n_k)
    s_pad = cdiv(s, bs) * bs
    if n_pad != n or s_pad != s:
        A = jnp.pad(A, ((0, n_pad - n), (0, n_pad - n)))
        F = jnp.pad(F, ((0, n_pad - n), (0, s_pad - s)))
        base = jnp.pad(base, ((0, n_pad - n), (0, s_pad - s)))
    grid = (cdiv(n_pad, bm), cdiv(s_pad, bs), cdiv(n_pad, bk))
    if interpret is None:
        interpret = default_interpret()
    kernel = functools.partial(_lp_round_kernel, c=c, k_steps=grid[2])
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bs), lambda i, j, k: (i, j)),   # base tile
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),   # A tile
            pl.BlockSpec((bk, bs), lambda i, j, k: (k, j)),   # F tile
        ],
        out_specs=pl.BlockSpec((bm, bs), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pad, s_pad), F.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bs), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(base, A, F)
    if n_pad != n or s_pad != s:
        out = out[:n, :s]
    return out
