"""Pallas TPU kernel: blocked online-softmax attention (forward).

FlashAttention adapted to TPU tiling: grid = (B·H, Lq/bq, Lk/bk) with the
key axis innermost ("arbitrary" semantics); the (m, l, acc) online-softmax
state lives in VMEM scratch across key steps, so each output tile makes one
HBM round-trip regardless of sequence length.  Causal and sliding-window
masks are applied from absolute positions; ``q_offset`` supports decode
(query positions start at the cache length).

Serving-path kernel (prefill/decode are jit'd forward passes); the training
path uses the jnp reference (XLA's fused attention is adequate there and
keeps the backward pass free).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, default_interpret

_NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, out_ref, m_scr, l_scr, acc_scr,
    *, scale, bq, bk, k_steps, causal, window, q_offset, lk_valid,
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                       # (bq, d)
    k = k_ref[0]                       # (bk, d)
    v = v_ref[0]                       # (bk, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                          # (bq, bk)

    q_pos = q_offset + pl.program_id(1) * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0
    )
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < lk_valid          # padded keys are never attended
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    # guard rows that have seen nothing yet (all -inf): exp(-inf - -inf)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_new

    @pl.when(j == k_steps - 1)
    def _flush():
        denom = jnp.maximum(l_scr[...], 1e-38)
        out_ref[0] = (acc_scr[...] / denom[:, None]).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "q_offset", "bq", "bk", "interpret",
    ),
)
def flash_attention(
    q: jax.Array,   # (B, H, Lq, D)
    k: jax.Array,   # (B, H, Lk, D)
    v: jax.Array,   # (B, H, Lk, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    bq: int = 256,
    bk: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    b, h, lq, d = q.shape
    _, _, lk, _ = k.shape
    bq = min(bq, lq)
    bk = min(bk, lk)
    lq_pad = cdiv(lq, bq) * bq
    lk_pad = cdiv(lk, bk) * bk
    if lq_pad != lq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, lq_pad - lq), (0, 0)))
    if lk_pad != lk:
        # padded keys are masked inside the kernel via lk_valid
        k = jnp.pad(k, ((0, 0), (0, 0), (0, lk_pad - lk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, lk_pad - lk), (0, 0)))
    qf = q.reshape(b * h, lq_pad, d)
    kf = k.reshape(b * h, lk_pad, d)
    vf = v.reshape(b * h, lk_pad, d)
    grid = (b * h, lq_pad // bq, lk_pad // bk)
    if interpret is None:
        interpret = default_interpret()
    scale = 1.0 / (d ** 0.5)
    kernel = functools.partial(
        _flash_kernel,
        scale=scale, bq=bq, bk=bk, k_steps=grid[2],
        causal=causal, window=window, q_offset=q_offset, lk_valid=lk,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda n, i, j: (n, i, 0)),
            pl.BlockSpec((1, bk, d), lambda n, i, j: (n, j, 0)),
            pl.BlockSpec((1, bk, d), lambda n, i, j: (n, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda n, i, j: (n, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, lq_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf)
    out = out.reshape(b, h, lq_pad, d)
    if lq_pad != lq:
        out = out[:, :, :lq]
    return out
