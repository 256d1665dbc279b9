"""Pallas TPU kernel: embedding-bag (multi-hot gather + weighted reduce).

Recsys hot path.  The vocab-sharded table shard for one device is kept
HBM-resident; the kernel streams batch tiles and keeps a (rows_budget, D)
*table panel* in VMEM, processing the batch tile against each panel:

  grid = (B/bb, V/bv); out[b] += Σ_k w[b,k]·T[idx[b,k]]  for idx in panel v

Indices outside the current panel are masked to weight 0 (panel-local
offset), so the sweep over panels accumulates exactly once per index.  This
is the TPU-native replacement for row-atomic gathers: every memory access
is a regular tile, the irregularity is absorbed by the mask.

For tables whose embedding-dim panel fits VMEM whole (V·D·4 ≤ ~8MB — true
for the per-device shard after vocab sharding at production scale), set
``bv = V`` and the sweep collapses to one step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, default_interpret


def _embed_bag_kernel(idx_ref, w_ref, tab_ref, out_ref, acc_ref, *, bv, v_steps, k_slots):
    v = pl.program_id(1)

    @pl.when(v == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    idx = idx_ref[...]                       # (bb, K)
    w = w_ref[...].astype(jnp.float32)       # (bb, K)
    tab = tab_ref[...]                       # (bv, D) panel
    base = v * bv
    local = idx - base                       # panel-local
    in_panel = (local >= 0) & (local < bv)
    local = jnp.where(in_panel, local, 0)
    w_masked = jnp.where(in_panel, w, 0.0)
    for k in range(k_slots):
        rows = tab[local[:, k], :].astype(jnp.float32)   # (bb, D)
        acc_ref[...] += w_masked[:, k][:, None] * rows

    @pl.when(v == v_steps - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bb", "bv", "interpret")
)
def embedding_bag(
    table: jax.Array,   # (V, D)
    idx: jax.Array,     # (B, K) int32
    w: jax.Array,       # (B, K)
    *,
    bb: int = 256,
    bv: int = 8192,
    interpret: bool | None = None,
) -> jax.Array:
    v_size, d = table.shape
    b, k_slots = idx.shape
    bb = min(bb, b)
    bv = min(bv, v_size)
    b_pad = cdiv(b, bb) * bb
    v_pad = cdiv(v_size, bv) * bv
    if b_pad != b:
        idx = jnp.pad(idx, ((0, b_pad - b), (0, 0)))
        w = jnp.pad(w, ((0, b_pad - b), (0, 0)))
    if v_pad != v_size:
        table = jnp.pad(table, ((0, v_pad - v_size), (0, 0)))
    grid = (b_pad // bb, v_pad // bv)
    if interpret is None:
        interpret = default_interpret()
    kernel = functools.partial(
        _embed_bag_kernel, bv=bv, v_steps=grid[1], k_slots=k_slots
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, k_slots), lambda i, v: (i, 0)),   # idx tile
            pl.BlockSpec((bb, k_slots), lambda i, v: (i, 0)),   # w tile
            pl.BlockSpec((bv, d), lambda i, v: (v, 0)),         # table panel
        ],
        out_specs=pl.BlockSpec((bb, d), lambda i, v: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b_pad, d), table.dtype),
        scratch_shapes=[pltpu.VMEM((bb, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(idx, w, table)
    if b_pad != b:
        out = out[:b]
    return out
