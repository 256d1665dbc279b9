"""Pallas TPU kernels: padded-CSR neighbor aggregation (gather + reduce).

TPU adaptation of the Giraph message loop: instead of scattering messages
edge-by-edge (GPU-style atomics have no TPU analogue), neighbors are packed
into an (M, max_deg) rectangle (``PaddedCSR`` / one ``BlockedCSR`` width
bucket) so each output row *gathers* its inputs — a pull model with fully
regular tiles:

  grid = (S/bs, M/bm, D/bd); for each (seat-block, row-block, deg-block):
      out[bm, bs] += Σ_{k<bd} wgt[bm, k] · F[nbr[bm, k], bs]

The (bm, bd) neighbor-id and weight tiles live in SMEM: the wrapper lays
each tile out contiguously in a flat array, so a tile is one 1-D block of
``bm·bd`` scalars (a multiple of 128, which Mosaic requires of rank-1
blocks).  The gather is a scalar-indexed row read ``F[pl.ds(id, 1), :]``
from F's seed/feature column panel (N, bs), which stays resident in VMEM
across the row-block sweep (seat-block outermost, BlockSpec index ignores
the row and degree axes) — the HBM traffic is one read of F per column
panel plus the nbr/wgt tiles.  Mosaic has no vector gather from a VMEM
ref, and its dynamic row reads need 32-bit rows, so a bf16 panel is
widened to f32 before the call (exact; the operands stay bf16-quantized).
VMEM budget: N·bs·4 bytes per panel buffer; ``vmem_limit_bytes`` is raised
to what the buffers need, and a panel beyond the chip's VMEM fails to
compile rather than falling back.  For larger N the caller shards nodes
first (the distributed engine's node bands keep per-shard N bounded).

The output row count M may differ from the panel row count N: a blocked-CSR
width bucket aggregates only its own rows while gathering from the full
panel (DESIGN.md §11).

``csr_round`` is the fused LP round: the same accumulation with a
``c · base`` epilogue folded into the flush, so one kernel call computes
``A_eff @ F + β²·Y`` for its row bucket without a second HBM pass.

``csr_round_residual`` is the fused *superstep*: the round plus the
per-column convergence reduction ``max_r |out − prev|`` emitted from the
same flush, so the σ-check the LP loops run never re-reads the (N, S)
state from HBM.  ``prev`` is the pre-round state slice for this bucket's
rows; the second output is one max-partial row per row block, reduced to
the (S,) residual by a cheap (grid_m, S) host-side max.  Accumulation is
fp32 regardless of the storage dtype, so a bf16 ``F``/``wgt`` pair (the
engine's ``storage_dtype="bf16"`` mode) quantizes only the operands.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, default_interpret, round_up

# Mosaic's default scoped-VMEM limit on the smallest current chip; a
# launch that needs more asks for it explicitly.
_DEFAULT_SCOPED_VMEM = 16 * 1024 * 1024
_VMEM_HEADROOM = 2 * 1024 * 1024
_XLA_FLAT_TILE = 1024


def _accumulate(nbr_ref, wgt_ref, f_ref, acc_ref, *, bm, bd):
    """``acc[r] += Σ_k wgt[r, k] · F[nbr[r, k]]`` over one SMEM tile.

    One scalar-indexed panel-row read per slot; the bd slots of a row are
    unrolled so the row's partial sum stays in registers.
    """

    def row(r, carry):
        acc = acc_ref[pl.ds(r, 1), :]
        for k in range(bd):
            idx = nbr_ref[r * bd + k]
            acc = acc + wgt_ref[r * bd + k] * f_ref[pl.ds(idx, 1), :]
        acc_ref[pl.ds(r, 1), :] = acc
        return carry

    lax.fori_loop(0, bm, row, 0)


def _csr_agg_kernel(nbr_ref, wgt_ref, f_ref, out_ref, acc_ref, *, d_steps, bm, bd):
    d = pl.program_id(2)

    @pl.when(d == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _accumulate(nbr_ref, wgt_ref, f_ref, acc_ref, bm=bm, bd=bd)

    @pl.when(d == d_steps - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _csr_round_kernel(
    nbr_ref, wgt_ref, f_ref, base_ref, out_ref, acc_ref, *, d_steps, bm, bd, c
):
    d = pl.program_id(2)

    @pl.when(d == 0)
    def _init():
        # epilogue folded into init: acc starts at c·base, the deg sweep
        # accumulates A_eff @ F on top — one VMEM-resident fused round.
        acc_ref[...] = c * base_ref[...].astype(jnp.float32)

    _accumulate(nbr_ref, wgt_ref, f_ref, acc_ref, bm=bm, bd=bd)

    @pl.when(d == d_steps - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _csr_round_res_kernel(
    nbr_ref,
    wgt_ref,
    f_ref,
    base_ref,
    prev_ref,
    out_ref,
    delta_ref,
    acc_ref,
    *,
    d_steps,
    bm,
    bd,
    c,
):
    d = pl.program_id(2)

    @pl.when(d == 0)
    def _init():
        acc_ref[...] = c * base_ref[...].astype(jnp.float32)

    _accumulate(nbr_ref, wgt_ref, f_ref, acc_ref, bm=bm, bd=bd)

    @pl.when(d == d_steps - 1)
    def _flush():
        acc = acc_ref[...]
        out_ref[...] = acc.astype(out_ref.dtype)
        # the residual folded into the flush: padded rows carry zero base,
        # zero weights, and zero prev, so they contribute |0 − 0| = 0
        diff = jnp.abs(acc - prev_ref[...].astype(jnp.float32))
        delta_ref[...] = jnp.max(diff, axis=0, keepdims=True)


@dataclasses.dataclass(frozen=True)
class _Tiling:
    """Padded sizes and block shapes of one launch."""

    m: int
    s: int
    bm: int
    bs: int
    bd: int
    m_pad: int
    s_pad: int
    d_pad: int

    @property
    def grid(self):
        return (self.s_pad // self.bs, self.m_pad // self.bm, self.d_pad // self.bd)

    def row_block(self):
        return pl.BlockSpec((self.bm, self.bs), lambda j, i, d: (i, j))

    def smem_tile(self):
        d_steps = self.grid[2]
        return pl.BlockSpec(
            (self.bm * self.bd,),
            lambda j, i, d: (i * d_steps + d,),
            memory_space=pltpu.SMEM,
        )

    def params(self, n_pad: int, row_arrays: int):
        """Compiler params; VMEM raised to what the buffers need."""
        need = 4 * (
            2 * n_pad * self.bs  # double-buffered resident panel
            + 2 * row_arrays * self.bm * self.bs  # row-block in/out tiles
            + self.bm * self.bs  # accumulator
        )
        limit = None
        if need + _VMEM_HEADROOM > _DEFAULT_SCOPED_VMEM:
            limit = need + _VMEM_HEADROOM
        return pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=limit,
        )


def _tile_inputs(nbr, wgt, F, bn, bs, bd):
    """Pad to whole blocks, flatten the (bm, bd) tiles for SMEM, and widen
    the panel to f32.

    ``bm`` is a multiple of 16 and ``bd`` of 8, so every SMEM tile holds a
    multiple of 128 scalars; ``bs`` is a multiple of 128 or all of S.
    Padded slots carry id 0 with weight 0 and add nothing.
    """
    m, dmax = nbr.shape
    n, s = F.shape
    bm = round_up(min(bn, m), 16)
    bs = s if s <= bs else round_up(bs, 128)
    bd = round_up(min(bd, dmax), 8)
    if cdiv(m, bm) * cdiv(dmax, bd) > 1:
        # XLA tiles a long rank-1 s32/f32 array by 1024 elements; a block
        # of the flat tile array must span whole XLA tiles
        bd = round_up(bd, _XLA_FLAT_TILE // math.gcd(bm, _XLA_FLAT_TILE))
    t = _Tiling(
        m=m,
        s=s,
        bm=bm,
        bs=bs,
        bd=bd,
        m_pad=cdiv(m, bm) * bm,
        s_pad=cdiv(s, bs) * bs,
        d_pad=cdiv(dmax, bd) * bd,
    )
    pad = ((0, t.m_pad - m), (0, t.d_pad - dmax))
    mb, db = t.m_pad // bm, t.d_pad // bd

    def flat_tiles(a):
        a = jnp.pad(a, pad).reshape(mb, bm, db, bd)
        return a.transpose(0, 2, 1, 3).reshape(-1)

    nbr = flat_tiles(nbr.astype(jnp.int32))
    wgt = flat_tiles(wgt.astype(jnp.float32))
    n_pad = round_up(n, 8)  # panel rows to the f32 sublane multiple
    F = jnp.pad(F.astype(jnp.float32), ((0, n_pad - n), (0, t.s_pad - s)))
    return nbr, wgt, F, t


def _pad_rows(a, t: _Tiling):
    if a.shape == (t.m_pad, t.s_pad):
        return a
    return jnp.pad(a, ((0, t.m_pad - t.m), (0, t.s_pad - t.s)))


@functools.partial(jax.jit, static_argnames=("bn", "bs", "bd", "interpret"))
def csr_aggregate(
    nbr: jax.Array,  # (M, D) int32
    wgt: jax.Array,  # (M, D)
    F: jax.Array,  # (N, S)
    *,
    bn: int = 256,
    bs: int = 128,
    bd: int = 16,
    interpret: bool | None = None,
) -> jax.Array:
    """out[r] = Σ_k wgt[r, k] · F[nbr[r, k]] for M rows over an (N, S) panel."""
    out_dtype = F.dtype
    nbr, wgt, Fp, t = _tile_inputs(nbr, wgt, F, bn, bs, bd)
    if interpret is None:
        interpret = default_interpret()
    kernel = functools.partial(
        _csr_agg_kernel, d_steps=t.grid[2], bm=t.bm, bd=t.bd
    )
    out = pl.pallas_call(
        kernel,
        grid=t.grid,
        in_specs=[
            t.smem_tile(),  # nbr tile
            t.smem_tile(),  # wgt tile
            pl.BlockSpec((Fp.shape[0], t.bs), lambda j, i, d: (0, j)),  # F panel
        ],
        out_specs=t.row_block(),
        out_shape=jax.ShapeDtypeStruct((t.m_pad, t.s_pad), out_dtype),
        scratch_shapes=[pltpu.VMEM((t.bm, t.bs), jnp.float32)],
        compiler_params=t.params(Fp.shape[0], row_arrays=1),
        interpret=interpret,
    )(nbr, wgt, Fp)
    return out[: t.m, : t.s]


@functools.partial(
    jax.jit, static_argnames=("c", "bn", "bs", "bd", "interpret")
)
def csr_round(
    nbr: jax.Array,  # (M, D) int32
    wgt: jax.Array,  # (M, D)
    F: jax.Array,  # (N, S)
    base: jax.Array,  # (M, S)
    *,
    c: float,
    bn: int = 256,
    bs: int = 128,
    bd: int = 16,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused LP round for one row bucket: ``c·base + Σ_k wgt·F[nbr]``."""
    m = nbr.shape[0]
    s = F.shape[1]
    if base.shape != (m, s):
        raise ValueError(f"base must be ({m}, {s}), got {base.shape}")
    out_dtype = F.dtype
    nbr, wgt, Fp, t = _tile_inputs(nbr, wgt, F, bn, bs, bd)
    base = _pad_rows(base, t)
    if interpret is None:
        interpret = default_interpret()
    kernel = functools.partial(
        _csr_round_kernel, d_steps=t.grid[2], bm=t.bm, bd=t.bd, c=c
    )
    out = pl.pallas_call(
        kernel,
        grid=t.grid,
        in_specs=[
            t.smem_tile(),  # nbr tile
            t.smem_tile(),  # wgt tile
            pl.BlockSpec((Fp.shape[0], t.bs), lambda j, i, d: (0, j)),  # F panel
            t.row_block(),  # base tile
        ],
        out_specs=t.row_block(),
        out_shape=jax.ShapeDtypeStruct((t.m_pad, t.s_pad), out_dtype),
        scratch_shapes=[pltpu.VMEM((t.bm, t.bs), jnp.float32)],
        compiler_params=t.params(Fp.shape[0], row_arrays=2),
        interpret=interpret,
    )(nbr, wgt, Fp, base)
    return out[: t.m, : t.s]


@functools.partial(
    jax.jit, static_argnames=("c", "bn", "bs", "bd", "interpret")
)
def csr_round_residual(
    nbr: jax.Array,  # (M, D) int32
    wgt: jax.Array,  # (M, D)
    F: jax.Array,  # (N, S) gather panel (storage dtype)
    base: jax.Array,  # (M, S) seed/base slice for this bucket
    prev: jax.Array,  # (M, S) pre-round state slice for this bucket
    *,
    c: float,
    bn: int = 256,
    bs: int = 128,
    bd: int = 16,
    interpret: bool | None = None,
) -> tuple:
    """Fused superstep for one row bucket.

    Returns ``(out, delta)`` where ``out = c·base + Σ_k wgt·F[nbr]`` in
    ``base.dtype`` (the state dtype — a bf16 panel still yields fp32
    state) and ``delta`` is the ``(grid_m, S)`` per-row-block partial of
    ``max_r |out − prev|``; reduce it with ``jnp.max(delta, axis=0)``.
    """
    m = nbr.shape[0]
    s = F.shape[1]
    if base.shape != (m, s) or prev.shape != (m, s):
        raise ValueError(
            f"base/prev must be ({m}, {s}), got {base.shape}/{prev.shape}"
        )
    nbr, wgt, Fp, t = _tile_inputs(nbr, wgt, F, bn, bs, bd)
    base = _pad_rows(base, t)
    prev = _pad_rows(prev, t)
    if interpret is None:
        interpret = default_interpret()
    kernel = functools.partial(
        _csr_round_res_kernel, d_steps=t.grid[2], bm=t.bm, bd=t.bd, c=c
    )
    grid_m = t.grid[1]
    out, delta = pl.pallas_call(
        kernel,
        grid=t.grid,
        in_specs=[
            t.smem_tile(),  # nbr tile
            t.smem_tile(),  # wgt tile
            pl.BlockSpec((Fp.shape[0], t.bs), lambda j, i, d: (0, j)),  # F panel
            t.row_block(),  # base tile
            t.row_block(),  # prev tile
        ],
        out_specs=[
            t.row_block(),
            # one (1, bs) max-partial per row block: a squeezed leading
            # axis keeps the block's last two dims equal to the array's
            pl.BlockSpec((None, 1, t.bs), lambda j, i, d: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t.m_pad, t.s_pad), base.dtype),
            jax.ShapeDtypeStruct((grid_m, 1, t.s_pad), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((t.bm, t.bs), jnp.float32)],
        compiler_params=t.params(Fp.shape[0], row_arrays=3),
        interpret=interpret,
    )(nbr, wgt, Fp, base, prev)
    return out[: t.m, : t.s], delta[:, 0, : t.s]
