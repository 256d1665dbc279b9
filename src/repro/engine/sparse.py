"""Sparse backends: blocked-CSR, the repo's scalability path.

``sparse`` runs the *fused-superstep* plan by default: buckets are
remapped into permuted row order once at prepare time (neighbor ids
rewritten through the inverse permutation), so every round writes its
output rows contiguously — no per-round inverse-permute gather — and the
round + the per-column convergence reduction ``max_r |Fn − F|`` come out
of one fused op (``csr_round_residual``) instead of separate HLO ops.
Label state crosses the whole ``while_loop`` in permuted space and is
inverse-permuted exactly once on exit.  ``kernel`` is the same engine
with each bucket's fused round routed through the Pallas kernel
(VMEM-resident panel, fp32 accumulation).  The pre-fusion per-round path
(separate aggregate, add, and residual ops) is kept behind
``fused_superstep=False`` as the bench A/B baseline.

Layout (``block_rows``/``width_mult``) and kernel panel sizes default to
the persisted autotune winners for this host + operator shape class
(``repro.engine.autotune``; ``LPConfig.autotune=False`` or explicit
constructor kwargs opt out).  ``storage_dtype="bf16"`` stores operator
weights and the per-round gather panel in bfloat16 with fp32 state and
accumulation.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blocked_csr import (
    blocked_csr_from_network,
    split_blocked_csr_from_network,
)
from repro.core.network import NormalizedNetwork
from repro.core.solver import LPConfig, SolveResult, chunk_columns
from repro.engine import autotune
from repro.engine.base import LPEngine, Operator, register_backend
from repro.kernels.segment_reduce import (
    csr_round_op,
    csr_round_ref,
    csr_round_residual_op,
)

# device-side bucket: (rows, nbr, wgt) with nbr/wgt (R, width)
Bucket = Tuple[jax.Array, jax.Array, jax.Array]

_STORAGE = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def _device_buckets(bcsr) -> Tuple[Tuple[Bucket, ...], jax.Array]:
    """Upload width buckets + the inverse row permutation (legacy path)."""
    buckets = bcsr.width_buckets()
    dev = tuple(
        (
            jnp.asarray(b.rows),
            jnp.asarray(b.nbr),
            jnp.asarray(b.wgt, dtype=jnp.float32),
        )
        for b in buckets
    )
    order = np.concatenate([b.rows for b in buckets])
    inv = np.argsort(order).astype(np.int32)
    return dev, jnp.asarray(inv)


def _bucket_agg(buckets, inv_perm, F):
    """``A @ F`` via per-bucket gather + einsum, back in node order."""
    parts = []
    for _, nbr, wgt in buckets:
        gathered = F[nbr].astype(jnp.float32)  # (R, w, S)
        parts.append(jnp.einsum("rw,rws->rs", wgt, gathered).astype(F.dtype))
    return jnp.concatenate(parts, axis=0)[inv_perm]


def _bucket_round(buckets, inv_perm, F, base, *, beta2: float):
    """Fused kernel round: ``β²·base + A @ F`` per bucket, node order."""
    parts = [
        csr_round_op(nbr, wgt, F, base[rows], c=beta2)
        for rows, nbr, wgt in buckets
    ]
    return jnp.concatenate(parts, axis=0)[inv_perm]


@functools.partial(
    jax.jit,
    static_argnames=(
        "beta2",
        "sigma",
        "max_iter",
        "seed_mode",
        "momentum",
        "use_kernel",
    ),
)
def _dhlp2_csr_loop(
    buckets,
    inv_perm,
    Y,
    F0,
    *,
    beta2: float,
    sigma: float,
    max_iter: int,
    seed_mode: str,
    momentum: float,
    use_kernel: bool,
):
    """Pre-fusion DHLP-2 on blocked-CSR buckets (bench A/B baseline)."""

    def cond(state):
        _, _, active, it, _ = state
        return jnp.logical_and(it < max_iter, jnp.any(active))

    def body(state):
        F, F_prev, active, it, col_iters = state
        base = Y if seed_mode == "fixed" else F
        if use_kernel:
            Fn = _bucket_round(buckets, inv_perm, F, base, beta2=beta2)
        else:
            agg = _bucket_agg(buckets, inv_perm, F)
            Fn = beta2 * base + agg
        if momentum:
            Fn = Fn + momentum * (F - F_prev)
        Fn = jnp.where(active[None, :], Fn, F)
        delta = jnp.max(jnp.abs(Fn - F), axis=0)
        still = jnp.logical_and(active, ~(delta < sigma))
        col_iters = col_iters + active.astype(jnp.int32)
        return Fn, F, still, it + 1, col_iters

    s = Y.shape[1]
    state0 = (
        F0,
        F0,
        jnp.ones((s,), dtype=bool),
        jnp.asarray(0, jnp.int32),
        jnp.zeros((s,), jnp.int32),
    )
    F, _, _, iters, col_iters = jax.lax.while_loop(cond, body, state0)
    return F, iters, col_iters


@functools.partial(
    jax.jit,
    static_argnames=("alpha", "sigma", "max_iter", "max_inner", "seed_mode"),
)
def _dhlp1_csr_loop(
    het_buckets,
    het_inv,
    hom_buckets,
    hom_inv,
    Y,
    F0,
    *,
    alpha: float,
    sigma: float,
    max_iter: int,
    max_inner: int,
    seed_mode: str,
):
    """Pre-fusion DHLP-1 on blocked-CSR (bench A/B baseline)."""
    beta = 1.0 - alpha

    def inner(Yp, F0i, active):
        def icond(istate):
            _, iact, it = istate
            return jnp.logical_and(it < max_inner, jnp.any(iact))

        def ibody(istate):
            F, iact, it = istate
            Fn = beta * Yp + alpha * _bucket_agg(hom_buckets, hom_inv, F)
            Fn = jnp.where(iact[None, :], Fn, F)
            delta = jnp.max(jnp.abs(Fn - F), axis=0)
            return Fn, jnp.logical_and(iact, ~(delta < sigma)), it + 1

        F, _, inner_it = jax.lax.while_loop(
            icond, ibody, (F0i, active, jnp.asarray(0, jnp.int32))
        )
        return F, inner_it

    def cond(state):
        _, active, it, _, _ = state
        return jnp.logical_and(it < max_iter, jnp.any(active))

    def body(state):
        F, active, it, tot_inner, col_iters = state
        src = Y if seed_mode == "fixed" else F
        Yp = beta * src + alpha * _bucket_agg(het_buckets, het_inv, F)
        Fn, inner_it = inner(Yp, F, active)
        Fn = jnp.where(active[None, :], Fn, F)
        delta = jnp.max(jnp.abs(Fn - F), axis=0)
        still = jnp.logical_and(active, ~(delta < sigma))
        col_iters = col_iters + active.astype(jnp.int32)
        return Fn, still, it + 1, tot_inner + inner_it, col_iters

    s = Y.shape[1]
    state0 = (
        F0,
        jnp.ones((s,), dtype=bool),
        jnp.asarray(0, jnp.int32),
        jnp.asarray(0, jnp.int32),
        jnp.zeros((s,), jnp.int32),
    )
    F, _, iters, tot_inner, col_iters = jax.lax.while_loop(cond, body, state0)
    return F, iters, tot_inner, col_iters


# --------------------------------------------------------------------------
# Fused-superstep plan: permuted-space buckets with remapped neighbor ids
# --------------------------------------------------------------------------


#: exact-width re-bucketing policy: a bucket closes when the next row's
#: width drops below ``SLACK`` of the bucket max (once it has at least
#: ``MIN_ROWS`` rows); bucket widths round up to a multiple of 8 so the
#: kernel's width panels stay aligned.  On heavy-tailed graphs this cuts
#: padded nnz ~3x vs the block-rows layout (which pads every row in a
#: 64-row block to the block max).
_TIGHTEN_SLACK = 0.9
_TIGHTEN_MIN_ROWS = 16
_TIGHTEN_ALIGN = 8


def _tighten_buckets(buckets):
    """Re-bucket rows by exact nonzero width (row order is free here).

    The block-rows layout pads every row in a block to the block's max
    width — on power-law degree graphs that is ~2-3x wasted gather+FMA
    per round.  The permuted-space plan owns the row order outright, so
    it can sort all rows by true width and group near-equal widths,
    keeping padding to a few percent.  Zero-weight padding entries are
    dropped (exact: they contribute nothing to the aggregation).

    Returns ``[(rows, nbr, wgt), ...]`` numpy triples, widest first.
    """
    rows_all = np.concatenate([b.rows for b in buckets])
    nbr_all = [b.nbr[i] for b in buckets for i in range(b.nbr.shape[0])]
    wgt_all = [b.wgt[i] for b in buckets for i in range(b.wgt.shape[0])]
    widths = np.array([int((w != 0).sum()) for w in wgt_all])
    order = np.argsort(-widths, kind="stable")
    out = []
    i, n = 0, len(order)
    while i < n:
        wmax = max(int(widths[order[i]]), 1)
        j = i + 1
        while j < n and (
            widths[order[j]] >= _TIGHTEN_SLACK * wmax
            or j - i < _TIGHTEN_MIN_ROWS
        ):
            j += 1
        bw = -(-wmax // _TIGHTEN_ALIGN) * _TIGHTEN_ALIGN
        sel = order[i:j]
        nbr = np.zeros((len(sel), bw), dtype=np.int32)
        wgt = np.zeros((len(sel), bw), dtype=np.float32)
        for k, r in enumerate(sel):
            nz = np.flatnonzero(wgt_all[r])
            nbr[k, : nz.size] = nbr_all[r][nz]
            wgt[k, : nz.size] = wgt_all[r][nz]
        out.append((rows_all[sel], nbr, wgt))
        i = j
    return out


def _device_plan(bcsr, *, storage: str, weight_scale: float = 1.0):
    """Permuted-space bucket plan for the fused-superstep loops.

    Returns ``(buckets, perm, rank)``: ``perm`` is the bucket-concat row
    order (node id at each permuted position), ``rank = argsort(perm)``
    (permuted position of each node id).  Bucket neighbor ids are
    pre-remapped through ``rank`` so rounds gather from — and write to —
    permuted space directly: output rows land contiguously at static
    offsets, no per-round inverse permute.  Rows are re-bucketed by
    exact width (:func:`_tighten_buckets`) — the plan's main perf lever.
    """
    tight = _tighten_buckets(bcsr.width_buckets())
    order = np.concatenate([rows for rows, _, _ in tight])
    rank = np.argsort(order).astype(np.int32)
    wdt = _STORAGE[storage]
    dev = tuple(
        (
            jnp.asarray(rank[nbr]),
            jnp.asarray(weight_scale * wgt, dtype=wdt),
        )
        for _, nbr, wgt in tight
    )
    return dev, jnp.asarray(order.astype(np.int32)), jnp.asarray(rank)


def _plan_round(
    buckets, F, base, *, c, use_kernel, storage, bn, bs, bd
):
    """One fused superstep over a permuted-space plan.

    ``F``/``base`` live in permuted space; returns ``(Fn, delta)`` with
    ``Fn`` permuted-space fp32 and ``delta`` the per-column residual
    ``max_r |Fn − F|`` (exact: the row max is permutation-invariant).

    Two lowerings of the same math: the Pallas path keeps the epilogue
    and residual partials on-chip per bucket (``csr_round_residual``);
    the oracle path only fuses per-bucket gathers — there XLA lowers the
    epilogue + residual best as ONE pass over the whole concatenated
    state, and the f32 accumulator never round-trips through ``storage``.
    Element order is identical either way, so f32 results are
    bit-identical across the two lowerings.
    """
    Fq = F.astype(_STORAGE[storage]) if storage != "f32" else F
    if not use_kernel:
        parts = [
            jnp.einsum(
                "rw,rws->rs",
                wgt.astype(jnp.float32),
                Fq[nbr].astype(jnp.float32),
            )
            for nbr, wgt in buckets
        ]
        Fn = c * base.astype(jnp.float32) + jnp.concatenate(parts, axis=0)
        delta = jnp.max(jnp.abs(Fn - F.astype(jnp.float32)), axis=0)
        return Fn, delta
    parts, dparts = [], []
    off = 0
    for nbr, wgt in buckets:
        m = nbr.shape[0]
        out, dl = csr_round_residual_op(
            nbr,
            wgt,
            Fq,
            base[off : off + m],
            F[off : off + m],
            c=c,
            bn=bn,
            bs=bs,
            bd=bd,
        )
        parts.append(out)
        dparts.append(dl)
        off += m
    Fn = jnp.concatenate(parts, axis=0)
    delta = jnp.max(jnp.concatenate(dparts, axis=0), axis=0)
    return Fn, delta


@functools.partial(
    jax.jit,
    static_argnames=(
        "beta2",
        "sigma",
        "max_iter",
        "seed_mode",
        "momentum",
        "use_kernel",
        "storage",
        "bn",
        "bs",
        "bd",
    ),
)
def _dhlp2_plan_loop(
    buckets,
    perm,
    rank,
    Y,
    F0,
    *,
    beta2: float,
    sigma: float,
    max_iter: int,
    seed_mode: str,
    momentum: float,
    use_kernel: bool,
    storage: str,
    bn: int,
    bs: int,
    bd: int,
):
    """Fused-superstep DHLP-2: state stays in permuted space end to end.

    Entry/exit permutes live inside the jit so a solve is ONE dispatch;
    on small networks the per-call op overhead of out-of-jit gathers
    would otherwise dominate the round work.
    """
    Yp = Y[perm]
    F0p = F0[perm]

    def cond(state):
        _, _, active, it, _ = state
        return jnp.logical_and(it < max_iter, jnp.any(active))

    def body(state):
        F, F_prev, active, it, col_iters = state
        base = Yp if seed_mode == "fixed" else F
        Fn, delta = _plan_round(
            buckets,
            F,
            base,
            c=beta2,
            use_kernel=use_kernel,
            storage=storage,
            bn=bn,
            bs=bs,
            bd=bd,
        )
        if momentum:
            # the kernel residual is pre-momentum; fold the heavy-ball
            # term in and recompute — still gather-free in permuted space
            Fn = Fn + momentum * (F - F_prev)
            delta = jnp.max(jnp.abs(Fn - F), axis=0)
        Fn = jnp.where(active[None, :], Fn, F)
        still = jnp.logical_and(active, ~(delta < sigma))
        col_iters = col_iters + active.astype(jnp.int32)
        return Fn, F, still, it + 1, col_iters

    s = Yp.shape[1]
    state0 = (
        F0p,
        F0p,
        jnp.ones((s,), dtype=bool),
        jnp.asarray(0, jnp.int32),
        jnp.zeros((s,), jnp.int32),
    )
    F, _, _, iters, col_iters = jax.lax.while_loop(cond, body, state0)
    return F[rank], iters, col_iters


@functools.partial(
    jax.jit,
    static_argnames=(
        "alpha",
        "sigma",
        "max_iter",
        "max_inner",
        "seed_mode",
        "use_kernel",
        "storage",
        "bn",
        "bs",
        "bd",
    ),
)
def _dhlp1_plan_loop(
    hom_buckets,
    het_buckets,
    het_base_map,
    reorder,
    hom_perm,
    hom_rank,
    Y,
    F0,
    *,
    alpha: float,
    sigma: float,
    max_iter: int,
    max_inner: int,
    seed_mode: str,
    use_kernel: bool,
    storage: str,
    bn: int,
    bs: int,
    bd: int,
):
    """Fused-superstep DHLP-1: state lives in *hom*-permuted space.

    The inner homogeneous solve dominates the superstep count, so its
    plan is fully gather-free; the outer hetero injection pays one base
    gather (``het_base_map``) and one output regather (``reorder``) per
    outer iteration.  α is folded into both plans' weights, so inner and
    outer rounds are plain fused rounds with ``c = β``.  Entry/exit
    permutes live inside the jit: one dispatch per solve.
    """
    beta = 1.0 - alpha
    Y = Y[hom_perm]
    F0 = F0[hom_perm]

    def inner(Yp, F0i, active):
        def icond(istate):
            _, iact, it = istate
            return jnp.logical_and(it < max_inner, jnp.any(iact))

        def ibody(istate):
            F, iact, it = istate
            Fn, delta = _plan_round(
                hom_buckets,
                F,
                Yp,
                c=beta,
                use_kernel=use_kernel,
                storage=storage,
                bn=bn,
                bs=bs,
                bd=bd,
            )
            Fn = jnp.where(iact[None, :], Fn, F)
            return Fn, jnp.logical_and(iact, ~(delta < sigma)), it + 1

        F, _, inner_it = jax.lax.while_loop(
            icond, ibody, (F0i, active, jnp.asarray(0, jnp.int32))
        )
        return F, inner_it

    def cond(state):
        _, active, it, _, _ = state
        return jnp.logical_and(it < max_iter, jnp.any(active))

    def body(state):
        F, active, it, tot_inner, col_iters = state
        src = Y if seed_mode == "fixed" else F
        Fq = F.astype(_STORAGE[storage]) if storage != "f32" else F
        src_het = src[het_base_map]
        parts = []
        off = 0
        for nbr, wgt in het_buckets:
            m = nbr.shape[0]
            base = src_het[off : off + m]
            if use_kernel:
                part = csr_round_op(
                    nbr, wgt, Fq, base, c=beta, bn=bn, bs=bs, bd=bd
                )
            else:
                part = csr_round_ref(nbr, wgt, Fq, base, beta)
            parts.append(part)
            off += m
        Yp = jnp.concatenate(parts, axis=0)[reorder]
        Fn, inner_it = inner(Yp, F, active)
        Fn = jnp.where(active[None, :], Fn, F)
        delta = jnp.max(jnp.abs(Fn - F), axis=0)
        still = jnp.logical_and(active, ~(delta < sigma))
        col_iters = col_iters + active.astype(jnp.int32)
        return Fn, still, it + 1, tot_inner + inner_it, col_iters

    s = Y.shape[1]
    state0 = (
        F0,
        jnp.ones((s,), dtype=bool),
        jnp.asarray(0, jnp.int32),
        jnp.asarray(0, jnp.int32),
        jnp.zeros((s,), jnp.int32),
    )
    F, _, iters, tot_inner, col_iters = jax.lax.while_loop(cond, body, state0)
    return F[hom_rank], iters, tot_inner, col_iters


class _CSRPayload:
    """Device-resident blocked-CSR operator bundle.

    ``plan``/``split_plan`` are the fused-superstep permuted-space plans;
    ``fused``/``split`` are the legacy node-order bundles (only built
    when ``fused_superstep=False``).  DHLP-1 members stay None for
    DHLP-2 configs and vice versa; ``plan`` is also built lazily for
    DHLP-1 when ``round`` needs the fused operator.
    """

    def __init__(self):
        self.fused = None
        self.fused_inv = None
        self.split = None  # ((het_buckets, het_inv), (hom_buckets, hom_inv))
        self.plan = None  # (buckets, perm, rank)
        self.split_plan = None  # (hom_bk, het_bk, het_base_map, reorder,
        #                          hom_perm, hom_rank)
        self.layout = None  # resolved (block_rows, width_mult)
        self.panels = None  # resolved (bn, bs, bd)


@register_backend("sparse")
class SparseCSREngine(LPEngine):
    """Blocked-CSR width-bucket engine — the default scalability path."""

    supports_momentum = True
    use_kernel = False

    def __init__(
        self,
        config=None,
        *,
        block_rows=None,
        width_mult=None,
        fused_superstep=True,
    ):
        super().__init__(config if config is not None else LPConfig())
        self.block_rows = block_rows  # None = autotuned (or default)
        self.width_mult = width_mult
        self.fused_superstep = fused_superstep
        self._round_jit = None  # built lazily; compiled per (F, Y) shape

    # ---------------------------------------------------------- param wiring
    def _resolve_params(self, norm: NormalizedNetwork) -> autotune.TunedParams:
        """Layout + panel parameters: explicit kwargs > cache > defaults."""
        tuned = None
        if self.config.autotune and (
            self.block_rows is None or self.width_mult is None
        ):
            tuned = autotune.lookup(norm.num_nodes, autotune.network_nnz(norm))
        base = tuned if tuned is not None else autotune.DEFAULT_PARAMS
        return autotune.TunedParams(
            block_rows=self.block_rows or base.block_rows,
            width_mult=self.width_mult or base.width_mult,
            bn=base.bn,
            bs=base.bs,
            bd=base.bd,
        )

    def _build(self, norm: NormalizedNetwork) -> Operator:
        cfg = self.config
        params = self._resolve_params(norm)
        pay = _CSRPayload()
        pay.layout = (params.block_rows, params.width_mult)
        pay.panels = (params.bn, params.bs, params.bd)
        if cfg.alg == "dhlp1":
            het, hom = split_blocked_csr_from_network(
                norm,
                hetero_scale=cfg.resolved_hetero_scale(norm.num_types),
                block_rows=params.block_rows,
                width_mult=params.width_mult,
            )
            if self.fused_superstep:
                hom_bk, hom_perm, hom_rank = _device_plan(
                    hom, storage=cfg.storage_dtype, weight_scale=cfg.alpha
                )
                het_buckets = het.width_buckets()
                het_order = np.concatenate([b.rows for b in het_buckets])
                het_rank = np.argsort(het_order).astype(np.int32)
                hom_rank_np = np.asarray(hom_rank)
                wdt = _STORAGE[cfg.storage_dtype]
                het_bk = tuple(
                    (
                        jnp.asarray(hom_rank_np[b.nbr]),
                        jnp.asarray(cfg.alpha * b.wgt, dtype=wdt),
                    )
                    for b in het_buckets
                )
                het_base_map = jnp.asarray(hom_rank_np[het_order])
                reorder = jnp.asarray(het_rank[np.asarray(hom_perm)])
                pay.split_plan = (
                    hom_bk,
                    het_bk,
                    het_base_map,
                    reorder,
                    hom_perm,
                    hom_rank,
                )
            else:
                pay.split = (_device_buckets(het), _device_buckets(hom))
        op = Operator(
            backend=self.name,
            norm=norm,
            num_nodes=norm.num_nodes,
            payload=pay,
        )
        if cfg.alg == "dhlp2":
            if self.fused_superstep:
                self._fused_plan(op)
            else:
                self._fused_buckets(op)
        return op

    def _fused_bcsr(self, op: Operator):
        cfg = self.config
        br, wm = op.payload.layout
        return blocked_csr_from_network(
            op.norm,
            alpha=cfg.alpha,
            hetero_scale=cfg.resolved_hetero_scale(op.norm.num_types),
            block_rows=br,
            width_mult=wm,
        )

    def _fused_buckets(self, op: Operator):
        """Legacy node-order fused buckets, built on first use."""
        pay: _CSRPayload = op.payload
        if pay.fused is None:
            pay.fused, pay.fused_inv = _device_buckets(self._fused_bcsr(op))
        return pay.fused, pay.fused_inv

    def _fused_plan(self, op: Operator):
        """Permuted-space fused plan, built on first use."""
        pay: _CSRPayload = op.payload
        if pay.plan is None:
            pay.plan = _device_plan(
                self._fused_bcsr(op), storage=self.config.storage_dtype
            )
        return pay.plan

    def solve(
        self,
        op: Operator,
        Y: np.ndarray,
        F0: Optional[np.ndarray] = None,
    ) -> SolveResult:
        cfg = self.config
        Y = np.asarray(Y)
        if Y.ndim == 1:
            Y = Y[:, None]

        chunks = chunk_columns(Y, cfg.seed_chunk)
        f0_chunks = (
            [None] * len(chunks)
            if F0 is None
            else chunk_columns(np.asarray(F0), cfg.seed_chunk)
        )
        parts: List[np.ndarray] = []
        outer, inner_tot, cols = 0, 0, []
        for Yc, F0c in zip(chunks, f0_chunks):
            Yd = jnp.asarray(Yc, jnp.float32)
            F0d = Yd if F0c is None else jnp.asarray(F0c, jnp.float32)
            loop, args, kwargs = self._solve_call(op, Yd, F0d)
            if cfg.alg == "dhlp2":
                F, it, ci = loop(*args, **kwargs)
            else:
                F, it, ti, ci = loop(*args, **kwargs)
                inner_tot += int(ti)
            parts.append(np.asarray(F, np.float64))
            outer = max(outer, int(it))
            cols.append(np.asarray(ci))
        return SolveResult(
            F=np.concatenate(parts, axis=1),
            outer_iters=outer,
            inner_iters=inner_tot,
            converged=bool(outer < cfg.max_iter),
            per_column_iters=np.concatenate(cols),
        )

    def _solve_call(self, op: Operator, Yd, F0d):
        """``(jitted loop, args, static kwargs)`` of one seed chunk's solve."""
        cfg = self.config
        pay: _CSRPayload = op.payload
        beta = 1.0 - cfg.alpha
        common = dict(sigma=cfg.sigma, max_iter=cfg.max_iter,
                      seed_mode=cfg.resolved_seed_mode())
        bn, bs, bd = pay.panels or (256, 128, 16)
        panels = dict(use_kernel=self.use_kernel, storage=cfg.storage_dtype,
                      bn=bn, bs=bs, bd=bd)
        if cfg.alg == "dhlp2":
            common.update(beta2=beta * beta, momentum=cfg.momentum)
            if self.fused_superstep:
                return (_dhlp2_plan_loop, (*self._fused_plan(op), Yd, F0d),
                        {**common, **panels})
            return (_dhlp2_csr_loop, (*self._fused_buckets(op), Yd, F0d),
                    {**common, "use_kernel": self.use_kernel})
        common.update(alpha=cfg.alpha, max_inner=cfg.max_inner)
        if self.fused_superstep:
            return (_dhlp1_plan_loop, (*pay.split_plan, Yd, F0d),
                    {**common, **panels})
        (hb, hi), (mb, mi) = pay.split
        return _dhlp1_csr_loop, (hb, hi, mb, mi, Yd, F0d), common

    def lower(self, op: Operator, Y: np.ndarray):
        """The lowered solve program for seed columns ``Y`` (one chunk).

        ``lower(op, Y).compile().as_text()`` shows what the device runs —
        e.g. whether the ``kernel`` backend's round is a Mosaic custom call.
        """
        Yd = jnp.asarray(Y, jnp.float32)
        loop, args, kwargs = self._solve_call(op, Yd, Yd)
        return loop.lower(*args, **kwargs)

    # -------------------------------------------------------------- rounds
    def _ensure_round_jit(self, op: Operator):
        if self._round_jit is not None:
            return self._round_jit
        cfg = self.config
        beta2 = (1.0 - cfg.alpha) ** 2
        if self.fused_superstep:
            bn, bs, bd = op.payload.panels or (256, 128, 16)
            storage = cfg.storage_dtype
            use_kernel = self.use_kernel

            def _round_impl(buckets, perm, rank, Fc, Yc):
                Fn, delta = _plan_round(
                    buckets,
                    Fc[perm],
                    Yc[perm],
                    c=beta2,
                    use_kernel=use_kernel,
                    storage=storage,
                    bn=bn,
                    bs=bs,
                    bd=bd,
                )
                return Fn[rank], delta

        elif self.use_kernel:

            def _round_impl(buckets, inv, Fc, Yc):
                out = _bucket_round(buckets, inv, Fc, Yc, beta2=beta2)
                return out, jnp.max(jnp.abs(out - Fc), axis=0)

        else:

            def _round_impl(buckets, inv, Fc, Yc):
                out = beta2 * Yc + _bucket_agg(buckets, inv, Fc)
                return out, jnp.max(jnp.abs(out - Fc), axis=0)

        # one jitted program per (F, Y) shape instead of eager per-bucket
        # dispatch — the serve tier's early-exit loop and hint refresh
        # call round once per superstep, so per-call overhead is its hot
        # path.  beta2 folds in as a constant (alpha is frozen per
        # engine).
        self._round_jit = jax.jit(_round_impl)
        return self._round_jit

    def round_with_residual(self, op: Operator, F, Y):
        """One fused superstep + its residual (serve's early-exit unit)."""
        fn = self._ensure_round_jit(op)
        Fd = jnp.asarray(F, jnp.float32)
        Yd = jnp.asarray(Y, jnp.float32)
        if self.fused_superstep:
            buckets, perm, rank = self._fused_plan(op)
            out, delta = fn(buckets, perm, rank, Fd, Yd)
        else:
            fused, fused_inv = self._fused_buckets(op)
            out, delta = fn(fused, fused_inv, Fd, Yd)
        return (
            np.asarray(out, dtype=np.float64),
            np.asarray(delta, dtype=np.float64),
        )

    def round(self, op: Operator, F, Y):
        return self.round_with_residual(op, F, Y)[0]


@register_backend("kernel")
class KernelCSREngine(SparseCSREngine):
    """Blocked-CSR with the fused Pallas superstep kernel per bucket.

    Interpret-mode on CPU, Mosaic on TPU.  Only the fused DHLP-2 round
    has a kernel; DHLP-1's two-phase schedule stays on ``sparse``/
    ``dense``.
    """

    supports_algs = ("dhlp2",)
    use_kernel = True
