"""DHLP-1 / DHLP-2 solvers — dense batched engine.

The paper's Giraph programs are re-expressed as tensor iterations
(DESIGN.md §2):

* one BSP superstep of message passing  ==  one (Sp)MM ``S @ F``
* the per-seed sweep (``y=1`` for one vertex at a time) ==  batched seed
  columns ``Y → F`` (the paper-faithful sequential sweep is kept as
  ``mode="sequential"`` and is the baseline the speedup tables measure
  against)
* ``voteToHalt`` == per-column convergence mask (converged columns freeze)

Engines:
  - :func:`dhlp2_dense` — one fused update per round.
  - :func:`dhlp1_dense` — outer injection + inner homogeneous solve.
Both run under ``jax.jit`` with ``lax.while_loop`` so the whole propagation
is a single XLA program (the distributed story lives in
``repro/parallel/lp_sharded.py``).
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Literal, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.network import (
    HeteroCOO,
    HeteroNetwork,
    NormalizedNetwork,
    seeds_identity,
)
from repro.kernels.common import F32_MATMUL

Algorithm = Literal["dhlp1", "dhlp2"]
SeedMode = Literal["fixed", "drift"]


@dataclasses.dataclass(frozen=True)
class LPConfig:
    """Solver hyper-parameters (paper Table 1 symbols α, σ)."""

    alg: Algorithm = "dhlp2"
    alpha: float = 0.5
    sigma: float = 1e-3
    max_iter: int = 1000  # outer-iteration cap (DHLP-2 rounds)
    max_inner: int = 200  # DHLP-1 inner-loop cap
    seed_mode: Optional[SeedMode] = None  # default: per-pseudocode
    mode: Literal["batched", "sequential"] = "batched"
    seed_chunk: int = 0  # 0 = all seeds in one program
    dtype: jnp.dtype = jnp.float32
    fused: bool = True  # DHLP-2: pre-combine αβH + αM (beyond-paper)
    # Execution backend, a `repro.engine` registry key ("dense", "sparse",
    # "sharded", "kernel", "auto").  None lets the caller decide (HeteroLP
    # stays dense, serve/launch/bench pick via registry).
    backend: Optional[str] = None
    # DEPRECATED — use backend="kernel".  Routes the dense fused round
    # through the Pallas lp_blockspmm kernel (interpret-mode on CPU; Mosaic
    # on TPU).  Constructing LPConfig(use_kernel=True) without an explicit
    # backend warns and maps to backend="kernel" (see __post_init__).
    use_kernel: bool = False
    # Heavy-ball acceleration (beyond-paper): F ← β²·base + A·F_t
    # + momentum·(F_t − F_{t−1}).  Same fixed point (fixed-seed mode), the
    # spectral radius of the iteration drops from ρ to ~√ρ-ish, cutting
    # rounds — and every roofline term of a solve scales with rounds.
    momentum: float = 0.0
    # The paper's pseudocode applies a uniform α to ALL heterogeneous
    # neighbors.  With T>2 node types the cross-type operator H then has
    # spectral radius up to T−1 and the iteration can diverge (MINProp's
    # convergence condition is that the cross-subnetwork coefficients sum
    # below 1).  ``None`` = auto-scale H by 1/(T−1); pass 1.0 for the
    # strictly-literal paper update.
    hetero_scale: Optional[float] = None
    # Mixed precision (sparse/kernel backends): "bf16" stores operator
    # weights and the per-round gather panel in bfloat16 while state and
    # accumulation stay fp32 — halves superstep memory traffic at a
    # slightly shifted fixed point (gated by agree_dense/recovery-AUC in
    # the bench matrix).  "f32" is exact.
    storage_dtype: Literal["f32", "bf16"] = "f32"
    # Consult the persisted autotune cache (repro.engine.autotune) for
    # blocked-CSR layout + kernel panel parameters.  A cold cache falls
    # back to the defaults; False pins the defaults unconditionally.
    autotune: bool = True

    def __post_init__(self) -> None:
        if self.storage_dtype not in ("f32", "bf16"):
            raise ValueError(
                f"storage_dtype must be 'f32' or 'bf16', got "
                f"{self.storage_dtype!r}"
            )
        if self.use_kernel and self.backend is None:
            warnings.warn(
                "LPConfig(use_kernel=True) is deprecated; use "
                "LPConfig(backend='kernel') — the engine registry routes it "
                "through the fused blocked-CSR Pallas round (DESIGN.md §11)",
                DeprecationWarning,
                stacklevel=3,
            )
            object.__setattr__(self, "backend", "kernel")

    def resolved_hetero_scale(self, num_types: int) -> float:
        if self.hetero_scale is not None:
            return float(self.hetero_scale)
        return 1.0 / max(1, num_types - 1)

    def resolved_seed_mode(self) -> SeedMode:
        if self.seed_mode is not None:
            return self.seed_mode
        # Pseudocode defaults: DHLP-1 reads gety() (fixed seed), DHLP-2
        # reads getf() (drifting seed).
        return "fixed" if self.alg == "dhlp1" else "drift"


@dataclasses.dataclass
class SolveResult:
    F: np.ndarray  # (N, S) final labels
    outer_iters: int  # rounds until all columns converged
    inner_iters: int  # DHLP-1 total inner iterations (0 for -2)
    converged: bool
    per_column_iters: Optional[np.ndarray] = None

    @property
    def supersteps(self) -> int:
        """Giraph superstep count equivalent (2 messages rounds per iter)."""
        return 2 * self.outer_iters + self.inner_iters


# --------------------------------------------------------------------------
# DHLP-2  (distributed Heter-LP)
# --------------------------------------------------------------------------
@functools.partial(
    jax.jit, static_argnames=("alpha", "sigma", "max_iter", "seed_mode")
)
def _dhlp2_step_loop(
    H: jax.Array,
    M: jax.Array,
    Y: jax.Array,
    F0: jax.Array,
    *,
    alpha: float,
    sigma: float,
    max_iter: int,
    seed_mode: str,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Un-fused (paper-faithful) DHLP-2: two propagation ops per round.

    ``F0`` is the warm-start state (pass ``Y`` for a cold solve).  In
    fixed-seed mode the fixed point is independent of ``F0``, so a warm
    start reaches the same answer in fewer rounds (DESIGN.md §9).
    """
    beta = 1.0 - alpha
    acc = jnp.float32

    def cond(state):
        _, active, it, _ = state
        return jnp.logical_and(it < max_iter, jnp.any(active))

    def body(state):
        F, active, it, col_iters = state
        src = Y if seed_mode == "fixed" else F
        # superstep A: heterogeneous injection  y' = βy + αHF
        Yp = beta * src + alpha * jnp.matmul(
            H, F, preferred_element_type=acc, precision=F32_MATMUL
        ).astype(F.dtype)
        # superstep B: homogeneous propagation  f = βy' + αMF
        Fn = beta * Yp + alpha * jnp.matmul(
            M, F, preferred_element_type=acc, precision=F32_MATMUL
        ).astype(F.dtype)
        Fn = jnp.where(active[None, :], Fn, F)  # voteToHalt: freeze
        delta = jnp.max(jnp.abs(Fn - F), axis=0)
        still = jnp.logical_and(active, ~(delta < sigma))
        col_iters = col_iters + active.astype(jnp.int32)
        return Fn, still, it + 1, col_iters

    s = Y.shape[1]
    state0 = (
        F0,
        jnp.ones((s,), dtype=bool),
        jnp.asarray(0, jnp.int32),
        jnp.zeros((s,), jnp.int32),
    )
    F, active, iters, col_iters = jax.lax.while_loop(cond, body, state0)
    return F, iters, col_iters


@functools.partial(
    jax.jit,
    static_argnames=("sigma", "max_iter", "seed_mode", "momentum", "use_kernel"),
)
def _dhlp2_fused_loop(
    A_eff: jax.Array,
    beta2: jax.Array,
    Y: jax.Array,
    F0: jax.Array,
    *,
    sigma: float,
    max_iter: int,
    seed_mode: str,
    momentum: float = 0.0,
    use_kernel: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused DHLP-2: one SpMM per round (DESIGN.md §2).

      drift:  F ← β²F + A_eff @ F
      fixed:  F ← β²Y + A_eff @ F [+ μ(F − F_prev) heavy-ball]

    ``F0`` warm-starts the iteration (pass ``Y`` for cold; DESIGN.md §9).
    """
    acc = jnp.float32

    def cond(state):
        _, _, active, it, _ = state
        return jnp.logical_and(it < max_iter, jnp.any(active))

    def body(state):
        F, F_prev, active, it, col_iters = state
        base = Y if seed_mode == "fixed" else F
        if use_kernel:
            from repro.kernels.lp_blockspmm import lp_round_op

            # beta2 is traced; fold it into the base operand (c stays
            # static for the kernel's BlockSpec closure).  use_kernel=True
            # here forces the kernel path: when the config opts in (e.g.
            # the bench backend matrix), the op's size heuristic must not
            # silently fall back to the jnp reference.
            Fn = lp_round_op(A_eff, F, beta2 * base, c=1.0, use_kernel=True)
        else:
            Fn = beta2 * base + jnp.matmul(
                A_eff, F, preferred_element_type=acc, precision=F32_MATMUL
            ).astype(F.dtype)
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
    F, _, active, iters, col_iters = jax.lax.while_loop(cond, body, state0)
    return F, iters, col_iters


# --------------------------------------------------------------------------
# DHLP-1  (distributed MINProp)
# --------------------------------------------------------------------------
@functools.partial(
    jax.jit,
    static_argnames=("alpha", "sigma", "max_iter", "max_inner", "seed_mode"),
)
def _dhlp1_loop(
    H: jax.Array,
    M: jax.Array,
    Y: jax.Array,
    F0: jax.Array,
    *,
    alpha: float,
    sigma: float,
    max_iter: int,
    max_inner: int,
    seed_mode: str,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """DHLP-1: outer hetero injection, inner homogeneous iterative solve.

    Pseudocode mapping: lines 1–10 (phase A, compute y′ and message) are the
    outer body's first op; lines 11–24 (phase B, iterate f_t until
    |current−last| < σ, then check outer |f − f_old| < σ) are the inner
    while_loop.
    """
    beta = 1.0 - alpha
    acc = jnp.float32

    def inner(Yp, F0, active):
        """Solve F = βY' + αMF to tolerance σ on active columns."""

        def icond(istate):
            _, iact, it = istate
            return jnp.logical_and(it < max_inner, jnp.any(iact))

        def ibody(istate):
            F, iact, it = istate
            Fn = beta * Yp + alpha * jnp.matmul(
                M, F, preferred_element_type=acc, precision=F32_MATMUL
            ).astype(F.dtype)
            Fn = jnp.where(iact[None, :], Fn, F)
            delta = jnp.max(jnp.abs(Fn - F), axis=0)
            return Fn, jnp.logical_and(iact, ~(delta < sigma)), it + 1

        F, _, inner_it = jax.lax.while_loop(
            icond, ibody, (F0, active, jnp.asarray(0, jnp.int32))
        )
        return F, inner_it

    def cond(state):
        _, active, it, _, _ = state
        return jnp.logical_and(it < max_iter, jnp.any(active))

    def body(state):
        F, active, it, tot_inner, col_iters = state
        src = Y if seed_mode == "fixed" else F
        Yp = beta * src + alpha * jnp.matmul(
            H, F, preferred_element_type=acc, precision=F32_MATMUL
        ).astype(F.dtype)
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
    F, active, iters, tot_inner, col_iters = jax.lax.while_loop(
        cond, body, state0
    )
    return F, iters, tot_inner, col_iters


# --------------------------------------------------------------------------
# Public solver
# --------------------------------------------------------------------------
class HeteroLP:
    """The paper's contribution as a composable module.

    >>> solver = HeteroLP(LPConfig(alg="dhlp2", alpha=0.5, sigma=1e-3))
    >>> result = solver.run(net)          # all-sources propagation
    """

    def __init__(self, config: LPConfig = LPConfig()):
        self.config = config

    # -- assembly ----------------------------------------------------------
    @staticmethod
    def _prepare(net) -> NormalizedNetwork:
        return coerce_normalized(net)

    # -- main entry ---------------------------------------------------------
    def run(
        self,
        net,
        seeds: Optional[np.ndarray] = None,
        F0: Optional[np.ndarray] = None,
    ) -> SolveResult:
        """Solve LP on ``net`` from ``seeds``.

        ``F0`` (same shape as ``seeds``) warm-starts the iteration from a
        previous solution — the fixed point is unchanged in fixed-seed mode
        and already-converged columns freeze in round 0 (DESIGN.md §9).
        """
        cfg = self.config
        norm = self._prepare(net)
        n = norm.num_nodes
        Y = seeds_identity(n) if seeds is None else np.asarray(seeds)
        if Y.ndim == 1:
            Y = Y[:, None]
        if Y.shape[0] != n:
            raise ValueError(f"seeds must have {n} rows, got {Y.shape}")
        if F0 is not None:
            F0 = np.asarray(F0)
            if F0.ndim == 1:
                F0 = F0[:, None]
            if F0.shape != Y.shape:
                raise ValueError(
                    f"F0 shape {F0.shape} must match seeds shape {Y.shape}"
                )

        if cfg.mode == "sequential":
            return self._run_sequential(norm, Y, F0)
        return self._run_batched(norm, Y, F0)

    # -- batched ------------------------------------------------------------
    def _run_batched(
        self,
        norm: NormalizedNetwork,
        Y: np.ndarray,
        F0: Optional[np.ndarray] = None,
    ) -> SolveResult:
        cfg = self.config
        chunks = self._chunk_columns(Y, cfg.seed_chunk)
        f0_chunks = (
            [None] * len(chunks)
            if F0 is None
            else self._chunk_columns(F0, cfg.seed_chunk)
        )
        F_parts, outer, inner, col_iters = [], 0, 0, []
        arrays = self._device_arrays(norm)
        for Yc, F0c in zip(chunks, f0_chunks):
            Yd = jnp.asarray(Yc, dtype=cfg.dtype)
            F0d = Yd if F0c is None else jnp.asarray(F0c, dtype=cfg.dtype)
            if cfg.alg == "dhlp2":
                if cfg.fused:
                    A_eff, beta2 = arrays["fused"]
                    F, it, ci = _dhlp2_fused_loop(
                        A_eff,
                        beta2,
                        Yd,
                        F0d,
                        sigma=cfg.sigma,
                        max_iter=cfg.max_iter,
                        seed_mode=cfg.resolved_seed_mode(),
                        momentum=cfg.momentum,
                        use_kernel=cfg.use_kernel,
                    )
                else:
                    H, M = arrays["split"]
                    F, it, ci = _dhlp2_step_loop(
                        H,
                        M,
                        Yd,
                        F0d,
                        alpha=cfg.alpha,
                        sigma=cfg.sigma,
                        max_iter=cfg.max_iter,
                        seed_mode=cfg.resolved_seed_mode(),
                    )
                ii = 0
            else:
                H, M = arrays["split"]
                F, it, tot_inner, ci = _dhlp1_loop(
                    H,
                    M,
                    Yd,
                    F0d,
                    alpha=cfg.alpha,
                    sigma=cfg.sigma,
                    max_iter=cfg.max_iter,
                    max_inner=cfg.max_inner,
                    seed_mode=cfg.resolved_seed_mode(),
                )
                ii = int(tot_inner)
            F_parts.append(np.asarray(F, dtype=np.float64))
            outer = max(outer, int(it))
            inner += ii
            col_iters.append(np.asarray(ci))
        F = np.concatenate(F_parts, axis=1)
        col = np.concatenate(col_iters)
        return SolveResult(
            F=F,
            outer_iters=outer,
            inner_iters=inner,
            converged=bool(outer < cfg.max_iter),
            per_column_iters=col,
        )

    # -- sequential (paper-faithful per-seed sweep) --------------------------
    def _run_sequential(
        self,
        norm: NormalizedNetwork,
        Y: np.ndarray,
        F0: Optional[np.ndarray] = None,
    ) -> SolveResult:
        """One seed at a time, exactly like the Giraph sweep.

        Kept as the faithful baseline; the batched mode is the beyond-paper
        optimization (DESIGN.md §2).  Runtime difference between the two is
        the repro analogue of the paper's distributed-vs-non-distributed
        Tables 5/6.
        """
        cfg = self.config
        arrays = self._device_arrays(norm)
        cols, outer, inner, per_col = [], 0, 0, []
        for c in range(Y.shape[1]):
            Yc = jnp.asarray(Y[:, c : c + 1], dtype=cfg.dtype)
            F0c = (
                Yc
                if F0 is None
                else jnp.asarray(F0[:, c : c + 1], dtype=cfg.dtype)
            )
            if cfg.alg == "dhlp2":
                H, M = arrays["split"]
                F, it, ci = _dhlp2_step_loop(
                    H,
                    M,
                    Yc,
                    F0c,
                    alpha=cfg.alpha,
                    sigma=cfg.sigma,
                    max_iter=cfg.max_iter,
                    seed_mode=cfg.resolved_seed_mode(),
                )
                ii = 0
            else:
                H, M = arrays["split"]
                F, it, tot_inner, ci = _dhlp1_loop(
                    H,
                    M,
                    Yc,
                    F0c,
                    alpha=cfg.alpha,
                    sigma=cfg.sigma,
                    max_iter=cfg.max_iter,
                    max_inner=cfg.max_inner,
                    seed_mode=cfg.resolved_seed_mode(),
                )
                ii = int(tot_inner)
            cols.append(np.asarray(F, dtype=np.float64))
            outer = max(outer, int(it))
            inner += ii
            per_col.append(int(ci[0]))
        return SolveResult(
            F=np.concatenate(cols, axis=1),
            outer_iters=outer,
            inner_iters=inner,
            converged=True,
            per_column_iters=np.asarray(per_col, np.int32),
        )

    # -- helpers -------------------------------------------------------------
    def operator_arrays(self, norm: NormalizedNetwork):
        """Device-resident dense operator arrays, cached per network.

        Public so the engine layer (``repro/engine/dense.py``) can reuse the
        prepared ``split``/``fused`` arrays for its ``round`` contract.
        """
        return self._device_arrays(norm)

    def _device_arrays(self, norm: NormalizedNetwork):
        cfg = self.config
        # key by identity of the live object (held in the cache entry, so
        # the address can't be recycled for a different network)
        cache = getattr(self, "_cache", None)
        if cache is not None and cache[0] is norm:
            return cache[1]
        H, M = norm.assemble_dense()
        H = H * cfg.resolved_hetero_scale(norm.num_types)
        out = {
            "split": (
                jnp.asarray(H, dtype=cfg.dtype),
                jnp.asarray(M, dtype=cfg.dtype),
            )
        }
        if cfg.alg == "dhlp2" and cfg.fused:
            beta = 1.0 - cfg.alpha
            A_eff = cfg.alpha * beta * H + cfg.alpha * M
            out["fused"] = (
                jnp.asarray(A_eff, dtype=cfg.dtype),
                jnp.asarray(beta * beta, dtype=jnp.float32),
            )
        self._cache = (norm, out)
        return out

    @staticmethod
    def _chunk_columns(Y: np.ndarray, chunk: int):
        return chunk_columns(Y, chunk)


def chunk_columns(Y: np.ndarray, chunk: int):
    """Split seed/state columns into ``chunk``-wide slices (0 = no split).

    Shared by every engine that honors ``LPConfig.seed_chunk`` — one copy
    of the boundary rule, not one per backend.
    """
    if chunk <= 0 or chunk >= Y.shape[1]:
        return [Y]
    return [Y[:, i : i + chunk] for i in range(0, Y.shape[1], chunk)]


def coerce_normalized(net) -> NormalizedNetwork:
    """Accept a raw or normalized network; the one coercion boundary.

    Shared by :class:`HeteroLP` and the engine registry
    (``repro/engine/base.py``) so the accepted-input rule cannot drift.
    """
    if isinstance(net, HeteroNetwork):
        return net.normalize()
    if isinstance(net, NormalizedNetwork):
        return net
    raise TypeError(f"unsupported network type {type(net)}")
