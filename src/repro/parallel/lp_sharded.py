"""Distributed LP: shard_map engine over a device mesh.

2-D decomposition (DESIGN.md §6):

* seed columns sharded over the ``data`` axis — columns are independent
  propagations, so this axis needs NO communication (the Giraph analogue is
  running disjoint seed sweeps on disjoint workers, which the paper cannot
  do because its sweep is sequential);
* edges sharded over the ``model`` axis — each shard owns E/k edges,
  computes a partial aggregate for ALL nodes, and a ``psum`` over the edge
  axis completes the superstep (the Giraph analogue is workers exchanging
  messages at the superstep barrier).

Per-device state: F_local (N, s/data). Per-iteration wire traffic:
one psum of (N, s/data) over the ``model`` axis — this is THE collective
the roofline analysis tracks for the LP core.

Straggler mitigation (beyond-paper): ``stale_sync=k`` refreshes the remote
contribution every k rounds only — between refreshes a shard iterates with
its own edges live and others' aggregates stale.  For a contraction mapping
this still converges (the stale operator is a perturbed contraction), and it
cuts the collective term by ~k×; the tests assert fixed-point agreement.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.blocked_csr import (
    blocked_csr_from_network,
    split_blocked_csr_from_network,
)
from repro.core.network import NormalizedNetwork
from repro.core.solver import LPConfig, SolveResult
from repro.graph.segment import segment_sum
from repro.parallel.collectives import compressed_psum


@dataclasses.dataclass
class ShardedLPArrays:
    """Host-side prepared arrays: edge shards stacked on a leading axis."""

    src: np.ndarray   # (k, Ep) int32 — fused operator edges
    dst: np.ndarray   # (k, Ep) int32
    w: np.ndarray     # (k, Ep) float32 (pre-scaled: αβ·scale·het ∪ α·hom)
    num_nodes: int
    beta2: float


def _shard_edges(src, dst, w, num_edge_shards: int):
    """Slice a destination-sorted edge triple into k equal shards.

    Inputs come from ``BlockedCSR.to_edges(include_pads=False)``: slots
    are row-major (dst non-decreasing) with the zero-weight tile padding
    already dropped, so equal slices are destination-contiguous — each
    shard's segment-sum output band stays localized, same property the
    COO prep sorted for — and a segment-sum never touches pad slots
    (which on skewed graphs would multiply per-superstep work).
    """
    e = src.shape[0]
    per = max(1, -(-e // num_edge_shards))
    pad = per * num_edge_shards - e
    src = np.concatenate([src, np.zeros(pad, np.int32)])
    dst = np.concatenate([dst, np.zeros(pad, np.int32)])
    w = np.concatenate([w.astype(np.float32), np.zeros(pad, np.float32)])
    return (
        src.reshape(num_edge_shards, per),
        dst.reshape(num_edge_shards, per),
        w.reshape(num_edge_shards, per),
    )


def place_edge_shards(mesh: Mesh, edge_axis: str, arrays) -> Tuple[jax.Array, ...]:
    """Upload stacked ``(k, Ep)`` edge shards one shard per device.

    A plain ``jnp.asarray`` would stage every shard whole on the first
    device; with the mesh sharding each device receives only its own.
    """
    sharding = NamedSharding(mesh, P(edge_axis, None))
    return tuple(jax.device_put(a, sharding) for a in arrays)


def place_label_panel(mesh: Mesh, seed_axis: str, x: np.ndarray) -> jax.Array:
    """Upload an ``(N, S)`` label panel split by columns over the seed axis."""
    sharding = NamedSharding(mesh, P(None, seed_axis))
    return jax.device_put(np.asarray(x, np.float32), sharding)


def prepare_sharded_operator(
    norm: NormalizedNetwork, cfg: LPConfig, num_edge_shards: int
) -> ShardedLPArrays:
    """Fused DHLP-2 operator as edge shards, derived from blocked-CSR.

    The blocked-CSR operator (DESIGN.md §11) is the shared format: shards
    are flat slices of its row-major slot storage, so the sharded engine
    consumes exactly the operator the sparse/kernel engines aggregate.
    """
    scale = cfg.resolved_hetero_scale(norm.num_types)
    beta = 1.0 - cfg.alpha
    bcsr = blocked_csr_from_network(
        norm, alpha=cfg.alpha, hetero_scale=scale
    )
    src, dst, w = bcsr.to_edges(include_pads=False)
    src, dst, w = _shard_edges(src, dst, w, num_edge_shards)
    return ShardedLPArrays(
        src=src,
        dst=dst,
        w=w,
        num_nodes=norm.num_nodes,
        beta2=beta * beta,
    )


def build_sharded_dhlp2(
    mesh: Mesh,
    *,
    num_nodes: int,
    beta2: float,
    sigma: float,
    max_iter: int,
    seed_mode: str,
    edge_axis: str = "model",
    seed_axis: str = "data",
    stale_sync: int = 1,
    compression: str = "none",
):
    """Returns a jit-compiled sharded DHLP-2 solver fn(src, dst, w, Y, F0).

    Input shardings: edge arrays P(edge_axis, None); Y and the warm-start
    state F0 P(None, seed_axis) (pass Y as F0 for a cold solve).
    Output: F with P(None, seed_axis), iteration count (replicated).
    """

    def shard_body(src, dst, w, Y, F0):
        # src/dst/w: (1, Ep) local edge shard; Y/F0: (N, s_local)
        src, dst, w = src[0], dst[0], w[0]
        Y = Y.astype(jnp.float32)
        F0 = F0.astype(jnp.float32)

        def local_agg(F):
            msgs = w[:, None] * F[src]
            return segment_sum(msgs, dst, num_nodes)

        # The loop predicate must be uniform across EVERY device in the
        # mesh: collectives inside a while body deadlock if participants
        # disagree on the trip count (seed shards converge at different
        # rounds, and the mesh's device assignment may place them in the
        # same collective clique).  We carry a globally-reduced
        # "anyone still active" scalar — a 4-byte pmax per round.
        def cond(state):
            _, _, it, _, _, global_active = state
            return jnp.logical_and(it < max_iter, global_active > 0)

        def body(state):
            F, active, it, col_iters, remote, _ = state
            base = Y if seed_mode == "fixed" else F
            local = local_agg(F)
            if stale_sync <= 1:
                agg = compressed_psum(
                    local, edge_axis, compression=compression
                )
                remote_n = agg - local  # kept for state-shape stability
            else:
                # staleness switch must also be trip-uniform: it is a pure
                # function of `it`, which is uniform by construction.
                do_sync = (it % stale_sync) == 0
                fresh = lax.cond(
                    do_sync,
                    lambda l: compressed_psum(
                        l, edge_axis, compression=compression
                    ) - l,
                    lambda l: remote,
                    local,
                )
                remote_n = fresh
                agg = local + fresh
            Fn = beta2 * base + agg
            Fn = jnp.where(active[None, :], Fn, F)
            delta = jnp.max(jnp.abs(Fn - F), axis=0)
            still = jnp.logical_and(active, ~(delta < sigma))
            col_iters = col_iters + active.astype(jnp.int32)
            ga = lax.pmax(
                jnp.any(still).astype(jnp.int32), (seed_axis, edge_axis)
            )
            return Fn, still, it + 1, col_iters, remote_n, ga

        s = Y.shape[1]
        state0 = (
            F0,
            jnp.ones((s,), dtype=bool),
            jnp.asarray(0, jnp.int32),
            jnp.zeros((s,), jnp.int32),
            jnp.zeros((num_nodes, s), jnp.float32),
            jnp.asarray(1, jnp.int32),
        )
        F, _, iters, col_iters, _, _ = lax.while_loop(cond, body, state0)
        # iteration counts differ across seed shards; report local columns'.
        return F, jnp.reshape(iters, (1,)), col_iters

    mapped = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(edge_axis, None),
            P(edge_axis, None),
            P(edge_axis, None),
            P(None, seed_axis),
            P(None, seed_axis),
        ),
        out_specs=(P(None, seed_axis), P(seed_axis), P(seed_axis)),
        check_vma=False,
    )
    return jax.jit(mapped)


def build_sharded_round(
    mesh: Mesh,
    *,
    num_nodes: int,
    beta2: float,
    edge_axis: str = "model",
    seed_axis: str = "data",
    compression: str = "none",
):
    """One fused fixed-seed DHLP-2 round on fused edge shards.

    The engine ``round`` contract (DESIGN.md §11.1): ``β²Y + A_eff @ F``
    with the same edge-sharded aggregation + psum as one superstep of the
    full solver — serve-side incremental hint refresh on a pod runs this
    per demoted column batch.
    """

    def shard_body(src, dst, w, F, Y):
        src, dst, w = src[0], dst[0], w[0]
        F = F.astype(jnp.float32)
        Y = Y.astype(jnp.float32)
        local = segment_sum(w[:, None] * F[src], dst, num_nodes)
        agg = compressed_psum(local, edge_axis, compression=compression)
        return beta2 * Y + agg

    mapped = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(edge_axis, None),
            P(edge_axis, None),
            P(edge_axis, None),
            P(None, seed_axis),
            P(None, seed_axis),
        ),
        out_specs=P(None, seed_axis),
        check_vma=False,
    )
    return jax.jit(mapped)


def build_sharded_dhlp1(
    mesh: Mesh,
    *,
    num_nodes: int,
    alpha: float,
    sigma: float,
    max_iter: int,
    max_inner: int,
    seed_mode: str,
    edge_axis: str = "model",
    seed_axis: str = "data",
    compression: str = "none",
):
    """Sharded DHLP-1: outer hetero injection + inner homogeneous solve.

    Takes SEPARATE hetero and homo edge shards (the algorithms mix them
    with different schedules).  Both loops carry globally-uniform
    predicates (pmax over the whole mesh) so in-loop collectives cannot
    deadlock across shards — same discipline as the DHLP-2 engine.
    """
    beta = 1.0 - alpha

    def shard_body(h_src, h_dst, h_w, m_src, m_dst, m_w, Y, F0):
        h_src, h_dst, h_w = h_src[0], h_dst[0], h_w[0]
        m_src, m_dst, m_w = m_src[0], m_dst[0], m_w[0]
        Y = Y.astype(jnp.float32)
        F0 = F0.astype(jnp.float32)

        def agg(src, dst, w, F):
            local = segment_sum(w[:, None] * F[src], dst, num_nodes)
            return compressed_psum(local, edge_axis, compression=compression)

        def inner(Yp, F0, active):
            def icond(istate):
                _, _, it, ga = istate
                return jnp.logical_and(it < max_inner, ga > 0)

            def ibody(istate):
                F, iact, it, _ = istate
                Fn = beta * Yp + alpha * agg(m_src, m_dst, m_w, F)
                Fn = jnp.where(iact[None, :], Fn, F)
                delta = jnp.max(jnp.abs(Fn - F), axis=0)
                still = jnp.logical_and(iact, ~(delta < sigma))
                ga = lax.pmax(
                    jnp.any(still).astype(jnp.int32), (seed_axis, edge_axis)
                )
                return Fn, still, it + 1, ga

            F, _, inner_it, _ = lax.while_loop(
                icond, ibody,
                (F0, active, jnp.asarray(0, jnp.int32),
                 jnp.asarray(1, jnp.int32)),
            )
            return F, inner_it

        def cond(state):
            _, _, it, _, ga = state
            return jnp.logical_and(it < max_iter, ga > 0)

        def body(state):
            F, active, it, tot_inner, _ = state
            src_lbl = Y if seed_mode == "fixed" else F
            Yp = beta * src_lbl + alpha * agg(h_src, h_dst, h_w, F)
            Fn, inner_it = inner(Yp, F, active)
            Fn = jnp.where(active[None, :], Fn, F)
            delta = jnp.max(jnp.abs(Fn - F), axis=0)
            still = jnp.logical_and(active, ~(delta < sigma))
            ga = lax.pmax(
                jnp.any(still).astype(jnp.int32), (seed_axis, edge_axis)
            )
            return Fn, still, it + 1, tot_inner + inner_it, ga

        s = Y.shape[1]
        state0 = (
            F0,
            jnp.ones((s,), dtype=bool),
            jnp.asarray(0, jnp.int32),
            jnp.asarray(0, jnp.int32),
            jnp.asarray(1, jnp.int32),
        )
        F, _, iters, tot_inner, _ = lax.while_loop(cond, body, state0)
        return F, jnp.reshape(iters, (1,)), jnp.reshape(tot_inner, (1,))

    mapped = jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(
            P(edge_axis, None), P(edge_axis, None), P(edge_axis, None),
            P(edge_axis, None), P(edge_axis, None), P(edge_axis, None),
            P(None, seed_axis),
            P(None, seed_axis),
        ),
        out_specs=(P(None, seed_axis), P(seed_axis), P(seed_axis)),
        check_vma=False,
    )
    return jax.jit(mapped)


def _prepare_split_operator(
    norm: NormalizedNetwork, cfg: LPConfig, num_edge_shards: int
):
    """Hetero and homo edge shards (scaled) from the blocked-CSR operators.

    The blocked-CSR pair is the same format the sparse engine's DHLP-1
    buckets aggregate; its row-major slots flatten to destination-sorted
    shards directly.
    """
    scale = cfg.resolved_hetero_scale(norm.num_types)
    het_csr, hom_csr = split_blocked_csr_from_network(
        norm, hetero_scale=scale
    )
    het = _shard_edges(*het_csr.to_edges(include_pads=False), num_edge_shards)
    hom = _shard_edges(*hom_csr.to_edges(include_pads=False), num_edge_shards)
    return het, hom


@dataclasses.dataclass
class ShardedPrepared:
    """Device-ready operator shards + the compiled solver for one mesh."""

    mesh: Mesh
    num_nodes: int
    arrays: Tuple[jax.Array, ...]
    solver: object
    alg: str
    edge_axis: str
    seed_axis: str


class ShardedHeteroLP:
    """Distributed solver running on an explicit device mesh."""

    def __init__(
        self,
        config: LPConfig = LPConfig(),
        *,
        stale_sync: int = 1,
        compression: str = "none",
    ):
        self.config = config
        self.stale_sync = stale_sync
        self.compression = compression
        self._prep_cache: Optional[Tuple[object, Mesh, ShardedPrepared]] = None

    def prepare(
        self,
        norm: NormalizedNetwork,
        mesh: Mesh,
        *,
        edge_axis: str = "model",
        seed_axis: str = "data",
    ) -> ShardedPrepared:
        """Shard the operator and build the compiled solver once per
        (network, mesh, axes) — repeat solves skip re-upload AND re-trace."""
        cache = self._prep_cache
        if (
            cache is not None
            and cache[0] is norm
            and cache[1] is mesh
            and cache[2].edge_axis == edge_axis
            and cache[2].seed_axis == seed_axis
        ):
            return cache[2]
        cfg = self.config
        k_edges = mesh.shape[edge_axis]
        n = norm.num_nodes
        if cfg.alg == "dhlp1":
            het, hom = _prepare_split_operator(norm, cfg, k_edges)
            solver = build_sharded_dhlp1(
                mesh,
                num_nodes=n,
                alpha=cfg.alpha,
                sigma=cfg.sigma,
                max_iter=cfg.max_iter,
                max_inner=cfg.max_inner,
                seed_mode=cfg.resolved_seed_mode(),
                edge_axis=edge_axis,
                seed_axis=seed_axis,
                compression=self.compression,
            )
            arrays = place_edge_shards(mesh, edge_axis, (*het, *hom))
        else:
            arrs = prepare_sharded_operator(norm, cfg, k_edges)
            solver = build_sharded_dhlp2(
                mesh,
                num_nodes=n,
                beta2=arrs.beta2,
                sigma=cfg.sigma,
                max_iter=cfg.max_iter,
                seed_mode=cfg.resolved_seed_mode(),
                edge_axis=edge_axis,
                seed_axis=seed_axis,
                stale_sync=self.stale_sync,
                compression=self.compression,
            )
            arrays = place_edge_shards(
                mesh, edge_axis, (arrs.src, arrs.dst, arrs.w)
            )
        prep = ShardedPrepared(
            mesh=mesh,
            num_nodes=n,
            arrays=arrays,
            solver=solver,
            alg=cfg.alg,
            edge_axis=edge_axis,
            seed_axis=seed_axis,
        )
        self._prep_cache = (norm, mesh, prep)
        return prep

    def solve_prepared(
        self,
        prep: ShardedPrepared,
        Y: np.ndarray,
        F0: Optional[np.ndarray] = None,
    ) -> SolveResult:
        cfg = self.config
        n = prep.num_nodes
        k_seeds = prep.mesh.shape[prep.seed_axis]
        Y = np.asarray(Y)
        if Y.ndim == 1:
            Y = Y[:, None]
        s = Y.shape[1]
        pad_s = (-s) % k_seeds
        if pad_s:
            Y = np.concatenate([Y, np.zeros((n, pad_s), Y.dtype)], axis=1)
        if F0 is None:
            F0 = Y
        else:
            F0 = np.asarray(F0)
            if F0.ndim == 1:
                F0 = F0[:, None]
            if pad_s:
                F0 = np.concatenate(
                    [F0, np.zeros((n, pad_s), F0.dtype)], axis=1
                )
        Yd = place_label_panel(prep.mesh, prep.seed_axis, Y)
        F0d = place_label_panel(prep.mesh, prep.seed_axis, F0)

        if prep.alg == "dhlp1":
            F, iters, tot_inner = prep.solver(*prep.arrays, Yd, F0d)
            outer = int(np.max(np.asarray(iters)))
            return SolveResult(
                F=np.asarray(F, np.float64)[:, :s],
                outer_iters=outer,
                inner_iters=int(np.max(np.asarray(tot_inner))),
                converged=bool(outer < cfg.max_iter),
            )
        F, iters, col_iters = prep.solver(*prep.arrays, Yd, F0d)
        outer = int(np.max(np.asarray(iters)))
        return SolveResult(
            F=np.asarray(F, np.float64)[:, :s],
            outer_iters=outer,
            inner_iters=0,
            converged=bool(outer < cfg.max_iter),
            per_column_iters=np.asarray(col_iters)[:s],
        )

    def run(
        self,
        norm: NormalizedNetwork,
        mesh: Mesh,
        seeds: Optional[np.ndarray] = None,
        *,
        edge_axis: str = "model",
        seed_axis: str = "data",
        F0: Optional[np.ndarray] = None,
    ) -> SolveResult:
        n = norm.num_nodes
        Y = np.eye(n, dtype=np.float32) if seeds is None else seeds
        prep = self.prepare(
            norm, mesh, edge_axis=edge_axis, seed_axis=seed_axis
        )
        return self.solve_prepared(prep, Y, F0=F0)
