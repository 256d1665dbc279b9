"""Sharded backend: the shard_map distributed engine behind the registry.

The operator shards are flat slices of the blocked-CSR slot storage
(``BlockedCSR.to_edges``) — the same format the sparse/kernel engines
aggregate, reshaped for the edge axis (DESIGN.md §6/§11).  The mesh is a
deployment knob: pass ``devices=`` (edge-axis size, seed axis 1) or a
ready ``mesh=``; ``auto`` never selects this backend.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from repro.core.network import NormalizedNetwork
from repro.core.solver import LPConfig, SolveResult
from repro.engine.base import LPEngine, Operator, register_backend


@register_backend("sharded")
class ShardedEngine(LPEngine):
    def __init__(
        self,
        config: Optional[LPConfig] = None,
        *,
        devices: Optional[int] = None,
        mesh=None,
        edge_axis: str = "model",
        seed_axis: str = "data",
        stale_sync: int = 1,
        compression: str = "none",
    ):
        super().__init__(config if config is not None else LPConfig())
        from repro.parallel.lp_sharded import ShardedHeteroLP

        self.devices = devices
        self.edge_axis = edge_axis
        self.seed_axis = seed_axis
        self._mesh = mesh
        self._solver = ShardedHeteroLP(
            self.config, stale_sync=stale_sync, compression=compression
        )

    def mesh(self):
        if self._mesh is None:
            import jax

            from repro.parallel.hints import auto_mesh

            devices = jax.devices()
            k = self.devices or len(devices)
            if k > len(devices):
                raise ValueError(
                    f"sharded backend needs {k} devices, host has {len(devices)}"
                )
            self._mesh = auto_mesh(
                (1, k), (self.seed_axis, self.edge_axis), devices=devices[:k]
            )
        return self._mesh

    def _build(self, norm: NormalizedNetwork) -> Operator:
        prep = self._solver.prepare(
            norm,
            self.mesh(),
            edge_axis=self.edge_axis,
            seed_axis=self.seed_axis,
        )
        return Operator(
            backend=self.name,
            norm=norm,
            num_nodes=norm.num_nodes,
            payload=prep,
        )

    def solve(
        self,
        op: Operator,
        Y: np.ndarray,
        F0: Optional[np.ndarray] = None,
    ) -> SolveResult:
        return self._solver.solve_prepared(op.payload, Y, F0=F0)

    def _round_fn(self, op: Operator):
        """Compiled one-round kernel + fused shards, cached per operator.

        DHLP-2 operators reuse the solver's fused edge shards; DHLP-1
        operators (split hetero/homo shards) build the fused triple on
        first use — ``round`` is the fused DHLP-2 update for every
        backend (DESIGN.md §11.1), independent of the solve schedule.
        """
        cache = getattr(self, "_round_cache", None)
        if cache is not None and cache[0] is op:
            return cache[1], cache[2]
        from repro.parallel.lp_sharded import (
            build_sharded_round,
            place_edge_shards,
            prepare_sharded_operator,
        )

        cfg = self.config
        mesh = self.mesh()
        beta = 1.0 - cfg.alpha
        prep = op.payload
        if prep.alg == "dhlp2":
            arrays = prep.arrays
        else:
            arrs = prepare_sharded_operator(
                op.norm, cfg, mesh.shape[self.edge_axis]
            )
            arrays = place_edge_shards(
                mesh, self.edge_axis, (arrs.src, arrs.dst, arrs.w)
            )
        fn = build_sharded_round(
            mesh,
            num_nodes=op.num_nodes,
            beta2=beta * beta,
            edge_axis=self.edge_axis,
            seed_axis=self.seed_axis,
            compression=self._solver.compression,
        )
        self._round_cache = (op, fn, arrays)
        return fn, arrays

    def round(self, op: Operator, F, Y):
        from repro.parallel.lp_sharded import place_label_panel

        fn, arrays = self._round_fn(op)
        mesh = self.mesh()
        k_seeds = mesh.shape[self.seed_axis]
        F = np.asarray(F, np.float32)
        Y = np.asarray(Y, np.float32)
        if F.ndim == 1:
            F = F[:, None]
        if Y.ndim == 1:
            Y = Y[:, None]
        s = F.shape[1]
        pad = (-s) % k_seeds
        if pad:
            z = np.zeros((F.shape[0], pad), np.float32)
            F = np.concatenate([F, z], axis=1)
            Y = np.concatenate([Y, z], axis=1)
        out = fn(
            *arrays,
            place_label_panel(mesh, self.seed_axis, F),
            place_label_panel(mesh, self.seed_axis, Y),
        )
        return np.asarray(out, np.float64)[:, :s]
