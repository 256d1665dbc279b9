"""Serving subsystem tests: scheduler coalescing, warm-start equivalence,
delta-update correctness, cache behavior, GraphDelta application."""
import queue
import threading

import numpy as np
import pytest

from repro.core import (
    GraphDelta,
    HeteroLP,
    HeteroNetwork,
    LPConfig,
    topk_exclusive,
)
from repro.serve import (
    ColumnCache,
    LPServeEngine,
    MicroBatcher,
    QuerySpec,
    ServeConfig,
)

SIGMA = 1e-6


def small_net(seed=0, n=(18, 12, 9)) -> HeteroNetwork:
    rng = np.random.default_rng(seed)
    P = []
    for ni in n:
        a = (rng.random((ni, ni)) < 0.35) * rng.random((ni, ni))
        np.fill_diagonal(a, 0)
        P.append((a + a.T) / 2)
    R = {(i, j): (rng.random((n[i], n[j])) < 0.3).astype(float)
         for (i, j) in [(0, 1), (0, 2), (1, 2)]}
    return HeteroNetwork(P=P, R=R)


def serve_cfg(**kw) -> ServeConfig:
    base = dict(
        lp=LPConfig(alg="dhlp2", seed_mode="fixed", sigma=SIGMA),
        max_wait_s=1e-3,
    )
    base.update(kw)
    return ServeConfig(**base)


class TestWarmStartEquivalence:
    def test_same_fixed_point_fewer_rounds(self):
        """Warm-started solve reaches the cold fixed point in fewer rounds."""
        net = small_net()
        cfg = LPConfig(alg="dhlp2", seed_mode="fixed", sigma=SIGMA)
        solver = HeteroLP(cfg)
        n = net.num_nodes
        Y = np.eye(n)[:, [0]]
        cold = solver.run(net, seeds=Y)
        # start from a noisy neighborhood of the solution
        rng = np.random.default_rng(1)
        F0 = cold.F + 1e-4 * rng.standard_normal(cold.F.shape)
        warm = solver.run(net, seeds=Y, F0=F0)
        assert np.max(np.abs(warm.F - cold.F)) < 10 * SIGMA
        assert warm.outer_iters < cold.outer_iters

    def test_converged_start_freezes_round_zero(self):
        """A column already at its fixed point costs ~no rounds."""
        net = small_net()
        cfg = LPConfig(alg="dhlp2", seed_mode="fixed", sigma=1e-4)
        solver = HeteroLP(cfg)
        Y = np.eye(net.num_nodes)[:, [3]]
        cold = solver.run(net, seeds=Y)
        again = solver.run(net, seeds=Y, F0=cold.F)
        assert int(again.per_column_iters[0]) <= 1
        assert np.max(np.abs(again.F - cold.F)) < 1e-4

    def test_dhlp1_warm_start(self):
        net = small_net()
        cfg = LPConfig(alg="dhlp1", sigma=SIGMA, max_iter=500, max_inner=300)
        solver = HeteroLP(cfg)
        Y = np.eye(net.num_nodes)[:, [2]]
        cold = solver.run(net, seeds=Y)
        warm = solver.run(net, seeds=Y, F0=cold.F)
        assert np.max(np.abs(warm.F - cold.F)) < 10 * SIGMA
        assert warm.outer_iters <= cold.outer_iters

    def test_sparse_engine_warm_start(self):
        from repro.engine import make_engine

        net = small_net()
        norm = net.normalize()
        cfg = LPConfig(alg="dhlp2", seed_mode="fixed", sigma=SIGMA)
        solver = make_engine("sparse", cfg)
        Y = np.eye(net.num_nodes)[:, [0]].astype(np.float32)
        cold = solver.run(norm, seeds=Y)
        warm = solver.run(norm, seeds=Y, F0=cold.F)
        assert np.max(np.abs(warm.F - cold.F)) < 1e-4
        assert warm.outer_iters <= cold.outer_iters

    def test_f0_shape_mismatch_raises(self):
        net = small_net()
        solver = HeteroLP(LPConfig(seed_mode="fixed"))
        Y = np.eye(net.num_nodes)[:, [0]]
        with pytest.raises(ValueError):
            solver.run(net, seeds=Y, F0=np.zeros((3, 1)))


class TestSchedulerCoalescing:
    def test_n_queries_one_solve(self):
        """N queued queries coalesce into one batched solve call."""
        net = small_net()
        engine = LPServeEngine(net, serve_cfg(max_batch=64))
        calls = []
        inner = engine._solve_batch

        def counting(specs):
            calls.append(len(specs))
            return inner(specs)

        engine.batcher._solve_batch = counting
        futs = [
            engine.submit(QuerySpec(entity=e, target_type=2, top_k=4))
            for e in range(10)
        ]
        served = engine.batcher.drain()
        assert served == 10
        assert calls == [10]          # ONE solver call for ten queries
        for e, fut in enumerate(futs):
            res = fut.result()
            unknown = int(np.sum(~net.support((0, 2))[e]))
            assert res.candidates.size == min(4, unknown)
            # scores come back descending
            assert np.all(np.diff(res.scores) <= 0)

    def test_max_batch_splits_ticks(self):
        net = small_net()
        engine = LPServeEngine(net, serve_cfg(max_batch=4))
        for e in range(10):
            engine.submit(QuerySpec(entity=e, target_type=2))
        engine.batcher.drain()
        assert engine.batcher.stats.batches == 3  # 4 + 4 + 2

    def test_backpressure_rejects_when_full(self):
        net = small_net()
        engine = LPServeEngine(net, serve_cfg(queue_depth=2))
        engine.submit(QuerySpec(entity=0, target_type=2))
        engine.submit(QuerySpec(entity=1, target_type=2))
        with pytest.raises(queue.Full):
            engine.submit(QuerySpec(entity=2, target_type=2), block=False)
        assert engine.batcher.stats.rejected == 1
        engine.batcher.drain()

    def test_background_thread_serves(self):
        net = small_net()
        engine = LPServeEngine(net, serve_cfg())
        engine.start()
        try:
            futs = [
                engine.submit(QuerySpec(entity=e, target_type=1))
                for e in range(6)
            ]
            results = [f.result(timeout=120) for f in futs]
        finally:
            engine.stop()
        assert all(r.version == 0 for r in results)
        assert all(r.latency_s > 0 for r in results)

    def test_invalid_spec_rejected_at_submit_not_in_batch(self):
        """A bad request fails alone instead of poisoning its batch."""
        net = small_net()
        engine = LPServeEngine(net, serve_cfg())
        good = engine.submit(QuerySpec(entity=0, target_type=2))
        with pytest.raises(ValueError, match="out of range"):
            engine.submit(QuerySpec(entity=10_000, target_type=2))
        with pytest.raises(ValueError, match="no such type"):
            engine.submit(QuerySpec(entity=0, target_type=9))
        engine.batcher.drain()
        assert good.result(timeout=60).candidates.size > 0

    def test_cancelled_future_dropped_batch_survives(self):
        net = small_net()
        engine = LPServeEngine(net, serve_cfg())
        doomed = engine.submit(QuerySpec(entity=0, target_type=2))
        kept = engine.submit(QuerySpec(entity=1, target_type=2))
        assert doomed.cancel()
        engine.batcher.drain()
        assert doomed.cancelled()
        assert kept.result(timeout=60).candidates.size > 0

    def test_operator_cache_keyed_by_identity(self):
        """Equal-by-value but distinct networks must not share operators."""
        from repro.engine import make_engine

        net = small_net()
        cfg = LPConfig(alg="dhlp2", seed_mode="fixed", sigma=1e-4)
        dense = HeteroLP(cfg)
        n1, n2 = net.normalize(), net.normalize()
        a1 = dense._device_arrays(n1)
        assert dense._device_arrays(n1) is a1       # same object: cached
        assert dense._device_arrays(n2) is not a1   # new object: rebuilt
        assert dense._cache[0] is n2                # entry keeps norm alive
        sparse = make_engine("sparse", cfg)
        o1 = sparse.prepare(n1)
        assert sparse.prepare(n1) is o1             # same object: cached
        assert sparse.prepare(n2) is not o1         # identity, not equality

    def test_solver_error_propagates_to_futures(self):
        batcher = MicroBatcher(
            lambda specs: (_ for _ in ()).throw(RuntimeError("boom")),
            max_wait_s=1e-3,
        )
        fut = batcher.submit(QuerySpec(entity=0, target_type=0))
        batcher.run_once(wait=False)
        with pytest.raises(RuntimeError, match="boom"):
            fut.result(timeout=5)
        assert batcher.stats.failed == 1


class TestColumnCache:
    def test_lru_eviction(self):
        cache = ColumnCache(capacity=2)
        for node in range(3):
            cache.put(0, node, np.full(4, node, dtype=float))
        assert cache.get(0, 0) is None          # evicted
        assert cache.get(0, 2) is not None
        assert cache.stats.evictions == 1

    def test_hit_refreshes_recency(self):
        cache = ColumnCache(capacity=2)
        cache.put(0, 0, np.zeros(4))
        cache.put(0, 1, np.ones(4))
        cache.get(0, 0)                          # 0 is now most-recent
        cache.put(0, 2, np.full(4, 2.0))
        assert cache.get(0, 1) is None           # 1 evicted, not 0
        assert cache.get(0, 0) is not None

    def test_engine_cache_hit_costs_zero_rounds(self):
        net = small_net()
        engine = LPServeEngine(net, serve_cfg())
        spec = QuerySpec(entity=5, target_type=2, top_k=6)
        first = engine.query(spec)
        second = engine.query(spec)
        assert first.source == "cold" and first.rounds > 0
        assert second.source == "cache" and second.rounds == 0
        np.testing.assert_array_equal(first.candidates, second.candidates)

    def test_neighbor_warm_start_fewer_rounds(self):
        """A near-duplicate drug's cached column is a good starting state."""
        net = small_net()
        # make drugs 0 and 1 near-identical: strong mutual similarity and
        # the same association rows, so their label columns nearly coincide
        B = {k: rels[""].toarray() for k, rels in net.blocks.items()}
        B[(0, 0)][0, 1] = B[(0, 0)][1, 0] = 1.0
        for pair in [(0, 1), (0, 2)]:
            B[pair][1] = B[pair][0]
        net = HeteroNetwork(
            P=[B[(t, t)] for t in range(net.num_types)],
            R={k: b for k, b in B.items() if k[0] != k[1]},
        )
        engine = LPServeEngine(net, serve_cfg())
        cold = engine.query(QuerySpec(entity=0, target_type=2))
        warm = engine.query(QuerySpec(entity=1, target_type=2))
        assert cold.source == "cold"
        assert warm.source == "warm"
        assert warm.rounds < cold.rounds
        # and the warm answer is the true fixed point, not an approximation
        direct = HeteroLP(
            LPConfig(alg="dhlp2", seed_mode="fixed", sigma=SIGMA)
        ).run(net, seeds=np.eye(net.num_nodes)[:, [1]])
        assert np.max(
            np.abs(engine.columns.get(0, 1) - direct.F[:, 0])
        ) < 100 * SIGMA


class TestDeltaUpdate:
    def test_incremental_matches_full_resolve(self):
        """Post-delta warm re-solve agrees with a cold solve on the new net."""
        net = small_net()
        engine = LPServeEngine(net, serve_cfg())
        engine.query(QuerySpec(entity=0, target_type=2))
        delta = GraphDelta(assoc=[((0, 2), 0, 4, 1.0), ((0, 1), 2, 3, 0.0)])
        version = engine.apply_delta(delta)
        assert version == 1
        incr = engine.query(QuerySpec(entity=0, target_type=2))
        assert incr.source == "warm"              # stale column reused

        cold = HeteroLP(
            LPConfig(alg="dhlp2", seed_mode="fixed", sigma=SIGMA)
        ).run(net.apply_delta(delta), seeds=np.eye(net.num_nodes)[:, [0]])
        served_col = engine.columns.get(version, 0)
        assert np.max(np.abs(served_col - cold.F[:, 0])) < 100 * SIGMA

    def test_incremental_fewer_rounds_than_cold(self):
        net = small_net()
        engine = LPServeEngine(net, serve_cfg())
        cold = engine.query(QuerySpec(entity=0, target_type=2))
        engine.apply_delta(GraphDelta(assoc=[((0, 2), 0, 4, 1.0)]))
        incr = engine.query(QuerySpec(entity=0, target_type=2))
        assert incr.rounds < cold.rounds

    def test_refresh_rounds_advance_stale_hints(self):
        """engine.round-based post-delta refresh: same answer, fewer
        re-solve rounds than an unrefreshed stale hint."""
        net = small_net()
        delta = GraphDelta(assoc=[((0, 2), 0, 4, 1.0)])

        plain = LPServeEngine(net, serve_cfg())
        plain.query(QuerySpec(entity=0, target_type=2))
        plain.apply_delta(delta)
        refreshed = LPServeEngine(net, serve_cfg(refresh_rounds=4))
        refreshed.query(QuerySpec(entity=0, target_type=2))
        refreshed.apply_delta(delta)

        # the refreshed hint moved toward the new fixed point in place
        h_plain = plain.columns.stale_hint(0)
        h_ref = refreshed.columns.stale_hint(0)
        assert h_ref is not None and not np.allclose(h_ref, h_plain)
        r_plain = plain.query(QuerySpec(entity=0, target_type=2))
        r_ref = refreshed.query(QuerySpec(entity=0, target_type=2))
        assert r_ref.source == "warm"
        assert r_ref.rounds <= r_plain.rounds
        # and both serve the same fixed point
        np.testing.assert_allclose(
            refreshed.columns.get(1, 0), plain.columns.get(1, 0),
            atol=100 * SIGMA,
        )

    def test_refresh_rounds_validation(self):
        with pytest.raises(ValueError, match="refresh_rounds"):
            serve_cfg(refresh_rounds=-1)

    def test_refresh_rounds_rejects_dhlp1(self):
        # engine.round is the fused DHLP-2 update; advancing DHLP-1 hints
        # with it would walk them toward the wrong fixed point
        with pytest.raises(ValueError, match="dhlp2"):
            serve_cfg(
                lp=LPConfig(alg="dhlp1", seed_mode="fixed", sigma=SIGMA),
                refresh_rounds=2,
            )

    def test_lp_backend_field_selects_serve_engine(self):
        cfg = serve_cfg(
            lp=LPConfig(alg="dhlp2", seed_mode="fixed", sigma=SIGMA,
                        backend="sparse")
        )
        assert cfg.resolved_engine() == "sparse"
        engine = LPServeEngine(small_net(), cfg)
        assert engine._engine.name == "sparse"

    def test_auto_engine_rescales_after_growth_delta(self, monkeypatch):
        """A node-adding delta crossing the dense/sparse policy boundary
        re-resolves an 'auto' engine instead of staying dense forever."""
        import repro.engine.base as engine_base

        monkeypatch.setattr(engine_base, "AUTO_DENSE_MAX_NODES", 60)
        net = small_net()  # 39 nodes -> dense
        engine = LPServeEngine(net, serve_cfg(engine="auto"))
        assert engine._engine.name == "dense"
        engine.apply_delta(GraphDelta(add_nodes={0: 40}))  # 79 nodes
        assert engine._engine.name == "sparse"
        # pinned engines are left alone
        pinned = LPServeEngine(small_net(), serve_cfg(engine="dense"))
        pinned.apply_delta(GraphDelta(add_nodes={0: 40}))
        assert pinned._engine.name == "dense"

    def test_engine_backend_conflict_rejected(self):
        with pytest.raises(ValueError, match="conflicts"):
            serve_cfg(
                lp=LPConfig(alg="dhlp2", seed_mode="fixed", sigma=SIGMA,
                            backend="sparse"),
                engine="dense",
            )

    def test_untouched_type_columns_survive(self):
        net = small_net()
        engine = LPServeEngine(net, serve_cfg())
        disease = net.offsets[1] + 2
        engine.query(QuerySpec(entity=disease, target_type=0))
        engine.apply_delta(GraphDelta(sim=[(2, 0, 1, 0.7)]))  # targets only
        res = engine.query(QuerySpec(entity=disease, target_type=0))
        assert res.source == "cache"              # carried across the bump

    def test_add_nodes_demotes_and_remaps(self):
        net = small_net()
        engine = LPServeEngine(net, serve_cfg())
        engine.query(QuerySpec(entity=0, target_type=2))
        n_before = engine.state.num_nodes
        engine.apply_delta(GraphDelta(add_nodes={0: 3}))
        assert engine.state.num_nodes == n_before + 3
        res = engine.query(QuerySpec(entity=0, target_type=2))
        assert res.source == "warm"               # remapped stale hint
        # the new drug is queryable once it gains an association
        new_drug = engine.state.sizes[0] - 1
        engine.apply_delta(
            GraphDelta(assoc=[((0, 2), new_drug, 0, 1.0)])
        )
        res = engine.query(QuerySpec(entity=new_drug, target_type=2))
        assert res.candidates.size > 0

    def test_empty_delta_is_noop(self):
        net = small_net()
        engine = LPServeEngine(net, serve_cfg())
        assert engine.apply_delta(GraphDelta()) == 0


class TestGraphDelta:
    def test_apply_edits(self):
        net = small_net()
        delta = GraphDelta(
            assoc=[((0, 2), 1, 2, 1.0)],
            sim=[(0, 3, 4, 0.5)],
        )
        new = net.apply_delta(delta)
        R, P = new.blocks[(0, 2)][""], new.blocks[(0, 0)][""]
        assert R[1, 2] == 1.0
        assert P[3, 4] == 0.5 and P[4, 3] == 0.5
        # original untouched
        R, P = net.blocks[(0, 2)][""], net.blocks[(0, 0)][""]
        assert P[3, 4] != 0.5 or R[1, 2] != 1.0

    def test_reversed_pair_orientation(self):
        net = small_net()
        new = net.apply_delta(GraphDelta(assoc=[((2, 0), 3, 1, 1.0)]))
        assert new.blocks[(0, 2)][""][1, 3] == 1.0

    def test_touched_types(self):
        delta = GraphDelta(assoc=[((0, 2), 0, 0, 1.0)], add_nodes={1: 1})
        assert delta.touched_types() == frozenset({0, 1, 2})

    def test_out_of_range_raises(self):
        net = small_net()
        with pytest.raises(ValueError):
            net.apply_delta(GraphDelta(assoc=[((0, 2), 999, 0, 1.0)]))
        with pytest.raises(ValueError):
            net.apply_delta(GraphDelta(sim=[(7, 0, 0, 1.0)]))


class TestRanking:
    def test_topk_exclusive_skips_known(self):
        scores = np.array([5.0, 4.0, 3.0, 2.0, 1.0])
        out = topk_exclusive(scores, 3, exclude=np.array([0, 2]))
        np.testing.assert_array_equal(out, [1, 3, 4])

    def test_topk_exclusive_bool_mask(self):
        scores = np.array([5.0, 4.0, 3.0])
        out = topk_exclusive(scores, 5, exclude=np.array([True, False, False]))
        np.testing.assert_array_equal(out, [1, 2])

    def test_engine_excludes_known_associations(self):
        net = small_net()
        engine = LPServeEngine(net, serve_cfg())
        res = engine.query(QuerySpec(entity=0, target_type=2, top_k=50))
        known = np.nonzero(net.support((0, 2))[0])[0]
        assert not set(res.candidates.tolist()) & set(known.tolist())
        inc = engine.query(
            QuerySpec(entity=0, target_type=2, top_k=50, include_known=True)
        )
        assert set(known.tolist()) <= set(inc.candidates.tolist())

    def test_same_type_excludes_self(self):
        net = small_net()
        engine = LPServeEngine(net, serve_cfg())
        res = engine.query(QuerySpec(entity=0, target_type=0, top_k=50))
        assert 0 not in res.candidates.tolist()


class TestServeConfigValidation:
    def test_drift_mode_rejected(self):
        with pytest.raises(ValueError, match="fixed"):
            ServeConfig(lp=LPConfig(alg="dhlp2", seed_mode="drift"))

    def test_unknown_engine_rejected(self):
        with pytest.raises(ValueError, match="engine"):
            ServeConfig(engine="giraph")

    def test_sparse_engine_serves(self):
        net = small_net()
        engine = LPServeEngine(
            net,
            serve_cfg(
                engine="sparse",
                lp=LPConfig(alg="dhlp2", seed_mode="fixed", sigma=1e-4),
            ),
        )
        cold = engine.query(QuerySpec(entity=0, target_type=2, top_k=5))
        hit = engine.query(QuerySpec(entity=0, target_type=2, top_k=5))
        assert cold.source == "cold" and hit.source == "cache"
        np.testing.assert_array_equal(cold.candidates, hit.candidates)

    def test_sharded_engine_serves_and_refreshes(self):
        """With the sharded ``round`` path in place (ROADMAP follow-up),
        serving — including post-delta incremental hint refresh, which
        runs ``engine.round`` on-mesh — works on backend='sharded'."""
        net = small_net()
        engine = LPServeEngine(
            net,
            serve_cfg(
                engine="sharded",
                refresh_rounds=2,
                lp=LPConfig(alg="dhlp2", seed_mode="fixed", sigma=1e-4),
            ),
        )
        cold = engine.query(QuerySpec(entity=1, target_type=2, top_k=5))
        hit = engine.query(QuerySpec(entity=1, target_type=2, top_k=5))
        assert cold.source == "cold" and hit.source == "cache"
        engine.apply_delta(GraphDelta(assoc=[((0, 2), 1, 3, 1.0)]))
        warm = engine.query(QuerySpec(entity=1, target_type=2, top_k=5))
        assert warm.source == "warm"
        assert warm.rounds <= cold.rounds
        # the sharded answer is the dense answer (same fixed point)
        dense = LPServeEngine(
            net.apply_delta(GraphDelta(assoc=[((0, 2), 1, 3, 1.0)])),
            serve_cfg(
                engine="dense",
                lp=LPConfig(alg="dhlp2", seed_mode="fixed", sigma=1e-4),
            ),
        ).query(QuerySpec(entity=1, target_type=2, top_k=5))
        assert warm.candidates.tolist() == dense.candidates.tolist()
        np.testing.assert_array_equal(cold.candidates, hit.candidates)
