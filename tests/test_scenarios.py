"""Scenario & workload subsystem: registry, generators, arrivals,
planted-truth evaluation, and the drugnet adapter contract.

The tentpole invariants (DESIGN.md §12): every registered scenario
produces a well-formed bundle whose planted truth the LP engines can
recover (held-out planted edges rank above true negatives), on networks
well beyond the paper's T=3 — including heterophilic association
structure — and the tri-partite adapter reproduces the historical
``make_drugnet`` RNG streams bit-for-bit.
"""
import numpy as np
import pytest

import repro.scenarios as sc
from repro.data.drugnet import DrugNetSpec, make_drugnet
from repro.eval.cv import cross_validate, kfold_masks, summarize
from repro.scenarios.generators import (
    KPartiteSpec,
    planted_kpartite,
    sizes_for_edges,
)


def _dense(net, pair):
    """A block of one relation (the generators' networks) as an array."""
    return net.blocks[pair][""].toarray()


def _pairs(net):
    return {p for p in net.blocks if p[0] != p[1]}


class TestRegistry:
    def test_at_least_five_scenarios(self):
        names = sc.available_scenarios()
        assert len(names) >= 5
        for expected in (
            "bio_tri",
            "kpartite5",
            "kpartite_heterophilic",
            "powerlaw",
            "streaming",
        ):
            assert expected in names

    def test_unknown_scenario_raises(self):
        with pytest.raises(KeyError, match="registered:"):
            sc.get_scenario("giraph_net")

    def test_generate_rejects_bad_scale(self):
        with pytest.raises(ValueError, match="scale"):
            sc.generate("bio_tri", scale=0.0)

    def test_bundles_are_well_formed(self):
        for name in sc.available_scenarios():
            scale = 0.02 if name == "powerlaw" else 0.25
            b = sc.generate(name, scale=scale, seed=0)
            net = b.network
            assert b.eval_pair in _pairs(net)
            for pair, mask in b.truth.items():
                assert mask.shape == net.support(pair).shape
                # planted positives are present edges
                assert not np.any(mask & ~net.support(pair)), (name, pair)
            d = b.describe()
            assert d["nodes"] == net.num_nodes


class TestDrugnetAdapter:
    """``data/drugnet.py`` is an adapter over the one generator idiom."""

    def test_adapter_matches_generator_exactly(self):
        spec = DrugNetSpec(n_drug=30, n_disease=20, n_target=15, seed=7)
        dn = make_drugnet(spec)
        pk = planted_kpartite(spec.to_kpartite())
        assert dn.network.blocks.keys() == pk.network.blocks.keys()
        for k in dn.network.blocks:
            np.testing.assert_array_equal(_dense(dn.network, k), _dense(pk.network, k))
        assert dn.truth is not None
        np.testing.assert_array_equal(dn.truth[(0, 2)], pk.truth[(0, 2)])

    def test_historical_rng_stream_preserved(self):
        """Frozen checksums of the pre-refactor make_drugnet draws: the
        committed bench baselines depend on these exact networks."""
        dn = make_drugnet(
            DrugNetSpec(n_drug=40, n_disease=30, n_target=20, seed=3)
        )
        net = dn.network
        p_sq = float(sum((_dense(net, (t, t)) ** 2).sum() for t in range(net.num_types)))
        r_sum = float(sum(_dense(net, k).sum() for k in _pairs(net)))
        assert repr(p_sq) == "233.22902809050655"
        assert r_sum == 209.0

    def test_bio_tri_scenario_matches_drugnet(self):
        b = sc.generate("bio_tri", scale=1.0, seed=0)
        dn = make_drugnet(DrugNetSpec(seed=0))
        np.testing.assert_array_equal(
            _dense(b.network, (0, 2)), _dense(dn.network, (0, 2))
        )


class TestGenerators:
    def test_heterophilic_truth_is_cross_cluster(self):
        spec = KPartiteSpec(
            sizes=(40, 30, 25), n_clusters=5, heterophily=True, seed=1
        )
        pk = planted_kpartite(spec)
        for (i, j), mask in pk.truth.items():
            same = (
                pk.clusters[i][:, None] == pk.clusters[j][None, :]
            )
            assert not np.any(mask & same), (i, j)
            assert mask.sum() > 0

    def test_homophilic_truth_is_intra_cluster(self):
        pk = planted_kpartite(KPartiteSpec(sizes=(40, 30), n_clusters=5))
        mask = pk.truth[(0, 1)]
        same = pk.clusters[0][:, None] == pk.clusters[1][None, :]
        assert not np.any(mask & ~same)

    def test_powerlaw_degrees_are_skewed(self):
        spec = KPartiteSpec(
            sizes=(400, 300, 200),
            degree="powerlaw",
            sim_density=0.35,
            sim_cross_frac=0.08,
            dense_sim_noise=False,
            seed=0,
        )
        pk = planted_kpartite(spec)
        deg = np.count_nonzero(_dense(pk.network, (0, 0)), axis=1)
        # hubs: max degree far above the mean — the cross-cluster support
        # means the tail is not capped at the cluster size n/k
        assert deg.max() > 4 * deg.mean()

    def test_sizes_for_edges_lands_near_target(self):
        spec = KPartiteSpec(sizes=(223, 150, 95))
        sizes = sizes_for_edges(spec, 50_000)
        import dataclasses

        pk = planted_kpartite(dataclasses.replace(spec, sizes=sizes))
        assert 25_000 < pk.network.num_edges < 100_000

    def test_powerlaw_full_scale_targets_million_edges(self):
        # size the full-scale cell WITHOUT generating it (CI-friendly)
        b = sc.generate("powerlaw", scale=0.02, seed=0)
        assert b.network.num_edges > 0.5 * b.meta["target_edges"]
        # the nominal target itself clears 1M with the same headroom
        assert 0.5 * sc.library._POWERLAW_EDGE_TARGET >= 600_000

    def test_non_complete_pair_schema(self):
        b = sc.generate("kpartite5", scale=0.25, seed=0)
        t = b.network.num_types
        assert t == 5
        all_pairs = {(i, j) for i in range(t) for j in range(i + 1, t)}
        assert _pairs(b.network) < all_pairs  # strictly sparser schema


class TestArrivals:
    def test_poisson_rate(self):
        rng = np.random.default_rng(0)
        t = sc.arrival_times("poisson", 200.0, 10.0, rng)
        assert np.all(np.diff(t) >= 0) and t[-1] < 10.0
        assert 1500 < len(t) < 2500

    def test_bursty_holds_mean_rate_and_bursts(self):
        rng = np.random.default_rng(0)
        t = sc.arrival_times("bursty", 200.0, 20.0, rng)
        assert np.all(np.diff(t) >= 0)
        assert 0.6 * 4000 < len(t) < 1.4 * 4000
        # burstiness: windowed counts overdispersed vs poisson
        counts, _ = np.histogram(t, bins=40)
        assert counts.var() > 2.0 * counts.mean()

    def test_diurnal_modulates_rate(self):
        rng = np.random.default_rng(0)
        t = sc.arrival_times("diurnal", 400.0, 10.0, rng, depth=0.9)
        first_half = (t < 5.0).sum()  # sin >= 0: the high-rate half
        assert first_half > 0.6 * len(t)

    def test_unknown_process_raises(self):
        rng = np.random.default_rng(0)
        with pytest.raises(ValueError, match="arrival process"):
            sc.arrival_times("constant", 1.0, 1.0, rng)

    def test_build_trace_targets_eval_pair_block(self):
        b = sc.generate("kpartite5", scale=0.25, seed=0)
        trace = sc.build_trace(b, "poisson", rate_qps=100, horizon_s=1.0)
        i, j = b.eval_pair
        lo = b.network.offsets[i]
        hi = lo + b.network.sizes[i]
        assert np.all((trace.entity >= lo) & (trace.entity < hi))
        assert np.all(trace.target_type == j)
        assert np.all(np.diff(trace.t) >= 0)


class TestPlantedTruthEval:
    """Satellite: eval/cv + metrics on a non-tri-partite (T>=4) scenario —
    held-out planted edges must rank above true negatives."""

    @pytest.fixture(scope="class")
    def k5(self):
        return sc.generate("kpartite5", scale=0.3, seed=0)

    def test_recovery_auc_above_09_on_two_backends(self, k5):
        problem = sc.make_recovery_problem(
            k5, holdout_frac=0.15, max_entities=16, seed=0
        )
        F_ref = None
        for backend in ("dense", "sparse"):
            res = sc.solve_recovery(problem, backend)
            m = problem.metrics(res.F)
            assert m["recovery_auc"] > 0.9, backend
            if F_ref is None:
                F_ref = res.F
            else:
                assert np.max(np.abs(res.F - F_ref)) < 5e-3
        assert problem.num_heldout >= 1

    def test_heterophilic_recovery_above_09(self):
        b = sc.generate("kpartite_heterophilic", scale=0.3, seed=0)
        m = sc.recovery_auc(
            b, "dense", holdout_frac=0.15, max_entities=16, seed=0
        )
        assert m["recovery_auc"] > 0.9

    def test_scenario_cross_validate_t5(self, k5):
        results = sc.scenario_cross_validate(k5, backend="dense", k=3)
        assert len(results) == 3
        summary = summarize(results)
        assert summary["auc"] > 0.9
        assert summary["aupr"] > 0.3
        assert 0.5 < summary["best_acc"] <= 1.0

    def test_cv_positives_must_be_present_edges(self, k5):
        pair = k5.eval_pair
        R = _dense(k5.network, pair)
        bad = np.ones_like(R, dtype=bool)  # claims absent edges as positive
        with pytest.raises(ValueError, match="present"):
            list(kfold_masks(R, k=2, positives=bad))

    def test_cv_folds_hide_only_planted_entries(self, k5):
        pair = k5.eval_pair
        R = _dense(k5.network, pair)
        planted = k5.truth[pair] & (R > 0)
        union = np.zeros_like(planted)
        for mask in kfold_masks(R, k=3, positives=planted):
            assert not np.any(mask & ~planted)
            union |= mask
        np.testing.assert_array_equal(union, planted)

    def test_cv_scores_noise_edges_nowhere(self, k5):
        """A noise edge (present, not planted) is neither hidden nor a
        negative: spiking its score must not change any fold metric."""
        pair = k5.eval_pair
        R = _dense(k5.network, pair)
        planted = k5.truth[pair] & (R > 0)
        noise = (R > 0) & ~planted
        if not noise.any():
            pytest.skip("no noise edges drawn at this scale/seed")
        base = np.random.default_rng(0).random(R.shape)
        spiked = base.copy()
        spiked[noise] = 1e9

        res_a = cross_validate(
            k5.network, pair, lambda net: base, k=2, positives=planted
        )
        res_b = cross_validate(
            k5.network, pair, lambda net: spiked, k=2, positives=planted
        )
        for a, b in zip(res_a, res_b):
            assert a.metrics == b.metrics


class TestStreamingScenario:
    def test_deltas_readd_heldout_edges(self):
        b = sc.generate("streaming", scale=1.0, seed=0)
        pair = b.eval_pair
        arriving = b.meta["arriving_truth"]
        assert int(arriving.sum()) == b.meta["heldout_edges"]
        # t=0 network lacks the held-out edges; truth agrees
        assert not np.any(b.network.support(pair) & arriving)
        assert not np.any(b.truth[pair] & arriving)
        net = b.network
        for td in b.deltas:
            net = net.apply_delta(td.delta)
        R_after = _dense(net, pair)
        rows, cols = np.nonzero(arriving)
        assert np.all(R_after[rows, cols] > 0)
        # delta times are ordered and inside the trace horizon
        ts = [td.t for td in b.deltas]
        assert ts == sorted(ts)
        assert b.trace is not None and ts[-1] < b.trace.horizon_s

    def test_trace_replay_through_serve_engine(self):
        """End-to-end: the streaming workload drives the serve stack —
        queries at trace pace (compressed), deltas interleaved."""
        from repro.core import LPConfig
        from repro.serve import LPServeEngine, QuerySpec, ServeConfig

        b = sc.generate(
            "streaming", scale=0.5, seed=0, rate_qps=30.0, horizon_s=1.0,
            n_deltas=2,
        )
        engine = LPServeEngine(
            b.network,
            ServeConfig(
                lp=LPConfig(alg="dhlp2", sigma=1e-3, seed_mode="fixed")
            ),
        )
        trace = b.trace
        di = 0
        results = []
        for i in range(min(len(trace), 12)):
            while di < len(b.deltas) and b.deltas[di].t <= float(trace.t[i]):
                engine.apply_delta(b.deltas[di].delta)
                di += 1
            results.append(
                engine.query(
                    QuerySpec(
                        entity=int(trace.entity[i]),
                        target_type=int(trace.target_type[i]),
                        top_k=5,
                    )
                )
            )
        assert len(results) == min(len(trace), 12)
        assert di >= 1  # at least one delta landed mid-trace
        versions = {r.version for r in results}
        assert len(versions) >= 2  # answers span network versions
