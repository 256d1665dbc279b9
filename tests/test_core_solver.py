"""Unit tests for the DHLP solvers (dense engine)."""
import numpy as np
import pytest

from repro.core import (
    HeteroLP,
    HeteroNetwork,
    LPConfig,
    fixed_seed_solution,
    dhlp1_inner_solution,
)


def rand_net(seed=0, n=(12, 9, 7), density=0.4):
    rng = np.random.default_rng(seed)
    P = []
    for ni in n:
        a = (rng.random((ni, ni)) < density) * rng.random((ni, ni))
        np.fill_diagonal(a, 0)
        P.append((a + a.T) / 2)
    R = {
        (i, j): (rng.random((n[i], n[j])) < density).astype(float)
        for (i, j) in [(0, 1), (0, 2), (1, 2)]
    }
    return HeteroNetwork(P=P, R=R)


@pytest.fixture(scope="module")
def net():
    return rand_net()


@pytest.fixture(scope="module")
def closed_form(net):
    norm = net.normalize()
    H, M = norm.assemble_dense()
    scale = LPConfig().resolved_hetero_scale(norm.num_types)
    return fixed_seed_solution(H * scale, M, np.eye(norm.num_nodes), 0.5)


class TestFixedPoint:
    def test_dhlp1_matches_closed_form(self, net, closed_form):
        res = HeteroLP(
            LPConfig(alg="dhlp1", sigma=1e-7, max_iter=500, max_inner=500)
        ).run(net)
        np.testing.assert_allclose(res.F, closed_form, atol=5e-6)
        assert res.converged

    def test_dhlp2_fixed_matches_closed_form(self, net, closed_form):
        res = HeteroLP(
            LPConfig(alg="dhlp2", seed_mode="fixed", sigma=1e-7, max_iter=5000)
        ).run(net)
        np.testing.assert_allclose(res.F, closed_form, atol=5e-6)

    def test_dhlp1_and_dhlp2_share_fixed_point(self, net):
        r1 = HeteroLP(
            LPConfig(alg="dhlp1", sigma=1e-7, max_iter=500, max_inner=500)
        ).run(net)
        r2 = HeteroLP(
            LPConfig(alg="dhlp2", seed_mode="fixed", sigma=1e-7, max_iter=5000)
        ).run(net)
        np.testing.assert_allclose(r1.F, r2.F, atol=1e-5)

    def test_inner_solution_closed_form(self, net):
        norm = net.normalize()
        S = norm.S_homo[0].toarray()
        rng = np.random.default_rng(3)
        yp = rng.random((S.shape[0], 4))
        f = dhlp1_inner_solution(S, yp, 0.5)
        # fixed point of f = 0.5*yp + 0.5*S f
        np.testing.assert_allclose(f, 0.5 * yp + 0.5 * (S @ f), atol=1e-10)


class TestModes:
    def test_fused_equals_unfused(self, net):
        kw = dict(alg="dhlp2", seed_mode="fixed", sigma=1e-7, max_iter=5000)
        rf = HeteroLP(LPConfig(fused=True, **kw)).run(net)
        ru = HeteroLP(LPConfig(fused=False, **kw)).run(net)
        np.testing.assert_allclose(rf.F, ru.F, atol=2e-6)

    def test_sequential_equals_batched(self, net):
        kw = dict(alg="dhlp2", seed_mode="fixed", sigma=1e-7)
        Y = np.eye(net.num_nodes)[:, :4]
        rs = HeteroLP(LPConfig(mode="sequential", **kw)).run(net, seeds=Y)
        rb = HeteroLP(LPConfig(mode="batched", **kw)).run(net, seeds=Y)
        np.testing.assert_allclose(rs.F, rb.F, atol=2e-6)

    def test_seed_chunking(self, net):
        kw = dict(alg="dhlp2", seed_mode="fixed", sigma=1e-7)
        rc = HeteroLP(LPConfig(seed_chunk=5, **kw)).run(net)
        rb = HeteroLP(LPConfig(**kw)).run(net)
        np.testing.assert_allclose(rc.F, rb.F, atol=2e-6)

    def test_drift_mode_converges_with_paper_sigma(self, net):
        res = HeteroLP(LPConfig(alg="dhlp2", sigma=1e-3)).run(net)
        assert res.converged
        assert np.isfinite(res.F).all()

    def test_literal_hetero_scale_divergence_is_reported(self, net):
        # uniform-α over all hetero neighbors (paper-literal) can diverge
        # with T=3 types; the solver must NOT report converged, and the
        # NaN/∞ columns must not be masked as converged.
        res = HeteroLP(
            LPConfig(alg="dhlp2", sigma=1e-4, hetero_scale=1.0, max_iter=200)
        ).run(net)
        assert not res.converged

    def test_per_column_iters_reported(self, net):
        res = HeteroLP(LPConfig(alg="dhlp2", sigma=1e-3)).run(net)
        assert res.per_column_iters is not None
        assert res.per_column_iters.shape == (net.num_nodes,)
        assert (res.per_column_iters <= res.outer_iters).all()
        assert res.supersteps >= res.outer_iters


class TestKernelPath:
    def test_pallas_kernel_in_loop_identical(self):
        """use_kernel routes the fused round through lp_blockspmm
        (interpret mode here); results must match the jnp path exactly."""
        net2 = rand_net(seed=9, n=(60, 45, 35), density=0.2)
        rj = HeteroLP(
            LPConfig(alg="dhlp2", seed_mode="fixed", sigma=1e-5)
        ).run(net2)
        rk = HeteroLP(
            LPConfig(alg="dhlp2", seed_mode="fixed", sigma=1e-5,
                     use_kernel=True)
        ).run(net2)
        np.testing.assert_array_equal(rk.F, rj.F)
        assert rk.outer_iters == rj.outer_iters


class TestHomogeneousSpecialCase:
    def test_single_type_is_classic_lp(self):
        """T=1 (no hetero blocks) reduces to Zhou et al. label propagation
        and classifies a planted-partition graph well above chance."""
        from repro.data.graphs import planted_partition_graph

        data = planted_partition_graph(200, 1200, 4, 8, homophily=0.85,
                                       train_frac=0.15, seed=3)
        net1 = HeteroNetwork(P=[data.edges.to_dense()], R={})
        y = np.zeros((200, 4))
        for c in range(4):
            y[(data.labels == c) & data.train_mask, c] = 1.0
        res = HeteroLP(
            LPConfig(alg="dhlp2", seed_mode="fixed", alpha=0.9, sigma=1e-4)
        ).run(net1, seeds=y)
        pred = np.argmax(res.F, axis=1)
        test = ~data.train_mask
        acc = (pred[test] == data.labels[test]).mean()
        assert acc > 0.6


class TestSigmaBehaviour:
    def test_smaller_sigma_more_iterations(self, net):
        """Paper Table 7: runtime (iterations) grows as σ shrinks."""
        iters = []
        for sigma in [0.2, 0.05, 0.01, 0.002]:
            res = HeteroLP(
                LPConfig(alg="dhlp2", seed_mode="fixed", sigma=sigma)
            ).run(net)
            iters.append(res.outer_iters)
        assert iters == sorted(iters)

    def test_alpha_bounds(self, net):
        for alpha in [0.1, 0.9]:
            res = HeteroLP(
                LPConfig(alg="dhlp2", seed_mode="fixed", alpha=alpha,
                         sigma=1e-6, max_iter=20000)
            ).run(net)
            assert res.converged
            assert np.isfinite(res.F).all()


class TestTwoTypes:
    def test_bipartite_network(self):
        """T=2 (e.g. drug-target only) must work; hetero scale is 1."""
        rng = np.random.default_rng(7)
        P = []
        for ni in (10, 8):
            a = rng.random((ni, ni)) * (rng.random((ni, ni)) < 0.5)
            np.fill_diagonal(a, 0)
            P.append((a + a.T) / 2)
        net2 = HeteroNetwork(P=P, R={(0, 1): (rng.random((10, 8)) < 0.4).astype(float)})
        norm = net2.normalize()
        H, M = norm.assemble_dense()
        want = fixed_seed_solution(H, M, np.eye(18), 0.5)
        res = HeteroLP(
            LPConfig(alg="dhlp2", seed_mode="fixed", sigma=1e-7, max_iter=5000)
        ).run(net2)
        np.testing.assert_allclose(res.F, want, atol=5e-6)
