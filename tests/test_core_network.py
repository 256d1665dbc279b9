"""Unit tests for network containers and normalization."""
import numpy as np
import pytest

from repro.core import (
    HeteroNetwork,
    bipartite_normalize,
    spectral_radius_upper_bound,
    symmetric_normalize,
)
from repro.core.network import HeteroCOO


def small_net(seed=0):
    rng = np.random.default_rng(seed)
    P = []
    for ni in (6, 5, 4):
        a = rng.random((ni, ni)) * (rng.random((ni, ni)) < 0.6)
        np.fill_diagonal(a, 0)
        P.append(a)
    R = {
        (0, 1): (rng.random((6, 5)) < 0.5).astype(float),
        (0, 2): (rng.random((6, 4)) < 0.5).astype(float),
        (1, 2): (rng.random((5, 4)) < 0.5).astype(float),
    }
    return HeteroNetwork(P=P, R=R)


class TestNormalize:
    def test_symmetric_normalize_spectrum(self):
        rng = np.random.default_rng(1)
        a = rng.random((20, 20))
        a = (a + a.T) / 2
        np.fill_diagonal(a, 0)
        s = symmetric_normalize(a)
        eig = np.linalg.eigvalsh(s)
        assert np.max(np.abs(eig)) <= 1.0 + 1e-9

    def test_bipartite_normalize_singular_values(self):
        rng = np.random.default_rng(2)
        r = (rng.random((12, 7)) < 0.5).astype(float)
        s = bipartite_normalize(r)
        sv = np.linalg.svd(s, compute_uv=False)
        assert sv.max() <= 1.0 + 1e-9

    def test_zero_degree_guard(self):
        a = np.zeros((4, 4))
        a[0, 1] = a[1, 0] = 1.0  # nodes 2,3 isolated
        s = symmetric_normalize(a)
        assert np.isfinite(s).all()
        assert s[2].sum() == 0

    def test_upper_bound(self):
        rng = np.random.default_rng(3)
        a = rng.random((10, 10))
        s = symmetric_normalize((a + a.T) / 2)
        rho = np.max(np.abs(np.linalg.eigvals(s)))
        assert rho <= spectral_radius_upper_bound(s) + 1e-9


class TestContainer:
    def test_shapes_and_offsets(self):
        net = small_net()
        assert net.num_types == 3
        assert net.sizes == [6, 5, 4]
        assert net.num_nodes == 15
        assert net.offsets == [0, 6, 11]
        types = net.type_of_node()
        assert (types[:6] == 0).all() and (types[11:] == 2).all()

    def test_similarity_symmetrized(self):
        net = small_net()
        for t in range(net.num_types):
            p = net.blocks[(t, t)][""].toarray()
            np.testing.assert_allclose(p, p.T)

    def test_transposed_R_canonicalized(self):
        rng = np.random.default_rng(4)
        P = [np.eye(3), np.eye(2)]
        r = rng.random((2, 3))
        net = HeteroNetwork(P=P, R={(1, 0): r})
        np.testing.assert_allclose(net.blocks[(0, 1)][""].toarray(), r.T)

    def test_assembly_disjoint_support(self):
        norm = small_net().normalize()
        H, M = norm.assemble_dense()
        assert (np.abs(H) * np.abs(M)).sum() == 0  # disjoint
        np.testing.assert_allclose(H, H.T, atol=1e-12)
        np.testing.assert_allclose(M, M.T, atol=1e-12)

    def test_effective_operator(self):
        norm = small_net().normalize()
        H, M = norm.assemble_dense()
        A_eff, beta2 = norm.assemble_effective(0.4)
        np.testing.assert_allclose(A_eff, 0.4 * 0.6 * H + 0.4 * M)
        assert beta2 == pytest.approx(0.36)

    def test_fold_masking(self):
        net = small_net()
        R = net.blocks[(0, 2)][""]
        mask = np.zeros(R.shape, dtype=bool)
        pos = np.argwhere(R.toarray() > 0)
        assert len(pos) > 0
        mask[pos[0][0], pos[0][1]] = True
        masked = net.with_masked_fold((0, 2), mask)
        assert masked.blocks[(0, 2)][""][pos[0][0], pos[0][1]] == 0
        assert masked.blocks[(0, 2)][""].nnz == R.nnz - 1
        # original untouched
        assert net.blocks[(0, 2)][""][pos[0][0], pos[0][1]] > 0

    def test_num_edges_counts_both_directions_of_R(self):
        net = HeteroNetwork(
            P=[np.zeros((2, 2)), np.zeros((2, 2))],
            R={(0, 1): np.array([[1.0, 0.0], [0.0, 1.0]])},
        )
        assert net.num_edges == 4


class TestCOO:
    def test_dense_coo_roundtrip(self):
        norm = small_net().normalize()
        H, M = norm.assemble_dense()
        coo = HeteroCOO.from_dense(H, M, norm.sizes)
        n = norm.num_nodes
        Hr = np.zeros((n, n))
        Hr[coo.het_dst, coo.het_src] = coo.het_w
        Mr = np.zeros((n, n))
        Mr[coo.hom_dst, coo.hom_src] = coo.hom_w
        np.testing.assert_allclose(Hr, H)
        np.testing.assert_allclose(Mr, M)

    def test_padding_is_noop(self):
        norm = small_net().normalize()
        coo = norm.to_coo()
        padded = coo.pad_to(64, 64)
        assert padded.het_src.shape[0] % 64 == 0
        assert padded.het_w[coo.het_src.shape[0]:].sum() == 0
