"""Property-based tests (hypothesis) for system invariants of the LP core."""
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (pip install -r requirements-dev.txt)",
)
from hypothesis import given, settings, strategies as st, HealthCheck

from repro.core import (
    HeteroLP,
    HeteroNetwork,
    LPConfig,
    extract_outputs,
    fixed_seed_solution,
    symmetric_normalize,
    bipartite_normalize,
)

SETTINGS = dict(
    max_examples=12,
    deadline=None,
    suppress_health_check=[HealthCheck.too_slow],
)


def build_net(seed, sizes, density):
    rng = np.random.default_rng(seed)
    P = []
    for ni in sizes:
        a = (rng.random((ni, ni)) < density) * rng.random((ni, ni))
        np.fill_diagonal(a, 0)
        P.append((a + a.T) / 2)
    R = {}
    for i in range(len(sizes)):
        for j in range(i + 1, len(sizes)):
            R[(i, j)] = (rng.random((sizes[i], sizes[j])) < density).astype(float)
    return HeteroNetwork(P=P, R=R)


@given(
    seed=st.integers(0, 10_000),
    n=st.integers(4, 24),
)
@settings(**SETTINGS)
def test_symmetric_normalize_bounded_spectrum(seed, n):
    rng = np.random.default_rng(seed)
    a = rng.random((n, n))
    a = (a + a.T) / 2
    s = symmetric_normalize(a)
    assert np.max(np.abs(np.linalg.eigvalsh(s))) <= 1.0 + 1e-8


@given(
    seed=st.integers(0, 10_000),
    rows=st.integers(2, 20),
    cols=st.integers(2, 20),
)
@settings(**SETTINGS)
def test_bipartite_normalize_bounded_sv(seed, rows, cols):
    rng = np.random.default_rng(seed)
    r = (rng.random((rows, cols)) < 0.5).astype(float)
    s = bipartite_normalize(r)
    sv = np.linalg.svd(s, compute_uv=False)
    assert sv.max() <= 1.0 + 1e-8


@given(
    seed=st.integers(0, 10_000),
    density=st.floats(0.15, 0.7),
)
@settings(**SETTINGS)
def test_solver_converges_and_matches_closed_form(seed, density):
    net = build_net(seed, (7, 6, 5), density)
    norm = net.normalize()
    H, M = norm.assemble_dense()
    cfg = LPConfig(alg="dhlp2", seed_mode="fixed", sigma=1e-7, max_iter=5000)
    res = HeteroLP(cfg).run(net)
    assert res.converged
    want = fixed_seed_solution(
        H * cfg.resolved_hetero_scale(3), M, np.eye(norm.num_nodes), cfg.alpha
    )
    np.testing.assert_allclose(res.F, want, atol=1e-5)


@given(seed=st.integers(0, 10_000))
@settings(**SETTINGS)
def test_labels_nonnegative_and_bounded(seed):
    """Nonnegative inputs → nonnegative labels; fixed-seed labels ≤ 1."""
    net = build_net(seed, (6, 5, 4), 0.4)
    res = HeteroLP(
        LPConfig(alg="dhlp2", seed_mode="fixed", sigma=1e-7)
    ).run(net)
    assert (res.F >= -1e-8).all()
    assert (res.F <= 1.0 + 1e-6).all()


@given(seed=st.integers(0, 10_000))
@settings(**SETTINGS)
def test_permutation_equivariance(seed):
    """Relabeling drugs permutes the output rows/cols identically."""
    rng = np.random.default_rng(seed)
    net = build_net(seed, (6, 5, 4), 0.5)
    perm = rng.permutation(6)
    B = {k: rels[""].toarray() for k, rels in net.blocks.items()}
    P2 = [B[(0, 0)][np.ix_(perm, perm)], B[(1, 1)], B[(2, 2)]]
    R2 = {
        (0, 1): B[(0, 1)][perm],
        (0, 2): B[(0, 2)][perm],
        (1, 2): B[(1, 2)],
    }
    net2 = HeteroNetwork(P=P2, R=R2)
    cfg = LPConfig(alg="dhlp2", seed_mode="fixed", sigma=1e-7, max_iter=5000)
    out1 = extract_outputs(
        HeteroLP(cfg).run(net).F, net.normalize()
    ).interactions[(0, 2)]
    out2 = extract_outputs(
        HeteroLP(cfg).run(net2).F, net2.normalize()
    ).interactions[(0, 2)]
    np.testing.assert_allclose(out2, out1[perm], atol=1e-5)


@given(
    seed=st.integers(0, 10_000),
    alpha=st.floats(0.1, 0.9),
)
@settings(**SETTINGS)
def test_alpha_zero_limit(seed, alpha):
    """As α→0 labels collapse to β²·Y (no propagation)."""
    net = build_net(seed, (6, 5, 4), 0.4)
    res = HeteroLP(
        LPConfig(alg="dhlp2", seed_mode="fixed", alpha=1e-6, sigma=1e-10,
                 max_iter=100)
    ).run(net)
    np.testing.assert_allclose(res.F, np.eye(net.num_nodes), atol=1e-4)


@given(seed=st.integers(0, 10_000))
@settings(**SETTINGS)
def test_symmetrized_outputs_symmetric(seed):
    net = build_net(seed, (5, 4, 4), 0.5)
    norm = net.normalize()
    res = HeteroLP(LPConfig(sigma=1e-5)).run(net)
    out = extract_outputs(res.F, norm)
    for s in out.similarities:
        np.testing.assert_allclose(s, s.T, atol=1e-9)
