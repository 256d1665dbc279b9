"""The operator built block by block equals the one built from dense (H, M).

``NormalizedNetwork.to_coo`` extracts each normalized block's nonzeros
without an ``(N, N)`` matrix; ``HeteroCOO.from_dense`` over
``assemble_dense()`` is the oracle.  Every array downstream — the COO, the
fused and split blocked-CSR operators, the exact-width plan buckets — must
be identical, bit for bit.
"""
import numpy as np
import pytest

from repro.core.blocked_csr import (
    blocked_csr_from_network,
    split_blocked_csr_from_network,
)
from repro.core.network import (
    GraphDelta,
    HeteroCOO,
    HeteroNetwork,
    NormalizedNetwork,
)
from repro.core.solver import LPConfig
from repro.engine.sparse import (
    _TIGHTEN_ALIGN,
    _TIGHTEN_MIN_ROWS,
    _TIGHTEN_SLACK,
    SparseCSREngine,
    _tighten_buckets,
)


def _sim(rng, n, density=0.4):
    a = rng.random((n, n)) * (rng.random((n, n)) < density)
    np.fill_diagonal(a, 0)
    return a


def _assoc(rng, n, m, density=0.3):
    return (rng.random((n, m)) < density).astype(float)


def _drugnet():
    from repro.data.drugnet import DrugNetSpec, make_drugnet

    spec = DrugNetSpec(n_drug=48, n_disease=32, n_target=24, n_clusters=6)
    return make_drugnet(spec).network.normalize()


def _empty_block_missing_pair():
    # four types as in DTINet: two types without similarity, and no
    # association between types 1 and 3
    rng = np.random.default_rng(1)
    sizes = (9, 12, 30, 20)
    P = [_sim(rng, sizes[0]), _sim(rng, sizes[1]), np.zeros((30, 30)),
         np.zeros((20, 20))]
    R = {
        (0, 1): _assoc(rng, 9, 12),
        (0, 2): _assoc(rng, 9, 30),
        (1, 2): _assoc(rng, 12, 30),
        (0, 3): _assoc(rng, 9, 20),
    }
    return HeteroNetwork(P=P, R=R).normalize()


def _isolated_nodes():
    rng = np.random.default_rng(2)
    P = [_sim(rng, 10), _sim(rng, 8), _sim(rng, 7)]
    R = {(0, 1): _assoc(rng, 10, 8), (1, 2): _assoc(rng, 8, 7)}
    # nodes with no edge at all: rows/cols cleared in every block
    for t, u in ((0, 3), (1, 0), (2, 6)):
        P[t][u, :] = P[t][:, u] = 0.0
    R[(0, 1)][3, :] = 0.0
    R[(0, 1)][:, 0] = 0.0
    R[(1, 2)][0, :] = 0.0
    R[(1, 2)][:, 6] = 0.0
    return HeteroNetwork(P=P, R=R).normalize()


def _after_delta():
    rng = np.random.default_rng(3)
    net = HeteroNetwork(
        P=[_sim(rng, 11), _sim(rng, 9), _sim(rng, 6)],
        R={(0, 1): _assoc(rng, 11, 9), (0, 2): _assoc(rng, 11, 6)},
    )
    delta = GraphDelta(
        add_nodes={0: 2, 2: 3},
        assoc=(((2, 0), 7, 11, 1.0), ((0, 1), 12, 4, 1.0)),
        sim=((2, 0, 8, 0.5),),
    )
    return net.apply_delta(delta).normalize()


def _assoc_ji_network():
    rng = np.random.default_rng(4)
    P = [_sim(rng, 7), _sim(rng, 5), _sim(rng, 9)]
    R = {(2, 0): _assoc(rng, 9, 7), (1, 0): _assoc(rng, 5, 7)}
    return HeteroNetwork(P=P, R=R).normalize()


def _assoc_ji_normalized():
    # the normalized container itself keyed (j, i): the block is (n_j, n_i)
    norm = _assoc_ji_network()
    return NormalizedNetwork(
        S_homo=norm.S_homo,
        S_het={(j, i): s.T.copy() for (i, j), s in norm.S_het.items()},
        sizes=norm.sizes,
    )


NETWORKS = {
    "drugnet": _drugnet,
    "empty_block_missing_pair": _empty_block_missing_pair,
    "isolated_nodes": _isolated_nodes,
    "after_add_nodes_delta": _after_delta,
    "assoc_ji_network": _assoc_ji_network,
    "assoc_ji_normalized": _assoc_ji_normalized,
}

_COO_FIELDS = ("het_src", "het_dst", "het_w", "hom_src", "hom_dst", "hom_w")
_CSR_FIELDS = ("col_idx", "val", "row_ptr", "widths")


@pytest.fixture(params=sorted(NETWORKS))
def norm(request):
    return NETWORKS[request.param]()


def _dense_coo(self):
    return HeteroCOO.from_dense(*self.assemble_dense(), sizes=self.sizes)


def _assert_same(a, b, fields):
    for f in fields:
        x, y = getattr(a, f), getattr(b, f)
        assert x.dtype == y.dtype, f
        assert np.array_equal(x, y), f


def test_to_coo_matches_dense_oracle(norm):
    coo, want = norm.to_coo(), _dense_coo(norm)
    _assert_same(coo, want, _COO_FIELDS)
    assert coo.num_nodes == want.num_nodes
    assert coo.sizes == want.sizes
    assert coo.het_w.size > 0 and coo.hom_w.size > 0


@pytest.mark.parametrize("layout", [(64, 8), (8, 1)])
def test_fused_blocked_csr_matches_dense_path(norm, layout, monkeypatch):
    br, wm = layout
    kw = dict(alpha=0.5, hetero_scale=0.5, block_rows=br, width_mult=wm)
    got = blocked_csr_from_network(norm, **kw)
    monkeypatch.setattr(NormalizedNetwork, "to_coo", _dense_coo)
    _assert_same(got, blocked_csr_from_network(norm, **kw), _CSR_FIELDS)


def test_split_blocked_csr_matches_dense_path(norm, monkeypatch):
    kw = dict(hetero_scale=0.5, block_rows=8, width_mult=4)
    got = split_blocked_csr_from_network(norm, **kw)
    monkeypatch.setattr(NormalizedNetwork, "to_coo", _dense_coo)
    for a, b in zip(got, split_blocked_csr_from_network(norm, **kw)):
        _assert_same(a, b, _CSR_FIELDS)


def _tighten_per_row(buckets):
    """The per-row re-bucketing the array version replaced (the oracle)."""
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


@pytest.mark.parametrize("layout", [(64, 8), (4, 1)])
def test_tighten_matches_per_row_oracle(norm, layout):
    br, wm = layout
    buckets = blocked_csr_from_network(
        norm, alpha=0.5, hetero_scale=0.5, block_rows=br, width_mult=wm
    ).width_buckets()
    got, want = _tighten_buckets(buckets), _tighten_per_row(buckets)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        for x, y in zip(g, w):  # rows, nbr, wgt
            assert x.dtype == y.dtype and x.shape == y.shape
            assert np.array_equal(x, y)


@pytest.mark.parametrize("alg", ["dhlp2", "dhlp1"])
def test_prepare_never_assembles_dense(alg, monkeypatch):
    norm = _empty_block_missing_pair()

    def _no_dense(self):
        raise AssertionError("an (N, N) matrix was assembled")

    monkeypatch.setattr(NormalizedNetwork, "assemble_dense", _no_dense)
    blocked_csr_from_network(norm, alpha=0.5, hetero_scale=0.5)
    split_blocked_csr_from_network(norm, hetero_scale=0.5)
    eng = SparseCSREngine(LPConfig(alg=alg, seed_mode="fixed"))
    op = eng.prepare(norm)
    res = eng.solve(op, np.eye(norm.num_nodes)[:, :3])
    assert np.isfinite(res.F).all()
