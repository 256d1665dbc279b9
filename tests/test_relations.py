"""Networks with several relations on one block (Hetionet's shape).

Each relation is normalized on its own and a block is the mean of its
relations.  Every engine must land on the fixed point of that operator as
the float64 oracle (``core/reference.py``) builds it from the raw
relations; single-relation networks keep today's operator bit for bit; a
delta re-normalizes only the relations it touches.
"""
import numpy as np
import pytest
import scipy.sparse as sp

from repro.core import LPConfig
from repro.core.network import (
    GraphDelta,
    HeteroCOO,
    HeteroNetwork,
    NormalizedNetwork,
)
from repro.core.normalize import bipartite_normalize, symmetric_normalize
from repro.core.reference import fixed_point, relation_mean_operator
from repro.engine import make_engine

SIZES = (14, 10, 8)
CASES = ("overlap", "three_sims", "zero_degree", "no_sim")


def _edges(rng, shape, density, *, sym=False):
    m = (rng.random(shape) < density).astype(float)
    if sym:
        m = np.triu(m, 1)
        m = m + m.T
    return m


def relation_network(case: str, seed: int = 0):
    """``(net, relations)``: a seeded random network of 3 types, and the
    same relations as ``(i, j, dense matrix)`` for the oracle."""
    rng = np.random.default_rng([seed, CASES.index(case)])
    n = SIZES
    rels = [
        (0, 0, "a", _edges(rng, (n[0], n[0]), 0.3, sym=True)),
        (1, 1, "a", _edges(rng, (n[1], n[1]), 0.3, sym=True)),
        (2, 2, "a", _edges(rng, (n[2], n[2]), 0.3, sym=True)),
        (0, 1, "a", _edges(rng, (n[0], n[1]), 0.25)),
        (0, 2, "a", _edges(rng, (n[0], n[2]), 0.25)),
        (1, 2, "a", _edges(rng, (n[1], n[2]), 0.25)),
    ]
    if case == "overlap":  # two relations on (0, 2), sharing some entries
        first = rels[4][3]
        second = np.maximum(first * (rng.random(first.shape) < 0.5), _edges(rng, first.shape, 0.2))
        rels.append((0, 2, "b", second))
    elif case == "three_sims":  # type 1 has three similarity relations
        rels += [(1, 1, k, _edges(rng, (n[1], n[1]), 0.3, sym=True)) for k in ("b", "c")]
    elif case == "zero_degree":  # rows and columns with no edge in one relation
        m = _edges(rng, (n[0], n[1]), 0.4)
        m[:5] = 0.0
        m[:, -3:] = 0.0
        rels.append((0, 1, "b", m))
    elif case == "no_sim":  # type 2 has no similarity relation
        rels[2] = (2, 2, "a", np.zeros((n[2], n[2])))
    P = [{} for _ in n]
    R = {}
    for i, j, name, m in rels:
        (P[i] if i == j else R.setdefault((i, j), {}))[name] = sp.csr_matrix(m)
    return HeteroNetwork(P=P, R=R), [(i, j, m) for i, j, _, m in rels]


def _oracle(relations, alpha, seeds):
    A = relation_mean_operator(SIZES, relations, alpha)
    return fixed_point(A, seeds, alpha)


def _seeds(n, cols):
    Y = np.zeros((n, len(cols)), np.float32)
    Y[cols, np.arange(len(cols))] = 1.0
    return Y


@pytest.mark.parametrize("alg", ["dhlp2", "dhlp1"])
@pytest.mark.parametrize("backend", ["dense", "sparse", "sharded"])
@pytest.mark.parametrize("case", CASES)
def test_engine_agrees_with_relation_mean_oracle(case, backend, alg):
    net, relations = relation_network(case)
    Y = _seeds(net.num_nodes, [0, 3, 15, 20, 29, 31])
    cfg = LPConfig(alg=alg, sigma=1e-8, seed_mode="fixed", max_iter=2000)
    res = make_engine(backend, cfg).run(net.normalize(), seeds=Y)
    want = _oracle(relations, cfg.alpha, Y)
    assert res.converged
    np.testing.assert_allclose(res.F, want, rtol=0, atol=2e-6)


@pytest.mark.parametrize("case", CASES)
def test_block_is_the_mean_of_its_relations(case):
    net, relations = relation_network(case)
    norm = net.normalize()
    A = relation_mean_operator(SIZES, relations, 0.5, hetero_scale=1.0)
    H, M = norm.assemble_dense()
    np.testing.assert_allclose(0.5 * M + 0.25 * H, A, rtol=0, atol=1e-15)


def test_operator_nnz_is_the_union_within_a_block():
    net, relations = relation_network("overlap")
    a, b = (m for i, j, m in relations if (i, j) == (0, 2))
    norm = net.normalize()
    assert norm.S_het[(0, 2)].nnz == np.count_nonzero(a + b) < np.count_nonzero(a) + np.count_nonzero(b)
    assert norm.operator_nnz == norm.to_coo().num_edges


def test_dense_and_sparse_relations_give_one_operator():
    net, relations = relation_network("three_sims")
    P = [{} for _ in SIZES]
    R = {}
    for (i, j), name, m in net.relations():
        (P[i] if i == j else R.setdefault((i, j), {}))[name] = m.toarray()
    dense = HeteroNetwork(P=P, R=R).normalize().to_coo()
    sparse = net.normalize().to_coo()
    for f in ("het_src", "het_dst", "hom_src", "hom_dst"):
        np.testing.assert_array_equal(getattr(dense, f), getattr(sparse, f))
    np.testing.assert_array_equal(dense.het_w, sparse.het_w)
    np.testing.assert_array_equal(dense.hom_w, sparse.hom_w)


@pytest.mark.parametrize("given", ["dense", "csr", "coo"])
def test_relations_are_held_as_canonical_csr(given):
    rng = np.random.default_rng(5)
    p = _edges(rng, (6, 6), 0.5, sym=True)
    r = _edges(rng, (6, 4), 0.5)
    wrap = {"dense": np.asarray, "csr": sp.csr_matrix, "coo": sp.coo_matrix}[given]
    net = HeteroNetwork(P=[wrap(p), wrap(np.zeros((4, 4)))], R={(1, 0): wrap(r.T)})
    for _, _, m in net.relations():
        assert isinstance(m, sp.csr_matrix) and m.has_canonical_format
        assert m.dtype == np.float64 and np.all(m.data != 0)
    np.testing.assert_array_equal(net.blocks[(0, 1)][""].toarray(), r)
    np.testing.assert_array_equal(net.support((1, 0)), r.T > 0)


@pytest.mark.parametrize("shape", [(7, 300), (40, 9000), (3, 3)])
@pytest.mark.parametrize("weights", ["real", "counts"])
def test_row_sums_are_numpys_dense_row_sums(shape, weights, monkeypatch):
    """The degrees ``normalize_relation`` uses equal ``ndarray.sum(axis=1)``
    bit for bit, which sums a row pairwise; a plain sum over the stored
    entries would not (real weights).  Small chunks: several a matrix."""
    from repro.core import normalize
    from repro.core.normalize import row_sums

    monkeypatch.setattr(normalize, "_ROW_CHUNK", 1000)
    rng = np.random.default_rng(shape)
    a = rng.random(shape) * (rng.random(shape) < 0.4)
    if weights == "counts":
        a = np.ceil(a * 3)
    np.testing.assert_array_equal(row_sums(sp.csr_matrix(a)), a.sum(axis=1))


# ------------------------------------------------ single relation: unchanged
def _todays_coo(P, R, sizes):
    """The single-relation operator as the dense path builds it: each block
    normalized densely, assembled into (H, M), then ``from_dense``."""
    n = sum(sizes)
    off = np.concatenate([[0], np.cumsum(sizes)])
    H, M = np.zeros((n, n)), np.zeros((n, n))
    for t, p in enumerate(P):
        p = np.asarray(p, np.float64)
        M[off[t] : off[t + 1], off[t] : off[t + 1]] = symmetric_normalize((p + p.T) / 2.0)
    for (i, j), r in R.items():
        s = bipartite_normalize(np.asarray(r, np.float64))
        H[off[i] : off[i + 1], off[j] : off[j + 1]] = s
        H[off[j] : off[j + 1], off[i] : off[i + 1]] = s.T
    return HeteroCOO.from_dense(H, M, sizes)


def _single_relation_inputs(kind):
    if kind == "drugnet":
        from repro.data.drugnet import DrugNetSpec, make_drugnet

        net = make_drugnet(DrugNetSpec(n_drug=30, n_disease=20, n_target=15, seed=3)).network
        P = [net.blocks[(t, t)][""].toarray() for t in range(net.num_types)]
        R = {p: r[""].toarray() for p, r in net.blocks.items() if p[0] != p[1]}
        return P, R
    rng = np.random.default_rng(7)
    P = [_edges(rng, (n, n), 0.3, sym=True) * rng.random((n, n)) for n in SIZES]
    P = [(p + p.T) / 2 for p in P]
    R = {(0, 1): _edges(rng, (14, 10), 0.3), (1, 2): _edges(rng, (10, 8), 0.3)}
    if kind == "sparse":
        P = [sp.csr_matrix(p) for p in P]
        R = {k: sp.csr_matrix(r) for k, r in R.items()}
    return P, R


@pytest.mark.parametrize("kind", ["drugnet", "dense", "sparse"])
def test_single_relation_coo_is_todays_bit_for_bit(kind):
    P, R = _single_relation_inputs(kind)
    net = HeteroNetwork(P=P, R=R)
    got = net.normalize().to_coo()
    dense_P = [p.toarray() if sp.issparse(p) else p for p in P]
    dense_R = {k: r.toarray() if sp.issparse(r) else r for k, r in R.items()}
    want = _todays_coo(dense_P, dense_R, net.sizes)
    for f in ("het_src", "het_dst", "het_w", "hom_src", "hom_dst", "hom_w"):
        np.testing.assert_array_equal(getattr(got, f), getattr(want, f), err_msg=f)


def test_assemble_dense_is_the_dense_engines_alone(monkeypatch):
    """A relation network normalizes, prepares and solves on the sparse and
    sharded engines with ``assemble_dense`` made to raise."""

    def refuse(self):
        raise AssertionError("assemble_dense called off the dense engine")

    monkeypatch.setattr(NormalizedNetwork, "assemble_dense", refuse)
    net, relations = relation_network("overlap")
    norm = net.normalize()
    Y = _seeds(net.num_nodes, [1, 17, 30])
    want = _oracle(relations, 0.5, Y)
    for backend in ("sparse", "sharded"):
        for alg in ("dhlp2", "dhlp1"):
            cfg = LPConfig(alg=alg, sigma=1e-8, seed_mode="fixed", max_iter=2000)
            res = make_engine(backend, cfg).run(norm, seeds=Y)
            np.testing.assert_allclose(res.F, want, rtol=0, atol=2e-6)


# ----------------------------------------------------------------- deltas
def _assert_same_norm(a: NormalizedNetwork, b: NormalizedNetwork):
    ca, cb = a.to_coo(), b.to_coo()
    for f in ("het_src", "het_dst", "het_w", "hom_src", "hom_dst", "hom_w"):
        np.testing.assert_array_equal(getattr(ca, f), getattr(cb, f), err_msg=f)
    assert a.sizes == b.sizes


@pytest.mark.parametrize(
    "delta",
    [
        GraphDelta(assoc=(((0, 2), 3, 4, 1.0, "b"), ((2, 0), 1, 2, 0.0, "a"))),
        GraphDelta(sim=((1, 2, 5, 1.0, "a"),)),
        GraphDelta(assoc=(((0, 2), 14, 1, 1.0, "b"),), add_nodes={0: 2}),
        GraphDelta(assoc=(((0, 1), 2, 3, 1.0, "c"),)),  # a relation the delta creates
    ],
    ids=["two-relations", "similarity", "grow", "new-relation"],
)
def test_relation_delta_matches_normalizing_from_scratch(delta):
    net, _ = relation_network("overlap")
    norm = net.normalize()
    new = net.apply_delta(delta)
    touched = net.touched_relations(delta)
    _assert_same_norm(norm.renormalized(new, touched), new.normalize())
    # relations the delta does not touch are shared, not copied
    for pair, name, m in net.relations():
        if (pair, name) not in touched:
            assert new.blocks[pair][name] is m


def test_unnamed_delta_on_a_single_relation_pair_applies():
    net, _ = relation_network("overlap")
    delta = GraphDelta(assoc=(((1, 2), 0, 0, 1.0),))
    new = net.apply_delta(delta)
    assert new.blocks[(1, 2)]["a"][0, 0] == 1.0
    assert net.touched_relations(delta) == {((1, 2), "a")}
    with pytest.raises(ValueError, match="must name one"):
        net.apply_delta(GraphDelta(assoc=(((0, 2), 0, 0, 1.0),)))


def test_delta_on_dense_single_relation_network_edits_one_block():
    P, R = _single_relation_inputs("dense")
    net = HeteroNetwork(P=P, R=R)
    delta = GraphDelta(assoc=(((1, 0), 2, 3, 1.0),))
    new = net.apply_delta(delta)
    assert net.touched_relations(delta) == {((0, 1), "")}
    assert new.blocks[(0, 1)][""][3, 2] == 1.0
    assert new.blocks[(1, 2)][""] is net.blocks[(1, 2)][""]
    _assert_same_norm(net.normalize().renormalized(new, net.touched_relations(delta)), new.normalize())


def test_serve_delta_renormalizes_only_the_touched_relation():
    from repro.obs import Telemetry
    from repro.serve import LPServeEngine, QuerySpec, ServeConfig

    net, _ = relation_network("three_sims")
    tel = Telemetry("trace")
    cfg = ServeConfig(lp=LPConfig(alg="dhlp2", seed_mode="fixed", sigma=1e-6), engine="sparse")
    eng = LPServeEngine(net, cfg, telemetry=tel)
    eng.apply_delta(GraphDelta(sim=((1, 0, 4, 1.0, "c"),)))
    eng.apply_delta(GraphDelta(assoc=(((0, 1), 0, 0, 1.0),), add_nodes={2: 1}))
    spans = [e for e in tel.events() if e.get("span") == "serve.delta.normalize"]
    # one similarity relation; then the edited (0, 1) relation and every
    # relation on type 2's three blocks
    assert [s["attrs"]["relations"] for s in spans] == [1, 4]
    res = eng.query(QuerySpec(entity=0, target_type=1, top_k=5))
    assert res.version == 2


def test_rank_excludes_a_pair_any_relation_holds():
    from repro.serve import LPServeEngine, QuerySpec, ServeConfig

    net, relations = relation_network("overlap")
    a, b = (m for i, j, m in relations if (i, j) == (0, 2))
    u = int(np.argmax((b > 0).sum(axis=1) - (a > 0).sum(axis=1)))
    only_b = set(np.nonzero((b[u] > 0) & (a[u] == 0))[0].tolist())
    assert only_b
    eng = LPServeEngine(net, ServeConfig(lp=LPConfig(alg="dhlp2", seed_mode="fixed", sigma=1e-6)))
    res = eng.query(QuerySpec(entity=u, target_type=2, top_k=50))
    known = set(np.nonzero((a[u] > 0) | (b[u] > 0))[0].tolist())
    assert not known & set(res.candidates.tolist())
    # from the other side of the pair: a disease's known compounds
    v = next(iter(only_b))
    res = eng.query(QuerySpec(entity=net.offsets[2] + v, target_type=0, top_k=50))
    assert u not in res.candidates.tolist()


# ---------------------------------------------------------------- storage
@pytest.mark.parametrize("case", ["overlap", "no_sim"])
def test_npz_round_trip_keeps_relations(tmp_path, case):
    net, _ = relation_network(case)
    path = net.save_npz(str(tmp_path / "net"))
    loaded = HeteroNetwork.load_npz(path)
    assert loaded.sizes == net.sizes
    assert [(p, n) for p, n, _ in loaded.relations()] == [(p, n) for p, n, _ in net.relations()]
    for (pair, name, m), (_, _, got) in zip(net.relations(), loaded.relations()):
        assert sp.issparse(got)
        np.testing.assert_array_equal(got.toarray(), m.toarray())
    _assert_same_norm(loaded.normalize(), net.normalize())


def test_single_relation_file_still_loads(tmp_path):
    P, R = _single_relation_inputs("dense")
    arrays = {f"P_{t}": p for t, p in enumerate(P)}
    arrays.update({f"R_{i}_{j}": r for (i, j), r in R.items()})
    path = str(tmp_path / "old.npz")
    np.savez_compressed(path, **arrays)
    loaded = HeteroNetwork.load_npz(path)
    _assert_same_norm(loaded.normalize(), HeteroNetwork(P=P, R=R).normalize())
    assert loaded.num_relations == len(P) + len(R)


def test_network_normalize_span_at_trace_level_only():
    from repro.api import NetworkSpec, ObsSpec, RunSpec, Session, SolveSpec
    from repro.scenarios.base import ScenarioBundle

    net, _ = relation_network("three_sims")
    bundle = ScenarioBundle(name="rel", network=net, truth={}, eval_pair=(0, 1))
    for level, want in (("trace", 1), ("off", 0)):
        spec = RunSpec(
            network=NetworkSpec(kind="scenario", name="powerlaw"),
            solve=SolveSpec(backend="sparse"),
            obs=ObsSpec(level=level),
        )
        sess = Session(spec, bundle=bundle)
        sess.norm
        spans = [e for e in sess.telemetry.events() if e.get("span") == "network.normalize"]
        assert len(spans) == want
        if want:
            assert spans[0]["attrs"] == {"relations": 8, "blocks": 6}
