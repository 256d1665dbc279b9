"""The relation generator: the sizes and per-relation edge counts its config
states, several relations on one pair, seeds that relabel one network; and
the dtinet cells' generator left as it was."""
import hashlib

import numpy as np
import pytest

from chipbench import harness, netgen, relgen, relref


def _config(name="hetionet", scale=1.0):
    cfg = harness.load_json("configs", name)
    cfg["generator"]["scale"] = scale
    return cfg


@pytest.fixture(scope="module")
def full():
    return relgen.generate(_config())


def test_network_matches_config(full):
    cfg = _config()
    assert list(full.sizes) == [t["nodes"] for t in cfg["types"]]
    assert list(full.type_names) == [t["name"] for t in cfg["types"]]
    assert full.num_nodes == cfg["nodes"] == 47_031
    assert full.num_edges == cfg["edges"] == sum(r["edges"] for r in cfg["relations"]) == 2_250_197
    assert len(full.relations) == len(cfg["relations"]) == 24
    # the union within a pair: fewer than the relations' sum where they overlap
    assert full.operator_nnz == cfg["operator_nnz"] < 2 * cfg["edges"]


@pytest.mark.parametrize("rel", [r["name"] for r in _config()["relations"]])
def test_each_relation_has_its_count_of_distinct_edges(full, rel):
    spec = next(r for r in _config()["relations"] if r["name"] == rel)
    got = next(r for r in full.relations if r.name == rel)
    keys = got.rows.astype(np.int64) * full.sizes[got.j] + got.cols
    assert np.unique(keys).size == keys.size
    assert keys.size == spec["edges"] * (2 if got.i == got.j else 1)
    assert got.rows.max() < full.sizes[got.i] and got.cols.max() < full.sizes[got.j]


def test_pairs_with_several_relations(full):
    names = list(full.type_names)
    count = {}
    for r in full.relations:
        count[(names[r.i], names[r.j])] = count.get((names[r.i], names[r.j]), 0) + 1
    several = {p for p, c in count.items() if c > 1}
    assert several == {
        ("Anatomy", "Gene"), ("Compound", "Gene"), ("Compound", "Disease"),
        ("Disease", "Gene"), ("Gene", "Gene"),
    }
    assert sum(1 for r in full.relations if "Gene" in (names[r.i], names[r.j])) == 16


def test_similarity_relations_symmetric_without_loops():
    net = relgen.generate(_config(scale=0.1))
    for r in net.relations:
        if r.i == r.j:
            assert np.all(r.rows != r.cols)
            pairs = set(zip(r.rows.tolist(), r.cols.tolist()))
            assert all((b, a) in pairs for a, b in pairs)


def test_seeds_relabel_one_network():
    base = relgen.generate(_config(scale=0.1))
    perms = netgen.permutations(base.sizes, 3_000_000_019)
    net = base.relabel(perms)
    assert net.num_edges == base.num_edges and net.operator_nnz == base.operator_nnz
    p = np.concatenate([off + pm for off, pm in zip(base.offsets, perms)])
    A, B = relref.operator(base, 0.5), relref.operator(net, 0.5)
    assert abs(B[p][:, p] - A).max() <= 1e-15  # the same operator, in another order


def test_same_network_seed_same_network():
    a, b = relgen.generate(_config(scale=0.1)), relgen.generate(_config(scale=0.1))
    for x, y in zip(a.relations, b.relations):
        np.testing.assert_array_equal(x.rows, y.rows)
        np.testing.assert_array_equal(x.cols, y.cols)


def test_dtinet_generator_unchanged():
    """The dtinet cells read the network they read before."""
    net = netgen.generate(_config("dtinet"))
    h = hashlib.sha256()
    for r, c, v in net.sim:
        h.update(r.tobytes())
        h.update(c.tobytes())
        h.update(v.tobytes())
    for k in sorted(net.assoc):
        r, c = net.assoc[k]
        h.update(repr(k).encode())
        h.update(r.tobytes())
        h.update(c.tobytes())
    assert h.hexdigest() == "08b7cf34e8a47a9f19ae0806f7ad304aad64f22725b472c69ebfdc59375c9322"
