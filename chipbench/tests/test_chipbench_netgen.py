"""The generator: the sizes and edge counts its config states, and seeds
that relabel the same network."""
import json

import numpy as np
import pytest

from chipbench import harness, netgen, reference


def _config(scale=1.0):
    cfg = harness.load_json("configs", "dtinet")
    cfg["generator"]["scale"] = scale
    return cfg


@pytest.fixture(scope="module")
def full():
    return netgen.generate(_config())


def test_network_matches_config(full):
    cfg = _config()
    assert list(full.sizes) == [t["nodes"] for t in cfg["types"]]
    assert full.num_nodes == cfg["nodes"]
    assert full.num_edges == cfg["edges"] == sum(r["edges"] for r in cfg["relations"])
    assert full.operator_nnz == cfg["operator_nnz"]


@pytest.mark.parametrize("rel", [r["name"] for r in _config()["relations"]])
def test_each_relation_has_its_count_of_distinct_edges(full, rel):
    cfg = _config()
    spec = next(r for r in cfg["relations"] if r["name"] == rel)
    names = [t["name"] for t in cfg["types"]]
    i, j = sorted(names.index(x) for x in spec["types"])
    if i == j:
        r, c, v = full.sim[i]
        assert np.all(r != c) and np.all(v == 1.0)
        pairs = set(zip(r.tolist(), c.tolist()))
        assert all((b, a) in pairs for a, b in pairs)  # symmetric
        assert len(pairs) == 2 * spec["edges"]
    else:
        r, c = full.assoc[(i, j)]
        assert len(set(zip(r.tolist(), c.tolist()))) == r.size == spec["edges"]
        assert r.max() < full.sizes[i] and c.max() < full.sizes[j]


def test_types_without_a_similarity_block(full):
    names = list(full.type_names)
    for t in ("disease", "side_effect"):
        assert full.sim[names.index(t)][0].size == 0


def test_same_network_seed_same_network():
    a, b = netgen.generate(_config(0.1)), netgen.generate(_config(0.1))
    for x, y in zip(a.sim, b.sim):
        np.testing.assert_array_equal(x[0], y[0])
        np.testing.assert_array_equal(x[1], y[1])
    for k in a.assoc:
        np.testing.assert_array_equal(a.assoc[k][0], b.assoc[k][0])


def test_seeds_relabel_one_network():
    base = netgen.generate(_config(0.1))
    perms = netgen.permutations(base.sizes, 3_000_000_019)
    net = base.relabel(perms)
    assert net.num_edges == base.num_edges
    p = np.concatenate([off + pm for off, pm in zip(base.offsets, perms)])
    A, B = reference.operator(base, 0.5), reference.operator(net, 0.5)
    # B[p[i], p[j]] == A[i, j]: the same operator, in another order
    np.testing.assert_allclose(B[p][:, p].toarray(), A.toarray(), rtol=0, atol=1e-15)


def test_new_associations_are_new_and_seeded():
    net = netgen.generate(_config(0.1))
    edges = netgen.new_associations(net, (0, 2), 12, 1)
    assert edges == netgen.new_associations(net, (0, 2), 12, 1)
    r, c = net.assoc[(0, 2)]
    have = set(zip(r.tolist(), c.tolist()))
    assert len(set(edges)) == 12 and not any((u, v) in have for _, u, v in edges)
    grown = net.with_assoc(edges)
    assert grown.num_edges == net.num_edges + 12
    json.dumps(edges)
