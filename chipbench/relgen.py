"""The benchmark's generator for networks with several relations on one
pair of types (Hetionet's shape), from a configuration's seed.

A configuration names its node types with their node counts, and its
relations with their edge counts (``chipbench/configs/<config>.json``);
several relations may join the same two types.  As in ``netgen.py``, each
relation gets exactly its count of distinct edges of weight 1, drawn with
weight ``theta_u * theta_v``, times ``cluster_boost`` where both ends share
a planted cluster (every node has a Pareto(``degree_exponent``) degree
weight and one of ``n_clusters`` clusters).  A relation within one type is
a similarity relation, symmetric with no diagonal.

Where ``netgen.py`` keys every pair of nodes, this draws edges one at a time
in proportion to their weight and skips repeats (successive sampling: the
same law as ``netgen``'s keys), so the 219 million gene pairs of Hetionet's
Gene–Gene relations are never listed on the host.

A run's ``--seed`` relabels the nodes of every type by a permutation drawn
from it (``netgen.permutations``): the same sizes, degrees and operator, in
another order.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np

from chipbench import netgen

#: the relation name of the empty similarity block a type without one gets
NO_SIMILARITY = "no-similarity"


@dataclasses.dataclass
class Relation:
    """One relation's edges: ``(rows, cols)`` of types ``(i, j)``, ``i <= j``;
    a similarity relation (``i == j``) lists both triangles."""

    name: str
    i: int
    j: int
    rows: np.ndarray
    cols: np.ndarray


@dataclasses.dataclass
class Network:
    """Per-relation COO of one heterogeneous network."""

    sizes: Tuple[int, ...]
    relations: List[Relation]
    type_names: Tuple[str, ...]

    @property
    def offsets(self) -> List[int]:
        return [int(x) for x in np.concatenate([[0], np.cumsum(self.sizes)[:-1]])]

    @property
    def num_nodes(self) -> int:
        return int(sum(self.sizes))

    @property
    def num_edges(self) -> int:
        """Undirected edges, each counted once, as the source counts them."""
        return int(sum(r.rows.size // (2 if r.i == r.j else 1) for r in self.relations))

    @property
    def operator_nnz(self) -> int:
        """Nonzeros of the propagation operator: within one pair of types
        the union of its relations' entries, an association pair's in both
        directions."""
        nnz = 0
        for i, j in {(r.i, r.j) for r in self.relations}:
            keys = [
                r.rows.astype(np.int64) * self.sizes[j] + r.cols
                for r in self.relations
                if (r.i, r.j) == (i, j)
            ]
            nnz += np.unique(np.concatenate(keys)).size * (1 if i == j else 2)
        return int(nnz)

    def relabel(self, perms: List[np.ndarray]) -> "Network":
        """Node ``u`` of type ``t`` becomes ``perms[t][u]``."""
        rels = [
            Relation(r.name, r.i, r.j, perms[r.i][r.rows], perms[r.j][r.cols])
            for r in self.relations
        ]
        return Network(self.sizes, rels, self.type_names)


class _Sampler:
    """Draws nodes of one type in proportion to their weights, from all of
    them or from one planted cluster."""

    def __init__(self, theta: np.ndarray, clusters: np.ndarray, k: int):
        self.order = np.argsort(clusters, kind="stable")
        cum = np.cumsum(theta[self.order])
        start = np.concatenate([[0.0], cum])
        bounds = np.searchsorted(clusters[self.order], np.arange(k + 1))
        self.cum = cum
        self.lo = start[bounds[:-1]]
        self.mass = start[bounds[1:]] - self.lo  # each cluster's weight

    def draw(self, rng: np.random.Generator, size: int, cluster=None) -> np.ndarray:
        r = rng.random(size)
        r = r * self.cum[-1] if cluster is None else self.lo[cluster] + r * self.mass[cluster]
        idx = np.minimum(np.searchsorted(self.cum, r, side="right"), self.order.size - 1)
        return self.order[idx]


def _draw(rng, a: _Sampler, b: _Sampler, n_b: int, count: int, boost: float, same: bool):
    """``count`` distinct edges ``u * n_b + v`` in draw order, each draw
    weighted ``theta_u * theta_v`` (times ``boost`` within a cluster)."""
    extra = (boost - 1.0) * a.mass * b.mass  # the in-cluster surplus
    p_extra = extra.sum() / (a.cum[-1] * b.cum[-1] + extra.sum())
    got = np.zeros(0, dtype=np.int64)
    while got.size < count:
        m = 2 * (count - got.size) + 1024
        in_cluster = rng.random(m) < p_extra
        u, v = a.draw(rng, m), b.draw(rng, m)
        if in_cluster.any():
            c = rng.choice(extra.size, size=int(in_cluster.sum()), p=extra / extra.sum())
            u[in_cluster], v[in_cluster] = a.draw(rng, c.size, c), b.draw(rng, c.size, c)
        if same:  # an unordered pair, no self-loop
            u, v = np.minimum(u, v), np.maximum(u, v)
            keep = u != v
            u, v = u[keep], v[keep]
        keys = np.concatenate([got, u.astype(np.int64) * n_b + v])
        _, first = np.unique(keys, return_index=True)
        got = keys[np.sort(first)]
    return got[:count]


def generate(config: dict) -> Network:
    """The network of a config."""
    gen = config["generator"]
    names, sizes, shape = netgen.shape(config)
    rng = np.random.default_rng(gen["network_seed"])
    k, a, boost = gen["n_clusters"], gen["degree_exponent"], gen["cluster_boost"]
    clusters = [rng.integers(0, k, size=n) for n in sizes]
    thetas = [1.0 + rng.pareto(a, size=n) for n in sizes]
    samplers = [_Sampler(t, c, k) for t, c in zip(thetas, clusters)]
    rels = []
    for spec, (i, j, count) in zip(config["relations"], shape):
        keys = _draw(rng, samplers[i], samplers[j], sizes[j], count, boost, i == j)
        rows, cols = np.divmod(keys, sizes[j])
        rows, cols = rows.astype(np.int32), cols.astype(np.int32)
        if i == j:
            rows, cols = np.concatenate([rows, cols]), np.concatenate([cols, rows])
        rels.append(Relation(spec["name"], i, j, rows, cols))
    return Network(tuple(sizes), rels, tuple(names))


def require_relation_blocks():
    """The program's ``repro.core.network``; raises ``RuntimeError`` where
    it cannot hold several relations on one pair of types, rather than let
    a cell hand it a merged or dense network, which would be another
    computation (or ~16 GB of dense Hetionet blocks on the host).

    The probe is the capability itself: a one-type network whose
    similarity is given as two named relations must be accepted.
    """
    import scipy.sparse as sp

    from repro.core import network as program

    one = sp.csr_matrix(np.ones((2, 2)))
    try:
        program.HeteroNetwork(P=[{"a": one, "b": one}], R={})
    except (TypeError, ValueError) as e:
        raise RuntimeError(
            "this program holds one relation per pair of types; the cell "
            f"needs several (relation-typed blocks in repro.core.network): {e}"
        ) from None
    return program


def to_program(net: Network):
    """The program's ``HeteroNetwork`` of ``net``: one sparse block per
    relation, never merged, never dense.  A type with no similarity
    relation gets an empty one (``NO_SIMILARITY``).
    """
    import scipy.sparse as sp

    program = require_relation_blocks()
    P = [{} for _ in net.sizes]
    R = {}
    for r in net.relations:
        m = sp.csr_matrix(
            (np.ones(r.rows.size), (r.rows, r.cols)), shape=(net.sizes[r.i], net.sizes[r.j])
        )
        (P[r.i] if r.i == r.j else R.setdefault((r.i, r.j), {}))[r.name] = m
    for t, n in enumerate(net.sizes):
        if not P[t]:
            P[t][NO_SIMILARITY] = sp.csr_matrix((n, n))
    return program.HeteroNetwork(P=P, R=R, type_names=net.type_names)
