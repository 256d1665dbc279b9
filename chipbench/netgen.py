"""The benchmark's own network generator: a heterogeneous network with a
configuration's node types, relations and edge counts, from its seed.

A configuration names its node types with their node counts, and its
relations with their edge counts (``chipbench/configs/<config>.json``).
Each relation gets exactly its count of distinct edges, drawn without
replacement (Efraimidis-Spirakis keys) with weight ``theta_u * theta_v``,
times ``cluster_boost`` where both ends share a planted cluster: every node
has a Pareto(``degree_exponent``) degree weight and one of ``n_clusters``
clusters.  A relation within one type is its similarity block, symmetric
with no diagonal; a relation across two types is a binary association
block.  Edges carry weight 1.

The output is per-block COO in float64:

* ``sim[t] = (rows, cols, vals)``: the similarity block of type ``t``, both
  triangles (empty where the configuration gives the type none);
* ``assoc[(i, j)] = (rows, cols)``: the association block, ``i < j``.

A run's ``--seed`` relabels the nodes of every type by a permutation drawn
from it.  The network a run sees is then isomorphic to the config's: the
same sizes, degrees and operator, in another order.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import numpy as np

Pair = Tuple[int, int]
Coo = Tuple[np.ndarray, np.ndarray, np.ndarray]


@dataclasses.dataclass
class Network:
    """Per-block COO of one heterogeneous network."""

    sizes: Tuple[int, ...]
    sim: List[Coo]
    assoc: Dict[Pair, Tuple[np.ndarray, np.ndarray]]
    type_names: Tuple[str, ...]

    @property
    def offsets(self) -> List[int]:
        return [int(x) for x in np.concatenate([[0], np.cumsum(self.sizes)[:-1]])]

    @property
    def num_nodes(self) -> int:
        return int(sum(self.sizes))

    @property
    def num_edges(self) -> int:
        """Undirected edges, each counted once, as a source counts them."""
        return int(
            sum(r.size for r, _, _ in self.sim) // 2
            + sum(r.size for r, _ in self.assoc.values())
        )

    @property
    def operator_nnz(self) -> int:
        """Nonzeros of the propagation operator: each edge in both directions."""
        return 2 * self.num_edges

    def relabel(self, perms: List[np.ndarray]) -> "Network":
        """Node ``u`` of type ``t`` becomes ``perms[t][u]``."""
        sim = [
            (perms[t][r], perms[t][c], v) for t, (r, c, v) in enumerate(self.sim)
        ]
        assoc = {
            (i, j): (perms[i][r], perms[j][c])
            for (i, j), (r, c) in self.assoc.items()
        }
        return Network(self.sizes, sim, assoc, self.type_names)

    def with_assoc(self, edges: List[Tuple[Pair, int, int]]) -> "Network":
        """A copy with the association edges ``(pair, row, col)`` added."""
        assoc = dict(self.assoc)
        for pair, u, v in edges:
            r, c = assoc[pair]
            assoc[pair] = (np.append(r, u), np.append(c, v))
        return Network(self.sizes, self.sim, assoc, self.type_names)


def shape(config: dict):
    """``(names, sizes, relations)`` of a config, cut by its generator's
    ``scale`` (1: the source's sizes; the CPU tests cut it): node counts by
    ``scale``, edge counts by ``scale**2`` (the same density).
    ``relations`` holds ``(i, j, edges)`` with type indices ``i <= j``."""
    scale = config["generator"]["scale"]
    names = [t["name"] for t in config["types"]]
    sizes = [max(4, int(round(t["nodes"] * scale))) for t in config["types"]]
    relations = []
    for rel in config["relations"]:
        i, j = sorted(names.index(x) for x in rel["types"])
        room = sizes[i] * (sizes[i] - 1) // 2 if i == j else sizes[i] * sizes[j]
        edges = rel["edges"] if scale == 1.0 else int(round(rel["edges"] * scale**2))
        relations.append((i, j, min(room, max(1, edges))))
    return names, sizes, relations


def _draw(rng, weight: np.ndarray, count: int) -> np.ndarray:
    """``count`` distinct flat indices of ``weight``, drawn without
    replacement in proportion to it (the largest ``log(U) / w`` keys)."""
    keys = np.log(rng.random(weight.size)) / weight
    return np.sort(np.argpartition(-keys, count - 1)[:count])


def generate(config: dict) -> Network:
    """The network of a config."""
    gen = config["generator"]
    names, sizes, relations = shape(config)
    rng = np.random.default_rng(gen["network_seed"])
    k, a, boost = gen["n_clusters"], gen["degree_exponent"], gen["cluster_boost"]
    clusters = [rng.integers(0, k, size=n) for n in sizes]
    thetas = [1.0 + rng.pareto(a, size=n) for n in sizes]
    empty = np.zeros(0, dtype=np.int32)
    sim: List[Coo] = [(empty, empty, np.zeros(0)) for _ in sizes]
    assoc: Dict[Pair, Tuple[np.ndarray, np.ndarray]] = {}
    for i, j, count in relations:
        if i == j:
            u, v = np.triu_indices(sizes[i], 1)
            w = thetas[i][u] * thetas[i][v]
            w *= np.where(clusters[i][u] == clusters[i][v], boost, 1.0)
            pick = _draw(rng, w, count)
            u, v = u[pick].astype(np.int32), v[pick].astype(np.int32)
            rows, cols = np.concatenate([u, v]), np.concatenate([v, u])
            order = np.lexsort((cols, rows))
            sim[i] = (rows[order], cols[order], np.ones(rows.size))
        else:
            w = np.outer(thetas[i], thetas[j])
            w *= np.where(clusters[i][:, None] == clusters[j][None, :], boost, 1.0)
            pick = _draw(rng, w.ravel(), count)
            rows, cols = np.divmod(pick, sizes[j])
            assoc[(i, j)] = (rows.astype(np.int32), cols.astype(np.int32))
    return Network(tuple(sizes), sim, assoc, tuple(names))


def permutations(sizes, seed: int) -> List[np.ndarray]:
    """One permutation per type, drawn from a run's seed."""
    rng = np.random.default_rng(seed)
    return [rng.permutation(n).astype(np.int32) for n in sizes]


def new_associations(net: Network, pair: Pair, count: int, seed: int):
    """``count`` distinct ``(pair, row, col)`` that ``net`` does not hold."""
    rng = np.random.default_rng(seed)
    i, j = pair
    r, c = net.assoc[pair]
    have = set((r.astype(np.int64) * net.sizes[j] + c).tolist())
    out: List[Tuple[Pair, int, int]] = []
    while len(out) < count:
        u = int(rng.integers(net.sizes[i]))
        v = int(rng.integers(net.sizes[j]))
        key = u * net.sizes[j] + v
        if key not in have:
            have.add(key)
            out.append((pair, u, v))
    return out


def to_program(net: Network):
    """The program's dense ``HeteroNetwork`` of ``net`` (the one adapter)."""
    from repro.core.network import HeteroNetwork

    P = []
    for n, (r, c, v) in zip(net.sizes, net.sim):
        p = np.zeros((n, n))
        p[r, c] = v
        P.append(p)
    R = {}
    for (i, j), (r, c) in net.assoc.items():
        m = np.zeros((net.sizes[i], net.sizes[j]))
        m[r, c] = 1.0
        R[(i, j)] = m
    return HeteroNetwork(P=P, R=R, type_names=net.type_names)
