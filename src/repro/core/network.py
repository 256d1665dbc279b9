"""Heterogeneous network container.

The paper's network (Fig. 1) has T node types (drug / disease / target in the
case study, T=3 but the container is generic), with

* ``P[i]``   — an ``(n_i, n_i)`` similarity (proximity) matrix per type, and
* ``R[(i,j)]`` — an ``(n_i, n_j)`` binary association matrix per type pair.

One type's similarity, or one pair's association, is a *block*.  The paper
has one relation per block; a network such as Hetionet has several on one
block (Compound–Gene binds, downregulates and upregulates), so a block holds
named *relations*, each a canonical ``scipy.sparse`` CSR matrix, whether it
was given dense or sparse.  From here to the sparse engines' edge lists
nothing builds a dense block; only the dense engine's ``assemble_dense``
does.

Node ids are globally flattened by concatenating types: type ``i`` occupies
rows ``[offset[i], offset[i] + n_i)``.  (The paper instead interleaves ids as
``3x + i`` so a Giraph vertex can recover its type with ``id % 3``; with
tensorized storage the block layout carries the same information and keeps
every block contiguous, which is what the MXU wants.)
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Mapping, Optional, Sequence, Set, Tuple

import numpy as np
import scipy.sparse as sp

from repro.core.normalize import (
    canonical_csr,
    csr_rows,
    normalize_relation,
    relation_mean,
)

TypePair = Tuple[int, int]
#: one relation of one block: ``((i, j), name)``, ``i <= j``
RelationKey = Tuple[TypePair, str]

#: the name of the relation of a block given as one matrix
DEFAULT_RELATION = ""


def _named(x) -> Dict[str, object]:
    """A block given as one matrix or as ``{relation: matrix}``."""
    if isinstance(x, Mapping):
        if not x:
            raise ValueError("a block given as relations needs at least one")
        return {str(k): v for k, v in x.items()}
    return {DEFAULT_RELATION: x}


def _row(m: sp.csr_matrix, u: int, transpose: bool) -> np.ndarray:
    """Row ``u`` of ``m`` (column ``u`` when ``transpose``), dense 1-D."""
    part = m[:, [u]] if transpose else m[[u]]
    return part.toarray().ravel()


def _grown(m: sp.csr_matrix, shape: Tuple[int, int]) -> sp.csr_matrix:
    """``m`` padded with zero rows and columns to ``shape``."""
    indptr = np.concatenate([m.indptr, np.full(shape[0] - m.shape[0], m.indptr[-1])])
    return sp.csr_matrix((m.data, m.indices, indptr), shape=shape)


def _edited(
    m: sp.csr_matrix, edits: Sequence[Tuple[int, int, float]]
) -> sp.csr_matrix:
    """A copy of ``m`` with each ``(row, col, weight)`` set in order (the
    last edit of an entry wins; weight 0 removes it)."""
    u, v, w = (np.asarray(x) for x in zip(*edits))
    key = u.astype(np.int64) * m.shape[1] + v
    _, first_of_reversed = np.unique(key[::-1], return_index=True)
    last = key.size - 1 - first_of_reversed
    rows = csr_rows(m)
    keep = ~np.isin(rows.astype(np.int64) * m.shape[1] + m.indices, key)
    r = np.concatenate([rows[keep], u[last]])
    c = np.concatenate([m.indices[keep], v[last]])
    d = np.concatenate([m.data[keep], w[last].astype(np.float64)])
    return canonical_csr(sp.coo_matrix((d, (r, c)), shape=m.shape))


def _normalized(pair: TypePair, m: sp.csr_matrix) -> sp.csr_matrix:
    """One relation normalized on its own (paper §3.1)."""
    return normalize_relation(m, similarity=pair[0] == pair[1])


class HeteroNetwork:
    """A heterogeneous network: T homogeneous nets + inter-type associations.

    Args:
      P: per type ``t``, its similarity: an ``(n_t, n_t)`` matrix, or
         ``{relation: matrix}`` for several similarity relations.
         Nonnegative, assumed symmetric (symmetrized on construction).  A
         type with no similarity relation takes an empty (sparse) matrix.
      R: ``(i, j)`` → the ``(n_i, n_j)`` association matrix, or
         ``{relation: matrix}``; a pair given as ``(j, i)`` is transposed.
      type_names: optional human names per type (e.g. drug/disease/target).

    Each matrix is a dense array or a ``scipy.sparse`` matrix, held as a
    canonical CSR matrix (``canonical_csr``).  ``blocks`` holds them:
    ``(i, j)`` with ``i <= j`` → ``{relation: matrix}``, where ``(t, t)`` is
    type ``t``'s similarity and a block given as one matrix has the one
    relation ``DEFAULT_RELATION``.  Networks derived from this one
    (``apply_delta``, ``with_masked_fold``) share the matrices they do not
    edit: treat them as read-only.
    """

    def __init__(
        self,
        P: Sequence,
        R: Mapping,
        type_names: Optional[Sequence[str]] = None,
    ):
        blocks: Dict[TypePair, Dict[str, sp.csr_matrix]] = {}
        for t, p in enumerate(P):
            rels = {k: canonical_csr(m) for k, m in _named(p).items()}
            # Similarity must be symmetric for the convergence proof; enforce.
            for k, m in rels.items():
                if m.shape[0] != m.shape[1]:
                    raise ValueError(f"P[{t}] must be square, got {m.shape}")
                rels[k] = canonical_csr((m + m.T) / 2.0)
            blocks[(t, t)] = rels
        for (i, j), r in R.items():
            if i == j:
                raise ValueError(f"R[{(i, j)}] must connect two distinct types")
            rels = {k: canonical_csr(m) for k, m in _named(r).items()}
            if i > j:  # canonicalize to i < j
                i, j = j, i
                rels = {k: canonical_csr(m.T) for k, m in rels.items()}
            if (i, j) in blocks:
                raise ValueError(f"duplicate association block {(i, j)}")
            blocks[(i, j)] = rels
        self._set_blocks(blocks, type_names)

    @classmethod
    def _of_blocks(cls, blocks, type_names) -> "HeteroNetwork":
        """A network of already-canonical blocks (no copy, no symmetrizing)."""
        net = cls.__new__(cls)
        net._set_blocks(blocks, type_names)
        return net

    def _set_blocks(self, blocks, type_names) -> None:
        sizes = []
        for t in range(len(blocks)):
            rels = blocks.get((t, t))
            if rels is None:
                break
            shapes = {m.shape for m in rels.values()}
            if len(shapes) != 1:
                raise ValueError(
                    f"similarity relations of type {t} differ in shape: {shapes}"
                )
            sizes.append(shapes.pop()[0])
        for (i, j), rels in blocks.items():
            if i != j:
                if not (0 <= i < j < len(sizes)):
                    raise ValueError(f"R[{(i, j)}]: no such pair of types")
                for m in rels.values():
                    if m.shape != (sizes[i], sizes[j]):
                        raise ValueError(
                            f"R[{(i, j)}] shape {m.shape} != {(sizes[i], sizes[j])}"
                        )
        if type_names is not None and len(type_names) != len(sizes):
            raise ValueError("type_names length mismatch")
        self.blocks: Dict[TypePair, Dict[str, sp.csr_matrix]] = blocks
        self.type_names = type_names
        self._sizes = sizes

    def __repr__(self) -> str:
        return (
            f"HeteroNetwork(sizes={self.sizes}, blocks={len(self.blocks)}, "
            f"relations={self.num_relations}, type_names={self.type_names})"
        )

    # ---------------------------------------------------------------- sizes
    @property
    def num_types(self) -> int:
        return len(self._sizes)

    @property
    def sizes(self) -> List[int]:
        return list(self._sizes)

    @property
    def num_nodes(self) -> int:
        return int(sum(self._sizes))

    @property
    def offsets(self) -> List[int]:
        out, acc = [], 0
        for n in self._sizes:
            out.append(acc)
            acc += n
        return out

    @property
    def num_edges(self) -> int:
        """Count of nonzero (undirected) entries, paper's |E| convention,
        summed over relations."""
        total = 0
        for (i, j), _, m in self.relations():
            total += m.nnz if i == j else 2 * m.nnz
        return total

    @property
    def num_relations(self) -> int:
        return sum(len(rels) for rels in self.blocks.values())

    def relations(self) -> Iterator[Tuple[TypePair, str, sp.csr_matrix]]:
        """Every relation as ``(block, name, matrix)``."""
        for pair, rels in self.blocks.items():
            for name, m in rels.items():
                yield pair, name, m

    def support(self, pair: TypePair) -> np.ndarray:
        """Which nodes of ``pair``'s two types are known to be linked: a
        dense ``(n_i, n_j)`` boolean, True where any relation of the block
        holds an entry (for truth masks and for excluding known pairs; the
        operator's weights are :meth:`normalize`'s)."""
        i, j = pair
        key = (min(i, j), max(i, j))
        out = np.zeros((self._sizes[key[0]], self._sizes[key[1]]), dtype=bool)
        for m in self.blocks.get(key, {}).values():
            out[csr_rows(m), m.indices] = True
        return out if i <= j else out.T

    def relation_rows(self, i: int, j: int, u: int) -> List[np.ndarray]:
        """Node ``u`` of type ``i`` against every node of type ``j``: one
        dense row per relation of the block (none where there is none)."""
        if i <= j:
            return [_row(m, u, False) for m in self.blocks.get((i, j), {}).values()]
        return [_row(m, u, True) for m in self.blocks.get((j, i), {}).values()]

    def type_of_node(self) -> np.ndarray:
        """Global-node-id -> type-id vector (the ``id % 3`` analogue)."""
        out = np.empty(self.num_nodes, dtype=np.int32)
        for i, (off, n) in enumerate(zip(self.offsets, self.sizes)):
            out[off : off + n] = i
        return out

    def block_slices(self) -> List[slice]:
        return [slice(off, off + n) for off, n in zip(self.offsets, self.sizes)]

    # -------------------------------------------------------------- storage
    def save_npz(self, path: str) -> str:
        """Write the network to one ``.npz`` (``NetworkSpec(kind='file')``).

        Layout: ``rel_pairs`` ``(K, 2)`` and ``rel_names`` ``(K,)`` list the
        K relations; relation ``k`` is ``rel_<k>_rows``/``_cols``/``_vals``/
        ``_shape`` (its stored entries, row-major, as COO); optional
        ``type_names``.  :meth:`load_npz` also reads the single-relation
        layout (``P_<t>``, ``R_<i>_<j>``, dense).  Returns the path
        actually written — numpy appends ``.npz`` when missing, and a
        return value that :meth:`load_npz` cannot open would be a trap.
        """
        arrays: Dict[str, np.ndarray] = {}
        pairs, names = [], []
        for k, (pair, name, m) in enumerate(self.relations()):
            pairs.append(pair)
            names.append(name)
            arrays[f"rel_{k}_rows"] = csr_rows(m).astype(np.int64)
            arrays[f"rel_{k}_cols"] = m.indices.astype(np.int64)
            arrays[f"rel_{k}_vals"] = m.data
            arrays[f"rel_{k}_shape"] = np.asarray(m.shape, np.int64)
        arrays["rel_pairs"] = np.asarray(pairs, np.int64).reshape(-1, 2)
        arrays["rel_names"] = np.asarray(names, dtype=str)
        if self.type_names is not None:
            arrays["type_names"] = np.asarray(list(self.type_names))
        np.savez_compressed(path, **arrays)
        return path if path.endswith(".npz") else path + ".npz"

    @classmethod
    def load_npz(cls, path: str) -> "HeteroNetwork":
        """Inverse of :meth:`save_npz`."""
        with np.load(path, allow_pickle=False) as data:
            names = (
                tuple(str(s) for s in data["type_names"])
                if "type_names" in data.files
                else None
            )
            if "rel_pairs" not in data.files:
                return cls._load_single_relation(path, data, names)
            blocks: Dict[TypePair, Dict[str, sp.csr_matrix]] = {}
            for k, ((i, j), rel) in enumerate(
                zip(data["rel_pairs"].tolist(), data["rel_names"].tolist())
            ):
                m = canonical_csr(
                    sp.coo_matrix(
                        (
                            data[f"rel_{k}_vals"],
                            (data[f"rel_{k}_rows"], data[f"rel_{k}_cols"]),
                        ),
                        shape=tuple(data[f"rel_{k}_shape"].tolist()),
                    )
                )
                blocks.setdefault((int(i), int(j)), {})[str(rel)] = m
        return cls._of_blocks(blocks, names)

    @classmethod
    def _load_single_relation(cls, path, data, names) -> "HeteroNetwork":
        p_keys = sorted(
            (k for k in data.files if k.startswith("P_")),
            key=lambda k: int(k.split("_")[1]),
        )
        if not p_keys:
            raise ValueError(f"{path}: no P_<t> similarity blocks found")
        P = [data[k] for k in p_keys]
        R = {}
        for k in data.files:
            if k.startswith("R_"):
                _, i, j = k.split("_")
                R[(int(i), int(j))] = data[k]
        return cls(P=P, R=R, type_names=names)

    # ----------------------------------------------------------- transforms
    def normalize(self) -> "NormalizedNetwork":
        """Paper §3.1: normalize every relation on its own, then take each
        block as the mean of its relations (``relation_mean``)."""
        rel = {
            pair: {name: _normalized(pair, m) for name, m in rels.items()}
            for pair, rels in self.blocks.items()
        }
        return NormalizedNetwork.of_relations(rel, self.sizes, self.type_names)

    def relation_of(self, pair: TypePair, relation: Optional[str]) -> str:
        """The relation an edit of block ``pair`` names: ``relation``, or,
        where it names none, the block's only relation (``DEFAULT_RELATION``
        for a block the edit creates)."""
        if relation is not None:
            return str(relation)
        rels = self.blocks.get(pair, {})
        if len(rels) > 1:
            raise ValueError(
                f"block {pair} holds relations {sorted(rels)}: an edit must name one"
            )
        return next(iter(rels), DEFAULT_RELATION)

    def touched_relations(self, delta: "GraphDelta") -> Set[RelationKey]:
        """The relations ``apply_delta(delta)`` changes: each one an edit
        names, and every relation of a block whose type grows."""
        out: Set[RelationKey] = set()
        grown = {t for t, count in delta.add_nodes.items() if count}
        for pair, name, _ in self.relations():
            if grown.intersection(pair):
                out.add((pair, name))
        for t, _, _, _, rel in delta.sim_edits():
            out.add(((t, t), self.relation_of((t, t), rel)))
        for pair, _, _, _, rel in delta.assoc_edits():
            p = (min(pair), max(pair))
            out.add((p, self.relation_of(p, rel)))
        return out

    def apply_delta(self, delta: "GraphDelta") -> "HeteroNetwork":
        """Return a new network with ``delta``'s edits applied.

        Only the relations the delta touches are copied and edited; the
        new network shares every other relation's matrix with this one.
        The serving layer (``repro/serve``) uses this as its incremental
        update path: apply, bump the network version, re-normalize the
        touched relations (``NormalizedNetwork.renormalized``), invalidate
        cached label columns whose types the delta touches, and warm-start
        the re-solve from the stale columns (DESIGN.md §9).
        """
        blocks = {pair: dict(rels) for pair, rels in self.blocks.items()}
        sizes = self.sizes
        T = len(sizes)

        # 1. grow blocks first so subsequent edge edits may target new nodes
        for t, count in sorted(delta.add_nodes.items()):
            if not 0 <= t < T:
                raise ValueError(f"add_nodes: no such type {t}")
            if count < 0:
                raise ValueError("add_nodes count must be >= 0")
            sizes[t] += count
        for (i, j), rels in blocks.items():
            shape = (sizes[i], sizes[j])
            for name, m in rels.items():
                if m.shape != shape:
                    rels[name] = _grown(m, shape)

        edits: Dict[RelationKey, List[Tuple[int, int, float]]] = {}
        # 2. similarity edits (kept symmetric; weight 0 removes the edge)
        for t, u, v, w, rel in delta.sim_edits():
            if not 0 <= t < T:
                raise ValueError(f"sim edit: no such type {t}")
            n = sizes[t]
            if not (0 <= u < n and 0 <= v < n):
                raise ValueError(
                    f"sim edit ({u}, {v}) out of range for type {t} (n={n})"
                )
            key = ((t, t), self.relation_of((t, t), rel))
            edits.setdefault(key, []).extend([(u, v, w), (v, u, w)])

        # 3. association edits (weight 0 removes the edge)
        for pair, u, v, w, rel in delta.assoc_edits():
            i, j = min(pair), max(pair)
            if pair[0] > pair[1]:
                u, v = v, u
            if not (0 <= i < j < T):
                raise ValueError(f"assoc edit: no such pair {pair}")
            if not (0 <= u < sizes[i] and 0 <= v < sizes[j]):
                raise ValueError(
                    f"assoc edit ({u}, {v}) out of range for {(sizes[i], sizes[j])}"
                )
            key = ((i, j), self.relation_of((i, j), rel))
            edits.setdefault(key, []).append((u, v, w))

        for (pair, name), e in edits.items():
            rels = blocks.setdefault(pair, {})
            m = rels.get(name)
            if m is None:  # a relation the delta creates
                shape = (sizes[pair[0]], sizes[pair[1]])
                m = sp.csr_matrix(shape)
            rels[name] = _edited(m, e)
        return HeteroNetwork._of_blocks(blocks, self.type_names)

    def with_masked_fold(
        self, pair: TypePair, mask: np.ndarray
    ) -> "HeteroNetwork":
        """Return a copy with the given association entries zeroed.

        Used by 10-fold CV (paper §6.2.1) and the deleted-interaction
        experiments (§6.2.2/§6.2.3): ``mask`` is a boolean ``(n_i, n_j)``
        array over the block ``(min(pair), max(pair))`` marking held-out
        entries, zeroed in every relation of the block.
        """
        key = (min(pair), max(pair))
        mask = np.asarray(mask, dtype=bool)
        blocks = {p: dict(rels) for p, rels in self.blocks.items()}
        for name, m in blocks[key].items():
            keep = ~mask[csr_rows(m), m.indices]
            counts = np.bincount(csr_rows(m)[keep], minlength=m.shape[0])
            indptr = np.concatenate([[0], np.cumsum(counts)])
            blocks[key][name] = sp.csr_matrix(
                (m.data[keep], m.indices[keep], indptr), shape=m.shape
            )
        return HeteroNetwork._of_blocks(blocks, self.type_names)


def _edit_fields(edit: tuple, kind: str) -> tuple:
    """An edit as its four fields and its relation (None: unnamed)."""
    if len(edit) == 4:
        return (*edit, None)
    if len(edit) == 5:
        return edit
    raise ValueError(f"{kind} edit {edit!r}: expected 4 fields, or 5 with a relation")


@dataclasses.dataclass(frozen=True)
class GraphDelta:
    """A batch of edits to a :class:`HeteroNetwork` (the online-update unit).

    Attributes:
      assoc: ``(pair, row, col, weight)`` association edits; ``row``/``col``
        are local indices within the pair's blocks and ``weight == 0``
        removes the edge.  Pairs are given in either orientation.  A fifth
        field names the relation; a pair of one relation needs none.
      sim: ``(type, u, v, weight)`` similarity edits (applied symmetrically),
        with an optional fifth field naming the relation, as ``assoc``.
      add_nodes: ``{type: count}`` — append ``count`` isolated nodes to the
        end of the type's block (no re-indexing of existing nodes).
    """

    assoc: Tuple[tuple, ...] = ()
    sim: Tuple[tuple, ...] = ()
    add_nodes: Mapping[int, int] = dataclasses.field(default_factory=dict)

    def __post_init__(self) -> None:
        object.__setattr__(self, "assoc", tuple(tuple(e) for e in self.assoc))
        object.__setattr__(self, "sim", tuple(tuple(e) for e in self.sim))
        object.__setattr__(self, "add_nodes", dict(self.add_nodes))
        for e in self.assoc:
            _edit_fields(e, "assoc")
        for e in self.sim:
            _edit_fields(e, "sim")

    @property
    def is_empty(self) -> bool:
        return not (self.assoc or self.sim or self.add_nodes)

    def assoc_edits(self) -> Iterator[tuple]:
        """``(pair, row, col, weight, relation or None)`` per edit."""
        return (_edit_fields(e, "assoc") for e in self.assoc)

    def sim_edits(self) -> Iterator[tuple]:
        """``(type, u, v, weight, relation or None)`` per edit."""
        return (_edit_fields(e, "sim") for e in self.sim)

    def touched_types(self) -> frozenset:
        """Types whose nodes the delta edits (serving's invalidation set)."""
        out = set()
        for (i, j), *_ in self.assoc:
            out.add(i)
            out.add(j)
        for t, *_ in self.sim:
            out.add(t)
        out.update(self.add_nodes)
        return frozenset(out)


@dataclasses.dataclass
class NormalizedNetwork:
    """Normalized blocks, ready for propagation.

    ``S_homo[t]`` and ``S_het[(i, j)]`` are each block's mean of its
    normalized relations, as canonical CSR matrices (a dense or other
    sparse block given here is converted).
    """

    S_homo: List[sp.csr_matrix]
    S_het: Dict[TypePair, sp.csr_matrix]
    sizes: List[int]
    type_names: Optional[Sequence[str]] = None
    #: each relation normalized on its own, ``(i, j)`` → ``{relation:
    #: block}``, which :meth:`renormalized` reuses; None on a view that
    #: ``HeteroNetwork.normalize`` did not make (``dataclasses.replace``)
    by_relation: Optional[Dict[TypePair, Dict[str, sp.csr_matrix]]] = dataclasses.field(
        default=None, init=False, repr=False, compare=False
    )

    def __post_init__(self) -> None:
        self.S_homo = [canonical_csr(s) for s in self.S_homo]
        self.S_het = {p: canonical_csr(s) for p, s in self.S_het.items()}

    @classmethod
    def of_relations(
        cls,
        rel: Dict[TypePair, Dict[str, sp.csr_matrix]],
        sizes: Sequence[int],
        type_names: Optional[Sequence[str]] = None,
    ) -> "NormalizedNetwork":
        """Each block the mean of its normalized relations ``rel``."""
        norm = cls(
            S_homo=[
                relation_mean(list(rel[(t, t)].values())) for t in range(len(sizes))
            ],
            S_het={
                p: relation_mean(list(r.values()))
                for p, r in rel.items()
                if p[0] != p[1]
            },
            sizes=list(sizes),
            type_names=type_names,
        )
        norm.by_relation = rel
        return norm

    def renormalized(
        self, net: HeteroNetwork, touched: Set[RelationKey]
    ) -> "NormalizedNetwork":
        """The normalization of ``net``, this one's network with only the
        ``touched`` relations changed (``HeteroNetwork.touched_relations``):
        those are normalized anew and their blocks re-combined; every other
        relation and block is reused."""
        if self.by_relation is None:
            return net.normalize()
        rel = {pair: dict(r) for pair, r in self.by_relation.items()}
        for pair, name in touched:
            rel.setdefault(pair, {})[name] = _normalized(pair, net.blocks[pair][name])
        have = {(p, n) for p, r in rel.items() for n in r}
        if have != {(p, n) for p, n, _ in net.relations()}:
            raise ValueError(
                "net is not this network with only the touched relations changed"
            )
        S_homo, S_het = list(self.S_homo), dict(self.S_het)
        for pair in {p for p, _ in touched}:
            mean = relation_mean(list(rel[pair].values()))
            if pair[0] == pair[1]:
                S_homo[pair[0]] = mean
            else:
                S_het[pair] = mean
        norm = NormalizedNetwork(S_homo, S_het, net.sizes, net.type_names)
        norm.by_relation = rel
        return norm

    @property
    def num_types(self) -> int:
        return len(self.S_homo)

    @property
    def num_nodes(self) -> int:
        return int(sum(self.sizes))

    @property
    def offsets(self) -> List[int]:
        out, acc = [], 0
        for n in self.sizes:
            out.append(acc)
            acc += n
        return out

    @property
    def operator_nnz(self) -> int:
        """Nonzeros of the propagation operator: each similarity block's,
        each association block's twice (it and its mirror).  A block of
        several relations counts the union of their entries."""
        het = sum(s.nnz for s in self.S_het.values())
        return sum(s.nnz for s in self.S_homo) + 2 * het

    def block_slices(self) -> List[slice]:
        return [slice(off, off + n) for off, n in zip(self.offsets, self.sizes)]

    # ------------------------------------------------------- dense assembly
    def assemble_dense(self) -> Tuple[np.ndarray, np.ndarray]:
        """Assemble the (N, N) homogeneous operator M and heterogeneous H.

        ``M`` is block-diagonal (within-type propagation), ``H`` holds the
        off-diagonal association blocks (cross-type propagation).  Their
        supports are disjoint; together they are the full propagation
        operator of one BSP superstep.  The dense engine's input only:
        the sparse engines read :meth:`to_coo`.
        """
        n = self.num_nodes
        sl = self.block_slices()
        M = np.zeros((n, n), dtype=np.float64)
        H = np.zeros((n, n), dtype=np.float64)
        for i, s in enumerate(self.S_homo):
            M[sl[i], sl[i]] = s.toarray()
        for (i, j), s in self.S_het.items():
            s = s.toarray()
            H[sl[i], sl[j]] = s
            H[sl[j], sl[i]] = s.T
        return H, M

    def assemble_effective(self, alpha: float) -> Tuple[np.ndarray, float]:
        """Beyond-paper fused operator for DHLP-2 (DESIGN.md §2).

        One DHLP-2 round ``F ← β(βF + αHF) + αMF`` equals
        ``F ← β²F + A_eff @ F`` with ``A_eff = αβH + αM`` (disjoint support).
        Returns ``(A_eff, β²)``.
        """
        beta = 1.0 - alpha
        H, M = self.assemble_dense()
        return alpha * beta * H + alpha * M, beta * beta

    # --------------------------------------------------------- COO assembly
    def to_coo(self) -> "HeteroCOO":
        """COO view of ``(H, M)``, extracted block by block.

        The same arrays as ``HeteroCOO.from_dense(*self.assemble_dense())``
        — edges in (dst, src) order, each block's own float64 weights —
        without materialising an ``(N, N)`` matrix: only each block's
        stored entries are read, offset into
        global ids and, for an association block, mirrored.
        """
        off = self.offsets
        hom = [
            (t, t, *_block_edges(s, off[t], off[t]))
            for t, s in enumerate(self.S_homo)
        ]
        het = []
        for (i, j), s in self.S_het.items():
            dst, src, w = _block_edges(s, off[i], off[j])
            het.append((i, j, dst, src, w))
            het.append((j, i, src, dst, w))  # the mirror block, s.T
        hs, hd, hw = _dst_major(het)
        ms, md, mw = _dst_major(hom)
        return HeteroCOO(
            het_src=hs,
            het_dst=hd,
            het_w=hw,
            hom_src=ms,
            hom_dst=md,
            hom_w=mw,
            num_nodes=self.num_nodes,
            sizes=list(self.sizes),
        )


def _block_edges(s: sp.csr_matrix, row_off: int, col_off: int):
    """``(dst, src, w)`` of one block's nonzeros, row-major, global ids
    (canonical CSR: rows in order, ascending columns within a row)."""
    return (
        (csr_rows(s) + row_off).astype(np.int32),
        (s.indices + col_off).astype(np.int32),
        s.data.astype(np.float64),
    )


def _dst_major(pieces):
    """Concatenate per-block edges into one (dst, src)-ordered ``(src, dst, w)``.

    ``pieces`` are ``(dst_type, src_type, dst, src, w)``, each listing the
    edges into one dst in ascending src — true of a block's row-major
    nonzeros and of their mirror alike.  Laid out by (dst type, src type),
    a stable sort by dst keeps that order and the src-type order between
    pieces, which together are src order.
    """
    pieces = sorted(pieces, key=lambda p: p[:2])
    dst = np.concatenate([p[2] for p in pieces] or [np.zeros(0, np.int32)])
    src = np.concatenate([p[3] for p in pieces] or [np.zeros(0, np.int32)])
    w = np.concatenate([p[4] for p in pieces] or [np.zeros(0)])
    order = np.argsort(dst, kind="stable")
    return src[order], dst[order], w[order]


@dataclasses.dataclass
class HeteroCOO:
    """COO edge-list view (the scalable/sparse engine's input).

    Homo and hetero edge sets are kept separate because DHLP mixes them with
    different coefficients.  Edges are stored destination-major so a
    segment-sum over ``dst`` is a contiguous reduce-by-key — the tensorized
    equivalent of Giraph delivering all messages addressed to a vertex in one
    superstep.
    """

    het_src: np.ndarray  # (E_h,) int32 — message source (column index)
    het_dst: np.ndarray  # (E_h,) int32 — message destination (row index)
    het_w: np.ndarray  # (E_h,) float — normalized weight
    hom_src: np.ndarray
    hom_dst: np.ndarray
    hom_w: np.ndarray
    num_nodes: int
    sizes: List[int]

    @classmethod
    def from_dense(
        cls, H: np.ndarray, M: np.ndarray, sizes: Sequence[int]
    ) -> "HeteroCOO":
        def _coo(a: np.ndarray):
            dst, src = np.nonzero(a)  # row=dst receives from col=src
            order = np.argsort(dst, kind="stable")
            dst, src = dst[order], src[order]
            return (
                src.astype(np.int32),
                dst.astype(np.int32),
                a[dst, src].astype(np.float64),
            )

        hs, hd, hw = _coo(H)
        ms, md, mw = _coo(M)
        return cls(
            het_src=hs,
            het_dst=hd,
            het_w=hw,
            hom_src=ms,
            hom_dst=md,
            hom_w=mw,
            num_nodes=int(H.shape[0]),
            sizes=list(sizes),
        )

    @property
    def num_edges(self) -> int:
        return int(self.het_src.shape[0] + self.hom_src.shape[0])

    def pad_to(self, het_mult: int = 1024, hom_mult: int = 1024) -> "HeteroCOO":
        """Pad edge arrays to a multiple so shapes are shard-friendly.

        Padding edges point at a zero-weight self-loop on node 0, which is a
        no-op under segment-sum (weight 0).
        """

        def _pad(src, dst, w, mult):
            e = src.shape[0]
            target = max(mult, ((e + mult - 1) // mult) * mult)
            pad = target - e
            if pad == 0:
                return src, dst, w
            return (
                np.concatenate([src, np.zeros(pad, np.int32)]),
                np.concatenate([dst, np.zeros(pad, np.int32)]),
                np.concatenate([w, np.zeros(pad, np.float64)]),
            )

        hs, hd, hw = _pad(self.het_src, self.het_dst, self.het_w, het_mult)
        ms, md, mw = _pad(self.hom_src, self.hom_dst, self.hom_w, hom_mult)
        return HeteroCOO(
            het_src=hs,
            het_dst=hd,
            het_w=hw,
            hom_src=ms,
            hom_dst=md,
            hom_w=mw,
            num_nodes=self.num_nodes,
            sizes=self.sizes,
        )


def seeds_identity(num_nodes: int) -> np.ndarray:
    """All-sources seed matrix: Y = I_N.

    The paper sweeps seeds one at a time (``y=1`` for a single vertex per
    sweep); the batched engines treat each seed as a column of Y.
    """
    return np.eye(num_nodes, dtype=np.float64)


def seeds_for_nodes(num_nodes: int, nodes: Sequence[int]) -> np.ndarray:
    y = np.zeros((num_nodes, len(nodes)), dtype=np.float64)
    for c, v in enumerate(nodes):
        y[v, c] = 1.0
    return y
