"""Matrix normalization (paper §3.1 / §5).

All propagation matrices must be normalized so the iteration is a
contraction-like map (spectral radius ≤ 1); this is the hypothesis of the
convergence proof inherited from MINProp [11] and Heter-LP [14].

* homogeneous similarity:  S = D^{-1/2} P D^{-1/2}
* bipartite association:   S = D_r^{-1/2} R D_c^{-1/2}

Zero-degree rows/columns (isolated entities — e.g. a "new drug" whose
interactions were all deleted in the §6.2.3 experiment) get a zero inverse
degree instead of inf, i.e. they emit/receive nothing through that block.

A network holds each relation as a canonical CSR matrix
(:func:`canonical_csr`: float64, sorted indices, no duplicates, no stored
zeros) and normalizes it with :func:`normalize_relation`, which gives the
dense functions' numbers bit for bit.  A block may hold several relations
(Hetionet's Gene–Gene covaries, interacts and regulates).  Each relation
is normalized on its own and the block is their mean, ``(1/m) Σ_r S_r``
(:func:`relation_mean`): every normalized relation has spectral norm at
most 1, so the mean does too and the contraction argument still holds,
where a sum of m relations could reach norm m.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np
import scipy.sparse as sp

#: entries of the dense rows :func:`row_sums` builds at a time (32 MB)
_ROW_CHUNK = 1 << 22


def _inv_sqrt(d: np.ndarray) -> np.ndarray:
    out = np.zeros_like(d, dtype=np.float64)
    nz = d > 0
    out[nz] = 1.0 / np.sqrt(d[nz])
    return out


def symmetric_normalize(P: np.ndarray) -> np.ndarray:
    """D^{-1/2} P D^{-1/2} with zero-degree guard."""
    P = np.asarray(P, dtype=np.float64)
    if P.ndim != 2 or P.shape[0] != P.shape[1]:
        raise ValueError(f"expected square matrix, got {P.shape}")
    d = P.sum(axis=1)
    inv = _inv_sqrt(d)
    return inv[:, None] * P * inv[None, :]


def bipartite_normalize(R: np.ndarray) -> np.ndarray:
    """D_r^{-1/2} R D_c^{-1/2} with zero-degree guard."""
    R = np.asarray(R, dtype=np.float64)
    if R.ndim != 2:
        raise ValueError(f"expected matrix, got {R.shape}")
    dr = R.sum(axis=1)
    dc = R.sum(axis=0)
    return _inv_sqrt(dr)[:, None] * R * _inv_sqrt(dc)[None, :]


def canonical_csr(m) -> sp.csr_matrix:
    """``m`` (dense or sparse) as a canonical float64 CSR matrix: ``m``
    itself where it is one already, else a new matrix."""
    if (
        isinstance(m, sp.csr_matrix)
        and m.dtype == np.float64
        and m.has_canonical_format
        and np.all(m.data != 0)
    ):
        return m
    out = sp.csr_matrix(m, dtype=np.float64, copy=True)
    out.sum_duplicates()
    out.eliminate_zeros()
    return out


def csr_rows(m: sp.csr_matrix) -> np.ndarray:
    """The row index of each stored entry of a CSR matrix, in storage order."""
    return np.repeat(np.arange(m.shape[0]), np.diff(m.indptr))


def row_sums(m: sp.csr_matrix) -> np.ndarray:
    """Each row's sum, equal bit for bit to ``m.toarray().sum(axis=1)``.

    numpy sums a dense row pairwise, an order a sum over the stored
    entries does not follow, so real weights are summed over dense rows,
    a chunk at a time.  Integer weights (counts, 0/1 edges) sum exactly
    in any order, so those take one pass over the stored entries.
    """
    if np.all(m.data == np.rint(m.data)):
        return np.bincount(csr_rows(m), m.data, m.shape[0])
    out = np.empty(m.shape[0])
    step = max(1, _ROW_CHUNK // max(1, m.shape[1]))
    for a in range(0, m.shape[0], step):
        out[a : a + step] = m[a : a + step].toarray().sum(axis=1)
    return out


def normalize_relation(m: sp.csr_matrix, similarity: bool) -> sp.csr_matrix:
    """One relation normalized on its own: :func:`symmetric_normalize` of
    a similarity, else :func:`bipartite_normalize`, on a canonical CSR
    matrix, with the dense functions' numbers bit for bit (a column sum in
    storage order is numpy's dense column order)."""
    if similarity and m.shape[0] != m.shape[1]:
        raise ValueError(f"expected square matrix, got {m.shape}")
    dr = _inv_sqrt(row_sums(m))
    dc = dr if similarity else _inv_sqrt(np.bincount(m.indices, m.data, m.shape[1]))
    data = dr[csr_rows(m)] * m.data * dc[m.indices]  # the dense formula's order
    out = sp.csr_matrix((data, m.indices.copy(), m.indptr.copy()), shape=m.shape)
    out.eliminate_zeros()
    return out


def relation_mean(blocks: Sequence[sp.csr_matrix]) -> sp.csr_matrix:
    """``(1/m) Σ_r S_r`` of a block's m normalized relations.

    One relation is returned as it is (the block *is* that relation), so a
    single-relation network's operator is unchanged bit for bit.
    """
    if len(blocks) == 1:
        return blocks[0]
    total = blocks[0]
    for b in blocks[1:]:
        total = total + b
    return canonical_csr(total / len(blocks))


def spectral_radius_upper_bound(S: np.ndarray) -> float:
    """Cheap upper bound via the max row sum (∞-norm)."""
    return float(np.abs(S).sum(axis=1).max(initial=0.0))
