"""Plain float64 operator of a network with several relations on one pair
of types, on ``scipy.sparse``; ``reference.solve`` finds its fixed point.

Independent of the program: it reads the benchmark's own per-relation COO
(``relgen.Network``) and nothing the program has made.  Each relation is
normalized on its own (``D^{-1/2} M D^{-1/2}`` within a type,
``D_r^{-1/2} M D_c^{-1/2}`` across two; a zero degree gives a zero factor),
and a block of several relations is their mean.  Then, as in
``reference.py``,

    A = α·S_hom + α·β·s·S_het,   β = 1 − α,   s = 1 / (T − 1),

each association block with its transpose in ``S_het``.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np
import scipy.sparse as sp

from chipbench.reference import _inv_sqrt
from chipbench.relgen import Network


def operator(net: Network, alpha: float) -> sp.csr_matrix:
    """The (N, N) float64 operator ``A`` in CSR."""
    beta = 1.0 - alpha
    scale = 1.0 / max(1, len(net.sizes) - 1)
    blocks: Dict[Tuple[int, int], List[sp.csr_matrix]] = {}
    for r in net.relations:
        shape = (net.sizes[r.i], net.sizes[r.j])
        m = sp.csr_matrix((np.ones(r.rows.size), (r.rows, r.cols)), shape=shape)
        d_row = _inv_sqrt(np.asarray(m.sum(axis=1)).ravel())
        d_col = _inv_sqrt(np.asarray(m.sum(axis=0)).ravel())
        blocks.setdefault((r.i, r.j), []).append(sp.diags(d_row) @ m @ sp.diags(d_col))
    off = net.offsets
    n = net.num_nodes
    parts = []
    for (i, j), mats in blocks.items():
        mean = (sum(mats[1:], mats[0]) / len(mats)).tocoo()
        if i == j:
            parts.append((alpha * mean.data, mean.row + off[i], mean.col + off[j]))
        else:
            w = alpha * beta * scale * mean.data
            parts.append((w, mean.row + off[i], mean.col + off[j]))
            parts.append((w, mean.col + off[j], mean.row + off[i]))
    vals, rows, cols = (np.concatenate(x) for x in zip(*parts))
    return sp.coo_matrix((vals, (rows, cols)), shape=(n, n)).tocsr()
