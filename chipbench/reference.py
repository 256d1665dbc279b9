"""Plain float64 reference of fixed-seed DHLP-2, on ``scipy.sparse``.

Independent of the program: it reads the benchmark's own COO network
(``netgen.Network``) and nothing the program has made.

The fixed point is ``F = β²·Y + A·F`` with

    A = α·S_hom + α·β·s·S_het,   β = 1 − α,   s = 1 / (T − 1),

``S_hom`` the block-diagonal similarities, each ``D^{-1/2} P D^{-1/2}``,
and ``S_het`` the association blocks ``D_r^{-1/2} R D_c^{-1/2}`` with
their transposes.  Jacobi sweeps from ``β²·Y`` until no entry moves by
``tol`` (the error then sits below ``tol·ρ/(1−ρ)``, ρ ≈ 0.6 here).
"""
from __future__ import annotations

from typing import Sequence

import numpy as np
import scipy.sparse as sp

from chipbench.netgen import Network


def _inv_sqrt(d: np.ndarray) -> np.ndarray:
    out = np.zeros_like(d)
    out[d > 0] = 1.0 / np.sqrt(d[d > 0])
    return out


def operator(net: Network, alpha: float) -> sp.csr_matrix:
    """The (N, N) float64 operator ``A`` in CSR."""
    beta = 1.0 - alpha
    scale = 1.0 / max(1, len(net.sizes) - 1)
    off = net.offsets
    rows, cols, vals = [], [], []
    for t, (r, c, v) in enumerate(net.sim):
        n = net.sizes[t]
        d = np.bincount(r, weights=v, minlength=n)
        inv = _inv_sqrt(d)
        rows.append(r + off[t])
        cols.append(c + off[t])
        vals.append(alpha * inv[r] * v * inv[c])
    for (i, j), (r, c) in net.assoc.items():
        inv_r = _inv_sqrt(np.bincount(r, minlength=net.sizes[i]).astype(float))
        inv_c = _inv_sqrt(np.bincount(c, minlength=net.sizes[j]).astype(float))
        w = alpha * beta * scale * inv_r[r] * inv_c[c]
        rows += [r + off[i], c + off[j]]
        cols += [c + off[j], r + off[i]]
        vals += [w, w]
    n = net.num_nodes
    a = sp.coo_matrix(
        (np.concatenate(vals), (np.concatenate(rows), np.concatenate(cols))),
        shape=(n, n),
    )
    return a.tocsr()


def solve(
    A: sp.csr_matrix,
    seeds: Sequence[int],
    alpha: float,
    *,
    tol: float = 1e-12,
    max_iter: int = 2000,
) -> np.ndarray:
    """``(N, len(seeds))`` fixed-point columns for one-hot seeds."""
    n = A.shape[0]
    base = np.zeros((n, len(seeds)))
    base[np.asarray(seeds), np.arange(len(seeds))] = (1.0 - alpha) ** 2
    F = base.copy()
    for _ in range(max_iter):
        Fn = base + A @ F
        moved = float(np.max(np.abs(Fn - F), initial=0.0))
        F = Fn
        if moved < tol:
            return F
    raise RuntimeError(f"reference did not converge in {max_iter} sweeps")


def top_k(scores: np.ndarray, k: int, exclude: np.ndarray) -> np.ndarray:
    """Indices of the ``k`` best non-excluded scores, best first."""
    s = np.where(exclude, -np.inf, scores)
    idx = np.argsort(-s, kind="stable")[:k]
    return idx[np.isfinite(s[idx])]
