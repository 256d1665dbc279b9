"""Autotune cache for blocked-CSR layout and kernel panel parameters.

`block_rows`/`width_mult` (layout) and `bn`/`bs`/`bd` (Pallas panel sizes)
ran hard-coded CPU defaults everywhere before this module.  The right
values depend on the host (cache sizes, core count, interpret-vs-Mosaic)
and on the operator's *shape class* — node count and mean degree decide
whether wide hub rectangles or many narrow buckets win.  Sweeping them
per solve would dwarf the solve; hard-coding them leaves throughput on
the table on every other host.

So: sweep once per (host fingerprint, shape class), persist the winner
under ``results/autotune/<host>.json``, and answer every later query
from a process-level memo — ``lookup`` is a dict probe, zero per-call
overhead.  A cold miss returns ``None`` and callers fall back to
:data:`DEFAULT_PARAMS` (today's defaults), so nothing ever blocks on a
sweep implicitly; only :func:`ensure_tuned` (called by the bench suite
and by users who opt in) pays the sweep cost.  ``LPConfig.autotune=False``
opts a solve out of consulting the cache entirely.

Shape classes bucket (num_nodes, nnz) by rounded log2 so one sweep covers
the whole neighborhood of sizes the serving tier replays — exact keying
would re-sweep on every scenario scale tweak.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import platform
import time
from pathlib import Path
from typing import Dict, Optional, Tuple

import numpy as np

DEFAULT_CACHE_DIR = Path("results") / "autotune"

# Candidate grid: layout first (dominates), panels on the winning layout.
LAYOUT_GRID: Tuple[Tuple[int, int], ...] = tuple(
    (br, wm) for br in (32, 64, 128) for wm in (4, 8, 16)
)
PANEL_GRID: Tuple[Tuple[int, int, int], ...] = (
    (128, 128, 8),
    (256, 128, 16),
    (256, 128, 32),
    (512, 128, 16),
)
# The panel sweep times the VMEM-resident kernel; beyond this many nodes
# the (N, bs) panel outgrows a few MiB of VMEM and the sweep is skipped.
PANEL_SWEEP_MAX_NODES = 16384


@dataclasses.dataclass(frozen=True)
class TunedParams:
    """One winning parameter set for a (host, shape class) cell."""

    block_rows: int = 64
    width_mult: int = 8
    bn: int = 256  # kernel row-panel
    bs: int = 128  # kernel label-column panel
    bd: int = 16  # kernel degree-slab

    def to_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, int]) -> "TunedParams":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: int(v) for k, v in d.items() if k in fields})


DEFAULT_PARAMS = TunedParams()

# process-level memo: resolved cache file path -> {shape_class: TunedParams}
_MEMO: Dict[str, Dict[str, TunedParams]] = {}


def host_fingerprint() -> str:
    """Stable id of the machine class the timings were taken on.

    Deliberately coarse — machine arch + core count + jax backend/version —
    so re-created containers of the same class share one cache file.
    """
    import jax

    parts = (
        platform.machine(),
        platform.system().lower(),
        f"cpu{os.cpu_count() or 1}",
        jax.default_backend(),
        f"jax{jax.__version__}",
    )
    return "-".join(parts).replace(" ", "_")


def shape_class(num_nodes: int, nnz: int) -> str:
    """Bucket an operator by rounded log2(nodes) and log2(mean degree)."""
    n = max(int(num_nodes), 2)
    d = max(float(nnz) / n, 1.0)
    return f"n{round(math.log2(n))}_d{round(math.log2(d))}"


def network_nnz(norm) -> int:
    """Cheap nnz estimate off the normalized blocks (no COO assembly)."""
    nnz = sum(int(np.count_nonzero(s)) for s in norm.S_homo)
    nnz += 2 * sum(int(np.count_nonzero(s)) for s in norm.S_het.values())
    return nnz


def cache_path(cache_dir: Optional[os.PathLike] = None) -> Path:
    base = Path(cache_dir) if cache_dir is not None else DEFAULT_CACHE_DIR
    return base / f"{host_fingerprint()}.json"


def _load(cache_dir: Optional[os.PathLike] = None) -> Dict[str, TunedParams]:
    path = cache_path(cache_dir)
    key = str(path.resolve())
    if key in _MEMO:
        return _MEMO[key]
    entries: Dict[str, TunedParams] = {}
    if path.exists():
        try:
            raw = json.loads(path.read_text())
            for sc, d in raw.get("entries", {}).items():
                entries[sc] = TunedParams.from_dict(d)
        except (json.JSONDecodeError, TypeError, ValueError):
            entries = {}  # corrupt cache == cold cache
    _MEMO[key] = entries
    return entries


def clear_memo() -> None:
    """Drop the process memo (tests re-point cache_dir mid-process)."""
    _MEMO.clear()


def lookup(
    num_nodes: int,
    nnz: int,
    *,
    cache_dir: Optional[os.PathLike] = None,
) -> Optional[TunedParams]:
    """Cached winner for this host + shape class, or None on a cold miss."""
    return _load(cache_dir).get(shape_class(num_nodes, nnz))


def save(
    num_nodes: int,
    nnz: int,
    params: TunedParams,
    *,
    cache_dir: Optional[os.PathLike] = None,
) -> Path:
    """Persist a winner and refresh the memo (atomic file replace)."""
    path = cache_path(cache_dir)
    path.parent.mkdir(parents=True, exist_ok=True)
    entries = dict(_load(cache_dir))
    entries[shape_class(num_nodes, nnz)] = params
    doc = {
        "host": host_fingerprint(),
        "entries": {sc: p.to_dict() for sc, p in sorted(entries.items())},
    }
    tmp = path.with_suffix(".json.tmp")
    tmp.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
    os.replace(tmp, path)
    _MEMO[str(path.resolve())] = entries
    return path


# --------------------------------------------------------------- the sweep


def _time_layout(norm, *, alpha, hetero_scale, block_rows, width_mult, s, repeats):
    """Seconds per einsum round at one (block_rows, width_mult) layout."""
    import jax
    import jax.numpy as jnp

    from repro.core.blocked_csr import blocked_csr_from_network

    bcsr = blocked_csr_from_network(
        norm,
        alpha=alpha,
        hetero_scale=hetero_scale,
        block_rows=block_rows,
        width_mult=width_mult,
    )
    buckets = tuple(
        (jnp.asarray(b.nbr), jnp.asarray(b.wgt, jnp.float32))
        for b in bcsr.width_buckets()
    )
    order = np.concatenate([b.rows for b in bcsr.width_buckets()])
    inv = jnp.asarray(np.argsort(order).astype(np.int32))

    @jax.jit
    def _round(bk, iv, F):
        parts = [
            jnp.einsum("rw,rws->rs", w, F[nbr].astype(jnp.float32))
            for nbr, w in bk
        ]
        return jnp.concatenate(parts, axis=0)[iv]

    F = jnp.asarray(
        np.random.default_rng(0).random((norm.num_nodes, s)), jnp.float32
    )
    _round(buckets, inv, F).block_until_ready()  # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        _round(buckets, inv, F).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best, bcsr


def _time_panels(bcsr, *, bn, bs, bd, s, repeats):
    """Seconds per fused-kernel round at one (bn, bs, bd) panel choice."""
    import jax.numpy as jnp

    from repro.kernels.segment_reduce import csr_round_residual_op

    buckets = [
        (jnp.asarray(b.nbr), jnp.asarray(b.wgt, jnp.float32))
        for b in bcsr.width_buckets()
    ]
    n = bcsr.num_rows
    rng = np.random.default_rng(0)
    F = jnp.asarray(rng.random((n, s)), jnp.float32)

    def _round():
        outs = []
        off = 0
        for nbr, wgt in buckets:
            m = nbr.shape[0]
            sl = F[off : off + m]
            out, _ = csr_round_residual_op(
                nbr, wgt, F, sl, sl, c=0.25, bn=bn, bs=bs, bd=bd
            )
            outs.append(out)
            off += m
        return [o.block_until_ready() for o in outs]

    _round()  # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        _round()
        best = min(best, time.perf_counter() - t0)
    return best


def ensure_tuned(
    norm,
    *,
    alpha: float = 0.5,
    hetero_scale: float = 1.0,
    s: int = 8,
    repeats: int = 2,
    cache_dir: Optional[os.PathLike] = None,
    force: bool = False,
    sweep_panels: bool = True,
) -> Tuple[TunedParams, bool]:
    """Return ``(params, cache_hit)`` for this host + operator shape.

    On a hit nothing is timed.  On a miss (or ``force=True``) sweeps the
    layout grid with the einsum round, then — for operators small enough
    for the VMEM-resident kernel — the panel grid with the fused-superstep
    kernel on the winning layout, and persists the combined winner.
    """
    nnz = network_nnz(norm)
    n = norm.num_nodes
    if not force:
        hit = lookup(n, nnz, cache_dir=cache_dir)
        if hit is not None:
            return hit, True

    best_t, best_layout, best_bcsr = float("inf"), LAYOUT_GRID[0], None
    for block_rows, width_mult in LAYOUT_GRID:
        t, bcsr = _time_layout(
            norm,
            alpha=alpha,
            hetero_scale=hetero_scale,
            block_rows=block_rows,
            width_mult=width_mult,
            s=s,
            repeats=repeats,
        )
        if t < best_t:
            best_t, best_layout, best_bcsr = t, (block_rows, width_mult), bcsr

    bn, bs, bd = DEFAULT_PARAMS.bn, DEFAULT_PARAMS.bs, DEFAULT_PARAMS.bd
    if sweep_panels and n <= PANEL_SWEEP_MAX_NODES:
        best_pt = float("inf")
        for cand in PANEL_GRID:
            t = _time_panels(
                best_bcsr, bn=cand[0], bs=cand[1], bd=cand[2], s=s,
                repeats=repeats,
            )
            if t < best_pt:
                best_pt, (bn, bs, bd) = t, cand

    params = TunedParams(
        block_rows=best_layout[0],
        width_mult=best_layout[1],
        bn=bn,
        bs=bs,
        bd=bd,
    )
    save(n, nnz, params, cache_dir=cache_dir)
    return params, False


__all__ = [
    "DEFAULT_PARAMS",
    "LAYOUT_GRID",
    "PANEL_GRID",
    "TunedParams",
    "cache_path",
    "clear_memo",
    "ensure_tuned",
    "host_fingerprint",
    "lookup",
    "network_nnz",
    "save",
    "shape_class",
]
