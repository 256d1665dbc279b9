"""Kernel-variant A/B cells: pre-fusion loop vs fused superstep vs
autotuned layout vs bf16 storage (DESIGN.md §15).

Three cell groups, all on the blocked-CSR sparse engine:

* ``drugnet_*`` — the case-study network solved by every variant, with
  fixed-point agreement against the dense reference strict-gated (bf16
  rides the same ``AGREEMENT_TOL`` bar as every other backend);
* ``powerlaw_race_*`` — a >=100k-edge heavy-tailed network, fused
  superstep raced against the pre-fusion per-round path it replaced
  (``speedup_vs_legacy`` on the fused record is the PR's headline);
* ``autotune_cache`` — ``ensure_tuned`` twice in a row: the sweep cost,
  then the (memo/disk) hit that every later solve pays.

Each timed cell also carries the analytic roofline terms
(``benchmarks/roofline.py``): per-round achieved FLOP/s and bandwidth,
their share of the device's published peaks where it has any, with the
deterministic FLOP/byte counts strict-gated — they change only when the round's math changes.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from repro.bench import BenchRecord, register_suite, stats_from_samples, time_callable
from repro.bench.timing import derived_throughput

AGREEMENT_TOL = 5e-3
SIGMA = 1e-4
SEED_COLS = 16
#: powerlaw edge-target scale — 0.2 of the 1.2M nominal ≈ 240k edges,
#: comfortably past the scenario disk-cache floor so generation is paid once
RACE_SCALE = 0.2
RACE_SIGMA = 1e-3
RACE_SEED_COLS = 8

#: (cell label, LPConfig overrides, engine kwargs)
VARIANTS = (
    ("legacy", {"autotune": False}, {"fused_superstep": False}),
    ("fused", {"autotune": False}, {}),
    ("autotuned", {"autotune": True}, {}),
    ("bf16", {"autotune": False, "storage_dtype": "bf16"}, {}),
)


def _roofline_terms(
    stats, *, nnz: int, num_nodes: int, s: int, supersteps: int, storage_bytes: int
) -> Dict[str, float]:
    try:
        from benchmarks.roofline import achieved_vs_peak, lp_round_cost
    except ImportError:  # run directly: sys.path[0] is benchmarks/
        from roofline import achieved_vs_peak, lp_round_cost

    cost = lp_round_cost(
        nnz=nnz, num_nodes=num_nodes, s=s, storage_bytes=storage_bytes
    )
    import jax

    round_s = stats.median_s / max(supersteps, 1)
    out = achieved_vs_peak(round_s, cost, jax.devices()[0].device_kind)
    out["round_flops"] = cost["flops"]
    out["round_bytes"] = cost["bytes"]
    return out


def _solve_record(
    name: str,
    variant: str,
    cfg_overrides: Dict[str, object],
    engine_kwargs: Dict[str, object],
    norm,
    Y: np.ndarray,
    *,
    sigma: float,
    nnz: int,
    edges: int,
    F_ref: np.ndarray = None,
    repeats: int = 3,
) -> BenchRecord:
    """Time one variant's full solve; agreement is vs ``F_ref``."""
    from repro.core.solver import LPConfig
    from repro.engine import make_engine

    cfg = LPConfig(alg="dhlp2", sigma=sigma, seed_mode="fixed", **cfg_overrides)
    engine = make_engine("sparse", cfg, **engine_kwargs)

    def solve():
        return engine.run(norm, seeds=Y)

    res = solve()  # warmup: plan build + compile + first run
    stats = time_callable(solve, warmup=0, repeats=repeats)
    storage_bytes = 2 if cfg_overrides.get("storage_dtype") == "bf16" else 4
    derived = derived_throughput(stats, edges=edges, supersteps=res.supersteps)
    derived.update(
        _roofline_terms(
            stats,
            nnz=nnz,
            num_nodes=norm.num_nodes,
            s=Y.shape[1],
            supersteps=int(res.supersteps),
            storage_bytes=storage_bytes,
        )
    )
    derived["outer_iters"] = float(res.outer_iters)
    derived["supersteps"] = float(res.supersteps)
    strict = ["outer_iters", "supersteps", "round_flops", "round_bytes"]
    if F_ref is not None:
        diff = float(np.max(np.abs(res.F - F_ref)))
        derived["agree_ref"] = 1.0 if diff <= AGREEMENT_TOL else 0.0
        derived["max_abs_diff_vs_ref"] = diff
        strict.append("agree_ref")
    rec = BenchRecord(
        suite="kernel_variants",
        name=name,
        backend="sparse",
        params={
            "variant": variant,
            "alg": "dhlp2",
            "sigma": sigma,
            "nodes": int(norm.num_nodes),
            "edges": int(edges),
            "nnz": int(nnz),
            "seeds": int(Y.shape[1]),
            "storage_dtype": cfg_overrides.get("storage_dtype", "f32"),
            "fused": bool(engine_kwargs.get("fused_superstep", True)),
        },
        stats=stats.to_dict(),
        derived=derived,
        strict=strict,
    )
    rec._median_s = stats.median_s  # intra-suite plumbing for the race cell
    rec._F = res.F
    return rec


def _drugnet_records(fast: bool) -> List[BenchRecord]:
    """Every variant on the case-study network, gated against dense."""
    from repro.core.solver import HeteroLP, LPConfig
    from repro.data.drugnet import DrugNetSpec, make_drugnet
    from repro.engine.autotune import network_nnz

    if fast:
        spec_net = DrugNetSpec(n_drug=48, n_disease=32, n_target=24, n_clusters=6)
    else:
        spec_net = DrugNetSpec(n_drug=96, n_disease=64, n_target=48, n_clusters=8)
    dn = make_drugnet(spec_net)
    norm = dn.network.normalize()
    n = norm.num_nodes
    nnz = network_nnz(norm)
    edges = dn.network.num_edges
    Y = np.eye(n, dtype=np.float32)[:, :SEED_COLS]
    F_dense = (
        HeteroLP(LPConfig(alg="dhlp2", sigma=SIGMA, seed_mode="fixed"))
        .run(norm, seeds=Y)
        .F
    )
    out: List[BenchRecord] = []
    for variant, cfg_over, eng_kw in VARIANTS:
        rec = _solve_record(
            f"drugnet_{variant}",
            variant,
            cfg_over,
            eng_kw,
            norm,
            Y,
            sigma=SIGMA,
            nnz=nnz,
            edges=edges,
            F_ref=F_dense,
            repeats=5 if fast else 3,
        )
        # vs-dense naming: this group's reference IS the dense engine
        rec.derived["agree_dense"] = rec.derived.pop("agree_ref")
        rec.derived["max_abs_diff_vs_dense"] = rec.derived.pop("max_abs_diff_vs_ref")
        rec.strict[rec.strict.index("agree_ref")] = "agree_dense"
        out.append(rec)
    return out


def _autotune_record(fast: bool) -> BenchRecord:
    """``ensure_tuned`` cold (sweep or persisted-cache load), then hot."""
    from repro.data.drugnet import DrugNetSpec, make_drugnet
    from repro.engine.autotune import ensure_tuned, network_nnz

    spec_net = DrugNetSpec(n_drug=48, n_disease=32, n_target=24, n_clusters=6)
    dn = make_drugnet(spec_net)
    norm = dn.network.normalize()
    samples, hits, params = [], [], None
    for _ in range(2):
        t0 = time.perf_counter()
        params, hit = ensure_tuned(norm, s=8, repeats=2)
        samples.append(time.perf_counter() - t0)
        hits.append(hit)
    return BenchRecord(
        suite="kernel_variants",
        name="autotune_cache",
        backend="sparse",
        params={
            "nodes": int(norm.num_nodes),
            "nnz": int(network_nnz(norm)),
            "tuned": params.to_dict(),
        },
        stats=stats_from_samples(samples).to_dict(),
        derived={
            # first call may legitimately hit a persisted cache from an
            # earlier pass on this host — informational, not gated
            "cache_hit_first": 1.0 if hits[0] else 0.0,
            # the second call must ALWAYS hit (same process, same shape)
            "cache_hit_second": 1.0 if hits[1] else 0.0,
            "cold_s": samples[0],
            "hot_s": samples[1],
        },
        strict=["cache_hit_second"],
    )


def _powerlaw_race_records(fast: bool) -> List[BenchRecord]:
    """Fused superstep vs the pre-fusion loop on a >=100k-edge network."""
    import repro.scenarios as sc
    from repro.engine.autotune import network_nnz

    bundle = sc.generate("powerlaw", scale=RACE_SCALE, seed=0)
    net = bundle.network
    norm = net.normalize()
    n = norm.num_nodes
    nnz = network_nnz(norm)
    Y = np.zeros((n, RACE_SEED_COLS), dtype=np.float32)
    Y[np.arange(RACE_SEED_COLS), np.arange(RACE_SEED_COLS)] = 1.0

    legacy = _solve_record(
        "powerlaw_race_legacy",
        "legacy",
        {"autotune": False},
        {"fused_superstep": False},
        norm,
        Y,
        sigma=RACE_SIGMA,
        nnz=nnz,
        edges=net.num_edges,
    )
    fused = _solve_record(
        "powerlaw_race_fused",
        "fused",
        {"autotune": False},
        {},
        norm,
        Y,
        sigma=RACE_SIGMA,
        nnz=nnz,
        edges=net.num_edges,
        F_ref=legacy._F,
    )
    fused.derived["speedup_vs_legacy"] = legacy._median_s / max(
        fused._median_s, 1e-12
    )
    return [legacy, fused]


@register_suite(
    "kernel_variants",
    description="fused-superstep / autotune / bf16 A-B cells with "
    "roofline achieved-vs-peak terms",
)
def records(fast: bool = True) -> List[BenchRecord]:
    out: List[BenchRecord] = []
    out.extend(_drugnet_records(fast))
    out.append(_autotune_record(fast))
    out.extend(_powerlaw_race_records(fast))
    for rec in out:  # drop intra-suite plumbing before serialization
        for attr in ("_median_s", "_F"):
            if hasattr(rec, attr):
                delattr(rec, attr)
    return out


if __name__ == "__main__":
    from repro.bench.report import legacy_csv_line

    for r in records(fast=True):
        print(legacy_csv_line(r))
