"""Roofline analysis from the dry-run's compiled artifacts.

Per (arch × shape × mesh) cell:
    compute term    = HLO_FLOPs / (chips × peak_FLOP/s)
    memory term     = HLO_bytes / (chips × HBM_bw)
    collective term = collective_bytes / (chips × link_bw)

Sources: ``compiled.cost_analysis()`` flops / bytes (per-device program).
Scan-over-layers programs report the loop body ONCE (verified against a
micro-benchmark); the dry-run compiled trip=0/trip=1 probes so we recover
exact totals:
    f(L) = f(0) + L · (f(1) − f(0)).
Collective bytes come from the HLO census (top-level vs in-loop buckets;
the in-loop bucket is multiplied by the trip count).

Hardware model: the per-chip peaks in :data:`PEAKS`, keyed by the
``device_kind`` JAX reports.  The dry-run prices its programs on
:data:`DRYRUN_TARGET`, the chip its production meshes describe.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional

#: Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
#: "TPU v5 lite" is TPU v5e — source: Google Cloud documentation, "TPU
#: v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
#: chip-to-chip interconnect over 4 links (50 GB/s per link).  A device
#: missing here has no peak: nothing is priced against another chip's.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
DRYRUN_TARGET = "TPU v5 lite"

CHIPS = {"single": 256, "multi": 512}


def corrected_costs(rec: Dict[str, Any]) -> Dict[str, float]:
    """Per-device FLOPs / HBM bytes with scan-body extrapolation."""
    cost = rec.get("cost", {})
    flops = float(cost.get("flops", 0.0))
    hbytes = float(cost.get("bytes accessed", 0.0))
    trip = rec.get("meta", {}).get("scan_trip")
    probe = rec.get("probe") or {}
    p0, p1 = probe.get("0"), probe.get("1")
    if trip and p0 and p1 and "flops" in p0 and "flops" in p1:
        body_f = p1["flops"] - p0["flops"]
        body_b = p1["bytes"] - p0["bytes"]
        flops = p0["flops"] + trip * body_f
        hbytes = p0["bytes"] + trip * body_b
    return {"flops": flops, "hbm_bytes": hbytes}


def collective_bytes(rec: Dict[str, Any]) -> Dict[str, float]:
    """Per-device collective bytes (loop bucket × trip count)."""
    cols = rec.get("collectives", {})
    trip = rec.get("meta", {}).get("scan_trip") or 1
    total, per_kind = 0.0, {}
    for kind, c in cols.items():
        if not isinstance(c, dict):
            continue
        b = c.get("bytes", 0) + trip * c.get("loop_bytes", 0)
        per_kind[kind] = b
        total += b
    return {"total": total, **per_kind}


def analyze(rec: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    if rec.get("status") != "ok":
        return None
    chips = CHIPS[rec["mesh"]]
    cost = corrected_costs(rec)
    col = collective_bytes(rec)
    # cost_analysis is the per-device program; totals are ×chips, and both
    # numerator and denominator scale by chips — terms are per-device time.
    peak = PEAKS[DRYRUN_TARGET]
    t_compute = cost["flops"] / peak["flops"]
    t_memory = cost["hbm_bytes"] / peak["hbm_bw"]
    t_collective = col["total"] / peak["ici_bw"]
    terms = {
        "compute": t_compute, "memory": t_memory, "collective": t_collective,
    }
    bottleneck = max(terms, key=terms.get)
    bound = max(terms.values())
    total_flops = cost["flops"] * chips
    model_flops = rec.get("meta", {}).get("model_flops")
    out = {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "kind": rec.get("kind"), "chips": chips,
        "flops_per_device": cost["flops"],
        "hbm_bytes_per_device": cost["hbm_bytes"],
        "collective_bytes_per_device": col["total"],
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "bottleneck": bottleneck,
        "roofline_bound_s": bound,
        # fraction of the bound the compute term occupies = how close the
        # cell is to being compute-limited (1.0 = at the compute roofline)
        "compute_fraction": t_compute / bound if bound > 0 else 0.0,
        "collectives": {k: v for k, v in col.items() if k != "total"},
    }
    if model_flops:
        out["model_flops"] = model_flops
        out["useful_flops_ratio"] = model_flops / max(total_flops, 1.0)
    peak_mem = rec.get("memory", {})
    if "temp_size_in_bytes" in peak_mem:
        out["temp_bytes"] = peak_mem["temp_size_in_bytes"]
        out["arg_bytes"] = peak_mem.get("argument_size_in_bytes", 0)
        out["fits_hbm_16g"] = (
            peak_mem["temp_size_in_bytes"]
            + peak_mem.get("argument_size_in_bytes", 0)
        ) < 16e9
    return out


def load(path: str) -> List[Dict[str, Any]]:
    """Read a dry-run census file in either format.

    Accepts the legacy bare-record JSONL and the telemetry artifact
    format (``telemetry/dryrun.jsonl``, DESIGN.md §14.1) — there a
    leading ``meta`` line is skipped and each ``event`` line's ``attrs``
    is the census record.
    """
    rows = []
    with open(path) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            kind = rec.get("kind") if isinstance(rec, dict) else None
            if kind == "meta":
                continue
            if kind == "event":
                rec = rec.get("attrs", {})
            rows.append(rec)
    return rows


def build_table(path: str = "results/dryrun.jsonl") -> List[Dict[str, Any]]:
    out = []
    for rec in load(path):
        a = analyze(rec)
        if a is not None:
            out.append(a)
    return out


# ---------------------------------------------------------------------------
# LP-round achieved-vs-peak (kernel-variant cells)
# ---------------------------------------------------------------------------
# The dry-run census covers compiled multi-pod programs; the LP kernel
# variants (benchmarks/kernel_variants.py) instead run live, so their
# roofline terms come from an analytic per-round cost model evaluated
# against the measured wall clock, on the device the round ran on.


def lp_round_cost(
    *, nnz: int, num_nodes: int, s: int, storage_bytes: int = 4
) -> Dict[str, float]:
    """Analytic FLOPs / HBM bytes for ONE fused LP round.

    The fused superstep computes ``c*base + A_eff @ F`` plus the residual
    reduction: 2 FLOPs per stored edge per seed column (multiply-add),
    plus the seed-term axpy and the ``|Fn − prev|`` max-reduce (2·N·S
    each).  Bytes: edge structure (int32 index + weight) read once, one
    gathered label row per edge, and base/prev reads + label write per
    node row (accumulation is f32 regardless of storage dtype, so the
    row-wise traffic stays 4-byte; ``storage_bytes`` scales the gather
    panel and the weights — the bf16 lever).
    """
    flops = 2.0 * nnz * s + 4.0 * num_nodes * s
    hbytes = (
        nnz * (4.0 + storage_bytes)  # nbr index + weight
        + nnz * storage_bytes * s  # gathered label rows
        + num_nodes * 4.0 * s * 3.0  # base + prev reads, label write
    )
    return {"flops": flops, "bytes": hbytes}


def achieved_vs_peak(
    round_s: float, cost: Dict[str, float], device_kind: str
) -> Dict[str, float]:
    """Achieved FLOP/s and bandwidth, and their share of the device's peaks.

    ``round_s`` is the measured wall time of one LP round on a device of
    ``device_kind``.  The ``frac_peak_*`` shares are given only for a
    device in :data:`PEAKS`; any other device (the CPU among them) gets
    the achieved rates alone.
    """
    t = max(round_s, 1e-12)
    out = {
        "achieved_gflops": cost["flops"] / t / 1e9,
        "achieved_gbps": cost["bytes"] / t / 1e9,
    }
    peak = PEAKS.get(device_kind)
    if peak is not None:
        out["frac_peak_flops"] = cost["flops"] / t / peak["flops"]
        out["frac_peak_bw"] = cost["bytes"] / t / peak["hbm_bw"]
    return out


# ---------------------------------------------------------------------------
# repro.bench suite: dry-run artifacts → BENCH records (ROADMAP item)
# ---------------------------------------------------------------------------
SAMPLE_ARTIFACTS = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "data", "dryrun_sample.jsonl"
)


def artifact_path() -> str:
    """Pick the dry-run artifact feed for the suite.

    The committed sample is the default — record keys and the strict HLO
    census must stay reproducible against ``benchmarks/baseline.json``,
    so a leftover ``results/dryrun.jsonl`` from a local sweep must NOT
    silently change the suite's identity.  Feeding live artifacts is an
    explicit opt-in via ``REPRO_DRYRUN_ARTIFACTS`` (refresh the sample
    itself with ``python -m repro.launch.dryrun --arch dhlp-bio --out
    benchmarks/data/dryrun_sample.jsonl`` and commit it with a refreshed
    baseline).
    """
    override = os.environ.get("REPRO_DRYRUN_ARTIFACTS")
    if override:
        print(f"roofline: reading artifacts from {override} "
              "(REPRO_DRYRUN_ARTIFACTS)", flush=True)
        return override
    return SAMPLE_ARTIFACTS


def records(fast: bool = True) -> List[Any]:
    """One BENCH record per analyzed (arch × shape × mesh) cell.

    ``stats`` carries the measured lower+compile wall time (the only
    clocked quantity a dry run has); the roofline terms land in
    ``derived`` with the per-device HLO census marked strict — they are
    deterministic functions of the committed artifact, so any drift means
    the compiled program changed, not the runner.
    """
    from repro.bench import BenchRecord, stats_from_samples

    path = artifact_path()
    out: List[Any] = []
    for rec in load(path):
        if rec.get("status") != "ok":
            print(
                f"roofline: skipped {rec.get('arch')}/{rec.get('shape')}"
                f"@{rec.get('mesh')} (status={rec.get('status')})",
                flush=True,
            )
            continue
        a = analyze(rec)
        if a is None:
            continue
        wall = float(rec.get("lower_s", 0.0)) + float(rec.get("compile_s", 0.0))
        derived = {
            "flops_per_device": a["flops_per_device"],
            "hbm_bytes_per_device": a["hbm_bytes_per_device"],
            "collective_bytes_per_device": a["collective_bytes_per_device"],
            "t_compute_s": a["t_compute_s"],
            "t_memory_s": a["t_memory_s"],
            "t_collective_s": a["t_collective_s"],
            "roofline_bound_s": a["roofline_bound_s"],
            "compute_fraction": a["compute_fraction"],
        }
        out.append(BenchRecord(
            suite="roofline",
            name=f"{a['arch']}/{a['shape']}",
            backend=a["mesh"],
            params={
                "chips": a["chips"],
                "kind": a.get("kind"),
                "bottleneck": a["bottleneck"],
                "artifact": (
                    "sample" if path == SAMPLE_ARTIFACTS else "live"
                ),
            },
            stats=stats_from_samples([wall]).to_dict(),
            derived=derived,
            strict=[
                "flops_per_device",
                "hbm_bytes_per_device",
                "collective_bytes_per_device",
            ],
        ))
    return out


def register() -> None:
    """Register the roofline suite with the shared bench registry."""
    from repro.bench.registry import register_suite

    register_suite(
        "roofline",
        description="roofline terms from multi-pod dry-run artifacts",
    )(records)


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--in", dest="inp", default="results/dryrun.jsonl")
    ap.add_argument("--out", default="results/roofline.json")
    ap.add_argument("--mesh", default=None, choices=[None, "single", "multi"])
    args = ap.parse_args()

    table = build_table(args.inp)
    if args.mesh:
        table = [t for t in table if t["mesh"] == args.mesh]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(table, f, indent=1)

    hdr = (
        f"{'arch':<22} {'shape':<14} {'mesh':<7} {'t_comp':>9} {'t_mem':>9} "
        f"{'t_coll':>9} {'bound':<11} {'comp%':>6} {'fits':>5}"
    )
    print(hdr)
    print("-" * len(hdr))
    for t in sorted(table, key=lambda x: (x["arch"], x["shape"], x["mesh"])):
        print(
            f"{t['arch']:<22} {t['shape']:<14} {t['mesh']:<7} "
            f"{t['t_compute_s']:>9.2e} {t['t_memory_s']:>9.2e} "
            f"{t['t_collective_s']:>9.2e} {t['bottleneck']:<11} "
            f"{100*t['compute_fraction']:>5.1f} "
            f"{'y' if t.get('fits_hbm_16g') else 'N':>5}"
        )
    print(f"\n{len(table)} cells → {args.out}")


if __name__ == "__main__":
    main()
