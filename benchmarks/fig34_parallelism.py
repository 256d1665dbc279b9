"""Paper Figures 3-4: effect of threads / workers on runtime.

Giraph's threads-per-worker and worker count both map to device-mesh size
here.  We sweep the edge-shard count of the distributed DHLP-2 engine over
meshes of the first *k* of ``jax.devices()``, all in the process that
holds the devices (a chip belongs to one process, so a child process
could not reach it).  A device count the host lacks becomes an explicit
error record.  On CPU the devices are virtual (``benchmarks/run.py`` and
``python -m repro`` fabricate 4, or 8 under ``--full``).

On a 1-core CPU host the sweep measures BSP coordination overhead
(more shards = more rendezvous on the same core) rather than speedup —
the shape of fig. 3's right half (too many threads slow down); on a TPU
host the same sweep spans chips.  Additionally, a stale-sync sweep shows
the straggler-mitigation trade (collective count vs iterations) from
DESIGN.md §6.
"""
from __future__ import annotations

import time
from typing import Dict, List

from repro.bench import BenchRecord, register_suite, stats_from_samples


def _run_one(norm, devices, stale: int) -> Dict:
    from repro.core import LPConfig
    from repro.parallel.hints import auto_mesh
    from repro.parallel.lp_sharded import ShardedHeteroLP

    k = len(devices)
    mesh = auto_mesh((1, k), ("data", "model"), devices=devices)
    cfg = LPConfig(alg="dhlp2", seed_mode="fixed", sigma=1e-5)
    solver = ShardedHeteroLP(cfg, stale_sync=stale)
    solver.run(norm, mesh)  # compile + run
    t0 = time.perf_counter()
    r = solver.run(norm, mesh)
    return {
        "devices": k,
        "stale": stale,
        "seconds": time.perf_counter() - t0,
        "iters": int(r.outer_iters),
        "converged": bool(r.converged),
    }


def run(device_counts=(1, 2, 4), stale_syncs=(1, 4)) -> List[Dict]:
    import jax

    from repro.data.drugnet import DrugNetSpec, make_drugnet

    dn = make_drugnet(
        DrugNetSpec(n_drug=48, n_disease=32, n_target=24, n_clusters=6, seed=0)
    )
    norm = dn.network.normalize()
    available = jax.devices()
    rows = []
    for dev in device_counts:
        for stale in stale_syncs:
            if dev > len(available):
                rows.append({
                    "devices": dev, "stale": stale,
                    "error": f"host has {len(available)} devices, "
                             f"the cell needs {dev}",
                })
                continue
            try:
                rows.append(_run_one(norm, available[:dev], stale))
            except Exception as e:  # noqa: BLE001 — one cell, one error row
                rows.append({"devices": dev, "stale": stale,
                             "error": f"{type(e).__name__}: {e}"[:200]})
    return rows


@register_suite("fig34_parallelism",
                description="paper Figs 3-4: worker-count sweep over a device mesh")
def records(fast: bool = True) -> List[BenchRecord]:
    rows = run(device_counts=(1, 2) if fast else (1, 2, 4, 8),
               stale_syncs=(1,) if fast else (1, 4))
    out: List[BenchRecord] = []
    for r in rows:
        name = f"d{r['devices']}s{r['stale']}"
        params = {"devices": r["devices"], "stale_sync": r["stale"]}
        if "error" in r:
            out.append(BenchRecord(
                suite="fig34_parallelism", name=name,
                backend=f"sharded{r['devices']}", params=params,
                error=r["error"],
            ))
        else:
            out.append(BenchRecord(
                suite="fig34_parallelism", name=name,
                backend=f"sharded{r['devices']}", params=params,
                stats=stats_from_samples([r["seconds"]]).to_dict(),
                derived={"iters": float(r["iters"]),
                         "converged": 1.0 if r["converged"] else 0.0},
                strict=["iters", "converged"],
            ))
    return out


def main(fast: bool = True) -> List[str]:
    from repro.bench.report import legacy_csv_line

    return [legacy_csv_line(r) for r in records(fast=fast)]


if __name__ == "__main__":
    import os

    # the sweep's virtual CPU devices must exist before the backend starts
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8"
    )
    for line in main(fast=False):
        print(line)
