"""Run one cell of the chip benchmark once.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's network from the seed, drives the program through its
normal path (RunSpec -> Session -> engine registry; the serve tier through
``Session.serve_engine()``), warms up every shape the window uses, measures
for ``--seconds``, checks what the timed path produced against the plain
float64 reference, and prints one JSON line last.  ``--trace 0`` reports
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
profiled run.  Without a TPU, or with fewer chips than the cell asks for,
it prints why on stderr, no result, and exits 2.

``--control`` runs the program's bfloat16 storage path in place of the
float32 one the configuration states: the check that must come out not
correct.  The benchmark's own runs never pass it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    harness.setup_env()
    bench = harness.load_benchmark()
    ctx = harness.Context.load(bench, args.workload, args.seed, args.seconds, args.trace, args.control)
    ctx.t_start = T_START
    try:
        ctx.device = harness.require_chips(int(ctx.cell["chips"]))
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    driver = harness.load_module("drivers", ctx.cell["driver"])
    try:
        state = driver.run(ctx)
        ctx.device["memory_peak_bytes"] = harness.peak_bytes()
        del state
        gc.collect()
        driver.verify(ctx)
    finally:
        ctx.clock.close()
    harness.emit(ctx, harness.result(ctx))
    return 0


if __name__ == "__main__":
    sys.exit(main())
