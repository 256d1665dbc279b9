"""Benchmark driver: runs every registered suite through the shared
harness (``repro.bench``) and writes the machine-readable report.

    PYTHONPATH=src python benchmarks/run.py              # fast pass -> BENCH_ci.json
    PYTHONPATH=src python benchmarks/run.py --full       # paper scale -> BENCH_full.json
    PYTHONPATH=src python benchmarks/run.py --only lp_matrix,table7_sigma

This is now a thin wrapper over ``repro.bench.driver.run_bench`` — the
same pass ``python -m repro run --bench`` and RunSpec ``bench`` sections
execute (DESIGN.md §10/§13).  Artifacts: ``BENCH_<label>.json`` at the
repo root (what CI uploads and ``repro.bench.compare`` gates on) plus a
timestamped per-run copy under ``results/``.  Any suite error makes the
exit code nonzero — no swallowed failures.
"""
from __future__ import annotations

import argparse
import os
import sys

# The backend matrix runs the sharded engine on 1/2/4 virtual host
# devices (8 on the full pass — the dhlp1 × sharded8 cell needs them);
# the device count is locked at jax init, so it must be set before ANY
# jax import (respect an operator-provided override).  argv is peeked
# here because argparse can only run inside main(), after this line.
_DEVICES = 8 if "--full" in sys.argv else 4
os.environ.setdefault(
    "XLA_FLAGS", f"--xla_force_host_platform_device_count={_DEVICES}"
)

# make `benchmarks.*` importable when invoked as `python benchmarks/run.py`
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="paper-scale parameters (slow on CPU)")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    ap.add_argument("--label", default=None,
                    help="report label (default: ci, or full with --full)")
    ap.add_argument("--no-write", action="store_true",
                    help="skip writing BENCH_<label>.json / results/")
    ap.add_argument("--list", action="store_true",
                    help="list registered suites and exit")
    args, _ = ap.parse_known_args(argv)

    from repro.bench.driver import (
        BenchSetupError,
        import_suite_modules,
        run_bench,
    )
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.list:
        from repro.bench import all_suites

        import_suite_modules()
        for s in all_suites():
            print(f"{s.name}: {s.description}")
        return 0

    only = args.only.split(",") if args.only else None
    try:
        outcome = run_bench(
            fast=not args.full,
            only=only,
            label=args.label,
            write=not args.no_write,
            echo=lambda line: print(line, flush=True),
        )
    except BenchSetupError as e:
        # the device count was locked from the PROCESS argv at import
        # (sys.argv peek above) — a programmatic main(['--full']) or an
        # abbreviated flag cannot raise it after jax initialized
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(
        f"suites={len(outcome.suites)} records={outcome.records} "
        f"failures={outcome.failures}",
        file=sys.stderr,
    )
    return 1 if outcome.failures else 0


if __name__ == "__main__":
    sys.exit(main())
