"""Sweep the offered rate of a serve cell's mix to find its knee, on the chip.

    python chipbench/knee.py --workload serve-dtinet-live --seed <n> \
        --start 2 --seconds 30

One set-up (the cell's network, session and every network version warmed),
then the cell's mix at each rate on a fresh serve engine, deltas included:
``--start`` QPS, doubled until the backlog grows (or ``--max`` is passed),
then ``--refine`` geometric bisections between the highest rate that held
and the lowest that did not.  One JSON line per rate, and last the knee:
the highest rate at which the backlog did not grow, with the cell's rate,
four fifths of it, to write into the traffic file as a number.

The backlog grows at a rate where any of these holds (``grows``):

* a query was refused or had no answer within the grace;
* the backlog (queries due and not yet answered) at the window's close
  exceeds that at its start by more than a tenth of the queries due in the
  window.  The mix's deltas fall at the same phase of both instants, so a
  tier that keeps up holds its backlog level across the window.

A rate at which the generator itself ran late (median lateness over 10 ms)
cannot be offered by this host: the sweep stops there and says so.  Single
late submissions (a re-prepare can hold the interpreter for a second) are
not that: their wait counts in the latency, which runs from the due time.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness  # noqa: E402

GROWTH = 0.1  # backlog growth over the window, as a share of its queries
LATE_S = 0.010


def measure(ctx, serve, plan, rate: float, seconds: float) -> dict:
    """One rate: what the window offered, answered and how late."""
    warmup = ctx.cell["traffic"]["warmup_s"]
    out = serve.play(ctx, plan, rate, warmup, seconds)
    s = serve.summarize(out)
    due, done = out["due"][s["inw"]], out["done"][s["inw"]]
    third = (out["w1"] - out["w0"]) / 3
    first = s["lat"][due < out["w0"] + third]
    last = s["lat"][due >= out["w1"] - third]
    p50_first = float(np.median(first)) if first.size else float("inf")
    p50_last = float(np.median(last)) if last.size else float("inf")
    answered = int(np.sum(done < out["w1"]))

    def backlog(t):
        return int(np.sum(out["due"] <= t) - np.sum(out["done"] <= t))

    row = {
        "rate_qps": rate,
        "seconds": seconds,
        "due": int(s["inw"].size),
        "answered_by_close_per_s": answered / seconds,
        "failed": int(s["failed"]),
        "p50_ms": float(np.percentile(s["lat"], 50) * 1e3),
        "p95_ms": float(np.percentile(s["lat"], 95) * 1e3),
        "p50_first_third_ms": p50_first * 1e3,
        "p50_last_third_ms": p50_last * 1e3,
        "backlog_start": backlog(out["w0"]),
        "backlog_close": backlog(out["w1"]),
        "late_p50_ms": s["late_p50"] * 1e3,
        "late_p99_ms": s["late_p99"] * 1e3,
        "deltas": len(s["fresh"]),
        "delta_apply_ms": float(np.mean(s["apply_s"]) * 1e3) if s["apply_s"] else None,
        "delta_fresh_ms": float(np.mean(s["fresh"]) * 1e3) if s["fresh"] else None,
    }
    row["grows"] = grows(row)
    row["generator_late"] = s["late_p50"] > LATE_S
    return row


def grows(row: dict) -> bool:
    """Whether the backlog grew at this rate (the module's rule)."""
    return (
        row["failed"] > 0
        or row["backlog_close"] - row["backlog_start"] > GROWTH * row["due"]
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--start", type=float, required=True)
    ap.add_argument("--max", type=float, default=10000.0)
    ap.add_argument("--refine", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)

    harness.setup_env()
    ctx = harness.Context.load(harness.load_benchmark(), args.workload, args.seed, args.seconds, 0)
    try:
        harness.require_chips(int(ctx.cell["chips"]))
    except harness.NoChip as e:
        print(f"knee: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    serve = harness.load_module("drivers", "serve")
    ctx.cell["traffic"]["grace_s"] = 10.0
    plan = serve.Plan(ctx)
    serve.warm_versions(ctx, plan)
    for note in ctx.notes:
        print(note, file=sys.stderr, flush=True)

    def at(rate):
        row = measure(ctx, serve, plan, rate, args.seconds)
        print(json.dumps(row), flush=True)
        return row

    held, broke, stop = None, None, None
    rate = args.start
    while rate <= args.max:
        row = at(rate)
        if row["generator_late"]:
            stop = "generator late"
            break
        if row["grows"]:
            broke = rate
            break
        held, rate = rate, rate * 2
    for _ in range(args.refine if held is not None and broke is not None else 0):
        mid = round(float(np.sqrt(held * broke)), 1)
        row = at(mid)
        if row["generator_late"]:
            stop = "generator late"
            break
        held, broke = (held, mid) if row["grows"] else (mid, broke)
    print(json.dumps({
        "knee_qps": held if broke is not None else None,
        "first_growing_qps": broke,
        "stopped": stop,
        "rate_qps": round(0.8 * held, 1) if held is not None and broke is not None else None,
    }), flush=True)
    ctx.clock.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
