"""Milliseconds of operator rebuild per delta due in the window: the union
of the program's ``engine.prepare`` spans inside the traced window over the
deltas due in it, so a rebuild's time times the rebuilds each delta causes.

Reads the trace's program spans (``chipbench/spancut.py``), which exist
when the session records them (``ObsSpec(level="trace")``)."""
import numpy as np


def read(ctx):
    spans = (ctx.facts.get("trace") or {}).get("spans")
    p = ctx.cell["traffic"]
    due = np.arange(p["delta_first_s"], p["warmup_s"] + ctx.seconds, p["delta_every_s"])
    deltas = int(np.count_nonzero(due >= p["warmup_s"]))
    if spans is None or not deltas:
        return None
    return spans.get("engine.prepare", {}).get("seconds", 0.0) * 1e3 / deltas
