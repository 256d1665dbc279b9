"""Mean milliseconds a job of the program's ``engine.fetch`` spans in the
traced window: the device-to-host copy of each job's result and its
float64 widening (``SparseCSREngine.solve``).

Reads the trace's program spans (``chipbench/spancut.py``), which exist
when the session records them (``ObsSpec(level="trace")``)."""


def read(ctx):
    spans = (ctx.facts.get("trace") or {}).get("spans") or {}
    fetch = spans.get("engine.fetch")
    jobs = ctx.attempted // ctx.cell["traffic"]["job"]
    if not fetch or not jobs:
        return None
    return fetch["seconds"] * 1e3 / jobs
