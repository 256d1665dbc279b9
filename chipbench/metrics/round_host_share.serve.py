"""Share of the early-exit rounds' time in which no operation ran on the
device, in %: the union of the program's ``serve.round`` spans inside the
traced window (host pack, device round, residual back, active-set update),
less the device-busy time inside it, over that union.

Reads the trace's program spans (``chipbench/spancut.py``), which exist
when the session records them (``ObsSpec(level="trace")``)."""


def read(ctx):
    spans = (ctx.facts.get("trace") or {}).get("spans") or {}
    rounds = spans.get("serve.round")
    if not rounds or rounds["seconds"] <= 0:
        return None
    return 100.0 * (1.0 - rounds["busy_s"] / rounds["seconds"])
