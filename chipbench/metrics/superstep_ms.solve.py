"""Device busy milliseconds per fused DHLP-2 superstep in the traced window.

A superstep is one fused round of ``F <- b^2 Y + A F`` over the job's
columns: ``SolveResult.outer_iters`` of each job (the program's own
``supersteps`` field counts two Giraph message rounds per fused round).
"""


def read(ctx):
    trace, rounds = ctx.facts.get("trace"), ctx.facts.get("rounds")
    if not trace or not rounds:
        return None
    return trace["busy_s"] / rounds * 1e3
