"""The supersteps' least time on the chip over device busy time, in %.

The least time is that of the compulsory work (``chipbench/roofline.py``):
the operator once per superstep and three passes over each column
advanced, at the chip's peak bandwidth or flop rate, whichever bounds it.
"""
from chipbench import roofline


def read(ctx):
    f, trace = ctx.facts, ctx.facts.get("trace")
    if not trace or not f.get("rounds") or trace["busy_s"] <= 0:
        return None
    work = roofline.superstep_work(f["nnz"], f["nodes"], f["rounds"], f["col_iters"])
    least = roofline.least_time(work, ctx.device["kind"])
    ctx.notes.append(
        f"superstep_roofline: {least['bound']}-bound, {work['bytes']:.6g} B, "
        f"{work['flops']:.6g} flop, least {least['seconds']:.6g} s, busy {trace['busy_s']:.6g} s"
    )
    return least["seconds"] / trace["busy_s"] * 100.0
