"""A cell's run at a tiny size on the CPU, with the chip guard skipped."""
from __future__ import annotations

from chipbench import harness

#: the tiny network's share of the config's node counts (edges by its square)
TINY_SCALE = 0.1


def context(cell: str, seed: int = 4_100_000_007, seconds: float = 1.5, *, trace=False, control=False):
    """The context of ``cell`` at the tiny size, as if on a TPU v5e."""
    harness.setup_env()
    ctx = harness.Context.load(harness.load_benchmark(), cell, seed, seconds, trace, control)
    ctx.config["generator"]["scale"] = TINY_SCALE
    traffic = ctx.cell["traffic"]
    if "job" in traffic:
        traffic["job"] = 64
    if "warmup_s" in traffic:
        traffic.update(
            rate_qps=20.0, warmup_s=1.0, delta_first_s=0.5, delta_every_s=1.0, grace_s=30.0
        )
    ctx.device = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
    return ctx


def run(ctx) -> dict:
    """Drive the rest of a run: the window, the check and the result line."""
    driver = harness.load_module("drivers", ctx.cell["driver"])
    try:
        state = driver.run(ctx)
        del state
        driver.verify(ctx)
    finally:
        ctx.clock.close()
    return harness.result(ctx)
