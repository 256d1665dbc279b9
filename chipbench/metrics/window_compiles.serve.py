"""Programs lowered inside the window (compiled, or loaded from the
persistent cache): ``jax.monitoring`` lowering events.  Should be 0."""


def read(ctx):
    return ctx.facts.get("window_compiles")
