"""Mean host milliseconds of ``LPServeEngine.apply_delta`` over the deltas
due in the window, lock wait included."""


def read(ctx):
    return ctx.facts.get("delta_apply_ms")
