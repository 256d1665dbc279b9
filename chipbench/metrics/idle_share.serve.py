"""Share of the traced window in which no operation ran on the device, in %."""


def read(ctx):
    trace = ctx.facts.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return (1.0 - trace["busy_s"] / trace["window_s"]) * 100.0
