"""95th percentile, in ms, of the scheduler's queue wait over the window's
answered queries: the program's ``QueryResult.queued_s``, from submit to
the query's batch entering the execute stage, before the engine lock."""
import numpy as np


def read(ctx):
    waits = [getattr(res, "queued_s", None) for _, res in ctx.facts.get("answers") or ()]
    if not waits or None in waits:
        return None
    return float(np.percentile(waits, 95)) * 1e3
