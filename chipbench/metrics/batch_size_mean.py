"""Queries per batch the scheduler completed in the window, from the
change of ``SchedulerStats.completed`` and ``batches`` across it."""


def read(ctx):
    return ctx.facts.get("batch_size_mean")
