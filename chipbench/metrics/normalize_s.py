"""Seconds of host clock to normalize the network, each relation on its
own and each block the mean of its relations: ``Session.norm``, the extent
of the program's ``network.normalize`` span (``drivers/solve_relations``)."""


def read(ctx):
    return ctx.facts.get("normalize_s")
