"""Seconds of host clock to normalize the network, assemble the operator
and upload it, until the plan is on the device (``deploy.session``)."""


def read(ctx):
    return ctx.facts.get("prepare_s")
