"""Seconds JAX spent lowering and compiling programs during set-up, from
its ``jax.monitoring`` duration events (a persistent-cache hit lowers but
does not compile)."""


def read(ctx):
    return ctx.facts.get("compile_s")
