"""Batch solves on a network with several relations on one pair of types.

The job loop and the check of ``drivers/solve.py``, unchanged, run on
``relgen``'s network: it is handed to the program one sparse block per
relation (``relgen.to_program``), and the check's float64 operator is the
relation mean (``relref.operator``).  The traffic keys are ``solve.py``'s.

Besides ``prepare_s`` this records ``normalize_s``: the host clock around
``Session.norm`` alone, the extent of the program's ``network.normalize``
span.

A program that cannot hold several relations on one pair fails the run at
once (``relgen.require_relation_blocks``), before any network is built.
"""
from __future__ import annotations

import time
import types

from chipbench import deploy, harness, netgen, reference, relgen, relref


def network(ctx):
    """``(net, base, perms)``, as ``deploy.network``, of ``relgen``'s network."""
    base = relgen.generate(ctx.config)
    perms = netgen.permutations(base.sizes, ctx.seed)
    return base.relabel(perms), base, perms


def session(ctx, net: relgen.Network):
    """The program's Session on ``net``, its operator prepared; records
    ``normalize_s`` and ``prepare_s`` (``deploy.session``'s extent)."""
    import jax

    from repro.api import NetworkSpec, RunSpec, Session
    from repro.scenarios.base import ScenarioBundle

    spec = RunSpec(
        network=NetworkSpec(kind="scenario", name="powerlaw"),
        solve=deploy.solve_spec(ctx),
        run_id=f"chipbench-{ctx.name}",
    )
    hetero = relgen.to_program(net)
    pair = next(p for p in hetero.blocks if p[0] != p[1])
    bundle = ScenarioBundle(name=ctx.cell["config"], network=hetero, truth={}, eval_pair=pair)
    sess = Session(spec, bundle=bundle)
    t0 = time.perf_counter()
    norm = sess.norm
    ctx.facts["normalize_s"] = time.perf_counter() - t0
    op = sess.engine.prepare(norm)
    jax.block_until_ready(list(getattr(op.payload, "__dict__", {}).values()))
    ctx.facts["prepare_s"] = time.perf_counter() - t0
    return sess


def _solve_driver():
    """A private instance of ``drivers/solve.py`` whose network, session
    and reference operator are the relation network's."""
    mod = harness.load_module("drivers", "solve")
    mod.deploy = types.SimpleNamespace(**{**vars(deploy), "network": network, "session": session})
    mod.reference = types.SimpleNamespace(**{**vars(reference), "operator": relref.operator})
    return mod


def run(ctx):
    relgen.require_relation_blocks()
    return _solve_driver().run(ctx)


def verify(ctx):
    _solve_driver().verify(ctx)
