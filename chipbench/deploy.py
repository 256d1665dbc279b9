"""A cell's deployment: its network from the seed, and the program's Session.

The network is the config's (``netgen.generate``, at its ``network_seed``) with
every type's nodes relabelled by a permutation drawn from the run's seed:
each seed gets the same sizes, degrees and operator, in another order.
"""
from __future__ import annotations

import time
from typing import Optional, Tuple

import numpy as np

from chipbench import netgen


def rng(ctx, stream: int) -> np.random.Generator:
    """An independent random stream of the run's seed."""
    return np.random.default_rng([ctx.seed, stream])


def network(ctx) -> Tuple[netgen.Network, netgen.Network, list]:
    """``(net, base, perms)``: the run's network, the config's, and the
    per-type relabelling that maps one onto the other."""
    base = netgen.generate(ctx.config)
    perms = netgen.permutations(base.sizes, ctx.seed)
    return base.relabel(perms), base, perms


def solve_spec(ctx):
    from repro.api import SolveSpec

    s = ctx.config["solver"]
    devices = ctx.cell["chips"] if ctx.cell.get("backend") == "sharded" else None
    return SolveSpec(
        alg=s["alg"],
        alpha=s["alpha"],
        sigma=s["sigma"],
        seed_mode=s["seed_mode"],
        max_iter=s["max_iter"],
        backend=ctx.cell.get("backend", "sparse"),
        devices=devices,
        storage_dtype="bf16" if ctx.control else s["storage_dtype"],
        autotune=s["autotune"],
    )


def session(ctx, net: netgen.Network, serve=None):
    """The program's Session on ``net``, its operator prepared.

    Records ``prepare_s``: the host clock around normalization, operator
    assembly and upload, until the uploaded plan is on the device.
    """
    import jax

    from repro.api import NetworkSpec, RunSpec, Session
    from repro.scenarios.base import ScenarioBundle

    spec = RunSpec(
        network=NetworkSpec(kind="scenario", name="powerlaw"),
        solve=solve_spec(ctx),
        serve=serve,
        run_id=f"chipbench-{ctx.name}",
    )
    hetero = netgen.to_program(net)
    bundle = ScenarioBundle(
        name=ctx.cell["config"], network=hetero, truth={}, eval_pair=(0, 1)
    )
    sess = Session(spec, bundle=bundle)
    t0 = time.perf_counter()
    op = sess.engine.prepare(sess.norm)
    jax.block_until_ready(list(getattr(op.payload, "__dict__", {}).values()))
    ctx.facts["prepare_s"] = time.perf_counter() - t0
    return sess


def type_index(net: netgen.Network, name: str) -> int:
    return list(net.type_names).index(name)


def sample_pick(
    r: np.random.Generator, n: int, k: int, must: Optional[Tuple[int, ...]] = ()
) -> np.ndarray:
    """``k`` distinct indices of ``range(n)`` drawn from ``r``, plus ``must``."""
    pick = set(r.choice(n, size=min(k, n), replace=False).tolist()) if n else set()
    return np.array(sorted(pick | set(must or ())), dtype=np.int64)
