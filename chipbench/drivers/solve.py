"""Batch solves back to back, through the program's solve path.

Traffic keys (``chipbench/traffic/<traffic>.json``):

* ``columns``: whose nodes are the seed columns, ``"all"`` or a type name
  (``"drug"``); jobs of ``job`` of them run through the session's engine
  (``Session.engine.run``), cycling through those nodes in an order drawn
  from the seed;
* ``check_per_job``, ``check_columns``: seed columns sampled from each
  job's result, and at most how many of them the reference checks.

One job of the window's shape runs as warm-up.  Then jobs run back to back
while the window is open; only whole jobs count, and the window ends when
the last job started ends.  ``solve_cols_per_s`` is the seed columns they
delivered to the host over that window.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import deploy, harness, reference


def _jobs(ctx, net):
    """Endless seed nodes of each job."""
    p = ctx.cell["traffic"]
    if p["columns"] == "all":
        nodes = np.arange(net.num_nodes)
    else:
        t = deploy.type_index(net, p["columns"])
        nodes = net.offsets[t] + np.arange(net.sizes[t])
    order = nodes[deploy.rng(ctx, 2).permutation(nodes.size)]
    pos = 0
    while True:
        idx = (pos + np.arange(p["job"])) % order.size
        pos = int(idx[-1] + 1) % order.size
        yield order[idx]


def _solve(sess, nodes):
    """``(F, rounds, column_iterations, converged)`` of one job."""
    n = sess.norm.num_nodes
    Y = np.zeros((n, nodes.size), dtype=np.float32)
    Y[nodes, np.arange(nodes.size)] = 1.0
    res = sess.engine.run(sess.norm, seeds=Y)
    iters = res.per_column_iters
    col_iters = int(iters.sum()) if iters is not None else res.outer_iters * nodes.size
    return res.F, res.outer_iters, col_iters, res.converged


def run(ctx):
    net = deploy.network(ctx)[0]
    sess = deploy.session(ctx, net)
    p = ctx.cell["traffic"]
    jobs = _jobs(ctx, net)
    pick = deploy.rng(ctx, 1)
    n = net.num_nodes

    with harness.span("warmup"):
        _solve(sess, next(jobs))
    ctx.facts["compile_s"] = ctx.clock.seconds
    ctx.e2e["setup_s"] = ctx.since_start()

    samples, cols, rounds, col_iters, unconverged = [], 0, 0, 0, 0
    ctx.start_trace()
    with harness.span("window"):
        t0 = t_end = time.perf_counter()
        while t_end - t0 < ctx.seconds:
            nodes = next(jobs)
            with harness.span("job"):
                F, r, ci, ok = _solve(sess, nodes)
            t_end = time.perf_counter()
            width = F.shape[1]
            for c in pick.choice(width, size=min(p["check_per_job"], width), replace=False):
                samples.append((int(nodes[c]), F[:, c].copy()))
            cols += width
            rounds += r
            col_iters += ci
            unconverged += 0 if ok else width
            del F
    ctx.stop_trace()

    ctx.attempted, ctx.failed = cols, unconverged
    ctx.e2e["solve_cols_per_s"] = cols / (t_end - t0)
    ctx.facts.update(
        net=net, samples=samples, rounds=rounds, col_iters=col_iters,
        nnz=net.operator_nnz, nodes=n,
    )
    ctx.notes.append(
        f"window {t_end - t0:.3f} s: {cols} columns, {rounds} rounds, "
        f"{col_iters} column-iterations"
    )
    return sess


def verify(ctx):
    """Sampled columns of the window's jobs against the float64 reference."""
    p = ctx.cell["traffic"]
    samples = ctx.facts["samples"]
    keep = deploy.rng(ctx, 3).permutation(len(samples))[: p["check_columns"]]
    samples = [samples[i] for i in sorted(keep)]
    nodes = sorted({s for s, _ in samples})
    alpha = ctx.config["solver"]["alpha"]
    t0 = time.perf_counter()
    F_ref = reference.solve(reference.operator(ctx.facts["net"], alpha), nodes, alpha)
    col = {s: i for i, s in enumerate(nodes)}
    err = max(
        (_rel_err(f, F_ref[:, col[s]]) for s, f in samples),
        default=float("inf"),
    )
    ctx.notes.append(f"reference: {len(nodes)} columns in {time.perf_counter() - t0:.3f} s")
    limits = ctx.cell["limits"]
    ctx.check("f_err", err, limits["f_err"])
    ctx.check("unconverged", ctx.failed, 0)


def _rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest gap of a column, over the reference column's largest entry."""
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
