"""Open-loop queries with live graph deltas, against the program's serve tier.

Traffic keys (``chipbench/traffic/<traffic>.json``):

* ``rate_qps``: Poisson arrivals of "top ``top_k`` ``target`` for a
  ``source``", the source drawn zipf(``zipf``) over its type in a rank
  order drawn from the seed; every seed gets the same set of gaps between
  arrivals, in its own order;
* ``delta_every_s``, ``delta_first_s``: one new ``source``–``target``
  association at these offsets, applied by a harness thread so that the
  query generator never waits for one; the edges are drawn from the
  config's network at its ``network_seed``, and the run's relabelling
  names them, so every seed sees the same operators in another order;
* ``warmup_s``: the same mix, deltas included, before the window;
* ``grace_s``: how long answers may come after the window closes;
* ``check_answers``: answers of the window the reference checks (plus the
  first answer on each network version).

The serve tier runs the program's production ``ServeSpec`` defaults.

Set-up first drives a scratch serve engine of the session over every
network version the run will see, with bursts of every batch width, so
that the window compiles nothing; then the live engine serves the mix.
Latency is from a query's due time to its answer.  A query that fails, is
refused, or has no answer when the grace ends counts in ``failed`` and,
in the tail, at the time waited for it: later than any answer.
"""
from __future__ import annotations

import queue
import threading
import time
from concurrent.futures import wait as wait_futures

import numpy as np

from chipbench import arrivals, deploy, harness, netgen, reference


class Plan:
    """What one run serves: the network, its deltas and the session."""

    def __init__(self, ctx):
        p = ctx.cell["traffic"]
        self.net, base, perms = deploy.network(ctx)
        self.src = deploy.type_index(self.net, p["source"])
        self.dst = deploy.type_index(self.net, p["target"])
        pair = (min(self.src, self.dst), max(self.src, self.dst))
        horizon = p["warmup_s"] + ctx.seconds
        self.delta_t = np.arange(p["delta_first_s"], horizon, p["delta_every_s"])
        drawn = netgen.new_associations(
            base, pair, len(self.delta_t), ctx.config["generator"]["network_seed"]
        )
        self.edges = [(pr, int(perms[pr[0]][u]), int(perms[pr[1]][v])) for pr, u, v in drawn]
        from repro.api import ServeSpec

        self.sess = deploy.session(ctx, self.net, ServeSpec(top_k=p["top_k"]))

    def delta(self, k: int):
        from repro.core.network import GraphDelta

        pair, u, v = self.edges[k]
        return GraphDelta(assoc=((pair, u, v, 1.0),))

    def query(self, node: int, top_k: int):
        from repro.serve.types import QuerySpec

        return QuerySpec(entity=int(node), target_type=self.dst, top_k=top_k)


def warm_versions(ctx, plan: Plan) -> None:
    """Every network version the run sees, at every batch width, through a
    scratch serve engine of the session."""
    p = ctx.cell["traffic"]
    eng = plan.sess.serve_engine()
    r = deploy.rng(ctx, 4)
    widths, w = [], eng.config.max_batch
    while w >= 1:
        widths.append(w)
        w //= 2
    sources = plan.net.offsets[plan.src] + np.arange(plan.net.sizes[plan.src])
    t0 = time.perf_counter()
    eng.start()
    try:
        for v in range(len(plan.edges) + 1):
            if v:
                eng.apply_delta(plan.delta(v - 1))
            for k in widths:
                nodes = r.choice(sources, size=k, replace=False)
                futs = [eng.submit(plan.query(n, p["top_k"])) for n in nodes]
                for f in futs:
                    f.result()
    finally:
        eng.stop()
    ctx.notes.append(
        f"warm_versions: {len(plan.edges) + 1} versions x {len(widths)} widths in "
        f"{time.perf_counter() - t0:.3f} s"
    )


def play(ctx, plan: Plan, rate: float, warmup_s: float, seconds: float, on_window=None):
    """Serve the mix on a fresh live engine; the window is
    ``[warmup_s, warmup_s + seconds)`` of its timeline."""
    p = ctx.cell["traffic"]
    r = deploy.rng(ctx, 5)
    horizon = warmup_s + seconds
    # one set of Poisson gaps for every seed, in the seed's order: each
    # seed offers the same number of queries
    gaps = np.diff(arrivals.poisson(rate, horizon, np.random.default_rng(0)), prepend=0.0)
    due = np.cumsum(r.permutation(gaps))
    ents = plan.net.offsets[plan.src] + arrivals.zipf_items(
        plan.net.sizes[plan.src], due.size, p["zipf"], r
    )
    deltas = [k for k, t in enumerate(plan.delta_t) if t < horizon]
    eng = plan.sess.serve_engine()
    eng.start()
    done = np.full(due.size, np.nan)
    futs = [None] * due.size
    late = np.zeros(due.size)
    applied = []  # (k, due, called, finished, version)
    t0 = time.perf_counter() + 0.05

    def sleep_until(t):
        dt = t - time.perf_counter()
        if dt > 0:
            time.sleep(dt)

    errors = []

    def apply_deltas():
        try:
            for k in deltas:
                sleep_until(t0 + plan.delta_t[k])
                with harness.span("apply_delta"):
                    called = time.perf_counter()
                    version = eng.apply_delta(plan.delta(k))
                    applied.append((k, t0 + plan.delta_t[k], called, time.perf_counter(), version))
        except Exception as e:  # noqa: BLE001 -- re-raised on the main thread
            errors.append(e)

    def on_done(i):
        return lambda _f: done.__setitem__(i, time.perf_counter())

    deltas_thread = threading.Thread(target=apply_deltas, name="chipbench-deltas")
    deltas_thread.start()
    window = None
    try:
        for i in range(due.size):
            if window is None and due[i] >= warmup_s:
                sleep_until(t0 + warmup_s)
                if on_window is not None:
                    on_window(eng)
                window = harness.span("window")
                window.__enter__()
            sleep_until(t0 + due[i])
            late[i] = time.perf_counter() - (t0 + due[i])
            try:
                with harness.span("submit"):
                    futs[i] = eng.submit(plan.query(ents[i], p["top_k"]), block=False)
            except queue.Full:
                continue
            futs[i].add_done_callback(on_done(i))
        sleep_until(t0 + horizon)
        stats_close = (eng.batcher.stats.completed, eng.batcher.stats.batches)
        compiles_close = ctx.clock.lowered
        if window is not None:
            window.__exit__(None, None, None)
        deltas_thread.join()
        if errors:
            raise errors[0]
        wait_futures([f for f in futs if f is not None], timeout=p["grace_s"])
        waited = time.perf_counter()
    finally:
        deltas_thread.join()
        eng.stop()
    return {
        "due": t0 + due, "futs": futs, "done": done,
        "late": late, "applied": applied, "w0": t0 + warmup_s, "w1": t0 + horizon,
        "waited": waited, "stats_close": stats_close, "compiles_close": compiles_close,
    }


def summarize(out: dict) -> dict:
    """Latency, freshness and counters of the queries due in the window."""
    due, done, futs = out["due"], out["done"], out["futs"]
    inw = np.flatnonzero((due >= out["w0"]) & (due < out["w1"]))
    lat, answers, failed = [], [], 0
    for i in inw:
        f = futs[i]
        ok = f is not None and f.done() and f.exception() is None and not np.isnan(done[i])
        if ok:
            lat.append(done[i] - due[i])
            answers.append((i, f.result()))
        else:
            failed += 1
            lat.append(out["waited"] - due[i])
    # first answer on each version or a later one
    firsts = {}
    for i, f in enumerate(futs):
        if f is not None and f.done() and f.exception() is None and not np.isnan(done[i]):
            v = f.result().version
            firsts[v] = min(firsts.get(v, np.inf), done[i])
    fresh, apply_s = [], []
    for k, t_due, called, finished, version in out["applied"]:
        if out["w0"] <= t_due < out["w1"]:
            first = min((t for v, t in firsts.items() if v >= version), default=out["waited"])
            fresh.append(first - t_due)
            apply_s.append(finished - called)
    late = out["late"][inw]
    return {
        "inw": inw, "lat": np.asarray(lat), "answers": answers, "failed": failed,
        "fresh": fresh, "apply_s": apply_s,
        "cache_hits": sum(r.source == "cache" for _, r in answers),
        "late_p50": float(np.percentile(late, 50)) if late.size else 0.0,
        "late_p99": float(np.percentile(late, 99)) if late.size else 0.0,
        "late_max": float(late.max(initial=0.0)),
    }


def run(ctx):
    p = ctx.cell["traffic"]
    plan = Plan(ctx)
    with harness.span("warm_versions"):
        warm_versions(ctx, plan)
    marks = {}

    def on_window(eng):
        marks["setup_s"] = ctx.since_start()
        marks["compile_s"] = ctx.clock.seconds
        marks["compiles"] = ctx.clock.lowered
        marks["stats"] = (eng.batcher.stats.completed, eng.batcher.stats.batches)

    ctx.start_trace()
    out = play(ctx, plan, p["rate_qps"], p["warmup_s"], ctx.seconds, on_window)
    ctx.stop_trace()
    s = summarize(out)
    ctx.e2e["setup_s"] = marks["setup_s"]
    ctx.e2e["query_p95_ms"] = float(np.percentile(s["lat"], 95)) * 1e3
    if s["fresh"]:
        ctx.e2e["delta_fresh_ms"] = float(np.mean(s["fresh"])) * 1e3
    (c0, b0), (c1, b1) = marks["stats"], out["stats_close"]
    ctx.facts.update(
        compile_s=marks["compile_s"],
        window_compiles=out["compiles_close"] - marks["compiles"],
        batch_size_mean=(c1 - c0) / (b1 - b0) if b1 > b0 else None,
        cache_hit_share=s["cache_hits"] / len(s["answers"]) if s["answers"] else None,
        delta_apply_ms=float(np.mean(s["apply_s"])) * 1e3 if s["apply_s"] else None,
        net=plan.net, edges=plan.edges, dst=plan.dst, top_k=p["top_k"],
        answers=s["answers"], versions_applied=[a[4] for a in out["applied"]],
    )
    ctx.attempted, ctx.failed = int(s["inw"].size), int(s["failed"])
    ctx.notes.append(
        f"generator lateness in the window: p50 {s['late_p50'] * 1e3:.3f} ms, "
        f"p99 {s['late_p99'] * 1e3:.3f} ms, max {s['late_max'] * 1e3:.3f} ms"
    )
    ctx.notes.append(
        f"window: {s['inw'].size} queries due, {len(s['answers'])} answered, "
        f"{len(s['fresh'])} deltas, p50 {np.percentile(s['lat'], 50) * 1e3:.3f} ms"
    )
    return plan


def verify(ctx):
    """Sampled answers of the window, on every version they came from,
    against the float64 reference of that version's network.

    ``answer_err`` is the widest gap, over the reference column's largest
    entry (the seed's own score), of either part of a served answer: a
    served score against the reference's score of that candidate, and the
    reference's score of the candidate served at each rank against the
    reference's own candidate at that rank (known associations excluded).
    A list of the wrong length, with repeats or with a known association
    reads infinite.
    """
    p = ctx.cell["traffic"]
    answers = ctx.facts["answers"]
    firsts = {}
    for j, (_, res) in enumerate(answers):
        firsts.setdefault(res.version, j)
    pick = deploy.sample_pick(
        deploy.rng(ctx, 6), len(answers), p["check_answers"], tuple(firsts.values())
    )
    by_version = {}
    for j in pick:
        by_version.setdefault(answers[j][1].version, []).append(answers[j][1])
    net, dst, alpha = ctx.facts["net"], ctx.facts["dst"], ctx.config["solver"]["alpha"]
    off, size = net.offsets[dst], net.sizes[dst]
    score_gap, rank_gap = 0.0, 0.0
    t0 = time.perf_counter()
    for version, results in sorted(by_version.items()):
        net_v = net.with_assoc(ctx.facts["edges"][:version])
        nodes = sorted({r.spec.entity for r in results})
        F = reference.solve(reference.operator(net_v, alpha), nodes, alpha)
        for r in results:
            full = F[:, nodes.index(r.spec.entity)]
            col = full[off : off + size]
            scale = np.max(np.abs(full))
            score_gap = max(score_gap, _score_gap(r, col) / scale)
            gap = _rank_gap(r, col, _known(net_v, r.spec.entity, dst))
            rank_gap = max(rank_gap, gap / scale)
    ctx.notes.append(
        f"reference: {len(pick)} answers on {len(by_version)} versions in "
        f"{time.perf_counter() - t0:.3f} s; answer_err parts: score {score_gap!r}, "
        f"rank {rank_gap!r}"
    )
    err = max(score_gap, rank_gap) if answers else float("inf")
    ctx.check("answer_err", err, ctx.cell["limits"]["answer_err"])
    ctx.check("unanswered", ctx.failed, 0)


def _known(net: netgen.Network, entity: int, dst: int) -> np.ndarray:
    """Mask over the ``dst`` block of the entity's known associations."""
    t = int(np.searchsorted(net.offsets, entity, side="right") - 1)
    u = entity - net.offsets[t]
    mask = np.zeros(net.sizes[dst], dtype=bool)
    if (t, dst) in net.assoc:
        r, c = net.assoc[(t, dst)]
        mask[c[r == u]] = True
    elif (dst, t) in net.assoc:
        r, c = net.assoc[(dst, t)]
        mask[r[c == u]] = True
    if t == dst:
        mask[u] = True
    return mask


def _score_gap(res, col: np.ndarray) -> float:
    """Largest gap of a served score from the reference's."""
    if len(res.candidates) == 0:
        return float("inf")
    return float(np.max(np.abs(np.asarray(res.scores) - col[res.candidates])))


def _rank_gap(res, col: np.ndarray, known: np.ndarray) -> float:
    """Largest gap, rank by rank, between the reference's scores of the
    served candidates and of its own top k."""
    want = reference.top_k(col, res.spec.top_k, known)
    got = np.asarray(res.candidates)
    if got.size != want.size or np.unique(got).size != got.size or known[got].any():
        return float("inf")
    return float(np.max(np.abs(col[want] - col[got]), initial=0.0))
