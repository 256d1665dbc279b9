"""Smoke run of the DHLP solve and serve path on a TPU.

    python chip_smoke.py              # one chip: solves, kernel, serve
    python chip_smoke.py --chips 4    # the sharded DHLP-2 solve on 4 chips

Drives the path ``python -m repro run`` takes — RunSpec → Session → the
engine registry — on the ``powerlaw`` scenario at scale 1.0 (4,396 nodes,
1,211,064 edges), generated from its seed with the scenario disk cache
off and autotune off, so a checkout runs exactly the committed defaults.

One chip, one phase per JSON line:

* ``solve`` DHLP-2 and DHLP-1 on ``sparse``, every node a seed column,
  σ-converged; each agrees with the ``dense`` engine on the same chip over
  all columns and with the float64 reference (``core/reference.py``) on
  sampled columns;
* ``kernel``: DHLP-2 on the ``kernel`` backend (Pallas under Mosaic,
  checked in the compiled program) agrees with the sparse result;
* ``serve``: the session's prepared engine answers a zipf workload with
  graph deltas interleaved; no query may fail, and every answer computed
  before the first delta matches the solve's column.

``--chips 4`` runs only the ``sharded`` DHLP-2 solve over four chips and
the one-chip ``sparse`` solve it is compared with.

Each line carries compile and run seconds (compile = lowering and
backend compilation inside the phase; run = the rest of its wall time,
host set-up and tracing included), and the device's
peak bytes in use where the backend reports them.  The last line is
``{"ok": true, "device": {...}}`` only when every phase passed; a failed
phase exits 1.  Without a TPU the script prints why and exits 2.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

#: Engine-vs-engine and engine-vs-reference bound on |ΔF| — the tolerance
#: tests/test_references_and_sparse.py holds the engines to.
ATOL = 1e-5
SEED = 0
SIGMA = 1e-6  # tight enough that σ-converged solves sit within ATOL
REF_SIGMA = 1e-9
REF_COLUMNS = 16
DELTAS = 2
# lowering and backend compilation; tracing is left out because a jit
# traced inside another reports its own span inside its caller's
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """The run's size; the defaults are the chip run, tests shrink them."""

    scale: float = 1.0
    kernel_columns: int = 512
    requests: int = 200


class CompileClock:
    """Seconds JAX spends lowering and compiling programs, while open."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self._monitoring = jax.monitoring
        self._monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_: Any) -> None:
        if name in _COMPILE_EVENTS:
            self.seconds += secs

    def close(self) -> None:
        self._monitoring.unregister_event_duration_listener(self._on_event)


def _timed(clock: CompileClock, fn: Callable[[], Any]):
    """``(result, {"compile_s", "run_s"})`` of one call."""
    c0, t0 = clock.seconds, time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    return out, {"compile_s": compile_s, "run_s": max(wall - compile_s, 0.0)}


def peak_bytes() -> Optional[int]:
    """The first device's peak bytes in use so far, where reported."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def device_info() -> Dict[str, Any]:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


class Smoke:
    """The network and sessions the phases share."""

    def __init__(self, cfg: SmokeConfig, clock: CompileClock):
        self.cfg = cfg
        self.clock = clock
        self._bundle = None
        self.solutions: Dict[str, np.ndarray] = {}
        self.sessions: Dict[str, Any] = {}

    def spec(self, alg: str, backend: str, *, serve: bool = False, devices=None):
        from repro.api import NetworkSpec, RunSpec, ServeSpec, SolveSpec

        cfg = self.cfg
        return RunSpec(
            network=NetworkSpec(
                kind="scenario", name="powerlaw", scale=cfg.scale, seed=SEED,
                cache=False,
            ),
            solve=SolveSpec(
                alg=alg, backend=backend, devices=devices, seed_mode="fixed",
                sigma=SIGMA, autotune=False,
            ),
            serve=(
                ServeSpec(requests=cfg.requests, deltas=DELTAS)
                if serve else None
            ),
            run_id=f"chip-smoke-{alg}-{backend}",
        )

    def session(self, alg: str, backend: str, **kw):
        """A Session on the shared network (generated once, from its seed)."""
        from repro.api import Session

        sess = Session(self.spec(alg, backend, **kw), bundle=self._bundle)
        if self._bundle is None:
            self._bundle = sess.bundle
        self.sessions[f"{alg}-{backend}"] = sess
        return sess

    def solve(self, alg: str, backend: str, **kw):
        """Session.solve() on a fresh session; ``(artifact, timing)``."""
        sess = self.session(alg, backend, **kw)
        _ = sess.norm  # generation + normalization are set-up, not solve
        art, timing = _timed(self.clock, sess.solve)
        self.solutions[f"{alg}-{backend}"] = art.F
        return art, timing

    def ref_columns(self, n: int) -> np.ndarray:
        rng = np.random.default_rng(SEED)
        return np.sort(rng.choice(n, size=min(REF_COLUMNS, n), replace=False))


def _max_abs(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))))


def reference_solution(sess, alg: str, cols: np.ndarray, sigma: float) -> np.ndarray:
    """float64 per-seed reference columns for ``alg``'s fixed point.

    The engines scale cross-type blocks by ``hetero_scale``; the reference
    loops take the literal blocks, so they run on a copy scaled the same.
    Fixed-seed DHLP-2 and DHLP-1 share Heter-LP's and MINProp's fixed
    points (Jacobi vs Gauss–Seidel sweeps of the same update).
    """
    from repro.core.reference import run_all_seeds

    norm = sess.norm
    scale = sess.lp_config().resolved_hetero_scale(norm.num_types)
    scaled = dataclasses.replace(
        norm, S_het={k: scale * v for k, v in norm.S_het.items()}
    )
    seeds = np.eye(norm.num_nodes)[:, cols]
    alpha = sess.lp_config().alpha
    if alg == "dhlp2":
        ref = run_all_seeds(
            scaled, alg="heterlp", alpha=alpha, sigma=sigma, seeds=seeds,
            seed_mode="fixed", max_iter=10_000,
        )
    else:
        ref = run_all_seeds(
            scaled, alg="minprop", alpha=alpha, sigma=sigma, seeds=seeds,
            max_outer=10_000, max_inner=10_000,
        )
    return ref.F


# --------------------------------------------------------------------------
# Phases: each returns one JSON-able record with "ok"
# --------------------------------------------------------------------------
def phase_solve(smoke: Smoke, alg: str) -> Dict[str, Any]:
    """``sparse`` solve of every seed column, checked against ``dense`` on
    the same device and against the float64 reference."""
    art, timing = smoke.solve(alg, "sparse")
    n = art.F.shape[0]
    dense, dense_timing = smoke.solve(alg, "dense")
    cols = smoke.ref_columns(n)
    sess = smoke.sessions[f"{alg}-sparse"]
    t0 = time.perf_counter()
    ref = reference_solution(sess, alg, cols, REF_SIGMA)
    ref_s = time.perf_counter() - t0
    dense_diff = _max_abs(art.F, dense.F)
    ref_diff = _max_abs(art.F[:, cols], ref)
    ok = (
        art.converged and dense.converged and art.F.shape == (n, n)
        and bool(np.isfinite(art.F).all())
        and dense_diff <= ATOL and ref_diff <= ATOL
    )
    return {
        "phase": "solve", "alg": alg, "backend": "sparse",
        "network": art.network, "columns": art.F.shape[1], **timing,
        "supersteps": art.supersteps, "outer_iters": art.outer_iters,
        "inner_iters": art.inner_iters, "converged": art.converged,
        "dense": {**dense_timing, "supersteps": dense.supersteps,
                  "max_abs_diff": dense_diff},
        "reference": {"columns": [int(c) for c in cols], "seconds": ref_s,
                      "sigma": REF_SIGMA, "max_abs_diff": ref_diff},
        "atol": ATOL, "peak_bytes": peak_bytes(), "ok": bool(ok),
    }


def phase_kernel(smoke: Smoke) -> Dict[str, Any]:
    """DHLP-2 on the Pallas ``kernel`` backend vs the ``sparse`` result."""
    sess = smoke.session("dhlp2", "kernel")
    norm = sess.norm
    n = norm.num_nodes
    k = min(smoke.cfg.kernel_columns, n)
    Y = np.eye(n, dtype=np.float32)[:, :k]
    engine = sess.engine
    res, timing = _timed(smoke.clock, lambda: engine.run(norm, seeds=Y))
    text = engine.lower(engine.prepare(norm), Y).compile().as_text()
    mosaic = "tpu_custom_call" in text
    sparse = smoke.solutions.get("dhlp2-sparse")
    if sparse is None:  # the solve phase did not run
        sparse = smoke.solve("dhlp2", "sparse")[0].F
    diff = _max_abs(res.F, sparse[:, :k])
    return {
        "phase": "kernel", "alg": "dhlp2", "backend": "kernel", "columns": k,
        **timing, "supersteps": res.supersteps, "converged": res.converged,
        "tpu_custom_call": mosaic, "sparse_max_abs_diff": diff, "atol": ATOL,
        "peak_bytes": peak_bytes(),
        "ok": bool(res.converged and mosaic and diff <= ATOL),
    }


def _expected_top(net, F: np.ndarray, result):
    """``(scores, candidates)`` the serve engine should rank from the
    solve's column: the query's target block, with known associations and
    the entity itself excluded."""
    from repro.core.ranking import topk_exclusive

    spec = result.spec
    t_ent = int(np.searchsorted(net.offsets, spec.entity, side="right")) - 1
    u = spec.entity - net.offsets[t_ent]
    tt = spec.target_type
    off = net.offsets[tt]
    scores = F[off : off + net.sizes[tt], spec.entity]
    exclude = np.zeros(scores.shape[0], dtype=bool)
    if (t_ent, tt) in net.R:
        exclude |= net.R[(t_ent, tt)][u] > 0
    elif (tt, t_ent) in net.R:
        exclude |= net.R[(tt, t_ent)][:, u] > 0
    if t_ent == tt:
        exclude[u] = True
    return scores, topk_exclusive(scores, spec.top_k, exclude)


def phase_serve(smoke: Smoke) -> Dict[str, Any]:
    """Zipf workload with deltas against the session's prepared engine."""
    sess = smoke.session("dhlp2", "sparse", serve=True)
    net = sess.network
    _ = sess.norm
    solve, solve_timing = _timed(smoke.clock, sess.solve)
    art, timing = _timed(smoke.clock, sess.serve)
    F = solve.F
    pre = [r for r in art.answers if r.version == 0]
    worst, mismatched = 0.0, 0
    for r in pre:
        scores, want = _expected_top(net, F, r)
        worst = max(worst, _max_abs(r.scores, scores[r.candidates]))
        if not np.array_equal(np.sort(r.candidates), np.sort(want)):
            # a swap across a near-tie is the same ranking at this precision
            gap = abs(float(np.min(r.scores)) - float(np.min(scores[want])))
            mismatched += gap > ATOL
    report = art.report
    ok = (
        art.failures == 0 and report["queries"] == smoke.cfg.requests
        and len(report["deltas"]) == DELTAS
        and len(pre) > 0 and worst <= ATOL and mismatched == 0
    )
    return {
        "phase": "serve", "backend": "sparse", "solve": solve_timing, **timing,
        "queries": report["queries"], "failed": art.failures,
        "deltas": len(report["deltas"]), "qps": report["qps"],
        "p50_s": report["p50"], "p99_s": report["p99"],
        "pre_delta_answers": len(pre), "answer_max_abs_diff": worst,
        "ranking_mismatches": mismatched, "atol": ATOL,
        "peak_bytes": peak_bytes(), "ok": bool(ok),
    }


def phase_sharded(smoke: Smoke, devices: int) -> Dict[str, Any]:
    """DHLP-2 on ``sharded`` over ``devices`` chips vs one-chip ``sparse``."""
    art, timing = smoke.solve("dhlp2", "sharded", devices=devices)
    sparse, sparse_timing = smoke.solve("dhlp2", "sparse")
    diff = _max_abs(art.F, sparse.F)
    mesh = smoke.sessions["dhlp2-sharded"].engine.mesh()
    return {
        "phase": "sharded", "alg": "dhlp2", "backend": "sharded",
        "devices": int(mesh.devices.size), "network": art.network,
        "columns": art.F.shape[1], **timing, "supersteps": art.supersteps,
        "converged": art.converged,
        "sparse": {**sparse_timing, "max_abs_diff": diff}, "atol": ATOL,
        "peak_bytes": peak_bytes(),
        "ok": bool(art.converged and sparse.converged and diff <= ATOL),
    }


Phase = Tuple[str, Callable[[], Dict[str, Any]]]


def one_chip_phases(smoke: Smoke) -> List[Phase]:
    return [
        ("solve-dhlp2", lambda: phase_solve(smoke, "dhlp2")),
        ("solve-dhlp1", lambda: phase_solve(smoke, "dhlp1")),
        ("kernel", lambda: phase_kernel(smoke)),
        ("serve", lambda: phase_serve(smoke)),
    ]


def run_phases(phases: List[Phase], emit=print) -> bool:
    """Run every phase, one JSON line each; True when all passed."""
    all_ok = True
    for name, phase in phases:
        try:
            rec = phase()
        except Exception as e:  # noqa: BLE001 — a failed phase is reported
            rec = {"phase": name, "ok": False,
                   "error": f"{type(e).__name__}: {e}"[:2000]}
        all_ok &= bool(rec.get("ok"))
        emit(json.dumps(rec, default=float))
    return all_ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded solve and its comparison")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    device = device_info()
    guard = {"phase": "guard", **device, "compile_cache": cache_dir}
    if device["platform"] != "tpu":
        guard.update(ok=False, error=f"needs a TPU, JAX found {device['platform']!r}")
        print(json.dumps(guard))
        print(f"chip_smoke: no TPU (platform {device['platform']!r})", file=sys.stderr)
        return 2
    if device["count"] < args.chips:
        guard.update(ok=False, error=f"needs {args.chips} chips, found {device['count']}")
        print(json.dumps(guard))
        return 2
    print(json.dumps({**guard, "jax": jax.__version__, "ok": True}), flush=True)

    clock = CompileClock()
    try:
        smoke = Smoke(SmokeConfig(), clock)
        if args.chips == 4:
            phases = [("sharded", lambda: phase_sharded(smoke, 4))]
        else:
            phases = one_chip_phases(smoke)
        ok = run_phases(phases, emit=lambda line: print(line, flush=True))
    finally:
        clock.close()
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
