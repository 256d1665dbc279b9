"""What every cell shares: finding its files, the chip guard, clocks, the
trace, the per-layer readers and the result line.

A cell is found by name, from files alone:

* its entry in ``BENCHMARK.json``: its config, traffic and chips;
* ``chipbench/traffic/<traffic>.json``: the driver, the mix's parameters,
  the backend and the limits of the correctness check;
* ``chipbench/configs/<config>.json``: the deployment's sizes and solver;
* ``chipbench/drivers/<driver>.py``: the traffic driver, with ``run(ctx)``;
* ``chipbench/metrics/<metric>.py``: one reader per per-layer metric, with
  ``read(ctx)`` that returns a number or None when it finds nothing.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENTS = (LOWER_EVENT, "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(kind: str, name: str) -> dict:
    with open(BENCH_DIR / kind / f"{name}.json") as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = BENCH_DIR / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}", path)
    if spec is None or not path.exists():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def cell_metrics(bench: dict, cell: str, section: str) -> List[dict]:
    """The ``section`` metrics of ``bench`` that ``cell`` reports."""
    return [
        m for m in bench[section] if cell in m.get("workloads", [w["name"] for w in bench["workloads"]])
    ]


# ----------------------------------------------------------------- the chip
def require_chips(chips: int) -> Dict[str, Any]:
    """The device JAX found; raises NoChip unless it has ``chips`` TPUs."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}


def peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest chip, where the backend says."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def enable_compile_cache() -> None:
    """The program's persistent compile cache at its fixed path in the
    checkout (or ``JAX_COMPILATION_CACHE_DIR``), keeping every program."""
    from repro.compile_cache import enable_compile_cache as program_cache
    import jax

    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileClock:
    """Seconds JAX spends lowering and compiling, and programs lowered."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.lowered = 0
        self._monitoring = jax.monitoring
        self._monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_: Any) -> None:
        if name in COMPILE_EVENTS:
            self.seconds += secs
            self.lowered += name == LOWER_EVENT

    def close(self) -> None:
        self._monitoring.unregister_event_duration_listener(self._on_event)


def span(name: str):
    """A host span on the profiler's clock, ``chipbench.<name>``."""
    import jax

    return jax.profiler.TraceAnnotation(f"chipbench.{name}")


# ------------------------------------------------------------- one run
class Context:
    """One run of one cell: its inputs, and what the driver records."""

    def __init__(self, bench, name, cell, config, seed, seconds, trace, control=False):
        self.t_start = time.perf_counter()
        self.bench = bench
        self.name = name
        self.cell = cell
        self.config = config
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.control = bool(control)
        self.device: Dict[str, Any] = {}
        self.e2e: Dict[str, float] = {}
        self.facts: Dict[str, Any] = {}  # what per-layer readers read
        self.checks: Dict[str, Dict[str, float]] = {}
        self.notes: List[str] = []  # stderr lines before the checks
        self.attempted = 0
        self.failed = 0
        self.clock = CompileClock()
        self._trace_dir: Optional[str] = None

    @classmethod
    def load(cls, bench, name, seed, seconds, trace, control=False) -> "Context":
        """The context of cell ``name``: its entry in ``BENCHMARK.json``,
        its traffic file and its config file."""
        entry = next(w for w in bench["workloads"] if w["name"] == name)
        cell = {**load_json("traffic", entry["traffic"]), "config": entry["config"], "chips": entry["chips"]}
        config = load_json("configs", entry["config"])
        return cls(bench, name, cell, config, seed, seconds, trace, control)

    def since_start(self) -> float:
        return time.perf_counter() - self.t_start

    def check(self, name: str, value: float, limit: float) -> None:
        """A compared number beside its limit; ``value <= limit`` passes."""
        self.checks[name] = {"value": float(value), "limit": float(limit)}

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c["value"] <= c["limit"] for c in self.checks.values())

    # the traced window -----------------------------------------------
    def start_trace(self) -> None:
        if self.trace:
            import jax

            self._trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(self._trace_dir)

    def stop_trace(self) -> None:
        if self._trace_dir is None:
            return
        import jax

        from chipbench import tracecut

        jax.profiler.stop_trace()
        try:
            path = tracecut.find_xplane(self._trace_dir)
            self.facts["trace"] = tracecut.reduce(path) if path else None
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            self._trace_dir = None


def per_layer(ctx: Context) -> Dict[str, Dict[str, Any]]:
    """Every per-layer metric of the cell that its reader finds."""
    out = {}
    for m in cell_metrics(ctx.bench, ctx.name, "per_layer"):
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def end_to_end(ctx: Context) -> Dict[str, Dict[str, Any]]:
    return {
        m["name"]: {"value": float(ctx.e2e[m["name"]]), "unit": m["unit"]}
        for m in cell_metrics(ctx.bench, ctx.name, "end_to_end")
    }


def result(ctx: Context) -> dict:
    """The run's last line (``checks`` last, as the harness's own key)."""
    device = dict(ctx.device)
    line: Dict[str, Any] = {
        "correct": ctx.correct,
        "attempted": int(ctx.attempted),
        "failed": int(ctx.failed),
        "metrics": per_layer(ctx) if ctx.trace else end_to_end(ctx),
    }
    tr = ctx.facts.get("trace")
    if ctx.trace and tr:
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        line["breakdown"] = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
    line["device"] = device
    line["checks"] = ctx.checks
    return line


def emit(ctx: Context, line: dict) -> None:
    for note in ctx.notes:
        print(note, file=sys.stderr)
    for name, c in ctx.checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def setup_env() -> None:
    """Paths and environment, before JAX is imported."""
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
