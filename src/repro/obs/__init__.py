"""Observability subsystem: telemetry, metrics, profiler phases (DESIGN.md §14).

The cross-cutting layer a :class:`~repro.api.session.Session` threads
through solve/serve/bench/dryrun when the spec carries an ``obs``
section:

* :mod:`repro.obs.telemetry` — structured spans/events + the level gate;
  recorded spans also land in a ``jax.profiler`` trace as ``repro.<kind>``;
* :mod:`repro.obs.metrics`   — counters, gauges, log-bucket histograms;
* :mod:`repro.obs.schema`    — JSONL schema validation (CI + ``--validate``);
* :mod:`repro.obs.export`    — OpenMetrics text snapshots (render/parse/lint);
* :mod:`repro.obs.slo`       — SLO watchdog + serve degradation ladder;
* :mod:`repro.obs.solve`     — the observed per-superstep solve loop;
* :mod:`repro.obs.profiler`  — ``jax.profiler`` phase traces;
* :mod:`repro.obs.summary`   — digest + text rendering for ``repro obs``.

Import-light on purpose: importing :mod:`repro.obs` must not pull jax
(the profiler imports it lazily), so the CLI can validate telemetry
artifacts without touching an accelerator runtime.
"""

from repro.obs.metrics import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    bucket_index,
)
from repro.obs.export import lint_openmetrics, parse_openmetrics, render_openmetrics
from repro.obs.schema import TelemetryError, validate_dir, validate_file, validate_line
from repro.obs.slo import ServeDegradation, SLOWatchdog
from repro.obs.telemetry import LEVELS, SCHEMA, Span, Telemetry

__all__ = [
    "DEFAULT_BUCKETS",
    "Counter",
    "Gauge",
    "Histogram",
    "LEVELS",
    "MetricsRegistry",
    "SCHEMA",
    "SLOWatchdog",
    "ServeDegradation",
    "Span",
    "Telemetry",
    "TelemetryError",
    "bucket_index",
    "lint_openmetrics",
    "parse_openmetrics",
    "render_openmetrics",
    "validate_dir",
    "validate_file",
    "validate_line",
]
