"""Profiler phases: ``jax.profiler`` traces of Session phases (DESIGN.md §14.4).

:func:`profile_phase` is a ``jax.profiler.trace`` context the Session
wraps around its solve/serve phases at ``obs.level="profile"``, writing
the device trace under ``results/<run_id>/telemetry/profile/``.  The
run's spans reach that trace through :class:`~repro.obs.Telemetry`
itself: every recorded span opens a ``repro.<kind>`` host annotation, on
the clock of the device's operations.
"""

from __future__ import annotations

import contextlib
import os


@contextlib.contextmanager
def profile_phase(telemetry, out_dir: str, phase: str):
    """``jax.profiler.trace`` around one Session phase (profile level only)."""
    if telemetry is None or not telemetry.profile_enabled:
        yield None
        return
    try:
        import jax.profiler as jprof
    except Exception:  # pragma: no cover - jax always present in repo
        yield None
        return
    trace_dir = os.path.join(out_dir, "profile", phase)
    os.makedirs(trace_dir, exist_ok=True)
    telemetry.event("profile.trace", phase=phase, dir=trace_dir)
    with jprof.trace(trace_dir):
        yield trace_dir
