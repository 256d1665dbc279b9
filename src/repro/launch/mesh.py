"""Production mesh builders.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import to fabricate the device pool; smoke tests and benches never do, and
see the single real CPU device.
"""
from __future__ import annotations

from repro.parallel.hints import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(shape, axes):
    """Small mesh over whatever devices exist (tests, local runs)."""
    return auto_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """The batch/data axes of a mesh: ('pod','data') when multi-pod."""
    names = mesh.axis_names
    return tuple(n for n in names if n in ("pod", "data"))
