"""Activation-sharding hints.

GSPMD's propagation from input shardings alone makes poor choices around
gathers (token embedding of a vocab-sharded table by batch-sharded ids) —
measured on granite train_4k it REPLICATED all activations, costing 13GB+
temp per device for a single dense layer.  The standard fix (the
MaxText/"logical axis rules" playbook) is explicit
``with_sharding_constraint`` on activations at layer boundaries.

Model code refers to LOGICAL axes; launchers register the physical mesh:

    set_ambient_mesh(mesh)        # dryrun / train driver, before tracing
    x = shard_hint(x, BATCH, None, TP)

``shard_hint`` is a no-op when no mesh is registered (unit tests, CPU
runs) and silently drops axes that don't exist in / divide the dim.
"""
from __future__ import annotations

import threading
from typing import Optional, Tuple, Union

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# logical axes
BATCH = "__batch__"      # data-parallel axes: ("pod", "data") ∩ mesh
TP = "__model__"         # tensor/expert-parallel axis: "model"

_state = threading.local()


def auto_mesh(shape, axes, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD propagates
    shardings from the inputs; ``make_mesh`` defaults to ``Explicit``).

    ``devices`` picks the devices to lay out (default: all of them).
    """
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def set_ambient_mesh(mesh: Optional[Mesh]) -> None:
    _state.mesh = mesh


def get_ambient_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def _resolve(axis, mesh) -> Optional[Union[str, Tuple[str, ...]]]:
    if axis is None:
        return None
    if axis == BATCH:
        axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        return axes or None
    if axis == TP:
        return "model" if "model" in mesh.axis_names else None
    return axis if axis in mesh.axis_names else None


def _axes_size(axes, mesh) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def shard_hint(x: jax.Array, *spec) -> jax.Array:
    mesh = get_ambient_mesh()
    if mesh is None:
        return x
    if len(spec) != x.ndim:
        raise ValueError(f"spec rank {len(spec)} != array rank {x.ndim}")
    resolved = []
    for axis, dim in zip(spec, x.shape):
        r = _resolve(axis, mesh)
        if r is not None and dim % _axes_size(r, mesh) != 0:
            r = None
        resolved.append(r)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved))
    )
