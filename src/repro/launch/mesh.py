"""Production meshes.

Function-scoped (importing this module never touches jax device state):
single-pod 16x16 = 256 chips ("data", "model"), multi-pod 2x16x16 = 512
chips ("pod", "data", "model"). The "pod" axis is the federated axis — one
pod per EC-node site in the paper's mapping (DESIGN.md §3).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the step functions place arrays through jit in/out
    # shardings and GSPMD propagation, not sharding-in-types
    # (``jax.make_mesh`` defaults to Explicit axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1, pods: int = 1):
    """Small mesh over however many devices exist (tests / examples)."""
    n = jax.device_count()
    data = n // (model_parallel * pods)
    if pods > 1:
        return _mesh((pods, data, model_parallel), ("pod", "data", "model"))
    return _mesh((data, model_parallel), ("data", "model"))


def batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
