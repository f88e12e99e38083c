"""Production mesh definition (functions only — importing this module never
touches jax device state).

Single pod: 16x16 = 256 chips, axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model); the pod axis
composes with data for DP/FSDP (batch and parameter sharding span pod*data).
"""
from __future__ import annotations

from typing import Tuple

import jax


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the recon engine's gathers and
    sharding constraints are written for GSPMD propagation, which jax's
    default Explicit axes reject."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(*, multi_pod: bool = False):
    """Tiny mesh for CI-scale sharding tests (8 virtual devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_flat_mesh(n_devices: int):
    """All-device (n, 1) data-parallel mesh — the fallback for benchmarks /
    smoke runs on hosts that don't expose the debug mesh's 8 devices."""
    return _make_mesh((n_devices, 1), ("data", "model"))


def dp_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes that act as data/FSDP parallel dims."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)


def axis_size(mesh, axes) -> int:
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
