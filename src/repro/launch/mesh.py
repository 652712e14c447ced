"""Production meshes. Defined as functions so importing this module never
touches jax device state (device count is locked at first jax init —
dryrun.py sets XLA_FLAGS before importing anything)."""

from __future__ import annotations

import jax
import numpy as np


def _auto(axes):
    return (jax.sharding.AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2x16x16 = 512 across two pods. The
    ``pod`` axis is the slow-DCI dimension (DESIGN.md §8)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Mesh over the first prod(shape) devices, every axis GSPMD auto."""
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:     # topology-aware ordering when the mesh fits
        return jax.make_mesh(shape, axes, axis_types=_auto(axes))
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices, have {len(devices)} — run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512")
    arr = np.asarray(devices[:n]).reshape(shape)
    return jax.sharding.Mesh(arr, axes, axis_types=_auto(axes))
