"""Production mesh construction.

Importing this module never touches jax device state; meshes are built
by functions only (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes: ("data", "model") / ("pod", "data", "model").  "pod" is the
    cross-pod data/FSDP axis (DCN-connected in production).
    """
    import jax
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have {len(devices)}; "
            f"set XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            f"BEFORE importing jax (see launch/dryrun.py)")
    dev = np.asarray(devices[:n]).reshape(shape)
    return jax.sharding.Mesh(
        dev, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(num_sites: int = 1, axis: str = "sites"):
    """Small mesh over whatever devices exist (tests, CPU examples)."""
    import jax
    devices = jax.devices()[:num_sites]
    return jax.sharding.Mesh(np.asarray(devices), (axis,),
                             axis_types=(jax.sharding.AxisType.Auto,))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
