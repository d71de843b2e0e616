"""Production meshes (assignment-mandated shapes).

single pod : (16, 16)      axes ("data", "model")      = 256 chips
multi-pod  : (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

Defined as functions so importing this module never touches jax device
state (the dry-run forces 512 host devices; tests see 1).
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType


def _axis_types(n: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, found {len(devices)} — run via "
            "launch/dryrun.py (which forces XLA_FLAGS host device count) or on a pod."
        )
    return jax.make_mesh(
        shape, axes, devices=devices[:need], **_axis_types(len(axes))
    )


def make_test_mesh(shape=(2, 2), axes=("data", "model")) -> jax.sharding.Mesh:
    need = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(f"test mesh {shape} needs {need} devices")
    return jax.make_mesh(
        shape, axes, devices=devices[:need], **_axis_types(len(axes))
    )
