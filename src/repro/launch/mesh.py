"""Production mesh construction (dry-run and real launches).

A FUNCTION, not a module constant: importing this module must never touch jax
device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods when multi_pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = int(np.prod(shape))
    devs = jax.devices()
    assert len(devs) >= need, (len(devs), need)
    return jax.make_mesh(shape, axes, devices=devs[:need],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_mesh_for(num_devices: int, model_parallel: int = 1,
                  axis_names=("data", "model")):
    """Small helper for CPU tests (e.g. 8 host devices: 4×2)."""
    devs = jax.devices()[:num_devices]
    return jax.make_mesh((num_devices // model_parallel, model_parallel),
                         axis_names, devices=devs,
                         axis_types=(AxisType.Auto,) * len(axis_names))
