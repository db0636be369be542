"""Production mesh builders.

A FUNCTION (not module-level constant) so importing never touches jax device
state.  Single-pod: (16, 16) = 256 chips, axes ("data", "model").  Multi-pod:
(2, 16, 16) = 512 chips, axes ("pod", "data", "model") — "pod" is the
DCN-class axis used for cross-pod data parallelism (or pipeline stages).
Every axis is Auto: GSPMD places what the sharding rules leave open.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 1
    for s in shape:
        need *= s
    devices = jax.devices()
    if len(devices) > need:       # single-pod mesh on the 512-device host
        devices = devices[:need]
    return Mesh(np.asarray(devices).reshape(shape), axes,
                axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for forced-multi-device unit tests."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever devices exist locally, as a 1-D data mesh (examples/CI)."""
    n = len(jax.devices())
    return jax.make_mesh((n,), ("data",), axis_types=(AxisType.Auto,))
