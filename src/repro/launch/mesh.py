"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state. The dry-run process
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; normal test/bench processes see the 1 real CPU device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Target: TPU v5e pods. Single pod = 16x16 (256 chips); two pods = 512."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(n_data: int, n_model: int, devices=None) -> Mesh:
    """Small mesh over explicit devices (tests, elastic remesh demos)."""
    import numpy as np

    devices = list(devices if devices is not None else jax.devices())
    need = n_data * n_model
    assert len(devices) >= need, (len(devices), need)
    arr = np.array(devices[:need]).reshape(n_data, n_model)
    return Mesh(arr, ("data", "model"), axis_types=(AxisType.Auto, AxisType.Auto))
