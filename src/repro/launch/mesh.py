"""Production meshes.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state. The dry-run launcher
sets XLA_FLAGS --xla_force_host_platform_device_count=512 *before* any jax
import; everything else (tests, benchmarks) sees the real single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the sharding rules place arrays with
    ``with_sharding_constraint``, which only accepts Auto mesh axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_test_mesh(n_devices: int | None = None, *, model: int = 1):
    """Small mesh over whatever devices exist (CPU tests)."""
    n = n_devices or len(jax.devices())
    data = n // model
    return auto_mesh((data, model), ("data", "model"))
