"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — smoke tests must keep seeing the
single real CPU device; only ``dryrun.py`` forces 512 placeholder devices.
"""
from __future__ import annotations

import math

import jax


def _mesh(shape, axes):
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devs)} — run under "
            f"dryrun.py (which sets xla_force_host_platform_device_count)")
    return jax.make_mesh(shape, axes, devices=devs[:n],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (TPU v5e pod slice); 2 pods multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_smoke_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for subprocess sharding tests (8 fake devices)."""
    return _mesh((n_data, n_model), ("data", "model"))


def mesh_context(mesh):
    """Context manager installing ``mesh`` as the ambient mesh
    (``jax.set_mesh``; ``sharding/specs._current_mesh`` reads it back)."""
    return jax.set_mesh(mesh)
