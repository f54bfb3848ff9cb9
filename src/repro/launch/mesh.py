"""Production meshes.

Defined as functions (never module-level constants) so importing this
module never touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import to obtain 512 placeholder devices.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh_for"]


def _axis_types_kwargs(n: int) -> dict:
    """Auto (compiler-propagated) sharding on every mesh axis."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips, axes (data, model).
    Multi-pod: 2x16x16 = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_types_kwargs(len(axes)))


def make_mesh_for(n_devices: int, model_parallel: int = 1):
    """Small test meshes on whatever devices exist (CPU smoke / unit tests)."""
    devs = jax.devices()[:n_devices]
    if len(devs) < n_devices:
        raise ValueError(f"need {n_devices} devices, have {len(devs)}")
    data = n_devices // model_parallel
    return jax.make_mesh(
        (data, model_parallel), ("data", "model"),
        devices=devs,
        **_axis_types_kwargs(2),
    )
