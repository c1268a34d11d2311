"""Production meshes (brief: 16x16 single-pod, 2x16x16 multi-pod).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
import to get 512 placeholder devices.
"""
from __future__ import annotations

import jax

# chip of the production pods, as ``jax.Device.device_kind`` names it
# (the dry-run's placeholder devices are CPUs)
PRODUCTION_DEVICE_KIND = "TPU v5 lite"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, *,
                    multi_pod: bool = False):
    """Small mesh for CPU tests (requires
    --xla_force_host_platform_device_count set accordingly)."""
    if multi_pod:
        return jax.make_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return jax.make_mesh((n_data, n_model), ("data", "model"))


def batch_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
