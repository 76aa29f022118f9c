"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state. The dry-run entrypoint sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 BEFORE importing jax.

Every mesh here has Auto axes: the model code shards through GSPMD
propagation and ``with_sharding_constraint`` hooks (models/shard_hooks.py),
and the trainer's per-slice submeshes (``jax.sharding.Mesh``) are Auto too,
so arrays from both can meet in one computation.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (v5e), optionally 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(devices: int = 8):
    """Small mesh for CPU tests (requires >= `devices` jax devices)."""
    return _auto_mesh((devices // 2, 2), ("data", "model"))


def make_data_mesh(num_devices: int | None = None):
    """1-D data-parallel mesh over the available devices — the default mesh
    for :class:`repro.api.backend.MeshBackend` (degenerates gracefully to a
    single CPU device in the test container)."""
    n = len(jax.devices()) if num_devices is None else num_devices
    return _auto_mesh((n,), ("data",))


def data_axes(mesh) -> tuple[str, ...]:
    """Axes carrying the batch dimension."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis_size(mesh) -> int:
    return mesh.shape["model"]
