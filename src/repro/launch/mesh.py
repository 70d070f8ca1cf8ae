"""Production mesh builders (DESIGN.md §5, §14).

Functions, not module constants — importing this module never touches jax
device state.  The dry-run (and only the dry-run) forces 512 host devices.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def host_mesh(n_shards: int):
    """1-D ``("data",)`` mesh over the first ``n_shards`` local devices.

    Used by the sharded walk image: devices come from ``jax.devices()``
    so forced host platforms (``--xla_force_host_platform_device_count``)
    work the same as real accelerators.
    """
    devs = jax.devices()
    if len(devs) < n_shards:
        raise ValueError(
            f"host_mesh: need {n_shards} devices, have {len(devs)}"
        )
    import numpy as np

    return jax.sharding.Mesh(np.asarray(devs[:n_shards]), ("data",))


def _make_mesh(shape: tuple, axes: tuple):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; multi-pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """Axes that carry the batch/vertex dimension (pod folds into data)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def make_mesh_like(shape: tuple, axes: tuple):
    """Elastic re-mesh helper: arbitrary (shape, axes) from survivors."""
    return _make_mesh(shape, axes)
