"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state). Single pod: 16x16 = 256 chips ("data","model");
multi-pod: 2x16x16 = 512 chips ("pod","data","model").
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 4) -> Mesh:
    """Small mesh over host devices for multi-device tests."""
    return _make_mesh((data, model), ("data", "model"))


def make_serving_mesh(device_count: int) -> Mesh:
    """1-D ("data",) mesh over the first ``device_count`` devices — the
    mesh the serving backend pool's data-parallel embed lanes span.
    Asking for more devices than jax exposes is an error, never a
    quietly narrower mesh."""
    import numpy as np
    avail = jax.devices()
    n = int(device_count)
    if not 1 <= n <= len(avail):
        raise ValueError(
            f"device_count={n}, but jax exposes {len(avail)} "
            f"{avail[0].platform} device(s)")
    return Mesh(np.array(avail[:n]), ("data",))


def dp_size(mesh: Mesh) -> int:
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n


def tp_size(mesh: Mesh) -> int:
    return mesh.shape["model"]
