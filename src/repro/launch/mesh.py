"""Production meshes. A function (not a module constant) so importing never
touches jax device state. Single pod: 16x16 = 256 chips (data x model).
Multi-pod: 2x16x16 = 512 chips; the 'pod' axis crosses DCN and is used only
for data parallelism (gradient all-reduce) — parameters never shard over it.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh", "make_serving_mesh"]

_AUTO2 = (AxisType.Auto,) * 2


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (CPU tests)."""
    return jax.make_mesh((data, model), ("data", "model"), axis_types=_AUTO2)


def make_serving_mesh(model: int = 1, devices=None):
    """``(data=1, model)`` mesh over an *explicit* device slice.

    Unlike ``make_host_mesh`` this never reaches for the global device
    list when a slice is given, so a replica router can pin each engine
    replica to its own disjoint devices. The data axis exists (size 1)
    because the forward passes' sharding constraints name both axes.
    """
    import numpy as np
    from jax.sharding import Mesh
    devs = list(devices) if devices is not None else jax.devices()[:model]
    if len(devs) != model:
        raise ValueError(
            f"make_serving_mesh(model={model}) needs exactly {model} "
            f"devices, got {len(devs)}")
    arr = np.empty((1, model), dtype=object)
    arr[0, :] = devs
    return Mesh(arr, ("data", "model"), axis_types=_AUTO2)
