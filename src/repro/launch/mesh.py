"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state.  The single-pod mesh is
(data=16, model=16) = 256 chips (one TPU v5e pod in this work's target);
the multi-pod mesh adds a leading DCN "pod" axis.  The pod axis composes
with "data" for batch/FSDP sharding, so the same configs scale to any
pod count (DESIGN.md §5).

:func:`placeholder_production_mesh` lays the same mesh over the CPU
backend's placeholder devices (``--xla_force_host_platform_device_count``)
for compile-only work, whatever the default backend is: on a chip host
the default backend has 1 or 4 devices, not 256.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False,
                         devices=None) -> Mesh:
    """The production mesh over ``devices`` (default: the default
    backend's devices)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def placeholder_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The production mesh over the CPU backend's placeholder devices."""
    return make_production_mesh(multi_pod=multi_pod,
                                devices=jax.devices("cpu"))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Arbitrary mesh (elastic remesh, tests)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: Optional[int] = None) -> Mesh:
    """Best-effort mesh over whatever devices exist (CPU tests, examples).

    Factors the local device count into (data, model)."""
    n = len(jax.devices())
    if model is None:
        model = 1
        for cand in (16, 8, 4, 2):
            if n % cand == 0 and n >= cand:
                model = cand
                break
    data = n // model
    devs = np.array(jax.devices()[:data * model]).reshape(data, model)
    return Mesh(devs, ("data", "model"), axis_types=(AxisType.Auto,) * 2)
