"""Elastic scaling: rebuild the mesh for a changed device count and
reshard the training state from the (mesh-agnostic) checkpoint.

Checkpoints store plain host arrays, so a job that loses (or gains) a
slice restarts with a new mesh factorization; only the data-parallel
extent changes — the model-axis extent is preserved when possible so
TP/EP layouts stay valid.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax
from jax.sharding import AxisType, Mesh

from repro.checkpoint import checkpoint as ckpt


def remesh(n_devices: int, model_axis: int,
           devices=None) -> Mesh:
    """Largest (data x model) mesh fitting n_devices, model extent fixed."""
    devices = devices if devices is not None else jax.devices()
    if n_devices > len(devices):
        raise ValueError(f"asked for {n_devices}, have {len(devices)}")
    while model_axis > 1 and n_devices % model_axis != 0:
        model_axis //= 2
    data = n_devices // model_axis
    grid = np.array(devices[:data * model_axis]).reshape(data, model_axis)
    return Mesh(grid, ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def restore_resharded(directory: str, step: int, target_tree, new_shardings):
    """Restore a checkpoint onto a different mesh (new shardings tree)."""
    return ckpt.restore(directory, step, target_tree, new_shardings)


def survivors_mesh(old_mesh: Mesh, lost: int) -> Tuple[Mesh, int]:
    """Mesh after losing ``lost`` devices (keeps model axis if possible)."""
    n = old_mesh.devices.size - lost
    model = old_mesh.shape.get("model", 1)
    new = remesh(n - (n % model) if n % model else n, model,
                 devices=list(old_mesh.devices.flatten()))
    return new, new.devices.size
