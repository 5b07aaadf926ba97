"""Seeded weights for a parameter tree, made on the device in one call.

The benchmark makes its own weights, so that the plain references in
``bench/configs/`` and the program under test read the same arrays and
neither depends on the program's initialiser.  The tree's shapes and
logical axis names come from the program (``Model.param_shapes`` and
``Model.logical``); the values come from here:

* ``embedding``: normal, std 0.02;
* norm scales (``ln``, ``ln1``, ``ln2``, ``final_norm``, ``gln``) and the
  Mamba2 skip ``D``: ones;
* Mamba2 ``A_log``: log of U[1, 16], and ``dt_bias``: the inverse
  softplus of a step size log-uniform in [time_step_min, time_step_max],
  as in the Mamba2 initialisation;
* the depthwise ``conv``: U[-1/2, 1/2] (fan-in 4);
* every other matrix: normal with std 1/sqrt(fan-in), where the fan-in
  is the product of the non-layer dims before the last when the last
  dim is the residual width (``embed``), and the first non-layer dim
  otherwise.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_ONES = {"ln", "ln1", "ln2", "final_norm", "gln", "D"}


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def _fan_in(shape, logical) -> int:
    dims = [(n, s) for n, s in zip(logical, shape) if n != "layers"]
    if len(dims) == 1:
        return dims[0][1]
    if dims[-1][0] == "embed":
        return math.prod(s for _, s in dims[:-1])
    return dims[0][1]


def _leaf(key, name, shape, logical, dt_range):
    if name == "embedding":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if name in _ONES:
        return jnp.ones(shape, jnp.float32)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        lo, hi = (math.log(v) for v in dt_range)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return dt + jnp.log(-jnp.expm1(-dt))          # inverse softplus
    if name == "conv":
        return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
    std = 1.0 / math.sqrt(_fan_in(shape, logical))
    return std * jax.random.normal(key, shape, jnp.float32)


def make(shapes, logical, seed: int, dt_range=(1e-3, 1e-1),
         shardings=None):
    """Weights for ``shapes`` (a tree of ShapeDtypeStructs) from ``seed``,
    each cast to its leaf's dtype, in one jitted call.  ``logical`` is the
    matching tree of logical axis-name tuples."""
    paths = jax.tree_util.tree_flatten_with_path(shapes)[0]
    treedef = jax.tree.structure(shapes)
    lg = treedef.flatten_up_to(logical)

    def build(key):
        keys = jax.random.split(key, len(paths))
        out = [_leaf(k, _leaf_name(p), s.shape, g, dt_range).astype(s.dtype)
               for k, (p, s), g in zip(keys, paths, lg)]
        return jax.tree.unflatten(treedef, out)

    # the key is an argument, so one compiled program serves every seed
    return jax.jit(build, out_shardings=shardings)(seed_key(seed))


def seed_key(seed: int):
    """A PRNG key from any whole number, folded into 32-bit words so that
    seeds past 2**32 stay distinct."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
