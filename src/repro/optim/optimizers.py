"""Optimizers (optax-free, pjit-friendly pure transformations).

AdamW for everything that fits; Adafactor (factored second moments) for
the trillion-parameter archs whose Adam state exceeds the fleet's HBM
(DESIGN.md §4).  All state lives in a pytree mirroring the param tree so
FSDP sharding rules apply to it transparently.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.scopes import scope


class Optimizer(NamedTuple):
    init: Callable[[Any], Any]
    # (grads, state, params) -> (new_params, new_state, metrics)
    update: Callable[[Any, Any, Any], Tuple[Any, Any, Dict]]
    # param PartitionSpec tree -> state PartitionSpec tree
    state_specs: Callable[[Any], Any]


def _global_norm(tree) -> jnp.ndarray:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def clip_by_global_norm(tree, max_norm: float):
    gn = _global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
    return jax.tree.map(lambda x: (x.astype(jnp.float32) * scale
                                   ).astype(x.dtype), tree), gn


# ------------------------------------------------------------ schedules
def cosine_schedule(peak_lr: float, warmup: int, total: int,
                    floor: float = 0.1) -> Callable:
    def lr(step):
        step = step.astype(jnp.float32) if hasattr(step, "astype") else float(step)
        warm = peak_lr * jnp.minimum(1.0, step / max(1, warmup))
        frac = jnp.clip((step - warmup) / max(1, total - warmup), 0.0, 1.0)
        cos = peak_lr * (floor + (1 - floor) * 0.5 *
                         (1 + jnp.cos(jnp.pi * frac)))
        return jnp.where(step < warmup, warm, cos)
    return lr


def constant_schedule(lr_val: float) -> Callable:
    return lambda step: jnp.asarray(lr_val, jnp.float32)


# ------------------------------------------------------------ adamw
def adamw(lr: Callable, b1: float = 0.9, b2: float = 0.95,
          eps: float = 1e-8, weight_decay: float = 0.1,
          clip_norm: Optional[float] = 1.0) -> Optimizer:
    def init(params):
        zeros = lambda: jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        return {"mu": zeros(), "nu": zeros(),
                "step": jnp.zeros((), jnp.int32)}

    @scope("optimizer")
    def update(grads, state, params):
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        if clip_norm is not None:
            grads, gn = clip_by_global_norm(grads, clip_norm)
        else:
            gn = _global_norm(grads)
        step = state["step"] + 1
        t = step.astype(jnp.float32)
        lr_t = lr(step)
        bc1 = 1 - b1 ** t
        bc2 = 1 - b2 ** t
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g,
                          state["mu"], grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g),
                          state["nu"], grads)
        def upd(p, m, v):
            u = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            u = u + weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr_t * u).astype(p.dtype)
        new_p = jax.tree.map(upd, params, mu, nu)
        return (new_p, {"mu": mu, "nu": nu, "step": step},
                {"grad_norm": gn, "lr": lr_t})

    def state_specs(param_specs, param_shapes=None):
        from jax.sharding import PartitionSpec as P
        return {"mu": param_specs, "nu": param_specs, "step": P()}

    return Optimizer(init, update, state_specs)


# ------------------------------------------------------------ adafactor
def adafactor(lr: Callable, eps: float = 1e-30, clip_threshold: float = 1.0,
              weight_decay: float = 0.0, min_dim: int = 64) -> Optimizer:
    """Factored second moments over the last two dims of big matrices."""
    def _factored(p):
        return p.ndim >= 2 and p.shape[-1] >= min_dim and p.shape[-2] >= min_dim

    def init(params):
        def mk(p):
            if _factored(p):
                return {"vr": jnp.zeros(p.shape[:-1], jnp.float32),
                        "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:],
                                        jnp.float32)}
            return {"v": jnp.zeros(p.shape, jnp.float32)}
        return {"fac": jax.tree.map(mk, params,
                                    is_leaf=lambda x: hasattr(x, "shape")),
                "step": jnp.zeros((), jnp.int32)}

    @scope("optimizer")
    def update(grads, state, params):
        step = state["step"] + 1
        t = step.astype(jnp.float32)
        lr_t = lr(step)
        beta2 = 1.0 - t ** -0.8

        def upd(p, g, s):
            g = g.astype(jnp.float32)
            g2 = jnp.square(g) + eps
            if _factored(p):
                vr = beta2 * s["vr"] + (1 - beta2) * jnp.mean(g2, axis=-1)
                vc = beta2 * s["vc"] + (1 - beta2) * jnp.mean(g2, axis=-2)
                denom = jnp.maximum(jnp.mean(vr, axis=-1, keepdims=True),
                                    eps)[..., None]
                u = g * jax.lax.rsqrt(vr[..., None] / denom) \
                      * jax.lax.rsqrt(vc[..., None, :])
                new_s = {"vr": vr, "vc": vc}
            else:
                v = beta2 * s["v"] + (1 - beta2) * g2
                u = g * jax.lax.rsqrt(v)
                new_s = {"v": v}
            rms = jnp.sqrt(jnp.mean(jnp.square(u)) + 1e-12)
            u = u / jnp.maximum(1.0, rms / clip_threshold)
            if weight_decay:
                u = u + weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr_t * u).astype(p.dtype), new_s

        flat_p, tdef = jax.tree.flatten(params)
        flat_g = tdef.flatten_up_to(grads)
        flat_s = tdef.flatten_up_to(state["fac"])
        outs = [upd(p, g, s) for p, g, s in zip(flat_p, flat_g, flat_s)]
        new_p = jax.tree.unflatten(tdef, [o[0] for o in outs])
        new_fac = jax.tree.unflatten(tdef, [o[1] for o in outs])
        return (new_p, {"fac": new_fac, "step": step},
                {"grad_norm": _global_norm(grads), "lr": lr_t})

    def state_specs(param_specs, param_shapes):
        from jax.sharding import PartitionSpec as P

        def mk(spec, shp):
            spec = tuple(spec) + (None,) * (len(shp.shape) - len(tuple(spec)))
            if _factored(shp):
                # vr drops the last dim, vc drops the second-to-last
                return {"vr": P(*spec[:-1]),
                        "vc": P(*(tuple(spec[:-2]) + (spec[-1],)))}
            return {"v": P(*spec)}

        return {"fac": jax.tree.map(
            mk, param_specs, param_shapes,
            is_leaf=lambda s: isinstance(s, P)), "step": P()}

    return Optimizer(init, update, state_specs)


def make_optimizer(name: str, lr: Callable = None, **kw) -> Optimizer:
    lr = lr or cosine_schedule(3e-4, 100, 10_000)
    if name == "adamw":
        return adamw(lr, **kw)
    if name == "adafactor":
        return adafactor(lr, **kw)
    raise ValueError(f"unknown optimizer {name!r}")
