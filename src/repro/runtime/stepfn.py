"""Train / serve step builders: the executable a workload cell lowers.

``build_train_step`` returns a :class:`StepBundle` carrying the jitted
function plus the abstract arguments and shardings needed to
``.lower().compile()`` it with no allocation (dry-run protocol) or to run
it for real (examples, smoke tests).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.params import TunableConfig
from repro.models import layers as L
from repro.models.model import Model, batch_logical, build_model, input_specs
from repro.optim.optimizers import Optimizer, make_optimizer
from repro.runtime import gradsync
from repro.runtime.loops import scan_layers
from repro.runtime.sharding import ShardingRules


def build_rules(mesh: Mesh, cfg: ArchConfig, rt: TunableConfig) -> ShardingRules:
    return ShardingRules(mesh=mesh, strategy=rt.shard_strategy,
                         fsdp_axes=cfg.fsdp_axes,
                         attn_tp_fallback=rt.attn_tp_fallback)


def cast_params_for_compute(params, rt: TunableConfig):
    """Cast master weights to the compute dtype ONCE, before any use —
    so FSDP all-gathers move compute-dtype bytes, not f32 masters
    (standard practice; halves the param-gather collective term under
    bf16).  Gradients still accumulate in f32 through the cast."""
    comp = jnp.dtype(rt.compute_dtype)
    return jax.tree.map(
        lambda x: x.astype(comp)
        if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != comp
        else x, params)


@dataclasses.dataclass
class StepBundle:
    """Everything needed to lower/compile/run one workload cell."""
    fn: Any                       # jitted step function
    args: Tuple                   # abstract ShapeDtypeStructs (lowering order)
    rules: ShardingRules
    kind: str                     # train | prefill | decode
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # shardings of ``args``: place concrete arguments with them before
    # the first call (an uncommitted array misses the jit cache once
    # its committed successor comes back, and the step compiles twice)
    in_shardings: Tuple = ()

    def lower(self):
        return self.fn.lower(*self.args)


def _param_shardings(model: Model, rules: ShardingRules):
    shapes = model.param_shapes()
    logical = model.logical()
    specs = jax.tree.map(
        lambda lg, sd: rules.param_spec(lg, sd.shape), logical, shapes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))
    sh = jax.tree.map(lambda s: rules.sharding(s), specs,
                      is_leaf=lambda s: isinstance(s, P))
    return shapes, specs, sh


def _batch_shardings(cfg, shape, rt, rules):
    specs = input_specs(cfg, shape, rt)
    lg = batch_logical(cfg, shape, rt)
    sh = {k: rules.sharding(rules.act_spec(lg[k], specs[k].shape))
          for k in specs}
    return specs, sh


def _cache_shardings(model: Model, batch: int, max_seq: int,
                     rt: TunableConfig, rules: ShardingRules):
    shapes, logical = model.cache_shapes(batch, max_seq, rt)
    def spec_of(lg, sd):
        return rules.sharding(rules.act_spec(lg, sd.shape))
    sh = jax.tree.map(spec_of, logical, shapes,
                      is_leaf=lambda x: isinstance(x, tuple) and all(
                          isinstance(e, (str, type(None))) for e in x))
    return shapes, sh


# ===================================================================== train
def build_train_step(cfg: ArchConfig, shape: ShapeConfig, rt: TunableConfig,
                     mesh: Mesh, optimizer: Optional[Optimizer] = None
                     ) -> StepBundle:
    model = build_model(cfg)
    rules = build_rules(mesh, cfg, rt)
    optimizer = optimizer or make_optimizer(cfg.optimizer)
    p_shapes, p_specs, p_sh = _param_shardings(model, rules)
    b_shapes, b_sh = _batch_shardings(cfg, shape, rt, rules)
    o_shapes = jax.eval_shape(optimizer.init, p_shapes)
    o_specs = optimizer.state_specs(p_specs, p_shapes)
    o_sh = jax.tree.map(lambda s: rules.sharding(s), o_specs,
                        is_leaf=lambda s: isinstance(s, P))

    explicit = (gradsync.explicit_applicable(cfg.family, rt)
                and mesh.shape.get("data", 1) > 1)
    # int8+error-feedback gradient compression: dp strategy only (every
    # leaf replicated -> one fused bucket); falls back to bf16 otherwise
    ef = (explicit and rt.grad_comm_dtype == "int8_ef"
          and rt.shard_strategy == "dp")
    if rt.grad_comm_dtype == "int8_ef" and not ef:
        rt = rt.replace(grad_comm_dtype="bfloat16")
    m = rt.microbatches

    def split_mb(batch):
        return jax.tree.map(
            lambda x: x.reshape((m, x.shape[0] // m) + x.shape[1:]), batch)

    if explicit:
        # ---- full-manual shard_map over every mesh axis; the model runs
        # on local shards with rules=None; grad collectives are explicit.
        data_axes = rules.batch_axes
        axis_sizes = {a: mesh.shape[a] for a in data_axes}
        n_shards = rules.data_axis_size()
        n_total = int(sum(int(np.prod(s.shape)) if s.shape else 1
                          for s in jax.tree.leaves(p_shapes))) if ef else 0

        def local_grads(params_local, batch_local, ef_local):
            # cast before the gather: wire bytes at compute dtype
            full = gradsync.gather_params(
                cast_params_for_compute(params_local, rt), p_specs)
            def loss_of(p, b):
                return model.loss_fn(p, b, rt, None)[0]
            if m == 1:
                loss, g = jax.value_and_grad(loss_of)(full, batch_local)
            else:
                def mb_step(acc, mb):
                    l, g = jax.value_and_grad(loss_of)(full, mb)
                    return jax.tree.map(jnp.add, acc,
                                        (l, jax.tree.map(
                                            lambda x: x.astype(jnp.float32),
                                            g))), None
                zero = (jnp.zeros((), jnp.float32),
                        jax.tree.map(lambda s: jnp.zeros(s.shape,
                                                         jnp.float32), full))
                (loss, g), _ = scan_layers(mb_step, zero,
                                           split_mb(batch_local),
                                           unroll=rt.unroll_layers)
                loss, g = loss / m, jax.tree.map(lambda x: x / m, g)
            scale = 1.0 / n_shards
            if ef:
                g, ef_local = gradsync.reduce_grads_int8_ef(
                    g, rt, data_axes, axis_sizes, ef_local, scale)
            else:
                g = gradsync.reduce_grads(g, p_specs, rt, data_axes, scale)
            loss = jax.lax.pmean(loss, data_axes)
            return loss, g, ef_local

        # under dp/fsdp, param specs reference only data/pod axes, so they
        # are valid manual specs as-is; batch is manual over the data axes
        in_b_specs = {k: P(*([data_axes] + [None] * (len(b_shapes[k].shape)
                                                     - 1)))
                      for k in b_shapes}
        ef_spec = P(data_axes, None)
        sm = jax.shard_map(local_grads, mesh=mesh,
                           in_specs=(p_specs, in_b_specs, ef_spec),
                           out_specs=(P(), p_specs, ef_spec),
                           check_vma=False)

        if ef:
            # augment the optimizer state with the per-shard EF residual
            o_shapes = {"opt": o_shapes,
                        "ef": jax.ShapeDtypeStruct((n_shards, n_total),
                                                   jnp.float32)}
            o_sh = {"opt": o_sh,
                    "ef": rules.sharding(P(data_axes, None))}

            def step(params, opt_state, batch):
                loss, grads, ef_new = sm(params, batch, opt_state["ef"])
                new_p, new_s, met = optimizer.update(grads,
                                                     opt_state["opt"],
                                                     params)
                return new_p, {"opt": new_s, "ef": ef_new}, dict(met,
                                                                 loss=loss)
        else:
            def step(params, opt_state, batch):
                dummy = jnp.zeros((n_shards, 1), jnp.float32)
                loss, grads, _ = sm(params, batch, dummy)
                new_p, new_s, met = optimizer.update(grads, opt_state,
                                                     params)
                return new_p, new_s, dict(met, loss=loss)

    else:
        # ---- auto-SPMD path: XLA schedules all collectives
        def loss_of(p, b):
            loss, _ = model.loss_fn(cast_params_for_compute(p, rt), b, rt,
                                    rules)
            return loss

        def step(params, opt_state, batch):
            if m == 1:
                loss, grads = jax.value_and_grad(loss_of)(params, batch)
            else:
                def mb_step(acc, mb):
                    l, g = jax.value_and_grad(loss_of)(params, mb)
                    g = jax.tree.map(lambda x: x.astype(jnp.float32), g)
                    return jax.tree.map(jnp.add, acc, (l, g)), None
                zero = (jnp.zeros((), jnp.float32),
                        jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32),
                                     params))
                (loss, grads), _ = scan_layers(mb_step, zero, split_mb(batch),
                                               unroll=rt.unroll_layers)
                loss = loss / m
                grads = jax.tree.map(lambda x: x / m, grads)
            new_p, new_s, met = optimizer.update(grads, opt_state, params)
            met = dict(met, loss=loss)
            return new_p, new_s, met

    donate = (0, 1) if rt.donate_buffers else ()
    jitted = jax.jit(step,
                     in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, None),
                     donate_argnums=donate)
    args = (p_shapes, o_shapes, b_shapes)
    return StepBundle(jitted, args, rules, "train",
                      notes={"explicit_comm": explicit,
                             "sharding_notes": list(rules.notes)},
                      in_shardings=(p_sh, o_sh, b_sh))


# ===================================================================== serve
def build_prefill_step(cfg: ArchConfig, shape: ShapeConfig,
                       rt: TunableConfig, mesh: Mesh) -> StepBundle:
    model = build_model(cfg)
    rules = build_rules(mesh, cfg, rt)
    p_shapes, p_specs, p_sh = _param_shardings(model, rules)
    b_shapes, b_sh = _batch_shardings(cfg, shape, rt, rules)

    def step(params, batch):
        return model.prefill_fn(params, batch, rt, rules,
                                max_seq=shape.seq_len)

    jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
    return StepBundle(jitted, (p_shapes, b_shapes), rules, "prefill",
                      notes={"sharding_notes": list(rules.notes)},
                      in_shardings=(p_sh, b_sh))


def build_decode_step(cfg: ArchConfig, shape: ShapeConfig,
                      rt: TunableConfig, mesh: Mesh) -> StepBundle:
    """One-token serve_step against a seq_len-deep cache."""
    model = build_model(cfg)
    rules = build_rules(mesh, cfg, rt)
    p_shapes, p_specs, p_sh = _param_shardings(model, rules)
    c_shapes, c_sh = _cache_shardings(model, shape.global_batch,
                                      shape.seq_len, rt, rules)
    t_shape = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    t_sh = rules.sharding(rules.act_spec(("batch", None), t_shape.shape))

    def step(params, cache, tokens):
        return model.decode_fn(params, cache, tokens, rt, rules)

    donate = (1,) if rt.donate_buffers else ()
    jitted = jax.jit(step, in_shardings=(p_sh, c_sh, t_sh),
                     out_shardings=(None, c_sh),
                     donate_argnums=donate)
    return StepBundle(jitted, (p_shapes, c_shapes, t_shape), rules, "decode",
                      notes={"sharding_notes": list(rules.notes)},
                      in_shardings=(p_sh, c_sh, t_sh))


def build_step(cfg: ArchConfig, shape: ShapeConfig, rt: TunableConfig,
               mesh: Mesh) -> StepBundle:
    """Dispatch on the cell kind (train_4k -> train, decode_* -> decode...)."""
    if shape.kind == "train":
        return build_train_step(cfg, shape, rt, mesh)
    if shape.kind == "prefill":
        return build_prefill_step(cfg, shape, rt, mesh)
    return build_decode_step(cfg, shape, rt, mesh)
