"""Mixture-of-Experts FFN with expert parallelism.

Three execution paths (chosen per workload shape, DESIGN.md §5):
  * ``ep_alltoall`` — training/prefill: tokens seq-sharded over the model
    axis, capacity-based dispatch, ``all_to_all`` to expert shards and
    back (the paper's "shuffle").  The wire dtype is the ``comm_codec``
    knob (spark.io.compression.codec analogue).
  * ``ep_gather`` — decode (few tokens): replicated dispatch, expert-
    sharded FFN, ``all_gather`` combine (no all-to-all for tiny T).
  * ``grouped`` — no ``model`` axis above 1: every expert held here,
    dropless; the token x top-k assignments sorted by expert and the
    three expert products run as grouped matmuls over the sorted rows
    (``jax.lax.ragged_dot``), so the work follows the rows routed.

``_dense_moe`` (a per-slot gather of each chosen expert's weights) is
the exact oracle the tests hold the other paths to; nothing runs it.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.params import TunableConfig
from repro.models import layers as L
from repro.models.scopes import scope


def moe_spec(cfg) -> Dict[str, L.PSpec]:
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": L.PSpec((d, E), ("embed", "expert"), 0.02),
        "wg": L.PSpec((E, d, ff), ("expert", "embed", "mlp")),
        "wu": L.PSpec((E, d, ff), ("expert", "embed", "mlp")),
        "wd": L.PSpec((E, ff, d), ("expert", "mlp", "embed")),
    }


def _route(xt, router_w, cfg):
    """xt: (T,d) -> (gate_vals (T,k), gate_idx (T,k), aux): the top-k of
    the float32 softmax over all experts, renormalized to sum to one
    where ``cfg.norm_topk_prob``."""
    logits = jnp.dot(xt, router_w, preferred_element_type=jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    gv, gi = jax.lax.top_k(gates, cfg.top_k)
    if cfg.norm_topk_prob:
        gv = gv / jnp.maximum(jnp.sum(gv, -1, keepdims=True), 1e-9)
    # load-balancing aux loss (Switch-style): E * sum_e f_e * p_e
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(
        jax.nn.one_hot(gi, cfg.n_experts, dtype=jnp.float32).sum(1), axis=0)
    aux = cfg.n_experts * jnp.sum(me * ce)
    return gv.astype(xt.dtype), gi, aux


def _counts(gi, E):
    """Rows routed to each of the E experts by the assignments ``gi``: a
    compare-and-sum, which a TPU runs far faster than a scatter-add whose
    every update lands on one of E slots."""
    return jnp.sum(gi.reshape(-1, 1) == jnp.arange(E), 0, dtype=jnp.int32)


def _max_load(counts):
    """The most rows routed to one expert over the mean: 1 is an even
    load, E / k all of them on one expert."""
    return jnp.max(counts) / jnp.mean(counts.astype(jnp.float32))


@scope("expert_ffn")
def _expert_ffn(tokens, wg, wu, wd, cfg, rt):
    """tokens: (E_local, C, d); weights: (E_local, ...)."""
    if cfg.mlp_act == "silu":
        h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", tokens, wg))
             * jnp.einsum("ecd,edf->ecf", tokens, wu))
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", tokens, wu))
    return jnp.einsum("ecf,efd->ecd", h, wd)


def _encode_wire(x, codec: str):
    """comm_codec knob: cast/quantize before putting bytes on the wire."""
    if codec == "int8":
        amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
        scale = jnp.maximum(amax, 1e-6) / 127.0
        q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
        return q.astype(jnp.int8), scale.astype(jnp.float32)
    return x.astype(jnp.dtype(codec)), None


def _decode_wire(x, scale, out_dtype):
    if scale is None:
        return x.astype(out_dtype)
    return (x.astype(jnp.float32) * scale).astype(out_dtype)


def _dispatch(xt, gv, gi, E, C):
    """Capacity-based dispatch.  Returns (buf (E,C,d), keep, slot, flat_e)."""
    T, d = xt.shape
    k = gv.shape[1]
    flat_e = gi.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    slot = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
    keep = slot < C
    src = jnp.repeat(xt, k, axis=0)
    buf = jnp.zeros((E, C, d), xt.dtype)
    buf = buf.at[jnp.where(keep, flat_e, E),
                 jnp.where(keep, slot, 0)].add(src * keep[:, None],
                                               mode="drop")
    return buf, keep, slot, flat_e


def _combine(back, gv, keep, slot, flat_e, T, k, d):
    g = back[jnp.where(keep, flat_e, 0), jnp.where(keep, slot, 0)]
    g = g * keep[:, None] * gv.reshape(-1)[:, None]
    return g.reshape(T, k, d).sum(axis=1)


# ---------------------------------------------------------------- paths
def _dense_moe(p, x, cfg, rt):
    """Exact (no-capacity) oracle: each top-k slot gathers its expert's
    weights per token.  x: (B,S,d) -> (y, aux)."""
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    gv, gi, aux = _route(xt, L.cast(p["router"], rt), cfg)
    outs = []
    for j in range(cfg.top_k):
        wgj = L.cast(p["wg"], rt)[gi[:, j]]
        wuj = L.cast(p["wu"], rt)[gi[:, j]]
        wdj = L.cast(p["wd"], rt)[gi[:, j]]
        if cfg.mlp_act == "silu":
            h = (jax.nn.silu(jnp.einsum("td,tdf->tf", xt, wgj))
                 * jnp.einsum("td,tdf->tf", xt, wuj))
        else:
            h = jax.nn.gelu(jnp.einsum("td,tdf->tf", xt, wuj))
        outs.append(jnp.einsum("tf,tfd->td", h, wdj) * gv[:, j:j+1])
    y = sum(outs).reshape(B, S, d)
    return y, aux


@scope("expert_ffn")
def _grouped_ffn(rows, sizes, wg, wu, wd, cfg):
    """The expert products over ``rows`` sorted by expert, ``sizes[e]``
    of them routed to expert e: grouped matmuls, no padding, no drops."""
    if cfg.mlp_act == "silu":
        h = (jax.nn.silu(jax.lax.ragged_dot(rows, wg, sizes))
             * jax.lax.ragged_dot(rows, wu, sizes))
    else:
        h = jax.nn.gelu(jax.lax.ragged_dot(rows, wu, sizes))
    return jax.lax.ragged_dot(h, wd, sizes)


def _grouped_moe(p, x, cfg, rt):
    """Dropless MoE with every expert held here.  The T*k assignments are
    sorted by expert, their rows gathered, run through the grouped expert
    products, gathered back by the inverse permutation and summed over
    the k slots, weighted by the gate values.  x: (B,S,d) ->
    (y, aux, max_load)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    xt = x.reshape(-1, d)
    gv, gi, aux = _route(xt, L.cast(p["router"], rt), cfg)
    flat = gi.reshape(-1)                                   # (T*k,)
    order = jnp.argsort(flat, stable=True)                  # sorted -> slot
    sizes = _counts(flat, E)
    out = _grouped_ffn(xt[order // k], sizes, L.cast(p["wg"], rt),
                       L.cast(p["wu"], rt), L.cast(p["wd"], rt), cfg)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))      # slot -> sorted
    y = jnp.einsum("tkd,tk->td", out[inverse].reshape(-1, k, d), gv,
                   preferred_element_type=jnp.float32)
    return y.astype(x.dtype).reshape(B, S, d), aux, _max_load(sizes)


def _ep_paths_applicable(cfg, rules, S):
    if rules is None:
        return None
    ep = rules.model_axis_size()
    if ep <= 1 or cfg.n_experts % ep != 0:
        return None
    if S > 1 and S % ep == 0:
        return "ep_alltoall"
    return "ep_gather"


@scope("moe")
def moe_mlp(p, x, cfg, rt: TunableConfig, rules):
    """MoE FFN sub-block.  x: (B,S,d) -> (y, aux_loss, max_load), where
    max_load is the most rows routed to one expert over the mean."""
    path = _ep_paths_applicable(cfg, rules, x.shape[1])
    if path is None:
        return _grouped_moe(p, x, cfg, rt)

    mesh = rules.mesh
    ep = rules.model_axis_size()
    E, k, d = cfg.n_experts, cfg.top_k, cfg.d_model
    B, S, _ = x.shape
    batch_axes = rules.batch_axes
    dp = rules.data_axis_size()
    manual_axes = tuple(mesh.shape.keys())
    fsdp_in_mesh = tuple(a for a in rules.fsdp_axes if a in mesh.shape) \
        if rules.fsdp else ()
    comp = L.dt(rt)

    def gather_w(w):
        """FSDP all-gather of an expert weight's embed dim inside shard_map."""
        for a in fsdp_in_mesh:
            w = jax.lax.all_gather(w, a, axis=1, tiled=True)
        return w

    if path == "ep_alltoall":
        B_local = B // dp
        S_local = S // ep
        T = B_local * S_local
        C = max(1, int(math.ceil(T * k * cfg.capacity_factor / E)))

        def body(xs, rw, wg, wu, wd):
            xt = xs.reshape(T, d)
            gv, gi, aux = _route(xt, rw, cfg)
            buf, keep, slot, fe = _dispatch(xt, gv, gi, E, C)
            wire, scale = _encode_wire(buf, rt.comm_codec)
            recv = jax.lax.all_to_all(wire, "model", 0, 1, tiled=True)
            rscale = (jax.lax.all_to_all(scale, "model", 0, 1, tiled=True)
                      if scale is not None else None)
            toks = _decode_wire(recv, rscale, comp)
            out = _expert_ffn(toks, gather_w(wg).astype(comp),
                              gather_w(wu).astype(comp),
                              jnp.swapaxes(gather_w(
                                  jnp.swapaxes(wd, 1, 2)), 1, 2).astype(comp),
                              cfg, rt)
            wire2, scale2 = _encode_wire(out, rt.comm_codec)
            back = jax.lax.all_to_all(wire2, "model", 1, 0, tiled=True)
            bscale = (jax.lax.all_to_all(scale2, "model", 1, 0, tiled=True)
                      if scale2 is not None else None)
            back = _decode_wire(back, bscale, comp)
            y = _combine(back, gv, keep, slot, fe, T, k, d)
            aux = jax.lax.pmean(aux, manual_axes)
            load = _max_load(jax.lax.psum(_counts(gi, E), manual_axes))
            return y.reshape(B_local, S_local, d), aux, load

        xspec = P(batch_axes or None, "model", None)
        f = jax.shard_map(
            body, mesh=mesh,
            in_specs=(xspec, P(None, None),
                      P("model", fsdp_in_mesh or None, None),
                      P("model", fsdp_in_mesh or None, None),
                      P("model", None, fsdp_in_mesh or None)),
            out_specs=(xspec, P(), P()), check_vma=False)
        return f(x, L.cast(p["router"], rt), p["wg"], p["wu"], p["wd"])

    # ep_gather: decode-time few-token path
    B_local = max(1, B // dp)
    T = B_local * S

    def body_g(xs, rw, wg, wu, wd):
        xt = xs.reshape(T, d)
        gv, gi, aux = _route(xt, rw, cfg)
        C = max(1, int(math.ceil(T * k * cfg.capacity_factor / E)))
        buf, keep, slot, fe = _dispatch(xt, gv, gi, E, C)
        e_local = E // ep
        ridx = jax.lax.axis_index("model")
        mine = jax.lax.dynamic_slice_in_dim(buf, ridx * e_local, e_local, 0)
        out = _expert_ffn(mine, gather_w(wg).astype(comp),
                          gather_w(wu).astype(comp),
                          jnp.swapaxes(gather_w(
                              jnp.swapaxes(wd, 1, 2)), 1, 2).astype(comp),
                          cfg, rt)
        wire, scale = _encode_wire(out, rt.comm_codec)
        full = jax.lax.all_gather(wire, "model", axis=0, tiled=True)
        fscale = (jax.lax.all_gather(scale, "model", axis=0, tiled=True)
                  if scale is not None else None)
        back = _decode_wire(full, fscale, comp)
        y = _combine(back, gv, keep, slot, fe, T, k, d)
        aux = jax.lax.pmean(aux, manual_axes)
        load = _max_load(jax.lax.psum(_counts(gi, E), manual_axes))
        return y.reshape(B_local, S, d), aux, load

    xspec = P(batch_axes or None, None, None)
    f = jax.shard_map(
        body_g, mesh=mesh,
        in_specs=(xspec, P(None, None),
                  P("model", fsdp_in_mesh or None, None),
                  P("model", fsdp_in_mesh or None, None),
                  P("model", None, fsdp_in_mesh or None)),
        out_specs=(xspec, P(), P()), check_vma=False)
    return f(x, L.cast(p["router"], rt), p["wg"], p["wu"], p["wd"])
