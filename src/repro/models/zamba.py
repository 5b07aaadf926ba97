"""Zamba2-style hybrid: Mamba2 backbone + ONE shared attention block.

81 Mamba2 blocks; a single shared transformer block (attn + MLP, weights
shared) is invoked after every ``attn_every``-th Mamba2 block.  Decode
carries SSM/conv states for every Mamba2 block plus a KV cache per shared-
block invocation.  Sub-quadratic: runs the long_500k cell.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.core.params import TunableConfig
from repro.models import layers as L
from repro.models import mamba2
from repro.runtime import remat
from repro.runtime.loops import scan_layers


def _shared_spec(cfg) -> Dict[str, L.PSpec]:
    return {
        "ln1": L.rmsnorm_spec(cfg.d_model),
        "attn": L.attn_spec(cfg),
        "ln2": L.rmsnorm_spec(cfg.d_model),
        "mlp": L.mlp_spec(cfg),
    }


def _split(cfg):
    g = cfg.n_layers // cfg.attn_every        # full groups
    rem = cfg.n_layers - g * cfg.attn_every
    return g, rem


def spec(cfg) -> Dict:
    g, rem = _split(cfg)
    out = {
        "embed": L.embed_spec(cfg),
        "groups": L.stacked(g, L.stacked(cfg.attn_every, mamba2.mamba_spec(cfg))),
        "shared": _shared_spec(cfg),
        "final_norm": L.rmsnorm_spec(cfg.d_model),
    }
    if rem:
        out["rem"] = L.stacked(rem, mamba2.mamba_spec(cfg))
    return out


def _shared_block(sp, x, positions, cfg, rt, rules):
    h = L.rmsnorm(x, sp["ln1"], rt, cfg.norm_eps)
    x = x + L.attention_block(sp["attn"], h, cfg=cfg, rt=rt, rules=rules,
                              positions=positions)
    h = L.rmsnorm(x, sp["ln2"], rt, cfg.norm_eps)
    return x + L.mlp_block(sp["mlp"], h, cfg=cfg, rt=rt, rules=rules)


def forward(p, h, positions, cfg, rt: TunableConfig, rules):
    def group(x, gp):
        x = remat.from_carry(x, rt)
        def inner(xc, mp):
            return mamba2.mamba_block(mp, xc, cfg, rt, rules), None
        x, _ = scan_layers(inner, x, gp, unroll=rt.unroll_layers)
        x = _shared_block(p["shared"], x, positions, cfg, rt, rules)
        return remat.to_carry(x, rt), None
    h, _ = scan_layers(remat.wrap_layer(group, rt),
                       remat.to_carry(h, rt), p["groups"],
                       unroll=rt.unroll_layers)
    h = remat.from_carry(h, rt)
    if "rem" in p:
        def inner(xc, mp):
            return mamba2.mamba_block(mp, xc, cfg, rt, rules), None
        h, _ = scan_layers(inner, h, p["rem"], unroll=rt.unroll_layers)
    return L.rmsnorm(h, p["final_norm"], rt, cfg.norm_eps)


def loss_fn(p, batch, cfg, rt: TunableConfig, rules):
    h = L.embed(p["embed"], batch["tokens"], rt)
    if rules is not None:
        h = rules.constrain(h, "batch", None, None)
    B, S, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    h = forward(p, h, positions, cfg, rt, rules)
    logits = L.unembed(p["embed"], h, cfg, rt, rules)
    return L.xent_loss(logits, batch["labels"], cfg), {}


# ------------------------------------------------------------- serving
def cache_shapes(cfg, batch: int, max_seq: int, rt: TunableConfig):
    g, rem = _split(cfg)
    mg, mg_lg = mamba2.mamba_cache_shapes(cfg, batch, g * cfg.attn_every)
    mg = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        (g, cfg.attn_every) + s.shape[1:], s.dtype), mg)
    mg_lg = jax.tree.map(lambda t: ("layers",) + t, mg_lg,
                         is_leaf=lambda t: isinstance(t, tuple))
    kv, kv_lg = L.attn_cache_shapes(cfg, batch, max_seq, rt, layers=g)
    shp = {"groups": mg, "kv": kv, "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    lg = {"groups": mg_lg, "kv": kv_lg, "pos": ()}
    if rem:
        mr, mr_lg = mamba2.mamba_cache_shapes(cfg, batch, rem)
        shp["rem"] = mr
        lg["rem"] = mr_lg
    return shp, lg


def init_cache(cfg, batch: int, max_seq: int, rt: TunableConfig):
    shp, _ = cache_shapes(cfg, batch, max_seq, rt)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shp)


def prefill_fn(p, batch, cfg, rt: TunableConfig, rules, max_seq: int):
    h = L.embed(p["embed"], batch["tokens"], rt)
    if rules is not None:
        h = rules.constrain(h, "batch", None, None)
    B, S, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    def group(x, gp):
        def inner(xc, mp):
            xc, st = mamba2.mamba_block(mp, xc, cfg, rt, rules,
                                        want_state=True)
            return xc, st
        x, states = scan_layers(inner, x, gp, unroll=rt.unroll_layers)
        hn = L.rmsnorm(x, p["shared"]["ln1"], rt, cfg.norm_eps)
        # the cache's k/v: the shared block's own projections again,
        # merged with them by the compiler (PERF.md, section 5)
        k = jnp.einsum("bsd,dhk->bshk", hn, L.cast(p["shared"]["attn"]["wk"], rt))
        v = jnp.einsum("bsd,dhk->bshk", hn, L.cast(p["shared"]["attn"]["wv"], rt))
        k = L.rope(k, positions, cfg.rope_theta)
        x = _shared_block(p["shared"], x, positions, cfg, rt, rules)
        kq, ks = L.quantize_kv(k, rt.kv_cache_dtype)
        vq, vs = L.quantize_kv(v, rt.kv_cache_dtype)
        extras = (kq, vq) if ks is None else (kq, vq, ks, vs)
        return x, (states, extras)

    h, (gstates, extras) = scan_layers(group, h, p["groups"],
                                       unroll=rt.unroll_layers)
    pad = max_seq - S
    def pad_seq(t):
        return jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    kv = {"k": pad_seq(extras[0]), "v": pad_seq(extras[1])}
    if len(extras) == 4:
        kv["k_scale"] = pad_seq(extras[2])
        kv["v_scale"] = pad_seq(extras[3])
    cache = {"groups": gstates, "kv": kv, "pos": jnp.array(S, jnp.int32)}
    if "rem" in p:
        def inner(xc, mp):
            xc, st = mamba2.mamba_block(mp, xc, cfg, rt, rules,
                                        want_state=True)
            return xc, st
        h, rstates = scan_layers(inner, h, p["rem"],
                                 unroll=rt.unroll_layers)
        cache["rem"] = rstates
    h = L.rmsnorm(h, p["final_norm"], rt, cfg.norm_eps)
    logits = L.unembed(p["embed"], h[:, -1:], cfg, rt, rules)
    return logits, cache


def decode_fn(p, cache, tokens, cfg, rt: TunableConfig, rules):
    h = L.embed(p["embed"], tokens, rt)
    pos = cache["pos"]

    def group(x, args):
        gp, gstate, gkv = args
        def inner(xc, margs):
            mp, mstate = margs
            return mamba2.mamba_decode_block(mp, xc, mstate, cfg, rt, rules)
        x, new_states = scan_layers(inner, x, (gp, gstate),
                                    unroll=rt.unroll_layers)
        hn = L.rmsnorm(x, p["shared"]["ln1"], rt, cfg.norm_eps)
        a, gkv = L.decode_attention_block(p["shared"]["attn"], hn, gkv, pos,
                                          cfg=cfg, rt=rt, rules=rules)
        x = x + a
        hn = L.rmsnorm(x, p["shared"]["ln2"], rt, cfg.norm_eps)
        x = x + L.mlp_block(p["shared"]["mlp"], hn, cfg=cfg, rt=rt,
                            rules=rules)
        return x, (new_states, gkv)

    h, (gstates, kv) = scan_layers(group, h,
                                   (p["groups"], cache["groups"],
                                    cache["kv"]),
                                   unroll=rt.unroll_layers)
    new_cache = {"groups": gstates, "kv": kv, "pos": pos + 1}
    if "rem" in p:
        def inner(xc, margs):
            mp, mstate = margs
            return mamba2.mamba_decode_block(mp, xc, mstate, cfg, rt, rules)
        h, rstates = scan_layers(inner, h, (p["rem"], cache["rem"]),
                                 unroll=rt.unroll_layers)
        new_cache["rem"] = rstates
    h = L.rmsnorm(h, p["final_norm"], rt, cfg.norm_eps)
    logits = L.unembed(p["embed"], h, cfg, rt, rules)
    return logits, new_cache
