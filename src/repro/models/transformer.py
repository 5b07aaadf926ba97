"""Dense decoder-only transformer (llama-style).

Also the backbone for the VLM (frontend embeds prepended) and MoE
(FFN swapped for expert-parallel MoE) families.  The layer stack is a
``lax.scan`` over stacked params (small HLO, remat-able).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.core.params import TunableConfig
from repro.models import layers as L
from repro.models import moe
from repro.runtime import remat
from repro.runtime.loops import scan_layers


def block_spec(cfg) -> Dict[str, L.PSpec]:
    out = {
        "ln1": L.rmsnorm_spec(cfg.d_model),
        "ln2": L.rmsnorm_spec(cfg.d_model),
        "attn": L.attn_spec(cfg),
    }
    if cfg.family == "moe":
        out["moe"] = moe.moe_spec(cfg)
    else:
        out["mlp"] = L.mlp_spec(cfg)
    return out


def spec(cfg) -> Dict:
    return {
        "embed": L.embed_spec(cfg),
        "blocks": L.stacked(cfg.n_layers, block_spec(cfg)),
        "final_norm": L.rmsnorm_spec(cfg.d_model),
    }


def _ffn(bp, h, cfg, rt, rules):
    """FFN sub-block -> (y, aux_loss)."""
    if "moe" in bp:
        return moe.moe_mlp(bp["moe"], h, cfg, rt, rules)
    return L.mlp_block(bp["mlp"], h, cfg=cfg, rt=rt, rules=rules), 0.0


def _block(bp, x, positions, cfg, rt: TunableConfig, rules):
    h = L.rmsnorm(x, bp["ln1"], rt, cfg.norm_eps)
    x = x + L.attention_block(bp["attn"], h, cfg=cfg, rt=rt, rules=rules,
                              positions=positions)
    h = L.rmsnorm(x, bp["ln2"], rt, cfg.norm_eps)
    y, aux = _ffn(bp, h, cfg, rt, rules)
    x = x + y
    if rules is not None:
        # sequence parallelism (beyond-paper): between blocks the residual
        # is seq-sharded over the model axis; XLA inserts the gather at
        # the attention boundary and the scatter after the FFN
        x = rules.constrain(x, "batch",
                            "seq_model" if rt.seq_parallel else None, None)
    return x, aux


def forward(p, h, positions, cfg, rt: TunableConfig, rules):
    """h: (B,S,d) embeddings -> (final hidden states, total aux loss)."""
    def body(x, bp):
        x = remat.from_carry(x, rt)
        x, aux = _block(bp, x, positions, cfg, rt, rules)
        return remat.to_carry(x, rt), aux
    body = remat.wrap_layer(body, rt)
    h, auxs = scan_layers(body, remat.to_carry(h, rt), p["blocks"],
                          unroll=rt.unroll_layers)
    h = remat.from_carry(h, rt)
    return L.rmsnorm(h, p["final_norm"], rt, cfg.norm_eps), jnp.sum(auxs)


def embed_inputs(p, batch, cfg, rt: TunableConfig, rules):
    """tokens (+ optional precomputed frontend embeddings) -> (B,S,d)."""
    h = L.embed(p["embed"], batch["tokens"], rt)
    if "frontend_embeds" in batch:  # vlm/audio stub: prepend patch embeds
        h = jnp.concatenate([L.cast(batch["frontend_embeds"], rt), h], axis=1)
    if rules is not None:
        h = rules.constrain(h, "batch", None, None)
    return h


AUX_COEF = 0.01


def loss_fn(p, batch, cfg, rt: TunableConfig, rules):
    h = embed_inputs(p, batch, cfg, rt, rules)
    B, S, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    h, aux = forward(p, h, positions, cfg, rt, rules)
    logits = L.unembed(p["embed"], h, cfg, rt, rules)
    labels = batch["labels"]
    if labels.shape[1] < S:  # frontend positions carry no labels
        logits = logits[:, S - labels.shape[1]:]
    loss = L.xent_loss(logits, labels, cfg)
    return loss + AUX_COEF * aux, {"xent": loss, "aux": aux}


# ------------------------------------------------------------- serving
def cache_shapes(cfg, batch: int, max_seq: int, rt: TunableConfig):
    shp, lg = L.attn_cache_shapes(cfg, batch, max_seq, rt)
    return ({"layers": shp, "pos": jax.ShapeDtypeStruct((), jnp.int32)},
            {"layers": lg, "pos": ()})


def init_cache(cfg, batch: int, max_seq: int, rt: TunableConfig):
    shp, _ = cache_shapes(cfg, batch, max_seq, rt)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shp)


def prefill_fn(p, batch, cfg, rt: TunableConfig, rules, max_seq: int):
    """Run the full prompt, build the KV cache, return last-token logits."""
    h = embed_inputs(p, batch, cfg, rt, rules)
    B, S, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    def body(x, bp):
        x = remat.from_carry(x, rt)
        hn = L.rmsnorm(x, bp["ln1"], rt, cfg.norm_eps)
        # the cache's k/v are the attention block's own projections
        # again: the compiler merges the two, so on a TPU v5e they cost
        # nothing of their own (PERF.md, section 5)
        k = jnp.einsum("bsd,dhk->bshk", hn, L.cast(bp["attn"]["wk"], rt))
        v = jnp.einsum("bsd,dhk->bshk", hn, L.cast(bp["attn"]["wv"], rt))
        k = L.rope(k, positions, cfg.rope_theta)
        x, _ = _block(bp, x, positions, cfg, rt, rules)
        kq, ks = L.quantize_kv(k, rt.kv_cache_dtype)
        vq, vs = L.quantize_kv(v, rt.kv_cache_dtype)
        extras = (kq, vq) if ks is None else (kq, vq, ks, vs)
        return remat.to_carry(x, rt), extras

    h, extras = scan_layers(body, remat.to_carry(h, rt), p["blocks"],
                            unroll=rt.unroll_layers)
    h = remat.from_carry(h, rt)
    h = L.rmsnorm(h, p["final_norm"], rt, cfg.norm_eps)
    logits = L.unembed(p["embed"], h[:, -1:], cfg, rt, rules)

    pad = max_seq - S
    def pad_seq(t):
        return jnp.pad(t, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    cache = {"k": pad_seq(extras[0]), "v": pad_seq(extras[1])}
    if len(extras) == 4:
        cache["k_scale"] = pad_seq(extras[2])
        cache["v_scale"] = pad_seq(extras[3])
    return logits, {"layers": cache, "pos": jnp.array(S, jnp.int32)}


def decode_fn(p, cache, tokens, cfg, rt: TunableConfig, rules):
    """One decode step.  tokens: (B,1) int32.  Returns (logits, cache)."""
    h = L.embed(p["embed"], tokens, rt)
    pos = cache["pos"]

    def body(x, args):
        bp, layer_cache = args
        hn = L.rmsnorm(x, bp["ln1"], rt, cfg.norm_eps)
        a, layer_cache = L.decode_attention_block(
            bp["attn"], hn, layer_cache, pos, cfg=cfg, rt=rt, rules=rules)
        x = x + a
        hn = L.rmsnorm(x, bp["ln2"], rt, cfg.norm_eps)
        y, _ = _ffn(bp, hn, cfg, rt, rules)
        return x + y, layer_cache

    h, new_layers = scan_layers(body, h, (p["blocks"], cache["layers"]),
                                unroll=rt.unroll_layers)
    h = L.rmsnorm(h, p["final_norm"], rt, cfg.norm_eps)
    logits = L.unembed(p["embed"], h, cfg, rt, rules)
    return logits, {"layers": new_layers, "pos": pos + 1}
