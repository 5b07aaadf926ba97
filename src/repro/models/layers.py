"""Shared model-zoo building blocks (pure-JAX, pytree params).

Parameters are declared as ``PSpec`` trees: shape + logical dim names +
init scale.  The same tree yields real arrays (``init_params``), dry-run
``ShapeDtypeStruct``s (``param_shapes``) and sharding specs
(``logical_tree`` consumed by runtime.sharding).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.params import TunableConfig
from repro.models.scopes import scope


# ---------------------------------------------------------------- params
@dataclasses.dataclass(frozen=True)
class PSpec:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    scale: Any = "fan_in"          # "fan_in" | float | "zeros" | "ones"

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def _is_pspec(x):
    return isinstance(x, PSpec)


def init_params(spec_tree, key, dtype=jnp.float32):
    leaves, treedef = jax.tree.flatten(spec_tree, is_leaf=_is_pspec)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, s in zip(keys, leaves):
        if s.scale == "zeros":
            out.append(jnp.zeros(s.shape, dtype))
        elif s.scale == "ones":
            out.append(jnp.ones(s.shape, dtype))
        else:
            if s.scale == "fan_in":
                fan = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
                sd = 1.0 / math.sqrt(max(1, fan))
            else:
                sd = float(s.scale)
            out.append((jax.random.normal(k, s.shape) * sd).astype(dtype))
    return jax.tree.unflatten(treedef, out)


def param_shapes(spec_tree, dtype=jnp.float32):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype), spec_tree,
        is_leaf=_is_pspec)


def logical_tree(spec_tree):
    return jax.tree.map(lambda s: s.logical, spec_tree, is_leaf=_is_pspec)


def stacked(n: int, spec_tree):
    """Prepend a scanned 'layers' dim to every PSpec in the tree."""
    return jax.tree.map(
        lambda s: PSpec((n,) + s.shape, ("layers",) + s.logical, s.scale),
        spec_tree, is_leaf=_is_pspec)


# ---------------------------------------------------------------- dtypes
def dt(rt: TunableConfig):
    return jnp.dtype(rt.compute_dtype)


def cast(x, rt: TunableConfig):
    return x.astype(dt(rt))


# ---------------------------------------------------------------- norms
def rmsnorm_spec(d: int) -> PSpec:
    return PSpec((d,), ("embed",), "ones")


@scope("norm")
def rmsnorm(x, scale, rt: TunableConfig, eps: float = 1e-5):
    if rt.attn_impl == "pallas" and x.ndim == 3:
        from repro.kernels.rmsnorm import ops as rms_ops
        return rms_ops.rmsnorm(x, scale, eps=eps)
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def layernorm(x, scale, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
            ).astype(x.dtype)


# ---------------------------------------------------------------- rope
def rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None, None] * freq  # (...,S,1,half)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------- attention
def attn_spec(cfg) -> Dict[str, PSpec]:
    # explicit fan-in scales: "fan_in" reads shape[-2], which for these
    # head-split weights is a head count, not the contracted width
    d, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(H * hd)
    return {
        "wq": PSpec((d, H, hd), ("embed", "heads", None), s_in),
        "wk": PSpec((d, Hkv, hd), ("embed", "kv_heads", None), s_in),
        "wv": PSpec((d, Hkv, hd), ("embed", "kv_heads", None), s_in),
        "wo": PSpec((H, hd, d), ("heads", None, "embed"), s_out),
    }


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, hkv, hd = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, hkv, n_rep, hd))
    return k.reshape(b, s, hkv * n_rep, hd)


def full_attention(q, k, v, *, causal: bool, rt: TunableConfig, rules=None,
                   q_positions=None, kv_positions=None):
    """q: (B,Sq,H,hd), k/v: (B,Skv,H,hd) (already GQA-repeated)."""
    if rt.attn_impl == "pallas" and causal and q.shape[1] == k.shape[1]:
        from repro.kernels.flash_attention import ops as fa_ops
        return fa_ops.flash_attention(q, k, v, causal=True,
                                      block_q=rt.attn_block_q,
                                      block_kv=rt.attn_block_kv)
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        if q_positions is None:
            q_positions = jnp.arange(sq)
        if kv_positions is None:
            kv_positions = jnp.arange(sk)
        mask = q_positions[:, None] >= kv_positions[None, :]
        scores = jnp.where(mask[None, None], scores, -1e30)
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@scope("attention")
def attention_block(p, x, *, cfg, rt: TunableConfig, rules, positions,
                    causal=True, kv_x=None, kv_positions=None):
    """Full (train/prefill) attention sub-block.  kv_x!=None => cross-attn."""
    B, S, _ = x.shape
    src = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, cast(p["wq"], rt))
    k = jnp.einsum("bsd,dhk->bshk", src, cast(p["wk"], rt))
    v = jnp.einsum("bsd,dhk->bshk", src, cast(p["wv"], rt))
    if kv_x is None:  # self-attention gets RoPE
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions if kv_positions is None else kv_positions,
                 cfg.rope_theta)
    k = _repeat_kv(k, cfg.n_heads // cfg.n_kv_heads)
    v = _repeat_kv(v, cfg.n_heads // cfg.n_kv_heads)
    bspec = rules.attn_batch_spec(B) if rules is not None else None
    if bspec is not None:
        # beyond-paper fallback: reshard so the attention op is
        # batch-parallel over (data, model) when heads don't divide TP
        resh = lambda t: jax.lax.with_sharding_constraint(
            t, rules.sharding(jax.sharding.PartitionSpec(*bspec, None, None, None)))
        q, k, v = resh(q), resh(k), resh(v)
    elif rules is not None:
        q = rules.constrain(q, "batch", None, "heads", None)
        k = rules.constrain(k, "batch", None, "heads", None)
        v = rules.constrain(v, "batch", None, "heads", None)
    o = full_attention(q, k, v, causal=causal and kv_x is None, rt=rt,
                       rules=rules)
    if rules is not None:
        o = rules.constrain(o, "batch", None, "heads", None)
    return jnp.einsum("bshk,hkd->bsd", o, cast(p["wo"], rt))


# ------------------------------------------------------- KV-cache decode
def quantize_kv(x, kv_dtype: str):
    """x: (B,S,Hkv,hd) -> (stored, scale).  int8: per-(token,head) scale."""
    if kv_dtype != "int8":
        return x.astype(jnp.dtype(kv_dtype)), None
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def dequantize_kv(stored, scale, out_dtype):
    if scale is None:
        return stored.astype(out_dtype)
    return (stored.astype(jnp.float32) * scale).astype(out_dtype)


def attn_cache_shapes(cfg, batch: int, max_seq: int, rt: TunableConfig,
                      layers: Optional[int] = None):
    """ShapeDtypeStructs + logical names for a stacked KV cache."""
    L = cfg.n_layers if layers is None else layers
    kvd = jnp.int8 if rt.kv_cache_dtype == "int8" else jnp.dtype(rt.kv_cache_dtype)
    shp = (L, batch, max_seq, cfg.n_kv_heads, cfg.hd)
    logical = ("layers", "batch", "seq_data" if batch == 1 else None,
               "kv_heads", None)
    out = {"k": jax.ShapeDtypeStruct(shp, kvd),
           "v": jax.ShapeDtypeStruct(shp, kvd)}
    lg = {"k": logical, "v": logical}
    if rt.kv_cache_dtype == "int8":
        sshp = (L, batch, max_seq, cfg.n_kv_heads, 1)
        out["k_scale"] = jax.ShapeDtypeStruct(sshp, jnp.float32)
        out["v_scale"] = jax.ShapeDtypeStruct(sshp, jnp.float32)
        lg["k_scale"] = logical
        lg["v_scale"] = logical
    return out, lg


@scope("attention")
def decode_attention_block(p, x, layer_cache, pos, *, cfg, rt: TunableConfig,
                           rules):
    """One-token decode self-attention against a KV cache.

    x: (B,1,d); layer_cache: {'k','v'[,scales]} with shapes (B,Smax,Hkv,hd).
    pos: scalar int32 current position.  Returns (out, updated_cache).
    """
    B = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x, cast(p["wq"], rt))
    k = jnp.einsum("bsd,dhk->bshk", x, cast(p["wk"], rt))
    v = jnp.einsum("bsd,dhk->bshk", x, cast(p["wv"], rt))
    posv = jnp.full((B, 1), pos, dtype=jnp.int32)
    q = rope(q, posv, cfg.rope_theta)
    k = rope(k, posv, cfg.rope_theta)
    kq, ks = quantize_kv(k, rt.kv_cache_dtype)
    vq, vs = quantize_kv(v, rt.kv_cache_dtype)
    upd = lambda buf, new: jax.lax.dynamic_update_slice(
        buf, new, (0, pos, 0, 0))
    cache = dict(layer_cache)
    cache["k"] = upd(layer_cache["k"], kq)
    cache["v"] = upd(layer_cache["v"], vq)
    if ks is not None:
        cache["k_scale"] = upd(layer_cache["k_scale"], ks)
        cache["v_scale"] = upd(layer_cache["v_scale"], vs)
    if rt.attn_impl == "pallas":
        # flash-decode kernel: streams the cache once at stored dtype
        # (int8 dequant fused), online softmax in VMEM
        from repro.kernels.flash_decode import ops as fd_ops
        o = fd_ops.flash_decode(q, cache["k"], cache["v"], pos + 1,
                                cache.get("k_scale"), cache.get("v_scale"),
                                block_kv=rt.attn_block_kv)
    else:
        kf = dequantize_kv(cache["k"], cache.get("k_scale"), dt(rt))
        vf = dequantize_kv(cache["v"], cache.get("v_scale"), dt(rt))
        kf = _repeat_kv(kf, cfg.n_heads // cfg.n_kv_heads)
        vf = _repeat_kv(vf, cfg.n_heads // cfg.n_kv_heads)
        smax = kf.shape[1]
        scale = 1.0 / math.sqrt(q.shape[-1])
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kf,
                            preferred_element_type=jnp.float32) * scale
        mask = (jnp.arange(smax) <= pos)[None, None, None, :]
        scores = jnp.where(mask, scores, -1e30)
        pr = jax.nn.softmax(scores.astype(jnp.float32),
                            axis=-1).astype(q.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", pr, vf)
    out = jnp.einsum("bshk,hkd->bsd", o, cast(p["wo"], rt))
    return out, cache


# ---------------------------------------------------------------- mlp
def mlp_spec(cfg, d_ff: Optional[int] = None) -> Dict[str, PSpec]:
    d = cfg.d_model
    ff = cfg.d_ff if d_ff is None else d_ff
    if cfg.mlp_act == "silu":
        return {"wg": PSpec((d, ff), ("embed", "mlp")),
                "wu": PSpec((d, ff), ("embed", "mlp")),
                "wd": PSpec((ff, d), ("mlp", "embed"))}
    return {"wu": PSpec((d, ff), ("embed", "mlp")),
            "wd": PSpec((ff, d), ("mlp", "embed"))}


@scope("mlp")
def mlp_block(p, x, *, cfg, rt: TunableConfig, rules):
    if cfg.mlp_act == "silu":
        h = jax.nn.silu(x @ cast(p["wg"], rt)) * (x @ cast(p["wu"], rt))
    elif cfg.mlp_act == "relu2":
        h = jnp.square(jax.nn.relu(x @ cast(p["wu"], rt)))
    else:
        h = jax.nn.gelu(x @ cast(p["wu"], rt))
    if rules is not None:
        h = rules.constrain(h, "batch", None, "mlp")
    return h @ cast(p["wd"], rt)


# ---------------------------------------------------------------- embed/loss
def padded_vocab(cfg, multiple: int = 512) -> int:
    return ((cfg.vocab + multiple - 1) // multiple) * multiple


def embed_spec(cfg) -> Dict[str, PSpec]:
    V = padded_vocab(cfg)
    out = {"embedding": PSpec((V, cfg.d_model), ("vocab", "embed"), 0.02)}
    if not cfg.tie_embeddings:
        out["unembed"] = PSpec((cfg.d_model, V), ("embed", "vocab"))
    return out


@scope("embed")
def embed(p, tokens, rt: TunableConfig):
    return jnp.take(cast(p["embedding"], rt), tokens, axis=0)


@scope("head")
def unembed(p, x, cfg, rt: TunableConfig, rules):
    w = p.get("unembed")
    if w is None:
        w = p["embedding"].T
    logits = jnp.einsum("bsd,dv->bsv", x, cast(w, rt),
                        preferred_element_type=jnp.float32)
    if rules is not None:
        logits = rules.constrain(logits, "batch", None, "vocab")
    return logits


@scope("head")
def xent_loss(logits, labels, cfg):
    """logits: (B,S,Vpad) f32; labels: (B,S) int32. Mean over tokens."""
    V = padded_vocab(cfg)
    mask = jnp.arange(V) < cfg.vocab
    logits = jnp.where(mask[None, None, :], logits, -1e30)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
