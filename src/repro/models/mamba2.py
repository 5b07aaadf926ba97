"""Mamba2 (SSD) blocks — chunked state-space duality algorithm.

Training/prefill uses the chunkwise-parallel SSD form (within-chunk
quadratic term + sequential cross-chunk state scan); decode is the O(1)
recurrent update.  The within-chunk term is the compute hot-spot the
``ssm_scan`` Pallas kernel tiles on TPU (ref semantics identical to
``ssd_chunked`` here).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.params import TunableConfig
from repro.models import layers as L
from repro.models.scopes import scope


def mamba_spec(cfg) -> Dict[str, L.PSpec]:
    d = cfg.d_model
    d_in = cfg.ssm_expand * d
    H = d_in // cfg.ssm_head_dim
    N = cfg.ssm_state
    return {
        "ln": L.rmsnorm_spec(d),
        "wx": L.PSpec((d, d_in), ("embed", "ssm_inner")),
        "wz": L.PSpec((d, d_in), ("embed", "ssm_inner")),
        "conv": L.PSpec((4, d_in), (None, "ssm_inner"), 0.2),
        "wB": L.PSpec((d, N), ("embed", None)),
        "wC": L.PSpec((d, N), ("embed", None)),
        "wdt": L.PSpec((d, H), ("embed", "ssm_heads")),
        "dt_bias": L.PSpec((H,), ("ssm_heads",), "zeros"),
        "A_log": L.PSpec((H,), ("ssm_heads",), "zeros"),
        "D": L.PSpec((H,), ("ssm_heads",), "ones"),
        "gln": L.PSpec((d_in,), ("ssm_inner",), "ones"),
        "wo": L.PSpec((d_in, d), ("ssm_inner", "embed")),
    }


def _causal_conv(x, w, state=None):
    """Depthwise causal conv, kernel 4.  x: (B,S,C), w: (4,C).

    state: (B,3,C) previous inputs for decode; returns (y, new_state)."""
    K = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(K))
    new_state = xp[:, -(K - 1):, :] if x.shape[1] >= 1 else state
    return y, new_state


def _gates(p, x, cfg, rt):
    """Common projections.  x:(B,S,d) -> (xin(B,S,H,P), z, Bm, Cm, dt, a)."""
    comp = L.dt(rt)
    d_in = cfg.ssm_expand * cfg.d_model
    H = d_in // cfg.ssm_head_dim
    P = cfg.ssm_head_dim
    z = x @ L.cast(p["wz"], rt)
    xin = x @ L.cast(p["wx"], rt)
    Bm = (x @ L.cast(p["wB"], rt)).astype(jnp.float32)
    Cm = (x @ L.cast(p["wC"], rt)).astype(jnp.float32)
    dt = jax.nn.softplus(
        (x @ L.cast(p["wdt"], rt)).astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    loga = dt * A                    # (B,S,H) log decay <= 0
    return xin, z, Bm, Cm, dt, loga


def ssd_chunked(X, Bm, Cm, dt, loga, chunk: int, h0=None):
    """Chunkwise SSD.  X:(B,S,H,P), Bm/Cm:(B,S,N), dt/loga:(B,S,H).

    Returns (Y:(B,S,H,P), h_final:(B,H,P,N))."""
    Bsz, S, H, P = X.shape
    N = Bm.shape[-1]
    if S % chunk:
        # pad with no-op tokens: dt=0 (no input), loga=0 (no decay)
        pad = chunk - S % chunk
        pz = lambda t: jnp.pad(t, [(0, 0), (0, pad)] +
                               [(0, 0)] * (t.ndim - 2))
        Y, h = ssd_chunked(pz(X), pz(Bm), pz(Cm), pz(dt), pz(loga),
                           chunk, h0)
        return Y[:, :S], h
    nc = S // chunk
    Q = chunk
    f32 = jnp.float32
    Xc = X.reshape(Bsz, nc, Q, H, P)
    Bc = Bm.reshape(Bsz, nc, Q, N)
    Cc = Cm.reshape(Bsz, nc, Q, N)
    dtc = dt.reshape(Bsz, nc, Q, H)
    lac = loga.reshape(Bsz, nc, Q, H)
    cum = jnp.cumsum(lac, axis=2)                       # (B,nc,Q,H)
    # within-chunk
    Lmat = jnp.exp(cum[:, :, :, None, :] - cum[:, :, None, :, :])
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    Lmat = jnp.where(tri[None, None, :, :, None], Lmat, 0.0)
    G = jnp.einsum("bcqn,bckn->bcqk", Cc, Bc)           # shared across heads
    scores = G[..., None] * Lmat * dtc[:, :, None, :, :]
    Y_intra = jnp.einsum("bcqkh,bckhp->bcqhp", scores.astype(f32),
                         Xc.astype(f32))
    # per-chunk state contribution
    dec_last = jnp.exp(cum[:, :, -1:, :] - cum)         # (B,nc,Q,H)
    Sc = jnp.einsum("bckh,bckhp,bckn->bchpn",
                    (dtc * dec_last).astype(f32), Xc.astype(f32), Bc)
    a_chunk = jnp.exp(cum[:, :, -1, :])                 # (B,nc,H)
    # sequential cross-chunk state scan
    def step(h, inp):
        a_c, S_c, C_c, cum_c = inp
        y_inter = jnp.einsum("bqn,bqh,bhpn->bqhp", C_c,
                             jnp.exp(cum_c), h)
        h = a_c[:, :, None, None] * h + S_c
        return h, y_inter
    if h0 is None:
        h0 = jnp.zeros((Bsz, H, P, N), f32)
    xs = (jnp.moveaxis(a_chunk, 1, 0), jnp.moveaxis(Sc, 1, 0),
          jnp.moveaxis(Cc, 1, 0), jnp.moveaxis(cum, 1, 0))
    h_final, Y_inter = jax.lax.scan(step, h0, xs)
    Y_inter = jnp.moveaxis(Y_inter, 0, 1).reshape(Bsz, nc, Q, H, P)
    Y = (Y_intra + Y_inter).reshape(Bsz, S, H, P)
    return Y.astype(X.dtype), h_final


@scope("mamba2")
def mamba_block(p, x, cfg, rt: TunableConfig, rules, want_state: bool = False):
    """Full Mamba2 block (train/prefill).  x: (B,S,d) -> (B,S,d).

    want_state=True additionally returns the decode cache entry."""
    B, S, d = x.shape
    d_in = cfg.ssm_expand * d
    H = d_in // cfg.ssm_head_dim
    P = cfg.ssm_head_dim
    h = L.rmsnorm(x, p["ln"], rt, cfg.norm_eps)
    xin, z, Bm, Cm, dt, loga = _gates(p, h, cfg, rt)
    xin, conv_state = _causal_conv(xin, L.cast(p["conv"], rt))
    xin = jax.nn.silu(xin)
    if rules is not None:
        xin = rules.constrain(xin, "batch", None, "ssm_inner")
    X = xin.reshape(B, S, H, P)
    with scope("ssd_scan"):
        if rt.attn_impl == "pallas":
            from repro.kernels.ssm_scan import ops as ssm_ops
            Y, h_final = ssm_ops.ssm_scan(X, Bm, Cm, dt, loga,
                                          chunk=cfg.ssm_chunk)
        else:
            Y, h_final = ssd_chunked(X, Bm, Cm, dt, loga, cfg.ssm_chunk)
    Y = Y + p["D"].astype(Y.dtype)[None, None, :, None] * X
    y = Y.reshape(B, S, d_in)
    y = L.rmsnorm(y * jax.nn.silu(z), p["gln"], rt, cfg.norm_eps)
    if rules is not None:
        y = rules.constrain(y, "batch", None, "ssm_inner")
    out = x + y @ L.cast(p["wo"], rt)
    if want_state:
        return out, {"ssm": h_final,
                     "conv": conv_state.astype(jnp.float32)}
    return out


# ------------------------------------------------------------- decode
def mamba_cache_shapes(cfg, batch: int, layers: int):
    d_in = cfg.ssm_expand * cfg.d_model
    H = d_in // cfg.ssm_head_dim
    shp = {
        "ssm": jax.ShapeDtypeStruct(
            (layers, batch, H, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
        "conv": jax.ShapeDtypeStruct((layers, batch, 3, d_in), jnp.float32),
    }
    lg = {"ssm": ("layers", "batch", "ssm_heads", None, None),
          "conv": ("layers", "batch", None, "ssm_inner")}
    return shp, lg


@scope("mamba2")
def mamba_decode_block(p, x, layer_cache, cfg, rt: TunableConfig, rules):
    """One-token recurrent update.  x: (B,1,d)."""
    B, _, d = x.shape
    d_in = cfg.ssm_expand * d
    H = d_in // cfg.ssm_head_dim
    P = cfg.ssm_head_dim
    h = L.rmsnorm(x, p["ln"], rt, cfg.norm_eps)
    xin, z, Bm, Cm, dt, loga = _gates(p, h, cfg, rt)
    xin, conv_state = _causal_conv(xin, L.cast(p["conv"], rt),
                                   state=layer_cache["conv"])
    xin = jax.nn.silu(xin)
    X = xin.reshape(B, H, P).astype(jnp.float32)
    a = jnp.exp(loga[:, 0, :])                          # (B,H)
    hs = layer_cache["ssm"]                             # (B,H,P,N)
    hs = (a[:, :, None, None] * hs
          + jnp.einsum("bh,bhp,bn->bhpn", dt[:, 0], X, Bm[:, 0]))
    Y = jnp.einsum("bn,bhpn->bhp", Cm[:, 0], hs)
    Y = Y + p["D"].astype(Y.dtype)[None, :, None] * X
    y = Y.reshape(B, 1, d_in).astype(x.dtype)
    y = L.rmsnorm(y * jax.nn.silu(z), p["gln"], rt, cfg.norm_eps)
    out = x + y @ L.cast(p["wo"], rt)
    return out, {"ssm": hs, "conv": conv_state.astype(jnp.float32)}
