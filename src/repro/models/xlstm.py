"""xLSTM: alternating mLSTM (matrix memory, chunkwise-parallel) and sLSTM
(scalar memory, strictly sequential) blocks, layout [slstm_every-1 : 1].

Deviations from the paper, documented in DESIGN.md: the mLSTM input gate
uses sigmoid stabilisation (instead of the running-max exponential-gate
stabiliser) so the chunkwise form shares the SSD machinery; sLSTM
recurrent weights are diagonal (element-wise) rather than block-diagonal.
Sub-quadratic: runs the long_500k cell.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from repro.core.params import TunableConfig
from repro.models import layers as L
from repro.runtime import remat
from repro.runtime.loops import scan_layers


# ----------------------------------------------------------- mLSTM
def mlstm_spec(cfg) -> Dict[str, L.PSpec]:
    d, H, hd = cfg.d_model, cfg.n_heads, cfg.hd
    s_in = 1.0 / math.sqrt(d)      # "fan_in" would read the head count
    return {
        "ln": L.rmsnorm_spec(d),
        "wq": L.PSpec((d, H, hd), ("embed", None, None), s_in),
        "wk": L.PSpec((d, H, hd), ("embed", None, None), s_in),
        "wv": L.PSpec((d, H, hd), ("embed", None, "ssm_inner"), s_in),
        "wi": L.PSpec((d, H), ("embed", None)),
        "wf": L.PSpec((d, H), ("embed", None)),
        "wog": L.PSpec((d, d), ("embed", None)),
        "wo": L.PSpec((d, d), ("ssm_inner", "embed")),
    }


def _mlstm_chunked(q, k, v, ig, lf, chunk: int, state=None):
    """Chunkwise mLSTM.  q/k/v: (B,S,H,hd); ig (input gate, (B,S,H)),
    lf (log forget, (B,S,H)).  Returns (y, (h_state, n_state))."""
    Bsz, S, H, hd = q.shape
    f32 = jnp.float32
    if S % chunk:
        # pad with no-op tokens: input gate 0, log-forget 0 (no decay)
        pad = chunk - S % chunk
        pz = lambda t: jnp.pad(t, [(0, 0), (0, pad)] +
                               [(0, 0)] * (t.ndim - 2))
        y, st = _mlstm_chunked(pz(q), pz(k), pz(v), pz(ig), pz(lf),
                               chunk, state)
        return y[:, :S], st
    nc, Q = S // chunk, chunk
    rs = lambda t: t.reshape((Bsz, nc, Q) + t.shape[2:])
    qc, kc, vc = rs(q), rs(k), rs(v)
    igc, lfc = rs(ig).astype(f32), rs(lf).astype(f32)
    cum = jnp.cumsum(lfc, axis=2)                         # (B,nc,Q,H)
    Lmat = jnp.exp(cum[:, :, :, None, :] - cum[:, :, None, :, :])
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    Lmat = jnp.where(tri[None, None, :, :, None], Lmat, 0.0)
    G = jnp.einsum("bcqhn,bckhn->bcqkh", qc.astype(f32), kc.astype(f32))
    W = Lmat * igc[:, :, None, :, :]        # decay-gate weights (no q.k)
    scores = G * W
    y_intra = jnp.einsum("bcqkh,bckhp->bcqhp", scores, vc.astype(f32))
    n_intra = jnp.einsum("bcqkh,bckhn->bcqhn", W, kc.astype(f32))
    dec_last = jnp.exp(cum[:, :, -1:, :] - cum)
    Sc = jnp.einsum("bckh,bckhp,bckhn->bchpn", igc * dec_last,
                    vc.astype(f32), kc.astype(f32))
    Nc = jnp.einsum("bckh,bckhn->bchn", igc * dec_last, kc.astype(f32))
    a_chunk = jnp.exp(cum[:, :, -1, :])

    def step(carry, inp):
        h, n = carry
        a_c, S_c, N_c, q_c, cum_c = inp
        dec = jnp.exp(cum_c)                              # (B,Q,H)
        y_in = jnp.einsum("bqhn,bqh,bhpn->bqhp", q_c, dec, h)
        nn_in = jnp.einsum("bqhn,bqh,bhn->bqh", q_c, dec, n)
        h = a_c[:, :, None, None] * h + S_c
        n = a_c[:, :, None] * n + N_c
        return (h, n), (y_in, nn_in)

    if state is None:
        h0 = jnp.zeros((Bsz, H, hd, hd), f32)
        n0 = jnp.zeros((Bsz, H, hd), f32)
    else:
        h0, n0 = state
    mv = lambda t: jnp.moveaxis(t, 1, 0)
    (hF, nF), (y_inter, nn_inter) = jax.lax.scan(
        step, (h0, n0), (mv(a_chunk), mv(Sc), mv(Nc), mv(qc.astype(f32)),
                         mv(cum)))
    y_inter = jnp.moveaxis(y_inter, 0, 1)
    nn_inter = jnp.moveaxis(nn_inter, 0, 1)
    y = y_intra + y_inter                                  # (B,nc,Q,H,hd)
    # normalizer: q . n  (intra part from n_intra, inter part nn_inter)
    qn = jnp.einsum("bcqhn,bcqhn->bcqh", qc.astype(f32), n_intra) + nn_inter
    denom = jnp.maximum(jnp.abs(qn), 1.0)[..., None]
    y = (y / denom).reshape(Bsz, S, H, hd)
    return y.astype(q.dtype), (hF, nF)


def mlstm_block(p, x, cfg, rt: TunableConfig, rules, want_state=False,
                state=None, decode=False):
    B, S, d = x.shape
    H, hd = cfg.n_heads, cfg.hd
    h = L.rmsnorm(x, p["ln"], rt, cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, L.cast(p["wq"], rt)) / (hd ** 0.5)
    k = jnp.einsum("bsd,dhk->bshk", h, L.cast(p["wk"], rt))
    v = jnp.einsum("bsd,dhk->bshk", h, L.cast(p["wv"], rt))
    if rules is not None:
        v = rules.constrain(v, "batch", None, None, "ssm_inner")
    ig = jax.nn.sigmoid((h @ L.cast(p["wi"], rt)).astype(jnp.float32))
    lf = jax.nn.log_sigmoid((h @ L.cast(p["wf"], rt)).astype(jnp.float32))
    og = jax.nn.sigmoid(h @ L.cast(p["wog"], rt))
    if decode:
        hs, ns = state
        f32 = jnp.float32
        a = jnp.exp(lf[:, 0])                              # (B,H)
        hs = (a[:, :, None, None] * hs
              + jnp.einsum("bh,bhp,bhn->bhpn", ig[:, 0], v[:, 0].astype(f32),
                           k[:, 0].astype(f32)))
        ns = a[:, :, None] * ns + ig[:, 0, :, None] * k[:, 0].astype(f32)
        yq = jnp.einsum("bhn,bhpn->bhp", q[:, 0].astype(f32), hs)
        qn = jnp.einsum("bhn,bhn->bh", q[:, 0].astype(f32), ns)
        y = (yq / jnp.maximum(jnp.abs(qn), 1.0)[:, :, None])[:, None]
        new_state = (hs, ns)
    else:
        y, new_state = _mlstm_chunked(q, k, v, ig, lf, cfg.ssm_chunk, state)
    y = y.reshape(B, S, d).astype(x.dtype) * og
    out = x + y @ L.cast(p["wo"], rt)
    if want_state or decode:
        return out, new_state
    return out


# ----------------------------------------------------------- sLSTM
def slstm_spec(cfg) -> Dict[str, L.PSpec]:
    d = cfg.d_model
    return {
        "ln": L.rmsnorm_spec(d),
        "wi": L.PSpec((d, d), ("embed", None)),
        "wf": L.PSpec((d, d), ("embed", None)),
        "wz": L.PSpec((d, d), ("embed", None)),
        "wog": L.PSpec((d, d), ("embed", None)),
        "ri": L.PSpec((d,), (None,), "zeros"),
        "rf": L.PSpec((d,), (None,), "zeros"),
        "rz": L.PSpec((d,), (None,), "zeros"),
        "ro": L.PSpec((d,), (None,), "zeros"),
        "wo": L.PSpec((d, d), ("embed", None)),
    }


def _slstm_step(p, carry, zi, zf, zz, zo):
    """One sLSTM timestep.  carry: (c, n, m, h) each (B,d) f32."""
    c, n, m, h = carry
    zi = zi + h * p["ri"]
    zf = zf + h * p["rf"]
    zz = jnp.tanh(zz + h * p["rz"])
    zo = jax.nn.sigmoid(zo + h * p["ro"])
    lf = jax.nn.log_sigmoid(zf)
    m_new = jnp.maximum(lf + m, zi)
    c = jnp.exp(lf + m - m_new) * c + jnp.exp(zi - m_new) * zz
    n = jnp.exp(lf + m - m_new) * n + jnp.exp(zi - m_new)
    h = zo * c / jnp.maximum(n, 1.0)
    return (c, n, m_new, h)


def slstm_block(p, x, cfg, rt: TunableConfig, rules, want_state=False,
                state=None, decode=False):
    B, S, d = x.shape
    f32 = jnp.float32
    hn = L.rmsnorm(x, p["ln"], rt, cfg.norm_eps)
    zi = (hn @ L.cast(p["wi"], rt)).astype(f32)
    zf = (hn @ L.cast(p["wf"], rt)).astype(f32)
    zz = (hn @ L.cast(p["wz"], rt)).astype(f32)
    zo = (hn @ L.cast(p["wog"], rt)).astype(f32)
    pf = {k2: p[k2].astype(f32) for k2 in ("ri", "rf", "rz", "ro")}
    if state is None:
        z = jnp.zeros((B, d), f32)
        state = (z, z, jnp.full((B, d), -1e30, f32), z)
    if decode:
        new_state = _slstm_step(pf, state, zi[:, 0], zf[:, 0], zz[:, 0],
                                zo[:, 0])
        y = new_state[3][:, None, :]
    else:
        def step(carry, inp):
            carry = _slstm_step(pf, carry, *inp)
            return carry, carry[3]
        mv = lambda t: jnp.moveaxis(t, 1, 0)
        new_state, ys = jax.lax.scan(step, state,
                                     (mv(zi), mv(zf), mv(zz), mv(zo)))
        y = jnp.moveaxis(ys, 0, 1)
    out = x + y.astype(x.dtype) @ L.cast(p["wo"], rt)
    if want_state or decode:
        return out, new_state
    return out


# ----------------------------------------------------------- model
def _layout(cfg):
    g = cfg.n_layers // cfg.slstm_every
    m_per_group = cfg.slstm_every - 1
    return g, m_per_group


def spec(cfg) -> Dict:
    g, mpg = _layout(cfg)
    return {
        "embed": L.embed_spec(cfg),
        "mblocks": L.stacked(g, L.stacked(mpg, mlstm_spec(cfg))),
        "sblocks": L.stacked(g, slstm_spec(cfg)),
        "final_norm": L.rmsnorm_spec(cfg.d_model),
    }


def forward(p, h, cfg, rt: TunableConfig, rules):
    def group(x, gp):
        mp, sp = gp
        x = remat.from_carry(x, rt)
        def inner(xc, mpp):
            return mlstm_block(mpp, xc, cfg, rt, rules), None
        x, _ = scan_layers(inner, x, mp, unroll=rt.unroll_layers)
        x = slstm_block(sp, x, cfg, rt, rules)
        return remat.to_carry(x, rt), None
    h, _ = scan_layers(remat.wrap_layer(group, rt),
                       remat.to_carry(h, rt),
                       (p["mblocks"], p["sblocks"]),
                       unroll=rt.unroll_layers)
    return L.rmsnorm(remat.from_carry(h, rt), p["final_norm"], rt,
                     cfg.norm_eps)


def loss_fn(p, batch, cfg, rt: TunableConfig, rules):
    h = L.embed(p["embed"], batch["tokens"], rt)
    if rules is not None:
        h = rules.constrain(h, "batch", None, None)
    h = forward(p, h, cfg, rt, rules)
    logits = L.unembed(p["embed"], h, cfg, rt, rules)
    return L.xent_loss(logits, batch["labels"], cfg), {}


def cache_shapes(cfg, batch: int, max_seq: int, rt: TunableConfig):
    g, mpg = _layout(cfg)
    H, hd, d = cfg.n_heads, cfg.hd, cfg.d_model
    f32 = jnp.float32
    shp = {
        "m_h": jax.ShapeDtypeStruct((g, mpg, batch, H, hd, hd), f32),
        "m_n": jax.ShapeDtypeStruct((g, mpg, batch, H, hd), f32),
        "s": tuple(jax.ShapeDtypeStruct((g, batch, d), f32)
                   for _ in range(4)),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }
    lg = {"m_h": ("layers", "layers", "batch", None, "ssm_inner", None),
          "m_n": ("layers", "layers", "batch", None, None),
          "s": tuple(("layers", "batch", None) for _ in range(4)),
          "pos": ()}
    return shp, lg


def init_cache(cfg, batch: int, max_seq: int, rt: TunableConfig):
    shp, _ = cache_shapes(cfg, batch, max_seq, rt)
    c = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shp)
    # sLSTM m-state starts at -inf surrogate
    c["s"] = (c["s"][0], c["s"][1], c["s"][2] - 1e30, c["s"][3])
    return c


def prefill_fn(p, batch, cfg, rt: TunableConfig, rules, max_seq: int):
    h = L.embed(p["embed"], batch["tokens"], rt)
    if rules is not None:
        h = rules.constrain(h, "batch", None, None)

    def group(x, gp):
        mp, sp = gp
        def inner(xc, mpp):
            xc, st = mlstm_block(mpp, xc, cfg, rt, rules, want_state=True)
            return xc, st
        x, mstates = scan_layers(inner, x, mp, unroll=rt.unroll_layers)
        x, sstate = slstm_block(sp, x, cfg, rt, rules, want_state=True)
        return x, (mstates, sstate)

    h, (mstates, sstates) = scan_layers(group, h,
                                        (p["mblocks"], p["sblocks"]),
                                        unroll=rt.unroll_layers)
    h = L.rmsnorm(h, p["final_norm"], rt, cfg.norm_eps)
    logits = L.unembed(p["embed"], h[:, -1:], cfg, rt, rules)
    cache = {"m_h": mstates[0], "m_n": mstates[1], "s": sstates,
             "pos": jnp.array(batch["tokens"].shape[1], jnp.int32)}
    return logits, cache


def decode_fn(p, cache, tokens, cfg, rt: TunableConfig, rules):
    h = L.embed(p["embed"], tokens, rt)

    def group(x, args):
        gp, sp, m_h, m_n, s_st = args
        def inner(xc, margs):
            mpp, hh, nn = margs
            xc, st = mlstm_block(mpp, xc, cfg, rt, rules, state=(hh, nn),
                                 decode=True)
            return xc, st
        x, mst = scan_layers(inner, x, (gp, m_h, m_n),
                             unroll=rt.unroll_layers)
        x, s_new = slstm_block(sp, x, cfg, rt, rules, state=s_st,
                               decode=True)
        return x, (mst, s_new)

    h, (mstates, sstates) = scan_layers(
        group, h, (p["mblocks"], p["sblocks"], cache["m_h"], cache["m_n"],
                   cache["s"]), unroll=rt.unroll_layers)
    h = L.rmsnorm(h, p["final_norm"], rt, cfg.norm_eps)
    logits = L.unembed(p["embed"], h, cfg, rt, rules)
    return logits, {"m_h": mstates[0], "m_n": mstates[1], "s": sstates,
                    "pos": cache["pos"] + 1}
