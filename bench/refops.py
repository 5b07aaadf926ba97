"""Plain float32 building blocks for the references in ``bench/configs/``.

Nothing here imports the program.  Every matrix product goes through a
``Matmul`` policy: :data:`F32` computes in float32 at the highest
precision (a TPU otherwise runs float32 products as bfloat16 passes),
and :data:`FP8` rounds both operands to float8 e4m3 with one scale per
tensor first.  ``FP8`` is the control: the reference in the precision
below the bfloat16 that the cells state, which a sound comparison has
to refuse.
"""
from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp

f32 = jnp.float32
Matmul = Callable[..., jax.Array]


def F32(spec: str, a, b):
    return jnp.einsum(spec, a.astype(f32), b.astype(f32),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=f32)


def _fp8(x):
    x = x.astype(f32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(f32) * scale


@jax.custom_vjp
def fp8_round(x):
    """x rounded to float8 e4m3 under one scale that maps its largest
    magnitude to the format's largest finite value (448).  Its gradient
    is rounded the same way, with a scale of its own, as a scaled float8
    backward pass rounds it."""
    return _fp8(x)


fp8_round.defvjp(lambda x: (_fp8(x), None), lambda _, ct: (_fp8(ct),))


def FP8(spec: str, a, b):
    return F32(spec, fp8_round(a), fp8_round(b))


MATMULS = {"f32": F32, "fp8": FP8}


def rmsnorm(x, w, eps: float):
    x = x.astype(f32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta: float):
    """Rotary embedding on (S, H, hd), rotating the two halves of hd."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=f32) / half)
    ang = pos.astype(f32)[:, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def causal_attention(q, k, v, mm: Matmul, block: int = 512):
    """Softmax attention of (S, H, hd) queries over (S, Hkv, hd) keys and
    values, query h reading key head h // (H / Hkv); computed in query
    blocks of up to ``block`` rows (a divisor of S) so that long
    sequences fit."""
    S, H, hd = q.shape
    rep = H // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    block = math.gcd(S, block)
    kpos = jnp.arange(S)

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        s = mm("qhd,khd->hqk", qb, k) / math.sqrt(hd)
        qpos = i * block + jnp.arange(block)
        s = jnp.where(qpos[None, :, None] >= kpos[None, None, :], s, -jnp.inf)
        return mm("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(one, jnp.arange(S // block))
    return out.reshape(S, H, hd)


def attention(p, x, pos, mm: Matmul, theta: float, cache: bool = False):
    """Self-attention sub-block on (S, d); with ``cache`` also the roped
    keys and the values it stores."""
    q = rope(mm("sd,dhk->shk", x, p["wq"]), pos, theta)
    k = rope(mm("sd,dhk->shk", x, p["wk"]), pos, theta)
    v = mm("sd,dhk->shk", x, p["wv"])
    out = mm("shk,hkd->sd", causal_attention(q, k, v, mm), p["wo"])
    return (out, k, v) if cache else out


def mlp(p, x, mm: Matmul, act: str):
    if act == "silu":
        h = jax.nn.silu(mm("sd,df->sf", x, p["wg"])) * mm("sd,df->sf", x,
                                                           p["wu"])
    else:                       # tanh-approximate GELU, as the program runs
        h = jax.nn.gelu(mm("sd,df->sf", x, p["wu"]), approximate=True)
    return mm("sf,fd->sd", h, p["wd"])


def logits(emb_or_unembed, x, vocab: int, mm: Matmul, tied: bool):
    """(S, vocab) logits over the real vocabulary (padding rows dropped)."""
    if tied:
        return mm("sd,vd->sv", x, emb_or_unembed[:vocab])
    return mm("sd,dv->sv", x, emb_or_unembed[:, :vocab])


def xent(lg, labels):
    """Mean next-token cross-entropy of (S, V) logits."""
    gold = jnp.take_along_axis(lg, labels[:, None], -1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(lg, -1) - gold)


def ssd_scan(X, Bm, Cm, dt, A, chunk: int, h0=None):
    """The Mamba2 state-space recurrence on one sequence.

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T and y_t = h_t C_t, per
    head, with X (S, H, P), Bm/Cm (S, N), dt (S, H), A (H,).  Computed
    chunk by chunk: inside a chunk the recurrence is unrolled into its
    exact quadratic form, and the state is carried across chunks.
    Returns (Y (S, H, P), final state (H, P, N))."""
    S, H, P = X.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    rs = lambda t: t.reshape((nc, chunk) + t.shape[1:])
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    hp = jax.lax.Precision.HIGHEST

    def one(h, c):
        x, b, cm, d = c
        cum = jnp.cumsum(d * A, 0)                          # (Q, H)
        dec = jnp.exp(jnp.where(tri[:, :, None],
                                cum[:, None, :] - cum[None, :, :], -jnp.inf))
        cb = jnp.einsum("qn,kn->qk", cm, b, precision=hp)
        w = cb[:, :, None] * dec * d[None, :, :]            # (Q, K, H)
        y = jnp.einsum("qkh,khp->qhp", w, x, precision=hp)
        y = y + jnp.einsum("qn,qh,hpn->qhp", cm, jnp.exp(cum), h,
                           precision=hp)
        tail = jnp.exp(cum[-1][None, :] - cum) * d          # (Q, H)
        h = (jnp.exp(cum[-1])[:, None, None] * h
             + jnp.einsum("kh,khp,kn->hpn", tail, x, b, precision=hp))
        return h, y

    if h0 is None:
        h0 = jnp.zeros((H, P, N), f32)
    h, Y = jax.lax.scan(one, h0, (rs(X.astype(f32)), rs(Bm), rs(Cm), rs(dt)))
    return Y.reshape(S, H, P), h
