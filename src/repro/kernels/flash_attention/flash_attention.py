"""Causal flash attention Pallas-TPU kernel.

Online-softmax tiling: the S x S score matrix never touches HBM — Q is
read once, K/V are streamed per Q-tile through VMEM blocks of
(block_q x block_kv).  The (block_q, block_kv) tile shape is the
spark.shuffle.file.buffer analogue (DESIGN.md §2.1 row 8): it sets the
VMEM working set and the HBM re-fetch factor for K/V.

Grid: (B, H, S/block_q, S/block_kv); the last axis is sequential on TPU,
so the online-softmax state (m, l, acc) lives in VMEM scratch across
KV steps.  Causal Q-tiles skip fully-masked KV tiles (@pl.when).  Keys
at or past ``kv_len`` (a padded tail) are masked out.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, block_q: int, block_kv: int,
                  causal: bool, kv_len: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = kj * block_kv
    # a KV tile entirely in the causal future contributes nothing
    needed = (not causal) or (k_start <= q_start + block_q - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)                # (bk, hd)
        s = q @ k.T                                        # (bq, bk)
        cols = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            s = jnp.where(rows >= cols, s, NEG_INF)
        else:
            s = jnp.where(cols < kv_len, s, NEG_INF)
        m_prev = m_ref[...]                                # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                             # (bq, bk)
        corr = jnp.exp(m_prev - m_new)                     # (bq, 1)
        l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = corr * acc_ref[...] + p @ v
        m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0, ...] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q",
                                             "block_kv", "kv_len",
                                             "interpret"))
def flash_attention_bhsd(q, k, v, *, causal: bool = True,
                         block_q: int = 128, block_kv: int = 128,
                         kv_len: int = None, interpret: bool = False):
    """q/k/v: (B, H, S, hd) -> (B, H, S, hd).  ``kv_len`` (default S)
    is the number of real keys when S carries a padded tail; under
    ``causal`` the mask already hides that tail from the real rows."""
    B, H, S, hd = q.shape
    block_q = min(block_q, S)
    block_kv = min(block_kv, S)
    assert S % block_q == 0 and S % block_kv == 0, (S, block_q, block_kv)
    scale = 1.0 / math.sqrt(hd)
    grid = (B, H, S // block_q, S // block_kv)
    kernel = functools.partial(_flash_kernel, scale=scale, block_q=block_q,
                               block_kv=block_kv, causal=causal,
                               kv_len=S if kv_len is None else kv_len)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # m (running max)
            pltpu.VMEM((block_q, 1), jnp.float32),   # l (running denom)
            pltpu.VMEM((block_q, hd), jnp.float32),  # acc
        ],
        interpret=interpret,
    )(q, k, v)
