"""Flash attention Pallas-TPU kernel with grouped query heads.

Online-softmax tiling: the S x S score matrix never touches HBM — Q is
read once, K/V are streamed per Q-tile through VMEM blocks of
(block_q x block_kv).  The (block_q, block_kv) tile shape is the
spark.shuffle.file.buffer analogue (DESIGN.md §2.1 row 8): it sets the
VMEM working set and the HBM re-fetch factor for K/V.

K/V carry ``Hkv`` heads, a divisor of the ``H`` query heads (GQA).  One
grid step reads a group of the query heads that share a KV head, as a
(group x block_q) row block, against one K and one V block: a group's
heads share each K/V fetch and fill one MXU product.  The group is
split only where its blocks would overrun the scoped VMEM
(:func:`head_group`).

Grid: (B, H / group, pairs).  The last axis walks a scalar-prefetched
table of (q tile, kv tile) pairs (:func:`pair_table`), each q tile's
pairs in a run that starts at kv tile 0 and ends on its last tile, so
the online-softmax state (m, l, acc) lives in VMEM scratch across the
run and the output block stays resident.  Under ``causal`` the table
holds only the pairs with a key at or before some query of the tile,
the diagonal tile last; otherwise every pair.  The iota mask runs only
on tiles that straddle the diagonal (causal) or hold keys at or past
``kv_len``, a padded tail (non-causal; under ``causal`` the diagonal
already hides that tail from the real rows).

A step's scores are held transposed, (block_kv, group x block_q): the
softmax's reductions over keys run across sublanes, not lanes, and the
float32 m, l and accumulator (hd, group x block_q) keep one lane a
query.  The MXU takes q, k and the softmax weights in the inputs' dtype
with float32 accumulation, as the model's XLA path does
(``layers.full_attention``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: scoped VMEM a grid step may plan on: Mosaic's default scoped limit
#: on a TPU v5e is 16 MiB, and :func:`_vmem_bytes` is an estimate
VMEM_BUDGET = 14 * 2**20


def pair_table(n_q: int, n_kv: int, block_q: int, block_kv: int,
               causal: bool) -> np.ndarray:
    """The grid's (q tile, kv tile) pairs in order, packed one int32 a
    pair as ``qi << 16 | kj << 1 | last`` (:func:`unpack`).  Each q
    tile's pairs run kv tile 0, 1, ... and ``last`` marks the run's
    end: under ``causal`` the diagonal tile, the last holding a key at
    or before the tile's last query; otherwise tile ``n_kv - 1``."""
    assert n_q < 2**15 and n_kv < 2**15, (n_q, n_kv)
    out = []
    for i in range(n_q):
        end = min(((i + 1) * block_q - 1) // block_kv, n_kv - 1) \
            if causal else n_kv - 1
        out += [i << 16 | j << 1 | (j == end) for j in range(end + 1)]
    return np.asarray(out, np.int32)


def unpack(entry):
    """A :func:`pair_table` entry -> (q tile, kv tile, last)."""
    return entry >> 16, (entry >> 1) & 0x7FFF, entry & 1


def _vmem_bytes(group: int, block_q: int, block_kv: int, hd: int,
                dtype) -> int:
    """A grid step's scoped VMEM, lanes padded to 128: double-buffered
    q, o, k and v blocks in ``dtype``, and per query, float32: two hd
    columns (the accumulator and its update), a column of scores and the
    m and l lanes.  It bounds from above, by 10% or more, what Mosaic
    allocates for a v5e at groups 1, 3 and 9, tiles 128 to 512, hd 64
    and 256, bf16 and float32."""
    isz = jnp.dtype(dtype).itemsize
    lanes = -(-hd // 128) * 128
    blocks = 4 * (group * block_q + block_kv) * lanes * isz
    per_query = 8 * hd + 4 * block_kv + 2 * 8 * 4
    return blocks + group * block_q * per_query


def head_group(group: int, block_q: int, block_kv: int, hd: int,
               dtype) -> int:
    """The largest divisor of the GQA ``group`` whose heads one grid
    step can hold in :data:`VMEM_BUDGET` at these tiles (at least 1)."""
    for g in range(group, 1, -1):
        if group % g == 0 and _vmem_bytes(g, block_q, block_kv, hd,
                                          dtype) <= VMEM_BUDGET:
            return g
    return 1


def _flash_kernel(tab_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  acc_ref, *, scale: float, block_q: int, block_kv: int,
                  causal: bool, kv_len: int):
    qi, kj, last = unpack(tab_ref[pl.program_id(2)])
    g, _, hd = q_ref.shape[1:]
    rows = g * block_q
    q_start = qi * block_q
    k_start = kj * block_kv

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _update(masked: bool):
        q = q_ref[0].reshape(rows, hd)                     # (g*bq, hd)
        k = k_ref[0, 0]                                    # (bk, hd)
        v = v_ref[0, 0]                                    # (bk, hd)
        # scores transposed, keys on sublanes and queries on lanes: the
        # softmax reduces over keys across sublanes, and m, l and the
        # accumulator's columns are one lane a query
        s = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (bk, g*bq)
        if masked:
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_kv, rows), 0)
            if causal:
                # a column's query position: its column within the q tile
                q_pos = q_start + jax.lax.broadcasted_iota(
                    jnp.int32, (block_kv, g, block_q), 2).reshape(
                        block_kv, rows)
                keep = q_pos >= k_pos
            else:
                keep = k_pos < kv_len
            s = jnp.where(keep, s, NEG_INF)
        m_prev = m_ref[...]                                # (1, g*bq)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)                             # (bk, g*bq)
        corr = jnp.exp(m_prev - m_new)                     # (1, g*bq)
        l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=0, keepdims=True)
        acc_ref[...] = corr * acc_ref[...] + jax.lax.dot_general(
            v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (hd, g*bq)
        m_ref[...] = m_new

    if causal:
        straddles = k_start + block_kv - 1 > q_start
    else:
        straddles = k_start + block_kv > kv_len

    @pl.when(straddles)
    def _masked():
        _update(masked=True)

    @pl.when(jnp.logical_not(straddles))
    def _unmasked():
        _update(masked=False)

    @pl.when(last == 1)
    def _finalize():
        o = acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)  # (hd, g*bq)
        o_ref[0] = o.T.reshape(g, block_q, hd).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q",
                                             "block_kv", "kv_len",
                                             "interpret"))
def flash_attention_bhsd(q, k, v, *, causal: bool = True,
                         block_q: int = 128, block_kv: int = 128,
                         kv_len: int = None, interpret: bool = False):
    """q: (B, H, S, hd), k/v: (B, Hkv, S, hd) with ``H % Hkv == 0`` ->
    (B, H, S, hd); query head ``h`` reads KV head ``h // (H // Hkv)``.
    ``kv_len`` (default S) is the number of real keys when S carries a
    padded tail; under ``causal`` the mask already hides that tail from
    the real rows."""
    B, H, S, hd = q.shape
    Hkv = k.shape[1]
    assert k.shape == v.shape == (B, Hkv, S, hd) and H % Hkv == 0, (
        q.shape, k.shape, v.shape)
    block_q = min(block_q, S)
    block_kv = min(block_kv, S)
    assert S % block_q == 0 and S % block_kv == 0, (S, block_q, block_kv)
    g = head_group(H // Hkv, block_q, block_kv, hd, q.dtype)
    blocks_per_kv = H // Hkv // g
    tab = jnp.asarray(pair_table(S // block_q, S // block_kv, block_q,
                                 block_kv, causal))
    kernel = functools.partial(_flash_kernel, scale=1.0 / math.sqrt(hd),
                               block_q=block_q, block_kv=block_kv,
                               causal=causal,
                               kv_len=S if kv_len is None else kv_len)

    def q_map(b, h, t, tab):
        return b, h, unpack(tab[t])[0], 0

    def kv_map(b, h, t, tab):
        return b, h // blocks_per_kv, unpack(tab[t])[1], 0

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // g, tab.shape[0]),
            in_specs=[
                pl.BlockSpec((1, g, block_q, hd), q_map),
                pl.BlockSpec((1, 1, block_kv, hd), kv_map),
                pl.BlockSpec((1, 1, block_kv, hd), kv_map),
            ],
            out_specs=pl.BlockSpec((1, g, block_q, hd), q_map),
            scratch_shapes=[
                pltpu.VMEM((1, g * block_q), jnp.float32),   # m
                pltpu.VMEM((1, g * block_q), jnp.float32),   # l
                pltpu.VMEM((hd, g * block_q), jnp.float32),  # acc
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        interpret=interpret,
    )(tab, q, k, v)
