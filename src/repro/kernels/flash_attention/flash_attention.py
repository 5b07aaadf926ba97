"""Flash attention Pallas-TPU kernel with grouped query heads.

Online-softmax tiling: the S x S score matrix never touches HBM.  Q is
read once; K/V are read in *spans*, blocks of ``span`` keys that stay
in VMEM while the grid walks the query tiles that read them.  The span
is the whole sequence wherever double-buffered K and V fit the scoped
VMEM beside the q and o blocks (:func:`kv_span`), so a KV head's K/V
crosses HBM once a call (:func:`kv_passes`).  The
(block_q x block_kv) tile is the spark.shuffle.file.buffer analogue
(DESIGN.md §2.1 row 8): ``block_q`` query rows against ``block_kv``
keys make one online-softmax update, so the tile sets the score working
set and the number of updates, and the K/V re-fetch only where the
sequence takes several spans.

K/V carry ``Hkv`` heads, a divisor of the ``H`` query heads (GQA).  One
grid step reads a group of the query heads that share a KV head, as a
(group x block_q) row block, against that KV head's K/V span: a group's
heads share each K/V fetch and fill one MXU product.  Where VMEM is
short the span shrinks first; the group is split only where its blocks
would overrun the scoped VMEM at the smallest span (:func:`head_group`).

Grid: (B, H / group, pairs).  The last axis walks a scalar-prefetched
table of (q tile, span) pairs (:func:`pair_table` with the span as its
kv block), each q tile's pairs in a run that starts at span 0 and ends
on its last span, so the online-softmax state (m, l, acc) lives in VMEM
scratch across the run and the output block stays resident.  Under
``causal`` the table holds only the spans with a key at or before some
query of the tile, the diagonal span last; otherwise every span.
Inside a step a loop walks the span's kv tiles of ``block_kv`` keys
that are live for the q tile, in order: the unmasked tiles, then those
that straddle the diagonal (causal) or hold keys at or past ``kv_len``,
a padded tail (non-causal; under ``causal`` the diagonal already hides
that tail from the real rows), which alone take the iota mask.

A tile's scores are held transposed, (block_kv, group x block_q): the
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
    """The grid's (q tile, kv block) pairs in order, packed one int32 a
    pair as ``qi << 16 | kj << 1 | last`` (:func:`unpack`).  Each q
    tile's pairs run kv block 0, 1, ... and ``last`` marks the run's
    end: under ``causal`` the diagonal block, the last holding a key at
    or before the tile's last query; otherwise block ``n_kv - 1``.  The
    kernel's kv block is its span (:func:`kv_span`)."""
    assert n_q < 2**15 and n_kv < 2**15, (n_q, n_kv)
    out = []
    for i in range(n_q):
        end = min(((i + 1) * block_q - 1) // block_kv, n_kv - 1) \
            if causal else n_kv - 1
        out += [i << 16 | j << 1 | (j == end) for j in range(end + 1)]
    return np.asarray(out, np.int32)


def unpack(entry):
    """A :func:`pair_table` entry -> (q tile, kv block, last)."""
    return entry >> 16, (entry >> 1) & 0x7FFF, entry & 1


def _vmem_bytes(group: int, block_q: int, block_kv: int, span: int,
                hd: int, dtype) -> int:
    """A grid step's scoped VMEM, lanes padded to 128: double-buffered
    q and o blocks and K and V spans in ``dtype``, and per query,
    float32: two hd columns (the accumulator and its update), a column
    of scores and one of their exponents, and the m and l lanes.  It
    bounds from above what Mosaic allocates for a v5e at groups 1, 3
    and 9, tiles 128 to 512, spans of 1 and 8 kv tiles, hd 64 and 256,
    bf16 and float32: by 2% or more, where the spans dominate."""
    isz = jnp.dtype(dtype).itemsize
    lanes = -(-hd // 128) * 128
    blocks = 4 * (group * block_q + span) * lanes * isz
    per_query = 8 * hd + 8 * block_kv + 2 * 8 * 4
    return blocks + group * block_q * per_query


def head_group(group: int, block_q: int, block_kv: int, hd: int,
               dtype) -> int:
    """The largest divisor of the GQA ``group`` whose heads one grid
    step can hold in :data:`VMEM_BUDGET` at these tiles, against the
    smallest span, one kv tile (at least 1)."""
    for g in range(group, 1, -1):
        if group % g == 0 and _vmem_bytes(g, block_q, block_kv, block_kv,
                                          hd, dtype) <= VMEM_BUDGET:
            return g
    return 1


def kv_span(S: int, hd: int, dtype, block_q: int, block_kv: int,
            group: int) -> int:
    """The keys of a K/V block a grid step holds: the largest multiple
    of ``block_kv`` that divides ``S`` and whose double-buffered K and
    V fit :data:`VMEM_BUDGET` beside a step of ``group`` query heads
    (``S`` itself where it fits; ``block_kv`` at least)."""
    n = S // block_kv
    for d in range(n, 1, -1):
        if n % d == 0 and _vmem_bytes(group, block_q, block_kv,
                                      d * block_kv, hd,
                                      dtype) <= VMEM_BUDGET:
            return d * block_kv
    return block_kv


def kv_passes(S: int, span: int, block_q: int, causal: bool) -> float:
    """HBM reads of a KV head's K/V in one walk of its q tiles, in
    units of the whole K/V: a span is fetched again wherever the pair
    table's span changes from one step to the next.  1 with one span;
    a sequence of several spans reads the live ones once per q tile."""
    _, sj, _ = unpack(pair_table(S // block_q, S // span, block_q, span,
                                 causal))
    return (1 + int(np.count_nonzero(np.diff(sj)))) * span / S


def _flash_kernel(tab_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  acc_ref, *, scale: float, block_q: int, block_kv: int,
                  causal: bool, kv_len: int):
    qi, sj, last = unpack(tab_ref[pl.program_id(2)])
    g, _, hd = q_ref.shape[1:]
    span = k_ref.shape[2]
    n_tiles = span // block_kv
    rows = g * block_q
    q_start = qi * block_q
    span_start = sj * span

    @pl.when(sj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _update(j, masked: bool):
        off = pl.multiple_of(j * block_kv, block_kv)
        k_start = span_start + off
        q = q_ref[0].reshape(rows, hd)                     # (g*bq, hd)
        k = k_ref[0, 0, pl.ds(off, block_kv)]              # (bk, hd)
        v = v_ref[0, 0, pl.ds(off, block_kv)]              # (bk, hd)
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

    # the span's live tiles [0, n_live) and the first that takes the
    # mask: under causal the first whose last key passes the tile's
    # first query, otherwise the first whose last key is past kv_len
    if causal:
        n_live = jnp.minimum(
            (q_start + block_q - 1 - span_start) // block_kv + 1, n_tiles)
        first_masked = jnp.maximum(q_start - span_start + 1, 0) // block_kv
    else:
        n_live = n_tiles
        first_masked = jnp.maximum(kv_len - span_start, 0) // block_kv
    first_masked = jnp.minimum(first_masked, n_live)

    def _tiles(masked: bool):
        def body(j, carry):
            _update(j, masked)
            return carry
        return body

    jax.lax.fori_loop(0, first_masked, _tiles(False), 0)
    jax.lax.fori_loop(first_masked, n_live, _tiles(True), 0)

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
    span = kv_span(S, hd, q.dtype, block_q, block_kv, g)
    blocks_per_kv = H // Hkv // g
    tab = jnp.asarray(pair_table(S // block_q, S // span, block_q, span,
                                 causal))
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
                pl.BlockSpec((1, 1, span, hd), kv_map),
                pl.BlockSpec((1, 1, span, hd), kv_map),
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
