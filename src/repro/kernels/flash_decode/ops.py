"""Jitted public wrapper: model-zoo layout (B,1,H,hd) q + (B,S,Hkv,hd)
cache -> (B,1,H,hd)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import tiling
from repro.kernels.flash_decode.flash_decode import flash_decode_bhsd


def flash_decode(q, k_cache, v_cache, length, k_scale=None, v_scale=None,
                 *, block_kv: int = 512):
    """q: (B,1,H,hd); caches: (B,S,Hkv,hd) [+ (B,S,Hkv,1) scales];
    length: scalar int32 live length.  A cache whose length has no
    aligned divisor is padded at the end; the kernel masks every
    position at or past ``length``."""
    B, _, H, hd = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    S_pad, (bk,) = tiling.fit_blocks(S, (block_kv,),
                                     tiling.sublane_tile(k_cache.dtype))
    pad = ((0, 0), (0, 0), (0, S_pad - S), (0, 0))
    tr = lambda t: (jnp.pad(t.transpose(0, 2, 1, 3), pad)
                    if t is not None else None)
    o = flash_decode_bhsd(q.transpose(0, 2, 1, 3), tr(k_cache),
                          tr(v_cache), tr(k_scale), tr(v_scale),
                          jnp.asarray([length], jnp.int32),
                          block_kv=bk, n_rep=H // Hkv,
                          interpret=tiling.interpret_mode())
    return o.transpose(0, 2, 1, 3).astype(q.dtype)
