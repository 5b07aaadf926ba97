"""Jitted public wrapper: (B, S, H, hd) layout used by the model zoo.

On the CPU backend (tests) the kernel body runs in interpret mode; on
the TPU it compiles to Mosaic (kernels/tiling.interpret_mode).  The XLA reference path stays the dry-run
default so cost_analysis reflects honest HLO (DESIGN.md §2.2).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import tiling
from repro.kernels.flash_attention.flash_attention import flash_attention_bhsd


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_kv: int = 128):
    """q: (B, S, H, hd), k/v: (B, S, Hkv, hd) with ``H % Hkv == 0``
    (GQA: not repeated to H heads) -> (B, S, H, hd).

    Blocks are fitted to the dtype's tile (kernels/tiling.py); a ragged
    S with no aligned divisor is padded at the end and sliced back."""
    S = q.shape[1]
    S_pad, (bq, bk) = tiling.fit_blocks(S, (block_q, block_kv),
                                        tiling.sublane_tile(q.dtype))
    pad = ((0, 0), (0, 0), (0, S_pad - S), (0, 0))
    qt, kt, vt = (jnp.pad(t.transpose(0, 2, 1, 3), pad) for t in (q, k, v))
    o = flash_attention_bhsd(qt, kt, vt, causal=causal, block_q=bq,
                             block_kv=bk, kv_len=S,
                             interpret=tiling.interpret_mode())
    return o[:, :, :S].transpose(0, 2, 1, 3)
