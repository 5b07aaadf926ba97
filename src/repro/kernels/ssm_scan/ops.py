"""Jitted public wrapper: model-zoo layout (B,S,H,P) -> kernel layout."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import tiling
from repro.kernels.ssm_scan.ssm_scan import ssm_scan_grid


def ssm_scan(X, Bm, Cm, dt, la, *, chunk: int = 256):
    """X: (B,S,H,P); Bm/Cm: (B,S,N); dt/la: (B,S,H) -> (Y, h_final).

    The chunk is fitted to the tile of X's dtype (kernels/tiling.py).
    A sequence with no aligned divisor is padded at the end with steps
    of zero input and zero log decay, which leave the state unchanged,
    so the final state is exact and Y is sliced back to S."""
    B, S, H, P = X.shape
    N = Bm.shape[-1]
    S_pad, (chunk,) = tiling.fit_blocks(S, (chunk,),
                                        tiling.sublane_tile(X.dtype))
    nc = S_pad // chunk
    pad = lambda t: jnp.pad(t, ((0, 0), (0, S_pad - S))
                            + ((0, 0),) * (t.ndim - 2))
    Xg = pad(X).reshape(B, nc, chunk, H, P).transpose(0, 3, 1, 2, 4)
    Bg = pad(Bm).reshape(B, nc, chunk, N)
    Cg = pad(Cm).reshape(B, nc, chunk, N)
    # (B, H, nc, 1, Q): each chunk's step sizes are one whole row
    dtg = pad(dt).reshape(B, nc, 1, chunk, H).transpose(0, 4, 1, 2, 3)
    lag = pad(la).reshape(B, nc, 1, chunk, H).transpose(0, 4, 1, 2, 3)
    Y, hF = ssm_scan_grid(Xg, Bg, Cg, dtg, lag, chunk=chunk,
                          interpret=tiling.interpret_mode())
    Y = Y.transpose(0, 2, 3, 1, 4).reshape(B, S_pad, H, P)[:, :S]
    return Y, hF
