"""Jitted public wrapper: accepts (..., d), flattens leading dims."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import tiling
from repro.kernels.rmsnorm.rmsnorm import rmsnorm_2d


def rmsnorm(x, scale, *, eps: float = 1e-5, block_rows: int = 256):
    """Accepts (..., d); the row block is fitted to the dtype's tile
    (kernels/tiling.py) and a row count with no aligned divisor is
    padded with zero rows, which are sliced off again."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    N = x2.shape[0]
    N_pad, (br,) = tiling.fit_blocks(N, (block_rows,),
                                     tiling.sublane_tile(x.dtype))
    y = rmsnorm_2d(jnp.pad(x2, ((0, N_pad - N), (0, 0))), scale, eps=eps,
                   block_rows=br, interpret=tiling.interpret_mode())
    return y[:N].reshape(shape)
