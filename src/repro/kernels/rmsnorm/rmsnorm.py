"""Fused RMSNorm Pallas-TPU kernel.

One pass over HBM: reads a (rows x d) tile into VMEM, computes the
row-wise rms in f32 and writes the scaled result — the unfused XLA path
reads x twice (mean-of-squares, then normalize).  d stays whole per tile
(reductions are row-local); rows per tile sized to VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * s_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows",
                                             "interpret"))
def rmsnorm_2d(x, scale, *, eps: float = 1e-5, block_rows: int = 256,
               interpret: bool = False):
    """x: (N, d), scale: (d,) -> (N, d)."""
    N, d = x.shape
    block_rows = min(block_rows, N)
    assert N % block_rows == 0, (N, block_rows)
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(N // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, d), x.dtype),
        interpret=interpret,
    )(x, scale)
