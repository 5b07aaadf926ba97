"""Chunked Mamba2/SSD scan Pallas-TPU kernel.

TPU adaptation of the SSD algorithm (DESIGN.md §2.2): the within-chunk
quadratic term is tiled per (batch, head, chunk) so the (Q x Q) decay
matrix lives only in VMEM (the XLA reference materializes it in HBM for
every head), and the cross-chunk recurrence exploits the TPU grid's
sequential last axis: the running (P x N) state is VMEM scratch carried
across chunk steps — no HBM round-trip between chunks.

Grid: (B, H, S/chunk).  Layouts prepared by ops.py:
  X  (B, H, nc, Q, P)   token inputs (head-split)
  Bm (B, nc, Q, N)      input projections (shared across heads)
  Cm (B, nc, Q, N)      output projections (shared across heads)
  dt (B, H, nc, 1, Q)   step sizes
  la (B, H, nc, 1, Q)   log decay (dt * A)
The singleton axis makes each dt/la block a whole (1, Q) row, which
meets Mosaic's tiling rule for any number of chunks.
Outputs: Y (B, H, nc, Q, P); final state (B, H, P, N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, b_ref, c_ref, dt_ref, la_ref, y_ref, hout_ref,
                h_ref, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    X = x_ref[0, 0, 0].astype(jnp.float32)          # (Q, P)
    Bm = b_ref[0, 0].astype(jnp.float32)            # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)            # (Q, N)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)        # (1, Q)
    la = la_ref[0, 0, 0].astype(jnp.float32)        # (1, Q)

    # Mosaic has no cumsum and no vector transpose, so the prefix sums
    # and the (1, Q) -> (Q, 1) turns are masked row/column reductions
    # over the (Q, Q) grid: exact f32 adds on the vector unit.
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = rows >= cols
    diag = rows == cols
    cum_c = jnp.sum(jnp.where(causal, la, 0.0), axis=1,
                    keepdims=True)                   # (Q, 1) prefix sums
    cum_r = jnp.sum(jnp.where(diag, cum_c, 0.0), axis=0,
                    keepdims=True)                   # (1, Q)
    dt_c = jnp.sum(jnp.where(diag, dt, 0.0), axis=1,
                   keepdims=True)                    # (Q, 1)
    # within-chunk: scores[t, j] = (C_t . B_j) * exp(cum_t - cum_j) * dt_j
    Lmat = jnp.exp(jnp.where(causal, cum_c - cum_r, -jnp.inf))
    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))   # (Q, Q)
    scores = G * Lmat * dt
    y = scores @ X                                   # (Q, P) intra
    # inter-chunk: y_t += exp(cum_t) * C_t . h_prev
    h = h_ref[...]                                   # (P, N)
    y = y + jnp.exp(cum_c) * jax.lax.dot_general(
        Cm, h, (((1,), (1,)), ((), ())))
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)
    # state update: h = exp(cum_last) * h + sum_j w_j X_j (x) B_j
    cum_last = cum_c[chunk - 1:chunk, :]             # (1, 1)
    w = dt_c * jnp.exp(cum_last - cum_c)             # (Q, 1)
    h_ref[...] = jnp.exp(cum_last) * h + jax.lax.dot_general(
        w * X, Bm, (((0,), (0,)), ((), ())))

    @pl.when(ci == nc - 1)
    def _emit_state():
        hout_ref[0, 0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_scan_grid(X, Bm, Cm, dt, la, *, chunk: int = 256,
                  interpret: bool = False):
    """See module docstring for layouts.  Returns (Y, h_final)."""
    B, H, nc, Q, P = X.shape
    N = Bm.shape[-1]
    assert Q == chunk
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, Q), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, Q), lambda b, h, c: (b, h, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc, Q, P), X.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(X, Bm, Cm, dt, la)
