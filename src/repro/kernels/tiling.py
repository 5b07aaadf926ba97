"""Shared tile fitting and backend choice for the public kernel wrappers.

Mosaic, the TPU kernel compiler, accepts a block whose second-to-last
dimension is a multiple of the dtype's sublane tile (8 rows for 32-bit
types, 16 for bfloat16, 32 for int8) or spans the whole array
dimension.  The public ops wrappers accept *any* shape — model-zoo
callers pass ragged sequence lengths — so each wrapper fits the
requested block to that rule with :func:`fit_blocks`: the largest
aligned divisor of the length when one exists, otherwise an aligned
block and a length padded up to a multiple of it.  The kernels mask
(or the wrappers slice off) the padded tail.  Every block the wrappers
hand Mosaic is aligned, including the single block of a short length.

The *tuner* is stricter on purpose: a tile knob that does not divide
the cell's sequence is a clean deterministic-crash trial
(``Knob.validate_tile``, core/space.py) — silent re-fitting during
tuning would alias distinct knob values to one measured config.

:func:`interpret_mode` picks how a kernel runs: Mosaic on the TPU, the
Pallas interpreter on the CPU backend (tests), and an error anywhere
else — never a quiet fall back to the interpreter.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import numpy as np


def interpret_mode() -> bool:
    """True on the CPU backend, False on the TPU; any other backend
    cannot run these kernels and raises."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"the Pallas TPU kernels cannot run on the "
                           f"{backend!r} backend")
    return backend == "cpu"


def sublane_tile(dtype) -> int:
    """Rows in one native TPU tile of ``dtype``: 8 for 32-bit types,
    16 for 16-bit, 32 for 8-bit."""
    return 8 * max(1, 4 // np.dtype(dtype).itemsize)


def round_up(n: int, m: int) -> int:
    return -(-int(n) // m) * m


def fit_block(block: int, n: int, align: int = 8) -> int:
    """Aligned block for a gridded dimension of length ``n``.

    The request is rounded down to a multiple of ``align`` (at least
    ``align``).  A length that fits in it gets one block, rounded up to
    ``align``; a longer one gets the largest multiple of ``align`` not
    above the request that divides ``n``, or the request itself when
    none does (the caller then pads ``n``).  The scan is bounded by
    ``block / align`` steps.
    """
    cap = max(align, int(block) // align * align)
    if n <= cap:
        return round_up(max(int(n), 1), align)
    for b in range(cap, align - 1, -align):
        if n % b == 0:
            return b
    return cap


def fit_blocks(n: int, blocks: Sequence[int], align: int
               ) -> Tuple[int, Tuple[int, ...]]:
    """``(n_padded, fitted_blocks)`` for blocks that all grid one
    dimension of length ``n``: no padding when every fitted block
    divides ``n``; otherwise ``n`` is padded to a multiple of the
    largest fitted block and the blocks are fitted to that length."""
    fitted = tuple(fit_block(b, n, align) for b in blocks)
    if all(n % b == 0 for b in fitted):
        return n, fitted
    n_pad = round_up(n, max(fitted))
    return n_pad, tuple(fit_block(b, n_pad, align) for b in blocks)
