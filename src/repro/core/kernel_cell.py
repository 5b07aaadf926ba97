"""KernelCell — Pallas tile sweeps as first-class campaign cells.

2403.00995 argues tuning at *stage* granularity beats one global config;
this module opens that surface here: each Pallas kernel's tile knobs
(``block_q`` / ``block_kv`` / scan-chunk / row-block) become a tuned
cell per (kernel, shape), driven through the existing
campaign/strategy/fabric/quarantine machinery unchanged.  Only measured
timing can adjudicate tiles — the roofline model treats them
analytically — so kernel cells are evaluated by
:class:`KernelBenchEvaluator`, which times the jitted kernel itself
(interpret mode on CPU, Mosaic on TPU), wrapped in the measured tier's
disk-backed :class:`~repro.core.measure.TimingCache`.

Design decisions:

  * every kernel's tile knobs are a **projection of the existing
    ``SPACE``** onto :class:`~repro.core.params.TunableConfig` fields
    (``attn_block_q``/``attn_block_kv`` for flash_attention; the q-tile
    field doubles as flash_decode's kv block, ssm_scan's chunk and
    rmsnorm's row block), so quarantine config keys, history records
    and every strategy work without a second config type;
  * a kernel cell is a :class:`~repro.core.campaign.CellSpec` whose
    ``arch`` is ``kernel-<name>`` (core/cells.py) and whose shapes
    come from the :data:`KERNELS` registry — cell keys stay three
    ``__``-separated parts, checkpoints/leases/reports all behave
    identically;
  * a tile that does not divide the shape's sequence length is a
    **clean deterministic-crash trial** (validated up front via
    ``Knob.validate_tile``), exactly like the paper's failed 0.1/0.7
    run — even though the public kernel wrappers themselves self-fit
    ragged shapes for correctness, the tuner never silently aliases
    one tile value to another.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Tuple

import jax
import numpy as np

from repro.configs.base import ShapeConfig
from repro.core.campaign import CellSpec
from repro.core.cells import base_name, kind, kind_of
from repro.core.params import TunableConfig
from repro.core.space import SPACE
from repro.core.tree import Stage
from repro.core.trial import (TrialError, TrialResult, Workload,
                              classify_exception)

# ------------------------------------------------------------- registry
@dataclasses.dataclass(frozen=True)
class KernelShape:
    """One benchmarked shape of a kernel.  ``seq_len`` is the dimension
    the tile knobs must divide; ``dims`` the full argument geometry."""
    name: str
    seq_len: int
    dims: Tuple[Tuple[str, int], ...]

    def dim(self, name: str) -> int:
        return dict(self.dims)[name]


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One tunable kernel: its SPACE tile projection, its shapes, and
    a builder returning ``(fn, args)`` where ``fn`` applies the kernel
    with the config's tiles (jitted by the evaluator)."""
    name: str
    knobs: Tuple[str, ...]
    shapes: Dict[str, KernelShape]
    build: Callable[[KernelShape, TunableConfig], Tuple[Callable, Tuple]]


def _shape(name: str, seq_len: int, **dims: int) -> KernelShape:
    return KernelShape(name, seq_len, tuple(sorted(dims.items())))


def _build_flash_attention(shape: KernelShape, rt: TunableConfig):
    from repro.kernels.flash_attention.ops import flash_attention
    B, H, S, hd = (shape.dim(n) for n in ("B", "H", "S", "hd"))
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, S, H, hd), jnp_dtype())
    k = jax.random.normal(kk, (B, S, H, hd), jnp_dtype())
    v = jax.random.normal(kv, (B, S, H, hd), jnp_dtype())

    def fn(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               block_q=rt.attn_block_q,
                               block_kv=rt.attn_block_kv)
    return fn, (q, k, v)


def _build_flash_decode(shape: KernelShape, rt: TunableConfig):
    import jax.numpy as jnp
    from repro.kernels.flash_decode.ops import flash_decode
    B, H, Hkv, S, hd = (shape.dim(n)
                        for n in ("B", "H", "Hkv", "S", "hd"))
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (B, 1, H, hd), jnp_dtype())
    kc = jax.random.normal(kk, (B, S, Hkv, hd), jnp_dtype())
    vc = jax.random.normal(kv, (B, S, Hkv, hd), jnp_dtype())
    length = jnp.int32(shape.dim("length"))

    def fn(q, kc, vc, length):
        return flash_decode(q, kc, vc, length,
                            block_kv=rt.attn_block_kv)
    return fn, (q, kc, vc, length)


def _build_ssm_scan(shape: KernelShape, rt: TunableConfig):
    from repro.kernels.ssm_scan.ops import ssm_scan
    B, S, H, P, N = (shape.dim(n) for n in ("B", "S", "H", "P", "N"))
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    X = jax.random.normal(ks[0], (B, S, H, P), jnp_dtype())
    Bm = jax.random.normal(ks[1], (B, S, N), jnp_dtype())
    Cm = jax.random.normal(ks[2], (B, S, N), jnp_dtype())
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, H),
                                           jnp_dtype()))
    la = -jax.nn.softplus(jax.random.normal(ks[4], (B, S, H),
                                            jnp_dtype()))

    def fn(X, Bm, Cm, dt, la):
        return ssm_scan(X, Bm, Cm, dt, la, chunk=rt.attn_block_q)
    return fn, (X, Bm, Cm, dt, la)


def _build_rmsnorm(shape: KernelShape, rt: TunableConfig):
    from repro.kernels.rmsnorm.ops import rmsnorm
    rows, d = shape.dim("rows"), shape.dim("d")
    kx, _ = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(kx, (rows, d), jnp_dtype())
    scale = jax.numpy.ones((d,), jnp_dtype())

    def fn(x, scale):
        return rmsnorm(x, scale, block_rows=rt.attn_block_q)
    return fn, (x, scale)


def jnp_dtype():
    import jax.numpy as jnp
    return jnp.float32


#: the tunable kernels.  Shapes are deliberately tiny: the evaluator
#: runs them in interpret mode on CPU (CI), where grid-step dispatch
#: dominates — exactly the overhead the tile knobs trade against VMEM.
#: "ragged" shapes make some tiles *invalid* (non-dividing), producing
#: the paper's deterministic-crash trials inside an otherwise normal
#: sweep.
KERNELS: Dict[str, KernelSpec] = {
    "flash_attention": KernelSpec(
        "flash_attention", ("attn_block_q", "attn_block_kv"),
        {"tiny": _shape("tiny", 256, B=1, H=2, S=256, hd=64),
         "ragged": _shape("ragged", 384, B=1, H=2, S=384, hd=64)},
        _build_flash_attention),
    "flash_decode": KernelSpec(
        "flash_decode", ("attn_block_kv",),
        {"tiny": _shape("tiny", 512, B=1, H=4, Hkv=2, S=512, hd=64,
                        length=384)},
        _build_flash_decode),
    "ssm_scan": KernelSpec(
        "ssm_scan", ("attn_block_q",),
        {"tiny": _shape("tiny", 512, B=1, S=512, H=2, P=8, N=8)},
        _build_ssm_scan),
    "rmsnorm": KernelSpec(
        "rmsnorm", ("attn_block_q",),
        {"tiny": _shape("tiny", 4096, rows=4096, d=512)},
        _build_rmsnorm),
}


# ---------------------------------------------------------------- cells
@dataclasses.dataclass
class KernelWorkload(Workload):
    """A kernel cell's workload: same key/identity contract as a step
    workload, but ``cfg``/``shp`` come from the kernel registry (the
    step-builder path is never taken — kernel cells are evaluated by
    :class:`KernelBenchEvaluator`)."""

    @property
    def cfg(self):
        raise TrialError(f"kernel workload {self.key()} has no arch "
                         "config — route it to KernelBenchEvaluator")

    @property
    def shp(self) -> ShapeConfig:
        ks = KERNELS[base_name(self.arch)].shapes[self.shape]
        return ShapeConfig(self.shape, ks.seq_len, 1, "kernel")


def kernel_cell(kernel: str, shape: str) -> CellSpec:
    """One (kernel, shape) tile-sweep cell, arch ``kernel-<name>``."""
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r} "
                         f"(known: {', '.join(sorted(KERNELS))})")
    if shape not in KERNELS[kernel].shapes:
        raise ValueError(
            f"unknown shape {shape!r} for kernel {kernel!r} "
            f"(known: {', '.join(sorted(KERNELS[kernel].shapes))})")
    return CellSpec(kind("kernel").prefix + kernel, shape, False)


def kernel_signature(arch: str, shape: str, multi_pod: bool = False
                     ) -> Dict:
    """Warm-start similarity features for a kernel cell (the kernel-side
    counterpart of :func:`repro.core.history.cell_signature`)."""
    ks = KERNELS.get(base_name(arch))
    return {
        "arch": arch,
        "shape": shape,
        "kind": "kernel",
        "family": arch,
        "multi_pod": bool(multi_pod),
        "active_knobs": list(ks.knobs) if ks else [],
    }


# --------------------------------------------------------------- stages
def kernel_stages(spec: Any) -> List[Stage]:
    """The tile-sweep tree for one kernel cell: a single joint stage
    whose alternatives are every non-default combination of the
    kernel's tile projection (≤ 8 + baseline — inside the paper's
    ≤ 10-trial budget)."""
    import itertools
    ks = KERNELS[base_name(spec.arch)]
    defaults = {n: SPACE[n].default for n in ks.knobs}
    alts = []
    for combo in itertools.product(*(SPACE[n].domain for n in ks.knobs)):
        delta = {n: v for n, v in zip(ks.knobs, combo)
                 if v != defaults[n]}
        if delta:
            alts.append(delta)
    return [Stage("tiles", SPACE[ks.knobs[0]].spark, alts,
                  kinds=("kernel",))]


# ------------------------------------------------------------ evaluator
class KernelBenchEvaluator:
    """Time the jitted kernel itself: median of N repeats after one
    warm-up (= the compile).  Interpret mode on CPU (the ops wrappers
    select it from the backend) keeps this CI-runnable; the same code
    path compiles to Mosaic on TPU.  Hardened exactly like the other
    evaluators: tile-divisibility is validated up front (clean
    deterministic crash), everything else goes through
    :func:`classify_exception`."""

    def __init__(self, repeats: int = 3):
        self.repeats = repeats

    def __call__(self, wl: Workload, rt: TunableConfig) -> TrialResult:
        t0 = time.time()
        try:
            if kind_of(wl.arch).name != "kernel":
                raise TrialError(f"{wl.key()} is not a kernel cell")
            ks = KERNELS.get(base_name(wl.arch))
            if ks is None or wl.shape not in ks.shapes:
                raise TrialError(f"unknown kernel cell {wl.key()}")
            shape = ks.shapes[wl.shape]
            SPACE.validate(rt)
            for knob in ks.knobs:
                SPACE[knob].validate_tile(getattr(rt, knob),
                                          shape.seq_len)
            fn, args = ks.build(shape, rt)
            jitted = jax.jit(fn)
            c0 = time.time()
            jax.block_until_ready(jitted(*args))
            compile_s = round(time.time() - c0, 2)
            ts = []
            for _ in range(self.repeats):
                t1 = time.time()
                jax.block_until_ready(jitted(*args))
                ts.append(time.time() - t1)
            return TrialResult(cost_s=float(np.median(ts)), compiles=1,
                               compile_s=compile_s)
        except Exception as e:
            err = str(e) if isinstance(e, TrialError) \
                else f"{type(e).__name__}: {e}"
            return TrialResult(cost_s=float("inf"), crashed=True,
                               error=err[:500],
                               failure=classify_exception(e),
                               compile_s=round(time.time() - t0, 2))
