"""Cell kinds — the one place the tuner tells its kinds of cell apart.

A **step** cell (spec ``arch:shape[:pod|multipod]``) tunes a model
step on the roofline model (core/trial.py); a **kernel** cell (spec
``kernel:<name>:<shape>``, arch ``kernel-<name>``) times one Pallas
kernel's tiles (core/kernel_cell.py); a **serve** cell (spec
``serve:<arch>:<trace>``, arch ``serve-<arch>``) replays a traffic
trace (serving/evaluator.py).  The kind rides in the arch prefix, so
cell keys, checkpoints, leases and history records carry no kind field.

A :class:`CellKind` binds a kind's prefix and spec head to the logic in
its own module; :func:`kind_of` / :func:`base_name` answer every
"which kind" question, and :class:`DispatchEvaluator` routes both
tiers' defaults.  A new kind is one more record in :func:`kinds`.  This
is the only ``core/`` module that imports the serving stack, when the
table is first built, so importing it is cheap and cycle-free.
"""
from __future__ import annotations

import dataclasses
import functools
import pathlib
from typing import Any, Callable, Mapping, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class CellKind:
    """One kind of cell and everything the tuner does differently for
    it."""
    name: str            # "step" | "kernel" | "serve"
    prefix: str          # arch prefix ("" for step cells)
    head: str            # spec head ("" for step cells)
    workload: Callable   # (arch, shape, multi_pod) -> Workload
    parse: Callable      # (spec, default_multi_pod) -> CellSpec
    signature: Callable  # (arch, shape, multi_pod) -> warm-start features
    stages: Callable     # CellSpec -> stages (None: the strategy's tree)
    evaluator: Callable  # trial tier, from the serve guard's slo_ttft
    measured: Callable   # measured tier, from (TimingCache, repeats)


def _headed(head: str, make: Callable[[str, str], Any]) -> Callable:
    """The parser of a ``<head>:<name>:<shape>`` spec."""
    def parse(item: str, default_multi_pod: bool = False) -> Any:
        parts = item.strip().split(":")
        if len(parts) != 3 or parts[0] != head:
            raise ValueError(f"bad {head} cell spec {item!r} "
                             f"(want {head}:<name>:<shape>)")
        return make(parts[1], parts[2])
    return parse


@functools.lru_cache(maxsize=None)
def kinds() -> Tuple[CellKind, ...]:
    """Every kind, prefixed kinds first: step cells (empty prefix and
    head) match whatever the others do not."""
    from repro.core import kernel_cell as kc
    from repro.core.campaign import parse_step_cell
    from repro.core.history import step_signature
    from repro.core.measure import CachedMeasure, ReducedWallClock
    from repro.core.trial import RooflineEvaluator, Workload
    from repro.serving import evaluator as se
    return (
        CellKind(
            "kernel", "kernel-", "kernel", kc.KernelWorkload,
            parse=_headed("kernel", kc.kernel_cell),
            signature=kc.kernel_signature,
            stages=kc.kernel_stages,
            evaluator=lambda slo: CachedMeasure(kc.KernelBenchEvaluator()),
            measured=lambda cache, n: CachedMeasure(
                kc.KernelBenchEvaluator(repeats=n), cache=cache,
                repeats=n)),
        CellKind(
            "serve", "serve-", "serve", se.ServeWorkload,
            parse=_headed("serve", se.serve_cell),
            signature=se.serve_signature,
            stages=se.serve_stages,
            evaluator=se.make_serve_evaluator,
            # serve trials are already measured: the re-rank replays the
            # same trace, guard off, keyed like the trial tier (trace
            # content folded in)
            measured=lambda cache, n: se.CachedServe(
                se.ServeEvaluator(), cache=cache, repeats=n)),
        CellKind(
            "step", "", "", Workload,
            parse=parse_step_cell,
            signature=step_signature,
            stages=lambda spec: None,
            evaluator=lambda slo: RooflineEvaluator(),
            measured=lambda cache, n: CachedMeasure(
                ReducedWallClock(repeats=n), cache=cache, repeats=n)),
    )


def kind(name: str) -> CellKind:
    """The kind called ``name``."""
    return next(k for k in kinds() if k.name == name)


def kind_of(arch: str) -> CellKind:
    """The kind of a cell, from its arch or its cell key (which starts
    with the arch)."""
    return next(k for k in kinds() if str(arch).startswith(k.prefix))


def base_name(arch: str) -> str:
    """The kernel or model name inside a cell's arch."""
    return arch[len(kind_of(arch).prefix):]


def parse_cell(item: str, default_multi_pod: bool = False) -> Any:
    """One cell spec, parsed by the kind its head names (a spec with no
    known head is a step cell)."""
    head = item.strip().split(":")[0]
    return next(k for k in kinds() if k.head in (head, "")) \
        .parse(item, default_multi_pod)


# ----------------------------------------------------------- dispatch
class DispatchEvaluator:
    """Route each workload to its kind's evaluator (``evaluators``: kind
    name -> ``(workload, config) -> TrialResult``).  A workload reaches
    its evaluator untouched, so a pure-step campaign decides exactly as
    its bare step evaluator would."""

    def __init__(self, evaluators: Mapping[str, Callable]):
        self.evaluators = dict(evaluators)

    def __call__(self, wl: Any, rt: Any) -> Any:
        return self.evaluators[kind_of(wl.arch).name](wl, rt)


def default_evaluator(slo_ttft: Optional[float] = None
                      ) -> DispatchEvaluator:
    """The trial tier: the roofline model for step cells, the
    timing-cached kernel bench for kernel cells, the timing-cached trace
    replay for serve cells (its SLO guard armed when ``slo_ttft`` is
    given)."""
    return DispatchEvaluator({k.name: k.evaluator(slo_ttft)
                              for k in kinds()})


def default_measured_evaluator(cache_dir: Optional[pathlib.Path] = None,
                               repeats: int = 3) -> DispatchEvaluator:
    """The measured tier when none is injected: the reduced wall-clock
    proxy for step cells, the kernel bench for kernel cells, the
    guard-off trace replay for serve cells — all behind one disk timing
    cache (``cache_dir`` defaults to ``results/trials/timings``)."""
    from repro.core.measure import TimingCache
    cache = TimingCache(cache_dir)
    return DispatchEvaluator({k.name: k.measured(cache, repeats)
                              for k in kinds()})
