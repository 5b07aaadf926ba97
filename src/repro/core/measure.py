"""The measured wall-clock tier (ISSUE 7).

The paper's whole method is trial-and-error over a *small number of
real experimental runs*; this module is where those few real runs live.
The roofline strategy screens the knob space for ~free, then the
campaign's two-tier re-rank pass (``Campaign(measure_top_k=k)``)
re-evaluates the top-k surviving configs of each cell with median-of-N
real jitted step timings and publishes the measured winner — the
headline number is a measured step time, not a model prediction.

Three pieces:

  * :class:`TimingCache` — the disk-backed timing memo, an instance of
    the two-level :class:`~repro.core.trial.CompileCache` (same atomic
    publish, same in-flight dedup, same memoization-by-failure-class
    policy: successes persist, deterministic crashes stay in-memory
    only, transient faults are never remembered) under
    ``results/trials/timings``.  Keys cover the *full* config dict —
    unlike the compile cache's compile-projection keys, a measured wall
    clock depends on every knob — and the device that took the timing
    (platform and ``device_kind``), so a CPU timing never answers a
    lookup for the chip.
  * :class:`CachedMeasure` — wraps any measured evaluator with the
    timing cache, so repeated measured trials re-pay nothing: a cache
    hit returns the stored cost with ``cached=True, compiles=0``; a
    memoized deterministic crash is re-raised with its stored failure
    class (pre-tagged, so :func:`~repro.core.trial.classify_exception`
    keeps it).
  * :func:`select_top_k` / :class:`ReducedWallClock` — the re-rank
    candidate selection over a cell's trial log, and the step cells'
    measured evaluator: it times the *reduced runnable proxy* of the
    step on a single-device host mesh, which times the CPU and not the
    chip (on a chip host pass a
    :class:`~repro.core.trial.WallClockEvaluator` over a mesh of the
    host's own chips as ``Campaign(measured_evaluator=...)`` instead).
    The measured tier's default, one evaluator per cell kind behind
    this cache, is :func:`repro.core.cells.default_measured_evaluator`.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import pathlib
from typing import Any, Callable, Dict, List, Optional

from repro.core.params import TunableConfig
from repro.core.trial import (CACHE_DIR, CompileCache, TrialResult,
                              WallClockEvaluator, Workload)

#: bump when the measured protocol changes (invalidates stored timings)
MEASURE_VERSION = "measure-v1"

TIMING_DIR = CACHE_DIR / "timings"


# ------------------------------------------------------------ the cache
class TimingCache(CompileCache):
    """Disk-backed measured-timing memo, keyed like the compile cache
    (opaque per-cell strings, JSON values, atomic publish) but over the
    full-config measure key."""

    CACHE_KIND = "timing_cache"      # separate hit-rate in metrics.json

    def __init__(self, directory: Optional[pathlib.Path] = None,
                 mem_entries: int = 512, use_disk: bool = True):
        super().__init__(directory or TIMING_DIR, mem_entries, use_disk)


def device_tag() -> str:
    """``platform/device_kind`` of the default backend's first device:
    the device a measured evaluation runs on."""
    import jax
    d = jax.devices()[0]
    return f"{d.platform}/{d.device_kind}"


def measure_key(wl: Workload, rt: TunableConfig, repeats: int,
                tag: str = MEASURE_VERSION) -> str:
    """Cache key of one measured evaluation: the cell, the *full*
    config (every knob can move a wall clock), the repeat count, the
    protocol version tag and the device (:func:`device_tag`)."""
    blob = json.dumps([tag, device_tag(), wl.key(), int(repeats),
                       rt.as_dict()], sort_keys=True, default=str)
    h = hashlib.sha1(blob.encode()).hexdigest()[:16]
    return f"{wl.key()}__measured__{h}"


class CachedMeasure:
    """Wrap a measured evaluator with the two-level timing cache.

    The wrapped callable keeps the evaluator contract ``(workload,
    config) -> TrialResult``, so it drops into a
    :class:`~repro.core.executor.SweepExecutor` (deadline / retry /
    quarantine) unchanged.  Fresh evaluations pass through with their
    own accounting; cache hits cost nothing (``cached=True``,
    ``compiles=0``); memoized deterministic crashes are replayed with
    their stored failure class.
    """

    def __init__(self, evaluator: Callable, cache: Optional[TimingCache]
                 = None, repeats: Optional[int] = None,
                 tag: str = MEASURE_VERSION):
        self.evaluator = evaluator
        self.cache = cache if cache is not None else TimingCache()
        self.repeats = repeats if repeats is not None \
            else int(getattr(evaluator, "repeats", 0))
        self.tag = tag

    def _key(self, wl: Workload, rt: TunableConfig) -> str:
        """Cache-key hook: subclasses fold extra identity into the key
        (the serve tier adds the trace's content hash)."""
        return measure_key(wl, rt, self.repeats, self.tag)

    def __call__(self, wl: Workload, rt: TunableConfig) -> TrialResult:
        key = self._key(wl, rt)
        fresh: List[TrialResult] = []

        def build() -> Dict:
            from repro.core import telemetry as _telemetry
            with _telemetry.current().span("measure", cell=wl.key()):
                res = self.evaluator(wl, rt)
            fresh.append(res)
            if res.crashed:
                return {"error": res.error, "failure": res.failure,
                        "compile_s": res.compile_s}
            return {"cost_s": res.cost_s, "compile_s": res.compile_s,
                    "compiles": res.compiles}

        entry = self.cache.get_or_build(key, build)
        if fresh:                        # this call ran the evaluator
            return fresh[0]
        if "error" in entry:             # memoized deterministic crash
            return TrialResult(
                cost_s=float("inf"), crashed=True,
                error=entry["error"],
                failure=entry.get("failure", ""), cached=True)
        return TrialResult(cost_s=float(entry["cost_s"]), cached=True,
                           compiles=0, compile_s=0.0)


# ------------------------------------------------- re-rank candidates
def select_top_k(log: List[Any], k: int) -> List[Dict]:
    """The measured tier's candidate list: the k cheapest *distinct,
    surviving* (non-crashed) configs of a cell's trial log, by model
    cost, ties broken by log order.  Each entry is
    ``{"name", "config": TunableConfig, "model_cost_s"}`` —
    ``candidates[0]`` is the model's own ranking choice, which the
    measured winner may overturn."""
    from repro.core.history import config_from_dict
    seen = set()
    entries: List[Dict] = []
    for e in log:
        d = e if isinstance(e, dict) else dataclasses.asdict(e)
        res = d.get("result") or {}
        if res.get("crashed"):
            continue
        ck = json.dumps(d.get("config"), sort_keys=True, default=str)
        if ck in seen:
            continue
        seen.add(ck)
        entries.append(d)
    entries.sort(key=lambda d: d["result"].get("cost_s", float("inf")))
    out = []
    for d in entries[:max(0, int(k))]:
        try:
            cfg = config_from_dict(d["config"])
        except (ValueError, TypeError, KeyError):
            continue                     # older knob space: skip cleanly
        out.append({"name": d.get("name", ""), "config": cfg,
                    "model_cost_s": d["result"].get("cost_s")})
    return out


# --------------------------------------- default measured evaluation
def _measure_mesh(multi_pod: bool = False):
    """A single-device host mesh: always valid on this CPU container
    (the CI environment forces 512 placeholder devices, under which the
    factored host mesh's data axis would not divide a tiny proxy
    batch)."""
    from repro.launch.mesh import make_mesh
    return make_mesh((1, 1), ("data", "model"))


@dataclasses.dataclass
class _ProxyWorkload(Workload):
    """Same cell identity, reduced config + capped shape (runnable on
    one CPU device — the calibration-point idea applied to execution:
    measure the runnable proxy, rank by its real wall clock)."""
    seq_cap: int = 128
    batch_cap: int = 8

    @property
    def cfg(self):
        from repro.configs import get_reduced
        return get_reduced(self.arch)

    @property
    def shp(self):
        from repro.configs import get_shape
        from repro.configs.base import ShapeConfig
        base = get_shape(self.shape)
        return ShapeConfig(f"measure_{base.name}",
                           min(base.seq_len, self.seq_cap),
                           min(base.global_batch, self.batch_cap),
                           base.kind)


class ReducedWallClock:
    """Hardened :class:`WallClockEvaluator` over each cell's reduced
    runnable proxy (CPU infrastructure).  Keeps the cell's identity for
    keys/history; only the executed program is reduced."""

    def __init__(self, repeats: int = 3, seq_cap: int = 128,
                 batch_cap: int = 8):
        self.repeats = repeats
        self.seq_cap = seq_cap
        self.batch_cap = batch_cap
        self._ev = WallClockEvaluator(
            lambda multi_pod=False: _measure_mesh(), None, repeats)

    def __call__(self, wl: Workload, rt: TunableConfig) -> TrialResult:
        proxy = _ProxyWorkload(wl.arch, wl.shape, wl.multi_pod,
                               seq_cap=self.seq_cap,
                               batch_cap=self.batch_cap)
        if proxy.shp.kind == "train" and rt.attn_impl == "pallas":
            # the flash kernel is forward-only (no VJP): executed train
            # steps take attention through the XLA path, exactly like
            # the roofline calibration compiles (core/trial.py); the
            # forward-only prefill/decode kinds keep the real kernel
            rt = rt.replace(attn_impl="xla")
        return self._ev(proxy, rt)
