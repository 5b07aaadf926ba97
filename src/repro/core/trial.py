"""Black-box trial runner (the paper's "experimental run").

A trial = one configuration of the 12 knobs applied to one workload cell
(arch x shape x mesh).  The application is a black box: the runner only
observes a scalar cost.

Two evaluators:
  * RooflineEvaluator — lower+compile on the production mesh, cost =
    analytic roofline step time (compile-only, on the CPU backend's
    placeholder devices even on a chip host, DESIGN.md §2.2).
    A config whose compiled peak memory exceeds per-chip HBM *crashes*,
    exactly like the paper's sort-by-key 0.1/0.7 run.
  * WallClockEvaluator — median of N real executions (the paper's
    protocol; used on real hardware and in the CPU examples/tests).

Trial-throughput engine: the expensive unit of the whole reproduction is
the calibration compile, and most knobs never reach the compiled HLO
(core/params.COMPILE_KNOBS / ANALYTIC_KNOBS).  The four calibration
compiles per trial are therefore memoized in a two-level
:class:`CompileCache` — an in-memory LRU in front of a disk cache —
keyed by ``TunableConfig.compile_key()`` (the compile projection), not
the full config hash.  A sweep over ``attn_block_q/kv``, ``comm_codec``
or ``kv_cache_dtype`` reuses one compile and recomputes only the
analytic roofline terms; the observed cost of every trial is bit-equal
to what the naive (compile-every-time) evaluator produces.  The cache is
thread-safe with in-flight deduplication so the parallel sweep executor
(core/executor.py) never compiles the same program twice concurrently.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import json
import os
import pathlib
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from repro.configs import get_config, get_shape
from repro.configs.base import ArchConfig, ShapeConfig
from repro.core import costmodel
from repro.core import telemetry as _telemetry
from repro.core.params import TunableConfig

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / "results" / "trials"

# ------------------------------------------------------- failure taxonomy
# Every crashed trial is classified so the layers above can react
# differently (ISSUE 6): deterministic failures stay memoized and scored
# (the config is genuinely bad), transient ones are retryable, timeouts
# come from the executor deadline, and worker-death is assigned post hoc
# by the quarantine ledger (the evaluation never returned at all).
FAILURE_DETERMINISTIC = "deterministic"
FAILURE_TRANSIENT = "transient"
FAILURE_TIMEOUT = "timeout"
FAILURE_WORKER_DEATH = "worker-death"

#: Environment faults that may succeed on retry.  TimeoutError and
#: ConnectionError are OSError subclasses, so disk/NFS hiccups, host
#: OOM and socket drops all land here; everything else (shape errors,
#: HBM overflow, XLA lowering failures) is deterministic per program.
_TRANSIENT_TYPES = (OSError, MemoryError)


def classify_exception(e: BaseException) -> str:
    """Map an evaluator exception to a failure class.  An exception that
    already carries a ``.failure`` attribute (e.g. :class:`TrialError`
    re-raised from a memoized entry) keeps its class."""
    tagged = getattr(e, "failure", "")
    if tagged:
        return tagged
    if isinstance(e, _TRANSIENT_TYPES):
        return FAILURE_TRANSIENT
    return FAILURE_DETERMINISTIC


class TrialError(RuntimeError):
    """An evaluator failure that carries its classification."""

    def __init__(self, message: str, failure: str = FAILURE_DETERMINISTIC):
        super().__init__(message)
        self.failure = failure


@dataclasses.dataclass
class TrialResult:
    cost_s: float                  # observed "runtime" (black-box metric)
    crashed: bool = False
    error: str = ""
    roofline: Optional[Dict] = None
    peak_bytes: Optional[float] = None
    fits_hbm: bool = True
    compile_s: float = 0.0
    cached: bool = False
    compiles: int = 0              # fresh XLA compiles this trial paid for
    failure: str = ""              # taxonomy class when crashed ("" if not)
    retries: int = 0               # transient retries this result absorbed

    @property
    def retryable(self) -> bool:
        return self.crashed and self.failure == FAILURE_TRANSIENT

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class Workload:
    """One tunable application instance (cell)."""
    arch: str
    shape: str
    multi_pod: bool = False

    @property
    def cfg(self) -> ArchConfig:
        return get_config(self.arch)

    @property
    def shp(self) -> ShapeConfig:
        return get_shape(self.shape)

    def key(self) -> str:
        return f"{self.arch}__{self.shape}__" + \
            ("multipod" if self.multi_pod else "pod")


class CompileCache:
    """Two-level memo of calibration-compile measurements.

    Level 1 is an in-memory LRU (per process); level 2 is the disk cache
    under ``results/trials/compiles``.  Keys are opaque strings built
    from (cell, calibration point, scan/unroll variant, compile
    projection).  Values are small JSON dicts — either a serialized
    :class:`costmodel.Roofline` or ``{"error": ..., "failure": ...}``
    for a program that failed to build/compile.  Only *deterministic*
    failures are memoized like successes; a transient fault (classified
    via :func:`classify_exception` — e.g. an ``OSError`` from the disk
    cache or a host OOM under a parallel sweep) is returned to its
    waiters but never remembered, so the next lookup rebuilds.

    ``get_or_build`` is thread-safe with in-flight deduplication: when N
    executor threads ask for the same key, one runs the builder and the
    rest block on its result.

    The disk level is also multi-*process* safe (the campaign fabric,
    core/fabric.py, shares one cache directory across workers): every
    write goes to a uniquely-named tempfile in the cache directory and
    is published with an atomic ``os.replace``, so two workers building
    the same key concurrently each publish a complete entry (last
    writer wins — the values are deterministic per key, so both wrote
    the same bytes); a reader that still encounters a torn/corrupt
    entry (e.g. left behind by a pre-fabric writer that crashed
    mid-write) treats it as a miss and rebuilds, repairing the entry.
    """

    #: telemetry event prefix — subclasses with their own semantics
    #: (the measured tier's TimingCache) override, so the metrics
    #: aggregator can report compile-cache and timing-cache hit rates
    #: separately
    CACHE_KIND = "cache"

    def __init__(self, directory: Optional[pathlib.Path] = None,
                 mem_entries: int = 512, use_disk: bool = True):
        self.dir = pathlib.Path(directory) if directory else \
            CACHE_DIR / "compiles"
        self.mem_entries = mem_entries
        self.use_disk = use_disk
        self._mem: "collections.OrderedDict[str, Dict]" = \
            collections.OrderedDict()
        self._lock = threading.Lock()
        self._inflight: Dict[str, threading.Event] = {}
        self.hits = 0
        self.misses = 0

    def _path(self, key: str) -> pathlib.Path:
        return self.dir / f"{key}.json"

    def _mem_put(self, key: str, val: Dict) -> None:
        self._mem[key] = val
        self._mem.move_to_end(key)
        while len(self._mem) > self.mem_entries:
            self._mem.popitem(last=False)

    def _lookup(self, key: str) -> Optional[Dict]:
        """One locked probe of memory then disk (caller holds no lock)."""
        with self._lock:
            if key in self._mem:
                self._mem.move_to_end(key)
                return self._mem[key]
        if self.use_disk:
            p = self._path(key)
            try:
                val = json.loads(p.read_text())
            except (OSError, ValueError):
                # missing, or torn by a crashed writer / a concurrent
                # non-atomic producer: treat as a miss and rebuild
                return None
            if not isinstance(val, dict):
                return None
            with self._lock:
                self._mem_put(key, val)
            return val
        return None

    def _disk_put(self, key: str, val: Dict) -> None:
        """Publish one entry atomically (core/fsutil.atomic_publish),
        safe against concurrent writers in other processes."""
        from repro.core.fsutil import atomic_publish
        self.dir.mkdir(parents=True, exist_ok=True)
        atomic_publish(self._path(key), json.dumps(val),
                       prefix=f".{key}.")

    def get_or_build(self, key: str, builder: Callable[[], Dict]) -> Dict:
        tel = _telemetry.current()
        while True:
            val = self._lookup(key)
            if val is not None:
                with self._lock:
                    self.hits += 1
                if tel.enabled:
                    tel.emit(f"{self.CACHE_KIND}.hit", key=key)
                return val
            with self._lock:
                ev = self._inflight.get(key)
                if ev is None:
                    self._inflight[key] = threading.Event()
                    self.misses += 1
                    break
            ev.wait()       # another thread is compiling this program
        if tel.enabled:
            tel.emit(f"{self.CACHE_KIND}.miss", key=key)
        try:
            val = builder()
            # memoization policy by failure class: successes go to both
            # levels; deterministic build errors are memoized in-memory
            # only (persisting them would outlive the run that observed
            # them); transient faults are memoized NOWHERE — the caller
            # sees this one failure, and the next lookup of the same key
            # rebuilds instead of replaying a stale environment hiccup
            if self.use_disk and "error" not in val:
                self._disk_put(key, val)
            if val.get("failure") != FAILURE_TRANSIENT:
                with self._lock:
                    self._mem_put(key, val)
            return val
        finally:
            with self._lock:
                self._inflight.pop(key).set()

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "mem_entries": len(self._mem)}


class RooflineEvaluator:
    """cost = calibrated analytic roofline seconds of the compiled step.

    XLA counts `while` bodies once, so instead of one full compile the
    evaluator compiles two small UNROLLED variants (1 and 3 layer-units)
    and extrapolates every term to the true depth
    (core/costmodel.calibration_points) — which also makes a trial ~10x
    cheaper than compiling the full stack.

    The four calibration compiles are memoized in a :class:`CompileCache`
    keyed by ``TunableConfig.compile_key()`` — configs that differ only
    in analytic knobs share one set of compiles (see module docstring).
    """

    def __init__(self, mesh_factory: Callable = None, use_cache: bool = True,
                 hbm_limit: float = None,
                 compile_cache: Optional[CompileCache] = None):
        from repro.launch.mesh import placeholder_production_mesh
        self._mesh_factory = mesh_factory or placeholder_production_mesh
        self.use_cache = use_cache
        self.hbm_limit = hbm_limit or costmodel.HW["hbm_per_chip"]
        self.compile_cache = compile_cache or \
            (CompileCache() if use_cache else
             CompileCache(use_disk=False, mem_entries=0))
        # per-trial accounting shared across threads
        self._acct = threading.local()
        self.total_compiles = 0
        self._count_lock = threading.Lock()

    # ------------------------------------------------------------- keys
    def _compile_id(self, wl: Workload, mesh, point_units: int,
                    rt_variant: TunableConfig) -> str:
        ck = rt_variant.compile_key(kind=wl.shp.kind, family=wl.cfg.family)
        # mesh axis ORDER matters for sharding — keep it in the key
        blob = json.dumps([wl.key(), point_units, list(mesh.shape.items()),
                           ck], sort_keys=True, default=str)
        h = hashlib.sha1(blob.encode()).hexdigest()[:16]
        return f"{wl.key()}__u{point_units}__{h}"

    # --------------------------------------------------------- compiles
    def _roofline_at(self, cfg, shape, rt: TunableConfig, mesh,
                     multi_pod: bool):
        from repro.runtime.stepfn import build_step
        bundle = build_step(cfg, shape, rt, mesh)
        with mesh:
            compiled = bundle.lower().compile()
        return costmodel.analyze(
            compiled, compute_dtype=rt.compute_dtype,
            pod_size=256 if multi_pod else 10**9)

    def _measured(self, wl: Workload, mesh, point_cfg, units_tag: int,
                  rt_variant: TunableConfig) -> costmodel.Roofline:
        """One memoized calibration compile -> Roofline (raises the
        memoized error if this program deterministically fails)."""
        key = self._compile_id(wl, mesh, units_tag, rt_variant)
        built = []

        def build() -> Dict:
            built.append(True)
            with _telemetry.current().span("compile", cell=wl.key(),
                                           key=key) as sp:
                t0 = time.time()
                try:
                    rl = self._roofline_at(point_cfg, wl.shp, rt_variant,
                                           mesh, wl.multi_pod)
                    return {"roofline": rl.as_dict(),
                            "compile_s": round(time.time() - t0, 2)}
                except Exception as e:
                    # classify BEFORE memoizing: only deterministic program
                    # failures may be remembered (the cache skips transient
                    # entries), so an OSError from the disk cache is not
                    # permanently recorded as a crashed program
                    sp.note(error=True)
                    return {"error": f"{type(e).__name__}: {e}"[:500],
                            "failure": classify_exception(e),
                            "compile_s": round(time.time() - t0, 2)}

        entry = self.compile_cache.get_or_build(key, build)
        acct = self._trial_acct()
        acct["cache_reads"] += 1
        if built:
            acct["compiles"] += 1
            acct["compile_s"] += entry.get("compile_s", 0.0)
            with self._count_lock:
                self.total_compiles += 1
        if "error" in entry:
            raise TrialError(entry["error"],
                             failure=entry.get("failure",
                                               FAILURE_DETERMINISTIC))
        return costmodel.roofline_from_dict(entry["roofline"])

    def _trial_acct(self) -> Dict[str, Any]:
        if not hasattr(self._acct, "d"):
            self._acct.d = {"compiles": 0, "compile_s": 0.0,
                            "cache_reads": 0}
        return self._acct.d

    # ------------------------------------------------------------ trial
    def calibrated_roofline(self, wl: Workload, rt: TunableConfig):
        """Compute + collective terms from two small UNROLLED compiles
        (while bodies count once, §7.1); PEAK memory from two small
        SCANNED compiles (buffer reuse only shows up scanned); the
        MEMORY term from the first-principles analytic traffic model
        (§7.3 — XLA-CPU 'bytes accessed' is unreliable for HBM traffic).
        The pallas-vs-xla attention distinction and every knob
        (remat/microbatch/dtypes/tiles/donation) enter analytically."""
        mesh = self._mesh_factory(multi_pod=wl.multi_pod)
        points, units = costmodel.calibration_points(wl.cfg)
        rt_unroll = rt.replace(unroll_layers=True, attn_impl="xla")
        r1 = self._measured(wl, mesh, points[0][0], 1, rt_unroll)
        r3 = self._measured(wl, mesh, points[1][0], 3, rt_unroll)
        rl = costmodel.extrapolate_roofline(r1, r3, units)
        rt_scan = rt.replace(unroll_layers=False, attn_impl="xla")
        p1 = self._measured(wl, mesh, points[0][0], 1, rt_scan)
        p3 = self._measured(wl, mesh, points[1][0], 3, rt_scan)
        peak = costmodel.extrapolate(p1.peak_mem_bytes or 0.0,
                                     p3.peak_mem_bytes or 0.0, units)
        data_size = 1
        for a in ("pod", "data"):
            data_size *= mesh.shape.get(a, 1)
        model_size = mesh.shape.get("model", 1)
        mem_bytes = costmodel.analytic_memory_bytes(
            wl.cfg, wl.shp, rt, data_size, model_size)
        if rt.attn_impl == "pallas":
            pcorr = costmodel.flash_peak_correction_bytes(
                wl.cfg, wl.shp, rt, data_size, model_size)
            peak = max(peak * 0.02, peak - pcorr)
        return dataclasses.replace(
            rl, memory_s=mem_bytes / costmodel.HW["hbm_bw"],
            bytes_per_chip=mem_bytes, peak_mem_bytes=peak)

    def __call__(self, wl: Workload, rt: TunableConfig) -> TrialResult:
        acct = self._trial_acct()
        acct["compiles"], acct["compile_s"] = 0, 0.0
        acct["cache_reads"] = 0
        try:
            rl = self.calibrated_roofline(wl, rt)
            peak = rl.peak_mem_bytes
            fits = peak is None or peak <= self.hbm_limit
            res = TrialResult(cost_s=rl.total_s, crashed=not fits,
                              roofline=rl.as_dict(), peak_bytes=peak,
                              fits_hbm=fits,
                              failure="" if fits else FAILURE_DETERMINISTIC)
        except Exception as e:
            # TrialError already carries the stored "TypeName: msg"
            err = str(e) if isinstance(e, TrialError) \
                else f"{type(e).__name__}: {e}"
            res = TrialResult(cost_s=float("inf"), crashed=True,
                              error=err[:500],
                              failure=classify_exception(e))
        res.compiles = acct["compiles"]
        res.compile_s = round(acct["compile_s"], 1)
        # "served from cache" requires the trial to have actually reached
        # a cache lookup — a trial that dies before any calibration
        # compile (e.g. in the mesh factory) was not cached, it crashed
        res.cached = acct["compiles"] == 0 and acct["cache_reads"] > 0
        return res


def _zeros_args(bundle) -> Tuple:
    """Concrete zero-filled arguments matching a bundle's abstract
    argument structs (the default when no ``make_args`` is supplied —
    timing does not care about values, only shapes/dtypes/shardings)."""
    import jax.numpy as jnp
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), bundle.args)


class WallClockEvaluator:
    """The paper's protocol: median of n repeats of the real step.

    Hardened to the same contract as :class:`RooflineEvaluator` (the
    measured tier, core/measure.py, runs it under the deadline/retry
    executor): exceptions are classified through
    :func:`classify_exception` — a :class:`TrialError` raised by a
    caching wrapper keeps its pre-tagged class — and every result
    carries ``compiles``/``compile_s``/``cached`` accounting, so
    measured crashes and costs land in checkpoints/history with the
    full PR-6 taxonomy instead of a bare string.

    ``make_args`` defaults to zero-filled concrete arguments derived
    from the step bundle; ``mesh_factory`` defaults to the production
    mesh over the default backend's devices, which only a 256-chip pod
    has — on a one- or four-chip host pass a factory over those chips.
    Tile knobs are validated against the cell's
    sequence length up front, so a non-dividing ``attn_block_q/kv`` is
    a clean deterministic-crash trial, not a Pallas grid assertion.
    """

    def __init__(self, mesh_factory: Optional[Callable] = None,
                 make_args: Optional[Callable] = None,
                 repeats: int = 5):
        if mesh_factory is None:
            from repro.launch.mesh import make_production_mesh
            mesh_factory = make_production_mesh
        self._mesh_factory = mesh_factory
        self._make_args = make_args     # (wl, rt, mesh) -> concrete args
        self.repeats = repeats

    def __call__(self, wl: Workload, rt: TunableConfig) -> TrialResult:
        from repro.core.space import SPACE
        from repro.runtime.stepfn import build_step
        t0 = time.time()
        compile_s = 0.0
        compiles = 0
        try:
            SPACE.validate(rt, seq_len=wl.shp.seq_len)
            mesh = self._mesh_factory(multi_pod=wl.multi_pod)
            bundle = build_step(wl.cfg, wl.shp, rt, mesh)
            args = self._make_args(wl, rt, mesh) \
                if self._make_args is not None else _zeros_args(bundle)
            with mesh:
                c0 = time.time()
                compiled = bundle.fn.lower(*args).compile()
                compile_s = round(time.time() - c0, 2)
                compiles = 1
                ts = []
                for _ in range(self.repeats):
                    t1 = time.time()
                    out = compiled(*args)
                    jax.block_until_ready(out)
                    ts.append(time.time() - t1)
                    if rt.donate_buffers and bundle.kind == "train":
                        args = (out[0], out[1], args[2])
                    elif rt.donate_buffers and bundle.kind == "decode":
                        args = (args[0], out[1], args[2])
            return TrialResult(cost_s=float(np.median(ts)),
                               compiles=compiles, compile_s=compile_s)
        except Exception as e:
            # TrialError already carries the stored "TypeName: msg"
            err = str(e) if isinstance(e, TrialError) \
                else f"{type(e).__name__}: {e}"
            return TrialResult(cost_s=float("inf"), crashed=True,
                               error=err[:500],
                               failure=classify_exception(e),
                               compiles=compiles,
                               compile_s=compile_s or
                               round(time.time() - t0, 2))


@dataclasses.dataclass
class TrialLogEntry:
    name: str
    delta: Dict[str, Any]
    config: Dict[str, Any]
    result: Dict[str, Any]
    accepted: Optional[bool] = None
    note: str = ""


class TrialRunner:
    """Counts and logs every run (the paper's <=10-runs budget is checked
    by tests against this counter).

    ``history`` is an optional emission hook ``(workload, rt, name,
    result, delta) -> None`` (see :meth:`~repro.core.history
    .TrialHistory.sink`): every *evaluated* trial is forwarded to it,
    so campaigns accumulate a persistent trial history; trials replayed
    from a checkpoint (``record(..., replayed=True)``) were already
    emitted by the run that evaluated them and are not re-emitted.
    """

    def __init__(self, workload: Workload, evaluator: Callable,
                 history: Optional[Callable] = None):
        self.workload = workload
        self.evaluator = evaluator
        self.history = history
        self.log: list[TrialLogEntry] = []

    @property
    def n_trials(self) -> int:
        return len(self.log)

    def run(self, rt: TunableConfig, name: str,
            delta: Dict[str, Any] = None) -> TrialResult:
        res = self.evaluator(self.workload, rt)
        self.record(rt, name, res, delta)
        return res

    def record(self, rt: TunableConfig, name: str, res: TrialResult,
               delta: Dict[str, Any] = None,
               replayed: bool = False) -> TrialResult:
        """Log an already-evaluated trial (parallel executor path).

        Exactly one log entry per evaluated configuration — the run
        budget counts evaluations, however they were scheduled."""
        self.log.append(TrialLogEntry(
            name=name, delta=delta or {}, config=rt.as_dict(),
            result={k: v for k, v in res.as_dict().items()
                    if k != "roofline"}))
        if self.history is not None and not replayed:
            self.history(self.workload, rt, name, res, delta or {})
        return res
