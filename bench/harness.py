"""Run one cell of ``BENCHMARK.json`` once and print its result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: the configuration as it is run, with
  its plain reference beside it (the file named by its ``reference``);
* ``bench/traffic/<traffic>.json``: the traffic mix, a file of
  parameters whose ``kind`` names the general runner
  ``bench/traffic/<kind>.py`` that reads it;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric;
* ``bench/limits/<workload>.json``: the limit of each number that the
  cell's comparison with its reference computes.

A runner (``bench/traffic/<kind>.py``) exposes ``Runner(cell, seed,
devices)``, whose construction is the set-up (weights from the seed,
compilation, warm-up, the steps that the check follows), and whose
``window(seconds)`` and ``check()`` measure the window and compare what
it produced with the reference.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_traces"


class BenchError(RuntimeError):
    """A cell that cannot run here; the run exits with no result."""


# ---------------------------------------------------------------- spec
def load_spec(root: pathlib.Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    chips: int
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    limits: Dict[str, float]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, spec: Optional[Dict[str, Any]] = None,
            root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic, metrics and
    limits loaded; a name or file that is missing raises BenchError."""
    spec = spec if spec is not None else load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"{name}: no configuration {w['config']!r}")
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if m["moves"] in e2e_names and _applies(m, name)]
    limits_file = root / "bench" / "limits" / f"{name}.json"
    limits = json.loads(limits_file.read_text()) \
        if limits_file.exists() else {}
    return Cell(name, w["config"], config, w["traffic"], traffic,
                int(w["chips"]), e2e, per_layer, limits)


def load_module(path: pathlib.Path):
    """Import a file of the benchmark by its path (names may hold '.'
    and '-', which ``import`` cannot spell)."""
    key = "bench._files." + str(path.relative_to(BENCH)).replace("/", ".")
    if key in sys.modules:
        return sys.modules[key]
    if not path.exists():
        raise BenchError(f"missing benchmark file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def runner_module(cell: Cell):
    return load_module(BENCH / "traffic" / f"{cell.traffic['kind']}.py")


def metric_module(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py")


def reference_module(cell: Cell):
    return load_module(BENCH / "configs" / cell.config["reference"])


def peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise BenchError(f"no published peaks for device {device_kind!r} "
                         f"in bench/peaks.json")
    return table[device_kind]


# ---------------------------------------------------------------- jax
def configure_jax():
    """Persistent compilation cache at the program's fixed directory in
    this checkout, holding every program so that only a cell's first run
    compiles.  Call before JAX starts a backend."""
    from repro.launch.chip import COMPILE_CACHE_DIR
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips_for(cell: Cell):
    """The cell's TPU devices; BenchError where there are none or too
    few."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise BenchError(f"JAX found no accelerator: {e}") from e
    if devs[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (default backend "
                         f"{devs[0].platform!r})")
    if len(devs) < cell.chips:
        raise BenchError(f"{cell.name} needs {cell.chips} chips, "
                         f"JAX found {len(devs)}")
    return devs[:cell.chips]


class CompileCounter:
    """Counts the backend compilations JAX reports while ``active``."""

    def __init__(self):
        import jax
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if self.active and event.endswith("backend_compile_duration"):
            self.count += 1


def memory_peak(devices) -> int:
    """Peak bytes on the fullest chip: the peak of live buffers plus the
    peak that the runtime reserved for programs' temporaries, which the
    TPU runtime keeps out of ``peak_bytes_in_use``."""
    def peak(d):
        s = d.memory_stats() or {}
        return (int(s.get("peak_bytes_in_use", 0))
                + int(s.get("peak_bytes_reserved", 0)))
    return max(peak(d) for d in devices)


# ---------------------------------------------------------------- run
@dataclasses.dataclass
class Window:
    """What a runner's window measured."""
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    counts: Dict[str, Any]


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""
    name: str
    value: float
    limit: Optional[float]

    @property
    def ok(self) -> bool:
        return (self.limit is not None and math.isfinite(self.value)
                and self.value <= self.limit)


def per_layer_metrics(cell: Cell, window: Window, reduced, peak_row,
                      devices) -> Dict[str, Dict[str, Any]]:
    ctx = dict(trace=reduced, counts=window.counts, peaks=peak_row,
               chips=len(devices))
    out = {}
    for m in cell.per_layer:
        value = metric_module(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, devices, peak_row: Dict[str, float]
        ) -> Dict[str, Any]:
    """Set up, measure and check one run; return the result line."""
    import jax
    counter = CompileCounter()
    t_runner = time.perf_counter()
    runner = runner_module(cell).Runner(cell, seed, devices)
    setup_s = time.perf_counter() - t_start
    phases = dict(start_s=t_runner - t_start, **getattr(runner, "phases", {}))
    trace_dir = TRACE_DIR / f"{cell.name}.{seed}"
    counter.active = True
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            window = runner.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
        counter.active = False
    mem = memory_peak(devices)
    t_check = time.perf_counter()
    checks: List[Check] = runner.check()
    phases["check_s"] = time.perf_counter() - t_check
    correct = bool(checks) and all(c.ok for c in checks)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    result: Dict[str, Any] = {"correct": correct,
                              "attempted": window.attempted,
                              "failed": window.failed}
    if trace:
        from bench import trace_reduce
        reduced = trace_reduce.reduce_dir(trace_dir, devices)
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["metrics"] = per_layer_metrics(cell, window, reduced,
                                              peak_row, devices)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["device"] = device
        result["breakdown"] = {"device_ops": reduced.top_ops(10),
                               "idle_gaps": reduced.top_gaps(10)}
    else:
        values = dict(window.end_to_end, setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
    result["compiles_in_window"] = counter.count
    result["setup_phases"] = phases
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def report(result: Dict[str, Any], out=sys.stdout, err=sys.stderr):
    """The compared numbers as the last lines on stderr, then the result
    as the last line on stdout."""
    print(f"setup phases (s): {result['setup_phases']}; compiles in the "
          f"window: {result['compiles_in_window']}", file=err, flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)


def main(workload: str, seed: int, seconds: float, trace: bool,
         t_start: float) -> int:
    try:
        cell = resolve(workload)
        configure_jax()
        devices = chips_for(cell)
        result = run(cell, seed, seconds, trace, t_start, devices,
                     peaks(devices[0].device_kind))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 1
    report(result)
    return 0
