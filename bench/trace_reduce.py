"""Reduce a JAX profiler trace to the numbers the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes; it is read
with ``jax.profiler.ProfileData``, nothing else.  Its device planes are
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
operation run on the chip, named by its HLO text.  The host plane
``/host:CPU`` holds the benchmark's own spans
(``jax.profiler.TraceAnnotation``), all named ``bench.<what>``;
``bench.window`` covers the measured window.

* busy: the union of the chip's operation intervals inside the window;
* idle gaps: the complement, each gap credited to the innermost
  benchmark span that covers its middle (``(no span)`` where none does);
* operation and kernel time: sums of event durations by instruction
  name; a ``while`` op (a loop, such as the scan over layers) holds
  other ops and is left out of the sums, not out of busy.
"""
from __future__ import annotations

import dataclasses
import glob
import pathlib
import re
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "bench.window"
LOOP = re.compile(r"%while\b")

Interval = Tuple[float, float]


@dataclasses.dataclass
class Event:
    name: str
    start: float          # seconds on the trace's clock
    end: float


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                      # averaged over the chips
    ops: Dict[str, float]              # device seconds by op name, all chips
    gaps: Dict[str, float]             # idle seconds by host span, chip 0
    device_events: List[Event]         # chip 0, inside the window
    chips: int

    def top_ops(self, n: int) -> List[list]:
        return [[k, v] for k, v in
                sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int) -> List[list]:
        return [[k, v] for k, v in
                sorted(self.gaps.items(), key=lambda kv: -kv[1])[:n]]

    def kernel(self, name: str) -> Tuple[int, float]:
        """(calls, device seconds) on the first chip of the custom call
        named after the kernel's wrapper ``name`` (``%name.<n>``)."""
        rx = re.compile(rf"%{re.escape(name)}(\.\d+)?$")
        evs = [e for e in self.device_events if rx.match(e.name)]
        return len(evs), sum(e.end - e.start for e in evs)


# ---------------------------------------------------------------- intervals
def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """a minus b, both sorted and disjoint."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ---------------------------------------------------------------- reading
def op_name(text: str) -> str:
    """An XLA op event's name is its HLO text; its instruction name is
    the part before " = " (``%flash_attention_bhsd.6``)."""
    return text.split(" = ", 1)[0]


def _events(line, device: bool) -> List[Event]:
    return [Event(op_name(e.name) if device else e.name, e.start_ns * 1e-9,
                  (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def read(path: str):
    """(device planes {chip: {line: [Event]}}, host spans [Event]) of an
    ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices: Dict[int, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            devices[int(m.group(1))] = {
                ln.name: _events(ln, ln.name == "XLA Ops")
                for ln in plane.lines}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(e for e in _events(ln, False)
                            if e.name.startswith("bench."))
    return devices, host


def reduce(devices: Dict[int, Dict[str, List[Event]]], host: List[Event],
           chip_ids: Optional[List[int]] = None) -> Reduced:
    windows = [e for e in host if e.name == WINDOW]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = windows[0].start, windows[0].end
    chip_ids = sorted(devices) if chip_ids is None else chip_ids
    if not chip_ids or any(c not in devices for c in chip_ids):
        raise ValueError(f"the trace holds chips {sorted(devices)}, the "
                         f"run used {chip_ids}")
    spans = [e for e in host if e.name != WINDOW]
    busy_total = 0.0
    ops: Dict[str, float] = {}
    first = None
    for c in chip_ids:
        evs = [e for e in devices[c].get("XLA Ops", [])
               if e.end > lo and e.start < hi]
        busy = union(clip([(e.start, e.end) for e in evs], lo, hi))
        busy_total += length(busy)
        for e in evs:
            if not LOOP.match(e.name):
                ops[e.name] = ops.get(e.name, 0.0) + (e.end - e.start)
        if first is None:
            first = (c, evs, busy)
    _, evs0, busy0 = first
    gaps: Dict[str, float] = {}
    for s, e in subtract([(lo, hi)], busy0):
        mid = 0.5 * (s + e)
        cover = [h for h in spans if h.start <= mid <= h.end]
        name = min(cover, key=lambda h: h.end - h.start).name \
            if cover else "(no span)"
        gaps[name] = gaps.get(name, 0.0) + (e - s)
    n = len(chip_ids)
    return Reduced(window_s=hi - lo, busy_s=busy_total / n, ops=ops,
                   gaps=gaps, device_events=evs0, chips=n)


def find(trace_dir) -> str:
    files = glob.glob(str(pathlib.Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"{trace_dir}: {len(files)} trace files, want 1")
    return files[0]


def reduce_dir(trace_dir, devices) -> Reduced:
    """Reduce the one trace under ``trace_dir`` for the chips ``devices``
    (JAX devices; their ids name the planes)."""
    devs, host = read(find(trace_dir))
    return reduce(devs, host, [d.id for d in devices])
