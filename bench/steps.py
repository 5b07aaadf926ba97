"""Parts shared by the step runners (``bench/traffic/*_steps.py``)."""
from __future__ import annotations

import collections
import time

import jax
import numpy as np


def arch_and_runtime(cell):
    """The program's ArchConfig of the cell's configuration and the
    TunableConfig its traffic mix sets."""
    from repro.configs.base import ArchConfig
    from repro.core.params import TunableConfig
    rt = TunableConfig(**cell.traffic["runtime"])
    rt.validate()
    return ArchConfig(**cell.config["arch"]), rt


def mesh(shape, devices):
    """A (data, model) mesh over ``devices``."""
    from jax.sharding import AxisType, Mesh
    data, model = int(shape["data"]), int(shape["model"])
    if data * model != len(devices):
        raise ValueError(f"mesh {data}x{model} needs {data * model} chips, "
                         f"the cell has {len(devices)}")
    devs = np.array(devices).reshape(data, model)
    return Mesh(devs, ("data", "model"), axis_types=(AxisType.Auto,) * 2)


#: steps kept in flight: while the host waits for the oldest, the next
#: two are queued on the device, so that a stall of the host shorter
#: than two steps leaves the chip busy
DEPTH = 3


def run(dispatch, done, seconds: float):
    """Dispatch steps 0, 1, ... until ``seconds`` have passed, waiting
    for the oldest step once ``DEPTH`` are in flight.

    ``dispatch(n)`` enqueues step n and returns what to wait for;
    ``done(x)`` receives it once the step has finished.  The window runs
    from the first dispatch to the end of the last step, so it holds all
    the work and all the time of the steps it counts.  Returns (steps,
    seconds)."""
    t0 = time.perf_counter()
    pending, n = collections.deque(), 0
    while True:
        with jax.profiler.TraceAnnotation("bench.step"):
            pending.append(dispatch(n + len(pending)))
        if len(pending) == DEPTH:
            with jax.profiler.TraceAnnotation("bench.sync"):
                done(jax.block_until_ready(pending.popleft()))
            n += 1
        if time.perf_counter() - t0 >= seconds:
            break
    while pending:
        with jax.profiler.TraceAnnotation("bench.sync"):
            done(jax.block_until_ready(pending.popleft()))
        n += 1
    return n, time.perf_counter() - t0
