"""Train steps through ``runtime/stepfn.build_train_step``.

Mix parameters (``bench/traffic/<mix>.json``): ``batch`` and ``seq``
(global batch in rows of ``seq`` tokens), ``zipf_a`` (token
distribution), ``mesh`` ({data, model} chips), ``runtime`` (the
``TunableConfig`` fields the cell sets) and ``check_steps``.

Set-up makes the weights and the optimizer state from the seed, compiles
the step and runs the first ``check_steps`` steps through it on batches
0, 1, ...; the window continues the same state on the following batches.
The check follows those first steps with the plain reference (see
``bench/compare.py``).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, harness, steps, tokens, weights, work


class Runner:
    def __init__(self, cell: harness.Cell, seed: int, devices):
        from repro.configs.base import ShapeConfig
        from repro.models.model import build_model
        from repro.runtime.stepfn import build_train_step
        self.cell, self.seed = cell, seed
        t = cell.traffic
        self.arch, self.rt = steps.arch_and_runtime(cell)
        self.mesh = steps.mesh(t["mesh"], devices)
        self.shape = ShapeConfig(cell.traffic_name, t["seq"], t["batch"],
                                 "train")
        self.bundle = build_train_step(self.arch, self.shape, self.rt,
                                       self.mesh)
        self.logical = build_model(self.arch).logical()
        self.n_check = int(t["check_steps"])
        self.step_tokens = t["batch"] * t["seq"]
        self.start(seed)

    # ------------------------------------------------------------ set-up
    def host_batch(self, i: int):
        t = self.cell.traffic
        return tokens.lm_batch(self.seed, i, t["batch"], t["seq"],
                               self.arch.vocab, t["zipf_a"])

    def batch(self, i: int, fault: str = ""):
        with jax.profiler.TraceAnnotation("bench.feed"):
            b = self.host_batch(i)
            if fault == "half_batch":
                b = compare.half_batch(b)
            return jax.device_put(b, self.bundle.in_shardings[2])

    def initial_params(self, seed: int):
        return weights.make(self.bundle.args[0], self.logical, seed,
                            shardings=self.bundle.in_shardings[0])

    def start(self, seed: int, fault: str = ""):
        """Weights and optimizer state from ``seed``, then the first
        ``check_steps`` steps; keeps what the check compares."""
        self.seed = seed
        t = time.perf_counter()
        params = self.initial_params(seed)
        opt = jax.jit(lambda: jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), self.bundle.args[1]),
            out_shardings=self.bundle.in_shardings[1])()
        jax.block_until_ready(opt)
        self.phases = {"weights_s": time.perf_counter() - t}
        losses = []
        for i in range(self.n_check):
            t = time.perf_counter()
            b = self.batch(i, fault)
            params, opt, met = self.bundle.fn(params, opt, b)
            losses.append(met["loss"])
            if i == 0:
                grads = compare.first_grads(opt)
                self.phases["first_step_s"] = time.perf_counter() - t
        self.observed = {
            "loss": [float(x) for x in losses],
            "grad": grads,
            "update": compare.leaf_norms_of_change(
                params, lambda: self.initial_params(seed)),
        }
        self.state = (params, opt)
        self.next_batch = self.n_check

    # ------------------------------------------------------------ window
    def window(self, seconds: float) -> harness.Window:
        losses = []

        def dispatch(n):
            params, opt = self.state
            params, opt, met = self.bundle.fn(params, opt,
                                              self.batch(self.next_batch + n))
            self.state = (params, opt)
            return met["loss"]

        def done(loss):
            losses.append(float(loss))

        n, elapsed = steps.run(dispatch, done, seconds)
        self.next_batch += n
        failed = sum(1 for x in losses if not np.isfinite(x))
        counts = {"kind": "train", "steps": n,
                  "step_flops": work.train_step_flops(
                      self.cell.config["arch"], self.shape.global_batch,
                      self.shape.seq_len, self.rt.compute_dtype),
                  "step_programs": ("jit_step",)}
        return harness.Window(n, failed,
                              {"tokens_per_s": n * self.step_tokens / elapsed},
                              counts)

    # ------------------------------------------------------------ check
    def check(self):
        self.state = None
        ref = compare.reference_train(self,
                                      harness.reference_module(self.cell))
        return compare.checks(compare.train_numbers(self.observed, ref),
                              self.cell.limits)
