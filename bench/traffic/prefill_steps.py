"""Prefill steps through ``runtime/stepfn.build_prefill_step``.

Mix parameters (``bench/traffic/<mix>.json``): ``batch`` and ``seq``
(sequences of ``seq`` prompt tokens per step), ``zipf_a``, ``mesh``,
and ``runtime``.

Set-up makes the weights from the seed and compiles and warms the step
on batch 0.  Window step n prefills batch n + 1.  The outputs (last
logits and cache) of one window step among the first three, drawn from
the seed, are kept and compared, every sequence of it, with the plain
reference once the window has closed (see ``bench/compare.py``).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import compare, harness, steps, tokens, weights, work


class Runner:
    def __init__(self, cell: harness.Cell, seed: int, devices):
        from repro.configs.base import ShapeConfig
        from repro.models.model import build_model
        from repro.runtime.stepfn import build_prefill_step
        self.cell = cell
        t = cell.traffic
        self.arch, self.rt = steps.arch_and_runtime(cell)
        self.mesh = steps.mesh(t["mesh"], devices)
        self.shape = ShapeConfig(cell.traffic_name, t["seq"], t["batch"],
                                 "prefill")
        self.bundle = build_prefill_step(self.arch, self.shape, self.rt,
                                         self.mesh)
        model = build_model(self.arch)
        self.logical = model.logical()
        self.cache_axes = compare.batch_axes(model.cache_shapes(
            t["batch"], t["seq"], self.rt)[1])
        self.step_tokens = t["batch"] * t["seq"]
        self.start(seed)

    def host_tokens(self, i: int):
        t = self.cell.traffic
        return tokens.rows(self.seed, i, t["batch"], t["seq"],
                           self.arch.vocab, t["zipf_a"])

    def batch(self, i: int):
        with jax.profiler.TraceAnnotation("bench.feed"):
            return jax.device_put({"tokens": self.host_tokens(i)},
                                  self.bundle.in_shardings[1])

    def start(self, seed: int):
        """Weights from ``seed``; the step compiled and run once."""
        self.seed = seed
        t = time.perf_counter()
        self.params = weights.make(self.bundle.args[0], self.logical, seed,
                                   shardings=self.bundle.in_shardings[0])
        jax.block_until_ready(self.params)
        self.phases = {"weights_s": time.perf_counter() - t}
        t = time.perf_counter()
        jax.block_until_ready(self.bundle.fn(self.params, self.batch(0)))
        self.phases["first_step_s"] = time.perf_counter() - t
        rng = np.random.default_rng([int(seed) % 2**64, 7])
        self.sample = int(rng.integers(3))
        self.kept = None

    def step(self, i: int):
        """Prefill batch ``i`` (the calibration reads one step alone)."""
        return self.bundle.fn(self.params, self.batch(i))

    def window(self, seconds: float) -> harness.Window:
        failed = []

        def dispatch(n):
            out = self.step(n + 1)
            if n == self.sample:
                self.kept = (n + 1, out)
            return out[0]

        def done(logits):
            failed.append(not np.isfinite(np.asarray(logits)).all())

        n, elapsed = steps.run(dispatch, done, seconds)
        if self.kept is None:          # a window shorter than the sample
            self.kept = (n, self.step(n))
        ops = work.prefill_step_ops(self.cell.config["arch"],
                                    self.shape.global_batch,
                                    self.shape.seq_len, self.rt.compute_dtype)
        counts = {"kind": "prefill", "steps": n, "ops": ops,
                  "step_flops": work.flops(ops)}
        return harness.Window(n, int(sum(failed)),
                              {"tokens_per_s": n * self.step_tokens / elapsed},
                              counts)

    def check(self):
        i, (logits, cache) = self.kept
        self.params = self.kept = None
        return compare.checks(self.numbers(i, logits, cache),
                              self.cell.limits)

    def numbers(self, i, logits, cache, matmul: str = "f32"):
        """Compare the program's outputs of batch ``i`` with the
        reference's, every sequence."""
        ref = harness.reference_module(self.cell)
        toks = self.host_tokens(i)
        ref_l, ref_c = compare.reference_prefill(self, ref, toks)
        axes = self.cache_axes
        if matmul != "f32":            # the control stands in for the program
            logits, cache = compare.reference_prefill(self, ref, toks,
                                                      matmul)
            logits, axes = logits[:, None, :], None
        return compare.prefill_numbers(logits, cache, ref_l, ref_c,
                                       self.arch.vocab, axes)
