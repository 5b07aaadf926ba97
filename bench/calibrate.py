#!/usr/bin/env python3
"""Readings that the limits in ``bench/limits/<workload>.json`` are set
from, made in one process on the chip at the cell's own size.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 7,8,9] [--fault-seeds 7,8,9] [--out FILE]

For each of ``--seeds``: the program's numbers against the float32
reference, through the cell's own compiled step (the lower readings).
For each of ``--control-seeds``: the same numbers of the control, the
reference computed with float8 matrix products, standing in for the
program (the upper readings).  For each of ``--fault-seeds``, in a train
cell: the numbers of the program fed a batch whose second half repeats
its first.  The benchmark's own runs do not run any of this.  Prints
one JSON object, and writes it to ``--out`` if given.
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def calibrate(cell, devices, seeds, control_seeds=(), fault_seeds=(),
              log=print):
    from bench import compare, harness
    runner = harness.runner_module(cell).Runner(cell, seeds[0], devices)
    ref = harness.reference_module(cell)
    train = cell.traffic["kind"] == "train_steps"
    out = {"workload": cell.name, "program": {}, "control": {}, "fault": {}}

    def program(seed, fault=""):
        if train:
            runner.start(seed, fault)
            runner.state = None
            return compare.train_numbers(runner.observed,
                                         compare.reference_train(runner, ref))
        runner.start(seed)
        logits, cache = runner.step(1)
        runner.params = None
        return runner.numbers(1, logits, cache)

    def control(seed):
        runner.seed = seed
        if train:
            runner.state = None
            return compare.train_numbers(
                compare.reference_train(runner, ref, "fp8"),
                compare.reference_train(runner, ref))
        runner.params = None
        return runner.numbers(1, None, None, matmul="fp8")

    for what, fn, which in (("program", program, seeds),
                            ("control", control, control_seeds),
                            ("fault", lambda s: program(s, "half_batch"),
                             fault_seeds if train else ())):
        for s in which:
            t = time.perf_counter()
            out[what][str(s)] = fn(s)
            log(f"{what} seed {s}: {out[what][str(s)]} "
                f"({time.perf_counter() - t:.1f} s)")
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=_seeds, required=True)
    p.add_argument("--control-seeds", type=_seeds, default=[])
    p.add_argument("--fault-seeds", type=_seeds, default=[])
    p.add_argument("--out")
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    cell = harness.resolve(args.workload)
    harness.configure_jax()
    devices = harness.chips_for(cell)
    out = calibrate(cell, devices, args.seeds, args.control_seeds,
                    args.fault_seeds,
                    log=lambda m: print(m, file=sys.stderr, flush=True))
    text = json.dumps(out)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
