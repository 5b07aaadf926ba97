#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout on a host with the TPU chips the cell
asks for.  It sets up the cell (weights from the seed, compilation from
the persistent cache in ``.jax_compile_cache/``, warm-up), measures for
``--seconds``, compares what the window produced with the plain
reference, and prints the result as one JSON line, last on stdout; the
numbers compared, each beside its limit, are the last lines on stderr.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  With no TPU, or
too few chips, it exits with 1 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        from repro.launch import chip
        from bench import harness
    except ImportError as e:
        print(f"bench: the program is not in this checkout: {e}",
              file=sys.stderr)
        return 1
    if not chip.tpu_host():
        print("bench: no TPU chip is attached, or JAX_PLATFORMS keeps JAX "
              "off it", file=sys.stderr)
        return 1
    return harness.main(args.workload, args.seed, args.seconds,
                        bool(args.trace), T_START)


if __name__ == "__main__":
    sys.exit(main())
