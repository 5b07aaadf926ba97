"""bench/run.py prints no result where there is no TPU, or no program."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _no_result(p):
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_run_refuses_a_host_without_a_tpu():
    _no_result(_run(ROOT))


def test_run_refuses_a_checkout_that_holds_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(tmp_path))


def test_harness_refuses_cpu_devices():
    with pytest.raises(harness.BenchError):
        harness.chips_for(harness.resolve(CELL["name"]))
    assert jax.devices()[0].platform == "cpu"
