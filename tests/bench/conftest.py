"""Cells of BENCHMARK.json cut to a size the CPU can run in a test."""
import copy

import jax
import pytest

from bench import harness

#: widths small enough for the CPU, with every axis a distinct length
TINY = {
    "smollm-135m": dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                        d_ff=128, vocab=512, head_dim=16),
    "zamba2-7b-1p": dict(n_layers=4, d_model=64, n_heads=4, n_kv_heads=4,
                         d_ff=128, vocab=512, head_dim=16, ssm_state=16,
                         ssm_head_dim=16, ssm_chunk=32, attn_every=2),
}
#: stands in for a chip's peaks in runs on the CPU
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _tiny_cell(name: str, batch: int = 4, seq: int = 64) -> harness.Cell:
    """The cell ``name`` with its configuration's widths and its traffic's
    sizes cut down; its limits are the committed ones."""
    c = harness.resolve(name)
    c.config = copy.deepcopy(c.config)
    c.config["arch"].update(TINY[c.config_name])
    c.traffic = dict(c.traffic, batch=batch, seq=seq,
                     mesh={"data": 1, "model": 1})
    c.chips = 1
    return c


def _run_cpu(cell: harness.Cell, seed: int = 2**31 + 5, seconds: float = 0.5):
    """One run of ``cell`` on the CPU, past the harness's look for a
    chip."""
    return harness.run(cell, seed, seconds, False, 0.0, jax.devices()[:1],
                       CPU_PEAKS)


@pytest.fixture
def tiny_cell():
    return _tiny_cell


@pytest.fixture
def cpu_run():
    return _run_cpu
