"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed beside the CPU backend, and it compiles
for a topology that is described, not attached.  These tests hand it
the four Pallas kernels at real model widths (one ragged length each)
and the prefill and decode steps that ``serving.BatchScheduler`` jits
at full-width smollm-135m, and check
that each compiles to a Mosaic kernel (``tpu_custom_call``) within one
chip's 16 GB.  Nothing runs, so they say nothing about results or
times.

The kernel wrappers pick interpret mode from the default backend, which
is the CPU here; each test steers them to Mosaic with ``monkeypatch``.
The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library.  The persistent
compilation cache is off around these compiles, because an entry for a
described chip cannot be read back here.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import tiling

HBM_BYTES = 16e9        # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    monkeypatch.setattr(tiling, "interpret_mode", lambda: False)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


@pytest.mark.parametrize("B,S,H,Hkv,hd,bq,bk", [
    (4, 2048, 9, 3, 64, 128, 128),      # smollm-135m prefill
    (16, 2048, 9, 3, 64, 128, 128),
    (4, 1003, 9, 3, 64, 128, 128),      # ragged: no aligned divisor, padded
    (1, 4096, 32, 32, 112, 128, 128),   # zamba2-7b attention
    (1, 1000, 32, 32, 112, 128, 128),
    (1, 4096, 32, 32, 112, 512, 512),
    # one smollm-135m prefill wave, 16 x 2048, at the tile knobs' values
    (16, 2048, 9, 3, 64, 256, 256),
    (16, 2048, 9, 3, 64, 512, 512),
    (16, 2048, 9, 3, 64, 512, 128),
    (16, 2048, 9, 3, 64, 128, 512),
    (2, 2048, 9, 1, 64, 512, 512)])     # a group of 9 split to 3 for VMEM
def test_flash_attention_compiles(one_chip, mosaic, B, S, H, Hkv, hd, bq,
                                  bk):
    from repro.kernels.flash_attention.ops import flash_attention
    q = _sds((B, S, H, hd), "bfloat16", one_chip)
    kv = _sds((B, S, Hkv, hd), "bfloat16", one_chip)
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                             block_q=bq, block_kv=bk),
             q, kv, kv)


@pytest.mark.parametrize("S,length", [(4096, 3000), (1056, 1040)])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_flash_decode_compiles(one_chip, mosaic, S, length, kv_dtype):
    from repro.kernels.flash_decode.ops import flash_decode
    B, H, Hkv, hd = 8, 9, 3, 64     # smollm-135m decode
    q = _sds((B, 1, H, hd), "bfloat16", one_chip)
    kv = _sds((B, S, Hkv, hd), kv_dtype, one_chip)
    if kv_dtype == "int8":
        sc = _sds((B, S, Hkv, 1), "float32", one_chip)
        _compile(lambda q, k, v, ks, vs: flash_decode(
            q, k, v, length, ks, vs, block_kv=512), q, kv, kv, sc, sc)
    else:
        _compile(lambda q, k, v: flash_decode(q, k, v, length,
                                              block_kv=512), q, kv, kv)


@pytest.mark.parametrize("B,S,H,P,N,chunk,dtype", [
    (1, 4096, 112, 64, 64, 256, "bfloat16"),   # zamba2-7b, 16 chunks
    (1, 1000, 112, 64, 64, 256, "bfloat16"),   # ragged, padded to 1024
    (1, 512, 2, 8, 8, 128, "float32")])        # kernel cell "tiny"
def test_ssm_scan_compiles(one_chip, mosaic, B, S, H, P, N, chunk, dtype):
    from repro.kernels.ssm_scan.ops import ssm_scan
    X = _sds((B, S, H, P), dtype, one_chip)
    BC = _sds((B, S, N), dtype, one_chip)
    d = _sds((B, S, H), "float32", one_chip)
    _compile(lambda X, Bm, Cm, dt, la: ssm_scan(X, Bm, Cm, dt, la,
                                                chunk=chunk),
             X, BC, BC, d, d)


@pytest.mark.parametrize("rows,d", [(8192, 576), (8192, 3584),
                                    (1001, 576)])
def test_rmsnorm_compiles(one_chip, mosaic, rows, d):
    from repro.kernels.rmsnorm.ops import rmsnorm
    _compile(lambda x, s: rmsnorm(x, s), _sds((rows, d), "bfloat16",
                                              one_chip),
             _sds((d,), "bfloat16", one_chip))


def _scheduler(one_chip):
    """A full-width smollm-135m BatchScheduler on the Pallas kernels
    (wave of 8, 1056-token cache) and its parameters as shapes."""
    from repro.configs import get_config
    from repro.core.params import default_config
    from repro.serving.scheduler import BatchScheduler
    rt = default_config(compute_dtype="bfloat16", attn_impl="pallas")
    sched = BatchScheduler(get_config("smollm-135m"), rt, params=None,
                           wave_size=8, max_seq=1056)
    return sched, _place(sched.model.param_shapes(), one_chip)


def _place(tree, sharding):
    return jax.tree.map(lambda s: _sds(s.shape, s.dtype, sharding), tree)


def _check_step(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


def test_serving_decode_step_compiles(one_chip, mosaic):
    """The decode step BatchScheduler jits, one token per lane."""
    sched, params = _scheduler(one_chip)
    cache = _place(sched.model.cache_shapes(8, 1056, sched.rt)[0], one_chip)
    tok = _sds((8, 1), "int32", one_chip)
    _check_step(sched._decode.lower(params, cache, tok).compile())


def test_serving_prefill_step_compiles(one_chip, mosaic):
    """The prefill step BatchScheduler jits, for prompts left-padded to
    a ragged 1003 tokens: flash attention and RMSNorm pad to aligned
    blocks inside the layer scan."""
    sched, params = _scheduler(one_chip)
    batch = {"tokens": _sds((8, 1003), "int32", one_chip)}
    _check_step(sched._prefill.lower(params, batch).compile())


def test_olmoe_prefill_cell_compiles_within_one_chip(topo, mosaic):
    """The step of the benchmark's cell ``olmoe-1b-7b-4l.prefill-8x4k``:
    four OLMoE layers with all 64 experts held, bf16 weights, 8 x 4096
    tokens, through flash attention, the RMSNorm kernel (QK-norm too) and
    the compiler's grouped-matmul kernels of ``jax.lax.ragged_dot``."""
    import numpy as np
    from jax.sharding import AxisType, Mesh

    from bench import harness, steps
    from repro.configs.base import ShapeConfig
    from repro.runtime.stepfn import build_prefill_step
    cell = harness.resolve("olmoe-1b-7b-4l.prefill-8x4k")
    arch, rt = steps.arch_and_runtime(cell)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    t = cell.traffic
    bundle = build_prefill_step(arch, ShapeConfig("c", t["seq"], t["batch"],
                                                  "prefill"), rt, mesh)
    compiled = bundle.lower().compile()
    text = compiled.as_text()
    assert "ragged-dot" in text
    _check_step(compiled)
