"""The OLMoE cell: its operation inventory (``bench/families/moe.py``),
the experts' device time (``bench/metrics/_expert_ffn.py``) and its
comparison with the plain reference at a size the CPU holds, under the
cell's committed limits.

The float8 control is not run here: at these widths its cache reading
(0.067-0.073) is under the cell's limit, which the control's reading at
the cell's widths on the chip (0.167-0.196) is above (``PERF.md``,
section 2)."""
import copy

import jax
import pytest

from bench import harness, trace_reduce, work
from bench.metrics import _scope as S
from repro.models import transformer

CELL = "olmoe-1b-7b-4l.prefill-8x4k"
#: OLMoE's block at widths the CPU holds, with the published 64 experts
#: and 8 a token: the benchmark's weights take the expert axis for the
#: fan-in of the experts' matrices, so with fewer experts their outputs
#: grow and a routing that bfloat16 flips near a tie moves a sequence's
#: logits by more than the cell's limit
TINY = dict(n_layers=2, d_model=96, n_heads=4, n_kv_heads=4, head_dim=16,
            d_ff=48, vocab=512, n_experts=64, top_k=8)


def _arch():
    return harness.resolve(CELL).config["arch"]


def test_the_cells_inventory_by_hand():
    """8 x 4096 tokens: 13.19 of the step's 19.83 TFLOP are the experts'
    (T * k rows through three d x d_ff products), each expert's weights
    read once; the QK-norms count as RMSNorms of widths H*hd and
    Hkv*hd."""
    a = _arch()
    ops = work.prefill_step_ops(a, 8, 4096, "bfloat16")
    rows = 8 * 4096 * 8
    e = work.op_work(ops, "expert_ffn")
    assert e["flops"] == 4 * 3 * 2 * rows * 2048 * 1024
    assert e["bytes"] == 4 * 2 * (3 * 64 * 2048 * 1024 + 2 * rows * 2048)
    assert round(e["flops"] / 1e12, 2) == 13.19
    assert round(work.flops(ops) / 1e12, 2) == 19.83
    assert work.op_work(ops, "router")["flops"] == 4 * 2 * 32768 * 2048 * 64
    norms = work.op_work(ops, "rmsnorm")["bytes"]
    row = work.rmsnorm(32768, 2048, "bfloat16")["bytes"]
    assert norms == 4 * 4 * row + work.rmsnorm(8, 2048, "bfloat16")["bytes"]
    assert work.op_work(ops, "mlp") is None
    no_qk = work.prefill_step_ops(dict(a, qk_norm=False), 8, 4096,
                                  "bfloat16")
    assert work.op_work(no_qk, "rmsnorm")["bytes"] == norms - 2 * 4 * row


#: a compiled MoE layer as the TPU compiler writes it: the grouped
#: matmuls' kernels carry the compiler's own op_name, the gate and up
#: products feed a multiply inside ``expert_ffn``, the down product the
#: gather back inside ``moe`` alone
HLO = r"""HloModule jit_step, is_scheduled=true

ENTRY %main (p: bf16[8,4]) -> bf16[8,4] {
  %p = bf16[8,4]{1,0} parameter(0)
  %fusion.1 = bf16[8,4]{1,0} fusion(%p), kind=kLoop, calls=%c, metadata={op_name="jit(step)/while/body/closed_call/moe/gather"}
  %ragged-dot-metadata = s32[9]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %ragged-dot-none = bf16[8,4]{1,0} custom-call(%ragged-dot-metadata, %fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %ragged-dot-none.1 = bf16[8,4]{1,0} custom-call(%ragged-dot-metadata, %fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %multiply_fusion.2 = bf16[8,4]{1,0} fusion(%ragged-dot-none, %ragged-dot-none.1), kind=kLoop, calls=%c, metadata={op_name="jit(step)/while/body/closed_call/moe/expert_ffn/mul"}
  %ragged-dot-none.2 = bf16[8,4]{1,0} custom-call(%ragged-dot-metadata, %multiply_fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.3 = bf16[8,4]{1,0} fusion(%ragged-dot-none.2), kind=kLoop, calls=%c, metadata={op_name="jit(step)/while/body/closed_call/moe/gather"}
  ROOT %fusion.4 = bf16[8,4]{1,0} fusion(%fusion.3), kind=kLoop, calls=%c, metadata={op_name="jit(step)/while/body/closed_call/attention/add"}
}
"""
OPS = {"%fusion.1": 1.0, "%ragged-dot-metadata": 0.125,
       "%ragged-dot-none": 2.0, "%ragged-dot-none.1": 2.0,
       "%multiply_fusion.2": 0.5, "%ragged-dot-none.2": 3.0,
       "%fusion.3": 1.5, "%fusion.4": 4.0}
EXPERTS = 0.125 + 2.0 + 2.0 + 0.5 + 3.0


@pytest.fixture
def traced(monkeypatch):
    monkeypatch.setattr(S, "step_text", lambda cell, devices: HLO)
    monkeypatch.setattr("sys.argv", ["bench/run.py", "--workload", CELL,
                                     "--trace", "1"])


def _ctx(steps=2):
    reduced = trace_reduce.Reduced(window_s=1.0, busy_s=1.0, ops=OPS,
                                   gaps={}, device_events=[], chips=1)
    ops = work.prefill_step_ops(_arch(), 8, 4096, "bfloat16")
    return dict(trace=reduced, peaks={"bf16_flops_per_s": 197e12,
                                      "hbm_bytes_per_s": 819e9},
                counts={"kind": "prefill", "steps": steps, "ops": ops},
                chips=1)


def test_the_compilers_grouped_matmuls_count_as_the_experts(traced):
    """The down product's users are ``moe`` alone, so ``_scope.py``'s
    nesting misses it; the reader credits every ragged-dot kernel inside
    ``moe`` to the experts."""
    ctx = _ctx()
    read = lambda name: harness.metric_module(name).read(ctx)
    assert read("moe_ms.step") == pytest.approx(1e3 * (EXPERTS + 2.5) / 2)
    assert read("expert_ffn_ms.step") == pytest.approx(1e3 * EXPERTS / 2)
    assert S.ms_within_per_step(ctx, "expert_ffn") < read(
        "expert_ffn_ms.step")
    w = work.op_work(ctx["counts"]["ops"], "expert_ffn")
    least = max(w["flops"] / 197e12, w["bytes"] / 819e9)
    assert read("expert_ffn_roofline.step") == pytest.approx(
        100 * least / (EXPERTS / 2))


def test_a_program_without_the_moe_scope_reads_nothing(traced,
                                                       monkeypatch):
    monkeypatch.setattr(S, "vocabulary", lambda: ("attention",))
    ctx = _ctx()
    for name in ("moe_ms.step", "expert_ffn_ms.step",
                 "expert_ffn_roofline.step"):
        assert harness.metric_module(name).read(ctx) is None


# ------------------------------------------------ against the reference
def _tiny_cell(batch=4, seq=64):
    c = harness.resolve(CELL)
    c.config = copy.deepcopy(c.config)
    c.config["arch"].update(TINY)
    c.traffic = dict(c.traffic, batch=batch, seq=seq)
    return c


def _run(cell):
    return harness.run(cell, 2**31 + 5, 0.2, False, 0.0, jax.devices()[:1],
                       {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})


def _answer_altered(mp):
    real = transformer.prefill_fn

    def prefill_fn(*a, **kw):
        logits, cache = real(*a, **kw)
        return logits.at[0].set(logits[1]), cache
    mp.setattr(transformer, "prefill_fn", prefill_fn)


def _first_layer_cache_zeroed(mp):
    real = transformer.prefill_fn

    def prefill_fn(*a, **kw):
        logits, cache = real(*a, **kw)
        layers = {k: v.at[0].set(0) for k, v in cache["layers"].items()}
        return logits, dict(cache, layers=layers)
    mp.setattr(transformer, "prefill_fn", prefill_fn)


def test_the_sound_path_is_correct():
    r = _run(_tiny_cell())
    assert r["correct"] is True, r["checks"]
    assert set(r["checks"]) == set(harness.resolve(CELL).limits)


@pytest.mark.parametrize("fault", [_answer_altered,
                                   _first_layer_cache_zeroed])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    r = _run(_tiny_cell())
    assert r["correct"] is False, r["checks"]
