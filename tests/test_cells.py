"""Cell kinds (core/cells.py): one registry tells step, kernel and serve
cells apart, and one dispatcher routes both tiers by it.

Load-bearing invariants:

  * every kind is told by its arch prefix (or cell key), parses back
    from its own ``spec()``, and brings its own signature, stage tree
    and evaluators;
  * both tiers' defaults send each kind's workload to that kind's
    evaluator and no other;
  * cell keys, specs, timing-cache keys and history signatures are the
    literal values the tuner has always written, so checkpoints, leases,
    cached costs and history records stay valid.
"""
import pytest

from repro.core import measure
from repro.core.campaign import CellSpec, parse_cells
from repro.core.cells import (DispatchEvaluator, base_name,
                              default_evaluator,
                              default_measured_evaluator, kind, kind_of,
                              kinds)
from repro.core.history import cell_signature
from repro.core.kernel_cell import KernelBenchEvaluator, kernel_cell
from repro.core.measure import CachedMeasure, ReducedWallClock
from repro.core.params import default_config
from repro.core.trial import RooflineEvaluator, TrialResult
from repro.serving.evaluator import CachedServe, ServeEvaluator, serve_cell

CELLS = {
    "step": lambda: CellSpec("smollm-135m", "train_4k"),
    "kernel": lambda: kernel_cell("flash_attention", "tiny"),
    "serve": lambda: serve_cell("smollm-135m", "poisson_tiny"),
}

# (signature kind, base name, trial-tier evaluator, measured evaluator)
EXPECT = {
    "step": ("train", "smollm-135m", RooflineEvaluator, ReducedWallClock),
    "kernel": ("kernel", "flash_attention", KernelBenchEvaluator,
               KernelBenchEvaluator),
    "serve": ("serve", "smollm-135m", ServeEvaluator, ServeEvaluator),
}


def _inner(ev):
    """The evaluator under a timing-cache wrapper."""
    return ev.evaluator if isinstance(ev, CachedMeasure) else ev


@pytest.mark.parametrize("name", sorted(CELLS))
def test_each_kind_is_told_parsed_signed_staged_and_routed(name, tmp_path):
    cell = CELLS[name]()
    sig_kind, base, trial_cls, measured_cls = EXPECT[name]
    k = kind(name)
    assert k in kinds()
    assert kind_of(cell.arch) is k and kind_of(cell.key()) is k
    assert k.prefix + base == cell.arch and base_name(cell.arch) == base
    assert k.parse(cell.spec()) == cell
    assert parse_cells(cell.spec()) == [cell]
    assert k.signature(cell.arch, cell.shape, False)["kind"] == sig_kind
    stages = k.stages(cell)
    if name == "step":
        assert stages is None            # the strategy keeps its tree
    else:
        assert isinstance(stages, list) and stages

    trial = default_evaluator()
    measured = default_measured_evaluator(tmp_path)
    assert isinstance(_inner(trial.evaluators[name]), trial_cls)
    assert isinstance(_inner(measured.evaluators[name]), measured_cls)
    for tier in (trial, measured):
        calls = []

        def stub(kname):
            def ev(wl, rt):
                calls.append(kname)
                return TrialResult(cost_s=1.0)
            return ev
        routed = DispatchEvaluator({kn: stub(kn) for kn in tier.evaluators})
        assert routed(cell.workload(), default_config()).cost_s == 1.0
        assert calls == [name]


def test_a_spec_of_one_kind_is_refused_by_another():
    with pytest.raises(ValueError):
        kind("serve").parse("kernel:flash_attention:tiny")
    with pytest.raises(ValueError):
        kind("kernel").parse("kernel:flash_attention")


# literal values: checkpoints, leases, timing-cache entries and history
# records are matched on them, so a change to any orphans what is stored
PINNED = {
    "step": dict(
        key="smollm-135m__train_4k__pod",
        spec="smollm-135m:train_4k:pod",
        measured="smollm-135m__train_4k__pod__measured__890adc51168ad8b8",
        signature={
            "arch": "smollm-135m", "shape": "train_4k", "kind": "train",
            "family": "dense", "multi_pod": False,
            "active_knobs": [
                "compute_dtype", "shard_strategy", "grad_comm_dtype",
                "remat_policy", "microbatches", "attn_block_q",
                "attn_block_kv", "fuse_grad_collectives",
                "remat_save_dtype", "donate_buffers",
                "attn_tp_fallback"]}),
    "kernel": dict(
        key="kernel-flash_attention__tiny__pod",
        spec="kernel:flash_attention:tiny",
        trial="kernel-flash_attention__tiny__pod__measured__"
              "68963ec664bca796",
        measured="kernel-flash_attention__tiny__pod__measured__"
                 "68963ec664bca796",
        signature={
            "arch": "kernel-flash_attention", "shape": "tiny",
            "kind": "kernel", "family": "kernel-flash_attention",
            "multi_pod": False,
            "active_knobs": ["attn_block_q", "attn_block_kv"]}),
    "serve": dict(
        key="serve-smollm-135m__poisson_tiny__pod",
        spec="serve:smollm-135m:poisson_tiny",
        trial="serve-smollm-135m__poisson_tiny__pod__measured__"
              "9605a6528b480e17",
        trial_slo="serve-smollm-135m__poisson_tiny__pod__measured__"
                  "9ad5e629bc345c2c",
        signature={
            "arch": "serve-smollm-135m", "shape": "poisson_tiny",
            "kind": "serve", "family": "dense", "multi_pod": False,
            "active_knobs": ["max_wave_size", "wave_admission",
                             "kv_cache_dtype", "donate_buffers",
                             "compute_dtype"]}),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_cell_keys_and_signatures_are_pinned(name, tmp_path, monkeypatch):
    monkeypatch.setattr(measure, "device_tag", lambda: "tpu/TPU v5 lite")
    cell, want = CELLS[name](), PINNED[name]
    wl, rt = cell.workload(), default_config()
    assert cell.key() == want["key"]
    assert cell.spec() == want["spec"]
    assert cell_signature(cell.arch, cell.shape, False) == want["signature"]
    if "trial" in want:
        assert default_evaluator().evaluators[name]._key(wl, rt) \
            == want["trial"]
    if "trial_slo" in want:
        assert default_evaluator(3.0).evaluators[name]._key(wl, rt) \
            == want["trial_slo"]
    if "measured" in want:
        measured = default_measured_evaluator(tmp_path).evaluators[name]
        assert measured._key(wl, rt) == want["measured"]
    else:
        # the measured serve entry folds the trace content in like the
        # trial tier does (tests/test_serving_loop.py)
        measured = default_measured_evaluator(tmp_path).evaluators[name]
        assert isinstance(measured, CachedServe)
