"""Measured tier: timing cache, top-k selection, the campaign's
two-tier re-rank pass, kernel cells and tile validation.

All campaign tests drive synthetic evaluators — the one real-XLA test
(the kernel-cell end-to-end) times interpret-mode Pallas at a tiny
shape.  Load-bearing invariants:

  * ``measure_top_k=0`` (the default) is a true no-op — the campaign's
    reports are bit-identical to a plain model-only run;
  * the re-rank pays at most k real evaluations per cell, publishes
    the measured winner into ``report.measured`` / the checkpoint, and
    flags when measurement overturned the model ranking;
  * the disk timing cache makes a repeat campaign's measured tier free
    (zero evaluator calls), and ``cell_done`` gates on the measured
    stamp so a finished walk still owes its re-rank;
  * a non-dividing tile knob is a clean deterministic-crash trial.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from repro.core.campaign import Campaign, CellSpec, parse_cells
from repro.core.cells import kind, kind_of
from repro.core.history import TrialHistory
from repro.core.measure import (CachedMeasure, ReducedWallClock,
                                TimingCache, measure_key, select_top_k)
from repro.core.params import default_config
from repro.core.trial import (FAILURE_DETERMINISTIC, FAILURE_TRANSIENT,
                              TrialError, TrialResult, WallClockEvaluator,
                              Workload)

CELL = [CellSpec("smollm-135m", "train_4k")]


def baseline_factory(spec):
    return default_config(shard_strategy="fsdp_tp", attn_impl="pallas")


def model_surface(wl, rt):
    """Model cost: bf16 and remat=full both look good."""
    c = 2.0
    if rt.compute_dtype == "bfloat16":
        c *= 0.8
    if rt.remat_policy == "full":
        c *= 0.85
    if rt.microbatches == 2:
        c *= 0.95
    return TrialResult(cost_s=round(c, 6))


class TruthSurface:
    """Measured cost that disagrees: remat=full is actually slower."""

    def __init__(self):
        self.calls = []

    def __call__(self, wl, rt):
        self.calls.append((wl.key(), rt.as_dict()))
        c = 1.0
        if rt.compute_dtype == "bfloat16":
            c *= 0.8
        if rt.remat_policy == "full":
            c *= 1.5
        if rt.microbatches == 2:
            c *= 0.97
        return TrialResult(cost_s=round(c, 6), compiles=1, compile_s=0.1)


def run_campaign(tmp_path, k, truth=None, cells=CELL, **kw):
    camp = Campaign(cells, strategy="tree", checkpoint_dir=tmp_path,
                    evaluator=model_surface,
                    baseline_factory=baseline_factory,
                    measure_top_k=k,
                    measured_evaluator=truth, **kw)
    return camp, camp.run()


# ----------------------------------------------------------- selection
def test_select_top_k_dedup_and_order():
    cfg = baseline_factory(None)
    log = []
    deltas = [{"microbatches": 4}, {"microbatches": 2}, {},
              {"remat_policy": "none"}, {"compute_dtype": "bfloat16"}]
    for i, (cost, crashed) in enumerate(
            [(3.0, False), (1.0, False), (2.0, True), (1.0, False),
             (0.5, False)]):
        d = dict(cfg.as_dict(), **deltas[i])
        log.append({"name": f"t{i}", "delta": {}, "config": d,
                    "result": {"cost_s": cost, "crashed": crashed}})
    # crash skipped; i=3 distinct from others; sorted by cost
    out = select_top_k(log, 3)
    assert [c["name"] for c in out] == ["t4", "t1", "t3"]
    assert out[0]["model_cost_s"] == 0.5
    # dedup: duplicate config keeps only the first occurrence
    log.append(dict(log[1], name="dup"))
    assert [c["name"] for c in select_top_k(log, 10)] \
        == ["t4", "t1", "t3", "t0"]
    assert select_top_k([], 5) == []


# ------------------------------------------------------------- caching
def test_cached_measure_roundtrip(tmp_path):
    wl = Workload("smollm-135m", "train_4k", False)
    rt = baseline_factory(None)
    truth = TruthSurface()
    cm = CachedMeasure(truth, TimingCache(tmp_path / "t"), repeats=3)
    r1 = cm(wl, rt)
    assert not r1.cached and r1.compiles == 1 and len(truth.calls) == 1
    # same process: in-memory hit
    r2 = cm(wl, rt)
    assert r2.cached and r2.compiles == 0 and r2.cost_s == r1.cost_s
    assert len(truth.calls) == 1
    # "new process": fresh cache object over the same disk dir
    cm2 = CachedMeasure(truth, TimingCache(tmp_path / "t"), repeats=3)
    r3 = cm2(wl, rt)
    assert r3.cached and len(truth.calls) == 1
    # different repeats -> different key -> re-measured
    cm3 = CachedMeasure(truth, TimingCache(tmp_path / "t"), repeats=5)
    assert not cm3(wl, rt).cached and len(truth.calls) == 2
    assert measure_key(wl, rt, 3) != measure_key(wl, rt, 5)


def test_cpu_timing_never_answers_for_the_chip(tmp_path, monkeypatch):
    """The timing key names the device: an entry a CPU run stored on
    disk is a miss for a TPU v5e lookup of the same cell and config."""
    import repro.core.measure as measure
    wl = Workload("smollm-135m", "train_4k", False)
    rt = baseline_factory(None)
    truth = TruthSurface()
    assert measure.device_tag() == "cpu/cpu"
    CachedMeasure(truth, TimingCache(tmp_path / "t"), repeats=3)(wl, rt)
    cpu_key = measure_key(wl, rt, 3)
    monkeypatch.setattr(measure, "device_tag", lambda: "tpu/TPU v5 lite")
    assert measure_key(wl, rt, 3) != cpu_key
    res = CachedMeasure(truth, TimingCache(tmp_path / "t"), repeats=3)(wl, rt)
    assert not res.cached and len(truth.calls) == 2


def test_cached_measure_error_memo(tmp_path):
    wl = Workload("smollm-135m", "train_4k", False)
    rt = baseline_factory(None)
    calls = []

    def crasher(w, r):
        calls.append(1)
        return TrialResult(cost_s=float("inf"), crashed=True,
                           error="ValueError: boom",
                           failure=FAILURE_DETERMINISTIC, compile_s=0.2)

    cm = CachedMeasure(crasher, TimingCache(tmp_path / "t"), repeats=3)
    r1 = cm(wl, rt)
    assert r1.crashed and not r1.cached and len(calls) == 1
    # deterministic crash: memoized in-memory, replayed with its class
    r2 = cm(wl, rt)
    assert r2.crashed and r2.cached \
        and r2.failure == FAILURE_DETERMINISTIC \
        and r2.error == "ValueError: boom" and len(calls) == 1
    # ... but never persisted to disk: a fresh process re-tries
    cm2 = CachedMeasure(crasher, TimingCache(tmp_path / "t"), repeats=3)
    assert cm2(wl, rt).crashed and len(calls) == 2

    def transient(w, r):
        calls.append(1)
        return TrialResult(cost_s=float("inf"), crashed=True,
                           error="OSError: flaky",
                           failure=FAILURE_TRANSIENT)

    cmt = CachedMeasure(transient, TimingCache(tmp_path / "u"), repeats=3)
    n0 = len(calls)
    cmt(wl, rt)
    r = cmt(wl, rt)                      # transient: never memoized
    assert len(calls) == n0 + 2 and not r.cached


# ----------------------------------------- hardened WallClockEvaluator
def test_wallclock_evaluator_accounting(monkeypatch):
    from repro.launch.mesh import make_mesh
    from repro.runtime import stepfn

    @dataclasses.dataclass
    class StubBundle:
        fn: object
        args: tuple
        kind: str = "train"

    def stub_build(cfg, shape, rt, mesh):
        # (params, opt, batch) -> (params', opt', loss): the shape the
        # evaluator's donate-buffer rotation expects for kind="train"
        s = jax.ShapeDtypeStruct((8,), jnp.float32)
        return StubBundle(
            fn=jax.jit(lambda p, o, b: (p + 1.0, o + 1.0,
                                        (b * 2.0).sum())),
            args=(s, s, s))

    monkeypatch.setattr(stepfn, "build_step", stub_build)
    ev = WallClockEvaluator(lambda multi_pod=False:
                            make_mesh((1, 1), ("data", "model")),
                            repeats=3)
    res = ev(Workload("smollm-135m", "train_4k", False),
             baseline_factory(None))
    assert not res.crashed and res.cost_s > 0
    assert res.compiles == 1 and res.compile_s >= 0.0

    def exploding(cfg, shape, rt, mesh):
        raise TrialError("CacheReplay: stored crash",
                         failure=FAILURE_TRANSIENT)

    monkeypatch.setattr(stepfn, "build_step", exploding)
    res = ev(Workload("smollm-135m", "train_4k", False),
             baseline_factory(None))
    assert res.crashed and res.failure == FAILURE_TRANSIENT
    assert res.error == "CacheReplay: stored crash"  # pre-tag kept
    assert res.compiles == 0 and res.compile_s >= 0.0


def test_wallclock_rejects_nondividing_tile():
    # validation fires before any mesh/build work: 384 % 256 != 0
    ev = WallClockEvaluator(lambda multi_pod=False: None, repeats=1)

    class OddSeq(Workload):
        @property
        def shp(self):
            from repro.configs.base import ShapeConfig
            return ShapeConfig("odd", 384, 8, "train")

    res = ev(OddSeq("smollm-135m", "train_4k", False),
             baseline_factory(None).replace(attn_block_kv=256))
    assert res.crashed and res.failure == FAILURE_DETERMINISTIC
    assert "divide" in res.error


# ------------------------------------------------- campaign re-rank
def test_campaign_measured_rerank(tmp_path):
    truth = TruthSurface()
    camp, reps = run_campaign(tmp_path, 2, CachedMeasure(
        truth, TimingCache(tmp_path / "timings")))
    rep = reps[CELL[0].key()]
    md = rep.measured
    assert md["k"] == 2 and md["evaluations"] <= 2
    assert len(truth.calls) <= 2         # bounded by k
    assert md["winner"] is not None
    assert md["candidates"][0]["config"] == md["model_choice"]
    # the measured winner is the truth-cheapest candidate
    best = min((c for c in md["candidates"] if not c.get("crashed")),
               key=lambda c: c["cost_s"])
    assert md["winner"] == best["config"]
    assert md["overturned"] == (best["rank"] != 0)
    # stats + checkpoint + history all carry the measured pass
    assert camp.last_stats["measured"]["cells"] == 1
    ckpt = json.loads((tmp_path / f"{CELL[0].key()}.json").read_text())
    assert ckpt["report"]["measured"]["k"] == 2
    hist = [json.loads(l) for l in
            (tmp_path / "history.jsonl").read_text().splitlines()]
    measured_rows = [h for h in hist
                     if h.get("strategy") == "tree+measured"]
    assert len(measured_rows) == md["evaluations"]
    assert all(h["name"].startswith("measured:")
               for h in measured_rows)


def test_measure_top_k_zero_is_noop(tmp_path):
    _, plain = run_campaign(tmp_path / "a", 0)
    camp, zero = run_campaign(tmp_path / "b", 0)
    rep = zero[CELL[0].key()]
    assert rep.measured is None
    assert dataclasses.asdict(rep) == dataclasses.asdict(
        plain[CELL[0].key()])
    assert "measured" not in camp.last_stats


def test_campaign_measured_resume_and_gating(tmp_path):
    truth = TruthSurface()
    cache = TimingCache(tmp_path / "timings")
    camp1, reps1 = run_campaign(tmp_path, 2,
                                CachedMeasure(truth, cache))
    n = len(truth.calls)
    assert camp1.cell_done(CELL[0])
    # resume: walk replays, measured stamp honored, no re-measure
    camp2, reps2 = run_campaign(tmp_path, 2,
                                CachedMeasure(truth, cache))
    assert len(truth.calls) == n
    assert reps2[CELL[0].key()].measured == \
        reps1[CELL[0].key()].measured
    # a different k owes a fresh re-rank: done gate flips off
    camp3 = Campaign(CELL, strategy="tree", checkpoint_dir=tmp_path,
                     evaluator=model_surface,
                     baseline_factory=baseline_factory,
                     measure_top_k=3)
    assert not camp3.cell_done(CELL[0])
    # ... and a plain model-only campaign ignores the stamp entirely
    camp4 = Campaign(CELL, strategy="tree", checkpoint_dir=tmp_path,
                     evaluator=model_surface,
                     baseline_factory=baseline_factory)
    assert camp4.cell_done(CELL[0])


def test_measured_all_crash_keeps_model_choice(tmp_path):
    def crasher(wl, rt):
        return TrialResult(cost_s=float("inf"), crashed=True,
                           error="RuntimeError: no device",
                           failure=FAILURE_DETERMINISTIC)

    camp, reps = run_campaign(tmp_path, 2, crasher)
    md = reps[CELL[0].key()].measured
    assert md["winner"] is None and "note" in md
    assert all(c["crashed"] for c in md["candidates"])
    assert camp.cell_done(CELL[0])       # a crashed re-rank still ends


def test_sensitivity_strategy_not_measurable(tmp_path):
    truth = TruthSurface()
    camp = Campaign(CELL, strategy="sensitivity",
                    checkpoint_dir=tmp_path, evaluator=model_surface,
                    baseline_factory=baseline_factory,
                    measure_top_k=2, measured_evaluator=truth)
    camp.run()
    assert truth.calls == []             # OFAT reports have no ranking


# --------------------------------------------------------- kernel cells
def test_parse_kernel_cells():
    cells = parse_cells("kernel:flash_attention:tiny,smollm-135m:train_4k")
    assert cells[0].arch == "kernel-flash_attention"
    assert [kind_of(c.arch).name for c in cells] == ["kernel", "step"]
    assert cells[0] == kind("kernel").parse(cells[0].spec())
    assert cells[0].spec() == "kernel:flash_attention:tiny"
    assert cells[1].arch == "smollm-135m"
    with pytest.raises(ValueError):
        parse_cells("kernel:nope:tiny")
    with pytest.raises(ValueError):
        parse_cells("kernel:flash_attention:nope")
    with pytest.raises(ValueError):
        parse_cells("kernel:flash_attention")


def test_kernel_cell_campaign(tmp_path):
    # real interpret-mode Pallas timing at a tiny shape: the whole
    # pipeline (stages, dispatch evaluator, checkpoint, report) runs
    cells = parse_cells("kernel:flash_decode:tiny")
    camp = Campaign(cells, strategy="tree", checkpoint_dir=tmp_path)
    reps = camp.run()
    rep = reps[cells[0].key()]
    assert rep.n_trials >= 2 and rep.baseline_cost > 0
    assert rep.final_cost <= rep.baseline_cost
    assert camp.cell_done(cells[0])


def test_kernel_bench_rejects_nondividing_tile():
    from repro.core.kernel_cell import KernelBenchEvaluator, kernel_cell
    wl = kernel_cell("flash_attention", "ragged").workload()  # S=384
    rt = baseline_factory(None).replace(attn_block_q=256)
    res = KernelBenchEvaluator(repeats=1)(wl, rt)
    assert res.crashed and res.failure == FAILURE_DETERMINISTIC
    assert "divide" in res.error


def test_space_tile_validation():
    from repro.core.space import SPACE
    rt = baseline_factory(None)
    SPACE.validate(rt)                   # no seq_len: historical path
    SPACE.validate(rt, seq_len=4096)
    SPACE.validate(rt.replace(attn_block_kv=256), seq_len=128)  # clamps
    with pytest.raises(ValueError, match="divide"):
        SPACE.validate(rt.replace(attn_block_q=256), seq_len=384)
    assert set(SPACE.seq_tile_knobs()) >= {"attn_block_q",
                                           "attn_block_kv"}


def test_reduced_wallclock_train_uses_xla_attention(monkeypatch):
    # forward-only flash kernel: the executed train proxy must swap to
    # the XLA attention path (same substitution as the roofline
    # calibration compiles) — prefill/decode keep attn_impl untouched
    seen = {}

    class SpyEv:
        repeats = 2

        def __call__(self, wl, rt):
            seen[wl.shp.kind] = rt.attn_impl
            return TrialResult(cost_s=1.0)

    ev = ReducedWallClock(repeats=2)
    ev._ev = SpyEv()
    rt = baseline_factory(None)
    ev(Workload("smollm-135m", "train_4k", False), rt)
    ev(Workload("smollm-135m", "prefill_32k", False), rt)
    assert seen == {"train": "xla", "prefill": "pallas"}
