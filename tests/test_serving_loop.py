"""The serving tuning loop (serving/evaluator.py + serving/canary.py):
serve cells, the SLO guardrail, winner promotion, and the bit-identity
guarantee that adding the serving stack changes nothing for step cells.
"""
import json
import types

import pytest

from repro.core.campaign import (Campaign, CellSpec, parse_cells,
                                 tuning_fingerprint)
from repro.core.cells import (DispatchEvaluator, default_evaluator,
                              default_measured_evaluator, kind, kind_of)
from repro.core.history import cell_signature
from repro.core.params import default_config
from repro.core.space import SPACE
from repro.core.trial import FAILURE_DETERMINISTIC, TrialResult
from repro.serving.canary import (SLO_QDELAY_FLOOR_S, SLO_TTFT_FLOOR_S,
                                  PromotionBoard, SLOGuard,
                                  SLOViolation, promote_winners)
from repro.serving import evaluator as serve_mod
from repro.serving.evaluator import (SERVE_KNOBS, CachedServe,
                                     ServeEvaluator, serve_cell,
                                     serve_signature, serve_stages)
from repro.serving.traffic import Trace


# ------------------------------------------------------------------ cells
def test_parse_serve_cell_roundtrip():
    cell = kind("serve").parse("serve:smollm-135m:poisson_tiny")
    assert cell.arch == "serve-smollm-135m"
    assert cell.shape == "poisson_tiny"
    assert cell.spec() == "serve:smollm-135m:poisson_tiny"
    # three-part key: checkpoints / leases / reports behave identically
    assert cell.key().count("__") == 2
    # campaign's parse_cells dispatches on the serve: prefix
    [again] = parse_cells("serve:smollm-135m:poisson_tiny")
    assert again == cell


@pytest.mark.parametrize("bad", ["serve:smollm-135m", "serve:a:b:c",
                                 "kernel:smollm-135m:poisson_tiny"])
def test_parse_serve_cell_rejects_malformed(bad):
    with pytest.raises(ValueError):
        kind("serve").parse(bad)


def test_serve_cell_validates_arch_and_trace():
    with pytest.raises(ValueError, match="unknown arch"):
        serve_cell("nope", "poisson_tiny")
    with pytest.raises(ValueError, match="unknown trace"):
        serve_cell("smollm-135m", "nope")


def test_serve_signature_via_history_dispatch():
    sig = cell_signature("serve-smollm-135m", "poisson_tiny", False)
    assert sig == serve_signature("serve-smollm-135m", "poisson_tiny")
    assert sig == kind_of("serve-smollm-135m").signature(
        "serve-smollm-135m", "poisson_tiny", False)
    assert sig["kind"] == "serve"
    assert sig["active_knobs"] == list(SERVE_KNOBS)


def test_serve_stages_propose_valid_deltas():
    stages = serve_stages(serve_cell("smollm-135m", "poisson_tiny"))
    assert stages, "serve cells need a stage tree"
    for st in stages:
        assert st.kinds == ("serve",)
        for alt in st.alternatives:
            SPACE.validate_delta(alt)   # includes the non-tunable knobs
    knobs = {k for st in stages for alt in st.alternatives for k in alt}
    assert knobs == set(SERVE_KNOBS)


# ------------------------------------------------- space / bit-identity
def test_serving_knobs_are_infrastructure():
    for name in ("max_wave_size", "wave_admission"):
        knob = SPACE[name]
        assert knob.tunable is False
        assert knob.reach == "analytic"
        assert name not in SPACE.domains()   # never swept for step cells
    # the serving knobs never reach a compile: the compile key of a
    # config with exotic serving settings equals the default's
    base = default_config()
    tweaked = base.replace(max_wave_size=8, wave_admission="full")
    assert tweaked.compile_key() == base.compile_key()


def _surface(wl, rt):
    c = 100.0 + 3.0 * len(wl.arch)
    if rt.compute_dtype == "bfloat16":
        c *= 0.7
    if rt.kv_cache_dtype == "int8":
        c *= 0.8
    return TrialResult(cost_s=round(c, 6))


def test_step_campaign_fingerprints_unchanged_by_serving_stack(tmp_path):
    """The serving-aware dispatch evaluator must leave every non-serving
    campaign bit-identical to the bare step evaluator (the PR-7
    regression bar)."""
    cells = [CellSpec("smollm-135m", "train_4k"),
             CellSpec("glm4-9b", "decode_32k")]
    bf = lambda spec: default_config(shard_strategy="fsdp_tp",
                                     attn_impl="pallas")
    bare = Campaign(cells, evaluator=_surface, baseline_factory=bf,
                    checkpoint_dir=tmp_path / "bare").run()
    dispatched = Campaign(cells,
                          evaluator=DispatchEvaluator({
                              **default_evaluator(slo_ttft=3.0).evaluators,
                              "step": _surface}),
                          baseline_factory=bf,
                          checkpoint_dir=tmp_path / "disp").run()
    assert list(bare) == list(dispatched)
    for key in bare:
        assert tuning_fingerprint(bare[key]) \
            == tuning_fingerprint(dispatched[key])


# ------------------------------------------------------------- SLO guard
def _guard(factor=2.0, ttft=1.0, qdelay=1.0, shadow=0.25):
    return SLOGuard(factor, {"mean_ttft_s": ttft, "p95_qdelay_s": qdelay},
                    shadow_frac=shadow)


def test_guard_passes_within_limits():
    g = _guard()
    for i in range(1, 9):
        g.observe(ttft_s=1.5, qdelay_s=1.5, served=i, total=8)


def test_guard_aborts_on_queue_delay_everywhere():
    g = _guard()
    with pytest.raises(SLOViolation, match="queue delay"):
        g.observe(ttft_s=0.1, qdelay_s=2.5, served=7, total=8)


def test_guard_shadow_slice_checks_per_request():
    g = _guard()                        # shadow = first 2 of 8
    with pytest.raises(SLOViolation, match="shadow slice"):
        g.observe(ttft_s=2.5, qdelay_s=0.0, served=1, total=8)


def test_guard_uses_running_mean_after_shadow():
    g = _guard()
    for i in range(1, 5):               # healthy shadow + early stream
        g.observe(ttft_s=0.1, qdelay_s=0.0, served=i, total=8)
    # one tail spike: per-request it exceeds 2x, but the running mean
    # does not — graduated candidates are judged on the mean
    g.observe(ttft_s=3.0, qdelay_s=0.0, served=5, total=8)
    # a sustained regression still aborts via the mean
    with pytest.raises(SLOViolation, match="mean TTFT"):
        for i in range(6, 9):
            g.observe(ttft_s=9.0, qdelay_s=0.0, served=i, total=8)


def test_guard_floors_protect_fast_incumbents():
    g = SLOGuard(2.0, {"mean_ttft_s": 1e-6, "p95_qdelay_s": 0.0})
    assert g.ttft_limit == 2.0 * SLO_TTFT_FLOOR_S
    assert g.qdelay_limit == 2.0 * SLO_QDELAY_FLOOR_S
    g.observe(ttft_s=0.3, qdelay_s=0.3, served=1, total=8)


def test_slo_violation_is_pretagged_deterministic():
    assert SLOViolation("slo-violation: x").failure \
        == FAILURE_DETERMINISTIC


# ----------------------------------------------------------- cost / keys
def test_cost_of_combines_ttft_qdelay_decode():
    stats = {"served": 4, "mean_ttft_s": 0.2, "p95_qdelay_s": 0.4,
             "decode_tok_per_s": 100.0, "decode_tokens": 40}
    # 1.0*0.2 + 0.5*0.4 + 1.0*(40/100/4)
    assert ServeEvaluator.cost_of(stats) == pytest.approx(0.5)
    assert ServeEvaluator.cost_of({"served": 0}) == 0.0


def test_cached_serve_key_folds_trace_content_and_slo(monkeypatch):
    wl = serve_cell("smollm-135m", "poisson_tiny").workload()
    wl2 = serve_cell("smollm-135m", "bursty_tiny").workload()
    rt = default_config()
    k = CachedServe(ServeEvaluator(), repeats=1)._key(wl, rt)
    # pure function of (cell, trace bytes, slo, config): two workers
    # always agree
    assert CachedServe(ServeEvaluator(), repeats=1)._key(wl, rt) == k
    assert CachedServe(ServeEvaluator(slo_ttft=3.0),
                       repeats=1)._key(wl, rt) != k
    assert CachedServe(ServeEvaluator(), repeats=1)._key(wl2, rt) != k
    assert CachedServe(ServeEvaluator(),
                       repeats=1)._key(wl, rt.replace(
                           max_wave_size=8)) != k
    # the measured tier's serve entry is keyed the same way: an edited
    # trace under the same name never reuses a stale measured cost
    measured = default_measured_evaluator().evaluators["serve"]
    km = measured._key(wl, rt)
    assert km == CachedServe(ServeEvaluator(), repeats=3)._key(wl, rt)
    tr = serve_mod.get_trace(wl.shape)
    monkeypatch.setattr(serve_mod, "get_trace",
                        lambda name: Trace(tr.meta, tr.requests[:-1]))
    assert measured._key(wl, rt) != km


def test_non_serve_workload_is_a_crashed_trial():
    res = ServeEvaluator()(CellSpec("smollm-135m", "train_4k").workload(),
                           default_config())
    assert res.crashed
    assert "not a serve cell" in res.error


# ------------------------------------------------------------- promotion
def test_promotion_board_lifecycle(tmp_path):
    board = PromotionBoard(tmp_path)
    assert board.live("c__t__pod") is None
    r1 = board.promote("c__t__pod", {"max_wave_size": 2}, 1.0, "w0")
    assert r1["action"] == "promoted" and r1["demoted"] is None
    live = board.live("c__t__pod")
    assert live["config"] == {"max_wave_size": 2}
    assert live["cost_s"] == 1.0

    # a worse candidate never lands: the live file is untouched
    r2 = board.promote("c__t__pod", {"max_wave_size": 8}, 2.0, "w1")
    assert r2["action"] == "kept-incumbent"
    assert board.live("c__t__pod")["config"] == {"max_wave_size": 2}

    # a strictly better one demotes the incumbent into the history
    r3 = board.promote("c__t__pod", {"max_wave_size": 4}, 0.5, "w1")
    assert r3["action"] == "promoted"
    assert r3["demoted"]["config"] == {"max_wave_size": 2}
    assert board.live("c__t__pod")["cost_s"] == 0.5
    assert [r["action"] for r in board.history()] \
        == ["promoted", "kept-incumbent", "promoted"]


def test_promote_winners_filters_and_overrides(tmp_path):
    def rep(cost, config, measured=None):
        return types.SimpleNamespace(final_cost=cost,
                                     final_config=config,
                                     measured=measured)
    reports = {
        "serve-a__t__pod": rep(1.5, {"max_wave_size": 2}),
        "serve-b__t__pod": rep(float("inf"), {"max_wave_size": 8}),
        "smollm-135m__train_4k__pod": rep(9.0, {}),   # step cell: skip
        "serve-c__t__pod": rep(
            2.0, {"max_wave_size": 4},
            measured={"winner": {"config": {"max_wave_size": 8},
                                 "cost_s": 1.0}}),
    }
    recs = promote_winners(tmp_path, reports, source="test")
    board = PromotionBoard(tmp_path)
    assert {r["cell"] for r in recs} \
        == {"serve-a__t__pod", "serve-c__t__pod"}
    assert board.live("serve-b__t__pod") is None      # crashed final
    assert board.live("smollm-135m__train_4k__pod") is None
    # the measured winner overrides the model winner
    assert board.live("serve-c__t__pod")["config"] \
        == {"max_wave_size": 8}
    assert board.live("serve-c__t__pod")["cost_s"] == 1.0


def test_live_file_is_valid_json(tmp_path):
    board = PromotionBoard(tmp_path)
    board.promote("serve-a__t__pod", {"wave_admission": "greedy"},
                  1.0, "w0", stats={"mean_ttft_s": 0.1})
    doc = json.loads(board.live_path("serve-a__t__pod").read_text())
    assert doc["stats"] == {"mean_ttft_s": 0.1}


# ------------------------------------------------------ end-to-end (slow)
@pytest.mark.slow
def test_serve_campaign_guard_aborts_and_promotes(tmp_path, monkeypatch):
    """One real serve cell through the campaign: the tree's
    wave_admission=full alternative regresses queue delay past the
    guardrail and is aborted mid-trace as a deterministic crash; the
    surviving winner is promoted to the live board."""
    from repro.launch import tune
    from repro.launch.tune import tune_campaign
    monkeypatch.setattr(tune, "RESULTS_DIR", tmp_path / "reports")
    cells = parse_cells("serve:smollm-135m:poisson_tiny")
    reports, stats = tune_campaign(cells, checkpoint_dir=tmp_path,
                                   slo_ttft=3.0, promote=True)
    [rep] = reports.values()
    assert rep.n_trials == 7             # baseline + 6 alternatives
    crashes = [e for e in rep.log if e["result"]["crashed"]]
    assert crashes, "the violator config must abort"
    for e in crashes:
        assert e["result"]["failure"] == FAILURE_DETERMINISTIC
        assert "slo-violation" in e["result"]["error"]
        # aborted mid-trace: the trace was never finished under it
        assert "/8 requests" in e["result"]["error"]
    assert rep.final_cost <= rep.baseline_cost
    board = PromotionBoard(tmp_path)
    live = board.live(cells[0].key())
    assert live is not None
    assert live["cost_s"] == pytest.approx(rep.final_cost)
    # the campaign summary renders the board
    assert "Serving: promoted live configs" \
        in (tmp_path / "campaign.md").read_text()
