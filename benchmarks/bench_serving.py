"""Serving-loop benchmark: SLO guardrail on/off, bounded bad-config
exposure, promotion, and repeat-campaign cache freeness.

Four arms over two real ``serve:<arch>:<trace>`` cells (the replay
evaluator actually runs — reduced model, CPU):

  * **bad_config** — the known-bad config (``wave_admission=full``
    starves the sparse Poisson trace) replayed once with the guard off
    (it finishes the whole trace and shows the tail queue delay a live
    stream would have eaten) and once with the guard armed (it must be
    aborted mid-trace, bounding worst-case exposure to a prefix of the
    stream);
  * **campaign_guard_on** — the full tuning tree per cell with
    ``slo_ttft=3.0``: the violator alternative is scored as a
    deterministic crash without finishing its trace, winners are
    promoted to a live board;
  * **campaign_guard_off** — the same tree with no guard: the violator
    burns a full replay but its (terrible) honest cost is rejected by
    the accept rule, so neither arm ever ships ``wave_admission=full``
    (the guard changes how fast a bad config is rejected, not whether
    it can win; marginal knobs may differ between arms — replay cost
    is a measured wall quantity with real noise);
  * **campaign_repeat** — fresh checkpoints, same disk timing cache:
    zero fresh successful replays (every surviving trial is a cache
    hit; only the never-memoized deterministic aborts re-run) and the
    re-promotion never regresses the live board.

Results land in results/benchmarks/BENCH_serving.json and a copy at
the repo root (BENCH_serving.json) for CI tracking.

Run:  PYTHONPATH=src python -m benchmarks.bench_serving
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse
import json
import pathlib
import re
import shutil
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent

DEFAULT_CELLS = ("serve:smollm-135m:poisson_tiny,"
                 "serve:smollm-135m:bursty_tiny")
SLO_TTFT = 3.0
BAD_DELTA = {"wave_admission": "full"}


def _baseline(spec=None):
    from repro.core.params import default_config
    return default_config(shard_strategy="fsdp_tp", attn_impl="pallas")


def _evaluator(scratch, slo_ttft=None):
    """The dispatch stack over a bench-local timing cache (the shared
    results/trials cache must not leak arm-to-arm state in here)."""
    from repro.core.cells import DispatchEvaluator, default_evaluator
    from repro.core.measure import TimingCache
    from repro.serving.evaluator import make_serve_evaluator
    serve = make_serve_evaluator(
        slo_ttft=slo_ttft, cache=TimingCache(scratch / "timings"))
    return DispatchEvaluator({**default_evaluator(slo_ttft).evaluators,
                              "serve": serve})


def run_bad_config(cells, scratch):
    """One replay of the known-bad config per guard setting."""
    from repro.serving.evaluator import ServeEvaluator
    wl = cells[0].workload()
    bad = _baseline().replace(**BAD_DELTA)
    off = ServeEvaluator()
    t0 = time.time()
    stats = off.replay(wl, bad)          # guard off: full trace
    wall_off = round(time.time() - t0, 3)
    on = ServeEvaluator(slo_ttft=SLO_TTFT)
    t0 = time.time()
    res = on(wl, bad)                    # guard on: must abort
    wall_on = round(time.time() - t0, 3)
    m = re.search(r"after (\d+)/(\d+) requests", res.error or "")
    served_at_abort, total = (int(m.group(1)), int(m.group(2))) \
        if m else (None, None)
    return {
        "bad_delta": BAD_DELTA,
        "guard_off": {"served": stats["served"],
                      "p95_qdelay_s": round(stats["p95_qdelay_s"], 3),
                      "mean_ttft_s": round(stats["mean_ttft_s"], 3),
                      "cost_s": round(ServeEvaluator.cost_of(stats), 4),
                      "wall_s": wall_off},
        "guard_on": {"aborted": bool(res.crashed),
                     "failure": res.failure,
                     "served_at_abort": served_at_abort,
                     "total": total,
                     "error": (res.error or "")[:160],
                     "wall_s": wall_on},
    }


def _campaign(cells, ckpt, evaluator):
    from repro.core.campaign import Campaign
    camp = Campaign(cells, strategy="tree", checkpoint_dir=ckpt,
                    evaluator=evaluator, baseline_factory=_baseline)
    t0 = time.time()
    reports = camp.run()
    return reports, round(time.time() - t0, 3)


def _arm_summary(cells, reports, wall):
    out = {"wall_s": wall, "cells": {}}
    for c in cells:
        rep = reports[c.key()]
        aborts = [e for e in rep.log if e["result"].get("crashed")
                  and "slo-violation" in e["result"].get("error", "")]
        fresh = [e for e in rep.log
                 if not e["result"].get("crashed")
                 and not e["result"].get("cached")]
        out["cells"][c.key()] = {
            "trials": rep.n_trials,
            "slo_aborts": len(aborts),
            "fresh_successful_replays": len(fresh),
            "baseline_cost_s": round(rep.baseline_cost, 4),
            "final_cost_s": round(rep.final_cost, 4),
            "final_config": rep.final_config,
        }
    return out


# ------------------------------------------------------------------ main
def main(cells_spec: str):
    from repro.core.campaign import parse_cells
    from repro.serving.canary import PromotionBoard, promote_winners
    cells = parse_cells(cells_spec)
    print(f"batch: {len(cells)} cells "
          f"({', '.join(c.key() for c in cells)})")
    scratch = ROOT / "results" / "bench_serving_scratch"
    shutil.rmtree(scratch, ignore_errors=True)
    scratch.mkdir(parents=True, exist_ok=True)

    bad = run_bad_config(cells, scratch)
    print(f"bad_config: guard off served {bad['guard_off']['served']} "
          f"(p95 qdelay {bad['guard_off']['p95_qdelay_s']}s); guard on "
          f"aborted after {bad['guard_on']['served_at_abort']}/"
          f"{bad['guard_on']['total']}")

    on_reports, on_wall = _campaign(
        cells, scratch / "guard_on", _evaluator(scratch, SLO_TTFT))
    guard_on = _arm_summary(cells, on_reports, on_wall)
    promote_winners(scratch, on_reports, source="bench:guard_on")
    board = PromotionBoard(scratch)
    live_first = {c.key(): board.live(c.key())["cost_s"] for c in cells}
    print(f"campaign_guard_on: {on_wall}s, aborts per cell "
          f"{[v['slo_aborts'] for v in guard_on['cells'].values()]}")

    off_reports, off_wall = _campaign(
        cells, scratch / "guard_off", _evaluator(scratch, None))
    guard_off = _arm_summary(cells, off_reports, off_wall)
    print(f"campaign_guard_off: {off_wall}s")

    rep_reports, rep_wall = _campaign(
        cells, scratch / "repeat", _evaluator(scratch, SLO_TTFT))
    repeat = _arm_summary(cells, rep_reports, rep_wall)
    promote_winners(scratch, rep_reports, source="bench:repeat")
    live_after = {c.key(): board.live(c.key())["cost_s"] for c in cells}
    fresh_repeat = sum(v["fresh_successful_replays"]
                       for v in repeat["cells"].values())
    print(f"campaign_repeat: {rep_wall}s, "
          f"{fresh_repeat} fresh successful replays")

    out = {
        "cells": [c.key() for c in cells],
        "slo_ttft": SLO_TTFT,
        "bad_config": bad,
        "campaign_guard_on": guard_on,
        "campaign_guard_off": guard_off,
        "campaign_repeat": repeat,
        "promotion": {"live_costs_first": live_first,
                      "live_costs_after_repeat": live_after,
                      "history_actions":
                          [r["action"] for r in board.history()]},
    }
    res_dir = ROOT / "results" / "benchmarks"
    res_dir.mkdir(parents=True, exist_ok=True)
    (res_dir / "BENCH_serving.json").write_text(json.dumps(out, indent=1))
    (ROOT / "BENCH_serving.json").write_text(json.dumps(out, indent=1))
    shutil.rmtree(scratch, ignore_errors=True)
    print(json.dumps(out, indent=1))

    g_on, g_off = bad["guard_on"], bad["guard_off"]
    assert g_on["aborted"] and g_on["failure"] == "deterministic", g_on
    assert g_on["served_at_abort"] < g_on["total"], \
        "the guard let the bad config finish its trace!"
    assert g_off["served"] == g_on["total"], \
        "guard-off replay did not serve the full trace!"
    for key, arm in guard_on["cells"].items():
        assert arm["slo_aborts"] >= 1, \
            f"{key}: guard-on campaign saw no SLO abort"
        # neither arm may ever ship the SLO-violating admission policy:
        # the guard aborts it, the honest replay cost rejects it
        for arm_name, summary in (("guard_on", guard_on),
                                  ("guard_off", guard_off)):
            final = summary["cells"][key]["final_config"]
            assert final.get("wave_admission", "greedy") != "full", \
                f"{key}: {arm_name} shipped the bad admission policy!"
    assert fresh_repeat == 0, \
        "repeat campaign re-paid successful replays despite the cache!"
    for key in live_first:
        assert live_after[key] <= live_first[key], \
            f"{key}: the live board regressed on re-promotion!"
    print("\nbench_serving: all invariants hold")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default=DEFAULT_CELLS)
    args = ap.parse_args()
    main(args.cells)
