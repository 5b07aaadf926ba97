"""Multi-pod dry-run: prove the distribution config is coherent.

MUST set the placeholder device count before ANY jax-touching import —
do not move these two lines.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import pathlib
import sys
import time
import traceback

import jax

from repro.configs import (get_config, get_shape, list_archs, SHAPES,
                           shape_applicable)
from repro.core import costmodel
from repro.core.params import TunableConfig, default_config
from repro.launch.mesh import placeholder_production_mesh
from repro.runtime.stepfn import build_step

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


def infra_default_rt(arch: str, **overrides) -> TunableConfig:
    """The cluster-level baseline configuration (DESIGN.md §2.2).

    Mirrors the paper: cluster settings (here: a 2D sharding able to hold
    every assigned model) are fixed infrastructure-wide per [8]; the 12
    application-level knobs start from Spark-like defaults (f32
    "Java serializer", no compression, balanced memory fractions ...).
    """
    base = dict(shard_strategy="fsdp_tp")
    base.update(overrides)
    return default_config(**base)


def run_cell(arch: str, shape_id: str, multi_pod: bool,
             rt: TunableConfig = None, save: bool = True) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_id)
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    rec = {"arch": arch, "shape": shape_id, "mesh": mesh_name,
           "kind": shape.kind}
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        rec.update(status="skip", reason=reason)
        if save:
            RESULTS_DIR.mkdir(parents=True, exist_ok=True)
            out = RESULTS_DIR / f"{arch}__{shape_id}__{mesh_name}.json"
            out.write_text(json.dumps(rec, indent=1))
        return rec
    rt = rt or infra_default_rt(arch)
    rec["tunable"] = rt.as_dict()
    mesh = placeholder_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    try:
        bundle = build_step(cfg, shape, rt, mesh)
        with mesh:
            lowered = bundle.lower()
            compiled = lowered.compile()
        ma = compiled.memory_analysis()
        rec["memory_analysis"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "peak_bytes": int(ma.argument_size_in_bytes
                              + ma.temp_size_in_bytes),
        }
        raw = costmodel.analyze(
            compiled, compute_dtype=rt.compute_dtype,
            pod_size=256 if multi_pod else 10**9)
        rec["roofline_raw"] = raw.as_dict()   # body-once HLO (uncalibrated)
        # calibrated terms: extrapolated from two small unrolled compiles
        from repro.core.trial import RooflineEvaluator, Workload
        ev = RooflineEvaluator(use_cache=False)
        rl = ev.calibrated_roofline(Workload(arch, shape_id, multi_pod), rt)
        rec["roofline"] = rl.as_dict()
        rec["model_flops"] = costmodel.model_flops(cfg, shape)
        per_chip_model = rec["model_flops"] / chips
        rec["useful_flops_ratio"] = (
            per_chip_model / rl.flops_per_chip if rl.flops_per_chip else 0.0)
        hbm = costmodel.HW["hbm_per_chip"]
        rec["fits_hbm"] = rec["memory_analysis"]["peak_bytes"] <= hbm
        rec["sharding_notes"] = bundle.notes.get("sharding_notes", [])
        rec["status"] = "ok"
        rec["compile_s"] = round(time.time() - t0, 1)
    except Exception as e:
        rec.update(status="fail", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:],
                   compile_s=round(time.time() - t0, 1))
    if save:
        RESULTS_DIR.mkdir(parents=True, exist_ok=True)
        out = RESULTS_DIR / f"{arch}__{shape_id}__{mesh_name}.json"
        out.write_text(json.dumps(rec, indent=1))
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="shape id (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = 0
    for arch in archs:
        for shape_id in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape_id, mp)
                if rec["status"] == "ok":
                    rl = rec["roofline"]
                    ma = rec["memory_analysis"]
                    msg = (f"OK   {rec['mesh']:18s} {arch:22s} {shape_id:12s}"
                           f" bottleneck={rl['bottleneck']:10s}"
                           f" total={rl['total_s']*1e3:9.2f}ms"
                           f" peak/chip={ma['peak_bytes']/1e9:7.2f}GB"
                           f" fits={rec['fits_hbm']}")
                elif rec["status"] == "skip":
                    msg = (f"SKIP {rec['mesh']:18s} {arch:22s} {shape_id:12s}"
                           f" ({rec['reason'][:60]}...)")
                else:
                    failures += 1
                    msg = (f"FAIL {rec['mesh']:18s} {arch:22s} {shape_id:12s}"
                           f" {rec['error'][:120]}")
                if not args.quiet or rec["status"] != "ok":
                    print(msg, flush=True)
                if rec["status"] == "ok" and not args.quiet:
                    print(f"     memory_analysis: {rec['memory_analysis']}")
                    print(f"     cost_analysis: flops/chip="
                          f"{rl['flops_per_chip']:.3e} bytes/chip="
                          f"{rl['bytes_per_chip']:.3e} coll_bytes="
                          f"{rl['collective_bytes']:.3e}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
