"""End-to-end training driver: data pipeline -> sharded train step ->
checkpoints, preemption-safe restart, straggler watchdog.

CPU: ``--reduced`` trains a reduced config for real.  One TPU chip:
the full config on a one-device mesh (``chip_smoke.py`` runs it).  TPU
fleet: the same driver with the production mesh and a full config.

Exit code 42 = preempted-after-checkpoint (relaunch with the same args;
--resume is implicit: the driver always resumes from the latest
checkpoint in --ckpt-dir if one exists).  A checkpoint that already
holds step ``--steps - 1`` or later is an error (exit 1): the run would
train nothing.  Every step appends ``{step, loss, grad_norm, step_s}``
to ``<ckpt-dir>/metrics.jsonl``; the first step's time includes its
compile.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpoint import CheckpointManager
from repro.configs import get_config, get_reduced, get_shape
from repro.configs.base import ShapeConfig
from repro.core.params import default_config
from repro.data.pipeline import SyntheticLM
from repro.ft.preemption import PreemptionHandler, RESTART_EXIT_CODE
from repro.ft.straggler import StragglerDetector
from repro.launch.chip import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.model import build_model
from repro.optim.optimizers import cosine_schedule, make_optimizer
from repro.runtime.stepfn import build_train_step

CKPT_ROOT = pathlib.Path(__file__).resolve().parents[3] / "results" / \
    "checkpoints"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None,
                    help="default: results/checkpoints/<arch> in the "
                         "checkout")
    ap.add_argument("--ckpt-interval", type=int, default=20)
    ap.add_argument("--log-interval", type=int, default=5)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--compute-dtype", default="bfloat16")
    ap.add_argument("--shard-strategy", default="dp")
    ap.add_argument("--remat", default="none")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    rt = default_config(compute_dtype=args.compute_dtype,
                        shard_strategy=args.shard_strategy,
                        remat_policy=args.remat,
                        microbatches=args.microbatches)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh())
    print(f"mesh: {dict(mesh.shape)}  arch: {cfg.name}  "
          f"params≈{cfg.param_count()/1e6:.1f}M", flush=True)

    optimizer = make_optimizer(cfg.optimizer,
                               cosine_schedule(args.lr, 10, args.steps))
    bundle = build_train_step(cfg, shape, rt, mesh, optimizer)
    model = build_model(cfg)

    pre = PreemptionHandler().install()
    ckpt_dir = pathlib.Path(args.ckpt_dir or CKPT_ROOT / cfg.name)
    ckpt_dir.mkdir(parents=True, exist_ok=True)
    mgr = CheckpointManager(str(ckpt_dir), interval=args.ckpt_interval)
    watchdog = StragglerDetector(factor=3.0)

    with mesh:
        start = mgr.latest_step()
        if start is not None:
            print(f"resuming from step {start}", flush=True)
            target = {"params": model.param_shapes(),
                      "opt": jax.eval_shape(optimizer.init,
                                            model.param_shapes())}
            state, _ = mgr.restore_latest(
                jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), target))
            params, opt_state = state["params"], state["opt"]
            start += 1
            if start >= args.steps:
                print(f"error: the checkpoint in {ckpt_dir} is at step "
                      f"{start - 1}, past --steps {args.steps}: nothing "
                      "to train", file=sys.stderr, flush=True)
                return 1
        else:
            params = model.init(jax.random.PRNGKey(args.seed))
            opt_state = optimizer.init(params)
            start = 0
        p_sh, o_sh, _ = bundle.in_shardings
        params = jax.device_put(params, p_sh)
        opt_state = jax.device_put(opt_state, o_sh)

        data = SyntheticLM(cfg, shape, rt, mesh, seed=args.seed)
        host = "host0"
        t_compile = time.time()
        for step in range(start, args.steps):
            batch = data.batch_at(step)
            t0 = time.time()
            params, opt_state, metrics = bundle.fn(params, opt_state, batch)
            jax.block_until_ready(metrics["loss"])
            dt_step = time.time() - t0
            watchdog.heartbeat(host, step, dt_step)
            with open(ckpt_dir / "metrics.jsonl", "a") as f:
                f.write(json.dumps({
                    "step": step, "loss": float(metrics["loss"]),
                    "grad_norm": float(metrics["grad_norm"]),
                    "step_s": dt_step}) + "\n")
            if step == start:
                print(f"first step (incl. compile): "
                      f"{time.time()-t_compile:.1f}s", flush=True)
            if step % args.log_interval == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {float(metrics['loss']):8.4f} "
                      f"gnorm {float(metrics['grad_norm']):8.3f} "
                      f"lr {float(metrics['lr']):.2e} {dt_step*1e3:7.1f}ms",
                      flush=True)
            if watchdog.check():
                print(f"stragglers: {watchdog.flagged}", flush=True)
            mgr.maybe_save(step, {"params": params, "opt": opt_state},
                           extra={"step": step})
            if pre.requested():
                print("preemption requested -> checkpoint + exit",
                      flush=True)
                mgr.maybe_save(step, {"params": params, "opt": opt_state},
                               extra={"step": step}, force=True)
                mgr.wait()
                return RESTART_EXIT_CODE
        mgr.maybe_save(args.steps - 1,
                       {"params": params, "opt": opt_state},
                       extra={"step": args.steps - 1}, force=True)
        mgr.wait()
    print("done.", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
