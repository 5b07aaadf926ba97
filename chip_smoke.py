#!/usr/bin/env python3
"""Bring-up run of the main path on a TPU chip, through the entry points.

    python3 chip_smoke.py               # one chip: every phase below
    python3 chip_smoke.py --four-chips  # four chips: the sharded train pair only

Phases, in one process (a TPU chip belongs to one process at a time):

  device   the default backend is the TPU; JAX_PLATFORMS may not force
           the CPU.  The CPU backend stays beside it for the roofline
           screen's compile-only placeholder devices.
  train    ``repro.launch.train.main`` at full-width smollm-135m (30
           layers, d_model 576, vocab 49152), batch 4 x seq 2048 with
           ``--remat full``: the default ``dots`` policy keeps every
           layer's f32 attention scores, 18.1 GB, which the chip's
           compiler refuses; ``full`` needs 4.3 GB.  The loss is finite
           and every step asked for runs.
  serve    ``serving.BatchScheduler`` at full width with
           ``attn_impl="pallas"`` (flash attention, flash decode and
           RMSNorm as Mosaic kernels) against ``attn_impl="xla"`` on 16
           seeded requests of 64-1024 prompt tokens, 32 new tokens each,
           in waves of 8; then ``repro.launch.serve.main`` once.
  kernels  each Pallas kernel against its ``ref.py`` at real widths, with
           one ragged length each, under the tolerances of
           tests/test_kernels.py.
  trials   one ``RooflineEvaluator`` trial of smollm-135m train_4k
           (compile-only, on CPU placeholder devices) and
           ``WallClockEvaluator`` trials on one chip's share of train_4k.

``--four-chips`` trains smollm-135m for a few steps on a (data=2,
model=2) mesh with ``shard_strategy="fsdp_tp"`` and on one chip, with
the same seed and batch, and compares the losses.

The lines before the last are bring-up observations, not benchmark
numbers.  The last line is ``{"ok": true, "device": {...}}``, printed
only when every phase passed; otherwise the exit code is 1.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import shutil
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
OUT = ROOT / "results" / "chip_smoke"

ARCH = "smollm-135m"
SEED = 0
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 4, 2048
SERVE_REQUESTS, SERVE_WAVE, SERVE_NEW = 16, 8, 32
SERVE_PROMPT = (64, 1024)
#: the Pallas path's prefill logits may be at most this many times
#: further from a float32 reference than the XLA bf16 path's are
SERVE_LOGIT_FACTOR = 2.0
WALLCLOCK_REPEATS = 3
#: per-step |loss difference| between the 2x2 mesh and one chip
FOUR_CHIP_LOSS_ATOL = 2e-2

#: kernel widths: (name, shape); ragged lengths have no aligned divisor
FLASH = [("smollm-135m", (4, 2048, 9, 3, 64)),     # (B, S, H, Hkv, hd)
         ("smollm-135m ragged", (4, 1003, 9, 3, 64)),
         ("zamba2-7b", (1, 4096, 32, 32, 112)),
         ("zamba2-7b ragged", (1, 1000, 32, 32, 112))]
DECODE = [("bf16", "bfloat16", 4096, 3000), ("int8", "int8", 4096, 3000),
          ("int8 ragged", "int8", 1000, 999)]     # (B=8, H=9, Hkv=3, hd=64)
SSM = [("zamba2-7b", 4096), ("zamba2-7b ragged", 1000)]  # H=112 P=64 N=64
RMS = [(8192, 576), (8192, 3584), (1001, 576)]


class PhaseError(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise PhaseError(msg)


def log(msg=""):
    print(msg, flush=True)


def fail(msg):
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


# --------------------------------------------------------------- device
def request_tpu():
    """Before JAX is imported: refuse an environment that forces the
    CPU, and ask for the TPU with the CPU backend beside it (512
    placeholder devices for the roofline screen's production mesh)."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        fail(f"JAX_PLATFORMS={platforms!r} keeps JAX off the TPU; "
             "unset it or include 'tpu'")
    os.environ["JAX_PLATFORMS"] = "tpu,cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=512").strip()
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch import chip
    except ImportError as e:
        fail(f"the repro package is not beside this script: {e}")
    if not chip.tpu_host():
        fail("no TPU chip is attached to this host")
    return chip


def phase_device(chips):
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"platform={d.platform} kind={d.device_kind!r} count={len(devs)} "
        f"cpu_placeholders={len(jax.devices('cpu'))}")
    check(d.platform == "tpu", f"default device is {d.platform}, not tpu")
    check(len(devs) >= chips, f"{len(devs)} chips, need {chips}")
    return d


# ---------------------------------------------------------------- train
def phase_train():
    from repro.launch import train
    out = OUT / "train"
    shutil.rmtree(out, ignore_errors=True)
    rc = train.main(["--arch", ARCH, "--steps", str(TRAIN_STEPS),
                     "--batch", str(TRAIN_BATCH), "--seq", str(TRAIN_SEQ),
                     "--remat", "full", "--ckpt-dir", str(out),
                     "--ckpt-interval", "0", "--log-interval", "1",
                     "--seed", str(SEED)])
    check(rc == 0, f"train.main exited {rc}")
    recs = [json.loads(line) for line in
            (out / "metrics.jsonl").read_text().splitlines()]
    check([r["step"] for r in recs] == list(range(TRAIN_STEPS)),
          f"steps run {[r['step'] for r in recs]}, asked {TRAIN_STEPS}")
    check(all(math.isfinite(r["loss"]) for r in recs),
          f"non-finite loss {[r['loss'] for r in recs]}")
    warm = [r["step_s"] for r in recs[1:]]
    log(f"losses {[r['loss'] for r in recs]}")
    log(f"first step incl. compile {recs[0]['step_s']:.2f}s; later steps "
        f"{[round(s, 4) for s in warm]}s = "
        f"{TRAIN_BATCH * TRAIN_SEQ / (sum(warm) / len(warm)):.0f} tokens/s")
    shutil.rmtree(out)              # the final checkpoint alone is 1.6 GB


# ---------------------------------------------------------------- serve
def _waves(items):
    return [items[i:i + SERVE_WAVE] for i in range(0, len(items), SERVE_WAVE)]


def _serve(cfg, params, impl, prompts):
    """Drain the prompts through a BatchScheduler; return each
    request's greedy tokens and the scheduler."""
    from repro.core.params import default_config
    from repro.serving.scheduler import BatchScheduler, Request
    rt = default_config(compute_dtype="bfloat16", attn_impl=impl)
    sched = BatchScheduler(cfg, rt, params, wave_size=SERVE_WAVE,
                           max_seq=SERVE_PROMPT[1] + SERVE_NEW)
    reqs = [Request(i, p, max_new_tokens=SERVE_NEW)
            for i, p in enumerate(prompts)]
    for r in reqs:
        sched.submit(r)
    t0 = time.time()
    done = sched.run_until_drained()
    wall = time.time() - t0
    check(len(done) == len(reqs), f"{impl}: {len(done)} of {len(reqs)} served")
    check(all(len(r.generated) == SERVE_NEW for r in reqs),
          f"{impl}: token counts {[len(r.generated) for r in reqs]}")
    s = sched.metrics.summary()
    log(f"{impl}: drained {len(reqs)} requests in {wall:.2f}s (incl. "
        f"compile); mean TTFT {s['mean_ttft_s']:.3f}s; decode "
        f"{s['decode_tok_per_s']:.0f} tok/s")
    return [r.generated for r in reqs], sched


def _forced(sched, params, prompts, tokens):
    """(requests, SERVE_NEW, vocab) logits of the scheduler's own jitted
    prefill and decode steps, each decode step fed ``tokens`` (the
    reference run's choices) in place of its own."""
    import numpy as np
    from repro.serving.scheduler import Request
    out = []
    for wave, fed in zip(_waves(prompts), _waves(tokens)):
        fed = np.asarray(fed, np.int32)
        batch = {"tokens": sched._pad_prompts([Request(0, p) for p in wave])}
        logits, cache = sched._prefill(params, batch)
        steps = [np.asarray(logits[:, -1], np.float32)]
        for t in range(SERVE_NEW - 1):
            logits, cache = sched._decode(params, cache, fed[:, t:t + 1])
            steps.append(np.asarray(logits[:, -1], np.float32))
        out.append(np.stack(steps, 1))
    return np.concatenate(out)


def _mosaic_kernels(sched, params, prompts):
    """tpu_custom_call count in the compiled prefill and decode steps of
    the last wave."""
    import numpy as np
    from repro.serving.scheduler import Request
    batch = {"tokens": sched._pad_prompts(
        [Request(0, p) for p in _waves(prompts)[-1]])}
    pre = sched._prefill.lower(params, batch).compile()
    _, cache = sched._prefill(params, batch)
    tok = np.zeros((batch["tokens"].shape[0], 1), np.int32)
    dec = sched._decode.lower(params, cache, tok).compile()
    return (pre.as_text().count("tpu_custom_call"),
            dec.as_text().count("tpu_custom_call"))


def phase_serve():
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.launch import serve
    from repro.models.model import build_model
    from repro.serving.scheduler import Request
    cfg = get_config(ARCH)
    rng = np.random.RandomState(SEED)
    lengths = rng.randint(SERVE_PROMPT[0], SERVE_PROMPT[1] + 1,
                          SERVE_REQUESTS)
    check(any(n % 8 for n in lengths), "no ragged prompt length")
    prompts = [rng.randint(0, cfg.vocab, n).astype(np.int32)
               for n in lengths]
    log(f"prompt lengths {lengths.tolist()}")
    params = build_model(cfg).init(jax.random.PRNGKey(SEED))
    toks_p, sched_p = _serve(cfg, params, "pallas", prompts)
    n_pre, n_dec = _mosaic_kernels(sched_p, params, prompts)
    log(f"tpu_custom_call: prefill {n_pre}, decode {n_dec}")
    check(n_pre > 0 and n_dec > 0, "a serving step runs no Mosaic kernel")
    toks_x, sched_x = _serve(cfg, params, "xla", prompts)
    prefix = [next((i for i, (a, b) in enumerate(zip(p, x)) if a != b),
                   SERVE_NEW) for p, x in zip(toks_p, toks_x)]
    log(f"free-running greedy tokens: common prefix per request {prefix}")

    # both paths replay the XLA run's tokens, so every step compares
    # logits for the same context
    ref_toks = np.asarray(toks_x)
    lx = _forced(sched_x, params, prompts, toks_x)
    lp = _forced(sched_p, params, prompts, toks_x)
    check((lx.argmax(-1) == ref_toks).all(),
          "the XLA replay differs from its own run")
    diff = np.abs(lp - lx).max(-1)                    # (requests, steps)
    top2 = np.sort(lx, -1)[..., -2:]
    decided = top2[..., 1] - top2[..., 0] > 2 * diff  # argmax cannot flip
    agree = lp.argmax(-1) == ref_toks
    log(f"teacher-forced over {SERVE_NEW} tokens: max|pallas-xla| "
        f"{float(diff.max()):.5f}; pallas picks the XLA token at "
        f"{int(agree.sum())}/{agree.size} steps; {int(decided.sum())} "
        f"steps decided by the margin, of which {int(agree[decided].sum())}"
        f" agree")
    check(agree[decided].all(),
          "the Pallas path picks another token where the margin decides")

    # the float32 reference: XLA attention, f32 compute, f32 matmuls
    rt32 = sched_x.rt.replace(compute_dtype="float32")
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, b: sched_x.model.prefill_fn(
            p, b, rt32, max_seq=sched_x.max_seq)[0][:, -1])
        l32 = np.concatenate([np.asarray(ref(params, {
            "tokens": sched_x._pad_prompts([Request(0, p) for p in w])}))
            for w in _waves(prompts)])
    err_p = float(np.abs(lp[:, 0] - l32).max())
    err_x = float(np.abs(lx[:, 0] - l32).max())
    log(f"prefill logits vs float32: max|err| pallas {err_p:.5f}, xla "
        f"{err_x:.5f} (allowed: pallas <= {SERVE_LOGIT_FACTOR} x xla); "
        f"logit std {float(l32.std()):.3f}")
    check(err_p <= SERVE_LOGIT_FACTOR * err_x,
          f"pallas logits {err_p} from float32, xla {err_x}")
    del params
    rc = serve.main(["--arch", ARCH, "--batch", "4", "--prompt-len", "256",
                     "--gen-tokens", "16", "--seed", str(SEED)])
    check(rc == 0, f"serve.main exited {rc}")


# -------------------------------------------------------------- kernels
def _compare(name, out, ref, atol, t0):
    import numpy as np
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    err = float(np.max(np.abs(out - ref)))
    ok = bool(np.all(np.abs(out - ref) <= atol + atol * np.abs(ref)))
    log(f"  {name}: shape {out.shape} max|err| {err:.2e} (atol=rtol="
        f"{atol}) {time.time() - t0:.2f}s incl. compile")
    check(ok and np.all(np.isfinite(out)), f"{name} differs from ref.py")


def phase_kernels():
    import jax
    import jax.numpy as jnp
    from repro.kernels import tiling
    from repro.kernels.flash_attention import ops as fa, ref as fa_ref
    from repro.kernels.flash_decode import ops as fd, ref as fd_ref
    from repro.kernels.rmsnorm import ops as rms, ref as rms_ref
    from repro.kernels.ssm_scan import ops as ssm, ref as ssm_ref
    from repro.models.layers import quantize_kv
    check(not tiling.interpret_mode(), "kernels would run interpreted")
    bf16 = jnp.bfloat16
    key = jax.random.PRNGKey(SEED)
    # the oracles at full f32 precision: the TPU's default f32 matmul
    # takes bf16 passes
    exact = jax.default_matmul_precision("highest")
    tr = lambda t: t.transpose(0, 2, 1, 3) if t is not None else None

    for name, (B, S, H, Hkv, hd) in FLASH:
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (B, S, H, hd)).astype(bf16)
        k, v = (jax.random.normal(kx, (B, S, Hkv, hd)).astype(bf16)
                for kx in (kk, kv))
        t0 = time.time()
        out = jax.block_until_ready(fa.flash_attention(q, k, v))
        rep = lambda t: jnp.repeat(t, H // Hkv, axis=2)
        with exact:
            ref = tr(fa_ref.attention_ref(tr(q), tr(rep(k)), tr(rep(v))))
        _compare(f"flash_attention {name}", out, ref, 2e-2, t0)

    B, H, Hkv, hd = 8, 9, 3, 64
    for name, kv_dtype, S, length in DECODE:
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, 1, H, hd)).astype(bf16)
        kq, ksc = quantize_kv(jax.random.normal(ks[1], (B, S, Hkv, hd)),
                              kv_dtype)
        vq, vsc = quantize_kv(jax.random.normal(ks[2], (B, S, Hkv, hd)),
                              kv_dtype)
        t0 = time.time()
        out = jax.block_until_ready(fd.flash_decode(q, kq, vq, length, ksc,
                                                    vsc, block_kv=512))
        with exact:
            ref = tr(fd_ref.decode_ref(tr(q), tr(kq), tr(vq), tr(ksc),
                                       tr(vsc), jnp.array([length])))
        _compare(f"flash_decode {name} S={S}", out, ref, 2e-2, t0)

    H, P, N = 112, 64, 64
    for name, S in SSM:
        ks = jax.random.split(key, 5)
        X = jax.random.normal(ks[0], (1, S, H, P)).astype(bf16)
        Bm = (jax.random.normal(ks[1], (1, S, N)) * 0.5).astype(bf16)
        Cm = (jax.random.normal(ks[2], (1, S, N)) * 0.5).astype(bf16)
        dt = jax.nn.softplus(jax.random.normal(ks[3], (1, S, H)))
        la = -dt * jnp.exp(jax.random.normal(ks[4], (H,)) * 0.2)[None, None]
        t0 = time.time()
        Y, h = jax.block_until_ready(ssm.ssm_scan(X, Bm, Cm, dt, la,
                                                  chunk=256))
        with exact:
            Yr, hr = ssm_ref.ssm_scan_ref(X, Bm, Cm, dt, la)
        _compare(f"ssm_scan {name} S={S} Y", Y, Yr, 5e-2, t0)
        _compare(f"ssm_scan {name} S={S} state", h, hr, 5e-2, t0)

    for rows, d in RMS:
        k1, k2 = jax.random.split(key)
        x = jax.random.normal(k1, (rows, d)).astype(bf16)
        s = (jax.random.normal(k2, (d,)) * 0.1 + 1.0).astype(bf16)
        t0 = time.time()
        out = jax.block_until_ready(rms.rmsnorm(x, s))
        _compare(f"rmsnorm ({rows}, {d})", out, rms_ref.rmsnorm_ref(x, s),
                 2e-2, t0)


# --------------------------------------------------------------- trials
def phase_trials():
    import jax
    import numpy as np
    from jax.sharding import AxisType, Mesh
    from repro.configs.base import ShapeConfig
    from repro.core.measure import CachedMeasure, TimingCache
    from repro.core.params import default_config
    from repro.core.trial import (FAILURE_DETERMINISTIC, RooflineEvaluator,
                                  WallClockEvaluator, Workload)

    # the screen: compile-only on the CPU backend's placeholder devices;
    # the tuner's baseline (launch/tune.py)
    t0 = time.time()
    res = RooflineEvaluator(use_cache=False)(
        Workload(ARCH, "train_4k"),
        default_config(shard_strategy="fsdp_tp", attn_impl="pallas"))
    log(f"roofline train_4k: cost {res.cost_s:.4f}s (model) crashed="
        f"{res.crashed} {res.error[:200]} compiles={res.compiles} "
        f"{time.time() - t0:.1f}s")
    check(not res.crashed and math.isfinite(res.cost_s),
          f"roofline trial crashed: {res.error[:300]}")

    class OneChipTrain4k(Workload):
        """train_4k's per-chip share: one sequence of 4096 tokens."""

        @property
        def shp(self):
            return ShapeConfig("train_4k_1chip", 4096, 1, "train")

        def key(self):
            return super().key() + "__1chip"

    one = lambda multi_pod=False: Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"),
        axis_types=(AxisType.Auto,) * 2)
    measured = CachedMeasure(WallClockEvaluator(one,
                                                repeats=WALLCLOCK_REPEATS),
                             cache=TimingCache(use_disk=False))
    wl = OneChipTrain4k(ARCH, "train_4k")
    base = default_config(remat_policy="full")
    for name, rt in [("remat=full", base),
                     ("remat=full bf16", base.replace(
                         compute_dtype="bfloat16"))]:
        res = measured(wl, rt)
        log(f"wall-clock {name}: median {res.cost_s:.4f}s of "
            f"{WALLCLOCK_REPEATS}, compile {res.compile_s}s, compiles="
            f"{res.compiles} cached={res.cached} {res.error[:200]}")
        check(not res.crashed and res.compiles == 1 and not res.cached
              and math.isfinite(res.cost_s), f"wall-clock {name} failed")
    # the default remat policy does not fit one chip at 4096 tokens: a
    # deterministic crash, as the paper's out-of-memory runs
    res = measured(wl, default_config())
    log(f"wall-clock default (remat=dots): crashed={res.crashed} "
        f"failure={res.failure} {res.error[:120]}")
    check(res.crashed and res.failure == FAILURE_DETERMINISTIC
          and "RESOURCE_EXHAUSTED" in res.error,
          "the default config was expected to exceed one chip's memory")


# ----------------------------------------------------------- four chips
def phase_four_chips():
    import jax
    import numpy as np
    from jax.sharding import AxisType, Mesh
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.core.params import default_config
    from repro.data.pipeline import SyntheticLM
    from repro.models.model import build_model
    from repro.optim.optimizers import cosine_schedule, make_optimizer
    from repro.runtime.stepfn import build_train_step
    cfg = get_config(ARCH)
    shape = ShapeConfig("four_chip", TRAIN_SEQ, TRAIN_BATCH, "train")
    rt = default_config(compute_dtype="bfloat16", shard_strategy="fsdp_tp",
                        remat_policy="full")
    devs = jax.devices()
    losses = {}
    for name, grid in (("2x2", (2, 2)), ("1 chip", (1, 1))):
        mesh = Mesh(np.array(devs[:grid[0] * grid[1]]).reshape(grid),
                    ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        opt = make_optimizer(cfg.optimizer,
                             cosine_schedule(3e-4, 10, TRAIN_STEPS))
        bundle = build_train_step(cfg, shape, rt, mesh, opt)
        t0 = time.time()
        with mesh:
            step = bundle.lower().compile()
            p_sh, o_sh, _ = step.input_shardings[0]
            params = build_model(cfg).init(jax.random.PRNGKey(SEED))
            opt_state = jax.device_put(opt.init(params), o_sh)
            params = jax.device_put(params, p_sh)
            data = SyntheticLM(cfg, shape, rt, mesh, seed=SEED)
            log(f"{name}: compiled in {time.time() - t0:.1f}s")
            for leaf in ("embedding",):
                arr = params["embed"][leaf]
                log(f"  param {leaf} {arr.shape} {arr.sharding.spec}: "
                    + ", ".join(f"dev{s.device.id}{s.data.shape}"
                                for s in arr.addressable_shards))
            blk = jax.tree.leaves(params["blocks"])[0]
            log(f"  param blocks[0] {blk.shape} {blk.sharding.spec}: "
                + ", ".join(f"dev{s.device.id}{s.data.shape}"
                            for s in blk.addressable_shards))
            losses[name] = []
            for i in range(TRAIN_STEPS):
                batch = data.batch_at(i)
                if i == 0:
                    tk = batch["tokens"]
                    log(f"  batch tokens {tk.shape} {tk.sharding.spec}: "
                        + ", ".join(f"dev{s.device.id}{s.data.shape}"
                                    for s in tk.addressable_shards))
                    if grid == (2, 2):
                        check(len({s.device.id for s in
                                   arr.addressable_shards}) == 4
                              and arr.addressable_shards[0].data.size
                              < arr.size, "parameters are not sharded")
                        check(tk.addressable_shards[0].data.shape[0]
                              == TRAIN_BATCH // 2, "batch is not split")
                t1 = time.time()
                params, opt_state, met = step(params, opt_state, batch)
                losses[name].append(float(met["loss"]))
                log(f"  step {i} loss {losses[name][-1]:.6f} "
                    f"{time.time() - t1:.3f}s")
        del params, opt_state
    diffs = [abs(a - b) for a, b in zip(losses["2x2"], losses["1 chip"])]
    log(f"per-step |loss 2x2 - loss 1 chip| {diffs} (atol "
        f"{FOUR_CHIP_LOSS_ATOL})")
    check(all(math.isfinite(x) for x in losses["2x2"]), "non-finite loss")
    check(max(diffs) <= FOUR_CHIP_LOSS_ATOL, "the sharded losses differ")


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (data=2, model=2) train pair")
    args = ap.parse_args(argv)
    chip = request_tpu()
    import jax
    cache_dir = chip.enable_compile_cache()
    cache_events = {"hits": 0, "requests": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            cache_events["requests"] += 1
    jax.monitoring.register_event_listener(on_event)

    chips = 4 if args.four_chips else 1
    phases = [("four_chips", phase_four_chips)] if args.four_chips else [
        ("train", phase_train), ("serve", phase_serve),
        ("kernels", phase_kernels), ("trials", phase_trials)]
    t_all = time.time()
    log("== device")
    try:
        dev = phase_device(chips)
    except Exception:
        traceback.print_exc()
        fail("phase device FAILED")
    failed = []
    for name, fn in phases:
        log(f"== {name}")
        t0 = time.time()
        try:
            fn()
            log(f"== {name} passed in {time.time() - t0:.1f}s")
        except Exception:
            traceback.print_exc()
            log(f"== {name} FAILED after {time.time() - t0:.1f}s")
            failed.append(name)
    log(f"compile cache {cache_dir}: {cache_events['hits']} hits of "
        f"{cache_events['requests']} cacheable compiles; total "
        f"{time.time() - t_all:.1f}s")
    if failed:
        fail(f"failed phases: {', '.join(failed)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
