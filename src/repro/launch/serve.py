"""Batched serving driver: prefill a prompt batch, decode N tokens.

CPU: ``--reduced`` serves a reduced config for real.  One TPU chip:
the full config (``chip_smoke.py`` runs smollm-135m this way).  Attention
takes the XLA path; ``serving.BatchScheduler`` with ``attn_impl="pallas"``
is the path that runs the Pallas kernels.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_reduced
from repro.configs.base import ShapeConfig
from repro.core.params import default_config
from repro.launch.chip import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.model import build_model, synth_inputs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-tokens", type=int, default=16)
    ap.add_argument("--kv-dtype", default="bfloat16")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    rt = default_config(compute_dtype="bfloat16",
                        kv_cache_dtype=args.kv_dtype)
    mesh = make_host_mesh()
    model = build_model(cfg)
    max_seq = args.prompt_len + args.gen_tokens

    with mesh:
        params = model.init(jax.random.PRNGKey(args.seed))
        pshape = ShapeConfig("serve", args.prompt_len, args.batch, "prefill")
        batch = synth_inputs(cfg, pshape, rt, jax.random.PRNGKey(args.seed))

        prefill = jax.jit(
            lambda p, b: model.prefill_fn(p, b, rt, max_seq=max_seq))
        decode = jax.jit(lambda p, c, t: model.decode_fn(p, c, t, rt))

        t0 = time.time()
        logits, cache = prefill(params, batch)
        jax.block_until_ready(logits)
        t_prefill = time.time() - t0
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)

        generated = [tok]
        t0 = time.time()
        for _ in range(args.gen_tokens - 1):
            logits, cache = decode(params, cache, tok)
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
            generated.append(tok)
        jax.block_until_ready(tok)
        t_dec = time.time() - t0
        toks = jnp.concatenate(generated, axis=1)

    n_dec = args.batch * (args.gen_tokens - 1)
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len}")
    print(f"prefill: {t_prefill*1e3:.1f} ms "
          f"({args.batch*args.prompt_len/t_prefill:.0f} tok/s)")
    print(f"decode:  {t_dec*1e3:.1f} ms for {n_dec} tokens "
          f"({n_dec/max(t_dec,1e-9):.0f} tok/s)")
    print(f"sample tokens[0,:8]: {toks[0,:8].tolist()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
