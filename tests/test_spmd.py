"""SPMD integration tests (run in subprocesses with 8 fake host devices
so the main pytest process keeps seeing 1 device, per the dry-run rule).

Covers: dp == tp == fsdp numerical equivalence of a real train step,
explicit-collective gradsync == auto path, and MoE expert-parallel
all-to-all path == dense reference.
"""
import os
import subprocess
import sys
import textwrap

import pytest


def run_sub(code: str, timeout=570) -> str:
    prelude = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
    """)
    out = subprocess.run([sys.executable, "-c", prelude + textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/root",
                              # keep children off TPU autodetection (no
                              # hardware attached; blocks for minutes)
                              "JAX_PLATFORMS": os.environ.get(
                                  "JAX_PLATFORMS", "cpu")},
                         cwd="/root/repo")
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_strategies_numerically_equivalent():
    """The shuffle-manager knob changes transport, not math: one train
    step under dp / tp / fsdp / fsdp_tp produces the same loss + params."""
    out = run_sub("""
        from repro.configs import get_reduced
        from repro.configs.base import ShapeConfig
        from repro.core.params import default_config
        from repro.launch.mesh import make_mesh
        from repro.models.model import build_model, synth_inputs
        from repro.optim.optimizers import constant_schedule, make_optimizer
        from repro.runtime.stepfn import build_train_step

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_reduced("smollm-135m")
        shape = ShapeConfig("t", 64, 8, "train")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        opt = make_optimizer("adamw", constant_schedule(1e-3))
        batch = synth_inputs(cfg, shape, default_config(),
                             jax.random.PRNGKey(1))
        results = {}
        for strat in ("dp", "tp", "fsdp", "fsdp_tp"):
            rt = default_config(shard_strategy=strat, donate_buffers=False)
            b = build_train_step(cfg, shape, rt, mesh, opt)
            with mesh:
                p2, s2, met = b.fn(params, opt.init(params), batch)
            results[strat] = (float(met["loss"]),
                              float(jnp.mean(jnp.abs(p2["final_norm"]))))
            print(strat, results[strat], "explicit:",
                  b.notes["explicit_comm"])
        base = results["dp"]
        for k, v in results.items():
            assert abs(v[0] - base[0]) < 1e-4, (k, v, base)
            assert abs(v[1] - base[1]) < 1e-4, (k, v, base)
        print("EQUIVALENT")
    """)
    assert "EQUIVALENT" in out


@pytest.mark.slow
def test_gradsync_comm_dtype_and_fusion_close_to_f32():
    """bf16/fused gradient collectives change bytes, not correctness."""
    out = run_sub("""
        from repro.configs import get_reduced
        from repro.configs.base import ShapeConfig
        from repro.core.params import default_config
        from repro.launch.mesh import make_mesh
        from repro.models.model import build_model, synth_inputs
        from repro.optim.optimizers import constant_schedule, make_optimizer
        from repro.runtime.stepfn import build_train_step

        mesh = make_mesh((8,), ("data",))
        cfg = get_reduced("smollm-135m")
        shape = ShapeConfig("t", 64, 8, "train")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        opt = make_optimizer("adamw", constant_schedule(1e-3))
        batch = synth_inputs(cfg, shape, default_config(),
                             jax.random.PRNGKey(1))
        losses = {}
        for name, kw in {
            "f32": dict(),
            "bf16": dict(grad_comm_dtype="bfloat16"),
            "fused": dict(fuse_grad_collectives=True),
            "fsdp_bf16": dict(grad_comm_dtype="bfloat16"),
        }.items():
            rt = default_config(shard_strategy="fsdp"
                                if name.startswith("fsdp") else "dp",
                                donate_buffers=False, **kw)
            b = build_train_step(cfg, shape, rt, mesh, opt)
            assert b.notes["explicit_comm"], name
            with mesh:
                p2, s2, met = b.fn(params, opt.init(params), batch)
            losses[name] = float(met["loss"])
            print(name, losses[name])
        for k, v in losses.items():
            assert abs(v - losses["f32"]) < 5e-3, (k, v)
        print("GRADSYNC_OK")
    """)
    assert "GRADSYNC_OK" in out


@pytest.mark.slow
def test_int8_ef_gradient_compression_converges():
    """int8+error-feedback all-reduce: loss still decreases ~like f32."""
    out = run_sub("""
        from repro.configs import get_reduced
        from repro.configs.base import ShapeConfig
        from repro.core.params import default_config
        from repro.launch.mesh import make_mesh
        from repro.models.model import build_model, synth_inputs
        from repro.optim.optimizers import constant_schedule, make_optimizer
        from repro.runtime.stepfn import build_train_step

        mesh = make_mesh((8,), ("data",))
        cfg = get_reduced("smollm-135m")
        shape = ShapeConfig("t", 64, 8, "train")
        model = build_model(cfg)
        params0 = model.init(jax.random.PRNGKey(0))
        opt = make_optimizer("adamw", constant_schedule(1e-3))
        batch = synth_inputs(cfg, shape, default_config(),
                             jax.random.PRNGKey(1))
        final = {}
        for gcd in ("float32", "int8_ef"):
            rt = default_config(shard_strategy="dp", grad_comm_dtype=gcd,
                                fuse_grad_collectives=True,
                                donate_buffers=False)
            b = build_train_step(cfg, shape, rt, mesh, opt)
            params = params0
            st = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                              b.args[1])
            with mesh:
                for _ in range(4):
                    params, st, met = b.fn(params, st, batch)
            final[gcd] = float(met["loss"])
            print(gcd, final[gcd])
        assert final["int8_ef"] < 6.25          # decreased from ~6.25
        assert abs(final["int8_ef"] - final["float32"]) < 0.1
        print("EF_OK")
    """)
    assert "EF_OK" in out


@pytest.mark.slow
def test_moe_ep_alltoall_matches_dense():
    """Expert-parallel dispatch/combine == dense reference (generous
    capacity so nothing drops)."""
    out = run_sub("""
        from repro.configs import get_reduced
        from repro.core.params import default_config
        from repro.launch.mesh import make_mesh
        from repro.models import moe
        from repro.models.layers import init_params
        from repro.runtime.sharding import ShardingRules

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_reduced("olmoe-1b-7b").replace(capacity_factor=8.0)
        rt = default_config(compute_dtype="float32",
                            comm_codec="float32")  # uncompressed wire
        spec = moe.moe_spec(cfg)
        params = init_params(spec, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, cfg.d_model))
        rules = ShardingRules(mesh=mesh, strategy="fsdp_tp")
        with mesh:
            y_ep, aux_ep, load_ep = jax.jit(
                lambda p, xx: moe.moe_mlp(p, xx, cfg, rt, rules))(params, x)
        y_dense, aux_dense = moe._dense_moe(params, x, cfg, rt)
        load_one = moe.moe_mlp(params, x, cfg, rt, None)[2]
        err = float(jnp.max(jnp.abs(y_ep - y_dense)))
        print("err", err, "aux", float(aux_ep), float(aux_dense))
        assert err < 1e-4, err
        # EP aux is the mean of per-shard load-balance estimators
        # (standard Switch-style per-device aux) — close to, but not
        # identical with, the global-batch estimator
        assert abs(float(aux_ep) - float(aux_dense)) < 0.1
        # the load is counted over every shard's routing: the global one
        assert abs(float(load_ep) - float(load_one)) < 1e-5
        print("MOE_OK")
    """)
    assert "MOE_OK" in out


@pytest.mark.slow
def test_moe_ep_gather_decode_path():
    out = run_sub("""
        from repro.configs import get_reduced
        from repro.core.params import default_config
        from repro.launch.mesh import make_mesh
        from repro.models import moe
        from repro.models.layers import init_params
        from repro.runtime.sharding import ShardingRules

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_reduced("olmoe-1b-7b").replace(capacity_factor=8.0)
        rt = default_config(compute_dtype="float32",
                            comm_codec="float32")  # uncompressed wire
        params = init_params(moe.moe_spec(cfg), jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 1, cfg.d_model))
        rules = ShardingRules(mesh=mesh, strategy="tp")
        with mesh:
            y_ep, _, _ = jax.jit(
                lambda p, xx: moe.moe_mlp(p, xx, cfg, rt, rules))(params, x)
        y_dense, _ = moe._dense_moe(params, x, cfg, rt)
        err = float(jnp.max(jnp.abs(y_ep - y_dense)))
        print("err", err)
        assert err < 1e-4, err
        print("GATHER_OK")
    """)
    assert "GATHER_OK" in out
