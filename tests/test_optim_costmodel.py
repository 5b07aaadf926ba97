"""Optimizers converge on a quadratic; cost model parses known HLO."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import costmodel
from repro.optim.optimizers import (adafactor, adamw, constant_schedule,
                                    cosine_schedule)


@pytest.mark.parametrize("make", [
    lambda: adamw(constant_schedule(0.05), weight_decay=0.0),
    lambda: adafactor(constant_schedule(0.1), min_dim=4),
])
def test_optimizer_converges_quadratic(make):
    opt = make()
    target = jnp.array(np.random.RandomState(0)
                       .standard_normal((8, 8)), jnp.float32)
    params = {"w": jnp.zeros((8, 8))}
    state = opt.init(params)

    @jax.jit
    def step(p, s):
        g = jax.grad(lambda pp: jnp.mean((pp["w"] - target) ** 2))(p)
        return opt.update(g, s, p)

    for _ in range(600):
        params, state, met = step(params, state)
    assert float(jnp.mean((params["w"] - target) ** 2)) < 5e-2


def test_state_specs_match_state_structure():
    from jax.sharding import PartitionSpec as P
    shapes = {"a": jax.ShapeDtypeStruct((128, 256), jnp.float32),
              "b": jax.ShapeDtypeStruct((7,), jnp.float32)}
    specs = {"a": P("data", "model"), "b": P(None)}
    for opt in (adamw(constant_schedule(1e-3)),
                adafactor(constant_schedule(1e-3))):
        st_shapes = jax.eval_shape(opt.init, shapes)
        st_specs = opt.state_specs(specs, shapes)
        # same tree structure (so shardings can be zipped)
        assert (jax.tree.structure(jax.tree.map(lambda x: 0, st_shapes))
                == jax.tree.structure(
                    jax.tree.map(lambda x: 0, st_specs,
                                 is_leaf=lambda s: isinstance(s, P))))


def test_cosine_schedule_shape():
    lr = cosine_schedule(1e-3, warmup=10, total=100)
    assert float(lr(0)) == 0.0
    assert float(lr(10)) == pytest.approx(1e-3, rel=1e-5)
    assert float(lr(100)) == pytest.approx(1e-4, rel=1e-3)


# ------------------------------------------------------------ costmodel
HLO_SAMPLE = """
  %all-reduce.7 = (f32[], f32[4,8]{1,0}, f32[8,4]{1,0}) all-reduce(%a, %b, %c), channel_id=2, replica_groups=[2,4]<=[8]
  %all-gather.3 = bf16[64,128]{1,0} all-gather(%x), channel_id=3, replica_groups=[16,16]<=[256]
  %rs = f32[32]{0} reduce-scatter(%y), channel_id=4, replica_groups=[1,512]<=[512]
  %a2a = s8[4,16,8]{2,1,0} all-to-all(%z), channel_id=5, replica_groups=[16,16]<=[256]
  %cp = f32[8]{0} collective-permute(%w), channel_id=6, source_target_pairs={{0,1}}
  %dot.4 = f32[8,8] dot(%p, %q)
"""


def test_parse_collectives_kinds_and_bytes():
    stats = costmodel.parse_collectives(HLO_SAMPLE)
    kinds = sorted(op.kind for op in stats.ops)
    assert kinds == ["all-gather", "all-reduce", "all-to-all",
                     "collective-permute", "reduce-scatter"]
    s = stats.summary()
    assert s["all-reduce"]["bytes"] == 4 + 32 * 4 + 32 * 4
    assert s["all-gather"]["bytes"] == 64 * 128 * 2
    assert s["all-to-all"]["bytes"] == 4 * 16 * 8
    ar = [op for op in stats.ops if op.kind == "all-reduce"][0]
    assert ar.group_size == 4
    ag = [op for op in stats.ops if op.kind == "all-gather"][0]
    assert ag.group_size == 16


def test_collective_seconds_ring_model():
    stats = costmodel.CollectiveStats(
        [costmodel.CollectiveOp("all-reduce", 1000_000, 16)])
    t = costmodel.collective_seconds(stats, pod_size=10**9)
    expect = 2 * (15 / 16) * 1e6 / costmodel.HW["ici_bw"] \
        + costmodel.HW["ici_latency"]
    assert t == pytest.approx(expect, rel=1e-6)
    # pod-axis group (size <= 4) goes over DCN
    stats2 = costmodel.CollectiveStats(
        [costmodel.CollectiveOp("all-gather", 1000_000, 2)])
    t2 = costmodel.collective_seconds(stats2, pod_size=256)
    assert t2 == pytest.approx((1 / 2) * 1e6 / costmodel.HW["dcn_bw"]
                               + costmodel.HW["ici_latency"], rel=1e-6)


def test_cost_analysis_is_per_partition():
    """Verify the per-partition normalization assumption on real HLO."""
    import subprocess, sys, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from jax.sharding import AxisType
        mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
        x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        f = jax.jit(lambda a: a @ a,
                    in_shardings=NamedSharding(mesh, P("data", None)))
        fl = f.lower(x).compile().cost_analysis()["flops"]
        # full matmul = 2*64^3; per-partition should be ~1/4
        print(fl / (2 * 64**3))
    """)
    import os, pathlib
    root = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ,
               PYTHONPATH=str(root / "src"),
               JAX_PLATFORMS=os.environ.get("JAX_PLATFORMS", "cpu"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env, cwd=str(root))
    assert out.returncode == 0, out.stderr[-1500:]
    ratio = float(out.stdout.strip().splitlines()[-1])
    assert 0.2 <= ratio <= 0.35, f"per-partition ratio {ratio}"
