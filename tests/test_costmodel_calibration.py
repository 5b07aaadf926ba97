"""Calibration layer: documents XLA's while-body-once counting and
verifies the unroll-extrapolation recovers true per-layer costs."""
import jax
import jax.numpy as jnp
import pytest

from repro.core import costmodel
from repro.configs import get_config, get_reduced


def _scan_flops(n, unroll):
    def f(x, ws):
        if unroll:
            for i in range(n):
                x = x @ ws[i]
            return x
        def body(c, w):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, ws)
        return y
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((n, 64, 64), jnp.float32)
    compiled = jax.jit(f).lower(x, ws).compile()
    return float(compiled.cost_analysis()["flops"])


def test_while_body_counted_once():
    """The raison d'etre of the calibration machinery."""
    assert _scan_flops(8, unroll=False) == pytest.approx(
        _scan_flops(2, unroll=False), rel=1e-3)
    assert _scan_flops(8, unroll=True) == pytest.approx(
        8 * 2 * 64**3, rel=1e-2)


def test_extrapolation_recovers_linear_cost():
    # measured at 1 and 3 units with outside=7, per_unit=2
    out = costmodel.extrapolate(7 + 2 * 1, 7 + 2 * 3, units=10)
    assert out == pytest.approx(7 + 2 * 10)
    # clamping: never negative per-unit
    assert costmodel.extrapolate(10.0, 8.0, units=100) == 10.0


def test_extrapolation_matches_direct_unrolled_compile():
    """Extrapolated flops from (1,3)-unit compiles == direct 6-unit
    unrolled compile (same graph family)."""
    v1 = _scan_flops(1, unroll=True)
    v3 = _scan_flops(3, unroll=True)
    v6 = _scan_flops(6, unroll=True)
    assert costmodel.extrapolate(v1, v3, 6) == pytest.approx(v6, rel=1e-2)


@pytest.mark.parametrize("arch", ["deepseek-coder-33b", "kimi-k2-1t-a32b",
                                  "zamba2-7b", "xlstm-1.3b",
                                  "seamless-m4t-medium"])
def test_calibration_points_shapes(arch):
    cfg = get_config(arch)
    points, units = costmodel.calibration_points(cfg)
    (c1, u1), (c3, u3) = points
    assert (u1, u3) == (1, 3)
    assert units >= 3
    # the small configs are structurally valid (spec builds)
    from repro.models.model import build_model
    for c in (c1, c3):
        build_model(c).param_shapes()


def test_model_flops_moe_counts_active_only():
    cfg = get_config("kimi-k2-1t-a32b")
    from repro.configs import get_shape
    dense_equiv = cfg.param_count()
    active = cfg.active_param_count()
    assert active < dense_equiv / 10          # 1T total, ~32B active
    mf = costmodel.model_flops(cfg, get_shape("train_4k"))
    assert mf == pytest.approx(6.0 * active * 256 * 4096)
