"""The reduction from a profiler trace to busy, idle, per-op time and
gaps by host span."""
import pathlib

import pytest

from bench import trace_reduce as T

E = T.Event
SAMPLE = pathlib.Path(T.__file__).with_name("trace_reduce.sample.xplane.pb")


def test_interval_algebra():
    assert T.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert T.clip([(0, 2), (3, 5)], 1, 4) == [(1, 2), (3, 4)]
    assert T.subtract([(0, 10)], [(1, 2), (4, 5)]) == [(0, 1), (2, 4),
                                                       (5, 10)]
    assert T.subtract([(0, 1), (2, 3)], [(0, 3)]) == []
    assert T.length([(0, 1), (2, 4)]) == 3


def _synthetic():
    ops = [E("%fusion.1", 1.0, 2.0), E("%fusion.2", 1.5, 3.0),
           E("%all-gather.3", 3.0, 4.0), E("%fusion.4", 3.5, 4.0),
           E("%while.5", 1.0, 6.0), E("%fusion.6", 5.0, 6.0),
           E("%fusion.7", 9.0, 9.5)]
    devices = {0: {"XLA Ops": ops}, 1: {"XLA Ops": [E("%fusion.1", 1, 5)]}}
    host = [E("bench.window", 0.5, 8.0), E("bench.step", 0.4, 1.2),
            E("bench.sync", 6.0, 8.0), E("bench.feed", 6.5, 7.0)]
    return devices, host


def test_busy_idle_ops_and_gaps_of_a_known_trace():
    r = T.reduce(*_synthetic(), chip_ids=[0])
    assert r.window_s == pytest.approx(7.5)
    assert r.busy_s == pytest.approx(5.0)      # [1, 6]; 9.0 lies outside
    # the loop holds other ops: out of the sums, not out of busy
    assert "%while.5" not in r.ops
    assert r.ops["%fusion.2"] == pytest.approx(1.5)
    # gaps: [0.5, 1] under bench.step, [6, 8] under bench.sync (its
    # middle, 7.0, is covered by bench.feed's end too: the innermost
    # span that covers it is bench.feed)
    assert r.gaps == pytest.approx({"bench.step": 0.5, "bench.feed": 2.0})
    assert r.kernel("fusion") == (4, pytest.approx(4.0))   # in the window


def test_busy_is_averaged_over_the_chips():
    r = T.reduce(*_synthetic(), chip_ids=[0, 1])
    assert r.busy_s == pytest.approx((5.0 + 4.0) / 2)
    assert r.chips == 2


def test_a_trace_without_the_window_or_the_chip_is_refused():
    devices, host = _synthetic()
    with pytest.raises(ValueError):
        T.reduce(devices, host[1:], [0])
    with pytest.raises(ValueError):
        T.reduce(devices, host, [2])


def test_op_names_are_hlo_instruction_names():
    assert T.op_name("%flash_attention_bhsd.6 = bf16[4] custom-call(%a)"
                     ) == "%flash_attention_bhsd.6"


def test_a_recorded_chip_trace():
    """A trace recorded on a TPU v5e: a small jitted program (two fusions)
    run three times, each followed by a 50 ms host sleep inside its
    ``bench.sleep`` span, all inside ``bench.window``.  The device's
    clock reads about 1.5 ms behind the host's there, so the first run's
    ops fall before the window opens, and two runs count."""
    devices, host = T.read(str(SAMPLE))
    assert sorted(devices) == [0]
    assert len(devices[0]["XLA Ops"]) == 12
    assert [h.name for h in host].count("bench.sleep") == 3
    r = T.reduce(devices, host, [0])
    assert r.window_s == pytest.approx(0.154518627, abs=1e-9)
    assert r.busy_s == pytest.approx(0.000361681, abs=1e-9)
    assert set(r.gaps) == {"bench.sleep"}
    assert r.gaps["bench.sleep"] == pytest.approx(r.window_s - r.busy_s)
    assert r.top_ops(2) == [["%fusion", pytest.approx(0.000181741)],
                            ["%convolution_tanh_fusion",
                             pytest.approx(0.000179907)]]
    assert r.kernel("fusion") == (2, pytest.approx(0.000181741))


def test_metric_readers_on_a_known_trace():
    from bench import harness
    devices = {0: {"XLA Ops": [E("%flash_attention_bhsd.3", 0.0, 2.0),
                               E("%fusion.1", 2.0, 3.0),
                               E("%flash_attention_bhsd.4", 3.0, 5.0)]}}
    host = [E("bench.window", 0.0, 10.0)]
    r = T.reduce(devices, host, [0])
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    counts = {"kind": "prefill", "steps": 2, "step_flops": 150.0,
              "ops": [{"op": "attention", "flops": 30.0, "bytes": 1.0},
                      {"op": "attention", "flops": 10.0, "bytes": 0.0},
                      {"op": "rmsnorm", "flops": 0.0, "bytes": 1.0}]}
    ctx = dict(trace=r, counts=counts, peaks=peaks, chips=1)
    read = lambda name: harness.metric_module(name).read(ctx)
    assert read("idle_share.step") == pytest.approx(50.0)
    # 2 steps x 150 operations over 10 s x 100 per second
    assert read("mfu.step") == pytest.approx(30.0)
    # least time 2 x max(40 / 100, 1 / 10) = 0.8 s over 4 s of kernel
    assert read("flash_attention_roofline.step") == pytest.approx(20.0)
    # a kernel with no calls in the trace reads nothing, not 0
    assert read("rmsnorm_roofline.step") is None
    # nor does a step whose inventory has no such operation
    ctx["counts"] = dict(counts, ops=counts["ops"][2:])
    assert read("flash_attention_roofline.step") is None
    ctx["counts"] = dict(counts, kind="serve")
    assert read("mfu.step") is None and read("idle_share.step") is None
