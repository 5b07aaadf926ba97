"""Device time credited to the program's layer scopes through the
compiled step's op metadata (``bench/metrics/_scope.py``)."""
import json
import pathlib

import jax
import pytest

from bench import harness, trace_reduce
from bench.metrics import _scope as S

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
SCOPE_METRICS = [m["name"] for m in SPEC["per_layer"]
                 if (ROOT / "bench" / "metrics" / f"{m['name']}.py"
                     ).read_text().count("bench.metrics._scope")]
VOCAB = S.vocabulary()

#: a compiled module's text: a fusion under attention's recomputation,
#: a matmul of the head's transpose, an MLP op nested in a norm (the
#: outermost scope wins), a loop counter with no scope, a copy with no
#: metadata whose users disagree, a fusion named by the computation it
#: calls, a chain of a copy and a bitcast with no metadata that feeds the
#: SSD scan inside Mamba2 alone, and an instruction the compiler merged
#: from two scopes (the first path decides)
HLO = r"""HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %exp.1 = f32[4]{0} exponential(%param_0), metadata={op_name="jit(step)/transpose(jvp())/while/body/checkpoint/rematted_computation/attention/exp"}
}

ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.7 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_type="exp" op_name="jit(step)/transpose(jvp())/while/body/checkpoint/rematted_computation/attention/exp" source_file="x.py" source_line=3}
  %dot.2 = f32[4]{0} dot(%fusion.7, %p), metadata={op_name="jit(step)/transpose(jvp(head))/bsd,dv->bsv/dot_general"}
  %add.3 = f32[4]{0} add(%dot.2, %p), metadata={op_name="jit(step)/jvp()/norm/mlp/add"}
  %add.4 = s32[] add(%dot.2, %p), metadata={op_name="jit(step)/while/body/add"}
  %copy.5 = f32[4]{0} copy(%add.3)
  %fusion.6 = (f32[4]{0}, f32[4]{0}) fusion(%copy.5), kind=kLoop, calls=%fused_computation.1
  %copy.9 = f32[4]{0:T(128)} copy(%dot.2)
  %bitcast.10 = f32[4]{0} bitcast(%copy.9)
  %multiply.11 = f32[4]{0} multiply(%bitcast.10, %p), metadata={op_name="jit(step)/while/body/mamba2/ssd_scan/mul"}
  %add.12 = f32[4]{0} add(%multiply.11, %p), metadata={op_name="jit(step)/while/body/attention/add;mlp/add"}
  ROOT %fusion.8 = f32[4]{0} fusion(%copy.5, %add.12), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jit(norm)/optimizer/sub"}
}
"""
OPS = {"%fusion.7": 3.0, "%dot.2": 2.0, "%add.3": 1.5, "%add.4": 0.25,
       "%copy.5": 0.5, "%fusion.6": 0.125, "%fusion.8": 0.75,
       "%copy.9": 0.0625, "%multiply.11": 0.5, "%add.12": 0.375}
SCOPED = {"attention": 3.0 + 0.125 + 0.375, "head": 2.0, "norm": 1.5,
          "optimizer": 0.75, "mamba2": 0.0625 + 0.5}
#: the loop counter and the copy whose users disagree
UNSCOPED = 0.25 + 0.5


def test_the_map_holds_every_instruction_of_the_module():
    names = S.op_names(HLO, VOCAB)
    assert set(names) == {"%param_0", "%exp.1", "%p", "%fusion.7", "%dot.2",
                          "%add.3", "%add.4", "%copy.5", "%fusion.6",
                          "%copy.9", "%bitcast.10", "%multiply.11",
                          "%add.12", "%fusion.8"}
    assert names["%fusion.6"] == names["%exp.1"]
    assert names["%dot.2"].endswith("transpose(jvp(head))/bsd,dv->bsv/"
                                    "dot_general")


def test_an_instruction_without_a_scope_takes_its_users_path():
    names = S.op_names(HLO, VOCAB)
    # through a bitcast, to the one layer that uses it
    assert names["%copy.9"] == names["%bitcast.10"] == names["%multiply.11"]
    # users in two layers: no scope
    assert S.scope_of(names["%copy.5"], VOCAB) is None
    # no user: its own path
    assert names["%add.4"] == "jit(step)/while/body/add"


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/jvp()/while/body/closed_call/mlp/add", "mlp"),
    ("jit(step)/transpose(jvp(head))/bsd,dv->bsv/dot_general", "head"),
    ("jit(step)/while/body/mamba2/ssd_scan/while/body/exp", "mamba2"),
    ("jit(step)/jvp()/norm/mlp/add", "norm"),
    ("jit(step)/jit(norm)/reduce_sum", None),
    ("jit(step)/while/body/reshape;mamba2/ssd_scan/reshape", "mamba2"),
    ("jit(step)/while/body/attention/add;mlp/add", "attention"),
    ("jit(step)/while/body/mlp/add;attention/add", "mlp"),
    ("jit(step)/while/body/add", None),
    ("", None),
])
def test_an_op_is_credited_to_the_outermost_scope(op_name, scope):
    assert S.scope_of(op_name, VOCAB) == scope


def test_the_scopes_and_the_unscoped_time_partition_the_ops():
    c = S.credit(OPS, S.op_names(HLO, VOCAB), VOCAB)
    assert c.scopes == pytest.approx(SCOPED)
    assert c.unscoped == pytest.approx(UNSCOPED)
    assert c.missing == 0
    assert sum(c.scopes.values()) + c.unscoped == pytest.approx(c.total)
    assert c.total == pytest.approx(sum(OPS.values()))
    # recompute and the scope nested in mamba2 cut across the partition
    assert c.within[S.RECOMPUTE] == pytest.approx(3.125)
    assert c.within["ssd_scan"] == pytest.approx(0.5625)
    assert c.within["mlp"] == pytest.approx(1.5 + 0.375)


def test_an_op_that_is_no_instruction_of_the_module_is_missing():
    c = S.credit(dict(OPS, **{"%not-in-the-module.1": 1.0}),
                 S.op_names(HLO, VOCAB), VOCAB)
    assert c.missing == 1.0
    assert c.unscoped == pytest.approx(UNSCOPED + 1.0)
    assert sum(c.scopes.values()) + c.unscoped == pytest.approx(c.total)


def _ctx(ops, steps=4, chips=1):
    reduced = trace_reduce.Reduced(window_s=1.0, busy_s=1.0, ops=ops,
                                   gaps={}, device_events=[], chips=chips)
    return dict(trace=reduced, counts={"kind": "train", "steps": steps},
                peaks={}, chips=chips)


@pytest.fixture
def traced_cell(monkeypatch):
    """The readers as a traced run of a train cell calls them: the cell
    named on the command line, its step's text the module above."""
    compiled = []

    def step_text(cell, devices):
        compiled.append(cell.name)
        return HLO

    monkeypatch.setattr(S, "step_text", step_text)
    monkeypatch.setattr("sys.argv", ["bench/run.py", "--workload",
                                     "smollm-135m.train-4k", "--trace", "1"])
    return compiled


def test_the_readers_give_device_ms_per_step(traced_cell):
    ctx = _ctx(OPS, steps=4, chips=2)
    read = {name: harness.metric_module(name).read(ctx)
            for name in SCOPE_METRICS}
    assert traced_cell == ["smollm-135m.train-4k"]     # one map a run
    per_step = 1e3 / (4 * 2)
    assert read["attention_ms.step"] == pytest.approx(3.5 * per_step)
    assert read["head_ms.step"] == pytest.approx(2.0 * per_step)
    assert read["optimizer_ms.step"] == pytest.approx(0.75 * per_step)
    assert read["mamba2_ms.step"] == pytest.approx(0.5625 * per_step)
    assert read["ssd_scan_ms.step"] == pytest.approx(0.5625 * per_step)
    assert read["recompute_ms.step"] == pytest.approx(3.125 * per_step)
    assert read["unscoped_share.step"] == pytest.approx(
        100 * UNSCOPED / sum(OPS.values()))
    # no op under its outermost scope: no value, never 0
    assert read["mlp_ms.step"] is None


def test_a_program_without_scopes_reads_nothing(traced_cell):
    ctx = _ctx({"%add.4": 1.0, "%copy.5": 2.0})
    assert all(harness.metric_module(name).read(ctx) is None
               for name in SCOPE_METRICS)


def test_a_map_that_misses_an_op_of_the_trace_reads_nothing(traced_cell):
    """A map from another program than the one traced (a stale cache
    entry, a drifted rebuild) must not pass its ops off as unscoped."""
    ctx = _ctx(dict(OPS, **{"%not-in-the-module.1": 1e-6}))
    assert all(harness.metric_module(name).read(ctx) is None
               for name in SCOPE_METRICS)


def test_without_a_cell_on_the_command_line_there_is_no_map(monkeypatch):
    monkeypatch.setattr("sys.argv", ["pytest", "-q"])
    monkeypatch.setattr(S, "step_text", pytest.fail)
    ctx = _ctx(OPS)
    assert all(harness.metric_module(name).read(ctx) is None
               for name in SCOPE_METRICS)


def test_every_reader_names_a_scope_of_the_program():
    """Each reader of ``_scope.py`` is a listed metric, and one that
    names a scope names one of the vocabulary and is called after it."""
    readers = [f.name[:-len(".py")]
               for f in (ROOT / "bench" / "metrics").glob("*.py")
               if "bench.metrics._scope" in f.read_text()]
    assert readers and sorted(readers) == sorted(SCOPE_METRICS)
    for name in readers:
        scope = getattr(harness.metric_module(name), "SCOPE", None)
        if scope is not None:
            assert scope in VOCAB and name == f"{scope}_ms.step"


@pytest.mark.parametrize("name", ["smollm-135m.train-4k",
                                  "zamba2-7b-1p.prefill-4k"])
def test_the_map_is_the_runners_step_compiled_with_metadata_in_the_key(
        name, tiny_cell, monkeypatch):
    key = "jax_compilation_cache_include_metadata_in_key"
    seen = []
    compile_ = jax.stages.Lowered.compile

    def compile_spy(self, *a, **k):
        seen.append(getattr(jax.config, key))
        return compile_(self, *a, **k)

    cell = tiny_cell(name, batch=2, seq=64)
    devices = jax.devices()[:1]
    runner_text = harness.runner_module(cell).Runner(
        cell, 2**31 + 7, devices).bundle.lower().compile().as_text()
    monkeypatch.setattr(jax.stages.Lowered, "compile", compile_spy)
    text = S.step_text(cell, devices)
    assert seen == [True] and getattr(jax.config, key) is False
    names = S.op_names(text, VOCAB)
    assert set(names) == set(S.op_names(runner_text, VOCAB))
    assert S.credit({n: 1.0 for n in names}, names, VOCAB).scopes


def test_an_untraced_run_builds_no_map(tiny_cell, cpu_run, monkeypatch):
    monkeypatch.setattr(S, "step_text", pytest.fail)
    cell = tiny_cell("smollm-135m.prefill-2k", batch=2, seq=64)
    result = cpu_run(cell, seconds=0.2)
    assert "tokens_per_s" in result["metrics"]
    assert jax.config.jax_compilation_cache_include_metadata_in_key is False
