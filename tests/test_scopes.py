"""The model's layers carry their scope (``models/scopes.py``) into the
compiled step: every matmul, convolution and custom call of a train or
prefill step names its layer, and remat's recomputation says so.  A
scope is read as the benchmark reads it (``bench/metrics/_scope.py``)."""
import re

import jax
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh

from bench.metrics._scope import scope_of
from repro.configs import get_reduced
from repro.configs.base import ShapeConfig
from repro.core.params import default_config
from repro.models import scopes
from repro.runtime.stepfn import build_prefill_step, build_train_step

RT = default_config().replace(remat_policy="full")
#: an instruction of the compiled text: name, opcode, op_name
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%\S+) = (?:\([^)]*\)|\S+) "
                         r"([\w\-]+)\((.*)$")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)


def _instructions(text):
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            op = OP_NAME.search(m.group(3))
            yield m.group(1), m.group(2), op.group(1) if op else ""


def _scope(op_name):
    return scope_of(op_name, scopes.VOCABULARY)


def _lowered(arch, kind):
    cfg = get_reduced(arch)
    build = build_train_step if kind == "train" else build_prefill_step
    return build(cfg, ShapeConfig("s", 64, 2, kind), RT, _mesh()).lower()


@pytest.mark.parametrize("arch,kind", [("smollm-135m", "train"),
                                       ("smollm-135m", "prefill"),
                                       ("zamba2-7b", "train"),
                                       ("zamba2-7b", "prefill")])
def test_every_matmul_and_custom_call_names_its_layer(arch, kind):
    text = _lowered(arch, kind).compile().as_text()
    heavy = [(n, op) for n, opcode, op in _instructions(text)
             if opcode in ("dot", "convolution", "custom-call")]
    assert heavy
    unnamed = [(n, op) for n, op in heavy if _scope(op) is None]
    assert not unnamed, unnamed[:5]
    found = {_scope(op) for _, _, op in _instructions(text)} - {None}
    want = {"embed", "norm", "attention", "mlp", "head"}
    if kind == "train":
        want.add("optimizer")
    if arch == "zamba2-7b":
        want.add("mamba2")
        assert any("/mamba2/" in op and "/ssd_scan/" in op
                   for _, _, op in _instructions(text))
    assert want <= found, want - found
    if kind == "train":
        recomputed = {_scope(op) for _, _, op in _instructions(text)
                      if "rematted_computation" in op.split("/")}
        layers = {"attention", "mlp"} if arch == "smollm-135m" \
            else {"mamba2"}
        assert layers <= recomputed, recomputed


def test_a_name_outside_the_vocabulary_is_refused():
    with pytest.raises(ValueError):
        scopes.scope("attn")
    with scopes.scope("mlp"):
        pass
