"""BENCHMARK.json: its shape, and every cell resolving by name to its
configuration, traffic, runner, reference, limits and metric readers."""
import copy
import json
import pathlib
import re

import pytest

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    names = ([c["name"] for c in SPEC["configs"]] + CELLS
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in SPEC["end_to_end"]:
        assert 0 < m["bound"] <= 0.25 and m["source"] in ("host_clock",
                                                           "device_trace")
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    for w in SPEC["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    cell = harness.resolve(name)
    assert cell.config["arch"]["name"] == cell.config_name
    assert callable(harness.runner_module(cell).Runner)
    ref = harness.reference_module(cell)
    assert callable(ref.loss) and callable(ref.prefill)
    assert cell.limits, "the cell's limits are set"
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.metric_module(m["name"]).read)
        assert m["moves"] in e2e


def test_every_configuration_has_a_cell_and_its_widths_are_published():
    keys = {"d_model": "hidden_size", "n_heads": "num_attention_heads",
            "n_kv_heads": "num_key_value_heads", "vocab": "vocab_size",
            "d_ff": "intermediate_size", "n_layers": "num_hidden_layers",
            "ssm_state": "mamba_d_state", "ssm_head_dim": "mamba_headdim",
            "ssm_expand": "mamba_expand", "ssm_chunk": "chunk_size",
            "head_dim": "kv_channels"}
    for c in SPEC["configs"]:
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        for ours, theirs in keys.items():
            if ours in cfg["arch"] and theirs in cfg:
                assert cfg["arch"][ours] == cfg[theirs], (c["name"], ours)


def test_a_cell_added_as_data_resolves_without_editing_a_file():
    spec = copy.deepcopy(SPEC)
    spec["workloads"].append({"name": "zamba2-7b-1p.train-4k",
                              "config": "zamba2-7b-1p",
                              "traffic": "train-4k", "chips": 1,
                              "why": "a cell made of existing files"})
    cell = harness.resolve("zamba2-7b-1p.train-4k", spec)
    assert cell.traffic["kind"] == "train_steps"
    assert harness.runner_module(cell).Runner
    # metrics without a workloads list apply to it; listed ones do not
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}


def test_unknown_names_are_refused():
    with pytest.raises(harness.BenchError):
        harness.resolve("no-such-cell")
    with pytest.raises(harness.BenchError):
        harness.peaks("cpu")
