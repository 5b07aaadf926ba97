"""A run with its timed path broken underneath comes out not correct,
and the control (the reference in float8 standing in for the program)
fails the committed limits.  Runs past the harness's look for a chip,
at a size the CPU holds, under each cell's committed limits."""
import jax
import jax.numpy as jnp
import pytest

from bench import compare, harness
from repro.models import transformer, zamba
from repro.runtime import stepfn


def _optimizer(monkeypatch, change):
    """The program's optimizer, its new parameters passed through
    ``change(old, new)``."""
    real = stepfn.make_optimizer

    def make(name, *a, **kw):
        opt = real(name, *a, **kw)

        def update(grads, state, params):
            new, st, met = opt.update(grads, state, params)
            return change(params, new), st, met
        return opt._replace(update=update)
    monkeypatch.setattr(stepfn, "make_optimizer", make)


def state_unchanged(mp):
    _optimizer(mp, lambda old, new: old)


def leaf_moved_double(mp):
    def change(old, new):
        new = dict(new)
        new["final_norm"] = 2 * new["final_norm"] - old["final_norm"]
        return new
    _optimizer(mp, change)


def _rows(fn):
    """Wrap a model function so that the second half of its batch
    repeats the first half."""
    def wrapped(p, batch, *a, **kw):
        h = (batch["tokens"].shape[0] + 1) // 2
        batch = {k: jnp.concatenate([v[:h], v[:v.shape[0] - h]])
                 for k, v in batch.items()}
        return fn(p, batch, *a, **kw)
    return wrapped


def train_half_batch(mp):
    mp.setattr(transformer, "loss_fn", _rows(transformer.loss_fn))


def prefill_half_batch(mod):
    return lambda mp: mp.setattr(mod, "prefill_fn", _rows(mod.prefill_fn))


def answer_altered(mod, row=0, like=1):
    """Sequence ``row``'s logits replaced by sequence ``like``'s."""
    real = mod.prefill_fn

    def prefill_fn(*a, **kw):
        logits, cache = real(*a, **kw)
        return logits.at[row].set(logits[like]), cache
    return lambda mp: mp.setattr(mod, "prefill_fn", prefill_fn)


FAULTS = {
    "train.state_unchanged": ("smollm-135m.train-4k", state_unchanged),
    "train.leaf_moved_double": ("smollm-135m.train-4k", leaf_moved_double),
    "train.half_batch": ("smollm-135m.train-4k", train_half_batch),
    "prefill.half_batch": ("smollm-135m.prefill-2k",
                           prefill_half_batch(transformer)),
    "prefill.answer_altered": ("smollm-135m.prefill-2k",
                               answer_altered(transformer)),
    "prefill.last_answer_altered": ("smollm-135m.prefill-2k",
                                    answer_altered(transformer, -1, 0)),
    "hybrid_prefill.half_batch": ("zamba2-7b-1p.prefill-4k",
                                  prefill_half_batch(zamba)),
    "hybrid_prefill.answer_altered": ("zamba2-7b-1p.prefill-4k",
                                      answer_altered(zamba)),
    "hybrid_prefill.last_answer_altered": ("zamba2-7b-1p.prefill-4k",
                                           answer_altered(zamba, -1, 0)),
}
CELLS = sorted({c for c, _ in FAULTS.values()})


@pytest.mark.parametrize("cell", CELLS)
def test_the_sound_path_is_correct(cell, tiny_cell, cpu_run):
    r = cpu_run(tiny_cell(cell))
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(fault, tiny_cell, cpu_run,
                                            monkeypatch):
    cell, plant = FAULTS[fault]
    plant(monkeypatch)
    r = cpu_run(tiny_cell(cell))
    assert r["correct"] is False, r["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_fails_the_limits(cell, tiny_cell):
    c = tiny_cell(cell)
    d = harness.runner_module(c).Runner(c, 2**31 + 9, jax.devices()[:1])
    ref = harness.reference_module(c)
    if c.traffic["kind"] == "train_steps":
        numbers = compare.train_numbers(
            compare.reference_train(d, ref, "fp8"),
            compare.reference_train(d, ref))
    else:
        numbers = d.numbers(1, None, None, matmul="fp8")
    checks = compare.checks(numbers, c.limits)
    assert not all(ch.ok for ch in checks), numbers
