"""The comparison with the plain reference that decides ``correct``.

Train cells (the reference follows the first ``check_steps`` steps, on
the same weights and batches, in float32):

* ``loss``: the largest relative gap of a step's loss;
* ``grad``: the first gradient as the optimizer got it (clipped), read
  back from its first moment after one step; per leaf, the gap between
  the program's norm and the reference's, over the larger of the
  reference's norm of that leaf and of the median leaf; the worst leaf;
* ``update``: the same for the norm of each leaf's change over the
  checked steps.

Leaves whose reference gradient is under a thousandth of the median
leaf's move under Adam by round-off alone; they are left out of
``grad`` and ``update``.

Prefill cells (one sampled step of the window, per sequence):

* ``logits``: RMS gap of the last position's logits over the standard
  deviation of the reference's, the worst sequence;
* ``cache``: RMS gap of each cache leaf over the reference's RMS, the
  worst leaf.

The reference imports nothing of the program; it reads the weights
that ``bench/weights.py`` makes from the seed and the batches of
``bench/tokens.py``.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import refops, weights
from bench.harness import Check

#: the program's AdamW as ``optim/optimizers.make_optimizer("adamw")``
#: states it: cosine schedule peaking at 3e-4 after 100 warm-up steps,
#: betas (0.9, 0.95), eps 1e-8, decoupled weight decay 0.1, gradients
#: clipped to global norm 1
ADAM = dict(peak_lr=3e-4, warmup=100, b1=0.9, b2=0.95, eps=1e-8, wd=0.1,
            clip=1.0)
#: leaves under this share of the median leaf's reference gradient are
#: left out
TINY_GRAD = 1e-3


def _path(p) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in p)


def leaf_norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    paths = [_path(p) for p, _ in flat]
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x.astype(
        jnp.float32)))) for x in jax.tree.leaves(t)])(tree)
    return dict(zip(paths, (float(n) for n in norms)))


def first_grads(opt_state) -> Dict[str, float]:
    """Leaf norms of the (clipped) gradient the optimizer received in its
    first step, from its first moment mu = (1 - b1) g."""
    return {k: v / (1 - ADAM["b1"])
            for k, v in leaf_norms(opt_state["mu"]).items()}


def leaf_norms_of_change(params, make_initial: Callable) -> Dict[str, float]:
    p0 = make_initial()
    diff = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))
    return leaf_norms(diff(params, p0))


def half_batch(batch):
    """The fault 'half of the batch left out, the mean taken over the
    rest': the second half of the rows repeats the first half."""
    out = {}
    for k, v in batch.items():
        h = (v.shape[0] + 1) // 2
        out[k] = np.concatenate([v[:h], v[:v.shape[0] - h]])
    return out


# ------------------------------------------------------------- train
def _adam_step(p, mu, nu, g, t):
    """One step of the program's AdamW (see ADAM), in float32."""
    a = ADAM
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, a["clip"] /
                                               jnp.maximum(gn, 1e-9)), g)
    lr = a["peak_lr"] * jnp.minimum(1.0, t / a["warmup"])
    mu = jax.tree.map(lambda m, x: a["b1"] * m + (1 - a["b1"]) * x, mu, g)
    nu = jax.tree.map(lambda v, x: a["b2"] * v + (1 - a["b2"]) * x * x, nu, g)
    bc1, bc2 = 1 - a["b1"] ** t, 1 - a["b2"] ** t
    p = jax.tree.map(
        lambda w, m, v: w - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + a["eps"])
                                  + a["wd"] * w), p, mu, nu)
    return p, mu, nu, g


def _program(runner, name: str, fn: Callable, matmul: str):
    """``fn(params, *rows, arch, mm)`` jitted over params and rows, kept
    on ``runner`` by name and matrix-product policy, so that a
    calibration over many seeds traces it once."""
    kept = runner.__dict__.setdefault("reference_programs", {})
    if (name, matmul) not in kept:
        arch, mm = runner.cell.config["arch"], refops.MATMULS[matmul]
        kept[name, matmul] = jax.jit(lambda p, *rows: fn(p, *rows, arch, mm))
    return kept[name, matmul]


def reference_train(runner, refmod, matmul: str = "f32",
                    fault: str = "") -> Dict:
    """Loss per step, first clipped gradient and change of each leaf
    after ``runner.n_check`` steps of the reference (float32, or the
    control's matmul policy), on the runner's weights and batches."""
    shard = runner.bundle.in_shardings[0]
    f32 = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                       runner.bundle.args[0])
    p0 = weights.make(f32, runner.logical, runner.seed, shardings=shard)
    row_grad = _program(runner, "row_grad", lambda p, tok, lab, a, mm:
                        jax.value_and_grad(lambda q: refmod.loss(
                            q, tok, lab, a, mm))(p), matmul)

    add = jax.jit(lambda a, b, w: jax.tree.map(lambda x, y: x + w * y, a, b))
    adam = jax.jit(_adam_step)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    p, mu, nu = p0, zeros(p0), zeros(p0)
    losses, grads = [], None
    with jax.default_matmul_precision("highest"):
        for i in range(runner.n_check):
            b = runner.host_batch(i)
            if fault == "half_batch":
                b = half_batch(b)
            rows = b["tokens"].shape[0]
            loss, g = 0.0, zeros(p0)
            for r in range(rows):
                l_r, g_r = row_grad(p, b["tokens"][r], b["labels"][r])
                g = add(g, g_r, 1.0 / rows)
                loss += float(l_r) / rows
            p, mu, nu, gc = adam(p, mu, nu, g, jnp.float32(i + 1))
            losses.append(loss)
            if i == 0:
                grads = leaf_norms(gc)
        diff = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))
        update = leaf_norms(diff(p, p0))
    return {"loss": losses, "grad": grads, "update": update}


def _leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
              keep: List[str]) -> float:
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def train_numbers(observed: Dict, ref: Dict) -> Dict[str, float]:
    med = float(np.median(list(ref["grad"].values())))
    keep = [k for k, v in ref["grad"].items() if v >= TINY_GRAD * med]
    loss = max(abs(a - b) / abs(b) for a, b in zip(observed["loss"],
                                                   ref["loss"]))
    return {"loss": loss,
            "grad": _leaf_gap(observed["grad"], ref["grad"], keep),
            "update": _leaf_gap(observed["update"], ref["update"], keep)}


def checks(numbers: Dict[str, float], limits: Dict) -> List[Check]:
    """The numbers that the cell compares: those its limits name."""
    return [Check(k, v, limits[k]) for k, v in numbers.items() if k in limits]


# ------------------------------------------------------------- prefill
def batch_axes(cache_logical) -> Dict[str, int]:
    """Each cache leaf's row axis, from the program's logical axis names
    (``Model.cache_shapes``)."""
    flat = jax.tree_util.tree_flatten_with_path(
        cache_logical, is_leaf=lambda t: isinstance(t, tuple))[0]
    return {_path(p): lg.index("batch") for p, lg in flat if "batch" in lg}


def reference_prefill(runner, refmod, tokens, matmul: str = "f32"):
    """The reference's last logits (rows, vocab) and cache leaves with a
    leading row axis, one sequence at a time, kept on the device."""
    f32 = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                       runner.bundle.args[0])
    p = weights.make(f32, runner.logical, runner.seed)
    one = _program(runner, "prefill", refmod.prefill, matmul)
    lg, caches = [], []
    with jax.default_matmul_precision("highest"):
        for r in range(tokens.shape[0]):
            l, c = one(p, tokens[r])
            lg.append(l)
            caches.append(c)
    return jnp.stack(lg), jax.tree.map(lambda *xs: jnp.stack(xs), *caches)


@jax.jit
def _logit_gaps(logits, ref):
    """Per row, the RMS gap of the logits over the reference's standard
    deviation; ``logits`` is cut to the reference's vocabulary."""
    lg = logits[:, -1, :ref.shape[-1]].astype(jnp.float32)
    return jnp.sqrt(jnp.mean(jnp.square(lg - ref), -1)) / jnp.std(ref, -1)


@functools.partial(jax.jit, static_argnums=2)
def _leaf_gap_rms(prog, ref, axis: int):
    """RMS gap of a cache leaf over the reference's RMS; the program's
    rows lie along ``axis``."""
    prog = jnp.moveaxis(prog.astype(jnp.float32), axis, 0)
    return jnp.sqrt(jnp.sum(jnp.square(prog - ref)) / jnp.sum(jnp.square(ref)))


def prefill_numbers(logits, cache, ref_logits, ref_cache, vocab: int,
                    batch_axes=None) -> Dict[str, float]:
    """``logits`` (rows, 1, padded vocab) and ``cache`` against the
    reference's, computed on the device in float32.  ``batch_axes`` maps
    each cache leaf's path to its row axis (the program's cache); None
    means axis 0 (a stand-in laid out as the reference is)."""
    per_row = _logit_gaps(logits, ref_logits[:, :vocab])
    worst = 0.0
    for path, ref in jax.tree_util.tree_flatten_with_path(ref_cache)[0]:
        prog = cache
        for k in path:
            prog = prog[k.key]
        ax = 0 if batch_axes is None else batch_axes[_path(path)]
        worst = max(worst, float(_leaf_gap_rms(prog, ref, ax)))
    return {"logits": float(jnp.max(per_row)), "cache": worst}
