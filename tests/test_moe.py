"""The one-device MoE path and OLMoE's block at a size the CPU holds.

The grouped (dropless) expert layer is held to the per-slot oracle
``moe._dense_moe`` under skewed routing.  A reduced OLMoE (QK-norm on,
top-k gates not renormalized) is held to the plain float32 reference of
the benchmark (``bench/configs/olmoe-1b-7b-4l.ref.py``) on the
benchmark's seeded weights: prefill logits and cache, decode through the
cache, and the train loss and gradients.  Everything computes in
float32, so each tolerance allows only float32 round-off in another
order of summation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, refops, weights
from repro.configs import get_config
from repro.core.params import default_config
from repro.models import moe
from repro.models.layers import init_params
from repro.models.model import build_model

#: OLMoE's block at widths the CPU holds, every axis a distinct length
TINY = get_config("olmoe-1b-7b").replace(
    name="olmoe-tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
    head_dim=16, d_ff=48, vocab=512, n_experts=8, top_k=2)
RT = default_config(compute_dtype="float32", kv_cache_dtype="float32")
SEED = 2**31 + 11
#: float32 against float32 in another order of summation: the gaps read
#: ~1e-6 (logits, cache, loss and each gradient leaf); 1e-4 leaves room
#: for other seeds, while a routing that differs (a flipped top-k choice,
#: a gate renormalized or not) or a missing QK-norm moves them by 1e-2
#: or more
RTOL = 1e-4


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


# ------------------------------------------------ grouped vs the oracle
def _steer(x, router, pull):
    """Inputs and router weights that add ``pull[e]`` or more to expert
    e's logit for every token; the seeded rest of the logits (std ~0.2)
    decides between experts pulled alike."""
    x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 1.0)
    for e, w in pull.items():
        router = router.at[0, e].set(w)
    return x, router


@pytest.mark.parametrize("case,top_k,norm", [
    ("seeded", 2, False), ("seeded", 2, True),
    ("skewed_five_empty", 2, False),
    ("every_row_to_one_expert", 1, False)])
def test_grouped_path_matches_the_dense_oracle(case, top_k, norm):
    cfg = TINY.replace(top_k=top_k, norm_topk_prob=norm)
    p = init_params(moe.moe_spec(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 20, cfg.d_model))
    if case == "skewed_five_empty":      # 5 always, then 2 or 6
        x, p["router"] = _steer(x, p["router"], {5: 20.0, 2: 10.0, 6: 10.0})
    elif case == "every_row_to_one_expert":
        x, p["router"] = _steer(x, p["router"], {3: 20.0})
    y, aux, load = jax.jit(lambda p, x: moe.moe_mlp(p, x, cfg, RT, None))(
        p, x)
    y_ref, aux_ref = moe._dense_moe(p, x, cfg, RT)
    assert _rel(y, y_ref) < 1e-5
    np.testing.assert_allclose(aux, aux_ref, rtol=1e-6)
    _, gi, _ = moe._route(x.reshape(-1, cfg.d_model), p["router"], cfg)
    counts = np.bincount(np.asarray(gi).ravel(), minlength=cfg.n_experts)
    np.testing.assert_allclose(load, counts.max() / counts.mean(),
                               rtol=1e-6)
    if case == "every_row_to_one_expert":
        assert counts[3] == x.shape[0] * x.shape[1]
        assert float(load) == cfg.n_experts
    if case == "skewed_five_empty":
        assert counts[5] == x.shape[0] * x.shape[1]
        assert counts[2] > 0 and counts[6] > 0 and (counts > 0).sum() == 3


def test_grouped_path_gradients_match_the_dense_oracle():
    cfg = TINY
    p = init_params(moe.moe_spec(cfg), jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, cfg.d_model))
    r = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    grouped = jax.grad(lambda p, x: jnp.sum(
        moe.moe_mlp(p, x, cfg, RT, None)[0] * r), (0, 1))(p, x)
    dense = jax.grad(lambda p, x: jnp.sum(
        moe._dense_moe(p, x, cfg, RT)[0] * r), (0, 1))(p, x)
    for g, d in zip(jax.tree.leaves(grouped), jax.tree.leaves(dense)):
        assert _rel(g, d) < 1e-5


# ------------------------------------------------ OLMoE vs the reference
@pytest.fixture(scope="module")
def olmoe():
    """The reduced model, the reference and float32 weights from SEED."""
    model = build_model(TINY)
    ref = harness.load_module(harness.BENCH / "configs"
                              / "olmoe-1b-7b-4l.ref.py")
    params = weights.make(model.param_shapes(jnp.float32), model.logical(),
                          SEED)
    arch = {k: getattr(TINY, k) for k in (
        "n_experts", "top_k", "norm_topk_prob", "norm_eps", "rope_theta",
        "vocab")}
    toks = np.random.default_rng(SEED).integers(0, TINY.vocab, (2, 24))
    return model, ref, params, arch, jnp.asarray(toks, jnp.int32)


def _ref(fn, *args):
    with jax.default_matmul_precision("highest"):
        return fn(*args, refops.F32)


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_prefill_matches_the_reference(olmoe, attn_impl):
    model, ref, params, arch, toks = olmoe
    rt = RT.replace(attn_impl=attn_impl)
    logits, cache = jax.jit(lambda p, b: model.prefill_fn(p, b, rt))(
        params, {"tokens": toks})
    for r in range(toks.shape[0]):
        lg, c = _ref(ref.prefill, params, toks[r], arch)
        assert _rel(logits[r, -1, :TINY.vocab], lg) < RTOL
        for name in ("k", "v"):
            assert _rel(cache["layers"][name][:, r],
                        c["layers"][name]) < RTOL


def test_decode_through_the_cache_matches_the_full_forward(olmoe):
    model, ref, params, arch, toks = olmoe
    P, S = 16, toks.shape[1]
    _, cache = jax.jit(lambda p, b: model.prefill_fn(p, b, RT, max_seq=S))(
        params, {"tokens": toks[:, :P]})
    decode = jax.jit(lambda p, c, t: model.decode_fn(p, c, t, RT))
    full = [_ref(ref.logits, params, toks[r], arch)
            for r in range(toks.shape[0])]
    for t in range(P, S):
        lg, cache = decode(params, cache, toks[:, t:t + 1])
        for r in range(toks.shape[0]):
            assert _rel(lg[r, 0, :TINY.vocab], full[r][t]) < RTOL


def test_train_loss_and_gradients_match_the_reference(olmoe):
    """One sequence, so that the program's load-balancing loss (taken
    over the batch's tokens) is the reference's."""
    model, ref, params, arch, toks = olmoe
    batch = {"tokens": toks[:1, :-1], "labels": toks[:1, 1:]}
    (loss, met), g = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, batch, RT), has_aux=True))(params)
    ref_loss, ref_g = _ref(lambda p, a, mm: jax.value_and_grad(
        lambda q: ref.loss(q, toks[0, :-1], toks[0, 1:], a, mm))(p),
        params, arch)
    np.testing.assert_allclose(loss, ref_loss, rtol=RTOL)
    assert float(met["moe_max_load"]) >= 1.0
    flat = jax.tree_util.tree_flatten_with_path(ref_g)[0]
    for (path, r), p in zip(flat, jax.tree.leaves(g)):
        assert _rel(p, r) < RTOL, jax.tree_util.keystr(path)


def test_param_count_counts_the_qk_norm_scales():
    cfg = get_config("olmoe-1b-7b")
    size = lambda c: sum(int(np.prod(s.shape)) for s in
                         jax.tree.leaves(build_model(c).param_shapes()))
    plain = cfg.replace(qk_norm=False)
    scales = cfg.n_layers * (cfg.n_heads + cfg.n_kv_heads) * cfg.hd
    assert size(cfg) - size(plain) == scales
    assert cfg.param_count() - plain.param_count() == scales
