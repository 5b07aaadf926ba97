"""End-to-end behaviour tests: every assigned architecture's reduced
config trains a step (finite loss/grads) and serves (prefill+decode),
per the smoke-test requirement; plus decode-vs-prefill consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced, list_archs
from repro.configs.base import ShapeConfig
from repro.core.params import default_config
from repro.models.model import build_model, synth_inputs
from repro.optim.optimizers import constant_schedule, make_optimizer

RT = default_config()
TRAIN = ShapeConfig("t", 64, 2, "train")
PREFILL = ShapeConfig("p", 32, 2, "prefill")


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", list_archs())
def test_train_step_smoke(arch, key):
    """One forward+backward+optimizer step: shapes ok, no NaNs."""
    cfg = get_reduced(arch)
    model = build_model(cfg)
    params = model.init(key)
    batch = synth_inputs(cfg, TRAIN, RT, key)
    opt = make_optimizer(cfg.optimizer, constant_schedule(1e-3))
    state = opt.init(params)

    @jax.jit
    def step(p, s, b):
        (loss, _), g = jax.value_and_grad(
            lambda pp: model.loss_fn(pp, b, RT), has_aux=True)(p)
        new_p, new_s, met = opt.update(g, s, p)
        return new_p, new_s, loss, met

    new_params, new_state, loss, met = step(params, state, batch)
    assert jnp.isfinite(loss), f"{arch}: loss not finite"
    assert jnp.isfinite(met["grad_norm"]), f"{arch}: grad norm not finite"
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         params, new_params)
    assert max(jax.tree.leaves(moved)) > 0, f"{arch}: params did not move"
    for leaf in jax.tree.leaves(new_params):
        assert jnp.isfinite(leaf).all(), f"{arch}: non-finite param"


@pytest.mark.parametrize("arch", list_archs())
def test_prefill_decode_smoke(arch, key):
    cfg = get_reduced(arch)
    model = build_model(cfg)
    params = model.init(key)
    batch = synth_inputs(cfg, PREFILL, RT, key)
    logits, cache = jax.jit(
        lambda p, b: model.prefill_fn(p, b, RT, max_seq=48))(params, batch)
    assert logits.shape[0] == 2 and jnp.isfinite(logits).all()
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    logits2, cache2 = jax.jit(
        lambda p, c, t: model.decode_fn(p, c, t, RT))(params, cache, tok)
    assert jnp.isfinite(logits2).all(), f"{arch}: decode logits not finite"
    assert int(cache2["pos"]) == int(cache["pos"]) + 1


@pytest.mark.parametrize("arch", ["smollm-135m", "glm4-9b", "zamba2-7b",
                                  "xlstm-1.3b", "seamless-m4t-medium"])
def test_decode_matches_teacher_forcing(arch, key):
    """Prefill(S) last-token logits == prefill(S-1) + decode(token S-1).

    Uses an f32 KV cache so the check is exact (bf16 caches round at the
    ~1e-1 logit level by design — spark.rdd.compress trade-off)."""
    cfg = get_reduced(arch)
    model = build_model(cfg)
    params = model.init(key)
    rt = default_config(kv_cache_dtype="float32")
    S = 16
    full = synth_inputs(cfg, ShapeConfig("p", S, 2, "prefill"), rt, key)
    logits_full, _ = model.prefill_fn(params, full, rt, max_seq=S)
    short = dict(full)
    short["tokens"] = full["tokens"][:, :S - 1]
    _, cache = model.prefill_fn(params, short, rt, max_seq=S)
    logits_dec, _ = model.decode_fn(params, cache,
                                    full["tokens"][:, S - 1:S], rt)
    err = float(jnp.max(jnp.abs(logits_full[:, -1] - logits_dec[:, 0])))
    assert err < 2e-2, f"{arch}: decode/prefill mismatch {err}"


def test_vlm_frontend_positions(key):
    """VLM: patch embeddings actually feed the backbone."""
    cfg = get_reduced("llava-next-34b")
    model = build_model(cfg)
    params = model.init(key)
    batch = synth_inputs(cfg, TRAIN, RT, key)
    l1, _ = model.loss_fn(params, batch, RT)
    batch2 = dict(batch, frontend_embeds=batch["frontend_embeds"] * 2.0)
    l2, _ = model.loss_fn(params, batch2, RT)
    assert abs(float(l1) - float(l2)) > 0, "frontend embeds ignored"


def test_decode_pallas_matches_xla(key):
    """The flash-decode kernel path == the XLA decode path."""
    cfg = get_reduced("glm4-9b")
    model = build_model(cfg)
    params = model.init(key)
    rt_x = default_config(kv_cache_dtype="float32")
    batch = synth_inputs(cfg, ShapeConfig("p", 16, 2, "prefill"), rt_x, key)
    logits, cache = model.prefill_fn(params, batch, rt_x, max_seq=32)
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    out_x, _ = model.decode_fn(params, cache, tok, rt_x)
    rt_p = rt_x.replace(attn_impl="pallas", attn_block_kv=16)
    out_p, _ = model.decode_fn(params, cache, tok, rt_p)
    err = float(jnp.max(jnp.abs(out_x - out_p)))
    assert err < 2e-3, err


def test_int8_kv_cache_close_to_bf16(key):
    """rdd.compress analogue: int8 KV decode stays close to bf16."""
    cfg = get_reduced("glm4-9b")
    model = build_model(cfg)
    params = model.init(key)
    batch = synth_inputs(cfg, PREFILL, RT, key)
    outs = {}
    for kv in ("bfloat16", "int8"):
        rt = default_config(kv_cache_dtype=kv)
        logits, cache = model.prefill_fn(params, batch, rt, max_seq=40)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        l2, _ = model.decode_fn(params, cache, tok, rt)
        outs[kv] = l2
    err = float(jnp.max(jnp.abs(outs["bfloat16"] - outs["int8"])))
    assert err < 0.5, f"int8 kv cache diverges: {err}"


@pytest.mark.parametrize("dtype,atol,seq", [
    pytest.param("float32", 2e-5, 256, id="float32-2e-05"),
    pytest.param("bfloat16", 3e-2, 256, id="bfloat16-0.03"),
    pytest.param("float32", 2e-5, 1024, id="float32-2e-05-chunked"),
    pytest.param("bfloat16", 3e-2, 1024, id="bfloat16-0.03-chunked")])
def test_attention_block_pallas_matches_xla(key, monkeypatch, dtype, atol,
                                            seq):
    """Causal attention through the flash kernel, which takes K/V at the
    KV heads, == the XLA path, which takes them repeated to the query
    heads, on a GQA config (9 query heads over 3 KV heads); at 1024 the
    XLA path computes its queries in chunks (``layers.query_chunks``)."""
    import dataclasses
    from repro.kernels.flash_attention import ops as fa_ops
    from repro.models import layers as L
    cfg = dataclasses.replace(get_reduced("smollm-135m"), n_heads=9,
                              n_kv_heads=3, head_dim=32)
    params = L.init_params(L.attn_spec(cfg), key)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, seq, cfg.d_model))
    positions = jnp.broadcast_to(jnp.arange(seq), (2, seq))
    chunks = L.query_chunks(seq, seq, causal=True)
    assert (len(chunks) > 1) == (seq > 256)
    kv_heads = {}

    def spy(impl, fn):
        def wrapped(q, k, v, **kw):
            kv_heads[impl] = (q.shape[2], k.shape[2], v.shape[2])
            return fn(q, k, v, **kw)
        return wrapped

    monkeypatch.setattr(L, "full_attention", spy("xla", L.full_attention))
    monkeypatch.setattr(fa_ops, "flash_attention",
                        spy("pallas", fa_ops.flash_attention))
    out = {}
    for impl in ("xla", "pallas"):
        rt = default_config(compute_dtype=dtype, attn_impl=impl)
        out[impl] = L.attention_block(params, x.astype(dtype), cfg=cfg,
                                      rt=rt, rules=None, positions=positions)
    assert kv_heads == {"xla": (9, 9, 9), "pallas": (9, 3, 3)}
    np.testing.assert_allclose(np.asarray(out["pallas"], np.float32),
                               np.asarray(out["xla"], np.float32),
                               atol=atol, rtol=atol)
