"""Plain float32 reference of smollm-135m as the program lays out its
weights: a Llama-style decoder (RMSNorm, rotary grouped-query attention,
SwiGLU MLP, tied embedding).  One sequence at a time; ``mm`` is the
matrix-product policy of ``bench/refops.py``."""
import jax
import jax.numpy as jnp

from bench import refops as R


def _hidden(p, tokens, a, mm, cache=False):
    S = tokens.shape[0]
    pos = jnp.arange(S)
    h = p["embed"]["embedding"][tokens].astype(R.f32)

    @jax.checkpoint
    def layer(h, bp):
        out = R.attention(bp["attn"], R.rmsnorm(h, bp["ln1"], a["norm_eps"]),
                          pos, mm, a["rope_theta"], cache=cache)
        y, kv = (out[0], out[1:]) if cache else (out, None)
        h = h + y
        h = h + R.mlp(bp["mlp"], R.rmsnorm(h, bp["ln2"], a["norm_eps"]), mm,
                      a["mlp_act"])
        return h, kv

    h, kv = jax.lax.scan(layer, h, p["blocks"])
    return R.rmsnorm(h, p["final_norm"], a["norm_eps"]), kv


def logits(p, tokens, a, mm):
    """(S, vocab) logits of one sequence."""
    h, _ = _hidden(p, tokens, a, mm)
    return R.logits(p["embed"]["embedding"], h, a["vocab"], mm, tied=True)


def loss(p, tokens, labels, a, mm):
    return R.xent(logits(p, tokens, a, mm), labels)


def prefill(p, tokens, a, mm):
    """Last-position logits (vocab,) and the cache {k, v} of shape
    (layers, S, kv heads, head dim) for one sequence."""
    h, (k, v) = _hidden(p, tokens, a, mm, cache=True)
    lg = R.logits(p["embed"]["embedding"], h[-1:], a["vocab"], mm, tied=True)
    return lg[0], {"layers": {"k": k, "v": v}}
