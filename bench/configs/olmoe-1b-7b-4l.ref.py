"""Plain float32 reference of OLMoE-1B-7B as the program lays out its
weights: RMSNorm, rotary multi-head attention with QK-norm (RMSNorm over
the whole q and the whole k projection, before RoPE), and a mixture of
SwiGLU experts in every layer: a softmax router over all experts, the
top-k of its gates (renormalized only where ``norm_topk_prob``) and the
gate-weighted sum of the chosen experts; untied embedding and head.  One
sequence at a time; ``mm`` is the matrix-product policy of
``bench/refops.py``.

The reference routes on its own float32 router logits.  It computes
every expert for every token in blocks of ``EXPERT_BLOCK`` experts, each
weighted by its gate where the token chose it and by 0 elsewhere, so
that it shares nothing with the program's sort and grouped products and
its intermediates stay small at the published widths."""
import jax
import jax.numpy as jnp

from bench import refops as R

#: experts computed at once
EXPERT_BLOCK = 8
#: the program's weight of the load-balancing loss (models/transformer.py)
AUX_COEF = 0.01


def attention(p, x, pos, a, mm, cache=False):
    S = x.shape[0]
    q = mm("sd,dhk->shk", x, p["wq"])
    k = mm("sd,dhk->shk", x, p["wk"])
    v = mm("sd,dhk->shk", x, p["wv"])
    q = R.rmsnorm(q.reshape(S, -1), p["q_norm"]["ln"],
                  a["norm_eps"]).reshape(q.shape)
    k = R.rmsnorm(k.reshape(S, -1), p["k_norm"]["ln"],
                  a["norm_eps"]).reshape(k.shape)
    q = R.rope(q, pos, a["rope_theta"])
    k = R.rope(k, pos, a["rope_theta"])
    out = mm("shk,hkd->sd", R.causal_attention(q, k, v, mm), p["wo"])
    return (out, k, v) if cache else out


def experts(p, x, a, mm):
    """(S, d) -> (S, d) output of the expert layer and the Switch-style
    load-balancing loss E * sum_e (share of the gates) * (share of the
    choices), as the program states it."""
    S, E, k = x.shape[0], a["n_experts"], a["top_k"]
    gates = jax.nn.softmax(mm("sd,de->se", x, p["router"]), -1)
    top, idx = jax.lax.top_k(gates, k)
    if a.get("norm_topk_prob", True):
        top = top / jnp.sum(top, -1, keepdims=True)
    # each token's weight of each expert: its gate if chosen, else 0
    w = jnp.zeros((S, E), R.f32).at[jnp.arange(S)[:, None], idx].set(top)
    block = min(EXPERT_BLOCK, E)

    def one(y, e0):
        take = lambda t: jax.lax.dynamic_slice_in_dim(t, e0, block)
        h = (jax.nn.silu(mm("sd,edf->esf", x, take(p["wg"])))
             * mm("sd,edf->esf", x, take(p["wu"])))
        out = mm("esf,efd->esd", h, take(p["wd"]))
        we = jax.lax.dynamic_slice_in_dim(w, e0, block, axis=1)
        return y + jnp.sum(out * we.T[:, :, None], 0), None

    y, _ = jax.lax.scan(one, jnp.zeros(x.shape, R.f32),
                        jnp.arange(0, E, block))
    chosen = jnp.mean(jnp.sum(jax.nn.one_hot(idx, E, dtype=R.f32), 1), 0)
    aux = E * jnp.sum(jnp.mean(gates, 0) * chosen)
    return y, aux


def _hidden(p, tokens, a, mm, cache=False):
    S = tokens.shape[0]
    pos = jnp.arange(S)
    h = p["embed"]["embedding"][tokens].astype(R.f32)

    @jax.checkpoint
    def layer(h, bp):
        out = attention(bp["attn"], R.rmsnorm(h, bp["ln1"], a["norm_eps"]),
                        pos, a, mm, cache=cache)
        y, kv = (out[0], out[1:]) if cache else (out, None)
        h = h + y
        y, aux = experts(bp["moe"], R.rmsnorm(h, bp["ln2"], a["norm_eps"]),
                         a, mm)
        return h + y, (kv, aux)

    h, (kv, aux) = jax.lax.scan(layer, h, p["blocks"])
    return R.rmsnorm(h, p["final_norm"], a["norm_eps"]), kv, jnp.sum(aux)


def logits(p, tokens, a, mm):
    """(S, vocab) logits of one sequence."""
    h, _, _ = _hidden(p, tokens, a, mm)
    return R.logits(p["embed"]["unembed"], h, a["vocab"], mm, tied=False)


def loss(p, tokens, labels, a, mm):
    """The program's training loss of one sequence: cross-entropy plus
    ``AUX_COEF`` times the load-balancing loss summed over the layers.
    The program takes that loss over a whole batch's tokens, so the two
    agree for a batch of one sequence."""
    h, _, aux = _hidden(p, tokens, a, mm)
    lg = R.logits(p["embed"]["unembed"], h, a["vocab"], mm, tied=False)
    return R.xent(lg, labels) + AUX_COEF * aux


def prefill(p, tokens, a, mm):
    """Last-position logits (vocab,) and the cache {k, v} of shape
    (layers, S, kv heads, head dim) for one sequence."""
    h, (k, v), _ = _hidden(p, tokens, a, mm, cache=True)
    lg = R.logits(p["embed"]["unembed"], h[-1:], a["vocab"], mm, tied=False)
    return lg[0], {"layers": {"k": k, "v": v}}
