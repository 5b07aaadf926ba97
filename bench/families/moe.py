"""Operation inventory of a decoder whose every layer is a mixture of
experts (``family: moe``): per layer, RMSNorm, the attention projections
with a QK-norm of q and k where the configuration has one (the same
RMSNorm kernel), attention, RMSNorm, the router and the experts; then the
final RMSNorm over the positions whose logits are taken, and the
unembedding (see ``bench/work.py``).

``expert_ffn`` is the three products of the experts over the T * top_k
rows routed to them (gate and up, d to d_ff, and down): each expert's
weights read once, each routed row read once and written once.
"""
from bench import work as W


def expert_ffn(a, batch, S, dtype):
    d, f, E = a["d_model"], a["d_ff"], a["n_experts"]
    rows = batch * S * a["top_k"]
    return {"flops": 3 * 2 * rows * d * f,
            "bytes": (3 * E * d * f + 2 * rows * d) * W.BYTES[dtype]}


def ops(a, batch, S, logit_positions, dtype):
    L, d, m = a["n_layers"], a["d_model"], batch * S
    out = [W.entry("rmsnorm", W.rmsnorm(m, d, dtype), 2 * L)]
    if a.get("qk_norm"):
        q, k = a["n_heads"] * W.hd(a), a["n_kv_heads"] * W.hd(a)
        out += [W.entry("rmsnorm", W.rmsnorm(m, q, dtype), L),
                W.entry("rmsnorm", W.rmsnorm(m, k, dtype), L)]
    return out + [
        W.entry("attention_proj", W.attention_proj(a, batch, S, dtype), L),
        W.entry("attention", W.attention(a, batch, S, dtype), L),
        W.entry("router", W.matmul(m, d, a["n_experts"], dtype), L),
        W.entry("expert_ffn", expert_ffn(a, batch, S, dtype), L),
        W.entry("rmsnorm", W.rmsnorm(batch * logit_positions, d, dtype)),
        W.entry("unembed", W.unembed(a, batch, logit_positions, dtype)),
    ]
