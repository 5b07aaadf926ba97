"""Operation inventory of a dense decoder (``family: dense``): per
layer, RMSNorm, attention, RMSNorm and MLP; then the final RMSNorm over
the positions whose logits are taken, and the unembedding (see
``bench/work.py``)."""
from bench import work as W


def ops(a, batch, S, logit_positions, dtype):
    L, d = a["n_layers"], a["d_model"]
    return [
        W.entry("rmsnorm", W.rmsnorm(batch * S, d, dtype), 2 * L),
        W.entry("attention_proj", W.attention_proj(a, batch, S, dtype), L),
        W.entry("attention", W.attention(a, batch, S, dtype), L),
        W.entry("mlp", W.mlp(a, batch, S, dtype), L),
        W.entry("rmsnorm", W.rmsnorm(batch * logit_positions, d, dtype)),
        W.entry("unembed", W.unembed(a, batch, logit_positions, dtype)),
    ]
