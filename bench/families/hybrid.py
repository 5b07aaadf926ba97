"""Operation inventory of a Zamba2-style hybrid (``family: hybrid``):
``n_layers`` Mamba2 blocks (RMSNorm, input projection, causal
convolution, scan, gated RMSNorm, output projection), and after every
``attn_every``-th one a call of the shared attention+MLP block (RMSNorm,
attention, RMSNorm, MLP); then the final RMSNorm over the positions
whose logits are taken, and the unembedding (see ``bench/work.py``)."""
from bench import work as W


def ops(a, batch, S, logit_positions, dtype):
    L, d = a["n_layers"], a["d_model"]
    groups = L // a["attn_every"]
    d_in, H, _, N = W.ssm_dims(a)
    m, b = batch * S, W.BYTES[dtype]
    # in_proj gives z and x (d_in each), B and C (N each) and dt (H)
    proj = W.add(W.matmul(m, d, 2 * d_in + 2 * N + H, dtype),
                  W.matmul(m, d_in, d, dtype))
    # depthwise causal convolution of width 4 over the d_in channels
    conv = {"flops": 2 * 4 * m * d_in, "bytes": 2 * m * d_in * b}
    return [
        W.entry("rmsnorm", W.rmsnorm(m, d, dtype), L + 2 * groups),
        W.entry("rmsnorm", W.rmsnorm(m, d_in, dtype), L),
        W.entry("mamba_proj", proj, L),
        W.entry("conv", conv, L),
        W.entry("ssm_scan", W.ssm_scan(a, batch, S, dtype), L),
        W.entry("attention_proj", W.attention_proj(a, batch, S, dtype),
                groups),
        W.entry("attention", W.attention(a, batch, S, dtype), groups),
        W.entry("mlp", W.mlp(a, batch, S, dtype), groups),
        W.entry("rmsnorm", W.rmsnorm(batch * logit_positions, d, dtype)),
        W.entry("unembed", W.unembed(a, batch, logit_positions, dtype)),
    ]
