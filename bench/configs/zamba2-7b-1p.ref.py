"""Plain float32 reference of one Zamba2-7B period as the program lays
out its weights: Mamba2 blocks (RMSNorm, input projections, a depthwise
causal conv of width 4 on x, the state-space recurrence, a gated
RMSNorm and the output projection) in groups of ``attn_every``, each
group followed by the one shared attention+MLP block.  The departures
from the published model are listed in ``zamba2-7b-1p.json``.  One
sequence at a time; ``mm`` is the matrix-product policy of
``bench/refops.py``."""
import jax
import jax.numpy as jnp

from bench import refops as R


def _mamba(mp, x, a, mm):
    """One Mamba2 block on (S, d) -> (out, {ssm, conv} state)."""
    S, d = x.shape
    d_in = a["ssm_expand"] * d
    P = a["ssm_head_dim"]
    H = d_in // P
    h = R.rmsnorm(x, mp["ln"], a["norm_eps"])
    z = mm("sd,de->se", h, mp["wz"])
    xin = mm("sd,de->se", h, mp["wx"])
    Bm = mm("sd,dn->sn", h, mp["wB"])
    Cm = mm("sd,dn->sn", h, mp["wC"])
    dt = jax.nn.softplus(mm("sd,dh->sh", h, mp["wdt"]) + mp["dt_bias"])
    A = -jnp.exp(mp["A_log"])
    xp = jnp.concatenate([jnp.zeros((3, d_in), R.f32), xin], 0)
    conv = sum(xp[i:i + S] * mp["conv"][i] for i in range(4))
    X = jax.nn.silu(conv).reshape(S, H, P)
    Y, hs = R.ssd_scan(X, Bm, Cm, dt, A, a["ssm_chunk"])
    Y = Y + mp["D"][None, :, None] * X
    y = R.rmsnorm(Y.reshape(S, d_in) * jax.nn.silu(z), mp["gln"],
                  a["norm_eps"])
    return x + mm("se,ed->sd", y, mp["wo"]), {"ssm": hs, "conv": xp[-3:]}


def _hidden(p, tokens, a, mm):
    S = tokens.shape[0]
    pos = jnp.arange(S)
    sp = p["shared"]
    h = p["embed"]["embedding"][tokens].astype(R.f32)

    @jax.checkpoint
    def mamba(x, mp):
        return _mamba(mp, x, a, mm)

    @jax.checkpoint
    def group(x, gp):
        x, states = jax.lax.scan(mamba, x, gp)
        y, k, v = R.attention(sp["attn"],
                              R.rmsnorm(x, sp["ln1"], a["norm_eps"]), pos, mm,
                              a["rope_theta"], cache=True)
        x = x + y
        x = x + R.mlp(sp["mlp"], R.rmsnorm(x, sp["ln2"], a["norm_eps"]), mm,
                      a["mlp_act"])
        return x, (states, k, v)

    h, (states, k, v) = jax.lax.scan(group, h, p["groups"])
    assert "rem" not in p, "a period holds whole groups"
    return R.rmsnorm(h, p["final_norm"], a["norm_eps"]), (states, k, v)


def _unembed(p):
    e = p["embed"]
    return (e["unembed"], False) if "unembed" in e else (e["embedding"], True)


def logits(p, tokens, a, mm):
    """(S, vocab) logits of one sequence."""
    h, _ = _hidden(p, tokens, a, mm)
    w, tied = _unembed(p)
    return R.logits(w, h, a["vocab"], mm, tied)


def loss(p, tokens, labels, a, mm):
    return R.xent(logits(p, tokens, a, mm), labels)


def prefill(p, tokens, a, mm):
    """Last-position logits (vocab,) and the cache of one sequence: the
    Mamba2 states (groups, per group, ...) and the shared block's roped
    keys and values (groups, S, kv heads, head dim)."""
    h, (states, k, v) = _hidden(p, tokens, a, mm)
    w, tied = _unembed(p)
    lg = R.logits(w, h[-1:], a["vocab"], mm, tied)
    return lg[0], {"groups": states, "kv": {"k": k, "v": v}}
