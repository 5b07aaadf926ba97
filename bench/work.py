"""Required operations and bytes, counted from shapes.

What the algorithm needs, whatever implements it:

* a matrix product of (m, k) by (k, n) is 2 m k n operations, and reads
  both operands and writes its result once;
* causal attention counts the lower triangle only: a sequence of S
  positions has S (S + 1) / 2 query-key pairs, each 2 hd for the scores
  and 2 hd for the weighted values, per query head;
* the Mamba2 recurrence per token, head, head channel and state entry:
  decay, input outer product and add (3), and the output read (2);
* the input-embedding lookup, elementwise work (norms, activations) and
  recomputation (remat) do not count; a training step is its forward
  pass three times over (forward, and a backward pass of twice the
  forward);
* a kernel's bytes are each input and output read or written once.

A model family's forward pass is an inventory of operations, each with
the operations and bytes it requires, kept in a file of its own,
``bench/families/<family>.py``, whose ``ops(a, batch, S,
logit_positions, dtype)`` returns it: a list of ``{"op", "flops",
"bytes"}`` over ``batch`` sequences of ``S`` tokens, with logits at
``logit_positions`` positions of each.  This file holds the counts that
the families share.  ``a`` is a configuration's ``arch`` block
(``bench/configs/*.json``).
"""
from __future__ import annotations

from typing import Dict, List

BYTES = {"float32": 4, "bfloat16": 2}


def entry(op: str, work: Dict, times: int = 1) -> Dict:
    """``times`` calls of ``op``, each doing ``work``."""
    return {"op": op, "flops": times * work["flops"],
            "bytes": times * work["bytes"]}


def hd(a) -> int:
    return a.get("head_dim") or a["d_model"] // a["n_heads"]


def matmul(m: int, k: int, n: int, dtype: str) -> Dict:
    return {"flops": 2 * m * k * n,
            "bytes": (m * k + k * n + m * n) * BYTES[dtype]}


def attention_pairs(S: int) -> int:
    return S * (S + 1) // 2


def attention_proj(a, batch: int, S: int, dtype: str) -> Dict:
    """The q, k, v and output projections of ``batch`` sequences."""
    d, H, Hkv = a["d_model"], a["n_heads"], a["n_kv_heads"]
    m = batch * S
    return add(matmul(m, d, H * hd(a), dtype),
               matmul(m, d, 2 * Hkv * hd(a), dtype),
               matmul(m, H * hd(a), d, dtype))


def attention(a, batch: int, S: int, dtype: str) -> Dict:
    """Causal attention of ``batch`` sequences, scores and weighted
    values: q, k, v in and the output out, once each."""
    H, Hkv = a["n_heads"], a["n_kv_heads"]
    return {"flops": batch * 4 * H * hd(a) * attention_pairs(S),
            "bytes": batch * S * hd(a) * (2 * H + 2 * Hkv) * BYTES[dtype]}


def mlp(a, batch: int, S: int, dtype: str) -> Dict:
    """A gated (SiLU: three matrices) or plain (two) MLP."""
    d, f, m = a["d_model"], a["d_ff"], batch * S
    up = 2 if a.get("mlp_act", "silu") == "silu" else 1
    return add(matmul(m, d, up * f, dtype), matmul(m, f, d, dtype))


def ssm_dims(a):
    d_in = a["ssm_expand"] * a["d_model"]
    P = a["ssm_head_dim"]
    return d_in, d_in // P, P, a["ssm_state"]


def ssm_scan(a, batch: int, S: int, dtype: str) -> Dict:
    """One Mamba2 scan over ``batch`` sequences: X in and Y out in the
    compute dtype; B, C, the step sizes and the final state in float32."""
    _, H, P, N = ssm_dims(a)
    b = BYTES[dtype]
    return {"flops": batch * 5 * S * H * P * N,
            "bytes": batch * (2 * S * H * P * b + 4 * (2 * S * N + S * H)
                              + 4 * H * P * N)}


def rmsnorm(rows: int, d: int, dtype: str) -> Dict:
    """RMSNorm of ``rows`` rows of width d: elementwise, so no counted
    operations; each row read and written, one scale vector."""
    return {"flops": 0, "bytes": 2 * rows * d * BYTES[dtype] + 4 * d}


def unembed(a, batch: int, logit_positions: int, dtype: str) -> Dict:
    """Logits over the real vocabulary at ``logit_positions`` positions
    of each sequence."""
    return matmul(batch * logit_positions, a["d_model"], a["vocab"], dtype)


def add(*works) -> Dict:
    return {k: sum(w[k] for w in works) for k in ("flops", "bytes")}


# ------------------------------------------------------------ families
def forward_ops(a, batch: int, S: int, logit_positions: int,
                dtype: str) -> List[Dict]:
    """The inventory of ``a``'s family (``bench/families/<family>.py``)."""
    from bench import harness
    mod = harness.load_module(harness.BENCH / "families" /
                              f"{a['family']}.py")
    return mod.ops(a, batch, S, logit_positions, dtype)


def flops(ops: List[Dict]) -> float:
    return float(sum(o["flops"] for o in ops))


def op_work(ops: List[Dict], *names: str) -> Dict:
    """The work of every entry of the operations ``names`` in the
    inventory (a kernel that fuses several names them all); None where
    the forward pass has none."""
    mine = [o for o in ops if o["op"] in names]
    return add(*mine) if mine else None


def train_step_flops(a, batch: int, S: int, dtype: str) -> float:
    return 3 * flops(forward_ops(a, batch, S, S, dtype))


def prefill_step_ops(a, batch: int, S: int, dtype: str) -> List[Dict]:
    """A prefill returns the logits of each sequence's last position."""
    return forward_ops(a, batch, S, 1, dtype)
