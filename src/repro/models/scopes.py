"""The names of the model's layers on the device.

A layer function runs under ``jax.named_scope(<name>)`` (``scope``, as a
decorator or a ``with`` block).  JAX writes the scope into the
``metadata={op_name=...}`` of every HLO instruction the layer lowers to,
in the forward pass, its transpose and its remat recomputation alike, so
each device op of a compiled step names the layer it belongs to.  A
scope changes only metadata, and nothing runs for it: the compiled
program does the same operations, though an instruction that merges ops
of two scopes may be numbered differently.

The vocabulary is fixed, so that a reader of a step's ops can rely on
it: the benchmark's per-layer times (``bench/metrics/*_ms.step.py``)
read these names.
"""
from __future__ import annotations

import jax

VOCABULARY = {
    "embed": "token embedding lookup (layers.embed)",
    "norm": "RMSNorm (layers.rmsnorm) outside the layers that hold one",
    "attention": "q/k/v/o projections, RoPE and the XLA or Pallas "
                 "attention (layers.attention_block, "
                 "decode_attention_block)",
    "attention_scores": "the XLA attention's scores, softmax and weighted "
                        "values (layers.full_attention), inside attention",
    "mlp": "the dense MLP (layers.mlp_block)",
    "moe": "the MoE layer: router, sort, gathers, expert products and "
           "combine (moe.moe_mlp)",
    "expert_ffn": "the expert products alone, inside moe "
                  "(moe._grouped_ffn, moe._expert_ffn)",
    "mamba2": "the Mamba2 mixer (mamba2.mamba_block, mamba_decode_block)",
    "ssd_scan": "the chunked SSD scan, inside mamba2",
    "head": "logits and the loss (layers.unembed, layers.xent_loss)",
    "optimizer": "the optimizer's update (optim/optimizers.py)",
}


def scope(name: str):
    """``jax.named_scope(name)`` for a name of the vocabulary."""
    if name not in VOCABULARY:
        raise ValueError(f"{name!r} is not a layer scope; the vocabulary "
                         f"is {sorted(VOCABULARY)}")
    return jax.named_scope(name)
