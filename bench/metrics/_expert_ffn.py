"""Device time of the experts' products, shared by ``expert_ffn_ms.step``
and ``expert_ffn_roofline.step``.

The program runs the products under its ``expert_ffn`` scope, inside
``moe`` (``repro/models/moe.py``).  On a TPU the compiler lowers
``jax.lax.ragged_dot``, the grouped matmul of those products, to
kernels of its own and writes its own ``op_name`` over the scope's
(``ragged-dot-none``, ``ragged-dot-metadata``), and ``_scope.py`` then
gives each the path of its users: the down product's users, the gather
back to the tokens' order, are ``moe`` alone.  So the ops credited here
are those whose path names ``expert_ffn`` at any depth, and those whose
instruction is one of the compiler's ``ragged-dot`` kernels inside
``moe``: the program calls ``ragged_dot`` nowhere else.  The map from
instructions to paths is ``_scope.py``'s, of the step compiled again
after the window (from the persistent cache).  Every reader returns
None where ``_scope.py`` finds no map, or the program has no such scope.
"""
from __future__ import annotations

import re
from typing import Optional

import jax

from bench.metrics import _scope

SCOPE = "expert_ffn"
#: an instruction of the compiler's lowering of ``ragged_dot``
RAGGED = re.compile(r"%ragged-dot-")


def seconds_per_step(ctx) -> Optional[float]:
    """Device seconds per step of the experts' products, on one chip."""
    if "expert_ffn_s" not in ctx:
        ctx["expert_ffn_s"] = None
        vocab = _scope.vocabulary()
        if (vocab and SCOPE in vocab and _scope.ms_per_step(ctx, "moe")
                and ctx["counts"].get("steps")):
            cell = _scope.cell_of_command_line()
            names = _scope.op_names(
                _scope.step_text(cell, jax.devices()[:cell.chips]), vocab)
            mine = 0.0
            for op, s in ctx["trace"].ops.items():
                path = names.get(op, "")
                if (SCOPE in _scope._parts(path) or (
                        RAGGED.match(op)
                        and _scope.scope_of(path, vocab) == "moe")):
                    mine += s
            ctx["expert_ffn_s"] = mine / (ctx["chips"]
                                          * ctx["counts"]["steps"])
    return ctx["expert_ffn_s"]
