"""Device ms per step of the experts' products alone (the grouped
matmuls inside ``moe``; see ``_expert_ffn.py``)."""
from bench.metrics._expert_ffn import seconds_per_step


def read(ctx):
    s = seconds_per_step(ctx)
    return None if not s else 1e3 * s
