"""Device ms per step of remat's recomputation: the ops under
``rematted_computation``, whatever their scope (see ``_scope.py``)."""
from bench.metrics._scope import RECOMPUTE, ms_within_per_step


def read(ctx):
    return ms_within_per_step(ctx, RECOMPUTE)
