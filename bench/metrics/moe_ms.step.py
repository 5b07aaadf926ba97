"""Device ms per step of the MoE layers (router, sort, gathers, expert
products and combine): the ops under the program's ``moe`` scope (see
``_scope.py``)."""
from bench.metrics._scope import ms_per_step

#: the program's scope (``repro/models/scopes.py``)
SCOPE = "moe"


def read(ctx):
    return ms_per_step(ctx, SCOPE)
