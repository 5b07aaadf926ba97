"""Device ms per step of the model's attention (q/k/v/o projections, RoPE,
the XLA or Pallas attention): the ops under the program's ``attention``
scope (see ``_scope.py``)."""
from bench.metrics._scope import ms_per_step

#: the program's scope (``repro/models/scopes.py``)
SCOPE = "attention"


def read(ctx):
    return ms_per_step(ctx, SCOPE)
