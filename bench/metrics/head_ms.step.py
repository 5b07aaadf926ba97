"""Device ms per step of the logits and the loss: the ops under the
program's ``head`` scope (see ``_scope.py``)."""
from bench.metrics._scope import ms_per_step

#: the program's scope (``repro/models/scopes.py``)
SCOPE = "head"


def read(ctx):
    return ms_per_step(ctx, SCOPE)
