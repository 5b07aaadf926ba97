"""Device ms per step of the model's dense MLP: the ops under the program's
``mlp`` scope (see ``_scope.py``)."""
from bench.metrics._scope import ms_per_step

#: the program's scope (``repro/models/scopes.py``)
SCOPE = "mlp"


def read(ctx):
    return ms_per_step(ctx, SCOPE)
