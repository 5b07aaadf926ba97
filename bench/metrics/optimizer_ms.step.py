"""Device ms per step of the optimizer's update: the ops under the
program's ``optimizer`` scope (see ``_scope.py``)."""
from bench.metrics._scope import ms_per_step

#: the program's scope (``repro/models/scopes.py``)
SCOPE = "optimizer"


def read(ctx):
    return ms_per_step(ctx, SCOPE)
