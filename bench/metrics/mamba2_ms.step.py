"""Device ms per step of the Mamba2 mixers, their chunked SSD scan
included: the ops under the program's ``mamba2`` scope (see
``_scope.py``)."""
from bench.metrics._scope import ms_per_step

#: the program's scope (``repro/models/scopes.py``)
SCOPE = "mamba2"


def read(ctx):
    return ms_per_step(ctx, SCOPE)
