"""Device ms per step of the Mamba2 mixer's chunked SSD scan: the ops
under the program's ``ssd_scan`` scope, which sits inside ``mamba2`` and
so is read at any depth of the path (see ``_scope.py``)."""
from bench.metrics._scope import ms_within_per_step

#: the program's scope (``repro/models/scopes.py``)
SCOPE = "ssd_scan"


def read(ctx):
    return ms_within_per_step(ctx, SCOPE)
