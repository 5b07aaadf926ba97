"""Share of the step's device op time that carries no scope of the
program, in %: how much of the step the ``*_ms.step`` readers leave
unnamed (see ``_scope.py``)."""
from bench.metrics._scope import unscoped_share


def read(ctx):
    return unscoped_share(ctx)
