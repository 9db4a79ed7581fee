"""Device seconds per call of the rolled blocked triangular solves (the
program's ``trsm`` scope, :func:`bench.scopes.scope_seconds`)."""

from bench import scopes


def read(run):
    return scopes.scope_seconds(run, "trsm")
