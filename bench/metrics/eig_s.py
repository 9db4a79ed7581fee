"""Device seconds per SVD of the whole eig stage (the program's ``eig``
scope, :func:`bench.scopes.scope_seconds`)."""

from bench import scopes


def read(run):
    return scopes.scope_seconds(run, "eig")
