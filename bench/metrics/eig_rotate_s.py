"""Device seconds per SVD of the block-Jacobi rotations: the gathers, the
Newton-Schulz step on J, and the row, column and V updates (the
program's ``eig/rotate`` scope, :func:`bench.scopes.scope_seconds`)."""

from bench import scopes


def read(run):
    return scopes.scope_seconds(run, "eig/rotate")
