"""Device seconds per call of the r-term combines of the polar iterations
and CholeskyQR2's sum of a_j Q1_j Q2_j^T (the program's ``combine``
scope, :func:`bench.scopes.scope_seconds`)."""

from bench import scopes


def read(run):
    return scopes.scope_seconds(run, "combine")
