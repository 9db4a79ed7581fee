"""Device seconds per call of the Cholesky factorizations of the shifted
Grams, with the shifts (the program's ``cholesky`` scope,
:func:`bench.scopes.scope_seconds`)."""

from bench import scopes


def read(run):
    return scopes.scope_seconds(run, "cholesky")
