"""Device seconds per SVD of the batched 2nb x 2nb eigh of the block-Jacobi
rounds (the program's ``eig/small_eigh`` scope,
:func:`bench.scopes.scope_seconds`)."""

from bench import scopes


def read(run):
    return scopes.scope_seconds(run, "eig/small_eigh")
