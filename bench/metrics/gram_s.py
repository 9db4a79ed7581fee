"""Device seconds per call of the Gram products, the Pallas kernel or the
jnp product, with the CholeskyQR2 second pass (the program's ``gram``
scope, :func:`bench.scopes.scope_seconds`)."""

from bench import scopes


def read(run):
    return scopes.scope_seconds(run, "gram")
