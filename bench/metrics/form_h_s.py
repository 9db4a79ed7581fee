"""Device seconds per call of H = (Q^T A + A^T Q) / 2 (the program's
``form_h`` scope, :func:`bench.scopes.scope_seconds`)."""

from bench import scopes


def read(run):
    return scopes.scope_seconds(run, "form_h")
