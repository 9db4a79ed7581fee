"""Programs compiled in set-up that the persistent compile cache did not
hold: JAX's cache requests less its hits (``jax.monitoring`` events,
counted by the harness's listener up to the end of set-up)."""


def read(run):
    counts = run.values.get("setup_cache")
    return None if counts is None else counts["misses"]
