"""Host seconds of the cell's compile in set-up: ``.lower().compile()``
of the timed entry.  A cache hit shows as a short compile."""


def read(run):
    return run.values.get("compile_s")
