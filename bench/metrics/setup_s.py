"""Set-up seconds: from process start to the first timed call (import,
device init, data, plan, compile or cache load, warm-up)."""


def read(run):
    return run.setup_s
