"""Zolotarev iterations of the plan: the length of its precomputed
schedule (None for a dynamic backend, which has none)."""


def read(run):
    return run.values.get("zolo_iterations")
