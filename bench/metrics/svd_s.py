"""Seconds per SVD: the window's wall time over the number of
``SvdPlan.svd`` calls completed in it (closed loop, each call ended by
``block_until_ready``)."""


def read(run):
    calls = run.values.get("calls")
    if not calls or calls["op"] != "svd":
        return None
    return calls["elapsed_s"] / calls["count"]
