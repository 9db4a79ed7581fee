"""Seconds of the eig stage per SVD: the traced window's seconds per
``SvdPlan.svd`` less one ``SvdPlan.polar(a, want_h=True)`` of the same
plan and matrix, timed after the window; both by host clock."""


def read(run):
    calls, polar_s = run.values.get("calls"), run.values.get("polar_s")
    if not calls or calls["op"] != "svd" or polar_s is None:
        return None
    return calls["elapsed_s"] / calls["count"] - polar_s
