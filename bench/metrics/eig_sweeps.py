"""Block-Jacobi sweeps per SVD, counted on the device inside the timed
executable: each op of the program's ``eig/sweep_test`` scope runs once
per sweep, so its events per call are the sweeps.  Checked against a
second count: each op of ``eig/small_eigh`` runs once per round, and a
sweep is b - 1 rounds (b the even padded block count, ceil(n / nb)
rounded up to even).  Where the ops of a scope disagree among themselves,
or the two counts disagree, both are logged and the reader returns
None (:func:`bench.scopes.op_events`)."""

from bench import scopes
from bench.harness import log


def read(run):
    nb = run.config["solver"].get("nb")
    sweeps = scopes.op_events(run, "eig/sweep_test")
    rounds = scopes.op_events(run, "eig/small_eigh")
    if nb is None or sweeps is None or rounds is None:
        return None
    b = -(-int(run.config["n"]) // int(nb))
    b += b % 2
    second = [r / (b - 1) for r in rounds]
    if len(sweeps) != 1 or second != sweeps:
        log(f"[scopes] eig_sweeps: sweep-test events per op and call "
            f"{sweeps}; small-eigh events per op and call over {b - 1} "
            f"rounds {second}: the counts disagree")
        return None
    return sweeps[0]
