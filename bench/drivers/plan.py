"""A dense matrix solved by one planned solver, closed loop.

Configuration keys: ``name``, ``n`` (the matrix is n x n), ``cond``
(the 2-norm condition number of the geometric spectrum), ``dtype``, and
``solver``: the fields of ``repro.solver.SvdConfig`` besides ``kappa``,
which is ``cond``.  The traffic's ``op`` is the entry
one client calls back to back: ``"polar"`` (``SvdPlan.polar(a,
want_h=True)``) or ``"svd"`` (``SvdPlan.svd(a)``).

Set-up makes the matrix on the device from the seed, plans, compiles the
entry with ``.lower().compile()`` and runs it once.  The window calls
the compiled entry until ``--seconds`` have passed, each call ended by
``block_until_ready``; one call's answer, drawn from the seed among the
first three, is kept for the comparison with the reference.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from bench import matrices, reference, traffic
from bench.harness import log

OPS = ("polar", "svd")


def _entry(plan, op: str):
    if op == "polar":
        def polar(a):
            return plan.polar(a, want_h=True)
        return polar

    def svd(a):
        return plan.svd(a)
    return svd


class State:
    pass


def prepare(run, devs):
    """What the run and its reference share: the op, the shape, the
    matrix's key and spectrum (on the first chip)."""
    cfg, op = run.config, run.traffic["op"]
    if op not in OPS:
        raise ValueError(f"plan driver: op {op!r} not in {OPS}")
    st = State()
    st.op = op
    st.n = int(cfg["n"])
    st.key = matrices.seed_key(run.seed, cfg["name"])
    st.sigma = jax.device_put(
        jnp.asarray(matrices.spectrum(st.n, cfg["cond"]),
                    jnp.float32), devs[0])
    return st


def setup(run, devs):
    import repro.solver as solver

    cfg, op = run.config, run.traffic["op"]
    st = prepare(run, devs)
    t = time.perf_counter()
    st.a = jax.block_until_ready(
        matrices.matrix(st.key, st.sigma, st.n, st.n))
    log(f"[data] {cfg['name']}: {st.n}x{st.n}, cond {cfg['cond']}, made on "
        f"the device in {time.perf_counter() - t:.3f}s")
    st.plan = solver.plan(solver.SvdConfig(kappa=cfg["cond"],
                                           **cfg["solver"]),
                          (st.n, st.n), cfg["dtype"])
    log(f"[plan] {st.plan!r}")
    t = time.perf_counter()
    st.exe = jax.jit(_entry(st.plan, op)).lower(st.a).compile()
    run.values["compile_s"] = time.perf_counter() - t
    log(f"[compile] {op}: {run.values['compile_s']:.3f}s")
    schedule = st.plan.schedule
    run.values["zolo_iterations"] = (None if schedule is None
                                     else len(schedule))
    run.values["plan_shape"] = (st.n, st.n)
    if run.trace and op == "svd":
        # eig_stage_s: the same plan's polar step, timed after the window
        st.polar_exe = jax.jit(_entry(st.plan, "polar")).lower(
            st.a).compile()
        jax.block_until_ready(st.polar_exe(st.a))
    t = time.perf_counter()
    jax.block_until_ready(st.exe(st.a))
    log(f"[warmup] first call {time.perf_counter() - t:.3f}s")
    st.keep = int(traffic.rng(run.seed, "keep").integers(3))
    return st


def window(run, st):
    kept = None
    ends = []
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation(f"bench:{st.op}"):
            out = jax.block_until_ready(st.exe(st.a))
        if len(ends) == st.keep or kept is None:
            kept = out
        ends.append(time.perf_counter() - t0)
        if ends[-1] >= run.seconds:
            break
        del out
    count, elapsed = len(ends), ends[-1]
    each = [b - a for a, b in zip([0.0] + ends, ends)]
    run.values["calls"] = {"op": st.op, "count": count,
                           "elapsed_s": elapsed}
    log(f"[window] {count} {st.op} calls in {elapsed:.3f}s: "
        f"{elapsed / count:.4f}s each (fastest {min(each):.4f}s, slowest "
        f"{max(each):.4f}s, call {each.index(max(each))})")
    log("[window] each call (s): " + " ".join(f"{t:.4f}" for t in each))
    return {"attempted": count, "failed": 0, "answer": kept}


def check(run, st, record):
    out = record.pop("answer")
    if run.trace and st.op == "svd":
        t = time.perf_counter()
        jax.block_until_ready(st.polar_exe(st.a))
        run.values["polar_s"] = time.perf_counter() - t
        st.polar_exe = None
    st.exe = st.a = None
    if st.op == "polar":
        q, h, _ = out
        nums = reference.polar_errors(q, h, st.key, st.sigma, st.n, st.n)
    else:
        u, s, vh = out
        nums = reference.svd_errors(u, s, vh, st.key, st.sigma, st.n,
                                    st.n)
    return reference.host(nums)


def control(run, st):
    """The control's numbers: the reference at "high" in the program's
    place, compared as the program's answer is."""
    if st.op == "polar":
        q, h = reference.polar_control(st.key, st.sigma, st.n, st.n)
        nums = reference.polar_errors(q, h, st.key, st.sigma, st.n, st.n)
    else:
        u, s, vh = reference.svd_control(st.key, st.sigma, st.n, st.n)
        nums = reference.svd_errors(u, s, vh, st.key, st.sigma, st.n,
                                    st.n)
    return reference.host(nums)
