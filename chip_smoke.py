#!/usr/bin/env python3
"""Bring-up check of the planned Zolo-SVD on a TPU, through its public
entry points.

    python chip_smoke.py [--seed N]            # one chip
    python chip_smoke.py --chips 4 [--seed N]  # the grouped mesh on four

One chip (the default) runs these phases in one process:

* data: the paper's ``nemeth03`` matrix (Table 3: n = 9506,
  kappa = 1.29), synthesized in f64 on the host from ``--seed`` and
  solved in f32.  Its exact spectrum is the reference for the singular
  values.
* solve: ``repro.solver.plan`` with ``method="auto"``, then the explicit
  XLA backend ``zolo_static`` and the explicit kernel backend
  ``zolo_pallas`` (one of which is what ``auto`` resolves to, and runs
  once), all with the block-Jacobi eig stage (``EIG_METHOD``).
  Each prints its compile, first-call and steady solve seconds and its
  retraces on repeat; the kernel plan must hold ``tpu_custom_call`` in
  its compiled HLO.
* reference: ``jnp.linalg.svd`` of the same f32 matrix on the host's CPU
  device (LAPACK), while the chip compiles and solves.  On the chip,
  ``jnp.linalg.svd`` and ``jnp.linalg.eigh`` do not compile at n = 9506
  in a run's time: XLA's TPU Cholesky, QR and triangular solve unroll
  one step per block: compiling ``jnp.linalg.eigh`` for a described v5e
  on an 8-core host took 73 s at n = 1024 and 251 s at n = 2048, and
  passed 16 GiB of host memory at n = 4096.
* serving: f32 requests of three shapes through ``repro.serve.SvdService``,
  each checked against ``jnp.linalg.svd`` of the same request on the
  same chip.

With ``--chips 4`` it runs only the grouped plan (paper Alg. 3) on a
{"zolo": 2, "sep": 2} mesh over four chips, the one-chip plan on device 0
and the reference, on the same matrix and seed; every chip must hold a
row block of U and report device memory in use.

Every result is checked in f64 on the host: the normwise singular-value
error max|s - sigma| / sigma_1, the residual ||A - U S V^T||_F / ||A||_2
(paper eq. 13), and OrthL / OrthR, ||I - Q^T Q||_F / n.  A Zolo reading
passes when it is at most max(LIMIT_FACTOR x the reference's reading on
the same input, LIMIT_FLOOR).  The script exits nonzero, and prints no
result line, when JAX finds no TPU or any phase or check fails; the last
line of a passing run is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# --- fixed before the first chip run; the same for every phase and seed ---
LIMIT_FACTOR = 10.0
LIMIT_FLOOR = 10.0 * float(np.finfo(np.float32).eps)
READINGS = ("sv_err", "residual", "orth_l", "orth_r")

REQUIRED_PLATFORM = "tpu"
MATRIX = "nemeth03"
MATRIX_N = 9506  # the paper's size (Table 3)
# The default eig stage ("eigh": jnp.linalg.eigh) does not compile at
# n = 9506 on a v5e (see the module docstring); the registered padded
# block-Jacobi does.  2 * nb = 256 keeps each small eigensolve inside
# XLA's TPU Jacobi kernel.
EIG_METHOD = "jacobi"
EIG_NB = 128
BACKENDS = ("auto", "zolo_static", "zolo_pallas")
KERNEL_BACKEND = "zolo_pallas"
# (m, n) request shapes; the wide one exercises the transposed path
SERVE_SHAPES = ((640, 512), (512, 768), (1024, 600))
SERVE_REQUESTS = 9
SERVE_KAPPA = 1e2
SERVE_BATCH = 4
GROUPED_R = 2


def log(msg: str) -> None:
    print(msg, flush=True)


def readings(a, sigma, u, s, vh) -> dict:
    """The paper's accuracy readings of one SVD, in f64 on the host.

    ``a`` is the f32 matrix that was solved, ``sigma`` its exact
    singular values (descending); ||A||_2 is ``sigma[0]``."""
    a = np.asarray(a, np.float64)
    u = np.asarray(u, np.float64)
    s = np.asarray(s, np.float64)
    vh = np.asarray(vh, np.float64)
    k = s.shape[0]
    rec = (u * s) @ vh
    rec -= a
    out = {"sv_err": float(np.max(np.abs(np.sort(s)[::-1] - sigma))
                           / sigma[0]),
           "residual": float(np.linalg.norm(rec) / sigma[0])}
    del rec
    for name, g in (("orth_l", u.T @ u), ("orth_r", vh @ vh.T)):
        g[np.diag_indices(k)] -= 1.0
        out[name] = float(np.linalg.norm(g) / k)
    return out


def limit(ref: float) -> float:
    return max(LIMIT_FACTOR * ref, LIMIT_FLOOR)


def judge(label: str, got: dict, ref: dict) -> list:
    """Print each reading beside its limit; return the failed ones."""
    failed = []
    for key in READINGS:
        lim = limit(ref[key])
        ok = got[key] <= lim
        log(f"[{label}] {key}={got[key]:.3e} limit={lim:.3e} "
            f"(reference {ref[key]:.3e}) {'ok' if ok else 'FAIL'}")
        if not ok:
            failed.append(f"{label}:{key}")
    return failed


def require_devices(count: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    if d.platform != REQUIRED_PLATFORM:
        raise SystemExit(f"chip_smoke: JAX found no {REQUIRED_PLATFORM} "
                         f"(platform {d.platform!r}); nothing was run")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} devices, found "
                         f"{len(devs)}")
    return devs


def build_matrix(seed: int):
    """(A in f32, its exact spectrum): the paper matrix from ``seed``."""
    from repro.configs.svd_paper import spectrum, synthesize

    t0 = time.perf_counter()
    a = synthesize(MATRIX, n=MATRIX_N, dtype=np.float32, seed=seed)
    sigma = spectrum(MATRIX, n=MATRIX_N)
    log(f"[data] {MATRIX}: n={a.shape[0]} kappa={sigma[0] / sigma[-1]:.3g} "
        f"seed={seed} built on the host in "
        f"{time.perf_counter() - t0:.1f}s")
    return a, sigma


def compile_timed(label: str, compile_fn, spec):
    """Trace, lower and compile one solver for ``spec`` (shapes and
    placement only, so it can run while the host builds the matrix)."""
    t0 = time.perf_counter()
    compiled = compile_fn(spec)
    log(f"[{label}] compile_s={time.perf_counter() - t0:.3f}")
    return compiled


def run_timed(label: str, call, a, traces=None):
    """Time calls to ``block_until_ready``.  A plan (``traces`` given)
    runs a first and a steady call, and its retraces over the repeat
    must be none; the reference runs once.  Returns the last result."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(call(a))
    line = f"[{label}] first_call_s={time.perf_counter() - t0:.3f}"
    if traces:
        before = traces()
        t0 = time.perf_counter()
        out = jax.block_until_ready(call(a))
        retraces = traces() - before
        line += (f" solve_s={time.perf_counter() - t0:.3f}"
                 f" retraces_on_repeat={retraces}")
        if retraces:
            raise RuntimeError(f"{label}: {retraces} retraces on a repeat "
                               f"call")
    log(line)
    return out


def compile_plan(label: str, plan, spec):
    log(f"[{label}] {plan!r}")
    compiled = compile_timed(label, plan.compile_svd, spec)
    if plan.method == KERNEL_BACKEND:
        has = "tpu_custom_call" in compiled.as_text()
        log(f"[{label}] tpu_custom_call in compiled HLO: {has}")
        if not has:
            raise RuntimeError(f"{label}: the Pallas kernels did not "
                               f"compile into the HLO")


def reference_svd(x):
    import jax.numpy as jnp

    return jnp.linalg.svd(x, full_matrices=False)


def host_reference(built) -> dict:
    """Readings of ``jnp.linalg.svd`` of the paper matrix on the host's
    CPU device; ``built`` is the future of :func:`build_matrix`."""
    import jax

    a_host, sigma = built.result()
    cpu = jax.devices("cpu")[0]
    t0 = time.perf_counter()
    out = to_host(jax.jit(reference_svd)(jax.device_put(a_host, cpu)))
    log(f"[reference] jnp.linalg.svd on the host {cpu.platform} device: "
        f"{time.perf_counter() - t0:.3f}s")
    return readings(a_host, sigma, *out)


def to_host(out):
    return tuple(np.asarray(x) for x in out)


def serve_phase(seed: int) -> list:
    """f32 requests through SvdService, each held to the limits against
    jnp.linalg.svd of the same request on the same chip."""
    import jax
    import jax.numpy as jnp

    from repro.launch.svd_serve import synth_matrix
    from repro.serve import ServiceConfig, SvdService

    reqs = []
    for i in range(SERVE_REQUESTS):
        m, n = SERVE_SHAPES[i % len(SERVE_SHAPES)]
        reqs.append(synth_matrix(m, n, SERVE_KAPPA, seed=seed * 1000 + i,
                                 dtype=jnp.float32))
    svc = SvdService(ServiceConfig(batch_size=SERVE_BATCH))
    t0 = time.perf_counter()
    svc.warmup(SERVE_SHAPES, dtypes=("float32",))
    warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    futs = [svc.submit(a) for a in reqs]
    svc.flush()
    outs = [to_host(f.result()) for f in futs]
    serve_s = time.perf_counter() - t0
    st = svc.stats()
    log(f"[serve] {len(reqs)} requests, shapes {list(SERVE_SHAPES)}: "
        f"warmup_s={warm_s:.3f} serve_s={serve_s:.3f} "
        f"batches={st['batches']} retries={st['retries']} "
        f"quarantined={st['quarantined']} retraces={st['retraces']}")
    if st["quarantined"] or st["retraces"]:
        raise RuntimeError(f"serving: quarantined={st['quarantined']} "
                           f"retraces={st['retraces']}")
    ref = jax.jit(reference_svd)
    failed = []
    for i, (a, out) in enumerate(zip(reqs, outs)):
        sigma = np.geomspace(1.0, 1.0 / SERVE_KAPPA, min(a.shape))
        a_host = np.asarray(a)
        got = readings(a_host, sigma, *out)
        want = readings(a_host, sigma, *to_host(ref(a)))
        failed += judge(f"serve#{i} {a.shape[0]}x{a.shape[1]}", got, want)
    return failed


def solve_phase(seed: int, plans: dict, devs, mesh_sharding=None,
                serve: bool = False) -> list:
    """Build the paper matrix and solve it with the plain reference on
    the host while every solver compiles (and, on one chip, while the
    serving phase runs); then solve with each, check each in f64 on the
    host, and hold each to the limits against the reference."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import repro.solver as solver

    n = MATRIX_N
    one = SingleDeviceSharding(devs[0])
    failed = []
    with ThreadPoolExecutor(3) as host:
        built = host.submit(build_matrix, seed)
        want = host.submit(host_reference, built)
        for label, plan in plans.items():
            sharding = one if plan.mesh is None else mesh_sharding
            compile_plan(label, plan,
                         jax.ShapeDtypeStruct((n, n), jnp.float32,
                                              sharding=sharding))
        if serve:
            failed += serve_phase(seed)
        a_host, sigma = built.result()
        a_one = jax.device_put(a_host, one)
        checks = {}
        for label, plan in plans.items():
            a = a_one if plan.mesh is None else jax.device_put(
                a_host, mesh_sharding)
            out = run_timed(label, plan.svd, a, traces=solver.trace_count)
            if plan.mesh is not None:
                check_grouped_layout(plan, out[0], devs)
            checks[label] = host.submit(readings, a_host, sigma,
                                        *to_host(out))
            del out
        want = want.result()
        for label in plans:
            failed += judge(label, checks[label].result(), want)
    return failed


def check_grouped_layout(plan, u, devs) -> None:
    """Every chip of the mesh must hold a row block of U, which the
    grouped plan forms from the row-sharded Alg. 3 iterate, and must
    report device memory in use.  Each chip's peak memory is printed."""
    rows = {s.device: s.data.shape[0] for s in u.addressable_shards}
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    log(f"[grouped] mesh {dict(plan.mesh.shape)}; rows of U per device "
        f"{[rows.get(d, 0) for d in devs]} of {u.shape[0]}; "
        f"peak_bytes_in_use per device {peaks}")
    if not all(rows.get(d, 0) for d in devs):
        raise RuntimeError("grouped: a device holds no share of U")
    if devs[0].platform == "tpu" and not all(peaks):
        raise RuntimeError("grouped: a chip reports no memory in use")


def one_chip(seed: int, devs) -> list:
    import repro.solver as solver

    n = MATRIX_N
    base = _config()
    plans = {"auto": solver.plan(base, (n, n), "float32")}
    resolved = plans["auto"].method
    log(f"[auto] resolved to {resolved}")
    for name in BACKENDS[1:]:
        if name == resolved:
            # the same resolution compiles to the same program: its
            # compile, solve and readings are auto's
            log(f"[{name}] is the plan auto resolved to; see [auto]")
            continue
        plans[name] = solver.plan(base.replace(method=name), (n, n),
                                  "float32")
    return solve_phase(seed, plans, devs[:1], serve=True)


def four_chips(seed: int, devs) -> list:
    from jax.sharding import NamedSharding, PartitionSpec as P

    import repro.solver as solver
    from repro.dist import zolo_group_mesh

    n = MATRIX_N
    mesh = zolo_group_mesh(GROUPED_R, devs)
    cfg = _config()
    plans = {"grouped": solver.plan(cfg, (n, n), "float32", mesh=mesh),
             "one_chip": solver.plan(cfg, (n, n), "float32")}
    return solve_phase(seed, plans, devs,
                       mesh_sharding=NamedSharding(mesh, P("sep", None)))


def _config():
    """The paper matrix's solver config: method "auto", the kappa hint
    of Table 3, and the block-Jacobi eig stage."""
    import repro.solver as solver
    from repro.configs.svd_paper import MATRICES

    return solver.SvdConfig(kappa=MATRICES[MATRIX].cond,
                            l0_policy="estimate_at_plan",
                            eig_method=EIG_METHOD, nb=EIG_NB)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    devs = require_devices(args.chips)

    from repro.launch.compile_cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    if args.chips == 4:
        failed = four_chips(args.seed, devs[:4])
    else:
        failed = one_chip(args.seed, devs)
    log(f"total_s={time.perf_counter() - t0:.1f}")
    if failed:
        log(f"chip_smoke: FAILED {failed}")
        return 1
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
