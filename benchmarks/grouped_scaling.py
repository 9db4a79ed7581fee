"""Grouped (Algorithm 3) scaling sweep over (r, sep) mesh factorizations.

At a fixed device count every divisor r of ndev gives a two-level layout
ndev = r groups x sep devices: r-way term parallelism over "zolo" and
the intra-group row distribution over "sep".  This suite runs the same
polar solve through an ``SvdPlan`` on each factorization (method="auto",
so the sep-aware cost model does the picking) twice — with the plan-time
static schedule and with the runtime-conditioning dynamic backend
(``l0_policy="runtime"`` -> ``zolo_grouped_dynamic``: in-graph
sep-collective sigma_min bound + in-graph coefficients) — records
wall-clock, parity against the single-device static driver, and the
plan's per-device flop estimate, and writes the machine-readable
``BENCH_grouped.json`` record (CPU rows prove layout/parity; a TPU run
of the same file regenerates honest wall-clock).

The sweep needs ``REPRO_BENCH_GROUPED_NDEV`` (default 8) devices, but
XLA's host-device count is fixed at jax import — so the ``run()`` suite
entry re-execs this module in a subprocess with XLA_FLAGS set, exactly
like the multi-device tests, and re-emits its rows.

  python -m benchmarks.grouped_scaling     (standalone: sets its own
                                            XLA_FLAGS before jax loads)
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

BENCH_JSON = os.environ.get("REPRO_BENCH_GROUPED_JSON", "BENCH_grouped.json")
NDEV = int(os.environ.get("REPRO_BENCH_GROUPED_NDEV", "8"))

if __name__ == "__main__":
    # must happen before any jax import in this process
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={NDEV}")
    os.environ.setdefault("JAX_ENABLE_X64", "1")


def _sweep():
    import jax
    import jax.numpy as jnp

    import repro.core as C
    import repro.solver as S
    from repro.dist import zolo_group_mesh
    from benchmarks.common import BENCH_N, emit, make_matrix, time_fn

    ndev = jax.device_count()
    n = min(BENCH_N, 256)
    m = 2 * n
    kappa = 1e4
    a = make_matrix(n, kappa, m=m, seed=17)

    # single-device reference at the r the auto path would use
    cfg = S.SvdConfig(kappa=kappa, l0_policy="estimate_at_plan")
    q_ref = None

    # static (plan-time schedule) and dynamic (runtime conditioning,
    # l0_policy="runtime") rows on every factorization: the dynamic
    # backend's price for serving any kappa from one executable is the
    # in-graph estimate + in-graph coefficients, visible as its
    # wall-clock delta at equal (r, sep)
    cfg_dyn = S.SvdConfig(l0_policy="runtime")

    records = []
    for r in [d for d in range(1, ndev + 1) if ndev % d == 0]:
        sep = ndev // r
        mesh = zolo_group_mesh(r)
        for label, c in (("static", cfg), ("dynamic", cfg_dyn)):
            p = S.plan(c, a.shape, a.dtype, mesh=mesh)
            assert p.mode == "grouped" and p.r == r and p.sep == sep
            if label == "dynamic":
                assert p.method == "zolo_grouped_dynamic", p.method
            q = p.polar(a, want_h=False)[0]
            if q_ref is None:
                ref = S.plan(S.SvdConfig(method="zolo_static", kappa=kappa,
                                         l0_policy="estimate_at_plan",
                                         r=r),
                             a.shape, a.dtype)
                q_ref = ref.polar(a, want_h=False)[0]
            t = time_fn(lambda x: p.polar(x, want_h=False)[0], a)
            orth = float(C.orthogonality(q))
            err = float(jnp.abs(q - q_ref).max())
            emit(f"grouped_scaling.{label}_r{r}_sep{sep}", t * 1e6,
                 f"method={p.method};flops_per_dev={p.flops_estimate:.3e};"
                 f"orth={orth:.2e};err_vs_ref={err:.2e}")
            records.append({
                "r": r, "sep": sep, "method": p.method,
                "schedule": label,
                "schedule_iters": (len(p.schedule)
                                   if p.schedule is not None else None),
                "us_per_call": t * 1e6,
                "flops_per_device": p.flops_estimate,
                "orth": orth, "max_err_vs_single_device": err,
            })

    record = {
        "suite": "grouped_scaling",
        "backend": jax.default_backend(),
        "ndev": ndev,
        "shape": [m, n],
        "dtype": str(jnp.dtype(a.dtype)),
        "kappa": kappa,
        "records": records,
    }
    with open(BENCH_JSON, "w") as f:
        json.dump(record, f, indent=2)
    emit("grouped_scaling.json_record", 0.0, BENCH_JSON)


def run():
    """Suite entry for ``benchmarks.run``: on a CPU host with too few
    devices, re-exec with NDEV virtual CPU devices (the harness process
    imported jax long ago), re-emitting the subprocess rows; on an
    accelerator host with too few devices, raise."""
    import jax
    from benchmarks.common import emit

    if jax.device_count() >= NDEV:
        _sweep()
        return
    if jax.default_backend() != "cpu":
        # an accelerator belongs to this process: a child could not
        # reach it, and virtual CPU devices would time the wrong chip
        raise RuntimeError(
            f"grouped_scaling needs {NDEV} devices; this "
            f"{jax.default_backend()} host has {jax.device_count()}")
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={NDEV}",
        JAX_ENABLE_X64="1")
    out = subprocess.run([sys.executable, "-m", "benchmarks.grouped_scaling"],
                         env=env, capture_output=True, text=True,
                         timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(
            f"grouped_scaling subprocess failed:\n{out.stderr[-2000:]}")
    for line in out.stdout.splitlines():
        if not line.startswith("grouped_scaling."):
            continue
        # re-emit through the harness CSV: name,us,derived
        parts = line.split(",", 2)
        emit(parts[0], float(parts[1]), parts[2] if len(parts) > 2 else "")
    if not os.path.exists(BENCH_JSON):
        raise RuntimeError(f"{BENCH_JSON} was not written")


if __name__ == "__main__":
    print("name,us_per_call,derived")
    _sweep()
