"""Paper Algorithm 3 live on 8 (host) devices, through the plan API: the
r subgroup contexts as a ('zolo', 'sep') mesh bound into an SvdPlan at
plan time, with the DGSUM2D combine as psum('zolo').

Also runs the paper-faithful vs gram-shared flop accounting (the
beyond-paper optimization of DESIGN.md §3).

  JAX_PLATFORMS=cpu python examples/distributed_svd.py
      (on the CPU it sets its own XLA_FLAGS for 8 virtual devices; on a
      TPU host run it without JAX_PLATFORMS to use the real chips;
      needs `pip install -e .` or PYTHONPATH=src)
"""

import os

if os.environ.get("JAX_PLATFORMS") == "cpu":
    # 8 virtual host devices and f64 numerics: CPU runs only — on an
    # accelerator the real devices and the f32 contract stand
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_ENABLE_X64", "1")

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro.core as C  # noqa: E402
import repro.solver as S  # noqa: E402
from repro.dist.grouped import (  # noqa: E402
    grouped_iteration_flops,
    zolo_group_mesh,
)


def main():
    print(f"devices: {len(jax.devices())}")
    rng = np.random.default_rng(5)
    m, n, kappa = 512, 256, 9.06e3  # linverse-class conditioning
    u, _ = np.linalg.qr(rng.standard_normal((m, n)))
    v, _ = np.linalg.qr(rng.standard_normal((n, n)))
    a = jnp.asarray(u @ np.diag(np.geomspace(1, 1 / kappa, n)) @ v.T)

    for r in (2, 4):
        mesh = zolo_group_mesh(r)
        print(f"\nr={r}: mesh = {dict(mesh.shape)}  "
              f"(TOP context = {r} groups, SEP = {mesh.shape['sep']} "
              f"devices each)")
        # the mesh makes mode resolve to "grouped"; the Zolotarev
        # schedule is precomputed at plan time and the compiled
        # executable is cached per (shape, dtype, config, mesh)
        cfg = S.SvdConfig(method="auto", kappa=kappa,
                          l0_policy="estimate_at_plan")
        p = S.plan(cfg, a.shape, a.dtype, mesh=mesh)
        print(f"  plan: method={p.method} mode={p.mode} r={p.r} "
              f"sep={p.sep} schedule_iters={len(p.schedule)}")
        q, h, info = p.polar(a)
        print(f"  orth={float(C.orthogonality(q)):.2e}  "
              f"rec={float(jnp.linalg.norm(q @ h - a) / jnp.linalg.norm(a)):.2e}")
        # the full grouped SVD (paper Alg. 2 over Alg. 3)
        u_p, s_p, vh_p = p.svd(a)
        s_ref = np.linalg.svd(np.asarray(a), compute_uv=False)
        err = float(np.abs(np.asarray(s_p) - s_ref).max())
        print(f"  Zolo-SVD singular-value error vs LAPACK: {err:.2e}")
        # cost model: paper-faithful (per-group Gram) vs gram-shared,
        # and the per-device effect of the intra-group sep distribution
        iters = len(p.schedule)
        faithful = grouped_iteration_flops(m, n, r, iters, False)
        shared = grouped_iteration_flops(m, n, r, iters, True)
        sep_aware = grouped_iteration_flops(m, n, r, iters, False,
                                            sep=p.sep)
        print(f"  flops: paper-faithful={faithful:.3e}  "
              f"gram-shared={shared:.3e}  saving={faithful / shared:.2f}x")
        print(f"  per-device critical path (sep={p.sep}): "
              f"{sep_aware / r:.3e}  "
              f"(plan.flops_estimate={p.flops_estimate:.3e})")

    # --- runtime conditioning: one executable for any kappa ------------
    # l0_policy="runtime" + mesh= resolves to zolo_grouped_dynamic: the
    # sigma_min bound is estimated sep-collectively in-graph and feeds
    # in-graph Zolotarev coefficients, so the SAME compiled plan serves
    # well- and ill-conditioned inputs with zero retraces.
    mesh = zolo_group_mesh(2)
    p_dyn = S.plan(S.SvdConfig(l0_policy="runtime"), a.shape, a.dtype,
                   mesh=mesh)
    print(f"\nruntime-kappa plan: method={p_dyn.method} r={p_dyn.r} "
          f"sep={p_dyn.sep}")
    for kap in (1e2, 1e8):
        u2, _ = np.linalg.qr(rng.standard_normal((m, n)))
        v2, _ = np.linalg.qr(rng.standard_normal((n, n)))
        a2 = jnp.asarray(u2 @ np.diag(np.geomspace(1, 1 / kap, n)) @ v2.T)
        t0 = S.trace_count()
        q, _, info = p_dyn.polar(a2, want_h=False)
        print(f"  kappa={kap:.0e}: orth={float(C.orthogonality(q)):.2e}  "
              f"iters={int(info.iterations)}  "
              f"retraces={S.trace_count() - t0}")


if __name__ == "__main__":
    main()
