"""The SVD service end-to-end on 8 (host) devices: a heterogeneous
request stream — tall, wide, two dtypes, two accuracy modes — bucketed
into a padded plan pool, continuously micro-batched, and dispatched with
the batch axis sharded one-matrix-per-device across the mesh.

  JAX_PLATFORMS=cpu python examples/svd_serve.py
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

import repro.solver as S  # noqa: E402
from repro.launch.svd_serve import synth_matrix  # noqa: E402
from repro.serve import ServiceConfig, SvdService  # noqa: E402


def main():
    ndev = len(jax.devices())
    print(f"devices: {ndev}")

    # batch_size == device count: every dispatched micro-batch puts one
    # padded matrix on each device (NamedSharding over the batch axis)
    svc = SvdService(ServiceConfig(batch_size=ndev, max_wait=0.002,
                                   data_axis=tuple(jax.devices())))

    # warm + pin the expected buckets: after this, every request is a
    # plan-cache hit and the stream runs with zero retraces
    shapes = [(96, 64), (40, 100), (120, 80)]
    keys = svc.warmup(shapes, modes=("fast", "standard"),
                      dtypes=("float64", "float32"))
    print(f"warmed {len(keys)} bucket plans "
          f"(cache: {S.cache_stats()['pinned']} pinned)")

    rng = np.random.default_rng(0)
    reqs, futs = [], []
    for i in range(3 * ndev):
        m, n = shapes[int(rng.integers(len(shapes)))]
        dtype = (jnp.float64, jnp.float32)[int(rng.integers(2))]
        mode = ("fast", "standard")[int(rng.integers(2))]
        # stay inside the "fast" mode's kappa-1e2 accuracy contract:
        # out-of-contract requests fail their runtime health check and
        # escalate (correct, but then the stream compiles retry lanes
        # and the zero-retrace claim above would not hold)
        a = synth_matrix(m, n, kappa=1e2, seed=i, dtype=dtype)
        reqs.append((a, mode))
        futs.append(svc.submit(a, mode))   # non-blocking
    svc.poll(force=True)                   # dispatch everything queued

    worst = 0.0
    for (a, mode), fut in zip(reqs, futs):
        u, s, vh = fut.result()            # the only blocking edge
        a64 = a.astype(jnp.float64)
        rec = jnp.linalg.norm(u.astype(jnp.float64) * s.astype(
            jnp.float64)[..., None, :] @ vh.astype(jnp.float64) - a64)
        worst = max(worst, float(rec / jnp.linalg.norm(a64)))
    st = svc.stats()
    print(f"served {st['solves']} solves in {st['batches']} batches "
          f"({ndev} slots each, one matrix per device)")
    print(f"worst reconstruction error: {worst:.2e}")
    print(f"pad waste {st['pad_waste']:.0%}, slot fill "
          f"{st['slot_fill']:.0%}, plan-cache hit rate "
          f"{st['plan_cache_hit_rate']:.0%}, retraces {st['retraces']}")


if __name__ == "__main__":
    main()
