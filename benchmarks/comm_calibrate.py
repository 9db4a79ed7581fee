"""Calibrate ``comm_flops_per_word`` — the flop-equivalent cost the
grouped (Algorithm 3) cost model charges per psum word.

``repro.dist.grouped.grouped_iteration_flops`` prices the two
collectives of a sep>1 mesh (the n^2-word "sep" Gram reduction and the
(mn/sep)-word "zolo" combine) at a flat ``comm_flops_per_word`` — a
round-number prior of 32 until measured.  This suite measures it: the
device's matmul flop rate (how many flops fit in a second) and the
all-reduce wall-clock per word on the local mesh, whose ratio is the
flop-equivalents one psum word costs.  The committed ``BENCH_comm.json``
records the CPU calibration (layout-honest; a TPU run of the same file
regenerates honest interconnect numbers), and a calibrated value threads
into planning via ``SvdConfig.extra["comm_flops_per_word"]`` — scored by
every registered ``flops_fn``, never passed to the backend.

Like ``grouped_scaling``, the sweep needs ``REPRO_BENCH_GROUPED_NDEV``
(default 8) devices, so the ``run()`` suite entry re-execs this module
in a subprocess with XLA_FLAGS set and re-emits its rows.

  python -m benchmarks.comm_calibrate     (standalone: sets its own
                                           XLA_FLAGS before jax loads)
"""

from __future__ import annotations

import functools
import json
import os
import subprocess
import sys

BENCH_JSON = os.environ.get("REPRO_BENCH_COMM_JSON", "BENCH_comm.json")
NDEV = int(os.environ.get("REPRO_BENCH_GROUPED_NDEV", "8"))

if __name__ == "__main__":
    # must happen before any jax import in this process
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={NDEV}")
    os.environ.setdefault("JAX_ENABLE_X64", "1")


def _calibrate_dtype(dtype, mesh, ndev, n):
    """Measure one dtype's (matmul flop rate, psum cost records,
    suggested flops-per-word).  A psum word is one element of the
    reduced array — bf16 words are half the bytes of f32 words, so the
    flop-equivalent cost per word genuinely differs per dtype (that is
    what a bf16 compute plan's cost model should be fed)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from benchmarks.common import emit, time_fn

    name = jnp.dtype(dtype).name

    # --- compute rate: the flop side of the flop-equivalent ----------
    a = jnp.ones((n, n), dtype)
    t_mm = time_fn(jax.jit(lambda x: x @ x), a)
    flop_rate = 2.0 * n ** 3 / t_mm  # flops / s
    emit(f"comm_calibrate.matmul_rate_{name}", t_mm * 1e6,
         f"n={n};flops_per_s={flop_rate:.3e}")

    # --- collective rate: psum wall-clock per word on the local mesh --
    records = []
    for words in (64 * 64, 128 * 128, 256 * 256):
        side = int(words ** 0.5)
        x = jnp.ones((ndev * side, side), dtype)

        @jax.jit
        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=P("sep", None), out_specs=P("sep", None))
        def allreduce(blk):
            # each device contributes its (side, side) block; one psum
            # over "sep" — the per-grid DGSUM2D this model prices
            return jnp.broadcast_to(
                jax.lax.psum(blk[:side], "sep"), blk.shape)

        t_ps = time_fn(allreduce, x)
        per_word = t_ps / words
        flops_per_word = per_word * flop_rate
        emit(f"comm_calibrate.psum_{side}x{side}_{name}", t_ps * 1e6,
             f"words={words};flops_per_word={flops_per_word:.1f}")
        records.append({"words": words, "us_per_psum": t_ps * 1e6,
                        "flops_per_word": flops_per_word})

    # suggest the mid-size measurement (small psums are latency-bound,
    # large ones bandwidth-bound; the Gram reduction sits in between)
    suggested = sorted(r["flops_per_word"]
                       for r in records)[len(records) // 2]
    return flop_rate, records, suggested


def _calibrate():
    import jax
    import jax.numpy as jnp

    from repro.dist import zolo_group_mesh
    from benchmarks.common import BENCH_N, emit

    ndev = jax.device_count()
    n = min(BENCH_N, 256)

    # the "sep" axis spans every device (zolo_group_mesh(1)), matching
    # the Gram-reduction collective of a maximally-distributed group
    mesh = zolo_group_mesh(1)

    # per-dtype calibration: f64 (the committed reference), f32 and
    # bf16 (the compute_dtype production precisions — their psum words
    # are narrower, and on real interconnects the flop-equivalent cost
    # per word is not the f64 value scaled by itemsize)
    per_dtype = {}
    for dtype in (jnp.float64, jnp.float32, jnp.bfloat16):
        name = jnp.dtype(dtype).name
        flop_rate, records, suggested = _calibrate_dtype(dtype, mesh,
                                                         ndev, n)
        per_dtype[name] = {
            "word_bytes": jnp.dtype(dtype).itemsize,
            "matmul_flops_per_s": flop_rate,
            "records": records,
            "comm_flops_per_word": suggested,
        }

    ref = per_dtype["float64"]
    record = {
        "suite": "comm_calibrate",
        "backend": jax.default_backend(),
        "ndev": ndev,
        # top-level keys stay the f64 reference calibration (the shape
        # earlier consumers of BENCH_comm.json read); per-dtype rows
        # live under "dtypes"
        "dtype": "float64",
        "word_bytes": ref["word_bytes"],
        "matmul_flops_per_s": ref["matmul_flops_per_s"],
        "records": ref["records"],
        "comm_flops_per_word": ref["comm_flops_per_word"],
        "dtypes": per_dtype,
        "usage": "SvdConfig(extra=(('comm_flops_per_word', "
                 f"{ref['comm_flops_per_word']:.1f}),)) — or export "
                 "REPRO_COMM_FLOPS_PER_WORD=<value> to rebase the "
                 "DEFAULT_COMM_FLOPS_PER_WORD prior for every plan "
                 "in the process",
    }
    with open(BENCH_JSON, "w") as f:
        json.dump(record, f, indent=2)
    emit("comm_calibrate.json_record", 0.0,
         f"{BENCH_JSON};comm_flops_per_word="
         f"{ref['comm_flops_per_word']:.1f}")


def run():
    """Suite entry for ``benchmarks.run``: on a CPU host with too few
    devices, re-exec with NDEV virtual CPU devices, re-emitting the
    subprocess rows; on an accelerator host with too few devices, raise
    (same protocol as ``grouped_scaling``)."""
    import jax
    from benchmarks.common import emit

    if jax.device_count() >= NDEV:
        _calibrate()
        return
    if jax.default_backend() != "cpu":
        # an accelerator belongs to this process: a child could not
        # reach it, and virtual CPU devices would time the wrong chip
        raise RuntimeError(
            f"comm_calibrate needs {NDEV} devices; this "
            f"{jax.default_backend()} host has {jax.device_count()}")
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={NDEV}",
        JAX_ENABLE_X64="1")
    out = subprocess.run([sys.executable, "-m", "benchmarks.comm_calibrate"],
                         env=env, capture_output=True, text=True,
                         timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(
            f"comm_calibrate subprocess failed:\n{out.stderr[-2000:]}")
    for line in out.stdout.splitlines():
        if not line.startswith("comm_calibrate."):
            continue
        parts = line.split(",", 2)
        emit(parts[0], float(parts[1]), parts[2] if len(parts) > 2 else "")
    if not os.path.exists(BENCH_JSON):
        raise RuntimeError(f"{BENCH_JSON} was not written")


if __name__ == "__main__":
    print("name,us_per_call,derived")
    _calibrate()
