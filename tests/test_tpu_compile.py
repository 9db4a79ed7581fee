"""Compile the Pallas kernels of the main path, and the grouped plan
(paper Alg. 3) on a 2x2 mesh, for a TPU v5e that is described, not
attached.

The TPU compiler refuses what interpret mode accepts (tile alignment,
fast-memory budgets), so these compiles guard the kernels at the
production tiles (``repro.core.zolo_pallas``: bn=256, bk=512, bm=256)
and at the paper's nemeth03 width (n = 9506) padded to those tiles,
without a chip.  Nothing runs: each test checks that the compiled HLO
calls the kernel (``tpu_custom_call``).
"""

import functools
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.gram import gram_kernel_call
from repro.kernels.grouped_combine import grouped_combine_kernel_call
from repro.kernels.matmul import matmul_kernel_call

BN, BK, BM = 256, 512, 256
NEMETH03_PADDED = 9506 + (-9506) % BK  # 9728: a multiple of every tile
SIZES = {"tiles": (2 * BK, 2 * BN), "nemeth03": (NEMETH03_PADDED,) * 2}
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _gram(m, n, dtype, chip):
    fn = functools.partial(gram_kernel_call, bn=BN, bk=BK, interpret=False)
    return jax.jit(fn).lower(_spec((m, n), dtype, chip),
                             _spec((), jnp.float32, chip))


def _grouped_combine(m, n, dtype, chip):
    fn = functools.partial(grouped_combine_kernel_call, bm=BM, bn=BN,
                           interpret=False)
    scalar = _spec((), jnp.float32, chip)
    return jax.jit(fn).lower(_spec((m, n), dtype, chip),
                             _spec((2, m, n), dtype, chip),
                             _spec((2,), jnp.float32, chip), scalar, scalar)


def _matmul(m, n, dtype, chip):
    fn = functools.partial(matmul_kernel_call, bm=BM, bn=BN, bk=BK,
                           interpret=False)
    return jax.jit(fn).lower(_spec((m, n), dtype, chip),
                             _spec((n, n), dtype, chip),
                             _spec((), jnp.float32, chip))


KERNELS = {"gram": _gram, "grouped_combine": _grouped_combine,
           "matmul": _matmul}


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, dtype, size):
    m, n = SIZES[size]
    compiled = KERNELS[kernel](m, n, DTYPES[dtype], one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()



def test_grouped_plan_compiles_for_v5e_2x2(topo, monkeypatch):
    """The grouped plan on {"zolo": 2, "sep": 2} over the four described
    chips: the shard_map body with its rolled triangular solves, the
    Pallas Gram and combine kernels, and the psums between chips."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    import repro.dist.grouped as grouped
    import repro.kernels.ops as ops
    import repro.solver as solver

    # steer the backend checks that see this host's CPU, not the chips
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    monkeypatch.setattr(grouped, "_default_combine_kernel", lambda d: True)
    monkeypatch.setattr(grouped, "_default_gram_kernel", lambda d: True)
    n = 512
    mesh = grouped.zolo_group_mesh(2, list(topo.devices))
    cfg = solver.SvdConfig(kappa=1.29, l0_policy="estimate_at_plan",
                           eig_method="jacobi", nb=128)
    plan = solver.plan(cfg, (n, n), "float32", mesh=mesh)
    text = plan.compile_svd(_spec((n, n), jnp.float32,
                                  NamedSharding(mesh, P("sep", None)))
                            ).as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
