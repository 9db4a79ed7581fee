"""Pallas TPU kernel: fused grouped combine (paper Alg. 3 step 4d + DGSUM2D).

Each Zolotarev group's contribution to the next iterate is

    Y_g = mhat * (xw_g * X + sum_j a_j T_j)

with ``xw_g`` = 1 on exactly one group and 0 elsewhere, so the "zolo"-axis
``psum`` of the Y_g *is* the updated iterate

    X2 = psum_zolo(Y_g) = mhat * (X + sum over all groups' terms)

and the replicated post-psum epilogue ``mhat * (X + t)`` of the old
grouped driver disappears: the weighted term combine is fused into the
pre-psum pass and the collective itself carries the result (the paper's
DGSUM2D directly produces the next iterate on every group).

T is stacked (r_local, m, n) — the group's local terms, row-sharded over
the "sep" axis exactly like X; in grouped (Alg. 3) execution r_local is 1.
The r loop is unrolled (r is small and static).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# Constant block index: a Python 0 becomes i64 under jax_enable_x64,
# and Mosaic refuses an i64 index map.
_ZERO = np.int32(0)

# The combine accumulates in f32 whatever the iterate dtype; the paired
# conditioning envelope is ``repro.core.svd.PALLAS_KAPPA_ENVELOPE``.
COMBINE_ACCUM_DTYPE = jnp.float32
COMBINE_KAPPA_ENVELOPE = "repro.core.svd:PALLAS_KAPPA_ENVELOPE"


def _grouped_combine_kernel(x_ref, t_ref, a_ref, s_ref, out_ref, *, r: int):
    # s = [mhat, xw]: the epilogue scale and this group's X weight
    acc = s_ref[1] * x_ref[...].astype(jnp.float32)
    for j in range(r):
        acc += a_ref[j] * t_ref[j].astype(jnp.float32)
    out_ref[...] = (s_ref[0] * acc).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def grouped_combine_kernel_call(x, t, a, mhat, xw, *, bm: int = 256,
                                bn: int = 256, interpret: bool = False):
    """Y = mhat * (xw * X + sum_j a[j] * T[j]).

    x: (m, n); t: (r, m, n); a: (r,); mhat, xw: scalars (xw may be a
    traced per-group value, e.g. ``axis_index("zolo") == 0``).  Output
    dtype follows x.
    """
    m, n = x.shape
    r = t.shape[0]
    if t.shape != (r, m, n):
        raise ValueError(
            f"grouped_combine_kernel_call: terms shape {t.shape} does "
            f"not stack x's {(m, n)} over r={r}")
    if m % bm != 0 or n % bn != 0:
        raise ValueError(
            f"grouped_combine_kernel_call needs tile-divisible shapes: "
            f"got ({m}, {n}) with bm={bm}, bn={bn} — pad through "
            f"kernels.ops.grouped_combine instead")
    a_arr = jnp.asarray(a, jnp.float32)
    s_arr = jnp.stack([jnp.asarray(mhat, jnp.float32).reshape(()),
                       jnp.asarray(xw, jnp.float32).reshape(())])
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        functools.partial(_grouped_combine_kernel, r=r),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((r, bm, bn), lambda i, j: (_ZERO, i, j)),
            pl.BlockSpec((r,), lambda i, j: (_ZERO,)),
            pl.BlockSpec((2,), lambda i, j: (_ZERO,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
    )(x, t, a_arr, s_arr)
