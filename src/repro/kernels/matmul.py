"""Pallas TPU kernel: tiled matmul C = alpha * A @ B (f32 accumulate).

Used by the polar/SVD pipeline for the dense products that are not Gram
matrices: Q1 Q2^T (eq. 12), U = Q_p V (Alg. 2 step 3), and H formation.
Standard (i, j, k) tiling with output revisiting on the contraction axis.
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

# f32 accumulation for any input dtype (bf16 included); the paired
# conditioning envelope is ``repro.core.svd.PALLAS_KAPPA_ENVELOPE``.
MATMUL_ACCUM_DTYPE = jnp.float32
MATMUL_KAPPA_ENVELOPE = "repro.core.svd:PALLAS_KAPPA_ENVELOPE"


def _matmul_kernel(a_ref, b_ref, alpha_ref, out_ref, *, n_k: int):
    k = pl.program_id(2)  # i, j unused: output block fixed by (0, 1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jax.lax.dot_general(
        a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _scale():
        out_ref[...] *= alpha_ref[0]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul_kernel_call(a, b, alpha=1.0, *, bm: int = 256, bn: int = 256,
                       bk: int = 512, interpret: bool = False):
    """C = alpha * A @ B.  a: (m, k); b: (k, n) -> f32 (m, n)."""
    m, kk = a.shape
    k2, n = b.shape
    if kk != k2:
        raise ValueError(
            f"matmul_kernel_call: inner dims disagree ({kk} vs {k2})")
    if m % bm != 0 or n % bn != 0 or kk % bk != 0:
        raise ValueError(
            f"matmul_kernel_call needs tile-divisible shapes: got "
            f"({m}, {kk}) @ ({k2}, {n}) with bm={bm}, bn={bn}, bk={bk} "
            f"— pad through kernels.ops.matmul instead")
    n_k = kk // bk
    alpha_arr = jnp.asarray(alpha, jnp.float32).reshape(1)
    grid = (m // bm, n // bn, n_k)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1,), lambda i, j, k: (_ZERO,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(a, b, alpha_arr)
