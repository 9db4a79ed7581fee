"""Pallas TPU kernel: fused shifted Gram matrix  G = A^T A + c I.

This is the compute hot spot of Zolo-PD's Cholesky variant (Alg. 1 step 4d
and Alg. 3 step 4c): every iteration forms Z_j = X^T X + c_{2j-1} I.  The
fusion saves one full n^2 read-modify-write for the +cI (and the paper's
Gram-sharing optimization means this kernel runs once per iteration, not r
times).

Tiling: grid (n/bn, n/bn, m/bk); A is streamed twice through VMEM in
(bk, bn) tiles; the (bn, bn) output tile accumulates in f32 across the k
dimension (TPU ``arbitrary`` semantics on k make the revisits legal).  MXU
alignment: all tile dims are multiples of 128 by default.
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

# Accumulation dtype of every dot in this kernel; sub-f32 inputs (bf16)
# are legal because the MXU widens to this before summing.  The
# conditioning envelope that pairs with it lives in
# ``repro.core.svd.PALLAS_KAPPA_ENVELOPE`` keyed by (input, accum) dtype.
GRAM_ACCUM_DTYPE = jnp.float32
GRAM_KAPPA_ENVELOPE = "repro.core.svd:PALLAS_KAPPA_ENVELOPE"

# In-kernel shift clamp: a *positive* Gram shift c is ridged up to at
# least SHIFT_RIDGE_FACTOR * eps(accum) * max diag(G).  At kappa >~ 1e4
# the odd Zolotarev coefficients underflow past the accumulated Gram's
# eps-level negative eigenvalues, Z = G + cI goes indefinite, and the
# downstream Cholesky emits NaN (ROADMAP 4a).  Ridging by an
# eps-of-the-accumulator multiple is below the Gram's own rounding error,
# so clean solves are unperturbed; c == 0 (unshifted Grams: CholeskyQR2's
# G2, the sigma_min estimate) is never touched.
SHIFT_RIDGE_FACTOR = 8.0


def _gram_kernel(a1_ref, a2_ref, c_ref, out_ref, *, n_k: int, bn: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a1 = a1_ref[...]
    a2 = a2_ref[...]
    # f32 inputs take the full-f32 MXU contraction (Mosaic's default is
    # a bf16 pass); bf16 inputs are exact in one pass
    out_ref[...] += jax.lax.dot_general(
        a1, a2, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST if a1.dtype == jnp.float32
                   else None))

    @pl.when(jnp.logical_and(k == n_k - 1, i == j))
    def _shift_diag():
        rows = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1)
        eye = (rows == cols).astype(out_ref.dtype)
        c = c_ref[0]
        # shift clamp: ridge a positive shift against the accumulator's
        # eps so Z = G + cI stays definite (see SHIFT_RIDGE_FACTOR)
        diag_max = jnp.max(out_ref[...] * eye)
        floor = (SHIFT_RIDGE_FACTOR
                 * jnp.finfo(GRAM_ACCUM_DTYPE).eps
                 * jnp.maximum(diag_max, 0.0))
        c_eff = jnp.where(c > 0.0, jnp.maximum(c, floor), c)
        out_ref[...] += c_eff * eye


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def gram_kernel_call(a, c, *, bn: int = 256, bk: int = 512,
                     interpret: bool = False):
    """G = A^T A + c I via pallas_call.  a: (m, n); c: scalar.

    Returns f32 (n, n).  m, n padded to tile multiples by the wrapper in
    ``ops.py``; this entry requires exact divisibility.
    """
    m, n = a.shape
    if n % bn != 0 or m % bk != 0:
        raise ValueError(
            f"gram_kernel_call needs tile-divisible shapes: got "
            f"({m}, {n}) with bn={bn}, bk={bk} — pad through "
            f"kernels.ops.gram instead")
    n_k = m // bk
    c_arr = jnp.asarray(c, jnp.float32).reshape(1)

    grid = (n // bn, n // bn, n_k)
    return pl.pallas_call(
        functools.partial(_gram_kernel, n_k=n_k, bn=bn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, i)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1,), lambda i, j, k: (_ZERO,)),
        ],
        out_specs=pl.BlockSpec((bn, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=interpret,
    )(a, a, c_arr)
