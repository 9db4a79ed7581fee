"""Triangular solves of the polar iterations (Zolo-PD and QDWH).

XLA's TPU triangular solve unrolls one step per 128-row block: at the
paper's n = 9506 with r = 2 terms a single solve took minutes of host
compile, and in forward substitution it held a temporary per block
(28 GB of a v5e's 16 GB HBM).  :func:`solve_lower` replaces it with a
blocked back substitution written as one rolled loop, which compiles its
body once and updates the solution in place.  Each step is one
(block x n) @ (n x k) product, so a solve costs 2 n^2 k flops, twice a
triangular solve's, plus one block-sized triangular solve per step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Row-block size of the blocked solve.
SOLVE_BLOCK = 256


def _solve_upper(u, b):
    """x with u x = b for upper-triangular ``u`` (..., n, n) and b
    (..., n, k)."""
    n = u.shape[-1]
    nb = min(SOLVE_BLOCK, n)
    pad = (-n) % nb
    if pad:  # identity rows and zero right-hand sides: exact
        lead = [(0, 0)] * (u.ndim - 2)
        u = jnp.pad(u, lead + [(0, pad), (0, pad)])
        u = u + jnp.diag(jnp.arange(n + pad) >= n).astype(u.dtype)
        b = jnp.pad(b, [(0, 0)] * (b.ndim - 2) + [(0, pad), (0, 0)])
    nblk = (n + pad) // nb

    def step(i, x):
        j = (nblk - 1 - i) * nb
        rows = jax.lax.dynamic_slice_in_dim(u, j, nb, axis=-2)
        # rows of x not yet solved are still zero, so the full-width
        # product only subtracts the solved unknowns
        rhs = jax.lax.dynamic_slice_in_dim(b, j, nb, axis=-2) - jnp.matmul(
            rows, x, precision=jax.lax.Precision.HIGHEST)
        xj = jax.lax.linalg.triangular_solve(
            jax.lax.dynamic_slice_in_dim(rows, j, nb, axis=-1), rhs,
            left_side=True, lower=False)
        return jax.lax.dynamic_update_slice_in_dim(x, xj, j, axis=-2)

    # the first step runs outside the loop, so that under shard_map the
    # carry already varies over every mesh axis that u and b vary over
    x = jax.lax.fori_loop(1, nblk, step, step(0, jnp.zeros_like(b)))
    return x[..., :n, :]


@jax.named_scope("trsm")
def solve_lower(l, b, *, left_side: bool, transpose_a: bool = False):
    """``lax.linalg.triangular_solve(l, b, lower=True, left_side=...,
    transpose_a=...)`` for lower-triangular ``l`` (..., n, n), through
    the rolled blocked back substitution.  A forward solve runs on the
    reversed unknowns: with J the exchange matrix, J L J is upper
    triangular and L y = b  <=>  (J L J)(J y) = J b.  Its operations
    carry the name scope ``trsm``."""
    if not left_side:  # x op(L) = b  <=>  op(L)^T x^T = b^T
        return jnp.swapaxes(_solve_left(
            l, jnp.swapaxes(b, -1, -2), not transpose_a), -1, -2)
    return _solve_left(l, b, transpose_a)


def _solve_left(l, b, transpose_a: bool):
    if transpose_a:  # L^T is upper triangular
        return _solve_upper(jnp.swapaxes(l, -1, -2), b)
    return jnp.flip(_solve_upper(jnp.flip(l, (-2, -1)), jnp.flip(b, -2)),
                    -2)
