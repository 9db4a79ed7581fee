"""Test matrices made on the device from a seed, with known factors.

A = U diag(sigma) V^T, where U and V have orthonormal columns and sigma
is a geometric spectrum from 1 down to 1/kappa.  Each orthogonal factor
is a product of random block reflectors I - 2 Y Y^T, each the
reflection through the span of a Gaussian (m, b) block orthonormalized
by CholeskyQR2 (b <= m / 4, so that the block is well conditioned),
applied in a rolled
``lax.fori_loop`` to the leading columns of the identity (one block per
b rows, so every column is mixed by every block), then polished by one
Newton-Schulz step.
Nothing here uses XLA's QR, eigh or SVD, which do not compile at
n = 9506 on a TPU in a run's time.

The same functions build the reference's factors after the window
(``precision="highest"``) and the control's (``precision="high"``, the
three-pass bf16 product).  This module imports nothing of the system under test.
"""

from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 128


def seed_key(seed: int, name: str):
    """A PRNG key from ``--seed`` and a name: the seed's 64 bits and the
    CRC-32 of the name, folded in one after the other, so that seeds
    above 2**31 are taken whole and two names never share a stream."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(zlib.crc32(name.encode()))
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def spectrum(n: int, kappa: float) -> np.ndarray:
    """Geometric singular values from 1 to 1/kappa, descending (f64)."""
    return np.geomspace(1.0, 1.0 / float(kappa), int(n))


def matmul(a, b, precision: str):
    """a @ b in f32 at ``precision``: "highest" is the full-f32 product;
    "high" is the three-pass bf16 product (hi*hi + hi*lo + lo*hi, f32
    accumulation): ``Precision.HIGH`` on a TPU, and written out on any
    other backend, whose f32 products ignore the precision flag."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    if jax.default_backend() == "tpu":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)
    a_hi = a.astype(jnp.bfloat16)
    a_lo = (a - a_hi.astype(a.dtype)).astype(jnp.bfloat16)
    b_hi = b.astype(jnp.bfloat16)
    b_lo = (b - b_hi.astype(b.dtype)).astype(jnp.bfloat16)

    def dot(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))


def orthogonal_columns(key, m: int, k: int, precision: str = "highest"):
    """(m, k) f32 with orthonormal columns: the first k columns of a
    product of ceil(m / b) random block reflectors, b = min(BLOCK, m / 4)."""
    bs = max(1, min(BLOCK, m // 4))
    nblocks = -(-m // bs)

    eye_bs = jnp.eye(bs, dtype=jnp.float32)

    def orthonormalize(y):
        # one CholeskyQR pass: y R^-1 with R^T R = y^T y
        g = jnp.matmul(y.T, y, precision=jax.lax.Precision.HIGHEST)
        r_inv = jax.scipy.linalg.solve_triangular(
            jnp.linalg.cholesky(g).T, eye_bs, lower=False)
        return jnp.matmul(y, r_inv, precision=jax.lax.Precision.HIGHEST)

    def body(b, x):
        y = jax.random.normal(jax.random.fold_in(key, b), (m, bs),
                              jnp.float32)
        y = orthonormalize(orthonormalize(y))
        return x - 2.0 * matmul(y, matmul(y.T, x, precision), precision)

    x = jax.lax.fori_loop(0, nblocks, body,
                          jnp.eye(m, k, dtype=jnp.float32))
    # one Newton-Schulz step: the blocks' rounding leaves X^T X - I at
    # about 1e-6 (in ||.||_F / sqrt(k)); one step takes it to the floor
    # that an f32 Gram sets, about 4e-7
    e = jnp.eye(k, dtype=jnp.float32) - matmul(x.T, x, precision)
    return x + 0.5 * matmul(x, e, precision)


@functools.partial(jax.jit, static_argnames=("m", "n", "precision"))
def factors(key, m: int, n: int, precision: str = "highest"):
    """(U, V) of the matrix that ``key`` names: (m, k) and (n, k),
    k = min(m, n)."""
    k = min(m, n)
    ku, kv = jax.random.split(key)
    return (orthogonal_columns(ku, m, k, precision),
            orthogonal_columns(kv, n, k, precision))


@functools.partial(jax.jit, static_argnames=("m", "n"))
def matrix(key, sigma, m: int, n: int):
    """A = U diag(sigma) V^T in f32, built at full f32 precision."""
    u, v = factors(key, m, n)
    return matmul(u * sigma[None, :], v.T, "highest")

