"""The plain reference and the comparison that decides ``correct``.

The reference answer of a matrix A = U diag(sigma) V^T made by
:mod:`bench.matrices` follows from its factors, rebuilt from the seed:
the polar factors are Q = U V^T and H = V diag(sigma) V^T, and the
singular values are sigma.  The numbers compared:

* ``q_err``: ||Q - Q_ref||_F / ||Q_ref||_F;
* ``h_err``: ||H - H_ref||_F / ||H_ref||_F.

An SVD answer (U, s, V^T) is compared through the polar factors it
implies, U V^T and V diag(s) V^T: they are defined whatever the order
of singular vectors within a cluster of nearly equal singular values,
and they cover U, s and V^T, and so the eig stage and U = Q V.  The
singular values are not compared on their own: the control's are the
exact sigma, so a limit on max |s - sigma| would have no control
reading to sit below.

The control is the reference itself, computed with every product of
width n at the three-pass bf16 precision (``"high"``) and put in the
program's place.  This module imports nothing of the system under test.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import matrices


def _rel(x, ref):
    x = x.astype(jnp.float32)
    return jnp.linalg.norm(x - ref) / jnp.linalg.norm(ref)


def polar_answer(key, sigma, m: int, n: int, precision: str):
    """(Q, H) of the matrix ``key`` names, at ``precision``."""
    u, v = matrices.factors(key, m, n, precision)
    q = matrices.matmul(u, v.T, precision)
    h = matrices.matmul(v * sigma[None, :], v.T, precision)
    return q, h


@functools.partial(jax.jit, static_argnames=("m", "n"))
def polar_errors(q, h, key, sigma, m: int, n: int):
    q_ref, h_ref = polar_answer(key, sigma, m, n, "highest")
    return {"q_err": _rel(q, q_ref), "h_err": _rel(h, h_ref)}


def _svd_polar(u, s, vh):
    q = matrices.matmul(u, vh, "highest")
    h = matrices.matmul(vh.T * s[None, :], vh, "highest")
    return q, h


@functools.partial(jax.jit, static_argnames=("m", "n"))
def svd_errors(u, s, vh, key, sigma, m: int, n: int):
    u, s, vh = (t.astype(jnp.float32) for t in (u, s, vh))
    q, h = _svd_polar(u, s, vh)
    return polar_errors(q, h, key, sigma, m, n)


@functools.partial(jax.jit, static_argnames=("m", "n"))
def polar_control(key, sigma, m: int, n: int):
    """The control's answer in the program's place: (Q, H) at "high"."""
    return polar_answer(key, sigma, m, n, "high")


@functools.partial(jax.jit, static_argnames=("m", "n"))
def svd_control(key, sigma, m: int, n: int):
    """The control's answer in the program's place: (U, s, V^T) at
    "high"."""
    u, v = matrices.factors(key, m, n, "high")
    return u, sigma, v.T


def host(numbers) -> dict:
    return {k: float(v) for k, v in jax.device_get(numbers).items()}
