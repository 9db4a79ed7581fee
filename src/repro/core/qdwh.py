"""QDWH-PD: QR-based dynamically weighted Halley polar decomposition.

Paper §2.1 (eqs. 2-4).  The baseline the paper compares Zolo-PD against.

Two drivers:

* :func:`qdwh_pd`        — dynamic: coefficients from a runtime lower bound
                           ``l`` inside a ``lax.while_loop``; per-iteration
                           QR (eq. 3) vs Cholesky (eq. 4) switch at
                           ``c_k <= 100`` exactly as suggested in [31]/§2.1.
* :func:`qdwh_pd_static` — trace-time schedule (unrolled); used inside
                           compiled train steps and dry-runs.

Both return ``(Q, H, info)`` with ``A = Q H``; set ``want_h=False`` to skip
forming H (the Muon path only needs Q).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import coeffs as _coeffs
from repro.core import norms as _norms
from repro.core.trisolve import solve_lower


class PolarInfo(NamedTuple):
    """Convergence record; a NamedTuple so compiled (jit) plans return it.

    ``converged`` is the runtime verdict the resilience layer keys on: a
    dynamic driver's ``while_loop`` can exit at the iteration cap with
    the residual rule unmet, and before this flag existed that exit was
    silent (the factors just carried reduced accuracy — or NaN — out).
    Static trace-time schedules are converged by construction (their
    depth was sized from l0 at plan time).  ``l_init`` records the
    sigma_min lower bound the solve actually ran under — the runtime
    analogue of the plan's kappa hint (kappa_est = 1/l_init), NaN when
    the driver has no bound (Newton, the SVD oracle, a schedule-only
    static call).
    """

    iterations: jnp.ndarray  # scalar int32
    residual: jnp.ndarray  # final ||X2 - X1||_F / ||X2||_F
    l_final: jnp.ndarray
    # Python-scalar defaults (not jnp arrays: no device work at class
    # definition) keep three-field construction by out-of-tree backends
    # valid; every in-repo driver sets both explicitly.
    converged: jnp.ndarray = True  # scalar bool: stopping rule met
    l_init: jnp.ndarray = float("nan")  # f32 entry bound; NaN unknown


def _eps_for(dtype) -> float:
    return float(jnp.finfo(dtype).eps)


@jax.named_scope("form_h")
def form_h(q, a):
    """H = (Q^T A + (Q^T A)^T) / 2 — the Hermitian polar factor.

    HIGHEST: H's eigenvalues are the singular values, and at TPU DEFAULT
    precision an f32 product runs as one bf16 pass (~2e-3 relative)."""
    qa = jnp.einsum("...mk,...mn->...kn", q, a,
                    precision=jax.lax.Precision.HIGHEST)
    return 0.5 * (qa + jnp.swapaxes(qa, -1, -2))


def _qdwh_qr_iter(x, a, b, c):
    """Inverse-free QR iteration (eq. 3): X+ = (b/c) X + (a - b/c)/sqrt(c) Q1 Q2^T."""
    m, n = x.shape[-2:]
    dtype = x.dtype
    stacked = jnp.concatenate(
        [jnp.sqrt(c).astype(dtype) * x,
         jnp.broadcast_to(jnp.eye(n, dtype=dtype), x.shape[:-2] + (n, n))],
        axis=-2)
    q, _ = jnp.linalg.qr(stacked)
    q1 = q[..., :m, :]
    q2 = q[..., m:, :]
    coef = ((a - b / c) / jnp.sqrt(c)).astype(dtype)
    # HIGHEST here and in the Gram below: the products are the new
    # iterate, and TPU DEFAULT precision runs f32 as one bf16 pass
    return (b / c).astype(dtype) * x + coef * jnp.einsum(
        "...mk,...nk->...mn", q1, q2, precision=jax.lax.Precision.HIGHEST)


def _qdwh_chol_iter(x, a, b, c):
    """Cholesky iteration (eq. 4): Z = I + c X^T X, X+ = (b/c)X + (a-b/c) X Z^{-1}."""
    n = x.shape[-1]
    dtype = x.dtype
    g = jnp.einsum("...mk,...mn->...kn", x, x,
                   preferred_element_type=jnp.promote_types(
                       dtype, jnp.float32),
                   precision=jax.lax.Precision.HIGHEST).astype(dtype)
    z = c.astype(dtype) * g + jnp.eye(n, dtype=dtype)
    l = jnp.linalg.cholesky(z)
    # W = Z^{-1} X^T via two triangular solves.
    xt = jnp.swapaxes(x, -1, -2)
    y = solve_lower(l, xt, left_side=True)
    w = solve_lower(l, y, left_side=True, transpose_a=True)
    xz = jnp.swapaxes(w, -1, -2)
    return (b / c).astype(dtype) * x + (a - b / c).astype(dtype) * xz


def qdwh_pd(a, *, alpha=None, l=None, max_iters: int = 12,
            eps: Optional[float] = None, want_h: bool = True,
            chol_switch: float = 100.0):
    """Dynamic QDWH polar decomposition of ``a`` (m >= n)."""
    dtype = a.dtype
    eps = eps or _eps_for(dtype)
    alpha = _norms.sigma_max_upper(a) if alpha is None else jnp.asarray(alpha)
    x0 = a / alpha.astype(dtype)
    l0 = _norms.sigma_min_lower_qr(x0) if l is None else jnp.asarray(l)
    l0 = jnp.clip(l0, 4 * eps, 1.0 - eps)
    tol = eps ** (1.0 / 3.0)

    def cond(state):
        x, _, l, k, res, _ = state
        return jnp.logical_and(k < max_iters, res > tol)

    def body(state):
        x, _, l, k, _, _ = state
        ca, cb, cc = _coeffs.qdwh_coeffs(l)
        x_new = jax.lax.cond(
            cc > chol_switch,
            lambda x_: _qdwh_qr_iter(x_, ca, cb, cc),
            lambda x_: _qdwh_chol_iter(x_, ca, cb, cc),
            x)
        res = _norms.frobenius(x_new - x) / jnp.maximum(
            _norms.frobenius(x_new), jnp.finfo(dtype).tiny)
        l_new = jnp.clip(_coeffs.qdwh_l_update(l, ca, cb, cc), 0.0, 1.0)
        return x_new, x, l_new, k + 1, res, res <= tol

    init = (x0, jnp.zeros_like(x0), l0.astype(jnp.result_type(l0, 0.0)),
            jnp.int32(0), jnp.asarray(1.0, dtype), jnp.asarray(False))
    x, _, l_fin, k, res, conv = jax.lax.while_loop(cond, body, init)
    info = PolarInfo(iterations=k, residual=res, l_final=l_fin,
                     converged=conv, l_init=l0.astype(jnp.float32))
    if want_h:
        return x, form_h(x, a), info
    return x, None, info


def qdwh_pd_static(a, *, l0: Optional[float] = None, max_iters: int = 8,
                   want_h: bool = True, qr_iters: Optional[int] = None,
                   schedule=None):
    """Unrolled QDWH with a trace-time coefficient schedule from ``l0``.

    ``a`` must already be scaled so that sigma_max(a) <= 1 (callers divide
    by a sigma_max upper bound first).  ``qr_iters``: how many leading
    iterations use the inverse-free QR form; default: while the schedule's
    ``c_k`` exceeds 100 (paper's switch).  A precomputed ``schedule``
    (sequence of ``(a, b, c, l)`` rows from
    :func:`repro.core.coeffs.qdwh_schedule_np`, e.g. bound by an
    ``SvdPlan``) takes precedence over ``l0``/``max_iters``.
    """
    if schedule is not None:
        sched = list(schedule)
    elif l0 is not None:
        sched = _coeffs.qdwh_schedule_np(float(l0), max_iters=max_iters)
    else:
        raise ValueError("qdwh_pd_static needs l0= or a precomputed "
                         "schedule=")
    x = a
    coeff_dtype = jnp.promote_types(a.dtype, jnp.float32)
    for i, (ca, cb, cc, _) in enumerate(sched):
        use_qr = cc > 100.0 if qr_iters is None else i < qr_iters
        fa = jnp.asarray(ca, coeff_dtype)
        fb = jnp.asarray(cb, coeff_dtype)
        fc = jnp.asarray(cc, coeff_dtype)
        if use_qr:
            x = _qdwh_qr_iter(x, fa, fb, fc)
        else:
            x = _qdwh_chol_iter(x, fa, fb, fc)
    info = PolarInfo(iterations=jnp.int32(len(sched)),
                     residual=jnp.asarray(0.0, a.dtype),
                     l_final=jnp.asarray(sched[-1][3], jnp.float32),
                     converged=jnp.asarray(True),
                     l_init=jnp.asarray(float(l0) if l0 is not None
                                        else float("nan"), jnp.float32))
    if want_h:
        return x, form_h(x, a), info
    return x, None, info
