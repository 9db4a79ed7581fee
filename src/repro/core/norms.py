"""Spectral-bound estimators for the polar-decomposition drivers.

The paper assumes alpha >= sigma_max(A) and beta <= sigma_min(X0) are known
or cheaply estimated (§3.2, Table 3).  On TPU we estimate in-graph:

* ``sigma_max_upper``    — guaranteed upper bound  sqrt(||A||_1 ||A||_inf)
                           (capped by ||A||_F, also an upper bound).
* ``sigma_max_power``    — power iteration (sharp, lower-biased).
* ``sigma_min_lower``    — inverse power iteration on the (ridged) Gram
                           matrix; returns a deliberately deflated estimate
                           (x0.5) so the Zolotarev interval stays valid.
* ``sigma_min_lower_qr`` — one QR + inverse iteration on R; never squares
                           the condition number, so it resolves sigma_min
                           down to ~eps * sigma_max (what
                           ``condition_estimate`` uses).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def frobenius(a):
    return jnp.sqrt(jnp.sum(jnp.abs(a) ** 2))


def frobenius_pair(a, b):
    """(||a||_F, ||b||_F) as one stacked length-2 vector.

    The single-process default behind the ``ZoloOps.fnorm_pair`` slot.
    Distributed bundles override it so both sums-of-squares ride ONE
    "sep" all-reduce instead of two — the dynamic driver's residual test
    (||X1 - X0||_F vs ||X1||_F) is the caller, once peeled and once per
    while-loop body, so the fusion removes one collective per iteration
    from the convergence-check critical path.
    """
    return jnp.sqrt(jnp.stack([jnp.sum(jnp.abs(a) ** 2),
                               jnp.sum(jnp.abs(b) ** 2)]))


def sigma_max_upper(a):
    """Guaranteed upper bound on sigma_max: min(sqrt(||A||_1 ||A||_inf), ||A||_F)."""
    n1 = jnp.max(jnp.sum(jnp.abs(a), axis=-2))
    ninf = jnp.max(jnp.sum(jnp.abs(a), axis=-1))
    return jnp.minimum(jnp.sqrt(n1 * ninf), frobenius(a))


def sigma_max_power(a, iters: int = 10, key=None):
    """Power iteration on A^T A; sharp estimate of sigma_max (lower-biased,
    so callers wanting a bound should multiply by a safety factor)."""
    m, n = a.shape[-2:]
    if key is None:
        key = jax.random.PRNGKey(0)
    v = jax.random.normal(key, a.shape[:-2] + (n,), dtype=a.dtype)
    v = v / jnp.linalg.norm(v, axis=-1, keepdims=True)

    def body(_, v):
        w = jnp.einsum("...mn,...n->...m", a, v)
        u = jnp.einsum("...mn,...m->...n", a, w)
        return u / jnp.maximum(jnp.linalg.norm(u, axis=-1, keepdims=True),
                               jnp.finfo(a.dtype).tiny)

    v = jax.lax.fori_loop(0, iters, body, v)
    return jnp.linalg.norm(jnp.einsum("...mn,...n->...m", a, v), axis=-1)


def sigma_min_lower(x, iters: int = 8, safety: float = 0.5, *, gram=None):
    """Deflated estimate of sigma_min(X) for X with sigma_max <= ~1.

    Inverse power iteration on G = X^T X + delta I via one Cholesky,
    delta = n * eps keeps the factorization well-posed even for singular X.
    Never returns below sqrt(delta) * safety (the resolution floor).

    The Gram product accumulates in f32-or-better and the iteration runs
    in that dtype (its eps sets the ridge): a bf16/f16 input would
    otherwise push the resolution floor to sqrt(n * eps_bf16) ~ 0.5 —
    an *over*-estimate of sigma_min, invalidating the Zolotarev interval
    it feeds.  Returns the promoted dtype (f32 for bf16/f16 inputs).

    ``gram`` swaps the Gram product for an injectable implementation
    with the :class:`repro.core.zolo.ZoloOps` ``gram(x)`` contract
    (f32-or-better accumulation).  This is how the grouped dynamic
    driver estimates the bound *sep-collectively in-graph*: ``x`` is
    then each device's (m/sep, n) row block, the collective ``gram``
    psums the partial product to the global (n, n) Gram, and everything
    after it (the n x n Cholesky and the length-n inverse-power
    iteration) is replicated per device — exactly the CholeskyQR
    distribution structure of the iteration itself.
    """
    n = x.shape[-1]
    dtype = jnp.promote_types(x.dtype, jnp.float32)
    eps = jnp.finfo(dtype).eps
    delta = n * eps
    if gram is None:
        g = jnp.einsum("...mk,...mn->...kn", x, x,
                       preferred_element_type=dtype)
    else:
        g = gram(x).astype(dtype)
    g = g + delta * jnp.eye(n, dtype=dtype)
    l = jnp.linalg.cholesky(g)

    def solve(v):
        y = jax.lax.linalg.triangular_solve(
            l, v[..., None], left_side=True, lower=True)
        z = jax.lax.linalg.triangular_solve(
            l, y, left_side=True, lower=True, transpose_a=True)
        return z[..., 0]

    v = jnp.ones(x.shape[:-2] + (n,), dtype=dtype) / jnp.sqrt(
        jnp.asarray(n, dtype))

    def body(_, v):
        w = solve(v)
        return w / jnp.maximum(jnp.linalg.norm(w, axis=-1, keepdims=True),
                               jnp.finfo(dtype).tiny)

    v = jax.lax.fori_loop(0, iters, body, v)
    lam = jnp.einsum("...n,...n->...", v, jnp.einsum("...kn,...n->...k", g, v))
    sig2 = jnp.maximum(lam - delta, delta)
    return safety * jnp.sqrt(sig2)


def sigma_min_lower_qr(x, iters: int = 12, safety: float = 0.5):
    """sigma_min lower estimate via one QR + inverse iteration on R.

    Unlike the Gram route this never squares the condition number, so it
    resolves sigma_min down to ~eps * sigma_max (the standard trick in
    production QDWH implementations: condition-estimate the R factor).

    bf16/f16 inputs promote to f32 up front (QR has no low-precision
    kernel, and the estimate would be meaningless at eps_bf16 anyway);
    like :func:`sigma_min_lower`, the result is the promoted dtype.
    """
    n = x.shape[-1]
    dtype = jnp.promote_types(x.dtype, jnp.float32)
    x = x.astype(dtype)
    r = jnp.linalg.qr(x, mode="r")

    def solve(v):
        # w = R^{-1} R^{-T} v  (power iteration on (R^T R)^{-1})
        y = jax.lax.linalg.triangular_solve(
            r, v[..., None], left_side=True, lower=False, transpose_a=True)
        z = jax.lax.linalg.triangular_solve(
            r, y, left_side=True, lower=False)
        return z[..., 0]

    v = jnp.ones(x.shape[:-2] + (n,), dtype=dtype) / jnp.sqrt(
        jnp.asarray(n, dtype))

    def body(_, v):
        w = solve(v)
        return w / jnp.maximum(jnp.linalg.norm(w, axis=-1, keepdims=True),
                               jnp.finfo(dtype).tiny)

    v = jax.lax.fori_loop(0, iters, body, v)
    mu = jnp.linalg.norm(solve(v), axis=-1)  # ~ 1 / sigma_min^2
    sig = 1.0 / jnp.sqrt(jnp.maximum(mu, jnp.finfo(dtype).tiny))
    eps = jnp.finfo(dtype).eps
    # an exactly singular R (every zero-padded serving slot) sends the
    # triangular solves to inf/NaN, and NaN would otherwise propagate
    # straight through maximum() into the Zolotarev coefficients; the
    # honest lower bound there is the floor itself (f(0) = 0 keeps the
    # null block exact through the iteration)
    sig = jnp.where(jnp.isfinite(sig), sig, jnp.asarray(0.0, dtype))
    return jnp.maximum(safety * sig, 4 * eps)


def singular_interval(a, iters: int = 8):
    """(lower, upper) bracket of the singular spectrum of ``a``.

    ``upper`` is the guaranteed :func:`sigma_max_upper` bound; ``lower``
    the deflated :func:`sigma_min_lower` estimate of the pre-scaled
    matrix, mapped back to the original scale.  This is the shift-
    selection seed of the spectral divide-and-conquer frontend
    (:mod:`repro.spectral.dnc`): every spectrum-splitting shift lives in
    [lower**2, upper**2] on the Gram's eigenvalue axis, so the bracket
    bounds its bisection.  Both ends are in-graph scalars (promoted to
    f32-or-better by the sigma_min route).
    """
    upper = sigma_max_upper(a)
    safe = jnp.maximum(upper, jnp.finfo(a.dtype).tiny)
    x0 = a / safe.astype(a.dtype)
    lower = sigma_min_lower(x0, iters=iters) * safe
    return lower, upper


def condition_estimate(a, iters: int = 12):
    """kappa_2 estimate: (upper bound on sigma_max) / (lower bound on
    sigma_min), i.e. an over-estimate — safe to feed the Zolotarev
    interval [1/kappa, 1].

    Routes sigma_min through the QR estimator: the Gram route squares
    the condition number and floors out near sqrt(n * eps), silently
    capping the estimate around 1e7 in f64 — useless at the paper's
    ill-conditioned regimes (kappa up to 1e16, Tables 5/10).
    """
    amax = sigma_max_upper(a)
    x0 = a / amax.astype(a.dtype)
    smin = sigma_min_lower_qr(x0, iters=iters)
    return 1.0 / smin
