"""Zolotarev and QDWH iteration coefficients (paper §2.1-§2.2).

Two backends:

* ``zolo_coeffs`` — JAX, jittable: coefficients computed *in-graph* from a
  runtime lower bound ``l`` (so condition estimates feeding a compiled
  train step work).  Uses :mod:`repro.core.elliptic`.
* ``zolo_schedule_np`` — numpy/scipy float64 at trace time: a *static*
  schedule of per-iteration coefficients for a fixed ``l0``.  This is what
  the ZoloMuon optimizer embeds (constants in the compiled graph, like the
  fixed Newton-Schulz coefficients in standard Muon).

Notation follows the paper: for order ``r`` and lower bound ``l``,

    c_i  = l^2 sn^2(i K'/(2r+1); l') / cn^2(...)      i = 1..2r   (eq. 7)
    Mhat = prod_j (1 + c_{2j-1}) / (1 + c_{2j})                    (eq. 8)
    a_j  = -prod_k (c_{2j-1} - c_{2k}) / prod_{k!=j} (c_{2j-1} - c_{2k-1})
                                                                   (eq. 10)
    l_next = Mhat * l * prod_j (l^2 + c_{2j}) / (l^2 + c_{2j-1})

(the paper's eq. for the l-update has a typo — ``l + c_{2j}`` — the correct
update is the scaled function evaluated at l, i.e. ``l^2 + c_{2j}``; this
matches [Nakatsukasa-Freund 2016] and is verified in tests against the
equioscillation property.)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from scipy import special as _scipy_special

from repro.core import elliptic

# Machine-epsilon targets used for convergence tests (paper: 1e-15 band).
EPS64 = 1.1e-16
MAX_R = 8


# ---------------------------------------------------------------------------
# JAX backend
# ---------------------------------------------------------------------------


def zolo_coeffs(l, r: int):
    """Zolotarev coefficients for order ``r`` and lower bound ``l`` (JAX).

    Returns ``(c, a, mhat)`` with ``c`` shaped (2r,) (``c[i-1]`` is the
    paper's ``c_i``), ``a`` shaped (r,), and scalar ``mhat``.
    ``r`` must be a static python int.
    """
    l = jnp.asarray(l)
    mc = l * l
    kp = elliptic.ellipk_mc(mc)
    i = jnp.arange(1, 2 * r + 1, dtype=l.dtype)
    u = i * kp / (2 * r + 1)
    sn, cn, _ = elliptic.ellipj_mc(u, mc)
    c = mc * (sn * sn) / (cn * cn)

    c_even = c[1::2]  # c_{2j},   j=1..r
    c_odd = c[0::2]  # c_{2j-1}, j=1..r
    mhat = jnp.prod((1.0 + c_odd) / (1.0 + c_even))

    # a_j via the residue formula; the k == j term in the denominator
    # product is masked to 1.
    diff_even = c_odd[:, None] - c_even[None, :]  # (j, k): c_{2j-1}-c_{2k}
    diff_odd = c_odd[:, None] - c_odd[None, :]  # (j, k): c_{2j-1}-c_{2k-1}
    eye = jnp.eye(r, dtype=l.dtype)
    a = -jnp.prod(diff_even, axis=1) / jnp.prod(diff_odd + eye, axis=1)
    return c, a, mhat


def zolo_l_update(l, c, mhat):
    """Map the lower bound through the scaled Zolotarev function."""
    l = jnp.asarray(l)
    c_even = c[1::2]
    c_odd = c[0::2]
    l2 = l * l
    return mhat * l * jnp.prod((l2 + c_even) / (l2 + c_odd))


def zolo_fn_scalar(x, c, a, mhat):
    """Evaluate hat-Z_{2r+1}(x; l) in partial-fraction form (eq. 9/11)."""
    x = jnp.asarray(x)
    c_odd = c[0::2]
    terms = a[..., :] / (x[..., None] ** 2 + c_odd)
    return mhat * x * (1.0 + jnp.sum(terms, axis=-1))


def zolo_fn_product(x, c, mhat):
    """Evaluate hat-Z_{2r+1}(x; l) in product form (eq. 8) — test oracle."""
    x = jnp.asarray(x)
    c_even = c[1::2]
    c_odd = c[0::2]
    num = x[..., None] ** 2 + c_even
    den = x[..., None] ** 2 + c_odd
    return mhat * x * jnp.prod(num / den, axis=-1)


# ---------------------------------------------------------------------------
# numpy/scipy backend (trace-time static schedules)
# ---------------------------------------------------------------------------


def _ellipj_mc_np(u, mc):
    if mc > 1e-14:
        sn, cn, dn, _ = _scipy_special.ellipj(np.asarray(u), 1.0 - mc)
        return sn, cn, dn
    try:
        # Extreme regime (kappa > 1e7): f64 Landen loses ~8 digits, so use
        # arbitrary precision when available (trace-time only, tiny inputs).
        import mpmath

        with mpmath.workdps(40):
            m = mpmath.mpf(1) - mpmath.mpf(float(mc))
            sn = np.array([float(mpmath.ellipfun("sn", float(x), m=m))
                           for x in np.atleast_1d(u)])
            cn = np.array([float(mpmath.ellipfun("cn", float(x), m=m))
                           for x in np.atleast_1d(u)])
            dn = np.array([float(mpmath.ellipfun("dn", float(x), m=m))
                           for x in np.atleast_1d(u)])
        return sn, cn, dn
    except ImportError:  # pragma: no cover
        sn, cn, dn = elliptic.ellipj_mc(jnp.float64(u), jnp.float64(mc))
        return np.asarray(sn), np.asarray(cn), np.asarray(dn)


def _ellipk_mc_np(mc):
    return float(_scipy_special.ellipkm1(mc))


def zolo_coeffs_np(l: float, r: int):
    """float64 numpy version of :func:`zolo_coeffs` (trace-time)."""
    l = float(l)
    mc = l * l
    kp = _ellipk_mc_np(mc)
    i = np.arange(1, 2 * r + 1, dtype=np.float64)
    u = i * kp / (2 * r + 1)
    sn, cn, _ = _ellipj_mc_np(u, mc)
    c = mc * sn**2 / cn**2
    c_even = c[1::2]
    c_odd = c[0::2]
    mhat = float(np.prod((1.0 + c_odd) / (1.0 + c_even)))
    a = np.empty(r, dtype=np.float64)
    for j in range(r):
        num = np.prod(c_odd[j] - c_even)
        den = np.prod(np.delete(c_odd[j] - c_odd, j))
        a[j] = -num / den
    return c, a, mhat


def zolo_l_update_np(l: float, c: np.ndarray, mhat: float) -> float:
    c_even = c[1::2]
    c_odd = c[0::2]
    l2 = l * l
    return float(mhat * l * np.prod((l2 + c_even) / (l2 + c_odd)))


@dataclasses.dataclass(frozen=True)
class ZoloIteration:
    """Static coefficients for one Zolo-PD iteration."""

    c: tuple  # (2r,)
    a: tuple  # (r,)
    mhat: float
    l_before: float
    l_after: float

    @property
    def r(self) -> int:
        return len(self.a)


def zolo_schedule_np(l0: float, r: int, max_iters: int = 8,
                     tol: float = 1.0 - 1e-15) -> list[ZoloIteration]:
    """Static per-iteration coefficient schedule until 1 - l <= 1 - tol."""
    sched = []
    l = float(l0)
    for _ in range(max_iters):
        c, a, mhat = zolo_coeffs_np(l, r)
        l_next = zolo_l_update_np(l, c, mhat)
        sched.append(ZoloIteration(tuple(c), tuple(a), mhat, l, l_next))
        l = l_next
        if l >= tol:
            break
    return sched


@functools.lru_cache(maxsize=None)
def zolo_iter_count(kappa: float, r: int, tol: float = 1e-15,
                    max_iters: int = 64) -> int:
    """Smallest k with hat-Z^k([1/kappa, 1]) inside [1 - tol, 1].

    This regenerates the paper's Table 1 from first principles (scalar
    recursion on the interval lower bound).
    """
    l = 1.0 / float(kappa)
    for k in range(1, max_iters + 1):
        c, _, mhat = zolo_coeffs_np(l, r)
        l = zolo_l_update_np(l, c, mhat)
        if 1.0 - l <= tol:
            return k
    return max_iters


def choose_r(kappa: float, max_groups: int = 3, tol: float = 1e-15) -> int:
    """Paper §3.2 policy: prefer small r (2 or 3); only grow r beyond that
    when it actually removes an iteration and resources allow (Table 1)."""
    kappa = max(float(kappa), 1.0 + 1e-12)
    best_r, best_iters = 1, zolo_iter_count(kappa, 1, tol)
    for r in range(2, min(max_groups, MAX_R) + 1):
        it = zolo_iter_count(kappa, r, tol)
        if it < best_iters:
            best_r, best_iters = r, it
    return best_r


# ---------------------------------------------------------------------------
# QDWH dynamic coefficients (paper eq. 2/3; Nakatsukasa-Bai-Gygi 2010)
# ---------------------------------------------------------------------------


def qdwh_coeffs(l):
    """Dynamically-weighted Halley coefficients (a, b, c) for bound ``l``.

    JAX-friendly; ``l`` may be a traced scalar.
    """
    l = jnp.asarray(l)
    l2 = l * l
    d = jnp.cbrt(4.0 * (1.0 - l2) / (l2 * l2))
    a = jnp.sqrt(1.0 + d) + 0.5 * jnp.sqrt(
        8.0 - 4.0 * d + 8.0 * (2.0 - l2) / (l2 * jnp.sqrt(1.0 + d))
    )
    b = (a - 1.0) ** 2 / 4.0
    c = a + b - 1.0
    return a, b, c


def qdwh_l_update(l, a, b, c):
    l = jnp.asarray(l)
    return l * (a + b * l * l) / (1.0 + c * l * l)


def qdwh_coeffs_np(l: float):
    l2 = l * l
    d = (4.0 * (1.0 - l2) / (l2 * l2)) ** (1.0 / 3.0)
    a = np.sqrt(1.0 + d) + 0.5 * np.sqrt(
        8.0 - 4.0 * d + 8.0 * (2.0 - l2) / (l2 * np.sqrt(1.0 + d))
    )
    b = (a - 1.0) ** 2 / 4.0
    c = a + b - 1.0
    return float(a), float(b), float(c)


def qdwh_schedule_np(l0: float, max_iters: int = 20,
                     tol: float = 1.0 - 1e-15) -> list[tuple]:
    """Static (a, b, c, l) schedule for QDWH from initial bound l0."""
    sched = []
    l = float(l0)
    for _ in range(max_iters):
        a, b, c = qdwh_coeffs_np(l)
        sched.append((a, b, c, l))
        l = float(l * (a + b * l * l) / (1.0 + c * l * l))
        if l >= tol:
            break
    return sched


def qdwh_iter_count(kappa: float, tol: float = 1e-15) -> int:
    return len(qdwh_schedule_np(1.0 / float(kappa), tol=1.0 - tol))
