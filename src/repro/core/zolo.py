"""Zolo-PD: polar decomposition via composed Zolotarev functions.

Paper Algorithm 1 / Algorithm 3, adapted to TPU per DESIGN.md §3:

* The r independent terms of eq. (12) are evaluated as one *batched*
  computation over a leading ``r`` axis (maps to the paper's r process
  groups; on a TPU slice the batch either vmaps onto the MXU or is split
  over a mesh axis by ``repro.dist.grouped``).
* **Gram sharing** (beyond-paper): within one address space the Gram
  product ``G = X^T X`` is computed once and shared by all r shifted
  factorizations Z_j = G + c_{2j-1} I.  The paper-faithful grouped mode
  (each group recomputes G) lives in ``repro.dist.grouped``.
* The first (ill-conditioned) iteration uses the *structured QR* of
  ``[X; sqrt(c) I]`` — either the paper-faithful blocked Householder
  (:mod:`repro.core.structured_qr`, MPDGEQRF/MPDORGQR analogue) or the
  TPU-native shifted CholeskyQR2 — selected by ``qr_mode``.

One engine, two orthogonal choices
----------------------------------

Every Zolo-PD backend in this repo is the SAME iteration, specialized
along two independent axes:

* **schedule source** — where the per-iteration coefficients come from:
  :func:`run_schedule` (a trace-time precomputed
  :func:`repro.core.coeffs.zolo_schedule_np` list, fully unrolled) or
  :func:`run_dynamic` (in-graph coefficients from the running lower
  bound ``l`` inside a ``lax.while_loop``, with the peeled
  stability-regime first iteration).
* **:class:`ZoloOps` execution bundle** — where the compute runs: the
  default jnp/einsum ops, the fused Pallas kernels
  (:func:`repro.core.zolo_pallas.pallas_zolo_ops`), or the
  sep-/zolo-collective distributed ops
  (:mod:`repro.dist.grouped_ops`).

Both loops share :func:`zolo_iteration` — the ONE iteration body.  The
public drivers are thin bindings of a (schedule source, ops bundle)
pair:

======================  ===============  ==========================
driver                  schedule source  ops bundle
======================  ===============  ==========================
``zolo_pd``             dynamic          any (default jnp)
``zolo_pd_static``      static           any (default jnp)
``zolo_pd_pallas``      static           ``pallas_zolo_ops``
``zolo_pd_pallas_dynamic``  dynamic      ``pallas_zolo_ops``
``grouped_zolo_pd_static``  static       sep/zolo-collective
``grouped_zolo_pd_dynamic`` dynamic      sep/zolo-collective
======================  ===============  ==========================

A new backend is a new pair, never a fifth loop.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import coeffs as _coeffs
from repro.core import norms as _norms
from repro.core.qdwh import PolarInfo, form_h
from repro.core.structured_qr import structured_qr_q1q2 as _structured_qr_q1q2
from repro.core.trisolve import solve_lower


# Ridge floor multiplier for the shifted-Gram coefficient in sub-f64
# iterates: c is clamped to >= factor * eps(accum dtype) * max diag(G)
# before Z = G + cI is factorized.  At kappa >~ 1e4 the odd Zolotarev
# shifts fall below the Gram's eps-level negative eigenvalue noise and
# the Cholesky goes indefinite (NaN); an eps-of-the-accumulator ridge is
# below G's own rounding error so clean solves are unperturbed.  Keep in
# sync with ``repro.kernels.gram.SHIFT_RIDGE_FACTOR`` (the in-kernel
# clamp on the fused shifted-Gram path).
SHIFT_RIDGE_FACTOR = 8.0


def _clamp_shift(c_odd, g, dtype):
    """Shift clamp: ridge positive Gram shifts for itemsize <= 4 iterates.

    f64 numerics are untouched — the f64 dynamic driver runs shifts of
    ~1e-20 at kappa 1e10 today, far below any eps-level floor, and
    clamping them would change converged results."""
    if jnp.dtype(dtype).itemsize > 4:
        return c_odd
    accum = jnp.promote_types(dtype, jnp.float32)
    diag_max = jnp.max(jnp.diagonal(g, axis1=-2, axis2=-1))
    floor = (SHIFT_RIDGE_FACTOR * jnp.finfo(accum).eps
             * jnp.maximum(diag_max, 0.0)).astype(c_odd.dtype)
    return jnp.where(c_odd > 0, jnp.maximum(c_odd, floor), c_odd)


def _gram(x, c=0.0):
    """G = X^T X (+ c I) with f32-or-better accumulation.

    HIGHEST: the Cholesky factors this Gram, and at TPU DEFAULT
    precision an f32 product runs as one bf16 pass (~2e-3 relative)."""
    g = jnp.einsum("...mk,...mn->...kn", x, x,
                   preferred_element_type=jnp.promote_types(x.dtype,
                                                            jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    if isinstance(c, (int, float)) and c == 0.0:
        return g
    n = x.shape[-1]
    # the f32-accumulated shifted Gram gets the same shift clamp as the
    # Pallas kernel (f64 accumulation passes through _clamp_shift intact)
    c_arr = _clamp_shift(jnp.asarray(c, g.dtype), g, g.dtype)
    return g + c_arr * jnp.eye(n, dtype=g.dtype)


def _polar_update(x, t, a, mhat):
    """X2 = mhat * (X + sum_j a_j T_j) over stacked terms t: (r, ..., m, n).

    The combine runs at the term dtype (f32-or-better: a sub-f32 iterate's
    terms come out of f32-accumulated factorizations) and the result is
    cast back to the iterate dtype, so a bf16 iterate stays bf16."""
    s = jnp.einsum("j,j...mn->...mn", a.astype(t.dtype), t)
    return (mhat * (x + s)).astype(x.dtype)


def _coeff_select_all(c_odd, a):
    """Default coefficient selector: this executor evaluates all r terms."""
    return c_odd, a


class ZoloOps(NamedTuple):
    """Injectable compute ops for the Zolotarev iteration hot spots.

    The engine below routes its hot loops through this bundle, so a
    backend can swap the default jnp/einsum path for fused kernels
    (``repro.core.zolo_pallas`` builds one on the Pallas kernels in
    :mod:`repro.kernels`) or for collective distributed versions
    (``repro.dist.grouped_ops`` all-reduces partial Grams over the
    intra-group "sep" mesh axis and fuses the r-term combine into the
    "zolo" psum) without touching the driver logic.

    * ``gram(x, c=0.0)``          -> X^T X + c I, f32-or-better
      accumulation (callers cast the result to the working dtype).
      ``x`` is the iterate (or a factor sharing its row distribution,
      e.g. the CholeskyQR2 Q1): a distributed implementation holds an
      (m/sep, n) row block and must all-reduce the partial product to
      the *global* Gram.
    * ``gram_local(q, c=0.0)``    -> same contract for an operand that
      is *replicated* (not row-distributed) — the CholeskyQR2 identity
      block Q2.  Never cross-device-reduced; single-address-space
      bundles point it at the same implementation as ``gram``.
    * ``polar_update(x, t, a, mhat)`` -> mhat * (X + sum_j a[j] T[j])
      with ``t`` the stacked (r, m, n) terms — the iteration combine
      (paper's DGSUM2D role).  A grouped bundle contributes
      ``mhat * (xw * X + a * T)`` with ``xw`` one-hot over groups and
      psums over "zolo" so the collective output IS the next iterate.
    * ``coeff_select(c_odd, a)``  -> the (c_odd, a) slice THIS executor
      evaluates.  The dynamic engine computes all r in-graph
      coefficients on every device and selects through this hook; the
      default keeps all r (single-address-space batched terms), a
      grouped bundle takes its own group's length-1 slice via
      ``axis_index("zolo")``.  (Static schedules select by data layout
      instead — the shard_map in_specs split the coefficient arrays —
      so :func:`run_schedule` never calls this.)
    * ``fnorm(x)``                -> global Frobenius norm of the
      (possibly row-distributed) iterate, for the dynamic engine's
      residual stopping rule; a sep-distributed bundle psums the local
      sum of squares.
    * ``fnorm_pair(a, b)``        -> length-2 vector of both Frobenius
      norms at once — the dynamic engine's residual test needs
      ``||X1 - X0||`` and ``||X1||`` together, and a sep-distributed
      bundle fuses both sums-of-squares into ONE all-reduce (two
      ``fnorm`` calls would pay two collectives per iteration on the
      convergence-check critical path).
    """

    gram: Callable = _gram
    polar_update: Callable = _polar_update
    gram_local: Callable = _gram
    coeff_select: Callable = _coeff_select_all
    fnorm: Callable = _norms.frobenius
    fnorm_pair: Callable = _norms.frobenius_pair


DEFAULT_OPS = ZoloOps()


def _chol_terms(x, c_odd, gram=None, *, ops: ZoloOps = DEFAULT_OPS):
    """T_j = X (X^T X + c_{2j-1} I)^{-1} for all j, batched over r.

    Returns W with shape (r, ..., n, m) holding Z_j^{-1} X^T (transposed
    terms); callers combine as sum_j a_j W_j^T.
    """
    n = x.shape[-1]
    # factorizations run at f32-or-better whatever the iterate dtype:
    # lax.linalg has no sub-f32 kernels, and a bf16 iterate's terms come
    # out of the f32-accumulated Gram anyway
    fdtype = jnp.promote_types(x.dtype, jnp.float32)
    r = c_odd.shape[0]
    if gram is None and r == 1:
        # single-term executor (the grouped r-sharded case): fold the
        # shift into the Gram call itself so a collective bundle carries
        # it inside the "sep" psum (fused shifted Gram) and the
        # kernel-/gram-side shift clamp applies
        with jax.named_scope("gram"):
            z = ops.gram(x, c_odd.astype(fdtype)[0])[None].astype(fdtype)
    else:
        if gram is None:
            with jax.named_scope("gram"):
                gram = ops.gram(x)
        g = gram.astype(fdtype)
        eye = jnp.eye(n, dtype=fdtype)
        with jax.named_scope("cholesky"):
            c_eff = _clamp_shift(c_odd.astype(fdtype), g, x.dtype)
            z = g[None] + c_eff[:, None, None] * eye  # (r, n, n)
    with jax.named_scope("cholesky"):
        l = jnp.linalg.cholesky(z)
    xt = jnp.broadcast_to(
        jnp.swapaxes(x, -1, -2).astype(fdtype),
        (r,) + x.shape[:-2] + (n, x.shape[-2]))
    y = solve_lower(l, xt, left_side=True)
    w = solve_lower(l, y, left_side=True, transpose_a=True)
    return w  # (r, n, m), fdtype


def term_sum_chol(x, c_odd, a, gram=None, *, ops: ZoloOps = DEFAULT_OPS):
    """sum_j a_j X (X^T X + c_{2j-1} I)^{-1} over the given (possibly
    partial) odd-coefficient slice — the Cholesky-variant Zolotarev term.

    Kept for callers wanting the bare term; the drivers go through
    :func:`zolo_iteration`."""
    w = _chol_terms(x, c_odd, gram=gram, ops=ops)
    return jnp.einsum("j,jnm->mn", a.astype(w.dtype), w).astype(x.dtype)


def term_sum_cholqr2(x, c_odd, a, *, ops: ZoloOps = DEFAULT_OPS):
    """sum_j (a_j / sqrt(c_j)) Q1_j Q2_j^T via shifted CholeskyQR2
    (eq. 12 analogue) over the given odd-coefficient slice.

    Q1_j = X R_j^{-1}, Q2_j = sqrt(c_j) R_j^{-1} with R_j from a two-pass
    shifted Cholesky QR of [X; sqrt(c_j) I].  Explicit Q (paper's MPDORGQR
    role) keeps the term stable for much smaller c_j than a single
    Cholesky.

    Both Gram passes route through ``ops``: the first (and the Q1 part
    of the second) uses ``ops.gram`` — Q1 shares X's row distribution —
    while the replicated identity-block part Q2^T Q2 uses
    ``ops.gram_local`` so a sep-distributed bundle does not all-reduce
    (and thereby over-count) it."""
    n = x.shape[-1]
    # factorizations at f32-or-better (see _chol_terms); the clamp below
    # ridges only Z's shift — sqrt_c and the final weights keep the exact
    # c so pass 2 still corrects to the true QR of [X; sqrt(c) I]
    fdtype = jnp.promote_types(x.dtype, jnp.float32)
    r = c_odd.shape[0]
    c_odd_f = c_odd.astype(fdtype)
    sqrt_c = jnp.sqrt(c_odd_f)
    eye = jnp.eye(n, dtype=fdtype)

    if r == 1:
        # fused shifted Gram: the shift rides the collective (see
        # _chol_terms); the gram implementation applies the shift clamp
        with jax.named_scope("gram"):
            z = ops.gram(x, c_odd_f[0])[None].astype(fdtype)
    else:
        with jax.named_scope("gram"):
            g = ops.gram(x).astype(fdtype)
        with jax.named_scope("cholesky"):
            c_eff = _clamp_shift(c_odd_f, g, x.dtype)
            z = g[None] + c_eff[:, None, None] * eye
    with jax.named_scope("cholesky"):
        l1 = jnp.linalg.cholesky(z)  # R1 = L1^T
    xb = jnp.broadcast_to(x.astype(fdtype), (r,) + x.shape)
    # Q1 = X R1^{-1}  (right-solve against upper-triangular R1 = L1^T)
    q1 = solve_lower(l1, xb, left_side=False, transpose_a=True)
    # Q2 = sqrt(c) R1^{-1}
    q2 = sqrt_c[:, None, None] * solve_lower(
        l1, jnp.broadcast_to(eye, (r, n, n)), left_side=False,
        transpose_a=True)
    # Second pass restores orthogonality: G2 = Q^T Q = Q1^T Q1 + Q2^T Q2.
    # The Grams take the *iterate* dtype so a sub-f32 bundle's kernels
    # run the production precision (no-op cast for f32/f64).
    with jax.named_scope("gram"):
        g2 = (ops.gram(q1.astype(x.dtype))
              + ops.gram_local(q2.astype(x.dtype))).astype(fdtype)
    with jax.named_scope("cholesky"):
        l2 = jnp.linalg.cholesky(g2)
    q1 = solve_lower(l2, q1, left_side=False, transpose_a=True)
    q2 = solve_lower(l2, q2, left_side=False, transpose_a=True)
    # the Q1 Q2^T product is the new iterate's term: HIGHEST, as _gram
    with jax.named_scope("combine"):
        return jnp.einsum("j,jmk,jnk->mn", a.astype(fdtype) / sqrt_c, q1,
                          q2, precision=jax.lax.Precision.HIGHEST)


def term_sum_householder(x, c_odd, a, block: int = 32, *,
                         ops: ZoloOps = DEFAULT_OPS):
    """sum_j (a_j / sqrt(c_j)) Q1_j Q2_j^T via blocked *structured*
    Householder QR of [X; sqrt(c_j) I] (MPDGEQRF/MPDORGQR analogue, §3.1)
    over the given odd-coefficient slice.

    ``ops`` is accepted for term-signature uniformity only: the blocked
    Householder QR has no kernel or sep-distributed implementation, so
    this term requires the *full* (undistributed) ``x`` — the grouped
    drivers reject it on a sep>1 mesh."""
    dtype = jnp.promote_types(x.dtype, jnp.float32)
    x = x.astype(dtype)  # the blocked QR has no sub-f32 path
    terms = []
    for j in range(c_odd.shape[0]):
        q1, q2 = _structured_qr_q1q2(x, jnp.sqrt(c_odd[j]).astype(dtype),
                                     block=block)
        terms.append((a[j] / jnp.sqrt(c_odd[j])).astype(dtype)
                     * jnp.einsum("mk,nk->mn", q1, q2))
    return sum(terms)


ITER_MODES = ("chol", "cholqr2", "householder")


def _validate_iter_mode(name: str, value: str, extra=()) -> None:
    """ValueError (not a downstream failure) for an unknown iteration
    mode, listing the valid choices."""
    valid = sorted(ITER_MODES) + list(extra)
    if value not in valid:
        raise ValueError(f"unknown {name}: {value!r} (one of {valid})")


def zolo_iteration(x, c_odd, a, mhat, *, mode: str = "chol",
                   ops: ZoloOps = DEFAULT_OPS, hh_block: int = 32):
    """THE Zolotarev iteration body (Alg. 1 step 4 / Alg. 3 step 4).

    X -> mhat * (X + sum_j a_j T_j(c_{2j-1})) with the shifted
    factorization for T_j picked by ``mode``:

    * ``"chol"``        — shared-Gram Cholesky (eq. 4 analogue; the
      steady-state term once the interval has left the stiff regime).
    * ``"cholqr2"``     — shifted CholeskyQR2 (TPU-native stable
      first-iteration term).
    * ``"householder"`` — blocked structured Householder QR (paper
      §3.1; paper-faithful stable term, not row-distributable).

    ``c_odd``/``a`` hold the odd shifts c_{2j-1} and weights a_j of the
    terms THIS executor evaluates — all r in the single-address-space
    drivers, this group's length-1 slice under ``repro.dist.grouped``.
    Every schedule source (static or dynamic) and every ops bundle
    (jnp, Pallas, sep-collective) runs through this one body: there is
    no forked per-driver iteration math anywhere else.
    """
    if mode == "chol":
        w = _chol_terms(x, c_odd, ops=ops)    # (r, ..., n, m)
        with jax.named_scope("combine"):
            t = jnp.swapaxes(w, -1, -2)       # stacked terms (r, ..., m, n)
            return ops.polar_update(x, t, a, mhat)
    if mode == "cholqr2":
        # the QR-form terms fold the a_j weights into their sum, so the
        # combine sees one pre-summed term with unit weight
        t = term_sum_cholqr2(x, c_odd, a, ops=ops)
    elif mode == "householder":
        t = term_sum_householder(x, c_odd, a, block=hh_block, ops=ops)
    else:
        _validate_iter_mode("mode", mode)
    one = jnp.ones((1,), jnp.promote_types(x.dtype, jnp.float32))
    with jax.named_scope("combine"):
        return ops.polar_update(x, t[None], one, mhat)


def run_schedule(x, c_odd, a_wts, mhats, *, qr_mode: str = "cholqr2",
                 qr_iters: int = 1, ops: ZoloOps = DEFAULT_OPS,
                 hh_block: int = 32):
    """THE static schedule source: the trace-time coefficient schedule,
    fully unrolled over :func:`zolo_iteration`.

    ``c_odd`` (iters, r_local) / ``a_wts`` (iters, r_local) /
    ``mhats`` (iters,) are the stacked per-iteration coefficients —
    r_local = r for the batched single-address-space drivers, 1 for a
    grouped shard_map body whose in_specs split the arrays over "zolo".
    The first ``qr_iters`` iterations use the stable-regime ``qr_mode``
    term; the rest use the shared-Gram Cholesky term.
    """
    for i in range(c_odd.shape[0]):
        mode = qr_mode if i < qr_iters else "chol"
        x = zolo_iteration(x, c_odd[i], a_wts[i], mhats[i], mode=mode,
                           ops=ops, hh_block=hh_block)
    return x


def run_dynamic(x0, l0, r: int, *, eps: float, max_iters: int = 8,
                first_mode: str = "auto", hh_block: int = 32,
                ops: ZoloOps = DEFAULT_OPS, allow_householder: bool = True):
    """THE dynamic schedule source: in-graph Zolotarev coefficients from
    the running lower bound, so one compiled executable serves any
    conditioning.

    The *first* iteration is peeled out of the while-loop and selects
    its factorization by stability regime (the paper's QR-first policy):

      l <  ~10 sqrt(eps)  -> structured Householder QR  (paper §3.1)
      l <  0.05           -> shifted CholeskyQR2         (TPU fast path)
      else                -> shared-Gram Cholesky        (eq. 4 analogue)

    ``first_mode`` in {"auto", "householder", "cholqr2", "chol"} —
    "auto" switches at runtime via lax.switch; a static choice compiles
    only one branch.  ``allow_householder=False`` substitutes the
    shifted CholeskyQR2 term in the extreme regime (a row-distributed
    ops bundle cannot run the structured Householder QR).  All remaining
    iterations use the shared-Gram Cholesky form (after one Zolotarev
    map the interval is always in Cholesky range).

    The stopping rule is the paper's residual criterion (Alg. 1 step 4e)
    only: an interval-bound certificate (stop when l >= 1 - O(eps)) is
    unsound in finite precision at extreme kappa — the fp iterate lags
    the exact-arithmetic l recursion (measured: orth 4e-5 where the
    certificate claimed convergence at kappa 1e16).  The residual rule
    reproduces the paper's *measured* Tables 5/10 (theory + <= 1).

    Every coefficient set passes through ``ops.coeff_select`` (a grouped
    bundle takes its group's slice) and residual norms through
    ``ops.fnorm`` (a distributed bundle all-reduces), so the SAME loop
    runs single-device, kernel-backed, and grouped.  Returns
    ``(x, l_final, iterations, residual, converged)``: ``converged`` is
    carried through the loop state and records whether the residual
    rule was met — an exit at ``max_iters`` with the rule unmet used to
    be indistinguishable from convergence, which is exactly the silent
    failure the resilience layer's verdicts key on.
    """
    dtype = x0.dtype
    # floor the residual tolerance at a few iterate-dtype eps: a bf16
    # iterate's step-to-step residual bottoms out near eps(bf16), below
    # which the f32-accumulation tol (e.g. r=1) would never be met
    tol = max(eps ** (1.0 / (2 * r + 1)),
              4.0 * float(jnp.finfo(dtype).eps))
    hh_thresh = 10.0 * eps ** 0.5
    qr_thresh = 0.05

    # --- peeled first iteration -------------------------------------------
    c0, a0, m0 = _coeffs.zolo_coeffs(l0, r)
    c0_odd = c0[0::2]

    def first(x_, mode):
        c_sel, a_sel = ops.coeff_select(c0_odd, a0)
        return zolo_iteration(x_, c_sel, a_sel, m0, mode=mode, ops=ops,
                              hh_block=hh_block)

    hh_mode = "householder" if allow_householder else "cholqr2"
    if first_mode == "auto":
        branch = (jnp.int32(0) + (l0 >= hh_thresh).astype(jnp.int32)
                  + (l0 >= qr_thresh).astype(jnp.int32))
        x1 = jax.lax.switch(
            branch,
            [lambda x_: first(x_, hh_mode),
             lambda x_: first(x_, "cholqr2"),
             lambda x_: first(x_, "chol")],
            x0)
    else:
        x1 = first(x0, first_mode)
    nrm1 = ops.fnorm_pair(x1 - x0, x1)  # one fused reduction for both
    res1 = nrm1[0] / jnp.maximum(nrm1[1], jnp.finfo(dtype).tiny)
    l1 = jnp.clip(_coeffs.zolo_l_update(l0, c0, m0), 0.0, 1.0 - eps)

    # --- remaining iterations: shared-Gram Cholesky ------------------------
    def cond(state):
        _, _, k, res, _ = state
        return jnp.logical_and(k < max_iters, res > tol)

    def body(state):
        x, l, k, _, _ = state
        c, av, mh = _coeffs.zolo_coeffs(l, r)
        c_sel, a_sel = ops.coeff_select(c[0::2], av)
        x_new = zolo_iteration(x, c_sel, a_sel, mh, mode="chol", ops=ops)
        nrm = ops.fnorm_pair(x_new - x, x_new)
        res = nrm[0] / jnp.maximum(nrm[1], jnp.finfo(dtype).tiny)
        l_new = jnp.clip(_coeffs.zolo_l_update(l, c, mh), 0.0, 1.0 - eps)
        return x_new, l_new, k + 1, res, res <= tol

    return jax.lax.while_loop(cond, body,
                              (x1, l1, jnp.int32(1), res1, res1 <= tol))


def zolo_pd_static(a, *, l0: Optional[float] = None,
                   r: Optional[int] = None, max_iters: int = 6,
                   want_h: bool = False, qr_mode: str = "cholqr2",
                   qr_iters: int = 1, hermitian_source=None,
                   schedule=None, ops: Optional[ZoloOps] = None):
    """Unrolled Zolo-PD with a trace-time coefficient schedule — the
    (static schedule, ``ops``) binding of the engine.

    ``a`` must be pre-scaled (sigma_max <= 1) with singular values in
    [l0, 1].  The first ``qr_iters`` iterations use ``qr_mode``
    ("cholqr2" | "householder" | "chol"); the rest use the shared-Gram
    Cholesky variant.  A precomputed ``schedule`` (sequence of
    :class:`repro.core.coeffs.ZoloIteration`, e.g. bound once by an
    ``SvdPlan``) takes precedence over ``l0``/``r``/``max_iters``.
    ``ops`` swaps the iteration's compute ops for an alternative
    :class:`ZoloOps` bundle — the hook the kernel-backed ``zolo_pallas``
    backend plugs into.  Returns (Q, H or None, PolarInfo).
    """
    _validate_iter_mode("qr_mode", qr_mode)
    ops = DEFAULT_OPS if ops is None else ops
    if schedule is not None:
        sched = list(schedule)
    elif l0 is not None:
        if r is None:
            r = _coeffs.choose_r(1.0 / float(l0))
        sched = _coeffs.zolo_schedule_np(float(l0), r, max_iters=max_iters)
    else:
        raise ValueError("zolo_pd_static needs l0= or a precomputed "
                         "schedule=")
    coeff_dtype = jnp.promote_types(a.dtype, jnp.float32)
    c_odd = jnp.asarray([it.c[0::2] for it in sched], coeff_dtype)
    a_wts = jnp.asarray([it.a for it in sched], coeff_dtype)
    mhats = jnp.asarray([it.mhat for it in sched], coeff_dtype)
    x = run_schedule(a, c_odd, a_wts, mhats, qr_mode=qr_mode,
                     qr_iters=qr_iters, ops=ops)
    src = a if hermitian_source is None else hermitian_source
    info = PolarInfo(iterations=jnp.int32(len(sched)),
                     residual=jnp.asarray(0.0, a.dtype),
                     l_final=jnp.asarray(sched[-1].l_after, jnp.float32),
                     converged=jnp.asarray(True),
                     l_init=jnp.asarray(sched[0].l_before, jnp.float32))
    if want_h:
        return x, form_h(x, src), info
    return x, None, info


def zolo_pd(a, r: int = 3, *, alpha=None, l=None, max_iters: int = 8,
            eps: Optional[float] = None, want_h: bool = True,
            first_mode: str = "auto", hh_block: int = 32,
            ops: Optional[ZoloOps] = None):
    """Dynamic Zolo-PD (paper Alg. 1/3) of ``a`` with m >= n — the
    (dynamic schedule, ``ops``) binding of the engine.

    ``r`` is static (it fixes array shapes); coefficients are computed
    in-graph from the running lower bound via the JAX elliptic functions,
    so a single compiled function serves any conditioning (see
    :func:`run_dynamic` for the first-iteration regime switch and the
    residual stopping rule).  ``ops`` swaps the iteration's compute ops
    for an alternative :class:`ZoloOps` bundle — the hook the
    kernel-backed ``zolo_pallas_dynamic`` backend plugs into.
    """
    _validate_iter_mode("first_mode", first_mode, extra=("auto",))
    ops = DEFAULT_OPS if ops is None else ops
    dtype = a.dtype
    # stopping tolerance from the *accumulation* precision: a bf16
    # iterate's factorizations and Grams accumulate in f32, and
    # eps(bf16) ~ 8e-3 as a base tolerance would stop after one step
    eps = eps or float(jnp.finfo(jnp.promote_types(dtype,
                                                   jnp.float32)).eps)
    # alpha must be a guaranteed upper bound (paper: alpha assumed known/
    # estimated); the loose bound costs a few extra decades of l, which at
    # Zolotarev convergence rates is at most one extra iteration.  Callers
    # with sharp knowledge (paper Table 3 setting) pass alpha explicitly.
    alpha = _norms.sigma_max_upper(a) if alpha is None else jnp.asarray(alpha)
    x0 = a / alpha.astype(dtype)
    l0 = _norms.sigma_min_lower_qr(x0) if l is None else jnp.asarray(l)
    l0 = jnp.clip(l0, 4 * eps, 1.0 - eps)
    l0 = l0.astype(jnp.result_type(l0, 0.0))
    x, l_fin, k, res, conv = run_dynamic(x0, l0, r, eps=eps,
                                         max_iters=max_iters,
                                         first_mode=first_mode,
                                         hh_block=hh_block, ops=ops)
    info = PolarInfo(iterations=k, residual=res, l_final=l_fin,
                     converged=conv, l_init=l0.astype(jnp.float32))
    if want_h:
        return x, form_h(x, a), info
    return x, None, info


def polar_canonical(a):
    """Return (a_work, transposed) with a_work.shape[-2] >= a_work.shape[-1].

    polar(A^T) = polar(A)^T for the orthogonal factor; callers transpose
    back.  Keeps the Gram matrix at min(m, n)^2.
    """
    m, n = a.shape[-2:]
    if m >= n:
        return a, False
    return jnp.swapaxes(a, -1, -2), True
