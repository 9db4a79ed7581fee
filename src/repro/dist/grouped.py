"""Paper Algorithm 3: grouped Zolo-PD over r independent process groups.

The r Zolotarev terms of eq. (12) are embarrassingly parallel: term j
only needs X and its own shift c_{2j-1}.  The paper runs each term in its
own ScaLAPACK process group (BLACS contexts) and combines with DGSUM2D.
Here the same two-level decomposition is a 2-D device mesh:

    zolo  (size r)        — one *group* per Zolotarev term (the paper's
                            TOP context)
    sep   (size ndev/r)   — devices *inside* a group (the paper's SEP
                            contexts — the per-group ScaLAPACK grid).
                            The iterate X is sharded row-wise over this
                            axis, so one term's Cholesky/QR work is
                            itself distributed and per-device memory for
                            the m x n iterate is O(m n / sep).

Both drivers here are thin ``shard_map`` bindings of the ONE iteration
engine in :mod:`repro.core.zolo`: they lay the iterate and coefficients
out over the mesh, compose the collective :class:`~repro.core.zolo.
ZoloOps` bundle (``sep_reduce_ops`` for the intra-group Gram psum —
the paper's per-grid PDSYRK + DGSUM2D — and ``zolo_term_group_ops``
for the per-group coefficient slice + fused combine whose "zolo" psum
output IS the next iterate), and hand off to the engine's loop.  There
is no grouped iteration math in this module.

* :func:`grouped_zolo_pd_static` — trace-time schedule
  (:func:`repro.core.coeffs.zolo_schedule_np`), laid out over the mesh
  by the shard_map in_specs and run by
  :func:`repro.core.zolo.run_schedule`.
* :func:`grouped_zolo_pd_dynamic` — runtime conditioning: the
  ``sigma_min`` lower bound is estimated *sep-collectively in-graph*
  (:func:`repro.core.norms.sigma_min_lower` over the collective Gram)
  and feeds :func:`repro.core.zolo.run_dynamic`'s in-graph Zolotarev
  coefficients, so ONE compiled executable serves any conditioning on
  the full (r, sep) mesh — the adaptive kappa-driven execution of the
  ROADMAP's dynamic-grouped item.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import coeffs as _coeffs
from repro.core import norms as _norms
from repro.core import zolo as _zolo
from repro.core.qdwh import PolarInfo
from repro.dist import grouped_ops as _gops


def zolo_group_mesh(r: int, devices=None) -> Mesh:
    """{"zolo": r, "sep": ndev // r} mesh over the available devices.

    "zolo" indexes the r Zolotarev-term groups (paper's TOP context);
    "sep" indexes devices within one group (paper's SEP contexts).
    """
    if devices is None:
        devices = jax.devices()
    ndev = len(devices)
    if r < 1 or ndev % r != 0:
        divisors = [d for d in range(1, ndev + 1) if ndev % d == 0]
        raise ValueError(
            f"cannot split {ndev} devices into r={r} Zolotarev groups; "
            f"r must divide the device count (valid r for {ndev} "
            f"devices: {divisors})")
    # r == ndev is valid: every group is a single device and the "sep"
    # axis has size 1 — the degenerate mesh single-device CI runs on.
    arr = np.asarray(devices).reshape(r, ndev // r)
    return Mesh(arr, ("zolo", "sep"))


def _mesh_layout(a, mesh: Mesh, r: Optional[int], qr_mode: str,
                 qr_iters: int, first_iter_modes=(),
                 mode_knob: str = "qr_mode"):
    """Shared mesh/shape validation for both grouped drivers.

    Returns (r, nsep, has_sep, m, n, m_pad, x_spec): the (r, sep)
    factorization, and the row padding to a "sep" multiple (zero rows
    are exact for every engine step: zero Gram contribution, zero solve
    rows, zero stays zero through the combine — pad once outside the
    shard_map and slice after).
    """
    if a.ndim != 2:
        raise ValueError(f"grouped Zolo-PD takes one matrix; got {a.shape}")
    if "zolo" not in mesh.axis_names:
        raise ValueError(f"mesh has no 'zolo' axis: {mesh.axis_names}")
    if r is None:
        r = mesh.shape["zolo"]
    if mesh.shape["zolo"] != r:
        raise ValueError(
            f"mesh 'zolo' axis has size {mesh.shape['zolo']} != r={r}")
    _zolo._validate_iter_mode(mode_knob, qr_mode, extra=first_iter_modes)
    has_sep = "sep" in mesh.axis_names
    nsep = int(mesh.shape["sep"]) if has_sep else 1
    if nsep > 1 and qr_mode == "householder" and qr_iters > 0:
        raise ValueError(
            f"{mode_knob}='householder' needs the full iterate on every "
            f"device (structured Householder QR is not row-distributed); "
            f"use a sep=1 mesh (r == ndev) or {mode_knob}='cholqr2'")
    m, n = a.shape
    m_pad = m + (-m) % nsep
    x_spec = P("sep", None) if has_sep else P()
    return r, nsep, has_sep, m, n, m_pad, x_spec


def _group_ops(has_sep: bool, xw, combine_kernel,
               gram_kernel: bool = False) -> _zolo.ZoloOps:
    """The grouped ZoloOps composition: intra-group sep collectives
    under the inter-group term-slice + fused combine layer.

    ``gram_kernel=True`` swaps the local base from the jnp ops to the
    Pallas-kernel bundle, so every Gram in the grouped path — the
    shared/shifted iterate Gram, the CholeskyQR2 second-pass ``g2``
    Grams (``gram(q1)`` row-sharded + ``gram_local(q2)`` replicated),
    and the dynamic driver's sigma_min Gram — runs the tiled kernel on
    the local block before the "sep" psum fuses in the shift."""
    if gram_kernel:
        from repro.core.zolo_pallas import pallas_zolo_ops
        base = pallas_zolo_ops()
    else:
        base = _zolo.DEFAULT_OPS
    if has_sep:
        base = _gops.sep_reduce_ops(base)
    return _gops.zolo_term_group_ops(base, xw=xw,
                                     combine_kernel=combine_kernel)


def _default_combine_kernel(dtype) -> bool:
    # the kernel accumulates in f32: never pick it by default for
    # wider-than-f32 inputs (the f64 parity tolerances would sink)
    return (jax.default_backend() == "tpu"
            and jnp.dtype(dtype).itemsize <= 4)


# the gram kernel follows the same policy: compiled on TPU for f32-and-
# narrower iterates, jnp elsewhere (interpret mode would run the kernel
# body in Python per device on CPU meshes)
_default_gram_kernel = _default_combine_kernel


def grouped_zolo_pd_static(a, *, mesh: Mesh, l0: Optional[float] = None,
                           r: Optional[int] = None, max_iters: int = 6,
                           qr_mode: str = "cholqr2", qr_iters: int = 1,
                           alpha=None, return_info: bool = False,
                           schedule=None, combine_kernel=None,
                           gram_kernel=None):
    """Grouped (Alg. 3) Zolo-PD orthogonal factor of ``a`` (m >= n) —
    the (static schedule, collective ops) binding of the engine.

    ``a`` must have singular values in [l0 * alpha, alpha] (alpha=1 when
    omitted, i.e. pre-scaled like :func:`repro.core.zolo.zolo_pd_static`).
    ``mesh`` must come from :func:`zolo_group_mesh` with a "zolo" axis of
    size ``r``; a "sep" axis of size > 1 distributes each term's rows
    (and its Gram/QR work) over the group's devices.  ``qr_mode`` /
    ``qr_iters`` select the stable-regime term for the first iterations
    exactly as in ``zolo_pd_static`` (qr_mode="householder" requires a
    sep axis of size 1: structured Householder QR is not row-
    distributable).  A precomputed ``schedule`` (sequence of
    :class:`repro.core.coeffs.ZoloIteration`, e.g. bound once by an
    ``SvdPlan``) takes precedence over ``l0``/``max_iters`` — the plan
    builds it at plan time and this driver only lays it out over the
    mesh.  ``combine_kernel`` forces (True) or suppresses (False) the
    Pallas grouped-combine kernel, and ``gram_kernel`` does the same for
    the Pallas gram kernel backing every local Gram (the shifted iterate
    Gram, the CholeskyQR2 second-pass ``g2``); the defaults (None)
    compile them on TPU for f32-and-narrower iterates and use the jnp
    path elsewhere.  Returns Q only (or (Q, PolarInfo)
    with ``return_info=True``); form H with ``repro.core.form_h(q, a)``
    (the paper forms H the same way, after the combine).
    """
    if schedule is not None and not len(schedule):
        raise ValueError("schedule= is empty: nothing to iterate")
    if r is None and schedule is not None:
        r = schedule[0].r
    r, nsep, has_sep, m, n, m_pad, x_spec = _mesh_layout(
        a, mesh, r, qr_mode, qr_iters)

    if schedule is not None:
        sched = list(schedule)
        if any(it.r != r for it in sched):
            raise ValueError(
                f"schedule order {[it.r for it in sched]} does not match "
                f"the mesh 'zolo' axis of size {r}")
    elif l0 is not None:
        sched = _coeffs.zolo_schedule_np(float(l0), r, max_iters=max_iters)
    else:
        raise ValueError("grouped Zolo-PD needs a static l0= or a "
                         "precomputed schedule=")
    coeff_dtype = jnp.promote_types(a.dtype, jnp.float32)
    # (iters, r): column j belongs to group j
    c_odd = jnp.asarray([it.c[0::2] for it in sched], coeff_dtype)
    a_wts = jnp.asarray([it.a for it in sched], coeff_dtype)
    mhats = jnp.asarray([it.mhat for it in sched], coeff_dtype)
    x0 = a if alpha is None else a / jnp.asarray(alpha, a.dtype)
    if m_pad != m:
        x0 = jnp.pad(x0, ((0, m_pad - m), (0, 0)))
    if combine_kernel is None:
        combine_kernel = _default_combine_kernel(a.dtype)
    if gram_kernel is None:
        gram_kernel = _default_gram_kernel(a.dtype)
    # pallas_call has no shard_map replication rule, so check_vma must be
    # False whenever ANY Pallas kernel (combine or gram) runs in the
    # body; the psum over "zolo" establishes the out_specs replication
    # either way, so rep checking is only disabled when a kernel path
    # actually runs
    check_vma = not (combine_kernel or gram_kernel)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(x_spec, P(None, "zolo"), P(None, "zolo"), P()),
        out_specs=x_spec, check_vma=check_vma)
    def run(x, c_grp, a_grp, mh):
        # c_grp / a_grp: (iters, 1) — this group's shift and weight per
        # iteration.  x: this device's (m_pad/sep, n) row block of the
        # iterate, replicated across groups.  Per-shard proof that the
        # sep axis is a real distribution (not replication): each device
        # holds 1/sep of the rows, so its Gram input — and its O(m n /
        # sep) memory — shrinks with the group size.
        if x.shape != (m_pad // nsep, n):
            raise AssertionError(
                f"iterate not row-sharded over 'sep': per-device shape "
                f"{x.shape}, expected ({m_pad // nsep}, {n}) "
                f"(m_pad={m_pad}, sep={nsep})")
        if not (c_grp.shape == (len(sched), 1) == a_grp.shape):
            raise AssertionError(
                f"coefficients not split over 'zolo': got {c_grp.shape}/"
                f"{a_grp.shape}, expected ({len(sched)}, 1)")
        # exactly one group carries X into the combine psum (exact — no
        # 1/r rescale rounding), every group adds its weighted term;
        # the engine's loop does the rest through the collective bundle
        xw = (jax.lax.axis_index("zolo") == 0).astype(coeff_dtype)
        ops = _group_ops(has_sep, xw, combine_kernel, gram_kernel)
        return _zolo.run_schedule(x, c_grp, a_grp, mh, qr_mode=qr_mode,
                                  qr_iters=qr_iters, ops=ops)

    q = run(x0, c_odd, a_wts, mhats)
    if m_pad != m:
        q = q[:m]
    if return_info:
        info = PolarInfo(iterations=jnp.int32(len(sched)),
                         residual=jnp.asarray(0.0, a.dtype),
                         l_final=jnp.asarray(sched[-1].l_after, jnp.float32),
                         converged=jnp.asarray(True),
                         l_init=jnp.asarray(sched[0].l_before, jnp.float32))
        return q, info
    return q


def grouped_zolo_pd_dynamic(a, *, mesh: Mesh, r: Optional[int] = None,
                            l=None, alpha=None, max_iters: int = 8,
                            first_mode: str = "auto",
                            eps: Optional[float] = None,
                            est_iters: int = 8,
                            return_info: bool = False,
                            combine_kernel=None, gram_kernel=None):
    """Grouped (Alg. 3) Zolo-PD with *runtime* conditioning — the
    (dynamic schedule, collective ops) binding of the engine.

    One compiled executable serves any conditioning on the full
    (r, sep) mesh: ``alpha`` defaults to the in-graph guaranteed upper
    bound :func:`repro.core.norms.sigma_max_upper`, and the lower bound
    ``l`` (when not given) is estimated *sep-collectively in-graph* —
    each device forms the partial Gram of its (m/sep, n) row block, one
    psum over "sep" yields the global Gram, and the deflated
    inverse-power estimate of :func:`repro.core.norms.sigma_min_lower`
    runs replicated on the n x n result (the same PDSYRK + DGSUM2D
    structure as the iteration itself).  The bound feeds
    :func:`repro.core.zolo.run_dynamic`'s in-graph Zolotarev
    coefficients; each group selects its own term through the bundle's
    ``coeff_select`` and the fused combine psum over "zolo" produces
    the next iterate.

    ``r`` is fixed by the mesh's "zolo" axis (it is a *static* group
    count, exactly like ``zolo_pd``'s r).  ``first_mode`` in {"auto",
    "cholqr2", "chol"} selects the peeled first iteration
    ("householder" additionally allowed on sep=1 meshes; under "auto"
    the extreme-regime branch substitutes shifted CholeskyQR2 on sep>1
    meshes — structured Householder QR is not row-distributable).
    Returns Q (or (Q, PolarInfo) with ``return_info=True``); the info
    carries the runtime iteration count, final residual, and final l.
    """
    r, nsep, has_sep, m, n, m_pad, x_spec = _mesh_layout(
        a, mesh, r, first_mode, qr_iters=1,
        first_iter_modes=("auto",), mode_knob="first_mode")
    dtype = a.dtype
    # accumulation-precision tolerance (see repro.core.zolo.zolo_pd):
    # a bf16 iterate still accumulates and factorizes in f32
    eps_f = eps or float(jnp.finfo(jnp.promote_types(dtype,
                                                     jnp.float32)).eps)
    alpha = _norms.sigma_max_upper(a) if alpha is None else jnp.asarray(alpha)
    x0 = a / alpha.astype(dtype)
    if m_pad != m:
        x0 = jnp.pad(x0, ((0, m_pad - m), (0, 0)))
    coeff_dtype = jnp.promote_types(dtype, jnp.float32)
    if combine_kernel is None:
        combine_kernel = _default_combine_kernel(dtype)
    if gram_kernel is None:
        gram_kernel = _default_gram_kernel(dtype)

    # check_vma=False: the rep checker cannot type the fori_loop carry of
    # the in-graph sigma_min estimate (the loop runs on the post-psum —
    # replicated — Gram, but the checker rejects the carry's widening
    # replication; jax suggests exactly this workaround).  Replication is
    # established by construction: every scalar derives from "sep"-psum
    # results and the iterate from the "zolo" combine psum.
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(x_spec,),
                       out_specs=(x_spec, P(), P(), P(), P(), P()),
                       check_vma=False)
    def run(x):
        if x.shape != (m_pad // nsep, n):
            raise AssertionError(
                f"iterate not row-sharded over 'sep': per-device shape "
                f"{x.shape}, expected ({m_pad // nsep}, {n}) "
                f"(m_pad={m_pad}, sep={nsep})")
        xw = (jax.lax.axis_index("zolo") == 0).astype(coeff_dtype)
        ops = _group_ops(has_sep, xw, combine_kernel, gram_kernel)
        if l is None:
            # the paper's runtime kappa estimate, distributed: partial
            # Gram + psum("sep") through the collective bundle (zero
            # pad rows contribute nothing), inverse-power replicated
            l0 = _norms.sigma_min_lower(x, iters=est_iters, gram=ops.gram)
        else:
            l0 = jnp.asarray(l)
        l0 = jnp.clip(l0, 4 * eps_f, 1.0 - eps_f)
        l0 = l0.astype(jnp.result_type(l0, 0.0))
        out = _zolo.run_dynamic(x, l0, r, eps=eps_f, max_iters=max_iters,
                                first_mode=first_mode, ops=ops,
                                allow_householder=(nsep == 1))
        # the runtime bound rides out with the engine's state: it is the
        # in-graph analogue of the plan's kappa hint, and the resilience
        # verdict checks it against the envelope the plan was admitted
        # under (replicated: derived from "sep"-psum results)
        return out + (l0.astype(jnp.float32),)

    q, l_fin, k, res, conv, l_used = run(x0)
    if m_pad != m:
        q = q[:m]
    if return_info:
        return q, PolarInfo(iterations=k, residual=res, l_final=l_fin,
                            converged=conv, l_init=l_used)
    return q


# round-number prior for the psum cost charged per word until measured;
# benchmarks/comm_calibrate.py produces the calibrated replacement.  The
# REPRO_COMM_FLOPS_PER_WORD environment variable overrides the prior at
# resolution time (see grouped_iteration_flops) so a deployment can feed
# its own calibration in without editing SvdConfig at every call site.
DEFAULT_COMM_FLOPS_PER_WORD = 32.0


def grouped_iteration_flops(m: int, n: int, r: int, iters: int,
                            gram_shared: bool, sep: int = 1,
                            comm_flops_per_word=None) -> float:
    """Flops (summed over the r groups, per device within a group) of
    ``iters`` Cholesky-variant Zolotarev iterations on an m x n matrix.

    Per term: one n x n Cholesky (n^3/3; replicated on every device of
    the group — the CholeskyQR structure keeps it un-distributed) plus
    two triangular solves against the local row block (2 m n^2 / sep).
    The Gram product (2 m n^2 / sep local partial + one "sep"-axis psum
    of n^2 words) is paid once per *group* in the paper-faithful mode
    (each group owns one term and recomputes G) and once per *iteration*
    in the single-address-space gram-shared mode (sep must be 1 there:
    gram sharing is the one-address-space ablation).  Collectives are
    charged at ``comm_flops_per_word`` flop-equivalents per word: the
    n^2 "sep" Gram reduction and the (m n / sep) "zolo" combine — so the
    model prices the sep speed-up against its communication and the
    planner's grouped scoring (this total / r = the per-group critical
    path) stays honest for sep > 1 meshes.

    ``comm_flops_per_word=None`` resolves to the
    ``REPRO_COMM_FLOPS_PER_WORD`` environment variable when set (a
    deployment-wide calibration hook, read at every resolution so tests
    can monkeypatch the environment), else to the
    ``DEFAULT_COMM_FLOPS_PER_WORD`` prior (so cost models can pass a
    caller's possibly-absent calibration straight through);
    ``benchmarks/comm_calibrate.py`` measures the actual psum cost per
    word against the device's matmul flop rate — per compute dtype, bf16
    included — (committed as ``BENCH_comm.json``), and a calibrated
    value threads through planning via
    ``SvdConfig.extra["comm_flops_per_word"]``.
    """
    if comm_flops_per_word is None:
        env = os.environ.get("REPRO_COMM_FLOPS_PER_WORD")
        comm_flops_per_word = (float(env) if env
                               else DEFAULT_COMM_FLOPS_PER_WORD)
    if sep < 1:
        raise ValueError(f"sep degree must be >= 1, got {sep}")
    if gram_shared and sep != 1:
        raise ValueError("gram_shared is the single-address-space mode; "
                         "the sep axis does not apply (got sep="
                         f"{sep})")
    gram = 2.0 * m * n * n / sep
    per_term = n ** 3 / 3.0 + 2.0 * m * n * n / sep
    if gram_shared:
        per_iter = gram + r * per_term
    else:
        comm = comm_flops_per_word * (
            (float(n * n) if sep > 1 else 0.0)      # "sep" Gram psum
            + (m * n / sep if r > 1 else 0.0))      # "zolo" combine psum
        per_iter = r * (gram + per_term + comm)
    return float(iters * per_iter)
