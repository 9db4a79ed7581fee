"""plan/execute engine: ``plan(SvdConfig, shape, dtype, mesh) -> SvdPlan``.

The paper's solver is plan-then-run: r is chosen from the condition
number (Table 1), the Zolotarev coefficient schedule is built once, the r
process-group contexts are allocated, and only then does the iteration
touch the matrix.  ``plan`` performs exactly those steps at trace time —
method resolution through the registry's capability flags and per-spec
``flops_fn`` cost model, schedule precomputation through the spec's
``plan_fn``, mesh binding for grouped (Algorithm 3) execution — and
returns an :class:`SvdPlan` whose ``svd`` / ``polar`` / ``svd_batched``
entry points run compiled executables cached per (shape, dtype, config):
repeated solves at a fixed shape never retrace.

``polar_svd`` / ``polar_decompose`` in :mod:`repro.core.svd` are thin
back-compat wrappers over this same path (via :func:`plan_for_call`), so
there is still exactly one dispatch route from any public entry point
down to a registered backend.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

import repro.core  # noqa: F401  (imported for its backend registrations)
from repro.core import coeffs as _coeffs
from repro.core import norms as _norms
from repro.core import registry as _registry
from repro.core import zolo as _zolo
from repro.solver.config import SvdConfig

_UNSET = object()  # "leave want_h to the backend's default" sentinel

# LRU-bounded: the back-compat wrappers fold data-dependent floats (e.g.
# l=0.9/kappa) into the config key, so a long-running caller sweeping
# conditioning values must not accumulate plans (and their compiled
# executables) without bound.  128 distinct live configurations is far
# beyond any in-repo workload; hot plans are kept by the LRU order.
_PLANS_MAX = 128
_PLANS: "collections.OrderedDict[tuple, SvdPlan]" = collections.OrderedDict()
_PINNED: set = set()  # plan keys exempt from LRU eviction
_STATS = {"traces": 0, "plan_hits": 0, "plan_misses": 0, "evictions": 0}


def trace_count() -> int:
    """Total backend traces performed by plan executables (monotonic).

    A repeated ``plan.svd`` call at a fixed (shape, dtype, config) must
    not move this counter — that is the no-retrace contract tests assert.
    """
    return _STATS["traces"]


def plan_cache_stats() -> dict:
    return dict(_STATS, plans=len(_PLANS))


def cache_stats() -> dict:
    """Public plan-cache counters: the serving observability surface.

    ``hits``/``misses``/``evictions`` are monotonic; ``size`` is live
    plans, ``pinned`` of those exempt from LRU eviction, ``capacity``
    the LRU bound (see :func:`set_plan_cache_capacity`).  A service
    measures steady-state hit rate as the hits/(hits+misses) delta
    between two snapshots.
    """
    return {"hits": _STATS["plan_hits"], "misses": _STATS["plan_misses"],
            "evictions": _STATS["evictions"], "size": len(_PLANS),
            "pinned": len(_PINNED), "capacity": _PLANS_MAX}


def _plan_key(p: "SvdPlan") -> tuple:
    return (p.config, p.shape, jnp.dtype(p.dtype), p.mesh)


def pin(p: "SvdPlan") -> None:
    """Exempt a plan from LRU eviction (a service's warmed bucket set
    must survive cache pressure from other tenants).  Idempotent; the
    plan re-enters the cache if it was already evicted."""
    key = _plan_key(p)
    _PLANS.setdefault(key, p)
    _PINNED.add(key)


def unpin(p: "SvdPlan") -> None:
    """Return a pinned plan to normal LRU lifetime.  Idempotent."""
    _PINNED.discard(_plan_key(p))


def set_plan_cache_capacity(n: int) -> int:
    """Set the LRU bound (returns the previous one), evicting now if the
    cache is over it.  Pinned plans never count toward eviction order
    but do occupy ``size`` — capacity below the pinned count keeps every
    pin and nothing else."""
    global _PLANS_MAX
    if n < 1:
        raise ValueError(f"plan cache capacity must be >= 1, got {n}")
    prev, _PLANS_MAX = _PLANS_MAX, int(n)
    _evict()
    return prev


def _evict() -> None:
    over = len(_PLANS) - _PLANS_MAX
    if over <= 0:
        return
    for key in list(_PLANS):  # OrderedDict: least-recently-used first
        if over <= 0:
            break
        if key in _PINNED:
            continue
        del _PLANS[key]
        _STATS["evictions"] += 1
        over -= 1


def clear_plan_cache() -> None:
    """Drop all cached plans (and their compiled executables), pins
    included.  Does not reset counters — they are monotonic."""
    _PLANS.clear()
    _PINNED.clear()


@dataclasses.dataclass(frozen=True)
class PlanResolution:
    """Everything a spec's ``plan_fn`` may bind static kwargs from."""

    method: str
    mode: str
    eig_method: str
    m: int
    n: int
    dtype: Any
    r: Optional[int]
    l0: Optional[float]
    kappa: Optional[float]  # resolved hint (config.kappa, 1/l0, or None)
    max_iters: Optional[int]
    qr_mode: Optional[str]   # None -> backend default
    qr_iters: Optional[int]  # None -> backend default
    nb: int
    # grouped (Alg. 3) mesh factorization ndev = r * sep: the intra-group
    # distribution degree (size of the mesh's "sep" axis; 1 otherwise)
    sep: int = 1
    # the config's compute_dtype resolved to a jnp.dtype (None: compute
    # in the plan dtype).  plan_fns that gate on precision — e.g. the
    # Pallas envelope check — must key on this, not ``dtype``: it names
    # the precision the kernels actually see.
    compute_dtype: Any = None


# config knobs routed through plan_fn, and the output keys that count as
# consuming them (a schedule subsumes the bounds it was built from; the
# dynamic drivers bind l0 as their l= override and qr_mode as the peeled
# first iteration's first_mode=)
_KNOB_CONSUMED_AS = {
    "r": ("r", "schedule"),
    "l0": ("l0", "l", "schedule"),
    "max_iters": ("max_iters", "schedule"),
    "qr_mode": ("qr_mode", "first_mode"),
    "qr_iters": ("qr_iters",),
}


def _capability_ok(spec, mode: str, runtime_l0: bool = False) -> bool:
    # auto never picks reference oracles or comparison baselines — they
    # stay reachable by explicit method= only
    if spec.is_oracle or spec.baseline:
        return False
    if runtime_l0 and not spec.dynamic:
        # the in-graph bound estimate needs a runtime-conditioning
        # backend in every mode (a grouped static schedule cannot
        # consume a bound that only exists at execution time)
        return False
    if mode == "grouped":
        return spec.supports_grouped
    if spec.requires_mesh:
        return False
    return spec.dynamic if mode == "dynamic" else not spec.dynamic


def _dynamic_methods(mesh_bound: bool) -> list:
    """Registered dynamic backends, restricted to grouped-capable ones
    when the caller's plan is mesh-bound — an error message listing
    methods the mesh could never run would send the caller in circles."""
    names = [n for n in _registry.list_polar()
             if _registry.get_polar(n).dynamic]
    if mesh_bound:
        return [n for n in names
                if _registry.get_polar(n).supports_grouped]
    # no mesh: a grouped-only backend is equally unreachable
    return [n for n in names if not _registry.get_polar(n).requires_mesh]


def _select_method(mode: str, m: int, n: int, r_hint: int,
                   kappa: float, dtype=None, sep: int = 1,
                   runtime_l0: bool = False, comm_flops_per_word=None):
    """method="auto": capability filter, then cheapest by ``flops_fn``.

    ``sep`` is the grouped mesh's intra-group distribution degree: the
    cost model divides each group's Gram/solve work by it (plus a psum
    communication term), so auto scoring ranks grouped backends by their
    true per-device critical path on the (r, sep) mesh.
    ``runtime_l0`` restricts candidates to dynamic backends (the
    l0_policy="runtime" bound only exists at execution time), and
    ``comm_flops_per_word`` threads a calibrated psum cost
    (``SvdConfig.extra``; see ``benchmarks/comm_calibrate.py``) into
    every cost model.
    """
    cands = [_registry.get_polar(name) for name in _registry.list_polar()]
    cands = [s for s in cands if _capability_ok(s, mode, runtime_l0)]
    if not cands:
        raise ValueError(f"no registered polar backend supports "
                         f"mode={mode!r}" +
                         (" with l0_policy='runtime'" if runtime_l0
                          else ""))
    comm_kw = ({} if comm_flops_per_word is None
               else {"comm_flops_per_word": comm_flops_per_word})

    def score(spec):
        if spec.flops_fn is None:
            return (1, 0.0, spec.name)  # unranked: after every costed spec
        flops = float(spec.flops_fn(m, n, r=r_hint, kappa=kappa,
                                    grouped=(mode == "grouped"),
                                    dtype=dtype, sep=sep, **comm_kw))
        if mode == "grouped":
            flops /= max(r_hint, 1)  # per-group critical path
        return (0, flops, spec.name)

    return min(cands, key=score)


def _validate_capability(spec, mode: str, config: SvdConfig,
                         mesh_bound: bool = False) -> None:
    if mode == "grouped":
        if not spec.supports_grouped:
            grouped = [n for n in _registry.list_polar()
                       if _registry.get_polar(n).supports_grouped]
            raise ValueError(
                f"polar method {spec.name!r} does not support grouped "
                f"(mesh=) execution; grouped-capable methods: {grouped}")
        if config.l0_policy == "runtime" and not spec.dynamic:
            raise ValueError(
                f"l0_policy='runtime' estimates the bound in-graph, "
                f"which needs a runtime-conditioning backend; "
                f"{spec.name!r} binds a trace-time schedule "
                f"(grouped-capable dynamic methods: "
                f"{_dynamic_methods(mesh_bound=True)})")
        return
    if spec.requires_mesh:
        raise ValueError(f"polar method {spec.name!r} runs grouped only; "
                         f"pass mesh=zolo_group_mesh(r)")
    if mode == "dynamic" and not spec.dynamic and not spec.is_oracle:
        raise ValueError(
            f"polar method {spec.name!r} has a trace-time schedule; "
            f"mode='dynamic' needs a runtime-conditioning backend "
            f"(registered dynamic methods: "
            f"{_dynamic_methods(mesh_bound)})")
    if mode == "static" and spec.dynamic and config.mode != "auto":
        raise ValueError(
            f"polar method {spec.name!r} is a dynamic (runtime "
            f"conditioning) backend; mode='static' needs a trace-time "
            f"schedule — use mode='dynamic' or 'auto'")
    if config.l0_policy == "runtime" and not spec.dynamic:
        raise ValueError(
            f"l0_policy='runtime' estimates the bound in-graph, which "
            f"needs a dynamic backend; {spec.name!r} is static "
            f"(registered dynamic methods: "
            f"{_dynamic_methods(mesh_bound)})")


def _resolve(config: SvdConfig, shape, dtype, mesh):
    m, n = shape
    explicit = (None if config.method == "auto"
                else _registry.get_polar(config.method))
    eig_spec = _registry.get_eig(config.eig_method)  # fail fast on typos

    # --- mode ---------------------------------------------------------
    mode = config.mode
    if mode == "auto":
        if mesh is not None:
            mode = "grouped"
        elif explicit is not None:
            mode = "dynamic" if explicit.dynamic else "static"
        elif config.l0_policy == "runtime":
            mode = "dynamic"
        else:
            mode = "static"
    if mode == "grouped" and mesh is None:
        raise ValueError("mode='grouped' needs mesh=zolo_group_mesh(r)")
    if mode != "grouped" and mesh is not None:
        raise ValueError(f"mesh= implies grouped execution but "
                         f"mode={mode!r}; use mode='grouped' or 'auto'")

    # --- l0 / kappa ---------------------------------------------------
    l0 = config.l0
    if l0 is None and config.l0_policy == "estimate_at_plan":
        if config.kappa is None:
            raise ValueError("l0_policy='estimate_at_plan' derives l0 "
                             "from the conditioning; set SvdConfig.kappa")
        l0 = 0.9 / float(config.kappa)
    kappa = config.kappa
    if kappa is None and l0 is not None:
        kappa = 1.0 / float(l0)
    kappa_eff = kappa if kappa is not None else 1e6  # scoring default

    # --- r / sep (paper Table 1 via choose_r, or the mesh's (r, sep)
    #     factorization of the device count) ---------------------------
    r = config.r
    sep = 1
    if mode == "grouped":
        mesh_r = None
        try:
            mesh_r = int(mesh.shape["zolo"])
        except Exception:
            pass  # capability check below rejects non-grouped specs
        try:
            sep = int(mesh.shape["sep"])
        except Exception:
            sep = 1  # custom mesh without an intra-group axis
        if mesh_r is not None and mesh_r * sep != mesh.size:
            raise ValueError(
                f"grouped execution lays ndev = r * sep out as the "
                f"('zolo', 'sep') factorization; mesh axes "
                f"{dict(mesh.shape)} do not factor its {mesh.size} "
                f"devices — build the mesh with zolo_group_mesh(r)")
        if r is None:
            r = mesh_r
        elif mesh_r is not None and mesh_r != r:
            raise ValueError(f"config.r={r} but the mesh 'zolo' axis has "
                             f"size {mesh_r}")
        if sep > 1 and config.qr_mode == "householder" and \
                (config.qr_iters is None or config.qr_iters > 0):
            # fail at plan time, not at first execution: the structured
            # Householder first iteration needs the full iterate on
            # every device (see grouped_zolo_pd_static)
            raise ValueError(
                f"qr_mode='householder' is not row-distributable over "
                f"the sep={sep} intra-group axis; use a sep=1 mesh "
                f"(r == ndev) or qr_mode='cholqr2'")
    elif r is None and kappa is not None:
        r = _coeffs.choose_r(kappa_eff)

    # --- method -------------------------------------------------------
    # comm_flops_per_word is a cost-model calibration (see
    # benchmarks/comm_calibrate.py), not a backend kwarg: it is consumed
    # here, at scoring time, and never reaches the driver
    comm_word = dict(config.extra).get("comm_flops_per_word")
    # scoring (and envelope) precision is the one the backend computes
    # in: compute_dtype when the config sets one, the plan dtype
    # otherwise — a bf16 compute plan over f32 inputs must be priced
    # (and envelope-capped) as bf16
    compute_dtype = (jnp.dtype(config.compute_dtype)
                     if config.compute_dtype is not None else None)
    score_dtype = compute_dtype if compute_dtype is not None else dtype
    if explicit is not None:
        spec = explicit
    else:
        spec = _select_method(mode, m, n,
                              r or _coeffs.choose_r(kappa_eff), kappa_eff,
                              dtype=score_dtype, sep=sep,
                              runtime_l0=(config.l0_policy == "runtime"),
                              comm_flops_per_word=comm_word)
    _validate_capability(spec, mode, config, mesh_bound=(mesh is not None))

    res = PlanResolution(method=spec.name, mode=mode,
                         eig_method=eig_spec.name, m=m, n=n, dtype=dtype,
                         r=r, l0=l0, kappa=kappa,
                         max_iters=config.max_iters,
                         qr_mode=config.qr_mode, qr_iters=config.qr_iters,
                         nb=config.nb, sep=sep,
                         compute_dtype=compute_dtype)

    # --- static kwargs -------------------------------------------------
    # extras pass through verbatim (a kwarg a backend does not accept
    # still reaches it and fails loudly, as a direct call would); config
    # knobs flow through the spec's plan_fn, which re-emits what the
    # backend takes (possibly under another name — l0 becomes a
    # schedule).  An explicitly-set knob the plan_fn does not consume is
    # a configuration error, reported here instead of being dropped.
    backend_kwargs = dict(config.extra)
    backend_kwargs.pop("comm_flops_per_word", None)  # scoring-only knob
    if spec.plan_fn:
        emitted = dict(spec.plan_fn(res))
        for knob, aliases in _KNOB_CONSUMED_AS.items():
            if getattr(config, knob) is not None and \
                    not any(a in emitted for a in aliases):
                raise ValueError(
                    f"polar method {spec.name!r} does not use {knob}=; "
                    f"its plan binds {sorted(emitted)}")
        backend_kwargs.update(emitted)
    else:
        # no plan_fn: explicitly-set knobs pass to the backend verbatim
        for knob in _KNOB_CONSUMED_AS:
            value = getattr(config, knob)
            if value is not None:
                backend_kwargs.setdefault(knob, value)
    eig_kwargs = {"nb": res.nb}
    if eig_spec.plan_fn:
        eig_kwargs.update(eig_spec.plan_fn(res))
    return spec, eig_spec, res, backend_kwargs, eig_kwargs


@dataclasses.dataclass(frozen=True, eq=False, repr=False)
class SvdPlan:
    """A bound solver: resolved config + precomputed schedule + compiled
    executables for one (shape, dtype, config, mesh).

    ``svd(a)`` / ``polar(a, want_h=)`` execute the 2-D problem the plan
    was built for; ``svd_batched`` / ``polar_batched`` vmap the same
    executable over leading axes (not available for grouped plans).  All
    entry points run through a per-plan jit cache, so the second call at
    the planned shape performs zero retraces.
    """

    config: SvdConfig
    shape: Tuple[int, int]
    dtype: Any
    mesh: Any
    resolution: PlanResolution
    _spec: Any
    _eig_spec: Any
    _backend_kwargs: Dict[str, Any]
    _eig_kwargs: Dict[str, Any]
    _exec: Dict[Any, Any] = dataclasses.field(default_factory=dict)

    # --- introspection ------------------------------------------------

    @property
    def method(self) -> str:
        return self.resolution.method

    @property
    def mode(self) -> str:
        return self.resolution.mode

    @property
    def r(self) -> Optional[int]:
        return self.resolution.r

    @property
    def sep(self) -> int:
        """Intra-group distribution degree of the grouped mesh (size of
        its "sep" axis; 1 for non-grouped plans): the recorded (r, sep)
        factorization is ndev = plan.r * plan.sep."""
        return self.resolution.sep

    @property
    def l0(self) -> Optional[float]:
        return self.resolution.l0

    @property
    def eig_method(self) -> str:
        return self.resolution.eig_method

    @property
    def schedule(self):
        """The precomputed trace-time schedule bound by the spec's
        ``plan_fn`` (None for dynamic backends)."""
        return self._backend_kwargs.get("schedule")

    @property
    def flops_estimate(self) -> Optional[float]:
        """Flop estimate from the spec's ``flops_fn``, on the same basis
        ``method="auto"`` scores with: total serial flops, or the
        per-group (per-device, for sep > 1 meshes) critical path
        (total / r with the group's work divided over sep) for grouped
        plans.  None when the backend registers no cost model."""
        if self._spec.flops_fn is None:
            return None
        res = self.resolution
        kappa = res.kappa if res.kappa is not None else 1e6
        r = res.r if res.r is not None else _coeffs.choose_r(kappa)
        grouped = self.mode == "grouped"
        comm_word = dict(self.config.extra).get("comm_flops_per_word")
        comm_kw = ({} if comm_word is None
                   else {"comm_flops_per_word": comm_word})
        score_dtype = (res.compute_dtype if res.compute_dtype is not None
                       else res.dtype)
        flops = float(self._spec.flops_fn(res.m, res.n, r=r, kappa=kappa,
                                          grouped=grouped,
                                          dtype=score_dtype, sep=res.sep,
                                          **comm_kw))
        return flops / max(r, 1) if grouped else flops

    def audit(self, *, raise_on_fail: bool = True):
        """Lower the plan's traceable impl and walk the jaxpr for graph
        invariants: psum count/axes per grouped iteration (the PR 4
        double-reduction class), f64 discipline under ``compute_dtype``,
        and no host callbacks.  Returns an
        :class:`repro.analysis.jaxpr_audit.AuditReport`; raises
        ``AuditError`` on violations unless ``raise_on_fail=False``."""
        from repro.analysis import jaxpr_audit as _audit

        return _audit.audit_plan(self, raise_on_fail=raise_on_fail)

    def __repr__(self):
        sep = f"sep={self.sep}, " if self.mode == "grouped" else ""
        return (f"SvdPlan(method={self.method!r}, mode={self.mode!r}, "
                f"r={self.r}, {sep}l0={self.l0}, shape={self.shape}, "
                f"dtype={jnp.dtype(self.dtype).name}, "
                f"eig={self.eig_method!r})")

    def _is_current(self) -> bool:
        """Cached plans go stale if their backend was re-registered."""
        try:
            return (_registry.get_polar(self.method) is self._spec
                    and _registry.get_eig(self.eig_method)
                    is self._eig_spec)
        except ValueError:
            return False

    # --- traceable implementations (shared with the back-compat
    #     wrappers in repro.core.svd, which call them uncompiled) ------

    @jax.named_scope("prescale")
    def _prescale(self, x):
        if self.config.scale == "power":
            # sharp 1.05x power-iteration bound (the ZoloMuon setting)
            alpha = 1.05 * _norms.sigma_max_power(x, iters=8) + 1e-12
        else:  # "bound": guaranteed upper bound
            alpha = _norms.sigma_max_upper(x)
        alpha = jnp.asarray(alpha)
        return (x / alpha.astype(x.dtype)).astype(x.dtype), alpha

    def _polar_canonical(self, a, want_h, extra=None):
        """Run the backend on the canonical (m >= n) orientation.

        Returns (q, h, info, transposed, alpha, out_dtype) with q/h still
        canonical and h of the *scaled* input when ``alpha`` is not None.
        """
        kw = dict(self._backend_kwargs)
        if extra:
            kw.update(extra)
        if want_h is not _UNSET:
            kw["want_h"] = want_h
        a_work, transposed = _zolo.polar_canonical(a)
        out_dtype = a_work.dtype
        if self.config.compute_dtype is not None:
            a_work = a_work.astype(jnp.dtype(self.config.compute_dtype))
        alpha = None
        if (self.config.scale != "none" and not self._spec.dynamic
                and not self._spec.is_oracle):
            # trace-time-schedule backends assume sigma_max <= 1; dynamic
            # backends estimate their own alpha in-graph
            a_work, alpha = self._prescale(a_work)
        if self.mode == "grouped":
            q, h, info = self._spec.grouped_fn(a_work, mesh=self.mesh,
                                               **kw)
        else:
            q, h, info = self._spec.fn(a_work, **kw)
        return q, h, info, transposed, alpha, out_dtype

    def _polar_impl(self, a, want_h=_UNSET, extra=None):
        q, h, info, transposed, alpha, out_dtype = \
            self._polar_canonical(a, want_h, extra)
        if h is not None and alpha is not None:
            h = h * alpha.astype(h.dtype)
        if transposed:
            if h is not None:
                # A = (Q_w H_w)^T = H_w Q_w^T; right factor
                # H = Q_w H_w Q_w^T satisfies A = Q_w^T H, H (n, n) PSD.
                h = jnp.einsum("...ik,...kl,...jl->...ij", q, h, q)
            q = jnp.swapaxes(q, -1, -2)
        q = q.astype(out_dtype)
        if h is not None:
            h = h.astype(out_dtype)
        return q, h, info

    def _svd_impl(self, a, extra=None):
        u, s, vh, _ = self._svd_impl_info(a, extra)
        return u, s, vh

    def _svd_impl_info(self, a, extra=None):
        q, h, info, transposed, alpha, out_dtype = \
            self._polar_canonical(a, True, extra)
        # lax.linalg has no sub-f32 eigensolver kernels: a bf16 compute
        # plan hands H to the eig stage at the accumulation precision
        # (no-op for f32/f64 — promote_types is the identity there)
        h = h.astype(jnp.promote_types(h.dtype, jnp.float32))
        if self.mode == "grouped":
            # the eig stage is not distributed: every device solves the
            # whole H, rather than the partitioner splitting the
            # eigensolver's loops over the mesh
            h = jax.lax.with_sharding_constraint(
                h, jax.sharding.NamedSharding(self.mesh,
                                              jax.sharding.PartitionSpec()))
        with jax.named_scope("eig"):
            w, v = self._eig_spec.fn(h, **self._eig_kwargs)
        with jax.named_scope("u_qv"):
            # HIGHEST: U is a result, and at TPU DEFAULT precision an f32
            # product runs as one bf16 pass (~2e-3 relative)
            u = jnp.einsum("...mk,...kn->...mn", q, v,
                           precision=jax.lax.Precision.HIGHEST)
            # ascending -> descending; fold any tiny negative eigenvalue's
            # sign into U so that s >= 0.
            sign = jnp.where(w < 0, -1.0, 1.0).astype(u.dtype)
            s = jnp.abs(w)
            if alpha is not None:
                s = s * alpha.astype(s.dtype)
            u = u * sign[..., None, :]
            order = jnp.argsort(-s, axis=-1)
            s = jnp.take_along_axis(s, order, axis=-1)
            u = jnp.take_along_axis(u, order[..., None, :], axis=-1)
            v = jnp.take_along_axis(v, order[..., None, :], axis=-1)
            vh = jnp.swapaxes(v, -1, -2)
            u = u.astype(out_dtype)
            s = s.astype(out_dtype)
            vh = vh.astype(out_dtype)
        if transposed:
            # a = (u s vh)^T = v s u^T
            return vh.swapaxes(-1, -2), s, jnp.swapaxes(u, -1, -2), info
        return u, s, vh, info

    def _svd_verified_impl(self, a, extra=None):
        # lazy: repro.resilience layers on repro.solver, not the reverse
        from repro.resilience import health as _rhealth

        u, s, vh, info = self._svd_impl_info(a, extra)
        return u, s, vh, _rhealth.solve_health(u, s, vh, info)

    # --- compiled execution -------------------------------------------

    def _executable(self, key, impl):
        fn = self._exec.get(key)
        if fn is None:
            def traced(a, _impl=impl):
                _STATS["traces"] += 1
                return _impl(a)

            fn = jax.jit(traced)
            self._exec[key] = fn
        return fn

    def _check(self, a, batched=False):
        shape = tuple(a.shape)
        if batched:
            ok = len(shape) >= 3 and shape[-2:] == self.shape
            expect = f"(..., {self.shape[0]}, {self.shape[1]})"
        else:
            ok = shape == self.shape
            expect = str(self.shape)
        if not ok:
            raise ValueError(
                f"plan compiled for shape {expect} got {shape}; plans "
                f"are per-shape — build another with plan(config, shape, "
                f"dtype)")
        if jnp.dtype(a.dtype) != jnp.dtype(self.dtype):
            raise ValueError(f"plan compiled for dtype "
                             f"{jnp.dtype(self.dtype).name} got "
                             f"{jnp.dtype(a.dtype).name}")

    def _batched(self, impl):
        if self.mode == "grouped":
            raise ValueError(
                "grouped (Algorithm 3) plans lay one matrix out over the "
                "('zolo', 'sep') mesh; batching is not supported — build "
                "a static/dynamic plan for batched inputs")

        def run(a):
            lead = a.shape[:-2]
            flat = a.reshape((-1,) + self.shape)
            out = jax.vmap(impl)(flat)
            return jax.tree.map(
                lambda t: t.reshape(lead + t.shape[1:]), out)

        return run

    def svd(self, a):
        """A = U diag(s) V^H (paper Alg. 2), s descending — compiled."""
        self._check(a)
        return self._executable(("svd",), self._svd_impl)(a)

    def compile_svd(self, a):
        """Trace, lower and compile ``svd`` for ``a`` ahead of its first
        call; returns the ``jax.stages.Compiled``, whose ``as_text()`` is
        the HLO the device runs.  A later ``svd(a)`` reuses it."""
        self._check(a)
        return self._executable(("svd",), self._svd_impl).lower(a).compile()

    def polar(self, a, want_h: bool = True):
        """(q, h, info) with A ~= Q H — compiled."""
        self._check(a)
        want_h = bool(want_h)
        return self._executable(
            ("polar", want_h),
            lambda x: self._polar_impl(x, want_h=want_h))(a)

    def svd_verified(self, a):
        """``svd`` plus its in-graph health — compiled.

        Returns ``(u, s, vh, health)`` with ``health`` a
        :class:`repro.resilience.health.SolveHealth` of device scalars
        (all-finite flag, ``||UᵀU - I||_F / n``, the driver's converged
        flag, and the runtime conditioning estimate), computed inside
        the same executable as the solve — one extra Gram reduction,
        no extra trace.  Judge it with
        :func:`repro.resilience.health.judge_plan`.
        """
        self._check(a)
        return self._executable(("svd_verified",),
                                self._svd_verified_impl)(a)

    def svd_batched(self, a):
        """``svd`` vmapped over leading axes of (..., m, n) — compiled."""
        self._check(a, batched=True)
        return self._executable(("svd_batched",),
                                self._batched(self._svd_impl))(a)

    def svd_batched_verified(self, a):
        """``svd_verified`` vmapped over leading axes — compiled.

        Health leaves carry the leading batch axes, so a serving layer
        triages entries individually (``jax.tree.map(lambda t: t[i],
        health)``) instead of failing a whole batch for one bad entry.
        """
        self._check(a, batched=True)
        return self._executable(("svd_batched_verified",),
                                self._batched(self._svd_verified_impl))(a)

    def polar_batched(self, a, want_h: bool = True):
        """``polar`` vmapped over leading axes — compiled (the ZoloMuon
        per-parameter-kind path)."""
        self._check(a, batched=True)
        want_h = bool(want_h)
        return self._executable(
            ("polar_batched", want_h),
            self._batched(lambda x: self._polar_impl(x,
                                                     want_h=want_h)))(a)


def plan(config: SvdConfig, shape, dtype, mesh=None) -> SvdPlan:
    """Resolve ``config`` for (shape, dtype[, mesh]) into a cached plan.

    Identical (config, shape, dtype, mesh) return the *same* plan object,
    whose compiled executables are reused — the compile-once / run-many
    contract.  A cached plan is rebuilt only if its backend registration
    changed underneath it.
    """
    if not isinstance(config, SvdConfig):
        raise TypeError(f"plan() takes an SvdConfig, got {type(config)}")
    shape = tuple(int(s) for s in shape)
    if len(shape) != 2:
        raise ValueError(f"plan() takes the 2-D problem shape (m, n), "
                         f"got {shape}; batched inputs go through "
                         f"svd_batched/polar_batched on a 2-D plan")
    dtype = jnp.dtype(dtype)
    key = (config, shape, dtype, mesh)
    cached = _PLANS.get(key)
    if cached is not None and cached._is_current():
        _STATS["plan_hits"] += 1
        _PLANS.move_to_end(key)
        return cached
    _STATS["plan_misses"] += 1
    spec, eig_spec, res, backend_kwargs, eig_kwargs = _resolve(
        config, shape, dtype, mesh)
    built = SvdPlan(config=config, shape=shape, dtype=dtype, mesh=mesh,
                    resolution=res, _spec=spec, _eig_spec=eig_spec,
                    _backend_kwargs=backend_kwargs,
                    _eig_kwargs=eig_kwargs)
    _PLANS[key] = built
    _PLANS.move_to_end(key)
    _evict()
    return built


def flops_estimate(config: SvdConfig, shape, dtype,
                   mesh=None) -> Optional[float]:
    """Cost-model score of ``config`` at (shape, dtype) without executing.

    Resolves (and caches) the plan and returns its ``flops_estimate`` —
    the same per-backend ``flops_fn`` basis ``method="auto"`` ranks
    with.  This is the strategy hook higher-level planners build on:
    :func:`repro.spectral.plan_topk` prices its "dense" strategy with
    exactly this call, so a top-k plan's sketch-vs-dense decision and
    the solver's own backend selection share one cost-model contract.
    None when the resolved backend registers no cost model.
    """
    return plan(config, shape, dtype, mesh=mesh).flops_estimate


_CONFIG_CALL_FIELDS = (("r", int), ("l0", float), ("max_iters", int),
                       ("qr_iters", int), ("qr_mode", str))


def plan_for_call(shape, dtype, *, method: str, eig_method: str = "eigh",
                  nb: int = 32, mesh=None, kw=None):
    """Back-compat bridge for ``polar_svd`` / ``polar_decompose``.

    Maps a legacy call signature onto (cached plan, runtime kwargs): the
    recognized schedule-shaping kwargs move into the config — so a
    wrapper call and a direct ``plan()`` call with the same knobs share
    one cached plan — remaining hashable kwargs ride in ``config.extra``
    verbatim, and unhashable (array-valued) kwargs plus ``want_h``
    (per-call, not configuration) are returned for the caller to pass at
    execution time, outside the cache key.  ``scale="none"`` is pinned:
    legacy callers pre-scale their input, and the wrappers preserve
    those numerics exactly.
    """
    kw = dict(kw or {})
    cfg_kw = {}
    for name, cast in _CONFIG_CALL_FIELDS:
        if kw.get(name) is not None:
            cfg_kw[name] = cast(kw.pop(name))
    runtime = {}
    if "want_h" in kw:
        runtime["want_h"] = kw.pop("want_h")
    static = {}
    for k, v in kw.items():
        try:
            hash(v)
        except TypeError:
            runtime[k] = v
        else:
            static[k] = v
    cfg = SvdConfig(method=method, eig_method=eig_method, nb=nb,
                    scale="none", extra=tuple(sorted(static.items())),
                    **cfg_kw)
    return plan(cfg, shape, dtype, mesh=mesh), runtime
