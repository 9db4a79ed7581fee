"""Jaxpr-level plan auditor — the runtime complement of the AST linter.

The linter proves what source text can prove; this module proves what
only the *traced graph* can: that a plan's compiled callables contain
exactly the collectives the (r, sep) algorithm calls for, no f64
compute in an f32-compute plan, and no host callbacks.

The psum-count contract is the PR 4 bug class made executable.  One
grouped Zolotarev iteration owes the mesh exactly:

* **one "sep" psum per distributed Gram** — ``sep_reduce_ops`` reduces
  the partial (m/sep, n) row-block product once; the CholeskyQR2 term
  does it twice (X-Gram + Q1-Gram) and its Q2-Gram must stay *local*
  (``gram_local``).  A second reduction there double-counts the Gram —
  silently wrong on a real slice, invisible on one device.
* **one "zolo" psum per iteration** — the fused weighted combine that
  *is* the next iterate.

So a static plan with schedule length I (QR-seeded for the first
``qr_iters`` iterations) owes ``sep``: ``qr_iters * cost(qr_mode) +
(I - qr_iters)`` and ``zolo``: ``I``, where cost is {householder: 0,
cholqr2: 2, chol: 1}; the dynamic driver adds its in-graph sigma_min
Gram, the peeled first iteration's compiled branches, and two
residual-norm reductions outside plus three inside the while body.
:func:`expected_grouped_psums` encodes the model,
:func:`audit_plan` checks a live plan against it, and
``SvdPlan.audit()`` / ``TopKPlan.audit()`` expose it on the plan
objects themselves.  Module-level counters feed
``SvdService.stats()["plan_audits"]``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import jax

__all__ = [
    "AuditError",
    "AuditReport",
    "audit_callable",
    "audit_plan",
    "audit_all_plans",
    "audit_stats",
    "expected_grouped_psums",
    "iter_eqns",
]

# every shard_map spelling of an all-reduce: jax.shard_map emits
# psum_invariant under check_vma=True and plain psum under check_vma=False
PSUM_PRIMS = {"psum", "psum_invariant"}
COLLECTIVE_PRIMS = PSUM_PRIMS | {
    "pmax", "pmin", "ppermute", "all_gather", "all_to_all",
    "reduce_scatter", "psum_scatter", "axis_index",
}
# f64 outputs of these primitives are *compute* in a wide dtype (the
# casts/transposes framing an f32-compute plan's f64 I/O are fine)
WIDE_COMPUTE_PRIMS = {
    "dot_general", "cholesky", "triangular_solve", "eigh", "eig", "qr",
    "lu", "svd", "householder_product", "integer_pow", "erf_inv",
    "pallas_call", "add", "sub", "mul", "div", "sqrt", "rsqrt", "exp",
    "log", "reduce_sum", "reduce_max", "reduce_min",
}
# one distributed-Gram "sep" psum per shared-Gram Cholesky term, two for
# the CholeskyQR2 term (X-Gram + Q1-Gram; the Q2-Gram is gram_local and
# owes NO reduction), none for structured Householder QR
MODE_SEP_PSUMS = {"chol": 1, "cholqr2": 2, "householder": 0}

_STATS = {"audited": 0, "passed": 0, "failed": 0}


def audit_stats() -> Dict[str, int]:
    """Monotonic audit counters (consumed by ``SvdService.stats()``)."""
    return dict(_STATS)


def reset_audit_stats() -> None:
    for k in _STATS:
        _STATS[k] = 0


class AuditError(RuntimeError):
    """A plan's traced graph violates a structural invariant."""

    def __init__(self, report: "AuditReport"):
        self.report = report
        lines = "\n  ".join(report.violations)
        super().__init__(
            f"plan audit failed for {report.entry}:\n  {lines}")


@dataclasses.dataclass
class AuditReport:
    """What one lowering revealed."""

    entry: str
    psum_counts: Dict[str, int]
    axis_names: Tuple[str, ...]       # every collective axis seen
    wide_compute: int                 # f64/c128 compute eqns found
    callbacks: Tuple[str, ...]
    checks: List[str]
    violations: List[str]

    @property
    def ok(self) -> bool:
        return not self.violations


def iter_eqns(jaxpr) -> Iterable[Any]:
    """Every eqn of ``jaxpr`` and all nested sub-jaxprs (pjit bodies,
    while/cond/scan branches, shard_map bodies, pallas kernels)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            items = val if isinstance(val, (list, tuple)) else (val,)
            for item in items:
                inner = getattr(item, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    yield from iter_eqns(inner)
                elif hasattr(item, "eqns"):
                    yield from iter_eqns(item)


def _collective_axes(eqn) -> Tuple[str, ...]:
    if eqn.primitive.name not in COLLECTIVE_PRIMS:
        return ()
    for key in ("axes", "axis_name", "axis_names"):
        val = eqn.params.get(key)
        if val is None:
            continue
        if isinstance(val, str):
            return (val,)
        return tuple(a for a in val if isinstance(a, str))
    return ()


def _is_wide(aval) -> bool:
    dtype = getattr(aval, "dtype", None)
    return dtype is not None and dtype in ("float64", "complex128")


def audit_callable(
    fn,
    args: Sequence[Any],
    *,
    entry: str = "callable",
    mesh_axes: Sequence[str] = (),
    expect_psums: Optional[Dict[str, int]] = None,
    allow_collectives: bool = True,
    forbid_wide_compute: bool = False,
    raise_on_fail: bool = True,
) -> AuditReport:
    """Trace ``fn(*args)`` and walk the jaxpr for invariant violations.

    ``args`` are abstract (``jax.ShapeDtypeStruct``) or concrete inputs.
    ``mesh_axes`` is the set of legally-bound collective axis names;
    ``expect_psums`` the exact per-axis all-reduce budget (None skips the
    count check); ``allow_collectives=False`` asserts a collective-free
    graph (the non-grouped contract); ``forbid_wide_compute`` rejects
    f64/c128 arithmetic (the compute_dtype<=f32 contract).
    """
    closed = jax.make_jaxpr(fn)(*args)
    counts: Dict[str, int] = {}
    seen_axes: List[str] = []
    callbacks: List[str] = []
    wide = 0
    violations: List[str] = []
    checks: List[str] = []
    mesh_axes = tuple(mesh_axes)

    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        axes = _collective_axes(eqn)
        if name in PSUM_PRIMS:
            for ax in axes:
                counts[ax] = counts.get(ax, 0) + 1
        if axes:
            for ax in axes:
                if ax not in seen_axes:
                    seen_axes.append(ax)
                if ax not in mesh_axes:
                    violations.append(
                        f"{name} over axis {ax!r} which is not bound by "
                        f"the plan's mesh (axes: {list(mesh_axes)})")
        elif name in COLLECTIVE_PRIMS and not allow_collectives:
            violations.append(f"collective {name} in a non-grouped graph")
        if "callback" in name or name == "outside_call":
            callbacks.append(name)
            violations.append(
                f"host callback primitive {name!r} in the compiled path "
                f"(breaks async dispatch and device-only serving)")
        if forbid_wide_compute and name in WIDE_COMPUTE_PRIMS:
            if any(_is_wide(v.aval) for v in eqn.outvars):
                wide += 1

    if seen_axes and not allow_collectives:
        violations.append(
            f"collectives over {seen_axes} in a graph that owes none")
    checks.append("collective-axis-validity")
    checks.append("no-host-callbacks")

    if forbid_wide_compute:
        checks.append("no-f64-compute")
        if wide:
            violations.append(
                f"{wide} f64/c128 compute eqn(s) in an f32-compute plan "
                f"(the compute_dtype cast is leaking)")

    if expect_psums is not None:
        checks.append("psum-count")
        for ax, want in expect_psums.items():
            got = counts.get(ax, 0)
            if got != want:
                hint = ("a Gram is reduced twice — the gram_local "
                        "double-psum class" if got > want
                        else "a reduction is missing — a partial Gram "
                        "or combine never left its shard")
                violations.append(
                    f"expected {want} {ax!r}-axis psum(s), found {got} "
                    f"({hint})")
        for ax in counts:
            if ax not in expect_psums:
                violations.append(
                    f"unbudgeted psum axis {ax!r} ({counts[ax]} eqn(s))")

    report = AuditReport(
        entry=entry,
        psum_counts=counts,
        axis_names=tuple(seen_axes),
        wide_compute=wide,
        callbacks=tuple(callbacks),
        checks=checks,
        violations=violations,
    )
    _STATS["audited"] += 1
    _STATS["passed" if report.ok else "failed"] += 1
    if raise_on_fail and not report.ok:
        raise AuditError(report)
    return report


def expected_grouped_psums(
    method: str,
    backend_kwargs: Dict[str, Any],
    *,
    sep: int = 1,
) -> Optional[Dict[str, int]]:
    """Per-axis all-reduce budget of one grouped plan's whole graph, or
    None when ``method`` is not a modelled grouped backend (the audit
    then still checks axis validity, just not counts).

    Counts are *static over the lowered jaxpr* — every compiled branch
    of the dynamic driver's peeled first iteration contributes, whether
    or not it executes.
    """
    if method == "zolo_grouped":
        sched = backend_kwargs.get("schedule") or ()
        iters = len(sched)
        if not iters:
            return None
        qr_mode = backend_kwargs.get("qr_mode", "cholqr2")
        qr_iters = min(int(backend_kwargs.get("qr_iters", 1)), iters)
        return {
            "sep": qr_iters * MODE_SEP_PSUMS[qr_mode]
            + (iters - qr_iters) * MODE_SEP_PSUMS["chol"],
            "zolo": iters,
        }
    if method == "zolo_grouped_dynamic":
        # in-graph sigma_min bound (skipped when the plan pinned l)
        est = 0 if "l" in backend_kwargs else 1
        first_mode = backend_kwargs.get("first_mode", "auto")
        if first_mode == "auto":
            # three compiled branches; structured Householder QR is only
            # row-distributable at sep == 1, else the extreme-regime
            # branch substitutes shifted CholeskyQR2
            hh = ("householder" if sep == 1 else "cholqr2")
            first_sep = (MODE_SEP_PSUMS[hh] + MODE_SEP_PSUMS["cholqr2"]
                         + MODE_SEP_PSUMS["chol"])
            first_zolo = 3
        else:
            first_sep = MODE_SEP_PSUMS[first_mode]
            first_zolo = 1
        # + 1 fused fnorm_pair psum for the peeled residual (the two
        # residual-rule norms ride one length-2 all-reduce; see
        # sep_reduce_ops.fnorm_pair), + (1 Gram + 1 fnorm_pair) per
        # while-loop body, + 1 "zolo" combine in the body
        return {
            "sep": est + first_sep + 1 + 2,
            "zolo": first_zolo + 1,
        }
    return None


def _effective_compute_is_narrow(plan) -> bool:
    """True when the plan's factorization dtype is <= f32 — the regime
    where any f64 compute eqn is a leak."""
    import jax.numpy as jnp

    compute = getattr(getattr(plan, "config", None), "compute_dtype", None)
    dtype = jnp.dtype(compute) if compute is not None else jnp.dtype(plan.dtype)
    return jnp.issubdtype(dtype, jnp.floating) and dtype.itemsize <= 4


def audit_plan(plan, *, raise_on_fail: bool = True) -> AuditReport:
    """Audit a live ``SvdPlan`` or ``TopKPlan`` by lowering its traceable
    impl and walking the jaxpr.  Duck-typed: an SvdPlan exposes
    ``_svd_impl`` (richest graph: backend + H + eig stage), a TopKPlan
    ``_impl``."""
    if not hasattr(plan, "_svd_impl") and not hasattr(plan, "_impl"):
        raise TypeError(
            f"audit_plan: {type(plan).__name__} exposes neither _svd_impl "
            f"nor _impl — not a plan object")
    shape = tuple(plan.shape)
    spec = jax.ShapeDtypeStruct(shape, plan.dtype)
    narrow = _effective_compute_is_narrow(plan)

    if hasattr(plan, "_svd_impl"):
        grouped = getattr(plan, "mode", None) == "grouped"
        mesh = getattr(plan, "mesh", None)
        mesh_axes = tuple(mesh.axis_names) if mesh is not None else ()
        expect = None
        if grouped:
            expect = expected_grouped_psums(
                plan.method, plan._backend_kwargs, sep=plan.sep)
        return audit_callable(
            plan._svd_impl, (spec,),
            entry=f"SvdPlan[{plan.method}, {shape}, "
                  f"{jax.numpy.dtype(plan.dtype).name}]",
            mesh_axes=mesh_axes,
            expect_psums=expect,
            allow_collectives=grouped,
            forbid_wide_compute=narrow,
            raise_on_fail=raise_on_fail,
        )
    return audit_callable(
        plan._impl, (spec,),
        entry=f"TopKPlan[{plan.strategy}, {shape}, "
              f"k={plan.config.k}]",
        mesh_axes=(),
        expect_psums=None,
        allow_collectives=False,
        forbid_wide_compute=narrow,
        raise_on_fail=raise_on_fail,
    )


def audit_all_plans(raise_on_fail: bool = False):
    """Audit every plan currently held by the solver and spectral plan
    caches (the pytest fixture's hook: whatever the suite built gets
    walked).  Returns ``[(entry, violations)]`` for the failures."""
    from repro.solver import planner as _planner
    from repro.spectral import topk as _topk

    failures: List[Tuple[str, List[str]]] = []
    plans = (list(_planner._PLANS.values())
             + list(_topk._TOPK_PLANS.values()))
    for plan in plans:
        try:
            report = audit_plan(plan, raise_on_fail=False)
        except Exception as e:  # un-lowerable (e.g. mesh devices gone)
            failures.append((repr(plan), [f"audit could not lower: {e}"]))
            continue
        if not report.ok:
            failures.append((report.entry, report.violations))
    if raise_on_fail and failures:
        raise RuntimeError(f"plan audits failed: {failures}")
    return failures
