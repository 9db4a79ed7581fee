"""repro.analysis: the AST invariant linter (per-rule good/bad fixtures,
suppressions, the baseline lifecycle, the CLI) and the jaxpr plan auditor
(dense/grouped/top-k green paths, the seeded double-psum regression, and
the SvdService stats wiring)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

import jax.numpy as jnp

import repro.solver as S
from repro.analysis import all_rules, run_lint, write_baseline
from repro.analysis import jaxpr_audit as JA
from repro.dist import zolo_group_mesh
from repro.serve import ServiceConfig, SvdService
from repro.spectral import TopKConfig, plan_topk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint(tmp_path, source, rule, baseline=None):
    """Lint one dedented fixture snippet with a single rule."""
    f = tmp_path / "fixture.py"
    f.write_text(textwrap.dedent(source))
    return run_lint([str(f)], rules=[rule], baseline=baseline)


# --- per-rule fixtures: each bad snippet is the historical bug ------------


def test_rule_registry_complete():
    assert set(all_rules()) == {
        "collective-axis", "accum-dtype", "plan-key-hygiene",
        "retrace-hazard", "bare-assert", "keyerror-dispatch",
        "kernel-accum-envelope"}
    for rule in all_rules().values():
        assert rule.doc  # every rule documents its bug class


def test_collective_axis_flags_undeclared_literal(tmp_path):
    res = lint(tmp_path, """
        import jax
        AXIS_NAMES = ("zolo", "sep")
        def f(x):
            return jax.lax.psum(x, "spe")  # typo for "sep"
        """, "collective-axis")
    assert len(res.findings) == 1
    assert "'spe'" in res.findings[0].message
    assert "sep" in res.findings[0].message  # names the known axes


def test_collective_axis_accepts_declared_axes(tmp_path):
    res = lint(tmp_path, """
        import jax
        from jax.sharding import Mesh
        def make(devs):
            return Mesh(devs, ("zolo", "sep"))
        def f(x):
            return jax.lax.psum(x, "sep") + jax.lax.axis_index("zolo")
        def g(x, axis="sep"):  # parameter default also declares
            return jax.lax.psum(x, axis)
        """, "collective-axis")
    assert res.findings == []


def test_collective_axis_check_rep_needs_justification(tmp_path):
    bad = lint(tmp_path, """
        import jax
        def run(f, mesh, specs):
            return jax.shard_map(f, mesh=mesh, in_specs=specs,
                                 out_specs=specs, check_vma=False)
        """, "collective-axis")
    assert len(bad.findings) == 1
    assert "check_vma" in bad.findings[0].message
    good = lint(tmp_path, """
        import jax
        def run(f, mesh, specs):
            # check_vma=False: the rep checker rejects the one-hot xw
            # combine; the psum budget is enforced by the jaxpr audit
            return jax.shard_map(f, mesh=mesh, in_specs=specs,
                                 out_specs=specs, check_vma=False)
        """, "collective-axis")
    assert good.findings == []


def test_accum_dtype_flags_unpinned_gram(tmp_path):
    res = lint(tmp_path, """
        import jax.numpy as jnp
        def gram_chol(x):
            g = jnp.einsum("mk,mn->kn", x, x)
            return jnp.linalg.cholesky(g)
        """, "accum-dtype")
    assert len(res.findings) == 1
    assert "einsum" in res.findings[0].message
    assert "preferred_element_type" in res.findings[0].message


def test_accum_dtype_accepts_pinned_or_sinkless(tmp_path):
    res = lint(tmp_path, """
        import jax.numpy as jnp
        def gram_chol(x):
            g = jnp.einsum("mk,mn->kn", x, x,
                           preferred_element_type=jnp.float32)
            return jnp.linalg.cholesky(g.astype(x.dtype))
        def plain_product(x):  # no factorization sink: not a Gram
            return jnp.matmul(x, x.T)
        """, "accum-dtype")
    assert res.findings == []


def test_plan_key_hygiene_flags_mutable_config(tmp_path):
    res = lint(tmp_path, """
        import dataclasses
        from typing import List
        @dataclasses.dataclass
        class SolveConfig:
            sizes: List[int]
        """, "plan-key-hygiene")
    msgs = [f.message for f in res.findings]
    assert len(msgs) == 2
    assert any("frozen" in m for m in msgs)
    assert any("sizes" in m for m in msgs)


def test_plan_key_hygiene_accepts_frozen_tuple_config(tmp_path):
    res = lint(tmp_path, """
        import dataclasses
        from typing import Tuple
        @dataclasses.dataclass(frozen=True)
        class SolveConfig:
            sizes: Tuple[int, ...] = ()
        @dataclasses.dataclass
        class _ScratchConfig:  # private: not a cache key
            buf: list = None
        @dataclasses.dataclass
        class Runner:  # not *Config/*Policy/*Key-suffixed
            log: list = None
        """, "plan-key-hygiene")
    assert res.findings == []


def test_retrace_hazard_flags_traced_branch_and_coercion(tmp_path):
    res = lint(tmp_path, """
        import jax
        @jax.jit
        def f(x, n):
            if n > 2:
                return float(x)
            return x
        """, "retrace-hazard")
    msgs = [f.message for f in res.findings]
    assert len(msgs) == 2
    assert any("Python `if`" in m for m in msgs)
    assert any("float()" in m for m in msgs)


def test_retrace_hazard_respects_static_argnames(tmp_path):
    res = lint(tmp_path, """
        import functools
        import jax
        @functools.partial(jax.jit, static_argnames=("n",))
        def g(x, n):
            if n > 2:          # n is static: branch is fine
                return x * 2
            if x.ndim > 2:     # .ndim/.shape are static attributes
                return x.sum()
            return x
        """, "retrace-hazard")
    assert res.findings == []


def test_bare_assert_flagged(tmp_path):
    res = lint(tmp_path, """
        def f(x):
            assert x > 0
            return x
        """, "bare-assert")
    assert len(res.findings) == 1
    assert "-O" in res.findings[0].message


def test_keyerror_dispatch_flags_unguarded_table(tmp_path):
    bad = lint(tmp_path, """
        TABLE = {"zolo": 1, "qdwh": 2}
        def pick(name):
            return TABLE[name]
        """, "keyerror-dispatch")
    assert len(bad.findings) == 1
    assert "TABLE[name]" in bad.findings[0].message
    good = lint(tmp_path, """
        TABLE = {"zolo": 1, "qdwh": 2}
        def pick(name):
            if name not in TABLE:
                raise ValueError(f"unknown {name!r}; known: {sorted(TABLE)}")
            return TABLE[name]
        """, "keyerror-dispatch")
    assert good.findings == []


# --- engine mechanics: suppression, baseline lifecycle, CLI ---------------


def test_inline_suppression(tmp_path):
    res = lint(tmp_path, """
        def f(x):
            # repro-lint: disable=bare-assert -- test-only helper
            assert x > 0
            return x
        """, "bare-assert")
    assert res.findings == [] and res.suppressed == 1


def test_baseline_lifecycle(tmp_path):
    src = "def f(x):\n    assert x > 0\n    return x\n"
    fix = tmp_path / "mod.py"
    fix.write_text(src)
    base = tmp_path / "baseline.json"

    first = run_lint([str(fix)], rules=["bare-assert"])
    assert len(first.findings) == 1
    write_baseline(str(base), first.findings)

    # baselined finding rides; nothing new fails
    second = run_lint([str(fix)], rules=["bare-assert"], baseline=str(base))
    assert second.ok and second.findings == [] and len(second.baselined) == 1

    # a NEW violation still fails against the same baseline
    fix.write_text(src + "\ndef g(y):\n    assert y\n    return y\n")
    third = run_lint([str(fix)], rules=["bare-assert"], baseline=str(base))
    assert not third.ok and len(third.findings) == 1

    # fixing the original flags its baseline entry as stale
    fix.write_text("def f(x):\n    return x\n")
    fourth = run_lint([str(fix)], rules=["bare-assert"], baseline=str(base))
    assert fourth.ok and fourth.stale_baseline == [
        first.findings[0].fingerprint()]


def test_fingerprint_is_line_independent(tmp_path):
    fix = tmp_path / "mod.py"
    fix.write_text("def f(x):\n    assert x\n    return x\n")
    a = run_lint([str(fix)], rules=["bare-assert"]).findings[0]
    fix.write_text("\n\n\ndef f(x):\n    assert x\n    return x\n")
    b = run_lint([str(fix)], rules=["bare-assert"]).findings[0]
    assert a.line != b.line and a.fingerprint() == b.fingerprint()


def _run_cli(args):
    env = {**os.environ, "PYTHONPATH": os.path.join(ROOT, "src")}
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=120)


def test_cli_json_and_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def f(x):\n    assert x\n    return x\n")
    out = _run_cli([str(bad), "--format=json"])
    assert out.returncode == 1, out.stderr
    data = json.loads(out.stdout)
    assert data["ok"] is False and data["files"] == 1
    assert data["findings"][0]["rule"] == "bare-assert"

    good = tmp_path / "good.py"
    good.write_text("def f(x):\n    return x\n")
    out = _run_cli([str(good), "--format=json"])
    assert out.returncode == 0, out.stdout + out.stderr
    assert json.loads(out.stdout)["ok"] is True

    out = _run_cli(["--list-rules"])
    assert out.returncode == 0
    assert "collective-axis" in out.stdout and "bare-assert" in out.stdout


def test_source_tree_is_lint_clean():
    """The acceptance criterion: the shipped tree carries zero findings
    (every historical violation was fixed, not baselined away)."""
    res = run_lint([os.path.join(ROOT, "src", "repro")])
    assert res.errors == []
    assert res.findings == [], "\n".join(f.render() for f in res.findings)
    assert res.files > 50  # sanity: the walk actually saw the tree


# --- jaxpr plan auditor ---------------------------------------------------


def test_expected_psum_model():
    # static: qr_iters * cost(qr_mode) + (I - qr_iters) Grams, I combines
    st = JA.expected_grouped_psums(
        "zolo_grouped",
        {"schedule": (0.0,) * 5, "qr_mode": "cholqr2", "qr_iters": 1})
    assert st == {"sep": 6, "zolo": 5}
    hh = JA.expected_grouped_psums(
        "zolo_grouped", {"schedule": (0.0,) * 3, "qr_mode": "householder"})
    assert hh == {"sep": 2, "zolo": 3}
    # dynamic: in-graph estimate + peeled 3-branch first iter + residuals
    # (each residual is ONE fused fnorm_pair psum — two norms ride a
    # single length-2 all-reduce; body = 1 Gram + 1 fnorm_pair)
    dy = JA.expected_grouped_psums(
        "zolo_grouped_dynamic", {"first_mode": "auto"}, sep=1)
    assert dy == {"sep": 7, "zolo": 4}
    # pinned l skips the estimate Gram; sep>1 swaps householder out
    dy2 = JA.expected_grouped_psums(
        "zolo_grouped_dynamic", {"first_mode": "auto", "l": 1e-3}, sep=4)
    assert dy2 == {"sep": 8, "zolo": 4}
    assert JA.expected_grouped_psums("zolo_static", {}) is None


def test_audit_dense_plan_green():
    p = S.plan(S.SvdConfig(method="zolo_static", l0=0.9 / 1e3, r=2),
               (48, 32), jnp.float64)
    rep = p.audit()
    assert rep.ok
    assert rep.psum_counts == {} and rep.axis_names == ()
    assert rep.callbacks == ()
    assert "collective-axis-validity" in rep.checks


def test_audit_static_grouped_plan_green():
    p = S.plan(S.SvdConfig(method="zolo_grouped", kappa=9.06e3,
                           l0_policy="estimate_at_plan"),
               (64, 32), jnp.float64, mesh=zolo_group_mesh(1))
    rep = p.audit()
    assert rep.ok and "psum-count" in rep.checks
    want = JA.expected_grouped_psums(p.method, p._backend_kwargs,
                                     sep=p.sep)
    assert rep.psum_counts == want
    assert want["zolo"] == len(p.schedule)  # one combine per iteration


def test_audit_dynamic_grouped_plan_green():
    p = S.plan(S.SvdConfig(l0_policy="runtime"), (64, 32), jnp.float64,
               mesh=zolo_group_mesh(1))
    assert p.method == "zolo_grouped_dynamic"
    rep = p.audit()
    assert rep.ok and set(rep.psum_counts) == {"sep", "zolo"}


def test_audit_topk_plan_green():
    p = plan_topk(TopKConfig(k=4, kappa=1e4), (96, 48))
    rep = p.audit()
    assert rep.ok
    # non-grouped contract: a top-k graph owes the mesh nothing
    assert rep.psum_counts == {} and rep.axis_names == ()


def test_audit_rejects_double_reduced_gram(monkeypatch):
    """The PR 4 regression, reintroduced on purpose: a bundle whose
    gram_local all-reduces makes CholeskyQR2's Q2-Gram psum twice, and
    the audit must reject the plan with the double-psum diagnosis."""
    from repro.dist import grouped_ops as gops
    from repro.solver import planner as planner_mod

    real = gops.sep_reduce_ops

    def double_reduced(base=None, *, axis="sep"):
        ops = real(base, axis=axis)
        return ops._replace(gram_local=ops.gram)

    monkeypatch.setattr(gops, "sep_reduce_ops", double_reduced)
    p = S.plan(S.SvdConfig(method="zolo_grouped", kappa=3.7e3,
                           l0_policy="estimate_at_plan"),
               (64, 32), jnp.float64, mesh=zolo_group_mesh(1))
    try:
        with pytest.raises(JA.AuditError) as ei:
            p.audit()
        report = ei.value.report
        assert not report.ok
        joined = "\n".join(report.violations)
        assert "'sep'" in joined and "gram_local" in joined
        # non-raising mode returns the same report for CI tabulation
        again = p.audit(raise_on_fail=False)
        assert again.violations == report.violations
    finally:
        # drop the deliberately-broken plan so the session-end
        # audit_all_plans sweep (REPRO_AUDIT_PLANS=1) stays green
        for key in [k for k, v in planner_mod._PLANS.items() if v is p]:
            del planner_mod._PLANS[key]


def test_audit_rejects_non_plan_object():
    with pytest.raises(TypeError, match="neither _svd_impl nor _impl"):
        JA.audit_plan(object())


def test_audit_all_plans_green_after_suite():
    failures = JA.audit_all_plans(raise_on_fail=False)
    assert failures == [], failures


def test_service_stats_report_plan_audits():
    before = JA.audit_stats()
    svc = SvdService(ServiceConfig(batch_size=2, max_wait=0.0,
                                   audit_plans=True))
    svc.warmup([(48, 32)])
    audits = svc.stats()["plan_audits"]
    assert audits["audited"] >= 1 and audits["failed"] == 0
    assert audits["passed"] == audits["audited"]
    after = JA.audit_stats()  # module counters are monotonic
    assert after["audited"] - before["audited"] >= audits["audited"]


def test_service_audit_off_by_default():
    svc = SvdService(ServiceConfig(batch_size=2, max_wait=0.0))
    svc.warmup([(48, 32)])
    assert svc.stats()["plan_audits"]["audited"] == 0
