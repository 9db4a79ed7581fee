"""Paper Algorithm 3 (grouped shard_map Zolo-PD) on 8 host devices.

Runs in subprocesses so the main test process keeps 1 device."""

import pytest

from conftest import run_multidevice_script

_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
import repro.core as C
from repro.dist import grouped_zolo_pd_static, zolo_group_mesh

rng = np.random.default_rng(5)
m, n, kappa = 256, 128, 9.06e3
u, _ = np.linalg.qr(rng.standard_normal((m, n)))
v, _ = np.linalg.qr(rng.standard_normal((n, n)))
a = jnp.asarray(u @ np.diag(np.geomspace(1, 1/kappa, n)) @ v.T)

for r in (2, 4):
    mesh = zolo_group_mesh(r)
    assert mesh.shape == {"zolo": r, "sep": 8 // r}
    q = grouped_zolo_pd_static(a, mesh=mesh, l0=0.9/kappa, r=r)
    h = C.form_h(q, a)
    orth = float(C.orthogonality(q))
    rec = float(jnp.linalg.norm(q @ h - a) / jnp.linalg.norm(a))
    assert orth < 1e-13, (r, orth)
    assert rec < 1e-12, (r, rec)
    # must agree with the single-jit batched (gram-shared) mode
    q2, _, _ = C.zolo_pd(a, r=r, l=0.9/kappa, want_h=False)
    assert float(jnp.abs(q - q2).max()) < 1e-10, r
print("GROUPED_OK")
"""


def test_grouped_zolo_subprocess():
    run_multidevice_script(_SCRIPT, "GROUPED_OK")


# The active "sep" axis: one Zolotarev term spans ndev/r devices with the
# iterate row-sharded inside the group (the paper's SEP contexts).  The
# driver's in-body trace-time assert proves each device holds an
# (m_pad/sep, n) row block — if the shard_map specs replicated X over
# "sep" (the pre-activation behavior), the assert would fire and every
# call below would fail.  m = 260 is divisible by neither sep degree, so
# the zero-row padding path is exercised throughout.
_SEP_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
import repro.core as C
from repro.dist import grouped_zolo_pd_static, zolo_group_mesh

rng = np.random.default_rng(7)
m, n, kappa = 260, 96, 9.06e3
u, _ = np.linalg.qr(rng.standard_normal((m, n)))
v, _ = np.linalg.qr(rng.standard_normal((n, n)))
a = jnp.asarray(u @ np.diag(np.geomspace(1, 1/kappa, n)) @ v.T)
l0 = 0.9 / kappa

qs = {}
for r, sep in ((2, 4), (4, 2), (8, 1)):
    mesh = zolo_group_mesh(r)
    assert mesh.shape == {"zolo": r, "sep": sep}
    q = grouped_zolo_pd_static(a, mesh=mesh, l0=l0, r=r)
    qs[(r, sep)] = np.asarray(q)
    orth = float(C.orthogonality(q))
    h = C.form_h(q, a)
    rec = float(jnp.linalg.norm(q @ h - a) / jnp.linalg.norm(a))
    assert orth < 1e-13, (r, sep, orth)
    assert rec < 1e-12, (r, sep, rec)
    # sep>1 vs sep=1 parity at the same r: a degenerate mesh over the
    # first r devices runs each term on one device
    mesh1 = zolo_group_mesh(r, devices=jax.devices()[:r])
    assert mesh1.shape == {"zolo": r, "sep": 1}
    q1 = grouped_zolo_pd_static(a, mesh=mesh1, l0=l0, r=r)
    # outputs are committed to different device sets: compare via host
    assert float(np.abs(np.asarray(q) - np.asarray(q1)).max()) < 1e-10, \
        (r, sep)
    # and parity with the single-device batched driver
    qb, _, _ = C.zolo_pd_static(a, l0=l0, r=r)
    assert float(np.abs(np.asarray(q) - np.asarray(qb)).max()) < 1e-10, \
        (r, sep)

# the sep-distributed (r=2, sep=4) solve matches the fully task-parallel
# (r=8, sep=1) one and the single-device driver at polar-parity tolerance
# (all converge to the same orthogonal factor)
q_sd, _, _ = C.zolo_pd_static(a, l0=l0, r=2)
assert float(np.abs(qs[(2, 4)] - qs[(8, 1)]).max()) < 1e-10
assert float(np.abs(qs[(2, 4)] - np.asarray(q_sd)).max()) < 1e-10

# sep>1 rejects the non-distributable structured-Householder first term
try:
    grouped_zolo_pd_static(a, mesh=zolo_group_mesh(2), l0=l0, r=2,
                           qr_mode="householder")
except ValueError as e:
    assert "householder" in str(e)
else:
    raise AssertionError("sep>1 householder must raise")
print("SEP_OK")
"""


def test_grouped_sep_axis_subprocess():
    run_multidevice_script(_SEP_SCRIPT, "SEP_OK")


# The plan path on a sep>1 mesh: auto resolves to a grouped backend via
# the sep-aware cost model, the plan records the (r, sep) factorization,
# and the flop estimate is the per-device critical path.
_SEP_PLAN_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
import repro.core as C
import repro.solver as S
from repro.core import registry
from repro.dist import zolo_group_mesh

rng = np.random.default_rng(11)
m, n, kappa = 256, 128, 1e4
u, _ = np.linalg.qr(rng.standard_normal((m, n)))
v, _ = np.linalg.qr(rng.standard_normal((n, n)))
a = jnp.asarray(u @ np.diag(np.geomspace(1, 1/kappa, n)) @ v.T)

mesh = zolo_group_mesh(2)          # {"zolo": 2, "sep": 4}
cfg = S.SvdConfig(kappa=kappa, l0_policy="estimate_at_plan")
p = S.plan(cfg, a.shape, a.dtype, mesh=mesh)
assert p.mode == "grouped" and p.r == 2 and p.sep == 4, (p.mode, p.r, p.sep)
spec = registry.get_polar(p.method)
assert spec.supports_grouped and not spec.is_oracle
# the sep degree reaches the registered cost model: at fixed r the
# per-device estimate shrinks when the group spans more devices
kw = dict(r=2, kappa=kappa, grouped=True)
assert spec.flops_fn(m, n, sep=4, **kw) < spec.flops_fn(m, n, sep=1, **kw)
assert p.flops_estimate == spec.flops_fn(m, n, sep=4, **kw) / 2

q, h, info = p.polar(a)
assert float(C.orthogonality(q)) < 1e-13
rec = float(jnp.linalg.norm(q @ h - a) / jnp.linalg.norm(a))
assert rec < 1e-12
t0 = S.trace_count()
p.polar(a)
assert S.trace_count() == t0, "repeated grouped polar retraced"

# invalid combinations fail at plan time, not at first execution
try:
    S.plan(cfg.replace(qr_mode="householder"), a.shape, a.dtype, mesh=mesh)
except ValueError as e:
    assert "householder" in str(e) and "sep" in str(e)
else:
    raise AssertionError("householder on a sep>1 mesh must fail at plan")
u_p, s_p, vh_p = p.svd(a)
s_ref = np.linalg.svd(np.asarray(a), compute_uv=False)
assert float(np.abs(np.asarray(s_p) - s_ref).max()) < 1e-11
print("SEP_PLAN_OK")
"""


def test_grouped_sep_plan_subprocess():
    run_multidevice_script(_SEP_PLAN_SCRIPT, "SEP_PLAN_OK")


# The dynamic grouped backend: runtime conditioning estimated
# sep-collectively in-graph, feeding in-graph Zolotarev coefficients —
# parity against the static grouped driver and the single-device dynamic
# driver on every (r, sep) factorization.  m = 260 is divisible by
# neither sep degree, so the zero-row padding path (including the padded
# in-graph sigma_min estimate) is exercised throughout.
_DYN_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
import repro.core as C
from repro.dist import (grouped_zolo_pd_dynamic, grouped_zolo_pd_static,
                        zolo_group_mesh)

rng = np.random.default_rng(13)
m, n, kappa = 260, 96, 9.06e3
u, _ = np.linalg.qr(rng.standard_normal((m, n)))
v, _ = np.linalg.qr(rng.standard_normal((n, n)))
a = jnp.asarray(u @ np.diag(np.geomspace(1, 1/kappa, n)) @ v.T)
l0 = 0.9 / kappa

q_sd, _, _ = C.zolo_pd(a, r=2, want_h=False)  # single-device dynamic
for r, sep in ((2, 4), (4, 2), (8, 1)):
    mesh = zolo_group_mesh(r)
    assert mesh.shape == {"zolo": r, "sep": sep}
    q, info = grouped_zolo_pd_dynamic(a, mesh=mesh, return_info=True)
    assert int(info.iterations) >= 1
    orth = float(C.orthogonality(q))
    assert orth < 1e-13, (r, sep, orth)
    h = C.form_h(q, a)
    rec = float(jnp.linalg.norm(q @ h - a) / jnp.linalg.norm(a))
    assert rec < 1e-12, (r, sep, rec)
    # parity vs the static grouped driver at the same (r, sep) and vs
    # the single-device dynamic driver (all converge to the polar factor)
    q_st = grouped_zolo_pd_static(a, mesh=mesh, l0=l0, r=r)
    assert float(np.abs(np.asarray(q) - np.asarray(q_st)).max()) < 1e-10, \
        (r, sep)
    q_dd, _, _ = C.zolo_pd(a, r=r, want_h=False)
    assert float(np.abs(np.asarray(q) - np.asarray(q_dd)).max()) < 1e-10, \
        (r, sep)
assert float(np.abs(np.asarray(
    grouped_zolo_pd_dynamic(a, mesh=zolo_group_mesh(2)))
    - np.asarray(q_sd)).max()) < 1e-10

# an explicit bound short-circuits the in-graph estimate but must agree
q_l = grouped_zolo_pd_dynamic(a, mesh=zolo_group_mesh(2), l=l0)
assert float(C.orthogonality(q_l)) < 1e-13

# householder first iteration: allowed on sep=1, rejected on sep>1
q_hh = grouped_zolo_pd_dynamic(a, mesh=zolo_group_mesh(8),
                               first_mode="householder")
assert float(C.orthogonality(q_hh)) < 1e-13
try:
    grouped_zolo_pd_dynamic(a, mesh=zolo_group_mesh(2),
                            first_mode="householder")
except ValueError as e:
    assert "first_mode" in str(e) and "sep" in str(e), e
else:
    raise AssertionError("sep>1 householder first_mode must raise")
print("DYN_OK")
"""


def test_grouped_dynamic_subprocess():
    run_multidevice_script(_DYN_SCRIPT, "DYN_OK")


# The dynamic grouped plan path: l0_policy='runtime' + mesh= resolves to
# zolo_grouped_dynamic on the (r, sep) mesh, and ONE compiled executable
# serves matrices of wildly different conditioning (kappa 1e2 and 1e10)
# with zero retraces between them — the adaptive kappa-driven execution
# the static schedule cannot provide.
_DYN_PLAN_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
import repro.core as C
import repro.solver as S
from repro.core import registry
from repro.dist import zolo_group_mesh

m, n = 260, 96
def mk(kappa, seed):
    rng = np.random.default_rng(seed)
    u, _ = np.linalg.qr(rng.standard_normal((m, n)))
    v, _ = np.linalg.qr(rng.standard_normal((n, n)))
    return jnp.asarray(u @ np.diag(np.geomspace(1, 1/kappa, n)) @ v.T)

mesh = zolo_group_mesh(2)          # {"zolo": 2, "sep": 4}
p = S.plan(S.SvdConfig(l0_policy="runtime"), (m, n), jnp.float64,
           mesh=mesh)
assert p.method == "zolo_grouped_dynamic", p.method
assert p.mode == "grouped" and p.r == 2 and p.sep == 4, (p.r, p.sep)
assert p.schedule is None            # nothing precomputed: runtime l
assert "sep=4" in repr(p), repr(p)
spec = registry.get_polar(p.method)
assert spec.dynamic and spec.supports_grouped and spec.requires_mesh
assert p.flops_estimate is not None and p.flops_estimate > 0

a_easy, a_hard = mk(1e2, 1), mk(1e10, 2)
q1, h1, i1 = p.polar(a_easy)
t0 = S.trace_count()
q2, h2, i2 = p.polar(a_hard)
assert S.trace_count() == t0, "kappa change retraced the dynamic plan"
for name, (a_, q_, h_, i_) in {"easy": (a_easy, q1, h1, i1),
                               "hard": (a_hard, q2, h2, i2)}.items():
    assert float(C.orthogonality(q_)) < 1e-13, name
    rec = float(jnp.linalg.norm(q_ @ h_ - a_) / jnp.linalg.norm(a_))
    assert rec < 1e-12, (name, rec)
# the hard matrix genuinely needs more of the while_loop
assert int(i2.iterations) > int(i1.iterations), \
    (int(i1.iterations), int(i2.iterations))

# parity with the static grouped plan at a kappa both can handle
kappa = 9.06e3
a = mk(kappa, 3)
p_st = S.plan(S.SvdConfig(method="zolo_grouped", kappa=kappa,
                          l0_policy="estimate_at_plan"),
              (m, n), jnp.float64, mesh=mesh)
q_dyn = p.polar(a, want_h=False)[0]
q_st = p_st.polar(a, want_h=False)[0]
assert float(np.abs(np.asarray(q_dyn) - np.asarray(q_st)).max()) < 1e-10

# the full grouped dynamic SVD (Alg. 2 over Alg. 3, runtime kappa)
u_p, s_p, vh_p = p.svd(a)
s_ref = np.linalg.svd(np.asarray(a), compute_uv=False)
assert float(np.abs(np.asarray(s_p) - s_ref).max()) < 1e-11

# auto with a known l0 stays on the cheaper static schedule; the
# dynamic backend's margin (runtime estimate + safety iteration) is
# visible in the registered cost models
p_auto = S.plan(S.SvdConfig(kappa=kappa, l0_policy="estimate_at_plan"),
                (m, n), jnp.float64, mesh=mesh)
assert not registry.get_polar(p_auto.method).dynamic, p_auto.method
kw = dict(r=2, kappa=kappa, grouped=True, sep=4)
assert registry.get_polar("zolo_grouped_dynamic").flops_fn(m, n, **kw) > \
    registry.get_polar("zolo_grouped").flops_fn(m, n, **kw)

# capability errors name only mesh-compatible backends
try:
    S.plan(S.SvdConfig(method="zolo_grouped", l0_policy="runtime"),
           (m, n), jnp.float64, mesh=mesh)
except ValueError as e:
    assert "zolo_grouped_dynamic" in str(e), e
    assert "'zolo'" not in str(e) and "qdwh" not in str(e), e
else:
    raise AssertionError("static grouped + runtime l0 must fail at plan")
print("DYN_PLAN_OK")
"""


def test_grouped_dynamic_plan_subprocess():
    run_multidevice_script(_DYN_PLAN_SCRIPT, "DYN_PLAN_OK")


# The grouped plan's collectives carry the name scope ``psum`` (the
# all-reduces' op_name metadata in the compiled module), which a
# profiler's name-scope view reads.
_PSUM_SCOPE_SCRIPT = r"""
import re
import jax, jax.numpy as jnp
import repro.solver as S
from repro.dist import zolo_group_mesh

n = 128
p = S.plan(S.SvdConfig(kappa=100.0, l0_policy="estimate_at_plan"), (n, n),
           "float32", mesh=zolo_group_mesh(2))
assert p.mode == "grouped" and p.sep == 4, p
entry = {"polar": p.polar, "svd": p.svd}[ENTRY]
text = jax.jit(entry).lower(jax.ShapeDtypeStruct((n, n), jnp.float32)) \
    .compile().as_text()
paths = [re.search(r'op_name="([^"]*)"', line).group(1)
         for line in text.splitlines()
         if re.search(r" all-reduce(-start)?\(", line) and "op_name" in line]
# the last component names the operation itself, the ones before it
# are scopes
assert any("psum" in path.split("/")[:-1] for path in paths), paths
print("PSUM_SCOPE_OK")
"""


@pytest.mark.parametrize("entry", ["polar", "svd"])
def test_grouped_plan_collectives_carry_psum_scope_subprocess(entry):
    run_multidevice_script(f"ENTRY = {entry!r}\n" + _PSUM_SCOPE_SCRIPT,
                           "PSUM_SCOPE_OK")
