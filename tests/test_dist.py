"""repro.dist API contract: sharding hints, group meshes, registry."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import make_matrix, run_multidevice_script


# --- sharding: hint / hint_tree --------------------------------------------


def test_hint_is_identity_outside_mesh_context():
    from repro.dist.sharding import current_rules, hint, hint_tree

    assert current_rules() is None
    x = jnp.ones((4, 8))
    assert hint(x, "batch", None) is x  # exact no-op, not a copy
    tree = {"w": x, "b": jnp.zeros((8,))}
    out = hint_tree(tree, {"w": ("batch", None), "b": (None,)})
    assert out["w"] is x and out["b"] is tree["b"]


def test_hint_constrains_inside_mesh_context():
    from repro.dist.sharding import (LogicalRules, activation_hints,
                                     current_rules, hint, hint_tree)
    from repro.launch.mesh import make_debug_mesh

    mesh = make_debug_mesh(1, 1)
    rules = LogicalRules({"batch": "data", "feat": "model"}, mesh=mesh)

    def f(x):
        with activation_hints(rules):
            assert current_rules() is rules
            return hint(x, "batch", "feat")

    jaxpr = str(jax.make_jaxpr(f)(jnp.ones((4, 8))))
    assert "sharding_constraint" in jaxpr
    # values are untouched, only placement is constrained
    np.testing.assert_array_equal(np.asarray(f(jnp.ones((4, 8)))), 1.0)
    assert current_rules() is None  # context restored

    def g(tree):
        with activation_hints(rules):
            return hint_tree(tree, {"w": ("batch", "feat")})

    jaxpr = str(jax.make_jaxpr(g)({"w": jnp.ones((4, 8))}))
    assert "sharding_constraint" in jaxpr


def test_activation_hints_requires_mesh():
    from repro.dist.sharding import LogicalRules, activation_hints

    with pytest.raises(ValueError, match="mesh"):
        with activation_hints(LogicalRules({"batch": "data"})):
            pass


def test_logical_rules_resolution():
    from repro.dist.sharding import LogicalRules
    from jax.sharding import PartitionSpec as P

    rules = LogicalRules({"batch": ("pod", "data"), "mlp": "model",
                          "seq": None})
    assert rules.spec(("batch", "seq", "mlp")) == \
        P(("pod", "data"), None, "model")
    assert rules.spec("REPLICATED") == P()
    assert rules.spec(None) == P()
    # unknown logical names resolve to replicated, not an error
    assert rules.spec(("nonexistent",)) == P(None)
    # axes missing from the bound mesh are dropped at resolution time
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh(1, 1)  # ("data", "model") only — no "pod"
    assert rules.spec(("batch", "mlp"), mesh=mesh) == P("data", "model")


def test_tree_shardings_structure():
    from repro.dist.sharding import arch_rules, tree_shardings
    from repro.launch.mesh import make_debug_mesh
    from repro.configs import get_smoke_config

    mesh = make_debug_mesh(1, 1)
    cfg = get_smoke_config("olmo-1b")
    rules = arch_rules(cfg, mesh, None)
    axes = {"w": ("embed", "vocab"), "scalars": "REPLICATED",
            "nested": {"b": ("batch", None)}, "skip": None}
    sh = tree_shardings(mesh, rules, axes)
    assert sh["skip"] is None
    assert isinstance(sh["w"], jax.sharding.NamedSharding)
    assert sh["scalars"].spec == jax.sharding.PartitionSpec()
    assert set(sh) == set(axes)


# --- grouped: zolo_group_mesh (needs 8 devices -> subprocess) ---------------

_MESH_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
from repro.dist import zolo_group_mesh

for r in (2, 4):
    mesh = zolo_group_mesh(r)
    assert mesh.shape == {"zolo": r, "sep": 8 // r}, (r, dict(mesh.shape))
    assert mesh.axis_names == ("zolo", "sep")
    assert mesh.devices.shape == (r, 8 // r)
try:
    zolo_group_mesh(3)  # 3 does not divide 8
except ValueError:
    pass
else:
    raise SystemExit("expected ValueError for r=3 on 8 devices")

# registry grouped routing: polar_svd(..., mesh=) must reach Algorithm 3
# through the ONE dispatch path (the README's distributed quickstart)
import repro.core as C
rng = np.random.default_rng(11)
m, n, kappa = 64, 32, 1e3
u, _ = np.linalg.qr(rng.standard_normal((m, n)))
v, _ = np.linalg.qr(rng.standard_normal((n, n)))
a = jnp.asarray(u @ np.diag(np.geomspace(1, 1 / kappa, n)) @ v.T)
mesh = zolo_group_mesh(2)
uu, s, vh = C.polar_svd(a, method="zolo_static", mesh=mesh,
                        l0=0.9 / kappa, r=2)
assert float(C.svd_residual(a, uu, s, vh)) < 1e-12
assert float(C.orthogonality(uu)) < 1e-13
# zolo_pd_static kwargs (qr_mode/qr_iters) must survive grouped routing
q, h, info = C.polar_decompose(a, method="zolo_grouped", mesh=mesh,
                               l0=0.9 / kappa, want_h=True,
                               qr_mode="chol", qr_iters=1)
assert int(info.iterations) >= 1
assert float(jnp.linalg.norm(q @ h - a) / jnp.linalg.norm(a)) < 1e-12
print("MESH_OK")
"""


def test_zolo_group_mesh_and_registry_routing_subprocess():
    run_multidevice_script(_MESH_SCRIPT, "MESH_OK", timeout=300)


def test_zolo_group_mesh_single_device_and_error_lists_divisors():
    """r == ndev is a valid degenerate mesh (sep axis of size 1) — the
    single-device CI case; invalid r errors name the valid divisors."""
    from repro.dist import zolo_group_mesh

    ndev = len(jax.devices())  # 1 in the main test process
    mesh = zolo_group_mesh(ndev)
    assert mesh.shape == {"zolo": ndev, "sep": 1}
    divisors = [d for d in range(1, ndev + 1) if ndev % d == 0]
    with pytest.raises(ValueError, match=str(divisors).replace("[", r"\[")):
        zolo_group_mesh(ndev + 7)
    with pytest.raises(ValueError, match="valid r"):
        zolo_group_mesh(0)


# --- registry ----------------------------------------------------------------


def test_registry_roundtrip_and_dispatch():
    import repro.core as C
    from repro.core import registry

    calls = []

    @registry.register_polar("_test_dummy", description="test-only")
    def dummy(a, **kw):
        calls.append(kw)
        q = jnp.eye(a.shape[-2], a.shape[-1], dtype=a.dtype)
        return q, None, C.PolarInfo(jnp.int32(0),
                                    jnp.asarray(0.0, a.dtype),
                                    jnp.asarray(1.0, jnp.float32))

    try:
        spec = registry.get_polar("_test_dummy")
        assert spec.fn is dummy and not spec.supports_grouped
        assert "_test_dummy" in registry.list_polar()
        # a *different* function under a taken name is rejected; the same
        # function (module reload) re-registers benignly
        with pytest.raises(ValueError, match="already registered"):
            registry.register_polar("_test_dummy")(lambda a, **kw: None)
        assert registry.register_polar("_test_dummy")(dummy) is dummy
        # dispatch through the ONE public path routes to the registration
        a = jnp.eye(4)
        q, h, _ = C.polar_decompose(a, method="_test_dummy", foo=7)
        assert calls == [{"foo": 7}]
        np.testing.assert_array_equal(np.asarray(q), np.eye(4))
        # non-grouped backends reject mesh= instead of ignoring it
        with pytest.raises(ValueError, match="grouped"):
            C.polar_decompose(a, method="_test_dummy", mesh=object())
    finally:
        registry.unregister_polar("_test_dummy")
        # drop its cached plans too, so the session-end audit of every
        # cached plan (test_analysis) never meets a backend that is gone
        from repro.solver import planner as planner_mod

        for key in [k for k, v in planner_mod._PLANS.items()
                    if v.method == "_test_dummy"]:
            del planner_mod._PLANS[key]
    assert "_test_dummy" not in registry.list_polar()


def test_registry_unknown_names_raise():
    import repro.core as C
    from repro.core import registry

    with pytest.raises(ValueError, match="unknown polar method"):
        registry.get_polar("does_not_exist")
    with pytest.raises(ValueError, match="unknown polar method"):
        C.polar_decompose(jnp.eye(4), method="does_not_exist")
    with pytest.raises(ValueError, match="unknown eig method"):
        C.polar_svd(jnp.eye(4), eig_method="does_not_exist")
    # grouped-only backends demand a mesh
    with pytest.raises(ValueError, match="mesh"):
        C.polar_decompose(jnp.eye(4), method="zolo_grouped")


def test_registry_capability_flags():
    from repro.core import registry

    assert registry.get_polar("zolo_static").supports_grouped
    assert registry.get_polar("zolo_grouped").requires_mesh
    assert registry.get_polar("svd").is_oracle
    assert registry.get_polar("zolo").dynamic
    assert {"eigh", "jacobi"} <= set(registry.list_eig())


def test_registry_rejects_inconsistent_capabilities():
    from repro.core import registry

    # supports_grouped with nothing to dispatch to is a registration
    # error, not a runtime TypeError
    with pytest.raises(ValueError, match="grouped_fn"):
        registry.register_polar("_test_bad_grouped",
                                supports_grouped=True)(lambda a, **kw: None)
    # requires_mesh without grouped support can never be dispatched
    with pytest.raises(ValueError, match="unsatisfiable"):
        registry.register_polar("_test_bad_mesh",
                                requires_mesh=True)(lambda a, **kw: None)
    assert "_test_bad_grouped" not in registry.list_polar()
    assert "_test_bad_mesh" not in registry.list_polar()


# --- wide (m < n) polar / SVD ------------------------------------------------


@pytest.mark.parametrize("method", ["zolo", "qdwh"])
def test_polar_decompose_wide_right_factor(method):
    import repro.core as C

    m, n = 48, 96
    a = make_matrix(m, n, 1e4, seed=3)
    q, h, _ = C.polar_decompose(a, method=method)
    assert q.shape == (m, n) and h.shape == (n, n)
    # A = Q H with the re-oriented right factor
    rec = float(jnp.linalg.norm(q @ h - a) / jnp.linalg.norm(a))
    assert rec < 1e-12
    assert float(jnp.abs(h - h.T).max()) < 1e-13  # symmetric
    assert float(jnp.linalg.eigvalsh(h).min()) > -1e-12  # PSD
    # rows of Q orthonormal
    g = q @ q.T
    assert float(jnp.abs(g - jnp.eye(m)).max()) < 1e-12


def test_polar_svd_wide_reconstruction():
    import repro.core as C

    m, n = 40, 104
    a = make_matrix(m, n, 9.06e3, seed=7)
    u, s, vh = C.polar_svd(a, method="zolo")
    assert u.shape == (m, m) and s.shape == (m,) and vh.shape == (m, n)
    assert float(C.svd_residual(a, u, s, vh)) < 1e-12
    assert float(C.orthogonality(u)) < 1e-13
    assert float(C.orthogonality(vh.swapaxes(-1, -2))) < 1e-13
    assert bool(jnp.all(s[:-1] >= s[1:]))  # descending
    s_ref = np.linalg.svd(np.asarray(a), compute_uv=False)
    np.testing.assert_allclose(np.asarray(s), s_ref, atol=1e-12)
