"""The program's named scopes reach the compiled module.

Each stage of Zolo-PD and of the block-Jacobi eig stage runs under a
``jax.named_scope``; the scopes are metadata only, and a profiler
trace's op is attributed to its stage through the ``op_name`` of the
instruction in the compiled module's text
(``metadata={op_name="jit(f)/.../trsm/while"}``).  A scope ``a/b`` is
held by a name path whose scopes hold ``a`` and, after it, ``b``."""

import re

import pytest

import jax
import jax.numpy as jnp

import repro.solver as solver

N = 256
LAYER_SCOPES = ("prescale", "gram", "cholesky", "trsm", "combine", "form_h",
                "eig", "u_qv", "psum")
POLAR_SCOPES = ("prescale", "gram", "cholesky", "trsm", "combine", "form_h")
SCOPES = {
    "polar": POLAR_SCOPES,
    "svd": POLAR_SCOPES + ("eig", "eig/small_eigh", "eig/rotate",
                           "eig/sweep_test", "eig/refine", "u_qv"),
}
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?(%[\w.\-]+) = .*?\s([a-z][\w-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _holds(path: str, scope: str) -> bool:
    # the last component names the operation itself (``jit(cholesky)/
    # cholesky``), so it is no scope
    parts = iter(path.split("/")[:-1])
    return all(any(p == s for p in parts) for s in scope.split("/"))


def _instructions(text: str):
    """(name, opcode, op_name path) of every instruction of the text."""
    out = []
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            path = _OP_NAME.search(line)
            out.append((m.group(1), m.group(2),
                        path.group(1) if path else ""))
    return out


@pytest.fixture(scope="module")
def modules():
    cfg = solver.SvdConfig(kappa=1.29, method="zolo_static",
                           l0_policy="estimate_at_plan",
                           eig_method="jacobi", nb=32)
    plan = solver.plan(cfg, (N, N), "float32")
    spec = jax.ShapeDtypeStruct((N, N), jnp.float32)

    def polar(a):
        return plan.polar(a, want_h=True)

    return {"polar": _instructions(jax.jit(polar).lower(spec).compile()
                                   .as_text()),
            "svd": _instructions(plan.compile_svd(spec).as_text())}


@pytest.mark.parametrize("entry,scope", [(e, s) for e in sorted(SCOPES)
                                         for s in SCOPES[e]])
def test_scope_reaches_the_compiled_module(modules, entry, scope):
    assert any(_holds(path, scope) for _, _, path in modules[entry])


@pytest.mark.parametrize("entry", sorted(SCOPES))
def test_every_loop_product_and_call_is_in_a_layer(modules, entry):
    outside = [(name, op, path) for name, op, path in modules[entry]
               if op in ("while", "dot", "custom-call")
               and not any(_holds(path, s) for s in LAYER_SCOPES)]
    assert not outside, outside[:5]

