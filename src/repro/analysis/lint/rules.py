"""Built-in lint rules — each one encodes a bug this repo already hit.

==================  =====================================================
rule                historical bug it encodes
==================  =====================================================
collective-axis     PR 4: psum/axis_index against an axis name that is
                    not bound by the surrounding mesh traces fine on one
                    device and deadlocks/miscomputes on a real slice;
                    ``check_vma=False`` without a written justification
                    hides replication-rule bugs (the double-psum class).
accum-dtype         PR 3: a Gram/einsum product without
                    ``preferred_element_type`` accumulates bf16/f16 on
                    TPU, and the downstream Cholesky/QR factors garbage.
plan-key-hygiene    PR 2/6: plan caches key on the config dataclass —
                    a mutable or unhashable config either explodes at
                    lookup or (worse) silently defeats the cache.
retrace-hazard      PR 6: ``float()``/``int()``/``np.*``/Python ``if``
                    on a traced value inside a jitted body either fails
                    at trace time or forces a retrace per call — the
                    serving path's zero-retrace guarantee dies.
bare-assert         PR 5: library ``assert`` vanishes under ``python
                    -O`` and reports no operand context; shape proofs
                    must fail loudly with real exceptions.
keyerror-dispatch   PR 3: registry dispatch through ``TABLE[name]``
                    surfaces an unactionable ``KeyError: 'zolo'``
                    instead of naming the known choices.
kernel-accum-       ROADMAP 4: a Pallas kernel that accepts sub-f32
envelope            operands but leaves an MXU product's accumulator
                    unpinned accumulates bf16 on TPU, and a kernel
                    module without a declared accumulator dtype and
                    envelope registration leaves the planner/health
                    judge nothing to gate its precision on.
==================  =====================================================

Heuristics are deliberately precision-first: variable-valued arguments
(e.g. the ``axis: str = "sep"`` parameters threaded through
``repro.dist.grouped_ops``) are not flagged — only literals the AST can
prove.  What a rule cannot prove it stays silent about; the jaxpr
auditor (:mod:`repro.analysis.jaxpr_audit`) covers the runtime side.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from repro.analysis.lint.engine import FileContext, Finding, register_rule

# ---------------------------------------------------------------------------
# shared AST helpers


def _call_name(node: ast.Call) -> str:
    """Dotted name of a call target: ``jax.lax.psum`` -> ``jax.lax.psum``."""
    return _dotted(node.func)


def _dotted(node: ast.AST) -> str:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return f"{base}.{node.attr}" if base else node.attr
    return ""


def _str_consts(node: ast.AST) -> List[str]:
    """All string literals in an expression (tuples/lists flattened)."""
    out = []
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
            out.append(sub.value)
    return out


def _kwarg(node: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in node.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _functions(tree: ast.AST) -> List[ast.FunctionDef]:
    return [n for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]


# ---------------------------------------------------------------------------
# collective-axis


class CollectiveAxisRule:
    """psum/axis_index axis names must be declared somewhere in the module
    (mesh construction, PartitionSpec, or an ``axis=``-style parameter
    default); ``check_vma=False`` needs a justification comment that
    mentions ``check_vma``."""

    name = "collective-axis"
    doc = ("collective axis literals must match a declared mesh axis; "
           "check_vma=False requires a 'check_vma' justification comment")

    COLLECTIVES = {"psum", "pmean", "pmax", "pmin", "all_gather",
                   "axis_index", "psum_scatter", "ppermute", "pshuffle",
                   "all_to_all"}
    SPEC_CALLS = {"P", "PartitionSpec", "NamedSharding"}
    AXIS_PARAMS = {"axis", "axis_name", "axis_names", "data_axis"}

    def declared_axes(self, ctx: FileContext) -> Set[str]:
        axes: Set[str] = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                callee = _call_name(node)
                tail = callee.rsplit(".", 1)[-1]
                if tail == "Mesh" or tail.endswith("_mesh") or tail in self.SPEC_CALLS:
                    for arg in list(node.args) + [kw.value for kw in node.keywords]:
                        axes.update(_str_consts(arg))
                kw = _kwarg(node, "axis_names")
                if kw is not None:
                    axes.update(_str_consts(kw))
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                args = node.args
                named = args.posonlyargs + args.args + args.kwonlyargs
                defaults = ([None] * (len(args.posonlyargs) + len(args.args)
                                      - len(args.defaults))
                            + list(args.defaults) + list(args.kw_defaults))
                for a, d in zip(named, defaults):
                    if a.arg in self.AXIS_PARAMS and d is not None:
                        axes.update(_str_consts(d))
            elif isinstance(node, ast.Assign):
                # module/function constants that look like axis tuples:
                #   AXES = ("zolo", "sep")
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name) and "axis" in tgt.id.lower():
                        axes.update(_str_consts(node.value))
        return axes

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        declared = self.declared_axes(ctx)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = _call_name(node)
            tail = callee.rsplit(".", 1)[-1]
            if tail in self.COLLECTIVES:
                axis_args: List[ast.expr] = []
                if tail == "axis_index":
                    axis_args += node.args[:1]
                else:
                    axis_args += node.args[1:2]
                for kwname in ("axis_name", "axis"):
                    kw = _kwarg(node, kwname)
                    if kw is not None:
                        axis_args.append(kw)
                for arg in axis_args:
                    for lit in _str_consts(arg):
                        if declared and lit not in declared:
                            yield ctx.finding(
                                node, self.name,
                                f"{tail}(..., {lit!r}): axis {lit!r} is not "
                                f"declared in this module (known: "
                                f"{sorted(declared)})")
                        elif not declared:
                            yield ctx.finding(
                                node, self.name,
                                f"{tail}(..., {lit!r}): no mesh axes are "
                                f"declared in this module at all")
            kw = _kwarg(node, "check_vma")
            if (kw is not None and isinstance(kw, ast.Constant)
                    and kw.value is False):
                near = ctx.comment_near(node.lineno)
                if "check_vma" not in near:
                    yield ctx.finding(
                        node, self.name,
                        "check_vma=False without a justification comment "
                        "mentioning 'check_vma' (replication-rule checking "
                        "caught the PR 4 double-psum class)")


# ---------------------------------------------------------------------------
# accum-dtype


class AccumDtypeRule:
    """Product ops feeding a factorization must pin their accumulator:
    ``einsum``/``matmul``/``dot``/``tensordot`` results that reach
    ``cholesky``/``qr``/``eigh``/``cholesky_qr2`` need
    ``preferred_element_type`` (or an explicit f32 promotion)."""

    name = "accum-dtype"
    doc = ("Gram/einsum accumulators feeding Cholesky/QR/eigh must carry "
           "preferred_element_type (bf16/f16 accumulation broke PR 3)")

    PRODUCTS = {"einsum", "matmul", "dot", "tensordot", "dot_general"}
    SINKS = {"cholesky", "qr", "eigh", "cholesky_qr2", "eig", "svd",
             "structured_qr_factor"}

    def _product_call(self, node: ast.AST) -> Optional[ast.Call]:
        if (isinstance(node, ast.Call)
                and _call_name(node).rsplit(".", 1)[-1] in self.PRODUCTS
                and _kwarg(node, "preferred_element_type") is None):
            return node
        return None

    def _names_in(self, node: ast.AST) -> Set[str]:
        return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        # nested defs are walked by their enclosing function too; flag
        # each product call once (outermost function wins)
        flagged: Set[int] = set()
        for fn in _functions(ctx.tree):
            yield from self._check_fn(ctx, fn, flagged)

    def _check_fn(self, ctx: FileContext, fn: ast.FunctionDef,
                  flagged: Set[int]):
        # 1. collect simple assignments name -> rhs (last write wins is
        #    fine for the fixpoint: we only need reachability).
        assigns: List[Tuple[str, ast.expr]] = []
        sink_args: List[ast.expr] = []
        body_nodes = list(ast.walk(fn))
        for node in body_nodes:
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                tgt = node.targets[0]
                if isinstance(tgt, ast.Name):
                    assigns.append((tgt.id, node.value))
                elif isinstance(tgt, ast.Tuple):
                    for el in tgt.elts:
                        if isinstance(el, ast.Name):
                            assigns.append((el.id, node.value))
            if isinstance(node, ast.Call):
                if _call_name(node).rsplit(".", 1)[-1] in self.SINKS:
                    sink_args.extend(node.args)
                    sink_args.extend(kw.value for kw in node.keywords)
        if not sink_args:
            return
        # 2. backward-reachable name set from the sink arguments.
        reach: Set[str] = set()
        for arg in sink_args:
            reach |= self._names_in(arg)
        for _ in range(len(assigns) + 1):
            grew = False
            for name, rhs in assigns:
                if name in reach:
                    new = self._names_in(rhs) - reach
                    if new:
                        reach |= new
                        grew = True
            if not grew:
                break
        # 3. flag unpinned product calls that feed the sink: either
        #    directly inside a sink argument, or assigned to a reachable
        #    name.

        def flag(call: ast.Call, how: str):
            if id(call) in flagged:
                return None
            flagged.add(id(call))
            op = _call_name(call).rsplit(".", 1)[-1]
            return ctx.finding(
                call, self.name,
                f"{op} result {how} a factorization in "
                f"{fn.name}() without preferred_element_type "
                f"(pin the accumulator or promote to f32 first)")

        for arg in sink_args:
            for sub in ast.walk(arg):
                call = self._product_call(sub)
                if call is not None:
                    f = flag(call, "feeds")
                    if f:
                        yield f
        for name, rhs in assigns:
            if name not in reach:
                continue
            for sub in ast.walk(rhs):
                call = self._product_call(sub)
                if call is not None:
                    f = flag(call, f"(via {name!r}) reaches")
                    if f:
                        yield f


# ---------------------------------------------------------------------------
# plan-key-hygiene


class PlanKeyHygieneRule:
    """Config-style dataclasses feed plan-cache keys: they must be
    ``frozen=True`` and must not annotate fields with unhashable or
    array types."""

    name = "plan-key-hygiene"
    doc = ("*Config/*Policy/*Key dataclasses feed cache keys: frozen=True "
           "required, no list/dict/set/ndarray-typed fields")

    SUFFIXES = ("Config", "Policy", "Key")
    UNHASHABLE = {"list", "List", "dict", "Dict", "set", "Set",
                  "bytearray", "ndarray", "Array"}

    def _dataclass_deco(self, cls: ast.ClassDef) -> Optional[ast.AST]:
        for deco in cls.decorator_list:
            target = deco.func if isinstance(deco, ast.Call) else deco
            if _dotted(target).rsplit(".", 1)[-1] == "dataclass":
                return deco
        return None

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            if not node.name.endswith(self.SUFFIXES) or node.name.startswith("_"):
                continue
            deco = self._dataclass_deco(node)
            if deco is None:
                continue
            frozen = False
            if isinstance(deco, ast.Call):
                kw = _kwarg(deco, "frozen")
                frozen = (isinstance(kw, ast.Constant) and kw.value is True)
            if not frozen:
                yield ctx.finding(
                    node, self.name,
                    f"dataclass {node.name} looks like a cache-key config "
                    f"but is not frozen=True (mutable keys defeat the plan "
                    f"cache)")
            for stmt in node.body:
                if not isinstance(stmt, ast.AnnAssign):
                    continue
                ann_names = {_dotted(sub).rsplit(".", 1)[-1]
                             for sub in ast.walk(stmt.annotation)
                             if isinstance(sub, (ast.Name, ast.Attribute))}
                bad = ann_names & self.UNHASHABLE
                if bad:
                    field = stmt.target.id if isinstance(
                        stmt.target, ast.Name) else "?"
                    yield ctx.finding(
                        stmt, self.name,
                        f"{node.name}.{field}: {sorted(bad)[0]}-typed field "
                        f"is unhashable/array-valued — cache keys must hold "
                        f"hashable scalars/tuples")


# ---------------------------------------------------------------------------
# retrace-hazard


class RetraceHazardRule:
    """Inside jit/shard_map bodies and lax control-flow callbacks, flag
    host-side coercion of traced values: ``float()``/``int()``/``bool()``
    on parameter-derived expressions, ``np.*`` calls on them, and Python
    ``if`` statements testing a bare parameter."""

    name = "retrace-hazard"
    doc = ("float()/int()/np.*/Python-if on traced values inside jitted "
           "bodies concretize tracers or force per-call retraces")

    JIT_MARKERS = {"jit", "shard_map", "pmap", "smap"}
    LAX_CONSUMERS = {"while_loop", "fori_loop", "scan", "cond", "switch",
                     "custom_root"}
    COERCERS = {"float", "int", "bool"}
    STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "aval", "sharding"}

    def _jitted_functions(self, ctx: FileContext) -> List[ast.FunctionDef]:
        out = []
        lax_fed: Set[str] = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call):
                tail = _call_name(node).rsplit(".", 1)[-1]
                if tail in self.LAX_CONSUMERS:
                    for arg in node.args:
                        if isinstance(arg, ast.Name):
                            lax_fed.add(arg.id)
        for fn in _functions(ctx.tree):
            for deco in fn.decorator_list:
                names = {_dotted(s).rsplit(".", 1)[-1]
                         for s in ast.walk(deco)
                         if isinstance(s, (ast.Name, ast.Attribute))}
                if names & self.JIT_MARKERS:
                    out.append(fn)
                    break
            else:
                if fn.name in lax_fed:
                    out.append(fn)
        return out

    def _is_traced_expr(self, node: ast.AST, params: Set[str]) -> bool:
        """Does the expression mention a parameter as a bare Name (not
        through a static attribute like ``.shape``)?"""
        for sub in ast.walk(node):
            if isinstance(sub, ast.Attribute) and sub.attr in self.STATIC_ATTRS:
                continue
            if isinstance(sub, ast.Name) and sub.id in params:
                # reject when this Name only appears under a static attr
                if not self._under_static_attr(node, sub):
                    return True
        return False

    def _under_static_attr(self, root: ast.AST, target: ast.Name) -> bool:
        for sub in ast.walk(root):
            if (isinstance(sub, ast.Attribute)
                    and sub.attr in self.STATIC_ATTRS):
                if any(s is target for s in ast.walk(sub.value)):
                    return True
        return False

    def _static_params(self, fn: ast.FunctionDef) -> Set[str]:
        """Names bound statically by the jit decorator
        (``static_argnames=(...)``) — not tracers."""
        out: Set[str] = set()
        for deco in fn.decorator_list:
            for sub in ast.walk(deco):
                if (isinstance(sub, ast.keyword)
                        and sub.arg == "static_argnames"):
                    out.update(_str_consts(sub.value))
        return out

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for fn in self._jitted_functions(ctx):
            params = {a.arg for a in (fn.args.posonlyargs + fn.args.args
                                      + fn.args.kwonlyargs)}
            params -= self._static_params(fn)
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    callee = _call_name(node)
                    tail = callee.rsplit(".", 1)[-1]
                    if (callee in self.COERCERS and node.args
                            and self._is_traced_expr(node.args[0], params)):
                        yield ctx.finding(
                            node, self.name,
                            f"{callee}() on a traced value inside jitted "
                            f"{fn.name}() concretizes the tracer")
                    if (callee.startswith("np.") or callee.startswith("numpy.")) \
                            and node.args \
                            and self._is_traced_expr(node.args[0], params):
                        yield ctx.finding(
                            node, self.name,
                            f"{callee}() inside jitted {fn.name}() pulls a "
                            f"traced value to host numpy")
                    del tail
                elif isinstance(node, ast.If):
                    if self._is_traced_expr(node.test, params):
                        yield ctx.finding(
                            node, self.name,
                            f"Python `if` on a traced value inside jitted "
                            f"{fn.name}() branches at trace time (retrace "
                            f"per distinct value); use jnp.where/lax.cond")


# ---------------------------------------------------------------------------
# bare-assert


class BareAssertRule:
    """No ``assert`` in library code: it disappears under ``python -O``
    and carries no operand context.  Raise a real exception."""

    name = "bare-assert"
    doc = ("library asserts vanish under -O and hide operands; raise "
           "ValueError/AssertionError explicitly")

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assert):
                yield ctx.finding(
                    node, self.name,
                    "bare assert in library code (stripped by -O); "
                    "use `if ...: raise`")


# ---------------------------------------------------------------------------
# keyerror-dispatch


class KeyErrorDispatchRule:
    """Dict dispatch on user input must fail loud: ``TABLE[name]`` where
    ``name`` is a function parameter and the function never membership-
    checks it raises a bare ``KeyError`` that names no alternatives."""

    name = "keyerror-dispatch"
    doc = ("dict dispatch on a parameter without a membership check "
           "raises an unactionable bare KeyError")

    def _guarded_names(self, fn: ast.FunctionDef) -> Set[str]:
        """Parameters that are membership-tested or .get()-dispatched
        somewhere in the function."""
        guarded: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Compare):
                ops = node.ops
                if any(isinstance(op, (ast.In, ast.NotIn)) for op in ops):
                    for sub in ast.walk(node.left):
                        if isinstance(sub, ast.Name):
                            guarded.add(sub.id)
            if isinstance(node, ast.Call):
                tail = _call_name(node).rsplit(".", 1)[-1]
                if tail == "get" and node.args:
                    for sub in ast.walk(node.args[0]):
                        if isinstance(sub, ast.Name):
                            guarded.add(sub.id)
            if isinstance(node, ast.Try):
                for handler in node.handlers:
                    htype = handler.type
                    names = {_dotted(s) for s in ast.walk(htype)} if htype else set()
                    if "KeyError" in names or htype is None:
                        # anything subscripted inside the try is guarded
                        for sub in ast.walk(node):
                            if isinstance(sub, ast.Subscript):
                                for s2 in ast.walk(sub.slice):
                                    if isinstance(s2, ast.Name):
                                        guarded.add(s2.id)
        return guarded

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        # dict-literal module/class-level tables by name
        tables: Set[str] = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Dict):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        tables.add(tgt.id)
        if not tables:
            return
        for fn in _functions(ctx.tree):
            params = {a.arg for a in (fn.args.posonlyargs + fn.args.args
                                      + fn.args.kwonlyargs)}
            guarded = self._guarded_names(fn)
            for node in ast.walk(fn):
                if not isinstance(node, ast.Subscript):
                    continue
                if not (isinstance(node.value, ast.Name)
                        and node.value.id in tables):
                    continue
                idx = node.slice
                if (isinstance(idx, ast.Name) and idx.id in params
                        and idx.id not in guarded):
                    yield ctx.finding(
                        node, self.name,
                        f"{node.value.id}[{idx.id}] dispatches on a "
                        f"parameter without a membership check — a typo "
                        f"raises bare KeyError naming no valid choices")


# ---------------------------------------------------------------------------
# kernel-accum-envelope


class KernelAccumEnvelopeRule:
    """Pallas kernel bodies must pin accumulation and declare an envelope.

    A kernel function is recognized structurally: two or more ``*_ref``
    parameters (pallas_call hands operands and outputs over as Refs).
    Such kernels may be handed sub-f32 operands (the bf16 envelope
    work), so two contracts apply:

    * every MXU product inside the body (``dot``/``dot_general``/
      ``einsum``/``matmul``) must pin ``preferred_element_type`` — an
      unpinned product accumulates in the operand dtype on TPU, which
      for bf16 inputs silently loses the f32 accumulation the envelope
      table was measured under;
    * the defining module must bind a module-level accumulator-dtype
      constant (a name containing ``ACCUM_DTYPE``) and an envelope
      registration pointer (a name containing ``ENVELOPE``), so the
      recorded precision contract is discoverable next to the kernel it
      governs rather than only in the planner.
    """

    name = "kernel-accum-envelope"
    doc = ("Pallas kernels taking sub-f32-capable Ref operands must pin "
           "preferred_element_type on MXU products and their module must "
           "declare *_ACCUM_DTYPE and an *ENVELOPE registration")

    PRODUCTS = {"dot", "dot_general", "einsum", "matmul"}

    def _kernel_fns(self, ctx: FileContext) -> List[ast.FunctionDef]:
        out = []
        for fn in _functions(ctx.tree):
            params = [a.arg for a in (fn.args.posonlyargs + fn.args.args
                                      + fn.args.kwonlyargs)]
            if sum(1 for p in params if p.endswith("_ref")) >= 2:
                out.append(fn)
        return out

    def _module_binds(self, ctx: FileContext, fragment: str) -> bool:
        for node in ctx.tree.body:  # module top level only
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, ast.AnnAssign):
                targets = [node.target]
            for tgt in targets:
                if isinstance(tgt, ast.Name) and fragment in tgt.id:
                    return True
        return False

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        kernels = self._kernel_fns(ctx)
        if not kernels:
            return
        for fn in kernels:
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                tail = _call_name(node).rsplit(".", 1)[-1]
                if tail in self.PRODUCTS \
                        and _kwarg(node, "preferred_element_type") is None:
                    yield ctx.finding(
                        node, self.name,
                        f"{tail} inside kernel {fn.name}() without "
                        f"preferred_element_type: sub-f32 operands would "
                        f"accumulate in their own dtype, off the envelope "
                        f"the kernel was measured under")
        if not self._module_binds(ctx, "ACCUM_DTYPE"):
            yield ctx.finding(
                kernels[0], self.name,
                "kernel module declares no *_ACCUM_DTYPE constant: the "
                "accumulator precision the envelope was measured under "
                "must be stated next to the kernel")
        if not self._module_binds(ctx, "ENVELOPE"):
            yield ctx.finding(
                kernels[0], self.name,
                "kernel module declares no *ENVELOPE registration "
                "pointer: the planner/health judge gate sub-f32 use on "
                "a recorded kappa envelope — name where it lives")


register_rule(CollectiveAxisRule())
register_rule(AccumDtypeRule())
register_rule(PlanKeyHygieneRule())
register_rule(RetraceHazardRule())
register_rule(BareAssertRule())
register_rule(KeyErrorDispatchRule())
register_rule(KernelAccumEnvelopeRule())
