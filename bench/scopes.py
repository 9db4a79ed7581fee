"""Device time of the program's named scopes, from a traced run.

The program names its stages with ``jax.named_scope``: ``prescale``,
``gram``, ``cholesky``, ``trsm``, ``combine``, ``form_h``, ``eig`` (inside
it ``small_eigh``, ``rotate``, ``sweep_test`` and ``refine``), ``u_qv``
and ``psum``.  A TPU trace's ``XLA Ops`` events carry no scope: an
event's name is the HLO instruction's text without its metadata.  The
scope of each op therefore comes from the compiled module's text, where
an instruction's ``metadata={op_name="jit(polar)/.../trsm/while"}`` holds
its name path.

:func:`scope_map` rebuilds the cell's timed entry as
``bench/drivers/plan.py`` does (the same ``SvdConfig`` from the run's
configuration, the same ``plan(...)``, which the plan cache returns as
the same object), lowers and compiles it (the persistent compile cache
serves it as a load), and parses the module's text once per run.

The rule: an op counts for scope S where its name path holds the
components of S in order (``eig/rotate`` is held by
``jit(svd)/.../eig/jit(block_jacobi_eigh)/while/body/.../rotate/dot``),
unless an op that calls its computation (a while's body or condition, a
call, a branch), or one that calls that one, holds S too.  So a while op
in ``trsm`` is counted once, and the ops of its body are not counted
again.  A scope's seconds and events are per timed call.

A program without these scopes (one that names no stage) gives no op in
any scope: every reader then returns None.
"""

from __future__ import annotations

import dataclasses
import re
import time
import weakref
from typing import Dict, List, Optional, Tuple

from bench.harness import log

# the layers of the device time: every op of a timed call falls in one
# of them, or in none (the remainder is logged)
LAYER_SCOPES = ("prescale", "gram", "cholesky", "trsm", "combine", "form_h",
                "eig", "u_qv", "psum")

_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?(%[\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(r"\b(?:body|condition|to_apply|calls|true_computation|"
                     r"false_computation)=(%[\w.\-]+)")
_CALLED_LIST = re.compile(r"\b(?:branch_computations|called_computations)="
                          r"\{([^}]*)\}")


@dataclasses.dataclass
class ModuleMap:
    """Each instruction of a compiled module: its name path and the
    instruction that calls its computation (None in the entry)."""

    path: Dict[str, str]
    parent: Dict[str, Optional[str]]

    def ancestors(self, op: str):
        seen = set()
        op = self.parent.get(op)
        while op is not None and op not in seen:
            seen.add(op)
            yield op
            op = self.parent.get(op)

    def outermost(self, ops, held) -> List[str]:
        """The ops of ``ops`` whose path ``held`` accepts, where no op
        that calls their computation has such a path."""
        return [op for op in ops if held(self.path.get(op, ""))
                and not any(held(self.path.get(a, ""))
                            for a in self.ancestors(op))]

    def counted(self, ops, scope: str) -> List[str]:
        """The ops of ``ops`` that count for ``scope``."""
        return self.outermost(ops, lambda path: holds(path, scope))

    def in_layer(self, op: str) -> bool:
        """The op, or an op that calls its computation, is in a layer
        scope."""
        return any(_in_layer(self.path.get(o, ""))
                   for o in (op, *self.ancestors(op)))


def _in_layer(path: str) -> bool:
    return any(holds(path, s) for s in LAYER_SCOPES)


def holds(path: str, scope: str) -> bool:
    """The name path's scopes hold the scope's components, in order.
    The last component of a path names the operation itself
    (``jit(cholesky)/cholesky``), so it is no scope."""
    parts = iter(path.split("/")[:-1])
    return all(any(p == s for p in parts) for s in scope.split("/"))


def parse(text: str) -> ModuleMap:
    """The :class:`ModuleMap` of a compiled module's text
    (``Compiled.as_text()``)."""
    path: Dict[str, str] = {}
    computation_of: Dict[str, str] = {}
    caller: Dict[str, str] = {}
    comp = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if m is None or comp is None:
            continue
        name = m.group(1)
        op_name = _OP_NAME.search(line)
        path[name] = op_name.group(1) if op_name else ""
        computation_of[name] = comp
        called = _CALLED.findall(line)
        for group in _CALLED_LIST.findall(line):
            called += [c.strip() for c in group.split(",") if c.strip()]
        for c in called:
            caller.setdefault(c, name)
    parent = {op: caller.get(c) for op, c in computation_of.items()}
    return ModuleMap(path=path, parent=parent)


def compiled_text(run) -> Tuple[str, float]:
    """The text of the cell's timed entry as compiled for the run's
    first chip, and the seconds its lowering and compile (or cache load)
    took."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import repro.solver as solver
    from bench.drivers.plan import _entry

    cfg, n = run.config, int(run.config["n"])
    plan = solver.plan(solver.SvdConfig(kappa=cfg["cond"], **cfg["solver"]),
                       (n, n), cfg["dtype"])
    spec = jax.ShapeDtypeStruct((n, n), jnp.dtype(cfg["dtype"]),
                                sharding=SingleDeviceSharding(
                                    jax.devices()[0]))
    t = time.perf_counter()
    text = jax.jit(_entry(plan, run.traffic["op"])).lower(spec).compile() \
        .as_text()
    return text, time.perf_counter() - t


_MAPS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def scope_map(run) -> Optional[ModuleMap]:
    """The run's :class:`ModuleMap`, built once per run; None where the
    run was not traced, or the module could not be rebuilt (logged)."""
    if (run.summary is None or not run.summary.chips
            or not run.values.get("calls")):
        return None
    if run not in _MAPS:
        try:
            text, secs = compiled_text(run)
        except Exception as e:  # a reader finds nothing; the run goes on
            log(f"[scopes] the timed entry could not be rebuilt: {e!r}")
            _MAPS[run] = None
            return None
        _MAPS[run] = parse(text)
        log(f"[scopes] the timed entry lowered and compiled again in "
            f"{secs:.3f}s for the scope map")
        _log_cover(run, _MAPS[run])
    return _MAPS[run]


def _calls(run) -> int:
    return int(run.values["calls"]["count"])


def scope_ops(run, scope: str) -> Optional[List[str]]:
    """The traced ops that count for ``scope``; None where there are
    none."""
    smap = scope_map(run)
    if smap is None:
        return None
    ops = smap.counted(run.summary.kernel_s, scope)
    return ops or None


def scope_seconds(run, scope: str) -> Optional[float]:
    """Device seconds of ``scope`` per timed call."""
    ops = scope_ops(run, scope)
    if ops is None:
        return None
    return sum(run.summary.kernel_s[op] for op in ops) / _calls(run)


def op_events(run, scope: str) -> Optional[List[float]]:
    """The distinct counts of device events per timed call among the ops
    of ``scope``: one count where each op runs as often as the others."""
    ops = scope_ops(run, scope)
    if ops is None:
        return None
    return sorted({run.summary.kernel_calls[op] / _calls(run) for op in ops})


def cover(run, smap: ModuleMap) -> Tuple[float, float, List[Tuple[str, float]]]:
    """(seconds in the layer scopes, busy seconds, the three longest
    traced ops in no layer scope and called by no traced op), each per
    timed call.  An op in two layer scopes (``psum`` inside ``gram``) is
    counted once."""
    calls, s = _calls(run), run.summary
    covered = sum(s.kernel_s[op]
                  for op in smap.outermost(s.kernel_s, _in_layer))
    outside = sorted(((op, t / calls) for op, t in s.kernel_s.items()
                      if not smap.in_layer(op)
                      and not any(a in s.kernel_s
                                  for a in smap.ancestors(op))),
                     key=lambda kv: -kv[1])
    return covered / calls, s.busy_s / calls, outside[:3]


def _log_cover(run, smap: ModuleMap) -> None:
    for scope in LAYER_SCOPES:
        secs = scope_seconds(run, scope)
        if secs is not None:
            log(f"[scopes] {scope}: {secs:.6f}s per call")
    covered, busy, top = cover(run, smap)
    share = 100.0 * covered / busy if busy else 0.0
    log(f"[scopes] layer scopes {covered:.6f}s of {busy:.6f}s busy per "
        f"call ({share:.2f}%); in none {busy - covered:.6f}s per call, "
        f"longest: " + ", ".join(f"{op} {t:.6f}s" for op, t in top))
