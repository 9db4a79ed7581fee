"""The reduction from named scopes to per-layer metrics
(:mod:`bench.scopes`), checked on hand-made HLO text shaped like the
compiled modules: while loops with their bodies and conditions, fusions,
Pallas and XLA custom calls, and the block-Jacobi sweep and round
loops."""

import pytest

from bench import harness, scopes, trace

HLO = """HloModule jit_f, is_scheduled=true

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(f)/trsm/while/body/mul"}
}

%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %gte.1 = f32[4]{0} get-tuple-element(%p), index=1
  %fusion.2 = f32[4]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/trsm/while/body/mul"}
  %rot.1 = f32[4]{0} negate(%fusion.2), metadata={op_name="jit(f)/eig/jit(g)/while/body/rotate/neg"}
  ROOT %tuple.1 = (s32[], f32[4]{0}) tuple(%gte.0, %rot.1)
}

%cond (q: (s32[], f32[4])) -> pred[] {
  %q = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt.1 = pred[] compare(%gte.3, %c.1), direction=LT, metadata={op_name="jit(f)/trsm/while/cond/lt"}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %while.1 = (s32[], f32[4]{0}) while(%t.1), condition=%cond, body=%body, metadata={op_name="jit(f)/trsm/while"}
  %gram.1 = f32[4]{0} custom-call(%x), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(f)/gram/jit(k)/pallas_call"}
  %chol.1 = f32[4]{0} custom-call(%x), custom_call_target="Cholesky", metadata={op_name="jit(f)/jit(cholesky)/cholesky"}
  ROOT %copy.1 = f32[4]{0} copy(%x)
}
"""


class FakeRun:
    """What :mod:`bench.scopes` reads of a run."""

    def __init__(self, summary, calls, config=None):
        self.summary = summary
        self.values = {"calls": {"op": "svd", "count": calls}}
        self.config = config or {}


def _summary(seconds, events):
    return trace.TraceSummary(chips=1, busy_s=sum(
        t for op, t in seconds.items() if op not in ("%fusion.2", "%lt.1",
                                                     "%rot.1")),
        kernel_s=seconds, kernel_calls=events, idle_gaps=[])


@pytest.fixture
def hand_run(monkeypatch):
    monkeypatch.setattr(scopes, "compiled_text", lambda run: (HLO, 0.0))
    seconds = {"%while.1": 3.0, "%fusion.2": 2.5, "%lt.1": 0.1,
               "%rot.1": 0.2, "%gram.1": 1.0, "%chol.1": 0.4,
               "%copy.1": 0.2}
    events = {op: 6 for op in seconds}
    return FakeRun(_summary(seconds, events), calls=2)


def test_parse_maps_each_op_to_its_path_and_caller():
    smap = scopes.parse(HLO)
    assert smap.path["%while.1"] == "jit(f)/trsm/while"
    assert smap.path["%copy.1"] == ""
    assert smap.parent["%fusion.2"] == "%while.1"
    assert smap.parent["%lt.1"] == "%while.1"
    assert smap.parent["%mul.1"] == "%fusion.2"
    assert smap.parent["%while.1"] is None
    assert list(smap.ancestors("%mul.1")) == ["%fusion.2", "%while.1"]


def test_scope_components_hold_in_order_and_the_op_name_is_no_scope():
    path = "jit(svd)/eig/jit(block_jacobi_eigh)/while/body/rotate/dot"
    assert scopes.holds(path, "eig/rotate")
    assert scopes.holds(path, "eig")
    assert not scopes.holds(path, "rotate/eig")
    assert not scopes.holds(path, "dot")
    assert not scopes.holds("jit(f)/jit(cholesky)/cholesky", "cholesky")
    assert not scopes.holds("jit(svd)/jit(eigh)/eigh", "eig")


def test_outermost_rule_counts_a_while_and_its_body_once(hand_run):
    # the while holds trsm, so its body's trsm ops are not counted again
    assert scopes.scope_ops(hand_run, "trsm") == ["%while.1"]
    assert scopes.scope_seconds(hand_run, "trsm") == pytest.approx(1.5)
    assert scopes.op_events(hand_run, "trsm") == [3.0]
    # a scope that only the body holds is counted in the body
    assert scopes.scope_ops(hand_run, "eig/rotate") == ["%rot.1"]
    assert scopes.scope_seconds(hand_run, "gram") == pytest.approx(0.5)


def test_a_scope_that_no_op_holds_reads_none(hand_run):
    # the Cholesky op is named by its function alone, in no scope
    assert scopes.scope_seconds(hand_run, "cholesky") is None
    assert scopes.op_events(hand_run, "u_qv") is None


def test_cover_is_per_call_and_names_what_falls_in_no_layer(hand_run):
    covered, busy, outside = scopes.cover(hand_run,
                                          scopes.scope_map(hand_run))
    assert covered == pytest.approx((3.0 + 1.0) / 2)
    assert busy == pytest.approx((3.0 + 1.0 + 0.4 + 0.2) / 2)
    assert outside == [("%chol.1", 0.2), ("%copy.1", 0.1)]


def test_untraced_or_chipless_run_reads_none(monkeypatch):
    monkeypatch.setattr(scopes, "compiled_text", lambda run: (HLO, 0.0))
    assert scopes.scope_seconds(FakeRun(None, calls=2), "trsm") is None
    empty = trace.TraceSummary(chips=0, busy_s=0.0, kernel_s={},
                               kernel_calls={}, idle_gaps=[])
    assert scopes.scope_seconds(FakeRun(empty, calls=2), "trsm") is None


def test_a_module_that_cannot_be_rebuilt_reads_none(monkeypatch, hand_run):
    def fail(run):
        raise RuntimeError("no plan")

    monkeypatch.setattr(scopes, "compiled_text", fail)
    assert scopes.scope_seconds(hand_run, "trsm") is None


SWEEP_HLO = """HloModule jit_svd

%rounds (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %eigh.1 = f32[4]{0} custom-call(%p), custom_call_target="EighTpu", metadata={op_name="jit(svd)/eig/while/body/while/body/small_eigh/eigh"}
  ROOT %sort.1 = f32[4]{0} sort(%eigh.1), metadata={op_name="jit(svd)/eig/while/body/while/body/small_eigh/sort"}
}

%sweeps (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %while.2 = f32[4]{0} while(%p), condition=%c, body=%rounds, metadata={op_name="jit(svd)/eig/while/body/while"}
  %test.1 = f32[4]{0} fusion(%while.2), kind=kLoop, calls=%f, metadata={op_name="jit(svd)/eig/while/body/sweep_test/reduce_sum"}
  ROOT %test.2 = f32[] sqrt(%test.1), metadata={op_name="jit(svd)/eig/while/body/sweep_test/sqrt"}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  ROOT %while.1 = f32[4]{0} while(%x), condition=%c, body=%sweeps, metadata={op_name="jit(svd)/eig/while"}
}
"""


@pytest.mark.parametrize("rounds_each,expect", [(27, 9.0), (24, None)])
def test_eig_sweeps_needs_the_two_counts_to_agree(monkeypatch, rounds_each,
                                                  expect):
    # n = 512, nb = 128: b = 4 blocks, 3 rounds a sweep; 2 calls
    monkeypatch.setattr(scopes, "compiled_text",
                        lambda run: (SWEEP_HLO, 0.0))
    events = {"%while.1": 2, "%while.2": 18, "%test.1": 18, "%test.2": 18,
              "%eigh.1": 2 * rounds_each, "%sort.1": 2 * rounds_each}
    run = FakeRun(_summary({op: 1.0 for op in events}, events), calls=2,
                  config={"n": 512, "solver": {"nb": 128}})
    assert harness.load_reader("eig_sweeps")(run) == expect
