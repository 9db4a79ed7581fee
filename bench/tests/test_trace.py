"""The reduction from a profiler trace to device metrics, checked on
hand-made intervals and on a small trace recorded on a TPU v5e (one
Pallas Gram kernel call at 1024 x 1024 and one f32 matmul, three times
each, under ``bench:`` host spans)."""

import pathlib

import pytest

from bench import trace

SMALL = pathlib.Path(__file__).resolve().parents[1] / "testdata" / \
    "small.xplane.pb"


def test_union_merges_overlaps_and_keeps_order():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4), (8, 9)]) == \
        [(0, 4), (5, 7), (8, 9)]
    assert trace.union([(0, 10), (2, 3)]) == [(0, 10)]
    assert trace.union([]) == []


def test_gaps_between_busy_intervals():
    assert trace.gaps([(0, 4), (5, 7), (8, 9)]) == [(4, 5), (7, 8)]


def test_label_is_the_innermost_open_span():
    spans = [(0, 100, "outer"), (10, 20, "inner")]
    assert trace._label(spans, 15) == "inner"
    assert trace._label(spans, 50) == "outer"
    assert trace._label(spans, 150) == "outside any span"


@pytest.fixture(scope="module")
def small():
    return trace.summarize(str(SMALL))


def test_recorded_trace_busy_time(small):
    # one chip; the ops do not overlap, so the union is their sum:
    # 3 Gram kernel calls, 3 matmul fusions and the Gram program's
    # prefetch copies, in nanoseconds as the profiler recorded them
    assert small.chips == 1
    assert small.busy_s == pytest.approx(477_764e-9, abs=1e-12)
    assert small.busy_s == pytest.approx(sum(small.kernel_s.values()),
                                         abs=1e-12)


def test_recorded_trace_kernel_times(small):
    assert small.kernel_calls == {"%gram_kernel_call.1": 3, "%fusion": 3,
                                  "%copy-start": 3, "%copy-done": 3}
    assert small.kernel_s["%gram_kernel_call.1"] == pytest.approx(
        236_322e-9, abs=1e-12)
    assert small.kernel_s["%fusion"] == pytest.approx(223_126e-9, abs=1e-12)
    assert small.ops_named("gram_kernel") == ["%gram_kernel_call.1"]


def test_recorded_trace_idle_gaps_are_named_by_host_span(small):
    names = {name for name, _ in small.idle_gaps}
    assert names <= {"gram", "matmul"}
    secs = [s for _, s in small.idle_gaps]
    assert secs == sorted(secs, reverse=True)
    assert secs[0] == pytest.approx(921_618e-9, abs=1e-12)


def test_gram_roofline_reader_on_recorded_trace(small):
    from bench import harness

    class Run:
        summary = small
        values = {"plan_shape": (4096, 4096)}
        device = {"kind": "TPU v5 lite"}

    share = harness.load_reader("gram_roofline")(Run)
    # at n = 1024 the bytes bound it: 8 n^2 bytes at 819 GB/s take
    # longer than n^2 (n + 1) operations at 197 TFLOP/s
    least = max(3 * 1024 * 1024 * 1025 / 197e12,
                3 * 4 * 2 * 1024 * 1024 / 819e9)
    assert least == 3 * 4 * 2 * 1024 * 1024 / 819e9
    assert share == pytest.approx(100 * least / 236_322e-9)
    assert 0 < share < 100
    # a plan smaller than the operand counts the plan's size
    Run.values = {"plan_shape": (1000, 1000)}
    small_least = max(3 * 1000 * 1000 * 1001 / 197e12,
                      3 * 4 * 2 * 1000 * 1000 / 819e9)
    assert harness.load_reader("gram_roofline")(Run) == pytest.approx(
        100 * small_least / 236_322e-9)
