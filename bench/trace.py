"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

The JAX profiler writes one ``.xplane.pb`` per traced window under
``<dir>/plugins/profile/<time>/``; :func:`jax.profiler.ProfileData`
reads it.  On a TPU each chip is a plane named ``/device:TPU:<i>``,
whose line ``XLA Ops`` holds one event per operation that ran on the
device (a Pallas kernel is the custom call that names it).  Host
threads are lines of the plane ``/host:CPU``; the benchmark's own
spans (``jax.profiler.TraceAnnotation``) are events there, on the same
clock.

* busy time: the union of the intervals of a chip's ``XLA Ops``
  events, averaged over the chips traced;
* per-kernel time: the sum of the durations of the events of each
  operation, over all chips; an event's name is the HLO instruction's
  text, and the operation is its name before `` = `` (a Pallas kernel's
  custom call is named after the kernel's Python function,
  ``%gram_kernel_call.<i>``);
* idle gaps: the stretches between busy intervals, each named by the
  innermost benchmark span (name prefix ``bench:``) that was open on the
  host at the gap's midpoint.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"


@dataclasses.dataclass
class TraceSummary:
    chips: int
    busy_s: float                 # union of op intervals, mean over chips
    kernel_s: Dict[str, float]    # event name -> seconds, summed over chips
    kernel_calls: Dict[str, int]  # event name -> number of events
    idle_gaps: List[Tuple[str, float]]  # (host span, seconds), longest first
    kernel_text: Dict[str, str] = dataclasses.field(default_factory=dict)
    # op name -> its HLO instruction text (shapes of result and operands)

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        ops = sorted(self.kernel_s.items(), key=lambda kv: -kv[1])
        return [[name, s] for name, s in ops[:k]]

    def ops_named(self, needle: str) -> List[str]:
        """The ops whose name holds ``needle``."""
        return [n for n in self.kernel_s if needle in n]


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``."""
    return event_name.split(" = ", 1)[0]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint ones, in order."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(merged: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    return [(a[1], b[0]) for a, b in zip(merged, merged[1:]) if b[0] > a[1]]


def _host_spans(planes) -> List[Tuple[float, float, str]]:
    spans = []
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name[len(SPAN_PREFIX):]))
    return spans


def _label(spans, t: float) -> str:
    """The innermost (shortest) span open at time ``t``."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "outside any span"


def summarize(path: str, max_gaps: int = 10) -> TraceSummary:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    planes = list(data.planes)
    busy, chips = 0.0, 0
    kernel_s: Dict[str, float] = {}
    kernel_calls: Dict[str, int] = {}
    kernel_text: Dict[str, str] = {}
    all_gaps: List[Tuple[float, float]] = []
    for plane in planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        ivals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                ivals.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                name = op_name(ev.name)
                kernel_s[name] = kernel_s.get(name, 0.0) + \
                    ev.duration_ns * 1e-9
                kernel_calls[name] = kernel_calls.get(name, 0) + 1
                kernel_text.setdefault(name, ev.name)
        if not ivals:
            continue
        chips += 1
        merged = union(ivals)
        busy += sum(e - s for s, e in merged) * 1e-9
        all_gaps += gaps(merged)
    spans = _host_spans(planes)
    all_gaps.sort(key=lambda g: g[0] - g[1])
    named = [(_label(spans, (s + e) / 2), (e - s) * 1e-9)
             for s, e in all_gaps[:max_gaps]]
    return TraceSummary(chips=chips, busy_s=busy / chips if chips else 0.0,
                        kernel_s=kernel_s, kernel_calls=kernel_calls,
                        idle_gaps=[[n, s] for n, s in named],
                        kernel_text=kernel_text)


def reduce_dir(trace_dir: str) -> TraceSummary:
    return summarize(find_xplane(trace_dir))


def idle_percent(run):
    """Share of a run's traced window in which no operation ran on the
    device: 1 - (union of the device's op intervals) / (traced window);
    None where the run was not traced or its trace holds no device."""
    if run.summary is None or not run.summary.chips or not run.window_s:
        return None
    return 100.0 * (1.0 - run.summary.busy_s / run.window_s)
