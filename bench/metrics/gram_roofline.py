"""Share of its roofline that the Pallas Gram kernel reaches: the least
time of all its calls in the traced window (bench.counts, peaks by
device kind) over their device time.

Each call's operand shape is read from its HLO text in the trace
(``custom-call(f32[M,N] ...)``); a dimension that the kernel's wrapper
padded past the plan's own is counted at the plan's size, so the work
counted is the least the call needs."""

import re

from bench import counts

KERNEL = "gram_kernel"
OPERAND = re.compile(r"custom-call\(\w+\[(\d+),(\d+)\]")


def read(run):
    if run.summary is None or "plan_shape" not in run.values:
        return None
    pm, pn = run.values["plan_shape"]
    least = seconds = 0.0
    for op in run.summary.ops_named(KERNEL):
        shape = OPERAND.search(run.summary.kernel_text.get(op, ""))
        if shape is None:
            return None
        m, n = min(int(shape.group(1)), pm), min(int(shape.group(2)), pn)
        t, _ = counts.roofline_s(counts.gram_flops(m, n),
                                 counts.gram_bytes(m, n), run.device["kind"])
        least += run.summary.kernel_calls[op] * t
        seconds += run.summary.kernel_s[op]
    if seconds <= 0:
        return None
    return 100.0 * least / seconds
