"""Operations and bytes that a kernel's call needs at least, from its
shapes: the work any implementation must do, so that a later kernel
that computes one triangle, or runs in bf16, cannot read over 100% of
its roofline."""

from __future__ import annotations

from bench import peaks


def gram_flops(m: int, n: int) -> float:
    """G = A^T A + c I for A of shape (m, n): n (n + 1) / 2 distinct
    entries of the symmetric result, 2 m operations each."""
    return float(m) * n * (n + 1)


def gram_bytes(m: int, n: int, itemsize: int = 4) -> float:
    """Read A once, write the (n, n) result once."""
    return float(itemsize) * (m * n + n * n)


def roofline_s(flops: float, nbytes: float, device_kind: str):
    """(least seconds, "compute" or "memory"): the larger of the time
    the peak FLOP/s and the time the peak bandwidth allow."""
    p = peaks.peak(device_kind)
    t_c = flops / p["flops_per_s"]
    t_m = nbytes / p["bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
