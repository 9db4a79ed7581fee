"""The paper's own experimental matrices (Tables 3 and 8), synthesized.

The UF sparse-collection matrices are not downloadable offline, so each is
matched by a synthetic matrix with the same dimension and 2-norm condition
number (geometric singular-value spectrum, Haar-random singular vectors).
Zolo-SVD is a dense direct method (paper §3.2: sparsity is not exploited),
so dimension + conditioning determine both cost and numerical difficulty.
CPU-sized stand-ins (n scaled down, same kappa) drive the wall-clock
benchmarks; full-sized entries drive flop/roofline accounting.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class SvdMatrixConfig:
    name: str
    n: int
    cond: float
    cpu_n: int  # reduced size for CPU wall-clock runs
    r_paper: int  # the paper's r choice (Table 3) or 2 (Tables 8/9)


# Table 3 (Example 1) + Table 8 (Example 3).
MATRICES = {
    "nemeth03": SvdMatrixConfig("nemeth03", 9_506, 1.29e0, 768, 2),
    "fv1": SvdMatrixConfig("fv1", 9_604, 1.40e1, 768, 3),
    "linverse": SvdMatrixConfig("linverse", 11_999, 9.06e3, 768, 4),
    "bcsstk18": SvdMatrixConfig("bcsstk18", 11_948, 3.46e11, 768, 2),
    "c-47": SvdMatrixConfig("c-47", 15_343, 3.16e8, 768, 2),
    "c-49": SvdMatrixConfig("c-49", 21_132, 6.02e8, 768, 2),
    "cvxbqp1": SvdMatrixConfig("cvxbqp1", 50_000, 1.09e11, 768, 2),
    "rand1": SvdMatrixConfig("rand1", 10_000, 3.97e7, 768, 2),
    "rand2": SvdMatrixConfig("rand2", 30_000, 1.24e7, 768, 2),
}

# Structured-QR benchmark shapes (paper Table 2).
QR_SHAPES = [(10_000, 5_000), (20_000, 10_000)]
QR_CPU_SHAPES = [(1_536, 768), (3_072, 1_536)]


def spectrum(name: str, *, cpu_size: bool = True,
             n: Optional[int] = None) -> np.ndarray:
    """The exact singular values :func:`synthesize` builds in, descending
    (geometric from 1 to 1/kappa); ``n`` overrides the size."""
    if name not in MATRICES:
        raise ValueError(f"unknown paper matrix {name!r}; known: "
                         f"{sorted(MATRICES)}")
    cfg = MATRICES[name]
    if n is None:
        n = cfg.cpu_n if cpu_size else cfg.n
    return np.geomspace(1.0, 1.0 / cfg.cond, n)


def synthesize(name: str, *, cpu_size: bool = True, n: Optional[int] = None,
               dtype=np.float64, seed: int = 0) -> np.ndarray:
    """Dense synthetic stand-in with matched n (or cpu_n, or the given
    ``n``) and kappa_2.

    The matrix is a function of (name, size, seed) alone: the per-matrix
    seed offset is a CRC of the name, not ``hash()``, which Python
    randomizes per process."""
    s = spectrum(name, cpu_size=cpu_size, n=n)
    n = s.shape[0]
    rng = np.random.default_rng(seed + zlib.crc32(name.encode()) % (2 ** 16))
    # Haar-random U, V via QR of Gaussian
    u, _ = np.linalg.qr(rng.standard_normal((n, n)))
    v, _ = np.linalg.qr(rng.standard_normal((n, n)))
    return (u * s) @ v.T if dtype == np.float64 else \
        ((u * s) @ v.T).astype(dtype)
