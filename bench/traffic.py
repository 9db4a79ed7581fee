"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<name>.json`` and turns them, with the seed, into the
work of one run.

Keys of a mix:

* ``op``: what the one client of a closed loop (call, wait for the
  answer, call again) calls, ``"polar"`` or ``"svd"``.

Every seed gets the same work, one matrix of the configuration's size
and spectrum; the seed draws its orthogonal factors
(:mod:`bench.matrices`) and which of the window's first calls is
compared with the reference.
"""

from __future__ import annotations

import zlib

import numpy as np


def rng(seed: int, name: str) -> np.random.Generator:
    """A generator from the whole ``--seed`` (any size) and a name."""
    seed = int(seed)
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32,
                                  zlib.crc32(name.encode())])
