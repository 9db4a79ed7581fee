"""The benchmark's own tests: run on the CPU at small sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _cache_dir(tmp_path_factory, monkeypatch):
    """Keep the CPU tests' compile cache out of the checkout, whose
    ``.jax_cache`` is the chip runs' cache."""
    from bench import harness

    monkeypatch.setattr(harness, "CACHE_DIR",
                        tmp_path_factory.getbasetemp() / "jax_cache")
