"""The control (the reference at three-pass bf16, in the program's
place) must come out not correct under each cell's committed limits,
by the harness's own judgment."""

import pytest

from bench import calibrate
from bench.tests import small


@pytest.mark.parametrize("cell", sorted(small.OVERRIDES))
def test_control_fails_a_limit(cell):
    correct, checks = calibrate.control_verdict(
        cell, small.SEED, require_chip=False,
        overrides=small.OVERRIDES[cell])
    assert not correct, checks
    assert any(c["value"] > c["limit"] for c in checks.values()), checks
