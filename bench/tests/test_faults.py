"""A run with its timed path broken underneath must read not correct;
a sound run at the same small size must read correct."""

import pytest

import repro.solver.planner as planner
from bench import harness
from bench.tests import small


def _run(cell):
    run = harness.Run(cell, small.SEED, small.SECONDS, False,
                      overrides=small.OVERRIDES[cell])
    return harness.execute(run, require_chip=False)


@pytest.mark.parametrize("cell", sorted(small.OVERRIDES))
def test_sound_run_is_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert "setup_s" in res["metrics"]


def _alter_polar(monkeypatch):
    orig = planner.SvdPlan.polar

    def polar(self, a, want_h=True):
        q, h, info = orig(self, a, want_h=want_h)
        return q.at[0, 0].add(1e-3), h, info

    monkeypatch.setattr(planner.SvdPlan, "polar", polar)


def _alter_svd(monkeypatch):
    orig = planner.SvdPlan.svd

    def svd(self, a):
        u, s, vh = orig(self, a)
        return u, s.at[0].multiply(1.0 + 1e-3), vh

    monkeypatch.setattr(planner.SvdPlan, "svd", svd)


@pytest.mark.parametrize("cell,fault", [
    ("nemeth03.pd", _alter_polar),
    ("nemeth03.svd", _alter_svd),
])
def test_fault_reads_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    res = _run(cell)
    assert not res["correct"], res["checks"]


def test_no_chip_no_result():
    run = harness.Run("nemeth03.pd", small.SEED, small.SECONDS, False,
                      overrides=small.OVERRIDES["nemeth03.pd"])
    with pytest.raises(harness.NoChip):
        harness.execute(run)
