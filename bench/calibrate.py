#!/usr/bin/env python3
"""The control's readings of a cell, judged as a run's are.

    python3 bench/calibrate.py --workload <name> --seeds 1 2 3

For each seed, makes the cell's inputs as a run does, at the cell's own
sizes, puts the control in the program's place (the reference computed
at the three-pass bf16 precision, ``"high"``) and judges its numbers
against the cell's committed limits with :func:`bench.harness.judge`,
the comparison that decides a run's ``correct``.  Prints one JSON line
per seed with the verdict and each number beside its limit; the control
has to come out not correct.  The program's own readings are those of
the cell's runs (``bench/run.py``).  Needs the chip, as a run does.
"""

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def control_verdict(cell: str, seed: int, require_chip: bool = True,
                    overrides=None):
    """(correct, checks) of the control on one seed of ``cell``."""
    import importlib

    import jax

    from bench import harness

    run = harness.Run(cell, seed, 0.0, False, overrides=overrides)
    harness.use_cache()
    devs = (harness.require_chips(run.cell["chips"]) if require_chip
            else jax.devices())
    driver = importlib.import_module(
        f"bench.drivers.{run.config['driver']}")
    st = driver.prepare(run, devs)
    return harness.judge(driver.control(run, st), run.limits)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        correct, checks = control_verdict(args.workload, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": True, "correct": correct,
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
