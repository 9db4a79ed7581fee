"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Wall-clock rows are CPU
medians (the container has no TPU); structural rows (iteration counts,
flop models, accuracy, roofline terms from the dry-run) are the
hardware-transferable results.  See EXPERIMENTS.md for interpretation.

  PYTHONPATH=src python -m benchmarks.run [--only iterations,fig2,...]
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("JAX_ENABLE_X64", "1")

from benchmarks import (  # noqa: E402
    accuracy,
    comm_calibrate,
    grouped_scaling,
    iterations,
    kernels_bench,
    pd_compare,
    roofline,
    structured_qr_bench,
    svd_compare,
    svd_serve,
    svd_topk,
)

SUITES = {
    "iterations": iterations.run,       # paper Tables 1, 5, 10
    "structured_qr": structured_qr_bench.run,  # paper Table 2
    "svd_compare": svd_compare.run,     # paper Tables 4, 9
    "pd_compare": pd_compare.run,       # paper Table 6
    "accuracy": accuracy.run,           # paper Figure 2
    "kernels": kernels_bench.run,       # Pallas kernel parity
    "grouped_scaling": grouped_scaling.run,  # Alg. 3 (r, sep) sweep
    "comm_calibrate": comm_calibrate.run,  # psum cost per word
    "svd_serve": svd_serve.run,         # serving solves/s + latency
    "svd_topk": svd_topk.run,           # partial-spectrum vs dense slice
    "roofline": roofline.run,           # §Roofline summary (from dry-run)
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    args = ap.parse_args()
    names = (args.only.split(",") if args.only else list(SUITES))
    print("name,us_per_call,derived")
    broken = []
    for name in names:
        try:
            SUITES[name]()
        except Exception as e:  # keep the harness going; report the break
            broken.append(name)
            print(f"{name}.ERROR,0.0,{type(e).__name__}:{str(e)[:120]}",
                  flush=True)
    if broken:
        sys.exit(f"broken suites: {','.join(broken)}")


if __name__ == "__main__":
    main()
