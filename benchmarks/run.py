"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (also saved under
``benchmarks/results/*.csv``). ``--full`` uses the paper-scale settings
(10k-step DES horizons, 1000-trial Monte-Carlo) — hours on CPU;
the default quick mode validates every claim at reduced scale in
minutes.

DES/Monte-Carlo suites (fig6/fig7/fig8/tablesC) run on the scenario
campaign runner and fan out across ``--jobs`` worker processes with
deterministic per-cell seeding (results identical at any worker count).

  PYTHONPATH=src python -m benchmarks.run [--full] [--only fig6,table2]
                                          [--jobs 4]
"""
from __future__ import annotations

import argparse
import inspect
import sys
import time

from . import (
    fig4_mu,
    fig5_overhead,
    fig6_time_to_train,
    fig7_availability,
    fig8_stacks,
    rectlr_bench,
    roofline,
    table2_min_ttt,
    tables_c_montecarlo,
)

SUITES = {
    "fig4": fig4_mu,
    "fig5": fig5_overhead,
    "fig6": fig6_time_to_train,
    "fig7": fig7_availability,
    "fig8": fig8_stacks,
    "table2": table2_min_ttt,
    "tablesC": tables_c_montecarlo,
    "rectlr": rectlr_bench,
    "roofline": roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="paper-scale horizons/trials (slow)")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    ap.add_argument("--jobs", type=int, default=1,
                    help="worker processes for campaign-backed suites")
    args = ap.parse_args()

    names = (args.only.split(",") if args.only else list(SUITES))
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    for name in names:
        if name not in SUITES:
            print(f"# unknown suite {name!r}; have {sorted(SUITES)}",
                  file=sys.stderr)
            continue
        t1 = time.perf_counter()
        run_fn = SUITES[name].run
        kw = ({"jobs": args.jobs}
              if "jobs" in inspect.signature(run_fn).parameters else {})
        for row in run_fn(quick=not args.full, **kw):
            print(row)
        print(f"# {name} done in {time.perf_counter() - t1:.1f}s", file=sys.stderr)
    print(f"# all suites done in {time.perf_counter() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
