"""One run of one benchmark cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero without a result line when JAX's first device is not a
TPU in the peak table, when the cell needs more chips than JAX sees, or
when the program's sources (``src/``) are not beside ``bench/``.
See ``bench/__init__.py`` for how cells are found.
"""
import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


if __name__ == "__main__":
    args = parse()
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from bench.harness import main_result
    sys.exit(main_result(args, T_START))
