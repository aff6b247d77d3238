"""Training cells whose comparison also reads the first gradient itself.

A cell runs exactly as under the ``train`` driver (its ``setup``,
``window`` and ``run``), and ``correct`` reads one more number,
``grad_vec_gap``: over leaves, the norm of the difference between the
program's first gradient (as its optimizer received it) and the plain
reference's (clipped), over the larger of the reference leaf's norm and
the median leaf's.

The ``train`` driver's numbers are norms. Where every sequence's
gradient points almost the same way, a batch that leaves half of its
sequences out moves each leaf's norm by no more than bf16 rounding
does, while it turns the gradient by several per cent. Mamba-2 at its
seeded init over uniform random tokens is such a model: its
per-sequence gradients lie at cosine 0.99 in every leaf (PERF.md
section 4).

Both gradients are caught where the ``train`` driver and
``bench.reftrain`` take the norms of the first gradient (their first
``leaf_norms`` call) and copied to the host, so that the device holds
what it always held.

    python -m bench.drivers.train_vectors --workload <cell> \
        --seeds a,b,... [--control-seeds x,y,...]

prints the readings that set a cell's limits, one JSON line each, as
``bench/calibrate.py`` does for ``train`` cells, with this number beside
the other three.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

from bench import reftrain, spec
from bench.drivers import train
from bench.drivers.train import setup, window

__all__ = ["setup", "window", "run", "vector_gap", "calibrate"]


def _to_host(layout, tree, scale: float) -> list:
    import jax
    return [np.asarray(x, np.float32) * np.float32(scale)
            for x in jax.tree.leaves(reftrain._slice_rows(layout, tree))]


@contextlib.contextmanager
def _first_gradient(module):
    """While open, the first tree whose norms ``module.leaf_norms``
    computes, times its scale, is copied to the host into the list
    yielded."""
    norms = module.leaf_norms
    caught = []

    def catch(layout, tree, scale: float = 1.0):
        if not caught:
            caught.append(_to_host(layout, tree, scale))
        return norms(layout, tree, scale)

    module.leaf_norms = catch
    try:
        yield caught
    finally:
        module.leaf_norms = norms


def vector_gap(prog: list, ref: list, names: list) -> tuple[float, str]:
    """(gap, leaf): the largest over leaves of |prog - ref| over the
    larger of |ref| and the median leaf's |ref|."""
    def norm(x):
        return float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))
    diff = np.array([norm(a - b) for a, b in zip(prog, ref)])
    size = np.array([norm(b) for b in ref])
    gaps = diff / np.maximum(size, np.median(size))
    worst = int(np.argmax(gaps))
    return float(gaps[worst]), names[worst]


def _names(cell: dict) -> list:
    from bench.weights import leaf_names
    c = cell["config_spec"]
    return leaf_names(spec.module("models", c["model_type"]).layout(c))


def run(ctx) -> dict:
    """One run of the cell: the ``train`` driver's, judged also on
    ``grad_vec_gap``."""
    from bench.correct import judge

    with _first_gradient(train) as prog, _first_gradient(reftrain) as ref:
        rec = train.run(ctx)
    gap, leaf = vector_gap(prog[0], ref[0], _names(ctx.cell))
    ctx.log(f"first gradient's vector: worst at {leaf}")
    numbers = {k: c["value"] for k, c in rec["checks"].items()}
    numbers["grad_vec_gap"] = gap
    rec["correct"], rec["checks"] = judge(numbers, ctx.cell["limits"])
    return rec


def calibrate(cell: dict, seeds, control_seeds) -> None:
    """``program`` lines for ``seeds``; for ``control_seeds`` also
    ``control`` (the reference in float8 in the program's place) and
    ``fault_half`` (the reference without the second half of every
    batch)."""
    from bench.correct import train_numbers

    names = _names(cell)

    def line(kind, seed, numbers, where, vec, **extra):
        numbers = dict(numbers, grad_vec_gap=vec[0])
        where = dict(where, grad_vec_gap=vec[1])
        print(json.dumps({"kind": kind, "seed": seed, "numbers": numbers,
                          "where": where, **extra}), flush=True)

    for seed in seeds:
        t0 = time.perf_counter()
        with _first_gradient(train) as prog:
            trainer, report, nums = setup(cell, seed)
        del trainer, report
        gc.collect()
        with _first_gradient(reftrain) as ref:
            full = reftrain.reference_steps(cell, seed)
        numbers, where = train_numbers(nums, full)
        line("program", seed, numbers, where,
             vector_gap(prog[0], ref[0], names),
             seconds=time.perf_counter() - t0)
        del prog
        if seed in control_seeds:
            for kind, kw in (("control", {"mm": "f8"}),
                             ("fault_half", {"rows": "half"})):
                with _first_gradient(reftrain) as low:
                    other = reftrain.reference_steps(cell, seed, **kw)
                numbers, where = train_numbers(other, full)
                line(kind, seed, numbers, where,
                     vector_gap(low[0], ref[0], names))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(root), str(root / "src")]
    from bench.device import devices
    from bench.harness import enable_cache

    cell = spec.cell(args.workload)
    devices(cell["chips"])
    enable_cache()
    calibrate(cell, [int(s) for s in args.seeds.split(",")],
              {int(s) for s in args.control_seeds.split(",") if s})
    return 0


if __name__ == "__main__":
    sys.exit(main())
