"""Readings that set a cell's correctness limits (run on the chip).

    python bench/calibrate.py --workload <cell> --seeds a,b,... \
        --control-seeds x,y,z [--seconds S]

Prints one JSON line per reading, all in one process so that set-up and
compiles are paid once:

- ``program``: the timed path's numbers against the plain reference,
  one line per seed (the lower reading is the largest over them);
- ``control``: the reference in float8 put in the program's place (the
  upper reading is the smallest over the control seeds);
- training only, ``fault_half``: the reference that leaves half of every
  batch out and takes the mean over the rest, in the program's place;
- cells on several chips only, ``fault_exchange``: the program with the
  gradient exchange between chips left out (each chip keeps its own
  partial gradient);
- serving only, ``fault_token``: one served token swapped for another.

A state left unchanged reads 1 on ``grad_gap`` and ``change_gap`` by
their definition and needs no run. Limits are then written into the
cell's file by hand, with the readings in PERF.md.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path


def _print(kind: str, seed: int, numbers: dict, **extra) -> None:
    print(json.dumps({"kind": kind, "seed": seed, "numbers": numbers,
                      **extra}), flush=True)


def train(cell: dict, seeds, control_seeds) -> None:
    from bench.correct import train_numbers
    from bench.drivers.train import setup
    from bench.reftrain import reference_steps

    for seed in seeds:
        t0 = time.perf_counter()
        trainer, report, prog = setup(cell, seed,
                                      mesh=cell.get("executor") is not None)
        if hasattr(trainer, "close"):
            trainer.close()
        del trainer, report
        gc.collect()
        ref = reference_steps(cell, seed)
        numbers, where = train_numbers(prog, ref)
        _print("program", seed, numbers, where=where,
               losses=prog["losses"], ref_losses=ref["losses"],
               seconds=time.perf_counter() - t0)
        if seed in control_seeds:
            for kind, kw in (("control", {"mm": "f8"}),
                             ("fault_half", {"rows": "half"})):
                low = reference_steps(cell, seed, **kw)
                numbers, where = train_numbers(low, ref)
                _print(kind, seed, numbers, where=where)
            if cell.get("executor") is not None:
                numbers, where = _no_exchange(cell, seed, ref)
                _print("fault_exchange", seed, numbers, where=where)


def _no_exchange(cell: dict, seed: int, ref: dict):
    """The mesh program with its bucketed gradient sync made a no-op."""
    from bench.correct import train_numbers
    from bench.drivers.train import setup
    from repro.dist.collectives import BucketedAllReduce

    sync = BucketedAllReduce.__call__
    BucketedAllReduce.__call__ = lambda self, grads: grads
    try:
        trainer, report, prog = setup(cell, seed, mesh=True)
    finally:
        BucketedAllReduce.__call__ = sync
    trainer.close()
    del trainer, report
    gc.collect()
    return train_numbers(prog, ref)


def serve(cell: dict, seeds, control_seeds, seconds: float) -> None:
    import numpy as np

    from bench import spec
    from bench.drivers.serve import (_sample, reference_gaps, serve_window,
                                     setup)
    from bench.traffic.open_loop_chat import schedule
    from bench.weights import generate

    c = cell["config_spec"]
    model_mod = spec.module("models", c["model_type"])
    engine, cache = setup(cell, seeds[0])
    for seed in seeds:
        if engine.params is None:
            engine.params = generate(model_mod.layout(c), seed)
        reqs = schedule(cell["traffic_spec"], seed, seconds,
                        model_mod.vocab(c))
        w = serve_window(engine, reqs, seconds)
        engine.drain_requests()
        by_id = {r.idx: r for r in reqs}
        picked = _sample(w, by_id, seed, cell["sample_requests"])
        items = [(by_id[r].tokens, np.asarray(w["finished"][r]))
                 for r in picked]
        engine.params = None
        gc.collect()
        gaps = reference_gaps(cell, seed, items)
        _print("program", seed, {"served_gap": max(gaps)}, gaps=gaps,
               served=sum(len(s) for _, s in items),
               finished=len(w["finished"]), due=len(w["due"]))
        if seed in control_seeds:
            gaps = reference_gaps(cell, seed, items, judge_mm="f8")
            _print("control", seed, {"served_gap": max(gaps)}, gaps=gaps)
            prompt, served = items[0]
            served = served.copy()
            served[1] = (served[1] + 1) % model_mod.vocab(c)
            gaps = reference_gaps(cell, seed, [(prompt, served)])
            _print("fault_token", seed, {"served_gap": max(gaps)})
    del engine, cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(root), str(root / "src")]
    from bench import spec
    from bench.device import devices
    from bench.harness import enable_cache

    cell = spec.cell(args.workload)
    devices(cell["chips"])
    enable_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    if cell["driver"] == "train":
        train(cell, seeds, controls)
    else:
        serve(cell, seeds, controls, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
