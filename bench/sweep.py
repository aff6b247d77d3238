"""Find the serving knee once, by a sweep on the chip.

    python bench/sweep.py --workload <serve cell> --seed n --seconds S \
        --rates 4,6,8,...

One engine, one process: for each rate the cell's mix is offered for
``S`` seconds at that rate, then whatever is left is drained out. Prints
one JSON line per rate: requests due and finished, time to first token
and gap between tokens (p50, p95), completed tokens per second, and the
backlog (requests submitted but not admitted) averaged over the second
and the last quarter of the window. The knee is the highest rate whose
backlog does not grow: last quarter's mean at most one request above the
second quarter's.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(root), str(root / "src")]
    import numpy as np

    from bench import spec
    from bench.device import devices
    from bench.drivers.serve import serve_window, setup
    from bench.harness import enable_cache
    from bench.traffic.open_loop_chat import schedule

    cell = spec.cell(args.workload)
    devices(cell["chips"])
    enable_cache()
    c = cell["config_spec"]
    vocab = spec.module("models", c["model_type"]).vocab(c)
    engine, _ = setup(cell, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        reqs = schedule(cell["traffic_spec"], args.seed, args.seconds,
                        vocab, rate_per_s=rate)
        w = serve_window(engine, reqs, args.seconds)
        engine.drain_requests()
        q = np.asarray(w["queue"] or [(0.0, 0)])

        def quarter(k):
            lo, hi = k * w["window_s"] / 4, (k + 1) * w["window_s"] / 4
            sel = (q[:, 0] >= lo) & (q[:, 0] < hi)
            return float(q[sel, 1].mean()) if sel.any() else 0.0
        tokens = sum(len(t) for t in w["finished"].values())
        print(json.dumps({
            "rate": rate, "due": len(w["due"]),
            "finished": len(w["finished"]),
            "ttft_ms": [1e3 * float(np.percentile(w["ttft_s"], p))
                        for p in (50, 95)],
            "itl_ms": [1e3 * float(np.percentile(w["itl_s"] or [0], p))
                       for p in (50, 95)],
            "tokens_per_s": tokens / w["window_s"],
            "backlog_q2": quarter(1), "backlog_q4": quarter(3),
            "late_p95_s": float(np.percentile(w["late_s"] or [0], 95))}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
