"""Serving launcher: continuous-batching decode over SPARe-masked replicas.

``python -m repro.launch.serve --arch qwen2.5-3b --requests 16`` runs the
full serving tier end to end: a deterministic
:class:`~repro.data.pipeline.RequestStream` feeds a
:class:`~repro.serve.replicas.ReplicaServer` (paged KV cache, fused
prefill, per-slot decode), optionally under a live failure campaign:

    python -m repro.launch.serve --arch qwen2.5-3b --requests 16 \\
        --replicas 3 \\
        --failure-model '{"kind": "correlated", "scope": "rack",
                          "burst_prob": 1.0, "mtbf": 400.0}'

Reports aggregate tokens/s, p50/p99 per-token latency, and the replica
event log; exits non-zero if any admitted request failed to complete
while a replica survived, or if anything compiled after warmup (the
SPARe no-recompile gate). ``benchmarks/serving_bench.py`` wraps the same
loop to record healthy-vs-degraded numbers in ``BENCH_serving.json``.

Model size follows :mod:`repro.launch.common`: the reduced config by
default, ``--no-smoke`` for the published widths, ``--layers K`` to cut
the depth. The persistent compile cache is on.
"""
from __future__ import annotations

import argparse
import json
import time

from repro.obs.metrics import latency_stats  # noqa: F401 — re-exported;
# the one implementation (exact-quantile histograms incl. p99.9) shared
# with benchmarks/serving_bench.py


def build_server(args, cfg, model, params, telemetry=None):
    from repro.serve import ReplicaServer

    injector = None
    if args.failure_model:
        from repro.des.params import DESParams
        from repro.scenarios.topology import ClusterTopology
        from repro.train import ScenarioInjector
        topo = (ClusterTopology(**json.loads(args.topology))
                if args.topology else
                ClusterTopology(n_groups=args.replicas, hosts_per_group=1,
                                hosts_per_rack=1))
        injector = ScenarioInjector(
            json.loads(args.failure_model), topo, n_groups=args.replicas,
            seconds_per_step=args.seconds_per_step,
            params=DESParams(n=args.replicas), seed=args.seed)

    ckpt = None
    if args.ckpt_dir:
        from repro.ckpt import CheckpointManager
        ckpt = CheckpointManager(args.ckpt_dir, n_groups=args.replicas,
                                 redundancy=1, mtbf=1e6, t_save=1.0,
                                 t_restart=1.0)

    return ReplicaServer(model, params, n_replicas=args.replicas,
                         injector=injector, ckpt=ckpt,
                         engine_kwargs=engine_kwargs(args),
                         telemetry=telemetry)


def engine_kwargs(args) -> dict:
    """:class:`~repro.serve.engine.ServeEngine` sizing from ``args``:
    slots, pages, generation budget and prompt buckets."""
    from repro.serve import pool_pages_for
    buckets = tuple(int(b) for b in args.buckets.split(","))
    return dict(
        n_slots=args.slots, page_size=args.page_size, max_new=args.max_new,
        buckets=buckets,
        n_pages=pool_pages_for(args.slots, max(buckets) + args.max_new,
                               args.page_size))


def serve_and_measure(srv, requests):
    """Drive the server to drain; return (finished, wall_seconds)."""
    for req in requests:
        srv.submit(req)
    t0 = time.perf_counter()
    done = srv.run()
    return done, time.perf_counter() - t0


def build_parser() -> argparse.ArgumentParser:
    from repro.launch.common import add_model_args
    ap = argparse.ArgumentParser(description=__doc__)
    add_model_args(ap)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots per replica")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--buckets", default="8,16",
                    help="prompt-length buckets (one prefill executable "
                         "each; prompts are exact-length, never padded)")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--failure-model", default=None,
                    help='failure-model JSON, e.g. \'{"kind": '
                         '"correlated", "scope": "rack", ...}\'')
    ap.add_argument("--topology", default=None,
                    help="ClusterTopology JSON (defaults to one replica "
                         "per rack)")
    ap.add_argument("--seconds-per-step", type=float, default=100.0)
    ap.add_argument("--ckpt-dir", default=None,
                    help="enables the wipe-out reload path")
    ap.add_argument("--report-json", default=None)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record telemetry and write a Perfetto-loadable "
                         "trace (per-replica prefill/decode/admit/evict "
                         "lanes + failure markers); metrics snapshot at "
                         "PATH.metrics.json")
    return ap


def main(argv: list[str] | None = None) -> None:
    args = build_parser().parse_args(argv)

    import jax

    from repro.data import RequestStream
    from repro.launch.common import enable_compile_cache, resolve_config
    from repro.models import build_model
    from repro.obs import Telemetry

    enable_compile_cache()
    cfg = resolve_config(args)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))

    # metrics always on (counters are cheap and the no-recompile gate
    # reads the snapshot); span recording only with --trace
    tel = Telemetry(trace=args.trace is not None)
    srv = build_server(args, cfg, model, params, telemetry=tel)
    srv.warmup()
    frozen = tel.snapshot()["counters"]["serve.exec_cache.misses"]
    buckets = tuple(int(b) for b in args.buckets.split(","))
    stream = RequestStream(cfg, buckets=buckets, max_new=args.max_new,
                           seed=args.seed)
    done, wall = serve_and_measure(srv, stream.requests(args.requests))

    stats = latency_stats(done)
    report = {
        "arch": args.arch,
        **srv.report(),
        **stats,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(stats["tokens"] / wall, 2) if wall else None,
        "requests": args.requests,
        "completed_requests": len(done),
    }
    print(json.dumps(report, indent=1))
    if args.report_json:
        with open(args.report_json, "w") as fh:
            json.dump(report, fh, indent=1)
    if args.trace:
        tel.dump_trace(args.trace)
        tel.metrics.dump(args.trace + ".metrics.json")
        print(f"[serve] trace -> {args.trace} (analyze: python -m "
              f"repro.launch.obs {args.trace})")

    assert len(done) == args.requests, (
        f"dropped {args.requests - len(done)} requests")
    # the frozen-recompiles gate reads the METRICS SNAPSHOT — the cache's
    # counters are the registry's, so snapshot and cache cannot diverge
    snap = tel.snapshot()
    assert snap["counters"]["serve.exec_cache.misses"] == frozen, (
        f"recompiled after warmup: "
        f"{snap['counters']['serve.exec_cache.misses'] - frozen} misses")


if __name__ == "__main__":
    main()
