"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs the full SPARe+CKPT loop (Alg. 1) at a configurable scale. By
default it runs the reduced same-family config (``--smoke``);
``--no-smoke`` runs the published widths, and ``--layers K`` cuts only
the depth (``chip_smoke.py`` at the repo root drives this launcher's
trainer on one TPU chip that way).

Failure injection comes in two flavors:

* ``--mtbf-steps K`` — the legacy toy injector: Poisson arrivals in step
  time, uniform single-group victims;
* ``--failure-model SPEC [--topology SPEC]`` — the scenario bridge
  (:mod:`repro.train.injection`): any registered
  :class:`repro.scenarios.models.FailureModel` drives the live trainer
  through the cluster topology, so rack/pod bursts and trace replays
  deliver *multi-group* kill batches to ``scheme.recover``. SPEC is a
  registry name (``correlated``) or a JSON object
  (``'{"kind": "correlated", "scope": "rack", "burst_prob": 0.5}'``).

``--sweep-regimes`` ignores ``--arch`` and runs the trainer campaign
preset instead: the tiny-config trainer across the three PR-2 regimes
(weibull / rack-burst / trace replay), verifying the §3.1 gradient
invariant after every recovery.

``--mesh`` swaps the emulated trainer for the :class:`repro.exec
.MeshExecutor`: the identical loop (same schemes, same injectors, same
report) but the step runs sharded over an ``n_groups x model_degree``
device mesh with the §3.1 weighted all-reduce on the wire. With
``JAX_PLATFORMS=cpu`` pinned, the launcher fans the host platform out
into enough emulated devices (the dry-run trick), so
``JAX_PLATFORMS=cpu python -m repro.launch.train --mesh`` works on any
machine; otherwise the mesh is built from the real accelerator devices
and fails if there are too few.

The persistent compile cache is on (:mod:`repro.launch.common`).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _spec(arg: str | None):
    """Parse a model/topology CLI spec: JSON object or bare name."""
    if arg is None:
        return None
    arg = arg.strip()
    if arg.startswith("{"):
        return json.loads(arg)
    return arg


def _resolve_r(args) -> int:
    """'-r 0 = Thm-4.3 optimal' — one policy for every launcher path."""
    from repro.core.theory import r_star
    return args.redundancy or max(2, min(r_star(args.n_groups),
                                         args.n_groups - 1))


def _sweep_regimes(args) -> None:
    from repro.scenarios.campaign import (run_trainer_cell,
                                          trainer_regime_cells)

    trace_dir = args.trace     # in sweep mode --trace names a DIRECTORY
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
        print(f"[sweep] telemetry on: one trace per regime under "
              f"{trace_dir}/", file=sys.stderr)
    cells = trainer_regime_cells(steps=args.steps, n=args.n_groups,
                                 r=_resolve_r(args),
                                 topology=_spec(args.topology),
                                 seconds_per_step=args.seconds_per_step,
                                 base_seed=args.seed,
                                 trace_dir=trace_dir or None)
    rows = []
    for cell in cells:
        label = cell["model"].get("label", cell["model"]["kind"])
        print(f"[sweep] {label}: N={cell['n']} r={cell['r']} "
              f"steps={cell['steps']}", file=sys.stderr)
        row = run_trainer_cell(cell)
        rows.append(row)
        print(f"[sweep] {label}: steps={row['steps_done']} "
              f"failures={row['failures']} wipeouts={row['wipeouts']} "
              f"reorders={row['reorders']} patches={row['patches']} "
              f"multi_group={row['multi_group_events']} "
              f"max_grad_err={row['max_grad_check_err']:.2e}")
    multi = sum(r["multi_group_events"] for r in rows)
    print(f"[sweep] total multi-group kill batches delivered to "
          f"scheme.recover: {multi}")
    if args.report_json:
        with open(args.report_json, "w") as f:
            json.dump(rows, f, indent=1)


def build_parser() -> argparse.ArgumentParser:
    from repro.launch.common import add_model_args
    ap = argparse.ArgumentParser(description=__doc__)
    add_model_args(ap)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--n-groups", type=int, default=8,
                    help="SPARe data-parallel degree N")
    ap.add_argument("--redundancy", "-r", type=int, default=0,
                    help="stack redundancy r (0 = Thm-4.3 optimal)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--per-type-batch", type=int, default=2)
    ap.add_argument("--mtbf-steps", type=float, default=0.0,
                    help="legacy Poisson injector: failures every ~K "
                         "steps (0 = none)")
    ap.add_argument("--failure-model", default=None,
                    help="scenario-bridge injection: model name or JSON "
                         "spec (repro.scenarios registry)")
    ap.add_argument("--topology", default=None,
                    help="cluster topology: preset name or JSON spec "
                         "(default: small layout at N)")
    ap.add_argument("--seconds-per-step", type=float, default=None,
                    help="step duration on the failure model's clock "
                         "(default: DES t_comp + t_allreduce)")
    ap.add_argument("--verify-equivalence", action="store_true",
                    help="check the §3.1 gradient invariant after every "
                         "successful recovery")
    ap.add_argument("--sweep-regimes", action="store_true",
                    help="run the tiny-config trainer (seq=32, "
                         "per-type batch 1, §3.1-verified) across the "
                         "three PR-2 failure regimes and exit; honors "
                         "--steps/--n-groups/-r/--seed/--topology/"
                         "--seconds-per-step, ignores the other flags")
    ap.add_argument("--mesh", action="store_true",
                    help="run on a real SPMD device mesh (repro.exec."
                         "MeshExecutor) instead of the emulated trainer; "
                         "under JAX_PLATFORMS=cpu the host platform is "
                         "fanned out into enough emulated devices")
    ap.add_argument("--model-degree", type=int, default=1,
                    help="size of the --mesh mesh's model axis, along "
                         "which the manual program replicates the step")
    ap.add_argument("--grad-compress", default="none",
                    choices=("none", "int8_ef"),
                    help="--mesh only: compress the bucketed gradient "
                         "sync (int8 payload + per-bucket scales over "
                         "the wire, EF residuals as device-local "
                         "state)")
    ap.add_argument("--elastic", action="store_true",
                    help="with --mesh: enable the elastic recovery tier "
                         "(repro.elastic.ElasticMeshExecutor) — an "
                         "unmaskable failure set shrinks the DP degree "
                         "and continues degraded when the TTT policy "
                         "favors it over restart")
    ap.add_argument("--t-reshape", type=float, default=60.0,
                    help="--elastic only: modeled outage seconds per "
                         "online resharding (weighed against the "
                         "t_restart outage by the TTT policy)")
    ap.add_argument("--scheme", default="spare",
                    help="fault-tolerance scheme (repro.des registry: "
                         "spare | replication | ckpt_only | adaptive)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--report-json", default=None)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record telemetry and write a Perfetto-loadable "
                         "Chrome trace here (analyze with "
                         "python -m repro.launch.obs PATH); a metrics "
                         "snapshot lands next to it at PATH.metrics.json")
    ap.add_argument("--trace-deep", action="store_true",
                    help="with --trace: in-jit bucket markers + EF "
                         "residual norms (changes the compiled program; "
                         "attribution sessions only)")
    return ap


def build_trainer(args, telemetry=None):
    """The trainer ``args`` describe: a :class:`SpareTrainer`, or with
    ``--mesh`` a :class:`~repro.exec.MeshExecutor` (elastic with
    ``--elastic``). Logs one ``[train]`` line naming the run."""
    from repro.des import get_scheme
    from repro.launch.common import resolve_config
    from repro.train.trainer import SpareTrainer

    cfg = resolve_config(args).scaled(grad_accum=1)
    r = _resolve_r(args)
    tag = "" if args.grad_compress == "none" else f"+{args.grad_compress}"
    plane = (f"{args.n_groups}x{args.model_degree}{tag}"
             if args.mesh else "emulated")
    print(f"[train] arch={args.arch} N={args.n_groups} r={r} "
          f"scheme={args.scheme} steps={args.steps} mesh={plane} "
          f"layers={cfg.n_layers} d_model={cfg.d_model} vocab={cfg.vocab} "
          f"params={cfg.param_count():,}")

    scheme_kwargs = {} if args.scheme == "ckpt_only" else {"r": r}
    common = dict(n_groups=args.n_groups, redundancy=r, seq=args.seq,
                  per_type_batch=args.per_type_batch, seed=args.seed,
                  ckpt_dir=args.ckpt_dir, base_lr=args.lr,
                  total_steps=args.steps, telemetry=telemetry,
                  scheme=get_scheme(args.scheme, **scheme_kwargs))
    if args.mesh:
        compress = None if args.grad_compress == "none" else \
            args.grad_compress
        mesh_kw = dict(model_degree=args.model_degree,
                       grad_compress=compress, **common)
        if args.elastic:
            from repro.elastic import ElasticMeshExecutor
            trainer = ElasticMeshExecutor(cfg, t_reshape=args.t_reshape,
                                          **mesh_kw)
        else:
            from repro.exec import MeshExecutor
            trainer = MeshExecutor(cfg, **mesh_kw)
    else:
        trainer = SpareTrainer(cfg, **common)
    return trainer


def build_injector(args):
    """The failure injector ``args`` select, or None."""
    from repro.train.trainer import PoissonInjector
    if args.failure_model is not None:
        from repro.train.injection import ScenarioInjector
        injector = ScenarioInjector(
            _spec(args.failure_model), _spec(args.topology),
            n_groups=args.n_groups,
            seconds_per_step=args.seconds_per_step, seed=args.seed)
    elif args.mtbf_steps > 0:
        injector = PoissonInjector(args.mtbf_steps, seed=args.seed)
    else:
        injector = None
    return injector


def main(argv: list[str] | None = None) -> None:
    ap = build_parser()
    args = ap.parse_args(argv)

    if args.sweep_regimes:
        _sweep_regimes(args)
        return
    if args.elastic and not args.mesh:
        ap.error("--elastic needs --mesh (the elastic tier reshapes a "
                 "real device mesh)")

    if args.mesh and os.environ.get("JAX_PLATFORMS") == "cpu":
        # must land before the FIRST jax import (jax locks the device
        # count on init); every repro import below is function-local so
        # this is still early enough. Only a run pinned to the CPU fans
        # out: on an accelerator the mesh must be real devices, and a
        # failed backend must not turn into an emulated CPU mesh that
        # exits 0. Append rather than setdefault — unrelated pre-set
        # XLA_FLAGS must not silently disable the fan-out (an explicit
        # user-set device count still wins).
        existing = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in existing:
            flag = ("--xla_force_host_platform_device_count="
                    f"{args.n_groups * args.model_degree}")
            os.environ["XLA_FLAGS"] = f"{existing} {flag}".strip()

    from repro.launch.common import enable_compile_cache
    enable_compile_cache()

    tel = None
    if args.trace is not None:
        from repro.obs import Telemetry
        tel = Telemetry(deep=args.trace_deep)
    trainer = build_trainer(args, telemetry=tel)
    injector = build_injector(args)
    t0 = time.perf_counter()
    rep = trainer.run(args.steps, injector=injector,
                      verify_equivalence=args.verify_equivalence)
    dt = time.perf_counter() - t0
    print(f"[train] done: {rep.steps_done} steps in {dt:.1f}s "
          f"({dt / max(rep.steps_done, 1):.2f}s/step)")
    print(f"[train] loss {rep.losses[0]:.4f} -> {rep.losses[-1]:.4f} | "
          f"failures={rep.failures} wipeouts={rep.wipeouts} "
          f"reshapes={rep.reshapes} reorders={rep.reorders} "
          f"patches={rep.patches} S_A={trainer.state.s_a} "
          f"ckpts={rep.ckpt_saves}")
    if rep.reshapes:
        print(f"[train] elastic: DP degree now {trainer.state.n} "
              f"(full {args.n_groups}); policy log: "
              f"{getattr(trainer, 'policy_log', [])}")
    if rep.events:
        print(f"[train] recovery events={len(rep.events)} "
              f"multi_group={rep.multi_group_events} "
              f"rollback_steps={rep.rollback_steps} "
              f"max_grad_err={rep.max_grad_check_err:.2e}")
    if args.report_json:
        with open(args.report_json, "w") as f:
            json.dump({"losses": rep.losses, "failures": rep.failures,
                       "wipeouts": rep.wipeouts, "steps": rep.steps_done,
                       "multi_group_events": rep.multi_group_events,
                       "max_grad_check_err": rep.max_grad_check_err},
                      f)
    if tel is not None:
        tel.dump_trace(args.trace)
        tel.metrics.dump(args.trace + ".metrics.json")
        print(f"[train] trace -> {args.trace} (analyze: python -m "
              f"repro.launch.obs {args.trace}) | metrics -> "
              f"{args.trace}.metrics.json")


if __name__ == "__main__":
    main()
