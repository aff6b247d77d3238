"""Repo-wide static analysis driver: ``python -m repro.launch.lint``.

One command runs every :mod:`repro.analysis` pass and renders one
deterministic report:

* **AST passes** (always, in-process, jax-free): the determinism lint
  and the thread-shared-state audit over every ``.py`` file under
  ``src/ tests/ benchmarks/ examples/``.
* **HLO passes** (``--hlo`` / ``--assert-clean``): donation audit,
  hot-path purity, wire-dtype policy, and collective-schedule
  determinism over the compiled production programs. These fan out as
  subprocesses because each target pins its own emulated device count
  *before* jax initializes: the five dryrun matrix cells re-lower at
  512 devices (via :func:`repro.launch.dryrun.lower_cell` — the exact
  jit sites CI compiles), and one certification child at 8 devices
  sweeps the live :class:`~repro.exec.executor.MeshExecutor` variants
  over the FULL RECTLR-recoverable survivor space, the reshaped-mesh
  executables of :class:`~repro.elastic.ElasticMeshExecutor` after a
  degraded-continue shrink, the demoted-set program a gray-failure
  SPARe demotion (``repro.health``) switches to, plus the
  :class:`~repro.train.trainer.SpareTrainer` jit site and every
  :class:`~repro.serve.engine.ExecutableCache` program of a warmed
  :class:`~repro.serve.engine.ServeEngine`.

The children are CPU processes by design: each runs with
``JAX_PLATFORMS=cpu``, so on a machine with an accelerator none of them
takes the chip.

Exit status: 0 unless ``--assert-clean`` is given and any unsuppressed
violation survives — the CI ``static-analysis`` job gates on exactly
that. ``--json`` prints the machine report (byte-identical across
runs); ``--out FILE`` writes it as the CI artifact.

Internal child modes (spawned by the driver, usable directly when
debugging one target): ``--cell ARCH SHAPE [--multi-pod]`` and
``--certify-executors``.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

from repro.analysis import Report, run_ast_passes

# the CI dryrun green-up matrix (one cell per model family); the lint
# gate certifies the same five programs it compiles
MATRIX_CELLS = (
    ("qwen2.5-3b", "train_4k", False),
    ("deepseek-v2-lite-16b", "train_4k", False),
    ("mamba2-1.3b", "long_500k", False),
    ("jamba-v0.1-52b", "decode_32k", True),
    ("musicgen-medium", "prefill_32k", True),
)


# ------------------------------------------------------------------ #
# child: one dryrun cell at 512 emulated devices                     #
# ------------------------------------------------------------------ #
def run_cell_passes(arch: str, shape: str, multi_pod: bool) -> Report:
    # importing dryrun pins XLA_FLAGS to 512 host devices — must happen
    # in a fresh process (this one), never in the jax-free parent
    from repro.analysis import (donation_audit, hot_path_purity,
                                schedule_determinism_cell, wire_dtype_policy)
    from repro.launch.dryrun import SHAPES, lower_cell

    report = Report()
    mesh = "2x16x16" if multi_pod else "16x16"
    kind = SHAPES[shape].kind
    # train cells sweep the stack depth (S_A rises as failures consume
    # redundancy); double-compile certification runs at the base depth
    depths = (1, 2) if kind == "train" else (1,)
    for s_a in depths:
        lowered, meta = lower_cell(arch, shape, multi_pod, s_a=s_a)
        tag = f"cell:{arch}/{shape}/{mesh}@S_A={s_a}"
        if lowered is None:
            report.note("cells", **{f"{tag} skipped": meta["reason"]})
            continue
        text = lowered.compile().as_text()

        donate, arg_leaves = meta["donate"], meta["arg_leaves"]
        donated_leaves = sum(arg_leaves[i] for i in donate)
        rng = None
        if donate:
            rng = (sum(arg_leaves[:min(donate)]),
                   sum(arg_leaves[:max(donate) + 1]))
        report.extend(donation_audit(text, donated_leaves, tag,
                                     donated_range=rng))
        report.extend(hot_path_purity(text, tag))
        report.extend(wire_dtype_policy(text, tag))
        if s_a == depths[0]:
            relowered, _ = lower_cell(arch, shape, multi_pod, s_a=s_a)
            report.extend(schedule_determinism_cell(
                text, relowered.compile().as_text(), tag,
                weights_shape=meta["weights_shape"]))
        report.note("cells", programs_certified=1,
                    donated_leaves_audited=donated_leaves)
    return report


# ------------------------------------------------------------------ #
# child: live executors / trainer / serve cache at 8 devices         #
# ------------------------------------------------------------------ #
def certify_executors() -> Report:
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    import jax

    from repro.analysis import (donation_audit, hot_path_purity,
                                schedule_determinism_executor,
                                wire_dtype_policy)
    from repro.analysis.hlo_passes import ef_state_policy
    from repro.configs import smoke_config
    from repro.exec.executor import MeshExecutor

    leaves = lambda t: len(jax.tree_util.tree_leaves(t))  # noqa: E731
    report = Report()
    cfg = smoke_config("qwen2.5-3b").scaled(grad_accum=1)

    # both wire formats of the production step's gradient sync (fp32
    # buckets, int8 EF), each swept over the FULL
    # RECTLR-recoverable survivor space (n=4, r=2: all singles + the
    # doubles the controller can mask)
    for compress in (None, "int8_ef"):
        tag = "executor:shard_map" + (f"+{compress}" if compress else "")
        ex = MeshExecutor(cfg, grad_compress=compress,
                          n_groups=4, redundancy=2, model_degree=2,
                          seq=32, per_type_batch=2, total_steps=50)
        text = ex.compiled_step_text()
        report.extend(donation_audit(text, ex.donated_leaves(), tag))
        report.extend(hot_path_purity(text, tag))
        report.extend(wire_dtype_policy(text, tag))
        report.extend(ef_state_policy(ex, tag))
        found, certified = schedule_determinism_executor(ex, tag)
        report.extend(found)
        report.note("collective-schedule-determinism",
                    survivor_sets_certified=certified)
        report.note("donation-audit",
                    donated_leaves_audited=ex.donated_leaves())

    # the elastic tier's reshaped-mesh executables: shrink past an
    # unmaskable adjacent pair (DP 8 -> 4 on the survivor submesh) and
    # certify the degraded-shape programs with the same passes, plus
    # the full RECTLR survivor sweep at the shrunken shape
    from repro.elastic import ElasticMeshExecutor

    for compress in (None, "int8_ef"):
        tag = "executor:elastic-reshaped" + (f"+{compress}" if compress
                                             else "")
        elx = ElasticMeshExecutor(cfg, grad_compress=compress, n_groups=8,
                                  redundancy=2, model_degree=1,
                                  seq=32, per_type_batch=2, total_steps=50)
        elx.reshape([0, 1])
        text = elx.compiled_step_text()
        report.extend(donation_audit(text, elx.donated_leaves(), tag))
        report.extend(hot_path_purity(text, tag))
        report.extend(wire_dtype_policy(text, tag))
        report.extend(ef_state_policy(elx, tag))
        found, certified = schedule_determinism_executor(elx, tag)
        report.extend(found)
        report.note("collective-schedule-determinism",
                    survivor_sets_certified=certified)
        report.note("donation-audit",
                    donated_leaves_audited=elx.donated_leaves())
        elx.close()

    # the gray tier's demoted-set executables: a fail-slow group
    # proactively masked out of the weighted sync runs the SAME mesh
    # shape one stack deeper — certify the demoted program with the full
    # pass set through the real demote path, then re-admit and record
    # that the weight table restored
    import numpy as np

    from repro.health.detector import HealthReport
    from repro.train.injection import ScriptedInjector
    from repro.train.trainer import TrainReport as _TrainReport

    tag = "executor:demoted"
    dex = MeshExecutor(cfg, n_groups=4, redundancy=2,
                       model_degree=2, seq=32, per_type_batch=2,
                       total_steps=50)
    factors = np.ones(4)
    factors[0] = 3.0
    hr = HealthReport(step=0, smoothed=factors * 64.0, zscores=factors,
                      factors=factors, flagged=(0,), newly_flagged=(0,))
    dinj = ScriptedInjector({}, seconds_per_step=64.0, n_groups=4)
    dex._demote([0], hr, dinj, _TrainReport())
    text = dex.compiled_step_text()
    report.extend(donation_audit(text, dex.donated_leaves(), tag))
    report.extend(hot_path_purity(text, tag))
    report.extend(wire_dtype_policy(text, tag))
    report.extend(ef_state_policy(dex, tag))
    found, certified = schedule_determinism_executor(dex, tag)
    report.extend(found)
    report.note("collective-schedule-determinism",
                survivor_sets_certified=certified)
    report.note("donation-audit",
                donated_leaves_audited=dex.donated_leaves())
    dex._readmit([0], hr, dinj, _TrainReport())
    report.note("cells", demoted_programs_certified=1,
                readmit_schedule_restored=int(
                    bool(dex.state.alive.all())
                    and int(dex.state.s_a) == 1))
    dex.close()

    # the emulation trainer's jit site (donate_argnums=(0, 1))
    from repro.train.trainer import SpareTrainer

    tr = SpareTrainer(cfg, n_groups=4, redundancy=2, seq=32,
                      per_type_batch=2, total_steps=50)
    text = tr.compiled_step_text()
    donated = leaves(tr.params) + leaves(tr.opt_state)
    report.extend(donation_audit(text, donated, "trainer:spare"))
    report.extend(hot_path_purity(text, "trainer:spare"))
    report.note("donation-audit", donated_leaves_audited=donated)

    # every AOT program a warmed ServeEngine can ever run
    from repro.models.model import build_model
    from repro.serve import ServeEngine, pool_pages_for

    scfg = smoke_config("qwen2.5-3b")
    model = build_model(scfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params, n_slots=2,
                         n_pages=pool_pages_for(2, 8 + 4, 4),
                         page_size=4, max_new=4, buckets=(8,))
    engine.warmup()
    for key, text, donated in engine.cache.programs():
        tag = "serve:" + "/".join(str(k) for k in key)
        report.extend(donation_audit(text, donated, tag))
        report.extend(hot_path_purity(text, tag))
        report.extend(wire_dtype_policy(text, tag))
        report.note("donation-audit", donated_leaves_audited=donated)
    report.note("cells", serve_programs_certified=len(engine.cache._exe))
    return report


# ------------------------------------------------------------------ #
# parent driver                                                      #
# ------------------------------------------------------------------ #
def _spawn(extra: list[str], out: Path, label: str) -> str | None:
    """Run one child lint mode; return its JSON report, or an error."""
    cmd = [sys.executable, "-m", "repro.launch.lint", *extra,
           "--child-out", str(out)]
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)      # each child pins its own device count
    env["JAX_PLATFORMS"] = "cpu"    # emulated devices, never the chip
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True)
    if proc.returncode != 0 or not out.exists():
        tail = (proc.stderr or proc.stdout or "")[-2000:]
        return f"child {label} failed (exit {proc.returncode}): {tail}"
    return None


def run_hlo_passes(report: Report, progress=lambda msg: None) -> None:
    from repro.analysis import Violation
    with tempfile.TemporaryDirectory(prefix="repro-lint-") as td:
        jobs = []
        for i, (arch, shape, multi_pod) in enumerate(MATRIX_CELLS):
            extra = ["--cell", arch, shape]
            if multi_pod:
                extra.append("--multi-pod")
            jobs.append((extra, Path(td) / f"cell{i}.json",
                         f"cell:{arch}/{shape}"))
        jobs.append((["--certify-executors"],
                     Path(td) / "executors.json", "certify-executors"))
        for extra, out, label in jobs:
            progress(f"[lint] {label} ...")
            err = _spawn(extra, out, label)
            if err:
                report.extend([Violation(label, 0, "analysis-driver", err)])
            else:
                report.merge_json(out.read_text())


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.lint",
        description="SPARe static analysis: determinism lint + compiled "
                    "SPMD invariant verification")
    ap.add_argument("--root", default=".",
                    help="repo root for the AST walk (default: cwd)")
    ap.add_argument("--hlo", action="store_true",
                    help="also certify compiled programs (subprocess "
                         "fan-out over dryrun cells + live executors)")
    ap.add_argument("--assert-clean", action="store_true",
                    help="run everything; exit 1 on any violation")
    ap.add_argument("--json", action="store_true",
                    help="print the machine report instead of text")
    ap.add_argument("--out", help="also write the JSON report here")
    # internal child modes
    ap.add_argument("--cell", nargs=2, metavar=("ARCH", "SHAPE"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--multi-pod", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--certify-executors", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--child-out", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.cell or args.certify_executors:
        report = (run_cell_passes(args.cell[0], args.cell[1],
                                  args.multi_pod)
                  if args.cell else certify_executors())
        payload = report.to_json()
        if args.child_out:
            Path(args.child_out).write_text(payload)
        else:
            print(payload)
        return 0

    report = Report()
    run_ast_passes(args.root, report)
    if args.hlo or args.assert_clean:
        run_hlo_passes(report, progress=lambda m: print(m, file=sys.stderr))

    if args.out:
        Path(args.out).write_text(report.to_json())
    print(report.to_json() if args.json else report.render_text())
    if args.assert_clean and not report.clean:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
