"""Smoke run of the SPARe trainer and serving engine on TPU chips.

    python chip_smoke.py               # one chip: train phase, serve phase
    python chip_smoke.py --four-chips  # four chips: the SPARe DP mesh path

One process does everything and is the only process that touches JAX.
The script checks the device first: when JAX's first device is not a
TPU it exits non-zero before it builds anything (so under
``JAX_PLATFORMS=cpu``, or without the repo's ``src/`` next to it, it
fails and prints no result). Any failed phase raises; the last line of
standard output is then never printed. On success the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

Configuration: qwen2.5-3b (``src/repro/configs/qwen2_5_3b.py``) at every
published width -- d_model 2048, 16 heads with 2 KV heads (GQA),
head_dim 128, d_ff 11008, vocabulary 151,936 (tied embeddings), QKV bias,
rope_theta 1e6. Weights are random, drawn from ``--seed``.

The cut, and why. Only depth and sequence length may be cut; no width is.
Training state costs 16 B per parameter (bf16 params and micro-gradient,
fp32 accumulator, fp32 Adam m/v): the tied embedding is ~311 M params and
each layer ~77 M. The published 36 layers (~3.1 B params, ~49 GB) cannot
fit one 16 GB v5e chip, so the depth is cut to ``LAYERS = 4`` -- the
deployment this stands for is the 36 layers pipelined over 9 stages of 4
layers, one stage per chip. The sequence stays at ``SEQ = 1024`` tokens
with N = 4 SPARe groups of one sequence each. Compiled for a v5e chip,
``memory_analysis()`` of that train step gives 5.77 GiB of arguments
(params + Adam state, donated) and 3.63 GiB of temporaries: ~9.4 GiB at
peak, leaving room for the two gradient trees of the §3.1 check
(1.15 GiB each, 1.29 GiB of temporaries). Eight layers would need
~14.3 GiB before that check, too close to 16 GB; so 4.

The four-chip path (``--four-chips``) runs the same 4 layers: every chip
holds a full replica of params and Adam state (pure DP), plus the flat
fp32 gradient buckets of the sync and, under int8 error feedback, fp32
residuals of the whole gradient. Compiled for a v5e:2x2 mesh, the
heaviest program, the int8-EF step at S_A=1, gives 8.66 GiB of
arguments (params, Adam state, both EF residual families; donated) and
4.09 GiB of temporaries: ~12.8 GiB per chip. The masked fp32 step at
S_A=2 gives 5.77 + 4.27 GiB. Chip 0 also holds, during the §3.1 sweep
only, the single-device oracle's params and two gradient trees (~3.5
GiB by the one-chip figures above) beside the mesh trainer's 5.77 GiB
of state. All of it fits 16 GB, so the four-chip path keeps 4 layers.

One chip, in order:

1. train -- the trainer ``repro.launch.train`` builds from
   ``--no-smoke --layers 4``: healthy steps at S_A=1, one scripted group
   kill that RECTLR masks (S_A rises, the step compiles once for the new
   depth), the §3.1 gradient check on the chip after the recovery, then
   more steps. Prints the losses, S_A before and after, the compile
   count, the §3.1 error, ``peak_bytes_in_use`` and the wall time of a
   warmed step that ends in ``block_until_ready``.
2. serve -- one ``ServeEngine`` replica sized by ``repro.launch.serve``
   over the trained params (no optimizer state): warm-up, then 4 seeded
   requests, prefill then decode. Every request must complete, nothing
   may compile after warm-up, and every generated token must be the
   greedy choice of a plain full-sequence forward over the same tokens
   up to bf16 noise.

Four chips: a ``MeshExecutor`` with 4 groups over the 4 chips
(``model_degree=1``). It prints which devices hold
each param leaf (all four must), checks the mesh gradient of every
one-group survivor set against the single-device vanilla-DP oracle
(``repro.exec.equivalence.survivor_set_sweep``), runs a healthy step and
a step masked after a scripted kill, and then one step with
``grad_compress="int8_ef"``, whose program must hold the compiled Pallas
kernel (a ``tpu_custom_call``).

The compile cache is ``repro.launch.common.enable_compile_cache``'s:
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import time
from pathlib import Path

LAYERS = 4
SEQ = 1024
N_GROUPS = 4
REDUNDANCY = 2
HEALTHY_STEPS = 3
KILLED_GROUP = 1
STEPS_AFTER_KILL = 2
EQUIV_TOL = 1e-2             # the trainer's §3.1 tolerance
N_REQUESTS = 4
GREEDY_GAP_TOL = 0.25        # logits: bf16 paged decode vs fp32 forward


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def train_argv(layers: int, seed: int, *extra: str) -> list[str]:
    return ["--no-smoke", "--arch", "qwen2.5-3b", "--layers", str(layers),
            "--n-groups", str(N_GROUPS), "-r", str(REDUNDANCY),
            "--per-type-batch", "1", "--seq", str(SEQ),
            "--steps", str(HEALTHY_STEPS + 1 + STEPS_AFTER_KILL),
            "--seed", str(seed), *extra]


def serve_argv(layers: int, seed: int) -> list[str]:
    return ["--no-smoke", "--arch", "qwen2.5-3b", "--layers", str(layers),
            "--slots", "4", "--buckets", "128,256", "--max-new", "16",
            "--page-size", "16", "--seed", str(seed)]


def require(ok: bool, what) -> None:
    """A failed check fails the run (unlike ``assert``, also under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def check_losses(losses) -> None:
    require(losses and all(math.isfinite(x) for x in losses),
            f"non-finite loss: {losses}")


# --------------------------------------------------------------------- #
# one chip                                                               #
# --------------------------------------------------------------------- #
def train_phase(seed: int):
    import jax

    from repro.launch import train as train_launch
    from repro.train.injection import ScriptedInjector
    from repro.train.trainer import TrainReport

    args = train_launch.build_parser().parse_args(train_argv(LAYERS, seed))
    trainer = train_launch.build_trainer(args)
    cfg = trainer.cfg
    injector = ScriptedInjector({HEALTHY_STEPS: [KILLED_GROUP]},
                                n_groups=N_GROUPS)
    t0 = time.perf_counter()
    rep = trainer.run(args.steps, injector=injector,
                      verify_equivalence=True, equivalence_tol=EQUIV_TOL)
    log(f"train: {rep.steps_done} steps in "
        f"{time.perf_counter() - t0:.3f} s (compiles included)")
    log(f"train: losses {rep.losses}")
    check_losses(rep.losses)
    require(rep.steps_done == args.steps, f"steps done {rep.steps_done}")
    # random init: CE of near-uniform logits over the vocabulary
    uniform = math.log(cfg.vocab)
    require(abs(rep.losses[0] - uniform) < 2.0,
            f"first loss {rep.losses[0]} far from ln(vocab) = {uniform}")

    require(len(rep.events) == 1 and not rep.events[0].wipeout,
            f"one masked recovery expected: {rep.events}")
    ev = rep.events[0]
    log(f"train: kill of group {ev.victims} at step {ev.step}: "
        f"S_A {ev.s_a_before} -> {ev.s_a_after}, "
        f"wipeout={ev.wipeout}, reordered={ev.reordered}")
    require(ev.s_a_after > ev.s_a_before == 1,
            f"S_A {ev.s_a_before} -> {ev.s_a_after}")
    # one compile per stack depth: S_A=1, then the masked depth
    log(f"train: step compiles {rep.recompiles}")
    require(rep.recompiles == 2, f"{rep.recompiles} step compiles")
    log(f"train: §3.1 gradient error {ev.grad_check_err!r} "
        f"(tolerance {EQUIV_TOL})")
    require(ev.grad_check_err is not None
            and ev.grad_check_err <= EQUIV_TOL,
            f"§3.1 error {ev.grad_check_err}")

    # warmed steps: the loop's own step (host batch, compiled step,
    # bookkeeping), timed to block_until_ready on the new state
    times = []
    report = TrainReport()
    for _ in range(2):
        t0 = time.perf_counter()
        trainer.train_step(report)
        jax.block_until_ready((trainer.params, trainer.opt_state))
        times.append(time.perf_counter() - t0)
    require(report.recompiles == 0, "warmed step compiled")
    check_losses(report.losses)
    log(f"train: warmed step wall seconds at S_A={trainer.state.s_a}: "
        f"{times}")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"train: peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
        f"(bytes_limit {stats.get('bytes_limit')})")
    # params only: the trainer (and its Adam state) dies on return
    return cfg, trainer.params


def serve_phase(cfg, params, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data import RequestStream
    from repro.launch import serve as serve_launch
    from repro.models import build_model
    from repro.obs import Telemetry
    from repro.serve import ExecutableCache, ServeEngine

    args = serve_launch.build_parser().parse_args(serve_argv(LAYERS, seed))
    kw = serve_launch.engine_kwargs(args)
    model = build_model(cfg)
    # the cache's miss count IS the serve.exec_cache.misses metric
    cache = ExecutableCache(Telemetry(trace=False).metrics)
    engine = ServeEngine(model, params, exec_cache=cache, **kw)
    t0 = time.perf_counter()
    engine.warmup()
    frozen = cache.misses
    log(f"serve: warm-up compiled {frozen} programs in "
        f"{time.perf_counter() - t0:.3f} s")

    stream = RequestStream(cfg, buckets=kw["buckets"],
                           max_new=kw["max_new"], seed=seed)
    requests = stream.requests(N_REQUESTS)
    for req in requests:
        engine.submit(req)
    t0 = time.perf_counter()
    done = engine.run()
    log(f"serve: {len(done)}/{N_REQUESTS} requests in "
        f"{time.perf_counter() - t0:.3f} s, "
        f"post-warm-up compiles {cache.misses - frozen}")
    require(len(done) == N_REQUESTS, f"dropped {N_REQUESTS - len(done)}")
    require(cache.misses == frozen, "recompiled after warm-up")

    # reference: one plain forward over prompt + generated tokens; the
    # engine's token at each position must be that forward's greedy
    # choice up to bf16 noise (a wrong cache picks ~random tokens,
    # several logit units below the max)
    forward = jax.jit(lambda p, t: model.forward(p, tokens=t))
    by_id = {r.req_id: r for r in requests}
    worst, agree, total = 0.0, 0, 0
    for fin in done:
        req = by_id[fin.req_id]
        require(fin.tokens.shape == (req.max_new,),
                f"generated shape {fin.tokens.shape}")
        seq = np.concatenate([req.tokens, fin.tokens[:-1]])[None]
        logits = np.asarray(forward(params, jnp.asarray(seq))[0],
                            np.float32)[req.prompt_len - 1:, :cfg.vocab]
        require(np.isfinite(logits).all(), "non-finite reference logits")
        chosen = logits[np.arange(len(fin.tokens)), fin.tokens]
        gaps = logits.max(axis=-1) - chosen
        worst = max(worst, float(gaps.max()))
        agree += int((logits.argmax(axis=-1) == fin.tokens).sum())
        total += len(fin.tokens)
    log(f"serve: tokens equal to the forward's argmax {agree}/{total}; "
        f"largest logit gap to it {worst!r} (tolerance {GREEDY_GAP_TOL})")
    require(worst <= GREEDY_GAP_TOL, f"served token {worst} logits below "
            f"the reference's greedy choice")


# --------------------------------------------------------------------- #
# four chips                                                             #
# --------------------------------------------------------------------- #
def check_kernel_in_step(ex) -> None:
    """The int8-EF step program must call the compiled Pallas kernel
    (Mosaic's ``tpu_custom_call``), not its interpreter or jnp oracle."""
    step = ex._compiled(ex.state.s_a)
    text = step.lower(ex.params, ex.opt_state, ex._device_batch(),
                      ex._ef_state).as_text()
    n_kernels = text.count("tpu_custom_call")
    log(f"mesh: int8-EF step holds {n_kernels} tpu_custom_call kernels")
    require(n_kernels > 0, "int8-EF sync did not pick the Pallas kernel")


def four_chip_phase(seed: int) -> None:
    import jax

    from repro.exec.equivalence import survivor_set_sweep
    from repro.launch import train as train_launch
    from repro.train.injection import ScriptedInjector

    parse = train_launch.build_parser().parse_args
    mesh_argv = train_argv(LAYERS, seed, "--mesh", "--model-degree", "1")
    ex = train_launch.build_trainer(parse(mesh_argv))
    all_ids = sorted(d.id for d in jax.devices())
    for path, leaf in jax.tree_util.tree_leaves_with_path(ex.params):
        ids = sorted(s.device.id for s in leaf.addressable_shards)
        log(f"mesh: param {jax.tree_util.keystr(path)} on devices {ids}")
        require(ids == all_ids, f"{jax.tree_util.keystr(path)} on {ids}")

    # §3.1 on the mesh: every one-group survivor set against the
    # single-device vanilla-DP oracle of a same-seed trainer (params
    # only: the oracle never steps, so its Adam state is dropped)
    ref = train_launch.build_trainer(parse(train_argv(LAYERS, seed)))
    ref.opt_state = None
    checks = survivor_set_sweep(ex, ref, step=0, max_failures=1)
    for c in checks:
        log(f"mesh: survivor set minus {c.victims} S_A={c.s_a} "
            f"mesh-vs-host {c.mesh_vs_host!r} "
            f"mesh-vs-vanilla {c.mesh_vs_vanilla!r}")
        require(c.ok(EQUIV_TOL), f"§3.1 on the mesh: {c}")
    require(len(checks) == N_GROUPS, f"{len(checks)} survivor sets")
    del ref
    gc.collect()        # the trainers' jitted oracles close over them

    rep = ex.run(2, injector=ScriptedInjector({1: [KILLED_GROUP]},
                                              n_groups=N_GROUPS))
    ev = rep.events[0]
    log(f"mesh: losses {rep.losses}; kill of group {ev.victims}: "
        f"S_A {ev.s_a_before} -> {ev.s_a_after}; compiles "
        f"{rep.recompiles}")
    check_losses(rep.losses)
    require(ev.s_a_after > ev.s_a_before and not ev.wipeout,
            f"masked kill expected: {ev}")
    ex.close()
    del ex
    gc.collect()

    ex = train_launch.build_trainer(parse(mesh_argv + ["--grad-compress",
                                                       "int8_ef"]))
    check_kernel_in_step(ex)
    rep = ex.run(1)
    log(f"mesh: int8-EF step loss {rep.losses}")
    check_losses(rep.losses)
    stats = [d.memory_stats() or {} for d in jax.devices()]
    log(f"mesh: peak_bytes_in_use per device "
        f"{[s.get('peak_bytes_in_use') for s in stats]}")
    ex.close()


# --------------------------------------------------------------------- #
def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="SPARe smoke run on TPU chips (see module docstring)")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip SPARe DP mesh path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform} ({dev.device_kind})", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: the repo's sources are not at {src}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    from repro.launch.common import enable_compile_cache
    log(f"compile cache at {enable_compile_cache()}")

    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        cfg, params = train_phase(args.seed)
        gc.collect()    # free the dropped trainer's Adam state now
        serve_phase(cfg, params, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
