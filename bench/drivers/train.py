"""Training cells: the SPARe stacked step, masked after a scripted kill.

Set-up builds one ``SpareTrainer`` from the configuration file, hands it
weights made from the seed, kills the mix's groups (the program's own
recovery masks them and raises S_A), and drives the trainer's own
``train_step`` through the mix's checked steps: they compile the step
and give the numbers that the plain reference later repeats. The window
then drives the same object's ``train_step`` until ``--seconds`` have
passed and ends in ``block_until_ready`` on the parameters and the
optimizer state. No snapshot falls in the window.
"""
from __future__ import annotations

import gc
import inspect
import time

from bench import spec
from bench.device import annotate, memory_peak_bytes
from bench.reftrain import diff_norms, leaf_norms
from bench.weights import generate

__all__ = ["setup", "window", "run"]

NEVER = 1 << 62       # snapshot_every: no snapshot in the window


class ProgramFault(RuntimeError):
    """The program does not run as the cell's files state."""


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise ProgramFault(what)


def _check_optimizer(opt: dict) -> None:
    """The program's AdamW and schedule constants must be the mix's:
    ``SpareTrainer`` passes only the base rate and the horizon."""
    from repro.optim import adamw_update
    from repro.train.step import make_train_step
    fixed = {**{k: p.default for k, p in inspect.signature(
        make_train_step).parameters.items() if k in
        ("warmup", "weight_decay", "clip_norm")},
        **{k: p.default for k, p in inspect.signature(
            adamw_update).parameters.items() if k in ("b1", "b2", "eps")}}
    for k, v in fixed.items():
        _require(v == opt[k], f"program's {k} is {v}, the mix states "
                              f"{opt[k]}")


def _check_params(program_tree, ours) -> None:
    import jax
    ps, os_ = jax.tree.structure(program_tree), jax.tree.structure(ours)
    _require(ps == os_, f"parameter tree {ps} is not the layout's {os_}")
    for a, b in zip(jax.tree.leaves(program_tree), jax.tree.leaves(ours)):
        _require(a.shape == b.shape and a.dtype == b.dtype,
                 f"parameter {a.shape} {a.dtype} is not the layout's "
                 f"{b.shape} {b.dtype}")


def setup(cell: dict, seed: int, mesh: bool = False):
    """The trainer after the kill and the checked steps, and the
    program's numbers for the reference to check."""
    import jax

    from repro.train.trainer import TrainReport

    c, mix = cell["config_spec"], cell["traffic_spec"]
    opt = mix["optimizer"]
    _check_optimizer(opt)
    cfg = spec.module("adapters", c["model_type"]).program_config(c)
    _require(cfg.moment_dtype == "float32", "Adam moments not float32")
    layout = spec.module("models", c["model_type"]).layout(c)
    kw = dict(n_groups=mix["n_groups"], redundancy=mix["redundancy"],
              seq=mix["seq"], per_type_batch=mix["per_type_batch"],
              seed=seed, base_lr=opt["base_lr"],
              total_steps=opt["total_steps"])
    if mesh:
        from repro.exec import MeshExecutor
        trainer = MeshExecutor(cfg, **cell["executor"], **kw)
    else:
        from repro.train.trainer import SpareTrainer
        trainer = SpareTrainer(cfg, **kw)
    params = generate(layout, seed)
    _check_params(trainer.params, params)
    if mesh:
        params = jax.device_put(params, jax.tree.map(
            lambda x: x.sharding, trainer.params))
    trainer.params = params        # the program's own init is dropped
    del params
    gc.collect()

    outcome = trainer.scheme.recover(trainer.state, list(mix["kill"]),
                                     step=trainer.step)
    _require(not outcome.wipeout and trainer.state.s_a == mix["s_a"],
             f"kill of {mix['kill']}: wipeout={outcome.wipeout}, "
             f"S_A={trainer.state.s_a}, expected {mix['s_a']}")
    report = TrainReport()
    prog = {"losses": []}
    for t in range(mix["checked_steps"]):
        prog["losses"].append(trainer.train_step(report, None, NEVER))
        if t == 0:
            # m after one update from zero is (1 - b1) times the
            # gradient the optimizer received
            prog["grad"] = leaf_norms(layout, trainer.opt_state.mu,
                                      1.0 / (1.0 - opt["b1"]))
    prog["change"] = diff_norms(layout, trainer.params,
                                generate(layout, seed))
    jax.block_until_ready((trainer.params, trainer.opt_state))
    return trainer, report, prog


def window(trainer, report, seconds: float, counter) -> dict:
    import jax
    steps = 0
    counter.active = True
    with annotate("window"):
        t0 = time.perf_counter()
        while True:
            with annotate("train_step"):
                trainer.train_step(report, None, NEVER)
            steps += 1
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready((trainer.params, trainer.opt_state))
        t1 = time.perf_counter()
    counter.active = False
    return {"t0": t0, "steps": steps, "window_s": t1 - t0}


def run(ctx) -> dict:
    """One run of a training cell; ``ctx`` is the harness's context."""
    from bench.correct import judge, train_numbers
    from bench.reftrain import reference_steps
    from bench.traffic.train_schedule import computed_tokens, useful_tokens

    cell, mix = ctx.cell, ctx.cell["traffic_spec"]
    trainer, report, prog = setup(cell, ctx.seed,
                                  mesh=cell.get("executor") is not None)
    s_a = int(trainer.state.s_a)
    with ctx.profiled() as traced:
        w = window(trainer, report, ctx.seconds, ctx.compiles)
    rec = {
        "setup_s": w["t0"] - ctx.t_start,
        "window_s": w["window_s"],
        "steps": w["steps"],
        "attempted": w["steps"],
        "failed": 0,
        "useful_tokens": w["steps"] * useful_tokens(mix),
        "computed_tokens": w["steps"] * computed_tokens(mix, s_a),
        "s_a": s_a,
        "memory_peak_bytes": memory_peak_bytes(ctx.devices),
        "trace_files": traced,
    }
    ctx.log(f"window: {w['steps']} steps at S_A={s_a} in "
            f"{w['window_s']!r} s; losses of the checked steps "
            f"{prog['losses']}")
    if hasattr(trainer, "close"):
        trainer.close()
    del trainer, report
    gc.collect()

    t_ref = time.perf_counter()
    ref = reference_steps(cell, ctx.seed)
    numbers, where = train_numbers(prog, ref)
    ctx.log(f"reference: losses {ref['losses']} in "
            f"{time.perf_counter() - t_ref:.1f} s; worst at {where}")
    rec["correct"], rec["checks"] = judge(numbers, cell["limits"])
    return rec
