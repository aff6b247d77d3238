import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers,
SPMD-partitions, compiles, and fits — without touching real hardware.

A CPU tool by design: the lines above pin JAX to the CPU (it never
takes an accelerator, even on a machine that has one) and fan the host
out into 512 devices. They MUST precede any jax import (jax locks the
platform and device count on first init); smoke tests and benches never
import this module, so they keep seeing 1 device.

Per cell this script:
  1. builds the production mesh (16,16) or (2,16,16);
  2. jits the real train / prefill / serve step with the production
     in/out shardings (donated params+opt);
  3. ``.lower().compile()`` — any sharding mismatch, unsupported
     collective, or compile-time OOM fails the cell;
  4. records ``memory_analysis()`` (per-device bytes: proves it fits 16 GB
     HBM), ``cost_analysis()`` (per-device FLOPs/bytes), and the
     collective-traffic table parsed from ``compiled.as_text()`` —
     the §Roofline inputs.

Results append to ``benchmarks/results/dryrun/*.json`` (one file per
cell, so a sweep can resume after interruption).

Usage:
  python -m repro.launch.dryrun --arch glm4-9b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --list
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, SHAPES, applicable, get_config
from repro.dist.sharding import batch_spec, cache_specs, param_specs
from repro.launch.hlo import analyze_hlo
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.models import build_model
from repro.optim import adamw_init
from repro.train import make_prefill, make_serve_step, make_train_step

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"

# TPU v5e per-chip constants (§Roofline)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def input_specs(cfg, shape, mesh, multi_pod: bool, s_a: int = 1):
    """ShapeDtypeStructs + shardings for one cell's step inputs."""
    bspec = batch_spec(shape.global_batch, mesh, multi_pod)
    if shape.kind == "train":
        n_micro = s_a * cfg.grad_accum
        b_micro = shape.global_batch // cfg.grad_accum
        batch = {"labels": _sds((n_micro, b_micro, shape.seq), jnp.int32),
                 "weights": _sds((n_micro, b_micro), jnp.float32)}
        shard = {"labels": P(None, bspec, None),
                 "weights": P(None, bspec)}
        if cfg.frontend:
            batch["embeds"] = _sds((n_micro, b_micro, shape.seq, cfg.d_model),
                                   jnp.bfloat16)
            shard["embeds"] = P(None, bspec, None, None)
        else:
            batch["tokens"] = _sds((n_micro, b_micro, shape.seq), jnp.int32)
            shard["tokens"] = P(None, bspec, None)
        return batch, shard
    if shape.kind == "prefill":
        b = shape.global_batch
        if cfg.frontend:
            return ({"embeds": _sds((b, shape.seq, cfg.d_model), jnp.bfloat16)},
                    {"embeds": P(bspec, None, None)})
        return ({"tokens": _sds((b, shape.seq), jnp.int32)},
                {"tokens": P(bspec, None)})
    # decode
    b = shape.global_batch
    if cfg.frontend:
        return ({"embeds": _sds((b, 1, cfg.d_model), jnp.bfloat16)},
                {"embeds": P(bspec, None, None)})
    return ({"tokens": _sds((b, 1), jnp.int32)},
            {"tokens": P(bspec, None)})


def model_flops_per_device(cfg, shape, n_devices: int) -> float:
    """MODEL_FLOPS = 6*N_active*D (train) / 2*N_active*D (inference),
    expressed per device to match cost_analysis granularity."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq
        return 6.0 * n_active * tokens / n_devices
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq
        return 2.0 * n_active * tokens / n_devices
    return 2.0 * n_active * shape.global_batch / n_devices


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               s_a: int = 1, overrides: dict | None = None):
    """Lower one cell's production jit-site and return ``(lowered,
    meta)``. This is THE jit call the sweep certifies — ``run_cell``
    compiles it for the roofline record, and the static analyzer
    (``repro.analysis`` via ``python -m repro.launch.lint``) re-lowers
    it to audit donation aliasing, hot-path purity, wire dtypes, and
    collective-schedule determinism on the byte-identical program.

    ``meta`` carries what the HLO passes need but the compiled text
    alone cannot recover: the per-argument flat leaf counts
    (``arg_leaves``), the donated argnums, and the expected per-device
    shape of the SPARe weight-table input (``weights_shape``, train
    cells only — the liveness check that proves masking reaches the
    program as runtime data).
    """
    cfg = get_config(arch)
    attn_chunk = 1024
    if overrides:
        overrides = dict(overrides)
        attn_chunk = overrides.pop("__attn_chunk", 1024)
        if overrides:
            cfg = cfg.scaled(**overrides)
    shape = SHAPES[shape_name]
    ok_run, why = applicable(cfg, shape)
    if not ok_run:
        return None, {"skipped": True, "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg, mesh=mesh, dp_axes=dp_axes(multi_pod),
                        attn_chunk=attn_chunk)

    p_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_spec = param_specs(p_shapes, cfg, multi_pod)
    p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), p_spec)

    batch, bspec_tree = input_specs(cfg, shape, mesh, multi_pod, s_a)
    b_shard = {k: NamedSharding(mesh, v) for k, v in bspec_tree.items()}
    n_leaves = lambda t: len(jax.tree_util.tree_leaves(t))  # noqa: E731

    meta = {"devices": mesh.size, "kind": shape.kind}
    with mesh:
        if shape.kind == "train":
            opt_shapes = jax.eval_shape(
                lambda p: adamw_init(p, moment_dtype=cfg.moment_dtype),
                p_shapes)
            o_spec = type(opt_shapes)(
                step=P(), mu=jax.tree.map(lambda s: s, p_spec),
                nu=jax.tree.map(lambda s: s, p_spec))
            o_shard = jax.tree.map(
                lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s,
                o_spec, is_leaf=lambda x: isinstance(x, P))
            step_fn = make_train_step(model, grad_shardings=p_shard)
            jitted = jax.jit(step_fn,
                             in_shardings=(p_shard, o_shard, b_shard),
                             out_shardings=(p_shard, o_shard, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(p_shapes, opt_shapes, batch)
            from repro.launch.mesh import dp_degree
            w = batch["weights"]
            meta.update(
                donate=(0, 1),
                arg_leaves=[n_leaves(p_shapes), n_leaves(opt_shapes),
                            n_leaves(batch)],
                weights_shape=(f"f32[{w.shape[0]},"
                               f"{w.shape[1] // dp_degree(mesh, multi_pod)}]"))
        elif shape.kind == "prefill":
            fn = make_prefill(model)
            jitted = jax.jit(fn, in_shardings=(p_shard, b_shard.get("tokens"),
                                               b_shard.get("embeds")),
                             out_shardings=None)
            lowered = jitted.lower(p_shapes, batch.get("tokens"),
                                   batch.get("embeds"))
            meta.update(donate=(), arg_leaves=[
                n_leaves(p_shapes), n_leaves(batch.get("tokens")),
                n_leaves(batch.get("embeds"))], weights_shape=None)
        else:  # decode
            cache_shapes = jax.eval_shape(
                lambda: model.init_decode_state(shape.global_batch, shape.seq))
            c_spec = cache_specs(cache_shapes, cfg, mesh, multi_pod)
            c_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), c_spec)
            fn = make_serve_step(model)
            jitted = jax.jit(
                fn,
                in_shardings=(p_shard, c_shard, None,
                              b_shard.get("tokens"), b_shard.get("embeds")),
                out_shardings=(None, c_shard),
                donate_argnums=(1,))
            lowered = jitted.lower(p_shapes, cache_shapes,
                                   jax.ShapeDtypeStruct((), jnp.int32),
                                   batch.get("tokens"), batch.get("embeds"))
            meta.update(donate=(1,), arg_leaves=[
                n_leaves(p_shapes), n_leaves(cache_shapes), 1,
                n_leaves(batch.get("tokens")),
                n_leaves(batch.get("embeds"))], weights_shape=None)
    return lowered, meta


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             s_a: int = 1, variant: str = "baseline",
             overrides: dict | None = None) -> dict:
    cfg = get_config(arch)
    if overrides and any(k != "__attn_chunk" for k in overrides):
        cfg = cfg.scaled(**{k: v for k, v in overrides.items()
                            if k != "__attn_chunk"})
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "variant": variant, "s_a": s_a, "ok": False}

    t0 = time.perf_counter()
    lowered, meta = lower_cell(arch, shape_name, multi_pod, s_a=s_a,
                               overrides=overrides)
    if lowered is None:
        rec.update(skipped=True, reason=meta["reason"], ok=True)
        return rec
    n_dev = meta["devices"]
    rec["lower_s"] = round(time.perf_counter() - t0, 1)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.perf_counter() - t1, 1)

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):   # pre-0.5 jax: one dict per program
        ca = ca[0] if ca else {}
    # trip-count-aware accounting (XLA's cost_analysis visits while bodies
    # once — useless for scan-over-layers; see repro/launch/hlo.py)
    hc = analyze_hlo(compiled.as_text())
    colls = {
        "counts": {k: int(v) for k, v in hc.collective_counts.items()},
        "bytes": {k: round(v) for k, v in hc.collective_bytes.items()},
        "total_bytes": round(hc.total_collective_bytes),
    }

    flops = hc.flops
    bytes_accessed = hc.bytes_accessed
    coll_bytes = hc.total_collective_bytes
    mf = model_flops_per_device(cfg, shape, n_dev)

    rec.update(
        ok=True,
        devices=n_dev,
        arg_bytes=int(ma.argument_size_in_bytes),
        out_bytes=int(ma.output_size_in_bytes),
        temp_bytes=int(ma.temp_size_in_bytes),
        alias_bytes=int(ma.alias_size_in_bytes),
        peak_bytes=int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                       + ma.output_size_in_bytes - ma.alias_size_in_bytes),
        flops_per_device=flops,
        bytes_per_device=bytes_accessed,
        xla_flops_loop_body_once=float(ca.get("flops", 0.0)),
        unknown_trip_loops=hc.unknown_trip_loops,
        collectives=colls,
        model_flops_per_device=mf,
        useful_flops_ratio=(mf / flops if flops else 0.0),
        roofline={
            "compute_s": flops / PEAK_FLOPS,
            # fusion-boundary reads+writes: the cost_analysis-convention
            # upper bound on HBM traffic (XLA:TPU fuses more aggressively)
            "memory_s": bytes_accessed / HBM_BW,
            # outputs-only: optimistic-fusion lower bound
            "memory_lb_s": hc.bytes_written / HBM_BW,
            "collective_s": coll_bytes / ICI_BW,
        },
    )
    terms = {k: rec["roofline"][k]
             for k in ("compute_s", "memory_s", "collective_s")}
    rec["bottleneck"] = max(terms, key=terms.get)
    return rec


def cell_list():
    cells = []
    for arch in ARCHS:
        for shape_name in SHAPES:
            for multi_pod in (False, True):
                cells.append((arch, shape_name, multi_pod))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--s-a", type=int, default=1,
                    help="all-reduce stack depth to lower (SPARe S_A)")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (python literal), "
                         "e.g. --set remat_policy='dots'")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out-dir", default=str(RESULTS_DIR))
    args = ap.parse_args()

    if args.list:
        for arch, shape, mp in cell_list():
            print(f"{arch} {shape} {'2x16x16' if mp else '16x16'}")
        return

    out_dir = Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    mesh_name = "2x16x16" if args.multi_pod else "16x16"
    tag = f"{args.arch}__{args.shape}__{mesh_name}__{args.variant}"
    import ast
    overrides = {}
    for kv in args.set:
        k, _, v = kv.partition("=")
        overrides[k] = ast.literal_eval(v)
    try:
        rec = run_cell(args.arch, args.shape, args.multi_pod,
                       s_a=args.s_a, variant=args.variant,
                       overrides=overrides or None)
    except Exception as e:  # noqa: BLE001 — record the failure, don't crash the sweep
        rec = {"arch": args.arch, "shape": args.shape, "mesh": mesh_name,
               "variant": args.variant, "ok": False,
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    (out_dir / f"{tag}.json").write_text(json.dumps(rec, indent=1))
    status = "OK" if rec.get("ok") else "FAIL"
    if rec.get("skipped"):
        status = "SKIP"
    print(f"[{status}] {tag} "
          f"compile={rec.get('compile_s', '-')}s "
          f"peak={rec.get('peak_bytes', 0)/2**30:.2f}GiB "
          f"bottleneck={rec.get('bottleneck', '-')}")
    if not rec.get("ok"):
        print(rec.get("error", ""))
        raise SystemExit(1)


if __name__ == "__main__":
    main()
