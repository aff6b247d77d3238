import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
"""Dry-run deep analysis: per-instruction collective/buffer attribution
with trip-count multipliers — the §Perf hypothesis tool.

A CPU tool by design, like :mod:`repro.launch.dryrun`: the lines above
pin JAX to the CPU and fan the host out into 512 devices before any jax
import, so it never takes an accelerator.

  python -m repro.launch.analyze --arch qwen2.5-3b --shape train_4k \
      [--multi-pod] [--top 15]
"""
import argparse
import re
from collections import defaultdict

from repro.launch import hlo as H

__all__ = ["top_collectives", "top_buffers", "compile_cell"]


def compile_cell(arch: str, shape_name: str, multi_pod: bool):
    from repro.launch.dryrun import run_cell  # noqa: F401 (env set above)
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import SHAPES, get_config
    from repro.dist.sharding import cache_specs, param_specs
    from repro.launch.dryrun import input_specs
    from repro.launch.mesh import dp_axes, make_production_mesh
    from repro.models import build_model
    from repro.optim import adamw_init
    from repro.train import make_prefill, make_serve_step, make_train_step
    import jax.numpy as jnp

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg, mesh=mesh, dp_axes=dp_axes(multi_pod))
    p_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_spec = param_specs(p_shapes, cfg, multi_pod)
    p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), p_spec)
    batch, bspec_tree = input_specs(cfg, shape, mesh, multi_pod, 1)
    b_shard = {k: NamedSharding(mesh, v) for k, v in bspec_tree.items()}
    with mesh:
        if shape.kind == "train":
            opt_shapes = jax.eval_shape(
                lambda p: adamw_init(p, moment_dtype=cfg.moment_dtype),
                p_shapes)
            o_spec = type(opt_shapes)(step=P(), mu=p_spec, nu=p_spec)
            o_shard = jax.tree.map(
                lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s,
                o_spec, is_leaf=lambda x: isinstance(x, P))
            fn = make_train_step(model, grad_shardings=p_shard)
            return jax.jit(fn, in_shardings=(p_shard, o_shard, b_shard),
                           out_shardings=(p_shard, o_shard, None),
                           donate_argnums=(0, 1)
                           ).lower(p_shapes, opt_shapes, batch).compile()
        if shape.kind == "prefill":
            fn = make_prefill(model)
            return jax.jit(fn, in_shardings=(p_shard, b_shard.get("tokens"),
                                             b_shard.get("embeds")),
                           out_shardings=None
                           ).lower(p_shapes, batch.get("tokens"),
                                   batch.get("embeds")).compile()
        cache_shapes = jax.eval_shape(
            lambda: model.init_decode_state(shape.global_batch, shape.seq))
        c_spec = cache_specs(cache_shapes, cfg, mesh, multi_pod)
        c_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), c_spec)
        fn = make_serve_step(model)
        return jax.jit(fn, in_shardings=(p_shard, c_shard, None,
                                         b_shard.get("tokens"),
                                         b_shard.get("embeds")),
                       out_shardings=(None, c_shard), donate_argnums=(1,)
                       ).lower(p_shapes, cache_shapes,
                               jax.ShapeDtypeStruct((), jnp.int32),
                               batch.get("tokens"),
                               batch.get("embeds")).compile()


def _walk(comps, entry, visit):
    """DFS from entry multiplying trip counts; visit(instr, comp, mult)."""
    def go(name, mult):
        comp = comps[name]
        for instr in comp.instrs:
            if instr.op == "while":
                body = re.search(r"body=%?([\w\.\-]+)", instr.attrs)
                trip = H._trip_count(instr, comps) or 1
                if body and body.group(1) in comps:
                    go(body.group(1), mult * trip)
                continue
            if instr.op in ("call", "async-start"):
                fm = re.search(r"(?:to_apply|calls|called_computation)"
                               r"=%?([\w\.\-]+)", instr.attrs)
                if fm and fm.group(1) in comps:
                    go(fm.group(1), mult)
                continue
            visit(instr, comp, mult)
    go(entry, 1.0)


def top_collectives(hlo_text: str, k: int = 15):
    comps, entry = H.parse_module(hlo_text)
    items = defaultdict(lambda: [0.0, 0, ""])

    def visit(instr, comp, mult):
        base = instr.op.removesuffix("-start").removesuffix("-done")
        if base not in H._COLLECTIVES or instr.op.endswith("-done"):
            return
        out_b = H._shape_bytes(instr.out_shapes)
        if instr.op.endswith("-start"):
            out_b //= 2
        moved = {"all-reduce": 2.0 * out_b,
                 "reduce-scatter": out_b * H._group_size(instr.attrs)
                 }.get(base, float(out_b))
        m = re.search(r'op_name="([^"]+)"', instr.attrs)
        src = m.group(1) if m else "?"
        shp = "/".join(f"{dt}{list(d)}" for dt, d in instr.out_shapes[:2])
        key = (base, shp, src[-110:])
        items[key][0] += moved * mult
        items[key][1] += int(mult)

    _walk(comps, entry, visit)
    rows = sorted(((v[0], v[1], k2) for k2, v in items.items()),
                  reverse=True)[:k]
    return rows


def top_buffers(hlo_text: str, k: int = 15):
    comps, entry = H.parse_module(hlo_text)
    items = defaultdict(lambda: [0.0, 0])

    def visit(instr, comp, mult):
        base = instr.op.removesuffix("-start")
        if base in H._COLLECTIVES or instr.op in H._NO_BYTES or \
                instr.op == "reshape":
            return
        b = H._shape_bytes(instr.out_shapes)
        if instr.op in H._READ_OPS:
            for o in instr.operands:
                b += H._shape_bytes(comp.shapes.get(o, []))
        m = re.search(r'op_name="([^"]+)"', instr.attrs)
        src = (m.group(1) if m else instr.op)[-100:]
        items[(instr.op, src)][0] += b * mult
        items[(instr.op, src)][1] += int(mult)

    _walk(comps, entry, visit)
    return sorted(((v[0], v[1], k2) for k2, v in items.items()),
                  reverse=True)[:k]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args()
    compiled = compile_cell(args.arch, args.shape, args.multi_pod)
    txt = compiled.as_text()
    print("== top collectives (bytes moved x trips) ==")
    for moved, trips, (op, shp, src) in top_collectives(txt, args.top):
        print(f"{moved / 2**30:9.2f} GiB x{trips:5d} {op:18s} {shp:28s} {src}")
    print("\n== top HBM traffic contributors ==")
    for b, trips, (op, src) in top_buffers(txt, args.top):
        print(f"{b / 2**30:9.2f} GiB x{trips:5d} {op:22s} {src}")


if __name__ == "__main__":
    main()
