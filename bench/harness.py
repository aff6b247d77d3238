"""One run of one cell: find it, check the chip, drive it, reduce the
trace, read the metrics, print the result.

The result is one JSON object on the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each number that
decided ``correct`` beside its limit. The same checks are the last lines
of standard error.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
from dataclasses import dataclass, field
from pathlib import Path

from bench import spec
from bench.correct import format_checks
from bench.device import (CompileCounter, NoChip, describe, devices,
                          profiled)

__all__ = ["Context", "run", "main_result", "NoChip", "OUT"]

OUT = spec.BENCH.parent / ".bench_out"
# a traced run measures the first TRACE_SECONDS of the window: a longer
# trace of a serving or training window holds millions of operations
TRACE_SECONDS = 20.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclass
class Context:
    """What a driver is given for one run."""

    cell: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devices: list
    compiles: CompileCounter
    out_dir: Path = field(default=OUT)

    def profiled(self):
        return profiled(self.trace, self.out_dir / "trace")

    @staticmethod
    def log(msg: str) -> None:
        log(msg)


def enable_cache() -> str:
    """The program's persistent compile cache, with every program kept
    (the default skips those that compile in under a second)."""
    import jax

    from repro.launch.common import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run(name: str, seed: int, seconds: float, trace: bool, t_start: float,
        require_chip: bool = True, cell: dict | None = None) -> dict:
    """The result of one run of cell ``name`` (or of ``cell`` itself)."""
    cell = spec.cell(name) if cell is None else cell
    devs = devices(cell["chips"], require_chip)
    cache = enable_cache() if require_chip else "off"
    log(f"cell {cell['name']} seed {seed} seconds {seconds} trace "
        f"{int(trace)} on {describe(devs)}; compile cache {cache}")
    driver = spec.module("drivers", cell["driver"])
    window = min(seconds, TRACE_SECONDS) if trace else seconds
    ctx = Context(cell, seed, window, trace, t_start, devs,
                  CompileCounter())
    rec = driver.run(ctx)
    log(f"compile events inside the window: {ctx.compiles.count}")
    rec["cell"] = cell
    rec["chips"] = cell["chips"]
    rec["peak"] = _peak(devs)
    if trace:
        from bench.trace import reduce
        rec["trace"] = reduce(rec["trace_files"], len(devs))
        shutil.rmtree(ctx.out_dir / "trace", ignore_errors=True)
    return assemble(rec, describe(devs), trace)


def _peak(devs):
    from bench.peaks import PEAKS
    return PEAKS.get(devs[0].device_kind)


def assemble(rec: dict, dev: dict, trace: bool) -> dict:
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for name, mod in spec.metric_modules().items():
        if mod.KIND != kind:
            continue
        value = mod.read(rec)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": mod.UNIT}
    dev = dict(dev, memory_peak_bytes=int(rec["memory_peak_bytes"]))
    result = {"correct": bool(rec["correct"]),
              "attempted": int(rec["attempted"]),
              "failed": int(rec["failed"]), "metrics": metrics,
              "device": dev}
    if trace:
        t = rec["trace"]
        dev.update(busy_s=t["busy_s"], window_s=t["window_s"])
        result["breakdown"] = {"device_ops": t["device_ops"][:10],
                               "idle_gaps": t["idle_gaps"][:10]}
    result["checks"] = rec["checks"]
    return result


def print_result(result: dict) -> None:
    for line in format_checks(result["checks"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main_result(args, t_start: float) -> int:
    """Run ``args`` and print; 2 without a chip, 1 when the program is
    at fault (no result line either way)."""
    src = spec.BENCH.parent / "src"
    if not (src / "repro").is_dir():
        print(f"bench: the program's sources are not at {src}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), t_start)
    except (NoChip, LookupError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print_result(result)
    return 0

