"""The chip: what JAX reports, its peak memory, compiles, the profiler."""
from __future__ import annotations

import contextlib
import shutil
from pathlib import Path

__all__ = ["NoChip", "devices", "describe", "memory_peak_bytes",
           "CompileCounter", "profiled", "annotate"]


class NoChip(RuntimeError):
    """JAX found no accelerator in the peak table, or too few chips."""


def devices(chips: int, require_chip: bool = True):
    """The first ``chips`` devices; with ``require_chip``, TPUs whose
    ``device_kind`` the peak table knows, or :class:`NoChip`."""
    import jax

    from bench.peaks import peak
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"JAX's first device is {devs[0].platform} "
                         f"({devs[0].device_kind}), not a TPU")
        try:
            peak(devs[0].device_kind)
        except KeyError as e:
            raise NoChip(str(e)) from None
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def describe(devs) -> dict:
    import jax
    all_devs = jax.devices()
    return {"platform": all_devs[0].platform, "kind": all_devs[0].device_kind,
            "count": len(all_devs)}


def memory_peak_bytes(devs) -> int:
    """``peak_bytes_in_use`` of the fullest of ``devs`` (0 where the
    backend keeps no such count)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


class CompileCounter:
    """Counts JAX's tracing and backend-compile events while active."""

    def __init__(self):
        self.count = 0
        self.active = False
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.active and ("backend_compile" in event
                            or "jaxpr_trace" in event):
            self.count += 1


@contextlib.contextmanager
def profiled(enabled: bool, out_dir: Path):
    """JAX's profiler around the block when ``enabled``; yields a list
    that holds the ``.xplane.pb`` path once the block has ended."""
    import jax
    found: list[Path] = []
    if not enabled:
        yield found
        return
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    jax.profiler.start_trace(str(out_dir))
    try:
        yield found
    finally:
        jax.profiler.stop_trace()
        found.extend(sorted(out_dir.rglob("*.xplane.pb")))


def annotate(name: str):
    """A host span on the profiler's clock (free when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name)
