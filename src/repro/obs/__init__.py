"""repro.obs — unified telemetry: span tracing + metrics registry.

The measurement substrate every layer reports into:

* :mod:`repro.obs.trace` — low-overhead host-side span recorder with
  Chrome-trace/Perfetto export, instant failure/recovery markers on
  per-DP-group tracks, and the nullable :class:`Telemetry` handle the
  trainer / mesh executor / serving tier thread through their hot
  loops (``None`` keeps the uninstrumented path allocation-free);
* :mod:`repro.obs.metrics` — counters / gauges / exact-quantile
  histograms, snapshottable to deterministic JSON;
* :mod:`repro.obs.scopes` — :data:`DEVICE_SCOPES`, the layer names the
  model / step / sync code puts on every device operation
  (``jax.named_scope``), and the join from a compiled program's text to
  them;
* ``python -m repro.launch.obs trace.json`` — text timeline + the
  recovery-attribution table (time lost to masking vs rollback vs
  restart) rendered from a dumped trace.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               latency_stats, quantile_key)
from repro.obs.scopes import DEVICE_SCOPES, hlo_scopes, scope_of
from repro.obs.trace import (Instant, Span, Telemetry, TraceRecorder,
                             TraceView, load_trace, maybe_span, tick)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "latency_stats",
    "quantile_key", "DEVICE_SCOPES", "hlo_scopes", "scope_of",
    "Telemetry", "TraceRecorder", "TraceView", "Span", "Instant",
    "load_trace", "maybe_span", "tick",
]
