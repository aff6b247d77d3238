"""Device time by layer and idle time by host phase, from a profiler trace
and the text of the programs that ran in it.

``reduce(files, n_devices, programs)`` reads the same ``.xplane.pb`` as
``bench.trace.reduce``, over the same window and with the same clock
skew, and adds what the program's own names tell:

- ``scopes``: device self time inside the window by layer. Each ``XLA
  Ops`` event runs inside an ``XLA Modules`` event of its device; the
  module's name (without its fingerprint) picks the program's text in
  ``programs`` ({module name: ``compiled.as_text()``}), the event's
  instruction name picks the instruction there, and the instruction's
  ``op_name`` gives its layer (``repro.obs.hlo_scopes``). Time with no
  program text, no instruction or no scope is ``other``. Summed over
  devices and divided by their number, like ``device_ops``.
- ``device_ops``: ``bench.trace``'s labels, with ``<scope>/`` in front
  where a scope was found (``attention/fusion:kOutput``).
- ``idle_gaps``: each idle instant of device 0 inside the window given to
  the innermost host span that covers it, the shortest one, harness span
  or program span (:data:`PROGRAM_SPANS`); ``host_other`` where none
  does. The values sum to the window less device 0's busy time.

Against a program without scopes (``repro.obs.hlo_scopes`` missing, or
no ``programs`` given) every second is ``other`` and the labels are
``bench.trace``'s own.
"""
from __future__ import annotations

import bisect
import re
from collections import Counter, defaultdict

from bench.trace import (LABELS, _clip, _device_planes, _events, _line,
                         _skew, label, self_times, union)

__all__ = ["PROGRAM_SPANS", "reduce", "split_idle", "scope_tables"]

# the program's own host spans (repro.obs.trace's vocabulary)
PROGRAM_SPANS = ("step", "batch", "dispatch", "loss_read", "feed",
                 "ckpt_save", "recover", "prefill", "decode", "admit",
                 "evict")
_INST = re.compile(r"%([\w.\-]+) = ")
_FINGERPRINT = re.compile(r"\(\d+\)$")


def scope_tables(programs: dict | None) -> dict:
    """{module name: {instruction: scope or None}} of ``programs``; empty
    where the program has no ``repro.obs.hlo_scopes``."""
    try:
        from repro.obs import hlo_scopes
    except ImportError:
        return {}
    tables = {}
    for text in (programs or {}).values():
        module, scopes = hlo_scopes(text)
        tables[module] = scopes
    return tables


def split_idle(gaps, spans) -> dict[str, float]:
    """Seconds of ``gaps`` [(start, end)] by the shortest of ``spans``
    [(name, start, end)] that covers each instant (``host_other`` where
    none does)."""
    events = []
    for s, e in gaps:
        events += [(s, 1, (0.0, "")), (e, -1, (0.0, ""))]
    for name, s, e in spans:
        key = (e - s, name)
        events += [(s, 2, key), (e, -2, key)]
    events.sort()
    out: dict[str, float] = defaultdict(float)
    active: Counter = Counter()
    in_gap, prev = 0, None
    for t, kind, key in events:
        if in_gap and t > prev:
            live = [k for k, n in active.items() if n > 0]
            out[min(live)[1] if live else "host_other"] += t - prev
        if abs(kind) == 1:
            in_gap += kind
        else:
            active[key] += 1 if kind > 0 else -1
        prev = t
    return dict(out)


def _host_spans(pd, names):
    return [(n, s, e) for plane in pd.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for n, s, e in _events(line)
            if n in names]


def _scope_of(tables, modules, starts, name, start):
    """Scope of the operation ``name`` that starts at ``start``: look up
    the module run that holds it, then the instruction."""
    k = bisect.bisect_right(starts, start) - 1
    if k < 0 or start >= modules[k][1]:
        return None
    table = tables.get(modules[k][2])
    m = _INST.match(name)
    if table is None or m is None:
        return None
    return table.get(m.group(1))


def reduce(files, n_devices: int, programs: dict | None = None) -> dict:
    """The layer and host-phase split of the trace ``files[0]``."""
    from jax.profiler import ProfileData
    if not files:
        raise RuntimeError("the profiler wrote no trace")
    pd = ProfileData.from_file(str(files[0]))
    spans = _host_spans(pd, set(LABELS) | set(PROGRAM_SPANS))
    windows = [(s, e) for name, s, e in spans if name == "window"]
    if len(windows) != 1:
        raise RuntimeError(f"expected one window span, found {len(windows)}")
    lo, hi = windows[0]
    planes = _device_planes(pd, n_devices)
    if not planes:
        raise RuntimeError("no device plane in the trace")
    skew = _skew(pd, sorted(s for _, s, _ in
                            _events(_line(planes[0], "XLA Modules"))))
    tables = scope_tables(programs)

    scopes: dict[str, float] = defaultdict(float)
    ops: dict[str, float] = defaultdict(float)
    gaps = []
    for i, plane in enumerate(planes):
        modules = sorted((s + skew, e + skew, _FINGERPRINT.sub("", n))
                         for n, s, e in _events(_line(plane, "XLA Modules")))
        starts = [m[0] for m in modules]
        evs = [(n, s + skew, e + skew)
               for n, s, e in _events(_line(plane, "XLA Ops"))
               if e + skew > lo and s + skew < hi]
        for n, s, e, own in self_times(evs):
            if e <= s:
                continue
            t = own * (min(e, hi) - max(s, lo)) / (e - s)
            scope = _scope_of(tables, modules, starts, n, s)
            scopes[scope or "other"] += t
            ops[f"{scope}/{label(n)}" if scope else label(n)] += t
        if i == 0:
            busy = union(_clip([(s, e) for _, s, e in evs], lo, hi))
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            gaps = [(edges[k], edges[k + 1])
                    for k in range(0, len(edges) - 1, 2)
                    if edges[k + 1] > edges[k]]
    n = len(planes)
    inner = [(name, s, e) for name, s, e in spans if name != "window"]
    return {
        "scopes": {k: v / n for k, v in scopes.items()},
        "device_ops": sorted(([k, v / n] for k, v in ops.items()),
                             key=lambda kv: -kv[1]),
        "idle_gaps": sorted(([k, v] for k, v in
                             split_idle(gaps, inner).items()),
                            key=lambda kv: -kv[1]),
    }
