"""From a profiler trace to the numbers the per-layer metrics read.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
Device planes (``/device:TPU:<i>``) carry one event per operation on
their ``XLA Ops`` line and one per program run on their ``XLA Modules``
line; the host plane carries the harness's own spans
(``jax.profiler.TraceAnnotation``) and the runtime's dispatches
(``PJRT_LoadedExecutable_Execute``). The measured window is the host
span named ``window``.

The device clock runs about a millisecond behind the host's in these
traces (a program appears to start before the host dispatched it). The
reduction shifts device times by the largest lead of a dispatch over its
program run, pairing the k-th dispatch of the trace with the k-th run
on device 0; where the counts differ it shifts nothing.

- ``busy_s``: per device, the union of operation intervals inside the
  window, averaged over the devices used; ``window_s`` its length.
- ``idle_gaps``: the window's time with no operation on device 0, each
  gap given to the innermost harness span that covers its middle
  (``host_other`` where none does), summed by span name.
- ``device_ops``: self time of operations inside the window (an
  operation's time less that of the operations nested in it, as a
  ``while`` holds its body) by label: the HLO instruction's name without
  its numeric suffix, with the fusion kind where there is one
  (``fusion:kOutput``), summed over devices and divided by their number.
- ``modules``: program time inside the window by the program's name, per
  device averaged, with the number of runs.
- ``exposed_collective_s``: per device, the time of collective
  operations (by instruction name: all-reduce, all-gather,
  reduce-scatter, all-to-all, collective-permute, with their async
  ``-start``/``-done`` halves) during which no other operation runs,
  averaged.
"""
from __future__ import annotations

import re
from collections import defaultdict

import numpy as np

__all__ = ["reduce", "union", "subtract", "label", "self_times",
           "COLLECTIVE"]

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute",
    re.I)
_INST = re.compile(r"%([\w.\-]+) = ")
_SUFFIX = re.compile(r"\.\d+$")
_KIND = re.compile(r"kind=(k\w+)")
LABELS = ("window", "train_step", "engine.step", "submit", "wait_arrival")


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged copy of ``[(start, end), ...]``."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def subtract(a, b) -> float:
    """Length of the merged intervals ``a`` not covered by ``b``."""
    a, b = union(a), union(b)
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def label(name: str) -> str:
    """Short label of an ``XLA Ops`` event (whose name is the whole HLO
    instruction): ``%fusion.12 = ... kind=kOutput ...`` -> ``fusion:kOutput``."""
    m = _INST.match(name)
    if not m:
        return name[:64]
    base = _SUFFIX.sub("", m.group(1))
    kind = _KIND.search(name)
    return f"{base}:{kind.group(1)}" if kind else base


def self_times(events) -> list[tuple[str, float, float, float]]:
    """``(name, start, end, self)`` for nested ``(name, start, end)``
    events: each one's length less its direct children's."""
    out, stack = [], []
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][2] <= s:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        stack.append([name, s, e, e - s])
    out.extend(tuple(x) for x in reversed(stack))
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _device_planes(pd, n_devices: int):
    planes = [p for p in pd.planes if re.fullmatch(r"/device:TPU:\d+", p.name)]
    planes.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    return planes[:n_devices]


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def _events(line):
    if line is None:
        return []
    return [(ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns)
             * 1e-9) for ev in line.events]


def _host_spans(pd):
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, s, e in _events(line):
                if name in LABELS:
                    spans.append((name, s, e))
    return spans


def _skew(pd, runs) -> float:
    """Seconds to add to device times (see the module docstring)."""
    calls = sorted(s for plane in pd.planes if plane.name.startswith("/host:")
                   for line in plane.lines for n, s, _ in _events(line)
                   if n == "PJRT_LoadedExecutable_Execute")
    if not calls or len(calls) != len(runs):
        return 0.0
    return max(0.0, max(c - r for c, r in zip(calls, runs)))


def reduce(files, n_devices: int) -> dict:
    """The numbers of the trace ``files[0]`` over ``n_devices``."""
    from jax.profiler import ProfileData
    if not files:
        raise RuntimeError("the profiler wrote no trace")
    pd = ProfileData.from_file(str(files[0]))
    spans = _host_spans(pd)
    windows = [(s, e) for name, s, e in spans if name == "window"]
    if len(windows) != 1:
        raise RuntimeError(f"expected one window span, found {len(windows)}")
    lo, hi = windows[0]
    planes = _device_planes(pd, n_devices)
    if not planes:
        raise RuntimeError("no device plane in the trace")

    skew = _skew(pd, sorted(s for _, s, _ in
                            _events(_line(planes[0], "XLA Modules"))))

    busy, exposed = [], []
    ops: dict[str, float] = defaultdict(float)
    modules: dict[str, list] = defaultdict(lambda: [0.0, 0])
    idle_by: dict[str, float] = defaultdict(float)
    gaps_all = []
    for i, plane in enumerate(planes):
        evs = [(n, s + skew, e + skew)
               for n, s, e in _events(_line(plane, "XLA Ops"))
               if e + skew > lo and s + skew < hi]
        intervals = union(_clip([(s, e) for _, s, e in evs], lo, hi))
        busy.append(sum(e - s for s, e in intervals))
        for n, s, e, own in self_times(evs):
            ops[label(n)] += own * (min(e, hi) - max(s, lo)) / (e - s) \
                if e > s else 0.0
        coll = [(s, e) for n, s, e in evs if COLLECTIVE.search(label(n))]
        other = [(s, e) for n, s, e in evs
                 if not COLLECTIVE.search(label(n))]
        exposed.append(subtract(_clip(coll, lo, hi), _clip(other, lo, hi)))
        for n, s, e in _events(_line(plane, "XLA Modules")):
            s, e = s + skew, e + skew
            if e > lo and s < hi:
                modules[n][0] += (min(e, hi) - max(s, lo)) / len(planes)
                modules[n][1] += 1
        if i == 0:
            edges = [lo] + [x for iv in intervals for x in iv] + [hi]
            gaps_all = [(edges[k], edges[k + 1])
                        for k in range(0, len(edges) - 1, 2)
                        if edges[k + 1] > edges[k]]
    inner = [(n, s, e) for n, s, e in spans if n != "window"]
    for s, e in gaps_all:
        mid = 0.5 * (s + e)
        cover = [(e2 - s2, n) for n, s2, e2 in inner if s2 <= mid <= e2]
        idle_by[min(cover)[1] if cover else "host_other"] += e - s
    n = len(planes)
    return {
        "window_s": hi - lo,
        "busy_s": float(np.mean(busy)),
        "device_ops": sorted(([k, v / n] for k, v in ops.items()),
                             key=lambda kv: -kv[1]),
        "idle_gaps": sorted(([k, v] for k, v in idle_by.items()),
                            key=lambda kv: -kv[1]),
        "modules": {k: {"seconds": v[0], "runs": v[1] // n}
                    for k, v in modules.items()},
        "exposed_collective_s": float(np.mean(exposed)),
        "skew_s": skew,
        "spans": spans,
    }
