"""Telemetry substrate tests: span recorder, metrics registry, and the
instrumented trainer/executor paths.

The gates ISSUE 7 promises:

* span nesting survives a Chrome-trace export/load round trip;
* histogram quantiles are numpy-exact (no sketch drift under p99.9);
* the telemetry-off path allocates NOTHING in ``repro.obs`` across a
  multi-step trainer run (tracemalloc-audited);
* two seeded runs under a deterministic clock export byte-identical
  traces and metrics snapshots;
* the obs CLI attributes >= 95% of a real (wall-clock) run into named
  phases and exits 0 under its own assert flags;
* masked and unmasked schedules at equal ``S_A`` publish identical
  wire-traffic metrics on the 8-device mesh (``spmd``-marked).
"""
import json
import os
import tracemalloc
import types

import numpy as np
import pytest

from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               latency_stats, quantile_key)
from repro.obs.trace import (NULL_SPAN, Telemetry, TraceRecorder,
                             load_trace, maybe_span, tick)


@pytest.fixture(scope="module")
def cfg():
    from repro.configs import smoke_config
    return smoke_config("qwen2.5-3b").scaled(grad_accum=1)


def _trainer(cfg, tel=None, *, n=6, r=2):
    from repro.train.trainer import SpareTrainer
    return SpareTrainer(cfg, n_groups=n, redundancy=r, seq=32,
                        per_type_batch=1, total_steps=100, telemetry=tel)


# ------------------------------------------------------------------ #
# trace recorder: nesting + export round trip                        #
# ------------------------------------------------------------------ #
def test_span_nesting_export_round_trip(tmp_path):
    rec = TraceRecorder(clock=tick())
    with rec.span("step", args={"step": 0}):
        with rec.span("compute"):
            with rec.span("feed"):
                pass
    rec.instant("failure", track="dp/1", args={"step": 0})
    with rec.span("recover", args={"victims": [1], "wipeout": False}):
        pass
    with rec.span("step", args={"step": 1}):
        pass

    path = tmp_path / "t.json"
    rec.dump(path)
    for view in (load_trace(str(path)), load_trace(rec.dumps()),
                 load_trace(rec.to_chrome())):
        assert view.tracks == ["dp/1", "main"]
        steps = view.named("step")
        assert [s.depth for s in steps] == [0, 0]
        assert [s.args["step"] for s in steps] == [0, 1]
        (compute,) = view.named("compute")
        (feed,) = view.named("feed")
        assert (compute.depth, feed.depth) == (1, 2)
        # containment: child strictly inside parent
        assert steps[0].ts <= compute.ts and compute.end <= steps[0].end
        assert compute.ts <= feed.ts and feed.end <= compute.end
        (rc,) = view.named("recover")
        assert rc.depth == 0 and rc.args["victims"] == [1]
        (inst,) = view.instants
        assert (inst.name, inst.track) == ("failure", "dp/1")
        assert view.wall_us("main") > 0


def test_trace_is_valid_chrome_format():
    rec = TraceRecorder(clock=tick())
    with rec.span("step"):
        pass
    rec.instant("failure", track="dp/0")
    doc = json.loads(rec.dumps())
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} == {"M", "X", "i"}
    (x,) = [e for e in evs if e["ph"] == "X"]
    assert x["tid"] == 0 and "dur" in x and "ts" in x
    (i,) = [e for e in evs if e["ph"] == "i"]
    assert i["s"] == "t"
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert names == {"main", "dp/0"}


# ------------------------------------------------------------------ #
# metrics: exact quantiles, registry, latency stats                  #
# ------------------------------------------------------------------ #
def test_histogram_quantiles_numpy_exact():
    rng = np.random.default_rng(0)
    vals = np.concatenate([rng.normal(10.0, 3.0, 997),
                           rng.exponential(50.0, 211)])
    h = Histogram()
    h.observe_many(vals[:500])
    for v in vals[500:]:
        h.observe(float(v))
    assert h.count == len(vals)
    assert h.sum == pytest.approx(float(vals.sum()))
    for q in (0.0, 1.0, 25.0, 50.0, 90.0, 99.0, 99.9, 100.0):
        assert h.quantile(q) == float(np.percentile(vals, q))
    s = h.summary(quantiles=(50.0, 99.9))
    assert s["p99_9"] == float(np.percentile(vals, 99.9))
    assert s["count"] == len(vals)


def test_histogram_empty():
    h = Histogram()
    assert h.summary() == {"count": 0}
    with pytest.raises(ValueError):
        h.quantile(50.0)


def test_quantile_key():
    assert quantile_key(50) == "p50"
    assert quantile_key(99.9) == "p99_9"
    assert quantile_key(99.0) == "p99"


def test_registry_get_or_create_and_collisions():
    reg = MetricsRegistry()
    c = reg.counter("train.steps")
    assert reg.counter("train.steps") is c
    c.inc()
    c.inc(4)
    reg.gauge("train.s_a").set(2)
    reg.histogram("lat").observe_many([1.0, 2.0, 3.0])
    with pytest.raises(TypeError):
        reg.gauge("train.steps")
    snap = reg.snapshot()
    assert snap["counters"]["train.steps"] == 5
    assert snap["gauges"]["train.s_a"] == 2
    assert snap["histograms"]["lat"]["count"] == 3
    assert "train.steps" in reg and "nope" not in reg
    # identical observation sequences snapshot byte-identically
    reg2 = MetricsRegistry()
    reg2.counter("train.steps").inc(5)
    reg2.gauge("train.s_a").set(2)
    reg2.histogram("lat").observe_many([1.0, 2.0, 3.0])
    assert reg.dumps() == reg2.dumps()


def test_latency_stats_p999():
    rng = np.random.default_rng(1)
    lats = [rng.exponential(0.01, 40) for _ in range(25)]
    done = [types.SimpleNamespace(latencies=l) for l in lats]
    out = latency_stats(done)
    allv = np.concatenate(lats)
    assert out["tokens"] == allv.size
    for q, key in ((50.0, "p50_ms"), (99.0, "p99_ms"), (99.9, "p99_9_ms")):
        assert out[key] == round(float(np.percentile(allv, q)) * 1e3, 3)
    empty = latency_stats([])
    assert empty == {"tokens": 0, "p50_ms": None, "p99_ms": None,
                     "p99_9_ms": None}


def test_exec_cache_counters_are_registry_entries():
    """Satellite gate: the serving ExecutableCache's miss/hit counters
    ARE the metrics registry's — snapshot and cache cannot diverge."""
    from repro.serve.engine import ExecutableCache
    reg = MetricsRegistry()
    cache = ExecutableCache(reg)
    assert cache.get(("decode", 8), lambda: "exe-a") == "exe-a"
    assert cache.get(("decode", 8), lambda: "never") == "exe-a"
    assert cache.get(("prefill", 8), lambda: "exe-b") == "exe-b"
    snap = reg.snapshot()["counters"]
    assert (cache.misses, cache.hits) == (2, 1)
    assert snap["serve.exec_cache.misses"] == 2
    assert snap["serve.exec_cache.hits"] == 1
    # standalone cache still counts, just privately
    solo = ExecutableCache()
    solo.get(("k",), lambda: 1)
    assert (solo.misses, solo.hits) == (1, 0)


# ------------------------------------------------------------------ #
# the telemetry-off hot path is allocation-free                      #
# ------------------------------------------------------------------ #
def test_null_span_is_a_singleton():
    assert maybe_span(None, "step") is NULL_SPAN
    assert maybe_span(None, "x", "dp/0", None) is NULL_SPAN
    with maybe_span(None, "step") as s:
        assert s is None
    # metrics-only telemetry still measures durations (no recording)
    tel_off = Telemetry(trace=False, clock=tick())
    with tel_off.span("step") as sp:
        pass
    assert sp.dur > 0 and tel_off.tracer is None


def test_telemetry_off_trainer_run_allocates_nothing_in_obs(cfg):
    """Run the real train loop (make_train_step dispatch included) with
    telemetry=None under tracemalloc: zero bytes may be attributed to
    any file in ``repro/obs``."""
    import repro.obs.trace as trace_mod
    tr = _trainer(cfg, None, n=4, r=2)
    tr.run(1)                      # compile outside the audited window
    obs_glob = os.path.join(os.path.dirname(trace_mod.__file__), "*")
    tracemalloc.start()
    try:
        tr.run(3)
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    obs_allocs = snap.filter_traces([tracemalloc.Filter(True, obs_glob)])
    leaked = sum(st.size for st in obs_allocs.statistics("filename"))
    assert leaked == 0, f"telemetry-off path allocated {leaked}B in obs"


# ------------------------------------------------------------------ #
# instrumented trainer: determinism + recovery accounting            #
# ------------------------------------------------------------------ #
def _seeded_traced_run(cfg):
    from repro.train.trainer import PoissonInjector
    tel = Telemetry(clock=tick())
    tr = _trainer(cfg, tel)
    rep = tr.run(8, injector=PoissonInjector(2.0, seed=7))
    return tel, rep


def test_trace_and_metrics_byte_determinism(cfg):
    tel_a, rep_a = _seeded_traced_run(cfg)
    tel_b, rep_b = _seeded_traced_run(cfg)
    assert rep_a.failures == rep_b.failures > 0
    assert tel_a.tracer.dumps() == tel_b.tracer.dumps()
    assert tel_a.metrics.dumps() == tel_b.metrics.dumps()
    snap = tel_a.snapshot()
    # wipe-outs roll the step counter back, so executed steps >= asked
    assert snap["counters"]["train.steps"] == rep_a.steps_done >= 8
    assert snap["counters"]["train.failures"] == rep_a.failures
    assert snap["histograms"]["train.step_seconds"]["count"] == \
        rep_a.steps_done
    assert snap["gauges"]["train.s_a"] is not None


def test_recovery_events_carry_durations(cfg):
    from repro.train.trainer import PoissonInjector
    tr = _trainer(cfg)
    # n=6, r=2, mtbf 1.5 steps: masks AND at least one wipe-out
    rep = tr.run(25, injector=PoissonInjector(1.5, seed=0),
                 snapshot_every=5)
    masks = [e for e in rep.events if not e.wipeout]
    wipes = [e for e in rep.events if e.wipeout]
    assert masks and wipes
    for e in rep.events:
        assert e.wall_seconds > 0           # measured host wall-clock
    for e in masks:
        assert e.step_seconds >= 0          # controller step-clock cost
        assert e.restart_seconds == 0.0 and e.rollback_depth == 0
    for e in wipes:
        assert e.restart_seconds == tr._t_restart > 0
        assert e.rollback_depth >= 0


def test_obs_cli_attribution_on_real_run(cfg, tmp_path, capsys):
    """Acceptance: a real (wall-clock) traced run analyzed by the obs
    CLI attributes >= 95% of main-track wall into named phases and
    carries failure markers + recovery spans."""
    from repro.launch import obs as obs_cli
    from repro.train.trainer import PoissonInjector
    tel = Telemetry()
    tr = _trainer(cfg, tel)
    rep = tr.run(6, injector=PoissonInjector(1.5, seed=3))
    assert rep.failures > 0
    path = tmp_path / "run.trace.json"
    tel.dump_trace(path)

    view = load_trace(str(path))
    ana = obs_cli.analyze(view)
    assert ana["coverage"] >= 0.95
    assert ana["failure_markers"] == rep.failures
    assert all(t.startswith("dp/") for t in ana["failure_tracks"])
    assert len(ana["recovery_events"]) == len(rep.events)
    kinds = {r["kind"] for r in ana["recovery_events"]}
    assert kinds <= {"mask", "restart"}
    phases = {p["phase"] for p in ana["phases"]}
    assert {"step", "batch", "dispatch", "loss_read"} <= phases

    rc = obs_cli.main([str(path), "--assert-coverage", "0.95",
                       "--assert-recovery-markers",
                       "--json", str(tmp_path / "rep.json")])
    assert rc == 0
    assert json.load(open(tmp_path / "rep.json"))["coverage"] >= 0.95
    # a trace with no failures must fail --assert-recovery-markers
    quiet = Telemetry(clock=tick())
    with quiet.span("step"):
        pass
    quiet.dump_trace(tmp_path / "quiet.json")
    capsys.readouterr()
    assert obs_cli.main([str(tmp_path / "quiet.json"),
                         "--assert-recovery-markers"]) == 1


# ------------------------------------------------------------------ #
# mesh executor: masked vs unmasked wire metrics (spmd)              #
# ------------------------------------------------------------------ #
@pytest.mark.spmd
def test_masked_vs_unmasked_wire_metrics_parity(cfg):
    """SPARe's no-recompile thesis through the metrics lens: a masked
    schedule at the same S_A publishes byte-identical wire-traffic
    gauges (the HLO-derived collective accounting) as the healthy one."""
    from repro.core import Rectlr, SpareState
    from repro.exec import MeshExecutor
    tel = Telemetry(trace=False)
    ex = MeshExecutor(cfg, n_groups=4, redundancy=2, model_degree=2,
                      seq=32, per_type_batch=2, total_steps=50,
                      telemetry=tel)
    masked = SpareState(4, 2)
    Rectlr().on_failures(masked, [0])
    healthy = SpareState(4, 2)
    healthy.s_a = masked.s_a          # same depth => same batch shapes

    readings = {}
    for label, st in (("masked", masked), ("healthy", healthy)):
        ex.state = st
        ex._wire_info.clear()         # force fresh HLO accounting
        ex.run(1)
        snap = tel.snapshot()["gauges"]
        readings[label] = (snap["sync.wire_bytes_per_step"],
                           snap["sync.collectives_per_step"])
    assert readings["masked"] == readings["healthy"]
    assert readings["healthy"][0] > 0 and readings["healthy"][1] > 0
    assert tel.snapshot()["counters"]["sync.wire_bytes_total"] == \
        readings["healthy"][0] * 2
