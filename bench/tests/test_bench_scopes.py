"""The split of a profiler trace by layer and by host phase
(``bench/scopes.py``).

``data/scoped.xplane.pb`` was recorded on one TPU v5e chip: the gradient
of a four-layer ``lax.scan`` whose layer holds an ``attention`` scope
(``tanh(x @ w0)``) and an ``mlp`` scope (``x + relu(h @ w1)``), 1024 x
1024 bf16, compiled once (its text is ``data/scoped.hlo.txt``) and run
four times inside a host span ``window``. Each run is a ``train_step``
span holding ``batch`` (a 2 ms sleep and the input's copy to the
device), ``dispatch`` and ``loss_read`` (one element read back); a 1 ms
sleep follows each ``train_step`` outside every span. The expected
numbers below are read off the trace's own events.
"""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench import scopes, trace  # noqa: E402

DATA = HERE / "data"


@pytest.mark.parametrize("spans,expected", [
    # nested spans: each instant goes to the shortest cover
    ([("train_step", 1.0, 10.0), ("batch", 1.0, 3.0),
      ("dispatch", 3.0, 4.0)],
     {"train_step": 4.5, "batch": 2.0, "dispatch": 1.0,
      "host_other": 0.5}),
    # no span at all
    ([], {"host_other": 8.0}),
    # a span that covers the gaps only in part
    ([("loss_read", 7.5, 12.0)], {"host_other": 6.0, "loss_read": 2.0}),
], ids=["nested", "none", "partial"])
def test_split_idle_gives_each_instant_to_its_innermost_span(spans,
                                                             expected):
    gaps = [(0.5, 4.0), (5.0, 9.5)]       # 8 s idle in all
    got = scopes.split_idle(gaps, spans)
    assert got == pytest.approx(expected)
    assert sum(got.values()) == pytest.approx(8.0)


def test_scopes_need_the_programs_text():
    """Without program text (or against a program that has no
    ``repro.obs.hlo_scopes``) all device time is ``other`` and the labels
    are ``bench.trace``'s."""
    t = trace.reduce([DATA / "small.xplane.pb"], 1)
    s = scopes.reduce([DATA / "small.xplane.pb"], 1, None)
    assert set(s["scopes"]) == {"other"}
    assert s["scopes"]["other"] == pytest.approx(
        sum(v for _, v in t["device_ops"]), abs=1e-12)
    assert dict(s["device_ops"]) == pytest.approx(dict(t["device_ops"]))
    gaps = dict(s["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        t["window_s"] - t["busy_s"], abs=1e-9)
    # the exact split gives the engine.step span the gaps inside it,
    # which the midpoint rule handed to its neighbours
    assert gaps["engine.step"] > 0
    assert "engine.step" not in dict(t["idle_gaps"])


def _scoped(programs=True):
    text = (DATA / "scoped.hlo.txt").read_text()
    progs = {"jit_toy_step": text} if programs else None
    return (trace.reduce([DATA / "scoped.xplane.pb"], 1),
            scopes.reduce([DATA / "scoped.xplane.pb"], 1, progs))


def test_recorded_scoped_trace_by_layer():
    t, s = _scoped()
    # the four runs of jit_toy_step hold the layers' operations; the
    # input's conversion and the read-back's slice are programs of their
    # own, with no text given, so they count as other
    assert {k.split("(")[0] for k in t["modules"]} == {
        "jit_toy_step", "jit_convert_element_type", "jit_dynamic_slice",
        "jit_squeeze"}
    assert s["scopes"] == pytest.approx(
        {"attention": 710_182e-9, "mlp": 604_686e-9, "other": 376_063e-9},
        abs=1e-9)
    assert sum(s["scopes"].values()) == pytest.approx(t["busy_s"],
                                                      abs=1e-9)
    step_s = t["modules"][next(k for k in t["modules"]
                               if k.startswith("jit_toy_step("))]["seconds"]
    assert s["scopes"]["attention"] + s["scopes"]["mlp"] > 0.8 * step_s
    ops = dict(s["device_ops"])
    assert ops["attention/fusion:kOutput"] == pytest.approx(378_444e-9,
                                                            abs=1e-9)
    assert "mlp/fusion:kOutput" in ops and "copy-done" in ops
    assert sum(ops.values()) == pytest.approx(t["busy_s"], abs=1e-9)


def test_recorded_scoped_trace_idle_by_phase():
    t, s = _scoped()
    gaps = dict(s["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(t["window_s"] - t["busy_s"],
                                               abs=1e-9)
    # the midpoint rule gave every gap to train_step or host_other; the
    # exact split finds the 2 ms sleeps in batch, the 1 ms sleeps outside
    # every span, and the first read-back (which compiled a slice) in
    # loss_read
    assert set(dict(t["idle_gaps"])) == {"train_step", "host_other"}
    assert gaps["loss_read"] > 0.1
    assert gaps["batch"] > 4 * 2e-3
    assert gaps["host_other"] > 4 * 1e-3
    assert 0 < gaps["dispatch"] < gaps["batch"]


def test_recorded_scoped_trace_on_a_program_without_scopes(monkeypatch):
    """Against a program that has no ``repro.obs.hlo_scopes`` (the
    parent commit's), the program text is ignored."""
    import repro.obs
    monkeypatch.delattr(repro.obs, "hlo_scopes")
    _, s = _scoped()
    _, without = _scoped(programs=False)
    assert s["scopes"] == without["scopes"]
    assert set(s["scopes"]) == {"other"}
    assert s["idle_gaps"] == without["idle_gaps"]
