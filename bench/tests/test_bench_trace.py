"""The reduction from a profiler trace to busy time, idle gaps, program
runs, operation self time and exposed collectives.

``data/small.xplane.pb`` was recorded on one TPU v5e chip: inside a host
span ``window``, four rounds of a 2048x2048 bf16 matmul program under a
``train_step`` span, a 2 ms sleep, an elementwise program under an
``engine.step`` span and a ``wait_arrival`` span that sleeps 1 ms. The
expected numbers below are read off the trace's own events.
"""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench.trace import label, reduce, self_times, subtract, union  # noqa: E402


def test_interval_arithmetic():
    assert union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    # collective [0, 10) with compute over [2, 3) and [5, 8): 6 exposed
    assert subtract([(0, 10)], [(2, 3), (5, 8)]) == pytest.approx(6.0)
    assert subtract([(0, 1), (4, 6)], [(0, 5)]) == pytest.approx(1.0)


def test_self_time_of_nested_operations():
    ops = [("while", 0.0, 10.0), ("a", 1.0, 3.0), ("b", 4.0, 9.0),
           ("c", 5.0, 6.0), ("d", 11.0, 12.0)]
    own = {n: t for n, _, _, t in self_times(ops)}
    assert own == pytest.approx({"while": 3.0, "a": 2.0, "b": 4.0,
                                 "c": 1.0, "d": 1.0})


def test_operation_labels():
    assert label("%fusion.12 = bf16[] fusion(x), kind=kOutput, calls=%f") \
        == "fusion:kOutput"
    assert label("%while.265 = (s32[]) while(%t), body=%b") == "while"
    assert label("%copy-done = bf16[2] copy-done(%copy-start)") == \
        "copy-done"


def test_recorded_chip_trace():
    t = reduce([HERE / "data" / "small.xplane.pb"], 1)
    assert t["window_s"] == pytest.approx(22_109_818e-9, abs=1e-9)
    # device 0's clock trails the host's: the first dispatch leads its
    # run by 1,037,787 ns, the largest of the eight leads
    assert t["skew_s"] == pytest.approx(1_037_787e-9, abs=1e-9)
    runs = {k.split("(")[0] + str(v["runs"]) for k, v in
            t["modules"].items()}
    assert runs == {"jit__lambda4"}
    # busy: the eight program runs, 4 x ~101.8 us and 4 x ~13.2 us
    modules = (101_721 + 101_823 + 101_863 + 101_804
               + 13_330 + 13_303 + 13_182 + 13_158) * 1e-9
    assert t["busy_s"] == pytest.approx(modules, abs=1e-7)
    ops = dict(t["device_ops"])
    assert ops["fusion:kOutput"] == pytest.approx(4 * 90.175e-6, rel=1e-3)
    gaps = dict(t["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        t["window_s"] - t["busy_s"], abs=1e-9)
    # the 2 ms sleeps lie outside every harness span, the gaps after the
    # elementwise program inside the wait_arrival spans
    assert gaps["host_other"] > gaps["wait_arrival"] > 4e-3
    assert t["exposed_collective_s"] == 0.0
