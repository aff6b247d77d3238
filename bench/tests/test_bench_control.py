"""The lower-precision control, the plain reference in float8 put in the
program's place, is not correct by the cell's comparison (here at a size
a CPU test run holds; on the chip at the cell's size, PERF.md)."""
import numpy as np

from bench_tiny import tiny_cell

SEED = 2**31 + 11


def test_train_control_is_not_correct():
    from bench.correct import judge, train_numbers
    from bench.reftrain import reference_steps
    cell = tiny_cell("qwen2.5-3b-L4.train.masked")
    ref = reference_steps(cell, SEED)
    numbers, _ = train_numbers(reference_steps(cell, SEED, mm="f8"), ref)
    assert judge(numbers, cell["limits"])[0] is False, numbers
    same, _ = train_numbers(ref, ref)
    assert judge(same, cell["limits"])[0] is True


def test_serve_control_is_not_correct():
    from bench.drivers.serve import reference_gaps
    cell = tiny_cell("qwen2.5-3b.serve.chat")
    rng = np.random.default_rng(0)
    items = [(rng.integers(0, 1000, n, dtype=np.int32),
              rng.integers(0, 1000, 16, dtype=np.int32))
             for n in (16, 32, 64, 64)]
    gaps = reference_gaps(cell, SEED, items, judge_mm="f8")
    assert max(gaps) > cell["limits"]["served_gap"], gaps
