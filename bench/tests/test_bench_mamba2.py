"""The Mamba-2 training cell against its plain reference, on the CPU.

``mamba2-1.3b-L12.train.masked`` cut to CPU size runs through the
program's trainer (the masked stacked step over SSD layers) and is
compared with ``bench/models/mamba2.py`` on seeded weights, under the
cell's own driver (``train_vectors``): a sound run is ``correct``, and a
step that returns its state unchanged or leaves half of the batch out
is not.
"""
import copy

import numpy as np
import pytest

from bench_tiny import TINY, TINY_LIMITS, run_tiny
from test_bench_faults import _half_batch, _unchanged_state

from bench import spec

CELL = "mamba2-1.3b-L12.train.masked"

# grad_vec_gap at this size: sound runs read at most 3.7e-2, half a batch
# at least 0.38 (CPU, three seeds)
TINY_VEC_LIMIT = 0.1


def _tiny_cell() -> dict:
    cell = copy.deepcopy(spec.cell(CELL))
    cell["config_spec"].update(TINY["mamba2"])
    cell["traffic_spec"]["seq"] = 64
    cell["limits"] = dict(TINY_LIMITS["train"], grad_vec_gap=TINY_VEC_LIMIT)
    return cell


@pytest.mark.parametrize("fault", [None, _unchanged_state, _half_batch],
                         ids=["sound", "unchanged_state", "half_batch"])
def test_mamba2_train_fault_is_not_correct(fault, monkeypatch):
    if fault is not None:
        fault(monkeypatch)
    result = run_tiny(CELL, cell=_tiny_cell())
    assert result["correct"] is (fault is None), result["checks"]
    assert "grad_vec_gap" in result["checks"]


def test_vector_gap_sees_what_norms_do_not():
    """A gradient turned within a leaf keeps every norm that the
    ``train`` driver compares, and reads its full size here."""
    from bench.drivers.train_vectors import vector_gap
    rng = np.random.default_rng(3)
    ref = [rng.normal(size=(64, 8)).astype(np.float32),
           rng.normal(size=(16,)).astype(np.float32)]
    turned = [ref[0][::-1].copy(), ref[1].copy()]
    assert vector_gap(ref, ref, ["w", "b"]) == (0.0, "w")
    gap, leaf = vector_gap(turned, ref, ["w", "b"])
    assert leaf == "w" and gap > 0.5
    assert np.isclose(np.linalg.norm(turned[0]), np.linalg.norm(ref[0]))
