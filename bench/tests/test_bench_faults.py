"""The comparison that decides ``correct`` fails a broken timed path.

Each test skips the harness's look for a chip and drives a whole run of
a cell cut to CPU size, once sound and once with a fault planted under
the timed path: a step that returns its state unchanged, half of the
batch left out (the mean taken over the rest), a served token altered
where it is produced.
"""
import pytest

from bench_tiny import run_tiny

TRAIN = ["qwen2.5-3b-L4.train.masked"]


def _unchanged_state(monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.train.trainer import SpareTrainer
    step = SpareTrainer._dispatch

    def dispatch(self, report):
        keep = jax.tree.map(jnp.copy, (self.params, self.opt_state))
        _, _, metrics = step(self, report)
        return keep[0], keep[1], metrics
    monkeypatch.setattr(SpareTrainer, "_dispatch", dispatch)


def _half_batch(monkeypatch):
    import repro.train.trainer as trainer
    batch_of = trainer.spare_batch

    def half(pipeline, state, step):
        batch = batch_of(pipeline, state, step)
        w = batch["weights"].copy()
        rows = w.shape[1] // 2
        w[:, :rows] *= 2.0
        w[:, rows:] = 0.0
        return dict(batch, weights=w)
    monkeypatch.setattr(trainer, "spare_batch", half)


@pytest.mark.parametrize("cell", TRAIN)
@pytest.mark.parametrize("fault", [None, _unchanged_state, _half_batch])
def test_train_fault_is_not_correct(cell, fault, monkeypatch):
    if fault is not None:
        fault(monkeypatch)
    result = run_tiny(cell)
    assert result["correct"] is (fault is None), result["checks"]


@pytest.mark.parametrize("alter", [False, True])
def test_serve_altered_token_is_not_correct(alter, monkeypatch):
    from repro.serve import ServeEngine
    step = ServeEngine.step

    def altered(self):
        done = step(self)
        for i, slot in enumerate(self.slots):
            if slot is not None and len(slot.generated) == 2:
                slot.generated[-1] = (slot.generated[-1] + 1) % 1000
                self.next_tok[i] = slot.generated[-1]
        return done
    if alter:
        monkeypatch.setattr(ServeEngine, "step", altered)
    result = run_tiny("qwen2.5-3b.serve.chat", seconds=1.5)
    assert result["correct"] is not alter, result["checks"]


_DP4 = """
import json, sys
sys.path[:0] = {paths!r}
from bench_tiny import run_tiny, tiny_mesh_cell
if sys.argv[1] == "fault":
    from repro.dist.collectives import BucketedAllReduce
    BucketedAllReduce.__call__ = lambda self, grads: grads
cell = tiny_mesh_cell()
print(json.dumps(run_tiny(cell["name"], cell=cell)["correct"]))
"""


@pytest.mark.parametrize("fault", ["sound", "fault"])
def test_dp4_exchange_left_out_is_not_correct(fault):
    """Four data-parallel groups on four virtual CPU devices (the path a
    four-chip cell would take); the fault drops the gradient exchange
    between them."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    here = Path(__file__).resolve().parent
    paths = [str(here.parents[1]), str(here.parents[1] / "src"), str(here)]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", _DP4.format(paths=paths), fault], env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.splitlines()[-1]) is (fault == "sound")
