"""Cells cut to a size a CPU test run holds: the same files, with every
width and length shrunk."""
import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import spec  # noqa: E402

TINY = {
    "qwen2": dict(hidden_size=64, intermediate_size=128,
                  num_attention_heads=4, num_key_value_heads=2,
                  num_hidden_layers=2, vocab_size=1000),
    "mamba2": dict(d_model=64, n_layer=2, vocab_size=1000, d_state=16,
                   headdim=16, chunk_size=32),
}


# limits for the cut cells, from CPU readings at this size (sound runs
# read at most 4e-4, 3.3e-3, 1.5e-2 and 5e-3): at a few hundred
# parameters a leaf, rounding moves the norms more than at full width
TINY_LIMITS = {"train": {"loss_gap": 0.002, "grad_gap": 0.01,
                         "change_gap": 0.05},
               "serve": {"served_gap": 0.03}}


def tiny_cell(name: str) -> dict:
    cell = copy.deepcopy(spec.cell(name))
    c = cell["config_spec"]
    c.update(TINY[c["model_type"]])
    mix = cell["traffic_spec"]
    if cell["driver"] == "train":
        mix["seq"] = 64
    else:
        mix.update(buckets=[16, 32, 64], bucket_weights=[0.5, 0.3, 0.2],
                   out_median=8, out_sigma=0.7, out_min=4, out_max=16,
                   rate_per_s=20.0)
        cell["engine"].update(slots=4, page_size=4, max_new=16)
    cell["limits"] = dict(TINY_LIMITS[cell["driver"]])
    return cell


def tiny_mesh_cell() -> dict:
    """The training cell as four data-parallel groups, one a device,
    through ``MeshExecutor`` (the four-chip path, on virtual devices)."""
    cell = tiny_cell("qwen2.5-3b-L4.train.masked")
    cell.update(chips=4, executor={"model_degree": 1, "sync": "shard_map"})
    cell["traffic_spec"]["per_type_batch"] = 4
    return cell


def run_tiny(name: str, seed: int = 2**31 + 5, seconds: float = 0.5,
             cell: dict | None = None):
    import time

    from bench import harness
    return harness.run(name, seed, seconds, False, time.perf_counter(),
                       require_chip=False,
                       cell=tiny_cell(name) if cell is None else cell)
