"""Every part of the benchmark is found by name, and every name and unit
keeps to the allowed characters."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
METRICS = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_and_matches_benchmark(name):
    cell = spec.cell(name)
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == name)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        entry["config"], entry["traffic"], entry["chips"])
    spec.module("drivers", cell["driver"])
    spec.module("traffic", cell["traffic_spec"]["generator"])
    assert set(cell["limits"]) and all(v > 0 for v in
                                       cell["limits"].values())


@pytest.mark.parametrize("config", [c["name"] for c in
                                    BENCHMARK["configs"]])
def test_config_loads_with_reference(config):
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == config)
    c = spec.load("configs", config)
    assert entry["file"] == f"bench/configs/{config}.json"
    assert c["reduced"] == entry["reduced"] and c["source"] == \
        entry["source"]
    assert all(k in c for k in c["reduced"])
    spec.module("models", c["model_type"]).layout(c)
    spec.module("adapters", c["model_type"])


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_reader_matches_benchmark(metric):
    entry = next(m for m in METRICS if m["name"] == metric)
    mod = spec.metric_modules()[metric]
    kind = "end_to_end" if entry in BENCHMARK["end_to_end"] else "per_layer"
    assert (mod.KIND, mod.UNIT) == (kind, entry["unit"])
    for cell in entry.get("workloads", []):
        assert cell in CELLS


def test_names_and_units_allowed():
    names = CELLS + [m["name"] for m in METRICS] + [
        c["name"] for c in BENCHMARK["configs"]]
    names += [w[k] for w in BENCHMARK["workloads"]
              for k in ("config", "traffic")]
    names += [k for c in BENCHMARK["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(CELLS)) == len(CELLS)
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    assert all(UNIT.match(m["unit"]) for m in METRICS)
    assert all(m["better"] in ("lower", "higher") for m in METRICS)
    readers = spec.metric_modules()
    assert {m["name"] for m in METRICS} <= set(readers)
    assert all(r.KIND in ("end_to_end", "per_layer") and UNIT.match(r.UNIT)
               for r in readers.values())


def test_unknown_names_are_refused():
    with pytest.raises(LookupError):
        spec.cell("no.such.cell")
    with pytest.raises(LookupError):
        spec.load("configs", "../BENCHMARK")


def test_peak_table_refuses_unknown_device():
    from bench.peaks import peak
    assert peak("TPU v5 lite")["bf16_flops"] == 197e12
    assert peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peak("cpu")
