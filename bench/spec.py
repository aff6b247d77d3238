"""Find the benchmark's parts by name.

A cell (``workloads/<name>.json``) names its model configuration
(``configs/<config>.json``), its traffic mix (``traffic/<traffic>.json``),
the chips it needs and the driver that runs it. A configuration names its
``model_type``; ``models/<model_type>.py`` holds the plain reference and
the parameter layout, ``adapters/<model_type>.py`` maps the configuration
onto the program's own config class. A traffic mix names its
``generator`` in ``traffic/<generator>.py``. Later cells, configurations,
mixes and metrics are new files; none of these loaders changes.
"""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")

__all__ = ["BENCH", "NAME", "load", "module", "cell", "metric_modules"]


def _path(kind: str, name: str, suffix: str) -> Path:
    if not NAME.match(name):
        raise LookupError(f"bad {kind} name {name!r}")
    path = BENCH / kind / f"{name}{suffix}"
    if not path.is_file():
        raise LookupError(f"no {kind} named {name!r} ({path})")
    return path


def load(kind: str, name: str) -> dict:
    """The JSON object ``<kind>/<name>.json``."""
    with open(_path(kind, name, ".json")) as f:
        return json.load(f)


def module(kind: str, name: str):
    """Import ``<kind>/<name>.py`` (names may hold dots)."""
    path = _path(kind, name, ".py")
    key = f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


def cell(name: str) -> dict:
    """The cell ``name`` with its configuration and traffic mix resolved
    under ``config_spec`` and ``traffic_spec``."""
    w = dict(load("workloads", name), name=name)
    w["config_spec"] = load("configs", w["config"])
    w["traffic_spec"] = load("traffic", w["traffic"])
    return w


def metric_modules() -> dict:
    """Every metric reader in ``metrics/``, by metric name."""
    return {p.stem: module("metrics", p.stem)
            for p in sorted((BENCH / "metrics").glob("*.py"))}
