"""Launcher plumbing: model-size switches, the compile-cache directory,
and ``chip_smoke.py``'s refusal to run without a TPU."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_config, smoke_config
from repro.launch import serve as serve_launch
from repro.launch import train as train_launch
from repro.launch.common import (compile_cache_dir, enable_compile_cache,
                                 resolve_config)

REPO = Path(__file__).resolve().parents[1]
LAUNCHERS = {"train": train_launch, "serve": serve_launch}


@pytest.mark.parametrize("launcher", sorted(LAUNCHERS))
def test_no_smoke_reaches_published_config(launcher):
    parse = LAUNCHERS[launcher].build_parser().parse_args
    full = resolve_config(parse(["--no-smoke"]))
    assert full == get_config("qwen2.5-3b")
    cut = resolve_config(parse(["--no-smoke", "--layers", "2"]))
    assert cut == get_config("qwen2.5-3b").scaled(n_layers=2)
    assert resolve_config(parse([])) == smoke_config("qwen2.5-3b")


def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache_dir() == str(tmp_path)
    # with the variable set, JAX reads it itself: no other dir in code
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_and_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first, second = compile_cache_dir(), compile_cache_dir()
    assert first == second
    assert Path(first).resolve().is_relative_to(REPO)
    assert Path(first).name == ".jax_cache"


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_tpu(where, tmp_path):
    """Under JAX_PLATFORMS=cpu the script exits non-zero at its device
    check, before building a model, and prints no result line."""
    script = REPO / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          cwd=script.parent, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout
    assert "[train]" not in proc.stdout
