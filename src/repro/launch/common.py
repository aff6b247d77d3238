"""Pieces every launcher shares: the model-size switches and the
persistent compile cache.

Model size: ``--smoke`` (the default) runs the reduced same-family
config of :func:`repro.configs.smoke_config`; ``--no-smoke`` runs the
published config of :func:`repro.configs.get_config` at every published
width. ``--layers K`` cuts only the depth (``cfg.scaled(n_layers=K)``),
the one cut that fits a full-width model onto one chip.

Compile cache: JAX keeps compiled programs where
``JAX_COMPILATION_CACHE_DIR`` points, when it is set, and this module
then sets no other directory. Otherwise the cache lives at one fixed
path inside the checkout, ``<repo>/.jax_cache`` (listed in
``.gitignore``): the directory is part of the cache key, so a name that
moved between runs would never hit. Only launchers turn the cache on;
importing this module does not.
"""
from __future__ import annotations

import argparse
import os
from pathlib import Path

__all__ = ["add_model_args", "resolve_config", "compile_cache_dir",
           "enable_compile_cache"]

_REPO_ROOT = Path(__file__).resolve().parents[3]


def add_model_args(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced same-family config (default); "
                         "--no-smoke runs the published widths")
    ap.add_argument("--layers", type=int, default=0, metavar="K",
                    help="cut the depth to K layers (0 = the config's "
                         "own depth); widths are never cut")


def resolve_config(args):
    """The :class:`~repro.models.config.ModelConfig` the switches of
    :func:`add_model_args` select."""
    from repro.configs import get_config, smoke_config
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    return cfg.scaled(n_layers=args.layers) if args.layers else cfg


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<repo>/.jax_cache``."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(_REPO_ROOT / ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn JAX's persistent compile cache on at
    :func:`compile_cache_dir`; returns the directory. Call before the
    first compile."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
