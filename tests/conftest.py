"""Shared pytest plumbing.

Registers two environment markers and auto-skips them when their
backend is absent — known environment gaps, not regressions:

* ``tpu`` — lowered-HLO comparisons that hold only as the real TPU
  toolchain lowers the program. Run them on a TPU VM with
  ``pytest -m tpu`` (they un-skip once ``jax.devices("tpu")``
  resolves).
* ``spmd`` — the ``repro.exec`` mesh tests need >= 8 devices. On any
  CPU host, fan the host platform out before the first jax import::

      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
          PYTHONPATH=src python -m pytest -m spmd

  (CI runs these in a dedicated job; the plain tier-1 invocation sees
  one device and skips them.)
"""
import functools

import pytest

SPMD_MIN_DEVICES = 8


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tpu: needs the Pallas TPU toolchain (Mosaic); auto-skipped when "
        "no TPU backend is present")
    config.addinivalue_line(
        "markers",
        f"spmd: needs >= {SPMD_MIN_DEVICES} devices (XLA_FLAGS="
        f"--xla_force_host_platform_device_count={SPMD_MIN_DEVICES}); "
        "auto-skipped otherwise")


@functools.lru_cache(maxsize=1)
def _tpu_available() -> bool:
    try:
        import jax
        return len(jax.devices("tpu")) > 0
    except Exception:
        return False


@functools.lru_cache(maxsize=1)
def _spmd_available() -> bool:
    try:
        import jax
        return jax.device_count() >= SPMD_MIN_DEVICES
    except Exception:
        return False


def pytest_collection_modifyitems(config, items):
    marked = {m for item in items for m in ("tpu", "spmd")
              if m in item.keywords}
    skips = {}
    if "tpu" in marked and not _tpu_available():
        skips["tpu"] = pytest.mark.skip(
            reason="no TPU backend; Pallas TPU kernels/HLO cannot run here")
    if "spmd" in marked and not _spmd_available():
        skips["spmd"] = pytest.mark.skip(
            reason=f"needs >= {SPMD_MIN_DEVICES} devices; set XLA_FLAGS="
                   f"--xla_force_host_platform_device_count="
                   f"{SPMD_MIN_DEVICES}")
    if not skips:
        return
    for item in items:
        for mark, skip in skips.items():
            if mark in item.keywords:
                item.add_marker(skip)
