"""Production + emulated mesh builders.

Defined as FUNCTIONS (never module-level constants) so importing this
module touches no jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then calls these.

Production target: TPU v5e pods.
  single-pod : (16, 16)    = 256 chips, axes (data, model)
  multi-pod  : (2, 16, 16) = 512 chips, axes (pod, data, model)

The SPARe data-parallel groups are the ``pod x data`` slices (N = 32 DP
groups of M = 16 model-sharded chips on the multi-pod mesh); the ``pod``
axis crosses the DCI boundary, which is exactly the axis the SPARe
failure-masking weights neutralize when a whole slice drops out.

:func:`make_emulated_mesh` builds the same ``(data, model)`` topology
from however many devices the host platform exposes — the
``repro.exec`` SPMD tests and benchmarks run the real sharded step on
any machine via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
from __future__ import annotations

import numpy as np

import jax

__all__ = ["make_production_mesh", "make_emulated_mesh", "dp_axes",
           "dp_degree"]


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_emulated_mesh(data_degree: int,
                       model_degree: int = 1) -> jax.sharding.Mesh:
    """``(data, model)`` mesh over the first ``data*model`` devices of
    the default backend — real chips on an accelerator host.

    On a CPU-only machine, export ``JAX_PLATFORMS=cpu`` and
    ``XLA_FLAGS=--xla_force_host_platform_device_count=<n>`` *before the
    first jax import* to fan one host out into ``n`` emulated devices —
    the same SPMD partitioner, collectives, and HLO the production mesh
    sees, at laptop scale.
    """
    need = data_degree * model_degree
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"mesh ({data_degree}, {model_degree}) needs {need} devices "
            f"but only {have} {jax.default_backend()} device(s) are "
            f"visible; on a CPU-only machine set JAX_PLATFORMS=cpu and "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            f"before the first jax import (see README §repro.exec)")
    devices = np.asarray(jax.devices()[:need]).reshape(
        data_degree, model_degree)
    return jax.sharding.Mesh(devices, ("data", "model"))


def dp_axes(multi_pod: bool) -> tuple[str, ...]:
    return ("pod", "data") if multi_pod else ("data",)


def dp_degree(mesh: jax.sharding.Mesh, multi_pod: bool) -> int:
    n = 1
    for a in dp_axes(multi_pod):
        n *= mesh.shape[a]
    return n
