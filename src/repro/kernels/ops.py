"""Jitted public wrapper around the Pallas kernel, and the platform test.

The platform selects the path, never a flag: :func:`on_tpu` is true
when JAX's default backend is the TPU, and then the wrapper compiles its
kernel with Mosaic; elsewhere ``interpret`` defaults to True and the
same kernel body runs in Python (the CPU correctness path).

:func:`int8_ef_quantize` has one caller on a production path:
:func:`repro.dist.collectives.compress_grad_int8` picks it when
``on_tpu()`` holds (the ``grad_compress="int8_ef"`` mesh sync) and its
jnp oracle otherwise. The model's GQA attention runs a kernel too, but
not one of this package: where ``on_tpu()`` holds and the shapes fit,
:func:`repro.models.attention.gqa_forward` calls splash attention's
causal MQA kernel (forward and backward, shipped with jax) through
:func:`repro.models.attention.attend_flash`.
"""
from __future__ import annotations

from functools import partial

import jax

from .int8_ef import int8_ef_pallas

__all__ = ["int8_ef_quantize", "on_tpu"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("block_rows", "interpret"))
def int8_ef_quantize(grad: jax.Array, error: jax.Array, *,
                     block_rows: int = 256,
                     interpret: bool | None = None
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused int8 EF quantize-accumulate (the compressed-reduce hot path).
    Returns ``(q int8, scale f32 scalar, new_error f32)``."""
    interp = (not on_tpu()) if interpret is None else interpret
    return int8_ef_pallas(grad, error, block_rows=block_rows,
                          interpret=interp)
