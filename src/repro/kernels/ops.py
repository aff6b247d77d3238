"""Jitted public wrappers around the Pallas kernels.

The platform selects the path, never a flag: :func:`on_tpu` is true
when JAX's default backend is the TPU, and then each wrapper compiles
its kernel with Mosaic; elsewhere ``interpret`` defaults to True and the
same kernel body runs in Python (the CPU correctness path).

Of these wrappers only :func:`int8_ef_quantize` has a caller on a
production path: :func:`repro.dist.collectives.compress_grad_int8` picks
it when ``on_tpu()`` holds (the ``grad_compress="int8_ef"`` mesh sync)
and its jnp oracle otherwise. The model's GQA attention runs a kernel
too, but not one of these: where ``on_tpu()`` holds and the shapes fit,
:func:`repro.models.attention.gqa_forward` calls splash attention's
causal MQA kernel (forward and backward, shipped with jax) through
:func:`repro.models.attention.attend_flash`. The forward-only
:func:`flash_attention`, the SSD scan and rmsnorm have no model caller;
those layers run their pure-jnp spellings.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_pallas
from .int8_ef import int8_ef_pallas
from .rmsnorm import rmsnorm_pallas
from .ssd_scan import ssd_scan_pallas

__all__ = ["flash_attention", "ssd_scan", "rmsnorm", "int8_ef_quantize",
           "on_tpu"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 256,
                    block_k: int = 256,
                    interpret: bool | None = None) -> jax.Array:
    """Causal GQA flash attention. q: (B,H,S,D); k/v: (B,KV,S,D)."""
    interp = (not on_tpu()) if interpret is None else interpret
    return flash_attention_pallas(q, k, v, causal=causal, block_q=block_q,
                                  block_k=block_k, interpret=interp)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, a_log: jax.Array, b: jax.Array,
             c: jax.Array, *, chunk: int = 128,
             interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """SSD chunk scan. x: (B,H,S,P); dt: (B,H,S); a_log: (H,);
    b/c: (B,G,S,N). Returns (y, final_state)."""
    interp = (not on_tpu()) if interpret is None else interpret
    a = -jnp.exp(a_log.astype(jnp.float32))
    return ssd_scan_pallas(x, dt[..., None], a, b, c, chunk=chunk,
                           interpret=interp)


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


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-5,
            block_rows: int = 256,
            interpret: bool | None = None) -> jax.Array:
    interp = (not on_tpu()) if interpret is None else interpret
    return rmsnorm_pallas(x, w, eps=eps, block_rows=block_rows,
                          interpret=interp)
