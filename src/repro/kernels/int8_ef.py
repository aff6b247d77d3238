"""Fused int8 error-feedback quantize-accumulate as a Pallas TPU kernel.

The compressed all-reduce path (paper Table 1: the 20 TB gradient sync)
sends int8 + one fp32 scale per tensor. The XLA spelling of
:func:`repro.dist.collectives.compress_grad_int8` is a chain of
elementwise ops that reads the gradient from HBM three times (EF
accumulate, quantize, residual); this kernel fuses the whole pipeline so
each element is read once per pass:

* pass 1 (``absmax``): one VMEM read of ``grad`` and ``error`` per tile,
  folded into one resident ``(8, 128)`` running elementwise
  ``max |grad + error|`` (a full native f32 tile, the smallest output
  block Mosaic accepts); the final 1024-way ``max`` is one XLA reduce;
* pass 2 (``quantize``): re-reads the tile once and writes *both* the
  int8 payload and the fp32 residual — the EF accumulate, the rounding,
  and the residual subtraction never leave VMEM.

All arithmetic is fp32 exactly like the reference: the int8 payload and
the scale are bit-identical to :func:`repro.kernels.ref.int8_ef_ref`.
The residual is exact up to ONE fp32 ulp of the dequantized value —
compilers (XLA:CPU's LLVM backend, and potentially Mosaic) may contract
``x - q*scale`` into an FMA, which keeps the product at higher
intermediate precision; the same contraction affects the *jitted*
unfused path, so the two fused/unfused spellings agree to the same
bound (property-tested in interpret mode). The slack is absorbed by the
next step's error feedback and is ~1e5x below the scale/2 quantization
error it rides with.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["int8_ef_absmax_kernel", "int8_ef_quantize_kernel",
           "int8_ef_pallas"]

_INT8_MAX = 127.0
_LANES = 128
_SUBLANES = 8


def int8_ef_absmax_kernel(x_ref, e_ref, o_ref):
    x = x_ref[...].astype(jnp.float32) + e_ref[...].astype(jnp.float32)
    # fold the tile's rows onto one (8, 128) vreg-shaped partial: max is
    # exact and order-free, so the result is bit-identical to one global
    # max over the whole tensor
    part = jnp.max(jnp.abs(x).reshape(-1, _SUBLANES, _LANES), axis=0)

    @pl.when(pl.program_id(0) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(part)     # |x| >= 0: the max identity

    o_ref[...] = jnp.maximum(o_ref[...], part)


def int8_ef_quantize_kernel(x_ref, e_ref, scale_ref, q_ref, err_ref):
    x = x_ref[...].astype(jnp.float32) + e_ref[...].astype(jnp.float32)
    scale = scale_ref[0, 0]
    # all-zero tensors keep scale 0 (q == 0, decompress == 0) but must
    # not divide by it — mirror the reference exactly
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / safe), -_INT8_MAX, _INT8_MAX)
    q_ref[...] = q.astype(jnp.int8)
    err_ref[...] = x - q * scale


def int8_ef_pallas(grad: jax.Array, error: jax.Array, *,
                   block_rows: int = 256, interpret: bool = False
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused EF quantization of one tensor (any shape/float dtype).

    Returns ``(q int8 [grad.shape], scale f32 scalar, new_error f32
    [grad.shape])`` with numerics identical to
    :func:`repro.dist.collectives.compress_grad_int8`.
    """
    shape = grad.shape
    n = grad.size
    x = grad.reshape(-1)
    e = error.reshape(-1)
    # tile to (rows, 128) lanes; int8 min tile is (32, 128)
    block = block_rows * _LANES
    pad = (-n) % block
    if pad:
        x = jnp.pad(x, (0, pad))
        e = jnp.pad(e, (0, pad))
    rows = x.size // _LANES
    x2 = x.reshape(rows, _LANES)
    e2 = e.reshape(rows, _LANES)
    n_blocks = rows // block_rows

    tile = pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))
    block_max = pl.pallas_call(
        int8_ef_absmax_kernel,
        grid=(n_blocks,),
        in_specs=[tile, tile],
        # one resident accumulator tile across the (sequential) grid
        out_specs=pl.BlockSpec((_SUBLANES, _LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((_SUBLANES, _LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x2, e2)
    scale = jnp.max(block_max) / _INT8_MAX

    q2, err2 = pl.pallas_call(
        int8_ef_quantize_kernel,
        grid=(n_blocks,),
        in_specs=[
            tile, tile,
            pl.BlockSpec((1, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=(tile, tile),
        out_shape=(jax.ShapeDtypeStruct(x2.shape, jnp.int8),
                   jax.ShapeDtypeStruct(x2.shape, jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2, e2, scale.reshape(1, 1))

    q = q2.reshape(-1)[:n].reshape(shape)
    err = err2.reshape(-1)[:n].reshape(shape)
    return q, scale, err
