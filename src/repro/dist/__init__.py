"""Distributed-communication substrate for the SPARe reproduction.

``repro.dist`` hosts the collective-communication helpers that sit between
the SPARe control plane (host-side schedules, supplier weights) and the
device-side SPMD program:

* :func:`repro.dist.collectives.weighted_all_reduce` — the supplier-
  weighted reduction of §3.1 (``ḡ = Σ_i w_i g_i``); inside a mapped
  computation it lowers to a single ``psum`` over the data axis, on the
  host it is the exact emulation the trainer and tests use.
* :func:`repro.dist.collectives.compress_grad_int8` /
  :func:`repro.dist.collectives.decompress_grad_int8` — int8
  error-feedback gradient quantization (beyond-paper): 4x less all-reduce
  traffic, with the residual carried forward so the long-run transmitted
  signal is unbiased.
"""
from .collectives import (
    BucketedAllReduce,
    BucketLayout,
    CompressedBucketSync,
    all_reduce_grads,
    bucket_layout,
    compress_grad_int8,
    constrain_grad,
    decompress_grad_int8,
    flatten_grads,
    psum_partial,
    unflatten_grads,
    weighted_all_reduce,
)
from .sharding import batch_spec, cache_specs, opt_specs, param_specs

__all__ = [
    "BucketedAllReduce",
    "BucketLayout",
    "CompressedBucketSync",
    "all_reduce_grads",
    "batch_spec",
    "bucket_layout",
    "cache_specs",
    "compress_grad_int8",
    "constrain_grad",
    "decompress_grad_int8",
    "flatten_grads",
    "opt_specs",
    "param_specs",
    "psum_partial",
    "unflatten_grads",
    "weighted_all_reduce",
]
