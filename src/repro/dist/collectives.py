"""Collective-communication helpers (weighted all-reduce, int8 EF compression).

SPARe's failure masking is, at the wire level, nothing but a *weighted*
gradient all-reduce: every (group, stack-slot) contributes its partial
gradient scaled by the supplier weight (``1/N`` for the designated
supplier of a shard type, ``0`` otherwise — :meth:`repro.core.SpareState
.device_schedule`), so the collected gradient equals vanilla DP's batch
gradient for every survivor set (§3.1 invariant). This module is the one
place that reduction is issued:

* on a real mesh (inside ``pmap``/``shard_map``) pass ``axis_name`` and
  the helper lowers to a single ``psum`` — failure masking costs zero
  extra collectives;
* host-side (laptop-scale emulation, trainers, tests) the same call is a
  plain weighted contraction with identical numerics.

The int8 error-feedback compressor is a beyond-paper bandwidth
optimization for the 20 TB-gradient all-reduce (paper Table 1): gradients
are quantized to int8 with a per-tensor scale (4x traffic reduction) and
the quantization residual is fed back into the next step's compression,
making the *cumulative* transmitted signal unbiased (Seide et al. 2014;
Karimireddy et al. 2019 — EF-SGD).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["weighted_all_reduce", "psum_partial", "all_reduce_grads",
           "constrain_grad", "compress_grad_int8", "decompress_grad_int8",
           "BucketLayout", "bucket_layout", "flatten_grads",
           "unflatten_grads", "BucketedAllReduce", "CompressedBucketSync"]


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def psum_partial(x: jax.Array, axis_name) -> jax.Array:
    """``psum`` whose inputs are *partial sums*, with the matching VJP.

    Inside ``shard_map`` each device holds its own partial contribution
    (a local weighted gradient, a local weighted loss): the derivative of
    the global sum w.r.t. a device's partial is exactly 1, so the
    backward pass is the identity. The stock ``lax.psum`` cannot know
    this — under ``check_vma=False`` its transpose is another ``psum``,
    which silently multiplies every gradient by the axis size (we
    measured exactly ``dp_degree``x on the first mesh bring-up). Routing
    the §3.1 reduction through this wrapper is what lets
    ``value_and_grad`` of a psummed loss return the correct *local*
    partial gradient, which is then all-reduced once per step.
    """
    return jax.lax.psum(x, axis_name)


def _psum_partial_fwd(x, axis_name):
    return jax.lax.psum(x, axis_name), None


def _psum_partial_bwd(axis_name, _res, ct):
    return (ct,)


psum_partial.defvjp(_psum_partial_fwd, _psum_partial_bwd)


def weighted_all_reduce(values: jax.Array, weights: jax.Array,
                        axis_name: str | None = None) -> jax.Array:
    """Supplier-weighted reduction ``Σ_i weights_i · values_i``.

    ``values`` and ``weights`` share a leading contraction shape (the
    per-example / per-slot axis); the result is the scalar (or trailing-
    shape) weighted sum. With ``axis_name`` set, the local partial sum is
    additionally ``psum``-reduced across the named mapped axis — this is
    the production spelling of the §3.1 weighted all-reduce; without it,
    the call is the exact host-side emulation. The psum is the
    partial-sum flavor (:func:`psum_partial`), so differentiating a loss
    built on this reduction yields each device's own partial gradient —
    see :func:`all_reduce_grads` for the per-step gradient sync.
    """
    w = weights.reshape(weights.shape + (1,) * (values.ndim - weights.ndim))
    local = jnp.sum(values * w.astype(values.dtype),
                    axis=tuple(range(weights.ndim)))
    if axis_name is not None:
        local = psum_partial(local, axis_name)
    return local


def all_reduce_grads(grads, axis_name: str):
    """One gradient all-reduce per step: psum every leaf of the (already
    supplier-weighted) local gradient pytree across the mapped data axis.

    This is the single collective SPARe's failure masking rides on — the
    weights folded into the per-example loss make the psummed result
    equal vanilla DP's batch gradient for every survivor set, so masking
    a failure never changes the collective schedule (paper §3.1, "zero
    extra collectives").
    """
    with jax.named_scope("sync"):
        return jax.tree.map(lambda g: psum_partial(g, axis_name), grads)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def constrain_grad(x: jax.Array, sharding) -> jax.Array:
    """Identity forward; pins the *cotangent* to ``sharding``.

    Used to force GSPMD to reduce-scatter weight gradients to their
    shard at the point of production (inside the backward of the layer
    scan) instead of all-reducing them to replicated form inside the
    loop.
    """
    return x


def _constrain_grad_fwd(x, sharding):
    return x, None


def _constrain_grad_bwd(sharding, _res, ct):
    return (jax.lax.with_sharding_constraint(ct, sharding),)


constrain_grad.defvjp(_constrain_grad_fwd, _constrain_grad_bwd)


def compress_grad_int8(
    grad: jax.Array, error: jax.Array, *, fused: bool | None = None
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Int8 error-feedback quantization of one gradient tensor.

    Compresses ``grad + error`` (the fresh gradient plus the residual the
    previous step failed to transmit) to int8 with a shared per-tensor
    scale, and returns the residual to carry into the next step::

        q, scale, new_error = compress_grad_int8(grad, error)
        wire_bytes = q          # 1/4 of fp32
        restored   = decompress_grad_int8(q, scale)
        # invariant: restored + new_error == grad + error   (exactly)

    Returns ``(q, scale, new_error)`` where ``q`` is int8 with the same
    shape as ``grad``, ``scale`` is the scalar dequantization step, and
    ``new_error = (grad + error) - decompress(q, scale)``.

    The whole arithmetic runs in fp32 regardless of ``grad``'s dtype:
    :func:`decompress_grad_int8` dequantizes in fp32, so a residual
    computed in e.g. bf16 would break the exact invariant above (the
    bf16 rounding of ``x - q*scale`` diverges from the fp32 value the
    receiver reconstructs). ``error`` carries the fp32 residual between
    steps; ``new_error`` is always returned as fp32.

    The max quantization error of a single step is ``scale/2 <= scale``;
    with error feedback the *cumulative* transmitted signal converges to
    the cumulative true gradient, which is what makes aggressive 8-bit
    compression safe for SGD-family optimizers.

    ``fused`` routes through the Pallas quantize-accumulate kernel
    (:func:`repro.kernels.ops.int8_ef_quantize`): one VMEM pass computes
    the EF accumulate, the quantization, and the residual together
    instead of the unfused XLA chain. Defaults to the kernel on TPU and
    the plain jnp spelling elsewhere; both compute the identical fp32
    math — ``q`` and ``scale`` bit-identical, the residual up to one
    fp32 ulp (compiler FMA contraction of ``x - q*scale``; the exact
    invariant above strictly holds on the op-by-op/eager path).
    """
    if fused is None:
        from repro.kernels.ops import on_tpu
        fused = on_tpu()
    if fused:
        from repro.kernels.ops import int8_ef_quantize
        return int8_ef_quantize(grad, error)
    # the unfused spelling IS the kernel oracle — one definition of the
    # accumulate/scale/clip/residual math keeps the bit-identical
    # contract between the paths from drifting
    from repro.kernels.ref import int8_ef_ref
    return int8_ef_ref(grad, error)


def decompress_grad_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`compress_grad_int8`: ``q * scale`` in fp32."""
    return q.astype(jnp.float32) * scale


# --------------------------------------------------------------------- #
# bucketed flat gradient sync                                           #
# --------------------------------------------------------------------- #
@dataclass(frozen=True)
class BucketLayout:
    """Deterministic flat-bucket layout of a gradient pytree.

    Leaves (in ``jax.tree`` order) are packed first-fit-in-order into
    contiguous fp32 buckets capped at ``max_bucket_elems`` (a leaf larger
    than the cap gets a bucket of its own), and every bucket is
    zero-padded up to a multiple of ``pad_to`` (the data-parallel chunk
    granularity of the compressed sync). The layout is a pure function of
    (tree structure, leaf shapes, cap, pad) — compress and decompress
    sides derive byte-identical placement with no coordination.
    """

    treedef: object
    shapes: tuple[tuple[int, ...], ...]    # per leaf
    dtypes: tuple[str, ...]                # per leaf (original dtype name)
    bucket_of: tuple[int, ...]             # leaf -> bucket index
    offsets: tuple[int, ...]               # leaf -> element offset in bucket
    bucket_sizes: tuple[int, ...]          # padded element counts
    pad_to: int

    @property
    def n_buckets(self) -> int:
        return len(self.bucket_sizes)

    @property
    def n_elems(self) -> int:
        return sum(self.bucket_sizes)


def bucket_layout(tree, *, max_bucket_elems: int = 1 << 23,
                  pad_to: int = 1) -> BucketLayout:
    """Pack ``tree``'s leaves (arrays or ShapeDtypeStructs) into buckets."""
    leaves, treedef = jax.tree.flatten(tree)
    shapes, dtypes, bucket_of, offsets = [], [], [], []
    sizes: list[int] = []          # unpadded fill of each open bucket
    for leaf in leaves:
        n = int(np.prod(leaf.shape, dtype=np.int64)) if leaf.shape else 1
        shapes.append(tuple(leaf.shape))
        dtypes.append(jnp.dtype(leaf.dtype).name)
        if not sizes or sizes[-1] + n > max_bucket_elems and sizes[-1] > 0:
            sizes.append(0)
        bucket_of.append(len(sizes) - 1)
        offsets.append(sizes[-1])
        sizes[-1] += n
    padded = tuple(-(-s // pad_to) * pad_to for s in sizes)
    return BucketLayout(treedef=treedef, shapes=tuple(shapes),
                        dtypes=tuple(dtypes), bucket_of=tuple(bucket_of),
                        offsets=tuple(offsets), bucket_sizes=padded,
                        pad_to=pad_to)


def flatten_grads(layout: BucketLayout, tree) -> list[jax.Array]:
    """Pytree -> list of contiguous fp32 1-D buckets (zero-padded)."""
    leaves = layout.treedef.flatten_up_to(tree)
    parts: list[list[jax.Array]] = [[] for _ in layout.bucket_sizes]
    fill = [0] * layout.n_buckets
    for i, leaf in enumerate(leaves):
        b = layout.bucket_of[i]
        parts[b].append(leaf.astype(jnp.float32).reshape(-1))
        fill[b] += parts[b][-1].size
    bufs = []
    for b, chunks in enumerate(parts):
        buf = jnp.concatenate(chunks) if len(chunks) > 1 else chunks[0]
        pad = layout.bucket_sizes[b] - fill[b]
        if pad:
            buf = jnp.pad(buf, (0, pad))
        bufs.append(buf)
    return bufs


def unflatten_grads(layout: BucketLayout, bufs) -> object:
    """Inverse of :func:`flatten_grads`; bit-transparent round trip.

    fp32 leaves come back untouched; bf16/fp16 leaves round-trip exactly
    because widening to fp32 is lossless and the cast back merely undoes
    it (the uncompressed bucketed psum adds device partials in fp32 — the
    same element order and width the per-leaf psum used).
    """
    leaves = []
    for i, shape in enumerate(layout.shapes):
        b, off = layout.bucket_of[i], layout.offsets[i]
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        leaf = jax.lax.slice(bufs[b], (off,), (off + n,)).reshape(shape)
        leaves.append(leaf.astype(layout.dtypes[i]))
    return jax.tree.unflatten(layout.treedef, leaves)


class BucketedAllReduce:
    """O(1)-collective gradient sync: psum a handful of flat buckets.

    Replaces the one-``psum``-per-parameter-leaf spelling of
    :func:`all_reduce_grads` inside ``shard_map``: the gradient pytree is
    flattened through a :class:`BucketLayout` (a fixed, small number of
    size-capped fp32 buffers), each bucket is psummed once, and the tree
    is rebuilt bit-transparently. Collective count per step is
    ``layout.n_buckets`` regardless of how many hundred leaves the model
    has; numerics are element-for-element identical to the per-leaf psum
    (same adds, same order, same fp32 width).
    """

    stateful = False

    def __init__(self, layout: BucketLayout, axis_name: str):
        self.layout = layout
        self.axis_name = axis_name

    @jax.named_scope("sync")
    def __call__(self, grads):
        bufs = flatten_grads(self.layout, grads)
        bufs = [psum_partial(b, self.axis_name) for b in bufs]
        return unflatten_grads(self.layout, bufs)


class CompressedBucketSync:
    """Two-phase int8 error-feedback all-reduce over flat buckets.

    The wire protocol (per bucket of ``B`` fp32 elements, data-parallel
    degree ``dp``), all arithmetic fp32 — int8 payloads are *gathered*
    and dequant-accumulated, never int-psummed, so there is no overflow
    at any ``dp``:

    1. quantize the local partial bucket (+ stage-1 EF residual) to int8
       with one fp32 scale per (device, bucket);
    2. ``all_to_all`` the int8 payload: device ``i`` receives every
       device's quantized partial of chunk ``i`` (``B`` int8 wire bytes),
       plus an ``all_gather`` of the ``dp`` fp32 scales;
    3. dequant-accumulate the chunk in fp32 — device ``i`` now owns the
       exact (up to stage-1 quantization) reduced chunk ``i``;
    4. re-quantize the reduced chunk (+ stage-2 EF residual, owned by
       the same device every step) and ``all_gather`` int8 chunks +
       scales back to everyone (``B`` int8 wire bytes);
    5. dequantize locally into the full reduced bucket.

    Wire bytes ~= ``2B`` vs the fp32 ring all-reduce's ``8B`` — the ~4x
    reduction gated by ``launch/hlo.py`` — and the collective *count* is
    a constant 4 per bucket, independent of the survivor set (masking
    stays weight data; the schedule is byte-identical masked vs
    unmasked). Both EF residuals are device-local sharded state
    (flat arrays split over the data axis) threaded through the train
    step; the cumulative transmitted gradient stays unbiased through
    both quantizations (Seide et al. 2014; Tang et al. 2019 — the
    1-bit-Adam-style two-stage EF).
    """

    stateful = True

    #: deep-mode telemetry (a ``repro.obs.Telemetry``), attached post-hoc
    #: by the mesh executor: emits in-jit ``bucket/<i>`` markers around
    #: each bucket's wire phases via ``jax.debug.callback``. Changing it
    #: changes the traced program — strictly an attribution-session knob.
    tel = None

    #: trailing axis of every int8 payload on the wire. The TPU compiler
    #: takes time linear in B for an all-to-all or all-gather of flat
    #: int8 chunks, and little for the same bytes in 128-lane rows, so
    #: each device's chunk must be a whole number of lanes: build the
    #: layout with ``pad_to=LANES * dp_degree`` (or a multiple).
    LANES = 128

    def __init__(self, layout: BucketLayout, dp_degree: int,
                 axis_name: str, *, fused: bool | None = None):
        pad = self.LANES * dp_degree
        for b, size in enumerate(layout.bucket_sizes):
            if size % pad:
                raise ValueError(
                    f"bucket {b} has {size} elements, not divisible by "
                    f"{self.LANES} * dp_degree={pad}; build the layout "
                    f"with pad_to={pad} (or a multiple)")
        self.layout = layout
        self.dp = dp_degree
        self.axis_name = axis_name
        self.fused = fused

    # -- EF state plumbing (global view, host side) ------------------- #
    def init_state(self):
        """Zero EF residuals, *global* shapes: ``err1[b]`` is every
        device's stage-1 residual for bucket ``b`` laid out flat
        (``dp * B`` fp32, device-sharded), ``err2[b]`` the chunk-owner
        stage-2 residual (``B`` fp32, device-sharded)."""
        return {
            "err1": tuple(np.zeros(self.dp * s, np.float32)
                          for s in self.layout.bucket_sizes),
            "err2": tuple(np.zeros(s, np.float32)
                          for s in self.layout.bucket_sizes),
        }

    def state_specs(self):
        """PartitionSpecs matching :meth:`init_state` (both residual
        families shard flat over the data axis — pure device-local
        state, no cross-device meaning)."""
        from jax.sharding import PartitionSpec as P
        spec = P(self.axis_name)
        return {"err1": tuple(spec for _ in self.layout.bucket_sizes),
                "err2": tuple(spec for _ in self.layout.bucket_sizes)}

    # -- the sync itself (device side, inside shard_map) -------------- #
    def _sync_bucket(self, buf, e1, e2):
        q1, s1, e1_new = compress_grad_int8(buf, e1, fused=self.fused)
        # ship everyone's partial of my chunk; scales ride separately.
        # int8 payloads move in LANES-wide rows (see LANES)
        mine = jax.lax.all_to_all(q1.reshape(self.dp, -1, self.LANES),
                                  self.axis_name, 0, 0
                                  ).reshape(self.dp, -1)      # (dp, B/dp)
        scales = jax.lax.all_gather(s1, self.axis_name)       # (dp,)
        chunk = jnp.einsum("j,jk->k", scales,
                           mine.astype(jnp.float32))          # fp32 sum
        q2, s2, e2_new = compress_grad_int8(chunk, e2, fused=self.fused)
        full_q = jax.lax.all_gather(q2.reshape(-1, self.LANES),
                                    self.axis_name
                                    ).reshape(self.dp, -1)    # (dp, B/dp)
        full_s = jax.lax.all_gather(s2, self.axis_name)       # (dp,)
        out = (full_q.astype(jnp.float32) * full_s[:, None]).reshape(-1)
        return out, e1_new, e2_new

    @jax.named_scope("sync")
    def __call__(self, grads, state):
        """Local (per-device) view: ``state['err1'][b]`` is this
        device's full-bucket residual, ``state['err2'][b]`` its owned
        chunk's. Returns (reduced grads pytree, new state)."""
        bufs = flatten_grads(self.layout, grads)
        out, ne1, ne2 = [], [], []
        tel = self.tel
        if tel is not None:
            tel.jit_instant("grad_sync", "sync", bufs[0])
        for b, (buf, e1, e2) in enumerate(zip(bufs, state["err1"],
                                              state["err2"])):
            if tel is not None:
                tel.jit_instant(f"bucket/{b}", "sync", buf)
            full, e1n, e2n = self._sync_bucket(buf, e1, e2)
            if tel is not None:
                tel.jit_instant(f"bucket/{b}/done", "sync", full)
            out.append(full)
            ne1.append(e1n)
            ne2.append(e2n)
        return (unflatten_grads(self.layout, out),
                {"err1": tuple(ne1), "err2": tuple(ne2)})

    def sync_once(self, grads):
        """Stateless spelling (zero residuals) for verification paths —
        single-step quantization error only, bounded by the §3.1
        quantization-tolerance oracle in ``exec/equivalence.py``."""
        zeros = {
            "err1": tuple(jnp.zeros(s, jnp.float32)
                          for s in self.layout.bucket_sizes),
            "err2": tuple(jnp.zeros(s // self.dp, jnp.float32)
                          for s in self.layout.bucket_sizes),
        }
        reduced, _ = self(grads, zeros)
        return reduced
