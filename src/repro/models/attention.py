"""Attention: GQA (causal flash kernel on the TPU, chunked jnp reference)
and DeepSeek MLA.

Full-sequence GQA (:func:`gqa_forward`, training and fused prefill) runs
splash attention's causal MQA kernel (:func:`attend_flash`, Pallas, with
its own backward) when :func:`flash_blocks` admits the shapes: on the
TPU, unpartitioned, at a head size and a length the kernel tiles.
Elsewhere it runs the query-chunked :func:`attend_chunked` (O(S * chunk)
score memory, plain einsums), which stays the CPU path and the kernel's
oracle.

Decode path scores one query against a (possibly sequence-sharded) KV
cache; softmax over the sharded key axis lowers to all-reduce(max)/(sum) —
the TPU analogue of split-KV flash-decode.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as splash, splash_attention_mask as splash_mask)

from repro.kernels import ops as kernel_ops

from .config import ModelConfig
from .layers import apply_rope, rmsnorm

__all__ = [
    "attend_chunked", "attend_flash", "flash_blocks", "gqa_forward",
    "gqa_decode", "mla_forward", "mla_decode", "KVCache", "MLACache",
    "init_gqa_cache", "init_mla_cache", "init_gqa_pool", "init_mla_pool",
    "paged_view", "gqa_decode_paged", "mla_decode_paged",
]

_NEG_INF = -2.0 ** 20  # large-but-finite: keeps bf16/softmax NaN-free


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(B, S, KV, dh) -> (B, S, KV*n_rep, dh) by head repetition."""
    if n_rep == 1:
        return k
    b, s, kv, dh = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, dh)
                            ).reshape(b, s, kv * n_rep, dh)


def attend_chunked(q: jax.Array, k: jax.Array, v: jax.Array,
                   chunk: int = 512, causal: bool = True) -> jax.Array:
    """Causal attention with query chunking.

    q: (B, S, H, dh); k, v: (B, S, H, dh)  (already GQA-expanded).
    Returns (B, S, H, dh). Scores for one chunk are (B, H, C, S) — the
    working set stays O(S*C) per head, which is what makes the 32k-prefill
    shapes compile inside a 16 GB HBM budget without a custom kernel.
    """
    b, s, h, dh = q.shape
    scale = dh ** -0.5
    chunk = min(chunk, s)
    n_chunks = s // chunk
    assert s % chunk == 0, f"seq {s} % chunk {chunk} != 0"

    kT = k.transpose(0, 2, 3, 1)         # (B, H, dh, S)
    vT = v.transpose(0, 2, 1, 3)         # (B, H, S, dh)
    q_chunks = q.reshape(b, n_chunks, chunk, h, dh).transpose(1, 0, 3, 2, 4)

    kpos = jnp.arange(s)

    def one_chunk(ci, qc):
        # qc: (B, H, C, dh)
        scores = jnp.einsum("bhcd,bhdk->bhck", qc, kT) * scale
        scores = scores.astype(jnp.float32)
        if causal:
            qpos = ci * chunk + jnp.arange(chunk)
            mask = qpos[:, None] >= kpos[None, :]
            scores = jnp.where(mask[None, None], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhck,bhkd->bhcd", probs, vT)

    out = jax.lax.map(lambda args: one_chunk(*args),
                      (jnp.arange(n_chunks), q_chunks))
    # (n_chunks, B, H, C, dh) -> (B, S, H, dh)
    return out.transpose(1, 0, 3, 2, 4).reshape(b, s, h, dh)


# Block edge of every flash kernel (forward, dq, dkv). On a v5e chip at
# B 4, H 16, KV 2, S 1024, dh 128 it beat 128 and 256 in each kernel and
# 1024 overall (PERF.md, section 6). Splash's fused backward was faster,
# but it rounds each key block's partial dq to bf16 before summing them.
FLASH_BLOCK = 512


def flash_blocks(s: int, head_dim: int,
                 partitioned: bool) -> splash.BlockSizes | None:
    """Block sizes of :func:`attend_flash` for a causal self-attention
    over ``s`` positions, or None where :func:`gqa_forward` keeps
    :func:`attend_chunked`: off the TPU; where the step is ``partitioned``
    over devices (GSPMD cannot split a Mosaic call, and the mesh
    executor's manual program keeps jnp until a four-chip cell times the
    kernel there); at a head size
    off the 128-lane tile; at a length the block does not divide.
    Every block is ``min(s, FLASH_BLOCK)``.
    """
    if partitioned or head_dim % 128 or not kernel_ops.on_tpu():
        return None
    block = min(s, FLASH_BLOCK)
    if block % 128 or s % block:
        return None
    return splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block)


def attend_flash(q: jax.Array, k: jax.Array, v: jax.Array,
                 blocks: splash.BlockSizes,
                 interpret: bool = False) -> jax.Array:
    """Causal GQA attention through splash attention's MQA kernels.

    q: (B, S, H, dh); k, v: (B, S, KV, dh), not repeated. The kernels run
    once per batch row and KV head over that head's H/KV query heads;
    they keep scores in VMEM with fp32 accumulation and an fp32 softmax
    and skip the blocks the causal mask hides. Splash applies no softmax
    scale, so q is scaled in fp32 first. Returns (B, S, H, dh) in q's
    dtype.

    Splash's own backward takes ``di = rowsum(dO * O)`` from the output
    rounded to q's dtype; in bf16 that leaves each query's score
    gradients a nonzero sum over keys, which the jnp path keeps at zero
    (measured on the chip as a doubled gap in the key bias's update,
    PERF.md section 6). So the forward kernel runs on an fp32 q and keeps
    its fp32 output for ``di``; dq and dkv run splash's backward kernels
    on the bf16 operands.
    """
    b, s, h, dh = q.shape
    kv = k.shape[2]
    g = h // kv
    mask = splash_mask.MultiHeadMask([splash_mask.CausalMask((s, s))] * g)
    kernel = splash.make_splash_mqa_single_device(
        mask, block_sizes=blocks, save_residuals=True, interpret=interpret)
    q = (q.astype(jnp.float32) * dh ** -0.5).astype(q.dtype)
    q = q.reshape(b, s, kv, g, dh).transpose(0, 2, 3, 1, 4)
    per_head = jax.vmap(_flash_core, in_axes=(None, 0, 0, 0))
    out = jax.vmap(per_head, in_axes=(None, 0, 0, 0))(
        kernel, q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
    # (B, KV, G, S, dh) -> (B, S, H, dh)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, dh)


@jax.custom_vjp
def _flash_core(kernel, q, k, v):
    """One KV head: q (G, S, dh), k and v (S, dh)."""
    return _flash_fwd(kernel, q, k, v)[0]


def _flash_fwd(kernel, q, k, v):
    o, (lse,) = kernel(q.astype(jnp.float32), k, v)
    return o.astype(q.dtype), (kernel, q, k, v, o, lse)


def _flash_bwd(res, do):
    kernel, q, k, v, o, lse = res
    kw = kernel.kwargs
    # the block masks as splash's own jitted entry hands them on
    dq_info, dkv_info = (
        m if m.partial_mask_blocks is None else m._replace(
            partial_mask_blocks=m.partial_mask_blocks.reshape(
                -1, *m.partial_mask_blocks.shape[-2:]))
        for m in (kernel.dq_mask_info, kernel.dkv_mask_info))
    grads = splash._splash_attention_bwd(
        False, kw["mask_value"], True, kw["block_sizes"], None,
        kw["mask_function"], None, kw["interpret"],
        (q, k, v, None, None, o, lse, dq_info, dkv_info), do)
    return (None, *grads[3:6])


_flash_core.defvjp(_flash_fwd, _flash_bwd)


# ------------------------------------------------------------------ #
# GQA                                                                 #
# ------------------------------------------------------------------ #
class KVCache(NamedTuple):
    k: jax.Array      # (B, S_max, KV, dh)
    v: jax.Array      # (B, S_max, KV, dh)


def init_gqa_cache(cfg: ModelConfig, batch: int, s_max: int,
                   dtype=jnp.bfloat16) -> KVCache:
    dh = cfg.resolved_head_dim
    shape = (batch, s_max, cfg.n_kv_heads, dh)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def _qkv(x, p, cfg: ModelConfig):
    b, s, _ = x.shape
    dh = cfg.resolved_head_dim
    q = jnp.dot(x, p["wq"])
    k = jnp.dot(x, p["wk"])
    v = jnp.dot(x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = q.reshape(b, s, cfg.n_heads, dh)
    k = k.reshape(b, s, cfg.n_kv_heads, dh)
    v = v.reshape(b, s, cfg.n_kv_heads, dh)
    return q, k, v


def gqa_forward(x: jax.Array, p: dict, cfg: ModelConfig,
                positions: jax.Array | None = None,
                chunk: int = 512, head_constrain=None,
                return_kv: bool = False, partitioned: bool = False):
    """Full-sequence causal GQA. x: (B, S, D) -> (B, S, D).

    The attention core runs :func:`attend_flash` where
    :func:`flash_blocks` admits the shapes (K and V stay unrepeated),
    else :func:`attend_chunked` over K and V repeated to H heads.
    ``partitioned`` says the caller splits the step over devices without
    ``head_constrain`` (``Model.partitioned``); either refuses the kernel.

    ``head_constrain`` pins (B, S, H, dh) tensors to head-sharding over
    the model axis (implicitly padded for H % TP != 0). Without it GSPMD
    may shard the *contraction* (head_dim) for awkward head counts and
    all-reduce the full (S x S) score tensors — measured 4.6 TB/step of
    avoidable all-reduce on starcoder2-7b (36 heads over TP=16); see
    EXPERIMENTS.md §Perf.

    ``return_kv`` additionally returns the decode-cache contents — the
    post-rope, pre-repeat ``KVCache(k, v)`` of shape (B, S, KV, dh) —
    which is the fused cache-filling prefill: the k/v are the exact
    tensors :func:`gqa_decode` would have written token by token, at
    zero extra compute (they are byproducts of the forward).
    """
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    q, k, v = _qkv(x, p, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    cache = KVCache(k, v) if return_kv else None
    blocks = flash_blocks(s, cfg.resolved_head_dim,
                          partitioned or head_constrain is not None)
    if blocks is not None:
        out = attend_flash(q, k, v, blocks)
    else:
        n_rep = cfg.n_heads // cfg.n_kv_heads
        k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
        if head_constrain is not None:
            q, k, v = head_constrain(q), head_constrain(k), head_constrain(v)
        out = attend_chunked(q, k, v, chunk=chunk)
        if head_constrain is not None:
            out = head_constrain(out)
    y = jnp.dot(out.reshape(b, s, -1), p["wo"])
    if return_kv:
        return y, cache
    return y


def init_gqa_pool(cfg: ModelConfig, n_pages: int, page_size: int,
                  dtype=jnp.bfloat16) -> KVCache:
    """Physical page pool for paged decode: (n_pages, PS, KV, dh) leaves.

    Page 0 is reserved as the *trash page*: inactive decode slots carry an
    all-zero block table and pos 0, so their per-step scatter lands there
    and their gather reads it — garbage in, garbage out, fully masked.
    The allocator must never hand out page 0.
    """
    dh = cfg.resolved_head_dim
    shape = (n_pages, page_size, cfg.n_kv_heads, dh)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def init_mla_pool(cfg: ModelConfig, n_pages: int, page_size: int,
                  dtype=jnp.bfloat16) -> MLACache:
    """Physical page pool for paged MLA decode (compressed-latent rows)."""
    return MLACache(
        jnp.zeros((n_pages, page_size, cfg.kv_lora_rank), dtype),
        jnp.zeros((n_pages, page_size, cfg.mla_d_rope), dtype),
    )


def paged_view(pool: jax.Array, table: jax.Array) -> jax.Array:
    """Gather a logical per-sequence cache view from the physical pool.

    pool: (n_pages, PS, *tail); table: (B, M) int32 page ids.
    Returns (B, M*PS, *tail) — the contiguous cache each row *thinks* it
    has. Rows past ``pos`` hold stale/trash data; callers mask them, and
    softmax's exp underflows the _NEG_INF scores to exactly 0.0, so stale
    pages are unreachable rather than merely unlikely.
    """
    b, m = table.shape
    g = jnp.take(pool, table.reshape(-1), axis=0)
    return g.reshape(b, m * pool.shape[1], *pool.shape[2:])


def _paged_write(pool: jax.Array, new: jax.Array, table: jax.Array,
                 pos: jax.Array) -> jax.Array:
    """Scatter one new token row per sequence into its current page.

    new: (B, *tail) — token ``pos[b]`` of row b. Distinct live sequences
    own distinct pages so the scatter indices never collide except on the
    trash page (0, 0), where last-write-wins is fine by construction.
    """
    ps = pool.shape[1]
    page = jnp.take_along_axis(table, (pos // ps)[:, None], axis=1)[:, 0]
    return pool.at[page, pos % ps].set(new)


def gqa_decode(x: jax.Array, p: dict, cfg: ModelConfig, cache: KVCache,
               pos: jax.Array) -> tuple[jax.Array, KVCache]:
    """One-token decode. x: (B, 1, D); pos: () int32 — current position.

    The cache key axis may be sharded ('model'); the masked softmax
    reduction then lowers to the split-KV pattern (all-reduce max / sum).
    """
    b = x.shape[0]
    dh = cfg.resolved_head_dim
    q, k_new, v_new = _qkv(x, p, cfg)
    posb = jnp.broadcast_to(pos[None], (b, 1)) if pos.ndim == 0 else pos
    q = apply_rope(q, posb, cfg.rope_theta)
    k_new = apply_rope(k_new, posb, cfg.rope_theta)

    k = jax.lax.dynamic_update_slice_in_dim(cache.k, k_new, pos, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache.v, v_new, pos, axis=1)

    n_rep = cfg.n_heads // cfg.n_kv_heads
    kh = _repeat_kv(k, n_rep)           # (B, S_max, H, dh)
    vh = _repeat_kv(v, n_rep)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kh) * dh ** -0.5
    valid = (jnp.arange(k.shape[1]) <= pos)[None, None, None, :]
    scores = jnp.where(valid, scores.astype(jnp.float32), _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vh)
    y = jnp.dot(out.reshape(b, 1, -1), p["wo"])
    return y, KVCache(k, v)


def gqa_decode_paged(x: jax.Array, p: dict, cfg: ModelConfig,
                     pool: KVCache, table: jax.Array,
                     pos: jax.Array) -> tuple[jax.Array, KVCache]:
    """One-token decode against a paged KV pool, per-row positions.

    x: (B, 1, D); pool leaves: (n_pages, PS, KV, dh); table: (B, M)
    physical page ids; pos: (B,) int32 — row b is generating token
    ``pos[b]``. Unlike :func:`gqa_decode` (scalar pos, dense per-row
    cache) every row advances independently, which is what continuous
    batching needs: admissions and evictions only rewrite the block
    table, never the compiled program.
    """
    b = x.shape[0]
    dh = cfg.resolved_head_dim
    q, k_new, v_new = _qkv(x, p, cfg)
    posb = pos[:, None]                             # (B, 1)
    q = apply_rope(q, posb, cfg.rope_theta)
    k_new = apply_rope(k_new, posb, cfg.rope_theta)

    k_pool = _paged_write(pool.k, k_new[:, 0], table, pos)
    v_pool = _paged_write(pool.v, v_new[:, 0], table, pos)

    n_rep = cfg.n_heads // cfg.n_kv_heads
    kh = _repeat_kv(paged_view(k_pool, table), n_rep)   # (B, M*PS, H, dh)
    vh = _repeat_kv(paged_view(v_pool, table), n_rep)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kh) * dh ** -0.5
    valid = (jnp.arange(kh.shape[1])[None] <= pos[:, None])[:, None, None, :]
    scores = jnp.where(valid, scores.astype(jnp.float32), _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vh)
    y = jnp.dot(out.reshape(b, 1, -1), p["wo"])
    return y, KVCache(k_pool, v_pool)


# ------------------------------------------------------------------ #
# MLA (DeepSeek multi-head latent attention)                          #
# ------------------------------------------------------------------ #
class MLACache(NamedTuple):
    """Compressed cache: latent c_kv + shared rope key (the whole point of
    MLA — cache is rank x (kv_lora + d_rope) per token, not heads x dh)."""
    c_kv: jax.Array    # (B, S_max, kv_lora)
    k_rope: jax.Array  # (B, S_max, d_rope)


def init_mla_cache(cfg: ModelConfig, batch: int, s_max: int,
                   dtype=jnp.bfloat16) -> MLACache:
    return MLACache(
        jnp.zeros((batch, s_max, cfg.kv_lora_rank), dtype),
        jnp.zeros((batch, s_max, cfg.mla_d_rope), dtype),
    )


def _mla_q(x, p, cfg: ModelConfig, positions):
    b, s, _ = x.shape
    h, dn, dr = cfg.n_heads, cfg.mla_d_nope, cfg.mla_d_rope
    if cfg.q_lora_rank:
        cq = rmsnorm(jnp.dot(x, p["wq_a"]), p["q_norm"], cfg.norm_eps)
        q = jnp.dot(cq, p["wq_b"])
    else:
        q = jnp.dot(x, p["wq"])
    q = q.reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_kv(x, p, cfg: ModelConfig, positions):
    """Project to the latent + shared rope key (cache contents)."""
    dr = cfg.mla_d_rope
    ckv = jnp.dot(x, p["wkv_a"])                       # (B,S,lora+dr)
    c_kv, k_rope = ckv[..., :cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank:]
    c_kv = rmsnorm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[..., None, :], positions, cfg.rope_theta)[..., 0, :]
    return c_kv, k_rope


def _mla_attend(q_nope, q_rope, c_kv, k_rope, p, cfg: ModelConfig,
                causal_pos: jax.Array | None):
    """Latent-space attention (the 'absorbed' MLA formulation).

    Scores are computed *in the latent space*: q_nope is absorbed through
    W_uk so the per-token key is just c_kv (rank 512), never the expanded
    (H, dh) keys — this is the TPU-friendly form (one big einsum, small
    cache reads).
    """
    b, s_q = q_nope.shape[:2]
    h, dn, dv = cfg.n_heads, cfg.mla_d_nope, cfg.mla_d_v
    wk = p["wk_b"].reshape(cfg.kv_lora_rank, h, dn)
    wv = p["wv_b"].reshape(cfg.kv_lora_rank, h, dv)
    # absorb: q_lat (B,Sq,H,lora) = q_nope . wk^T
    q_lat = jnp.einsum("bqhd,lhd->bqhl", q_nope, wk)
    scores = jnp.einsum("bqhl,bkl->bhqk", q_lat, c_kv)
    scores = scores + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope)
    scores = scores.astype(jnp.float32) * (dn + cfg.mla_d_rope) ** -0.5
    if causal_pos is not None:
        qpos, kpos = causal_pos
        if qpos.ndim == 2:
            # per-row positions (B, Sq) — the paged-decode spelling
            mask = (qpos[:, :, None] >= kpos[None, None, :])[:, None]
        else:
            mask = (qpos[:, None] >= kpos[None, :])[None, None]
        scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q_nope.dtype)
    o_lat = jnp.einsum("bhqk,bkl->bqhl", probs, c_kv)   # latent values
    out = jnp.einsum("bqhl,lhd->bqhd", o_lat, wv)       # expand via W_uv
    return out.reshape(b, s_q, h * dv)


def mla_forward(x: jax.Array, p: dict, cfg: ModelConfig,
                positions: jax.Array | None = None,
                chunk: int = 512, return_kv: bool = False):
    """Full-sequence causal MLA. Query-chunked like the GQA path.

    ``return_kv`` additionally returns ``MLACache(c_kv, k_rope)`` — the
    exact compressed rows :func:`mla_decode` would have cached token by
    token (fused cache-filling prefill, zero extra compute).
    """
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    q_nope, q_rope = _mla_q(x, p, cfg, positions)
    c_kv, k_rope = _mla_kv(x, p, cfg, positions)

    chunk = min(chunk, s)
    n_chunks = s // chunk
    assert s % chunk == 0
    kpos = jnp.arange(s)

    def one_chunk(ci):
        sl = lambda t: jax.lax.dynamic_slice_in_dim(t, ci * chunk, chunk, axis=1)
        qpos = ci * chunk + jnp.arange(chunk)
        return _mla_attend(sl(q_nope), sl(q_rope), c_kv, k_rope, p, cfg,
                           (qpos, kpos))

    out = jax.lax.map(one_chunk, jnp.arange(n_chunks))
    out = out.transpose(1, 0, 2, 3).reshape(b, s, -1)
    y = jnp.dot(out, p["wo"])
    if return_kv:
        return y, MLACache(c_kv, k_rope)
    return y


def mla_decode(x: jax.Array, p: dict, cfg: ModelConfig, cache: MLACache,
               pos: jax.Array) -> tuple[jax.Array, MLACache]:
    """One-token MLA decode against the compressed latent cache."""
    b = x.shape[0]
    posb = jnp.broadcast_to(pos[None], (b, 1))
    q_nope, q_rope = _mla_q(x, p, cfg, posb)
    c_new, kr_new = _mla_kv(x, p, cfg, posb)
    c_kv = jax.lax.dynamic_update_slice_in_dim(cache.c_kv, c_new, pos, axis=1)
    k_rope = jax.lax.dynamic_update_slice_in_dim(cache.k_rope, kr_new, pos, axis=1)

    s_max = c_kv.shape[1]
    qpos = pos[None]                     # (1,)
    kpos = jnp.arange(s_max)
    out = _mla_attend(q_nope, q_rope, c_kv, k_rope, p, cfg, (qpos, kpos))
    return jnp.dot(out, p["wo"]), MLACache(c_kv, k_rope)


def mla_decode_paged(x: jax.Array, p: dict, cfg: ModelConfig,
                     pool: MLACache, table: jax.Array,
                     pos: jax.Array) -> tuple[jax.Array, MLACache]:
    """One-token MLA decode against a paged compressed-latent pool.

    Same contract as :func:`gqa_decode_paged`: table (B, M) page ids,
    pos (B,) per-row positions, page 0 is the trash page.
    """
    posb = pos[:, None]                             # (B, 1)
    q_nope, q_rope = _mla_q(x, p, cfg, posb)
    c_new, kr_new = _mla_kv(x, p, cfg, posb)
    c_pool = _paged_write(pool.c_kv, c_new[:, 0], table, pos)
    r_pool = _paged_write(pool.k_rope, kr_new[:, 0], table, pos)
    c_kv = paged_view(c_pool, table)                # (B, M*PS, lora)
    k_rope = paged_view(r_pool, table)
    kpos = jnp.arange(c_kv.shape[1])
    out = _mla_attend(q_nope, q_rope, c_kv, k_rope, p, cfg, (posb, kpos))
    return jnp.dot(out, p["wo"]), MLACache(c_pool, r_pool)
