"""Mamba-2 (SSD — state-space duality) block.

Chunked SSD semantics (Dao & Gu 2024): within chunks of length Q the
recurrence is computed as a masked attention-like quadratic form, every
chunk at once, with C·Bᵀ taken once per group of heads; only the
(heads, head_dim, d_state) state crosses chunks, in a scan over the
chunks' own states. Decode keeps O(1) state per token — which is why the
ssm/hybrid families are the only ones qualifying for the long_500k shape.

Projections are split per component (z/x/B/C/dt) instead of one fused
in_proj so each weight shards cleanly over the ``model`` axis (heads and
d_inner are model-sharded; the small B/C/dt projections replicate).

The per-chunk quadratic form runs as plain jnp; a Pallas kernel for it
would be checked against :func:`ssd_chunked`.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .config import ModelConfig, SSMConfig
from .layers import rmsnorm

__all__ = ["ssd_chunked", "ssd_decode_step", "mamba_forward", "mamba_decode",
           "MambaCache", "init_mamba_cache"]


class MambaCache(NamedTuple):
    conv: jax.Array    # (B, W-1, conv_dim) — rolling conv window
    state: jax.Array   # (B, nheads, head_dim, d_state) — SSD state


def init_mamba_cache(cfg: ModelConfig, batch: int,
                     dtype=jnp.bfloat16) -> MambaCache:
    s = cfg.ssm
    assert s is not None
    d_in = s.d_inner(cfg.d_model)
    nh = s.n_heads(cfg.d_model)
    conv_dim = d_in + 2 * s.n_groups * s.d_state
    return MambaCache(
        jnp.zeros((batch, s.conv_width - 1, conv_dim), dtype),
        jnp.zeros((batch, nh, s.head_dim, s.d_state), jnp.float32),
    )


# ------------------------------------------------------------------ #
# SSD core                                                            #
# ------------------------------------------------------------------ #
# The most fp32 (Q, Q) working set, B·H·chunks·Q²·4 bytes, one call
# holds at once; past it the chunks run in blocks (:func:`ssd_blocks`).
QQ_BUDGET_BYTES = 1 << 30


def ssd_blocks(batch: int, seq: int, heads: int, chunk: int) -> int:
    """How many blocks :func:`ssd_chunked` runs a call's chunks in: the
    fewest whose (Q, Q) working set fits :data:`QQ_BUDGET_BYTES`, each
    an equal share of the ``seq // chunk`` chunks."""
    nc = seq // chunk
    per_chunk = batch * heads * chunk * chunk * 4
    return next(nc // k for k in range(nc, 0, -1) if nc % k == 0
                and (k * per_chunk <= QQ_BUDGET_BYTES or k == 1))


def ssd_chunked(x: jax.Array, dt: jax.Array, a_log: jax.Array,
                b: jax.Array, c: jax.Array, chunk: int,
                state0: jax.Array | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.

    x:  (B, S, H, P)   — per-head inputs (P = head_dim)
    dt: (B, S, H)      — softplus'd timestep
    a_log: (H,)        — A = -exp(a_log)
    b, c: (B, S, G, N) — input/output projections of G groups, H % G == 0
                         (heads g*H/G .. (g+1)*H/G - 1 read group g)
    state0: (B, H, P, N) — state before the first token, else zeros
    Returns (y (B,S,H,P), final_state (B,H,P,N)).

    All decay math in fp32; the recurrence is y_t = c_t . S_t with
    S_t = exp(dt_t A) S_{t-1} + dt_t b_t (x) x_t. Every chunk of a block
    is computed at once (:func:`_ssd_block`); blocks, one unless the
    (Q, Q) working set passes :data:`QQ_BUDGET_BYTES`, run in a scan
    that carries the state.
    """
    bs, s, h, p = x.shape
    g, n = b.shape[2:]
    assert s % chunk == 0, f"seq {s} % chunk {chunk} != 0"
    assert h % g == 0, f"heads {h} % groups {g} != 0"
    a = -jnp.exp(a_log.astype(jnp.float32))              # (H,)
    init = (jnp.zeros((bs, h, p, n), jnp.float32)
            if state0 is None else state0.astype(jnp.float32))
    nb = ssd_blocks(bs, s, h, chunk)
    if nb == 1:
        return _ssd_block(x, dt, a, b, c, chunk, init)

    def blocks(t):                                       # (nb, B, S/nb, *)
        return jnp.moveaxis(t.reshape(bs, nb, s // nb, *t.shape[2:]), 1, 0)

    def body(state, blk):
        xk, dtk, bk, ck = blk
        y, state = _ssd_block(xk, dtk, a, bk, ck, chunk, state)
        return state, y

    final, ys = jax.lax.scan(body, init, tuple(map(blocks, (x, dt, b, c))))
    return jnp.moveaxis(ys, 0, 1).reshape(bs, s, h, p), final


def _segment_decay(cum: jax.Array) -> jax.Array:
    """L[i, j] = exp(cum_i - cum_j) for j <= i, else 0; (..., Q) to
    (..., Q, Q).

    Masked before the exp, as the paper's segment sum is: above the
    diagonal the exponent is positive and overflows fp32 once a chunk's
    decay passes ~88, and the backward's 0 * inf would be NaN."""
    q = cum.shape[-1]
    mask = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    return jnp.exp(jnp.where(mask, cum[..., :, None] - cum[..., None, :],
                             -jnp.inf))


def _ssd_block(x, dt, a, b, c, chunk, state):
    """:func:`ssd_chunked` over every chunk of ``x`` at once, from the
    fp32 ``state`` (B, H, P, N) and with ``a`` = A (H,)."""
    bs, s, h, p = x.shape
    g, n = b.shape[2:]
    r, nc = h // g, s // chunk
    z = nc * bs * g
    f32 = jnp.float32
    # head-major chunked layout, (c, B, G) flattened to z: x (z, R, Q, P),
    # the (Q, Q) blocks in the two minor dimensions, B and C per group
    xr = x.reshape(bs, nc, chunk, g, r, p).transpose(1, 0, 3, 4, 2, 5)
    xr = xr.reshape(z, r, chunk, p).astype(f32)
    dtr, dta = (t.reshape(bs, nc, chunk, g, r).transpose(1, 0, 3, 4, 2)
                .reshape(z, r, chunk) for t in (dt.astype(f32), dt * a))
    br, cr = (t.reshape(bs, nc, chunk, g, n).transpose(1, 0, 3, 2, 4)
              .reshape(z, chunk, n).astype(f32) for t in (b, c))
    cum = jnp.cumsum(dta, axis=-1)                       # (z, R, Q)
    seg_total = cum[..., -1]                             # (z, R)

    # intra-chunk quadratic form, C·Bᵀ once per group
    cb = jnp.einsum("zin,zjn->zij", cr, br)
    w = cb[:, None] * _segment_decay(cum) * dtr[..., None, :]
    y = jnp.einsum("zrij,zrjp->zrip", w, xr)

    # each chunk's own state at its end: decay-to-end-weighted outer
    # products; these exponents and exp(cum) are <= 0 (dt >= 0, A < 0)
    x_end = xr * (jnp.exp(seg_total[..., None] - cum) * dtr)[..., None]
    s_chunk = jnp.einsum("zrjp,zjn->zrpn", x_end, br)

    # only the (B, G, R, P, N) state crosses chunks, in a scan over them
    def carry(st, inp):
        decay, s_c = inp
        return st * decay[..., None, None] + s_c, st

    final, prev = jax.lax.scan(
        carry, state.reshape(bs * g, r, p, n),
        (jnp.exp(seg_total).reshape(nc, bs * g, r),
         s_chunk.reshape(nc, bs * g, r, p, n)))
    # inter-chunk: y_inter[i] = exp(cum_i) * c_i . state entering the chunk
    y_inter = jnp.einsum("zin,zrpn->zrip", cr, prev.reshape(z, r, p, n))
    y = (y + y_inter * jnp.exp(cum)[..., None]).astype(x.dtype)
    y = y.reshape(nc, bs, g, r, chunk, p).transpose(1, 0, 4, 2, 3, 5)
    return y.reshape(bs, s, h, p), final.reshape(bs, h, p, n)


def ssd_decode_step(x: jax.Array, dt: jax.Array, a_log: jax.Array,
                    b: jax.Array, c: jax.Array, state: jax.Array
                    ) -> tuple[jax.Array, jax.Array]:
    """Single-token SSD update. x (B,H,P), dt (B,H), b,c (B,H,N),
    state (B,H,P,N) fp32. Returns (y (B,H,P), new_state)."""
    dtf = dt.astype(jnp.float32)
    a = -jnp.exp(a_log.astype(jnp.float32))
    decay = jnp.exp(dtf * a[None, :])                    # (B,H)
    outer = jnp.einsum("bhp,bhn->bhpn", x.astype(jnp.float32),
                       b.astype(jnp.float32)) * dtf[:, :, None, None]
    new_state = state * decay[:, :, None, None] + outer
    y = jnp.einsum("bhpn,bhn->bhp", new_state, c.astype(jnp.float32))
    return y.astype(x.dtype), new_state


# ------------------------------------------------------------------ #
# full block                                                          #
# ------------------------------------------------------------------ #
def _conv1d_causal(x: jax.Array, w: jax.Array, bias: jax.Array,
                   prefix: jax.Array | None = None) -> jax.Array:
    """Depthwise causal conv. x (B,S,C); w (C,W); prefix (B,W-1,C).

    f32 taps+bias (cheap: 4-tap depthwise) with a single rounding point —
    the decode path computes the same window product in f32, so both
    paths round identically and the SSD recurrence sees the same inputs.
    """
    width = w.shape[1]
    if prefix is None:
        prefix = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([prefix, x], axis=1).astype(jnp.float32)
    wf = w.astype(jnp.float32)
    out = sum(
        xp[:, i : i + x.shape[1], :] * wf[None, None, :, i]
        for i in range(width)
    )
    # indexing w as (C, W): w[:, i] per tap
    return out + bias[None, None, :].astype(jnp.float32)


def _split_proj(x, p, cfg: ModelConfig):
    s = cfg.ssm
    z = jnp.dot(x, p["wz"])                              # (B,S,d_in)
    xc = jnp.dot(x, p["wx"])                             # (B,S,d_in)
    bproj = jnp.dot(x, p["wb"])                          # (B,S,G*N)
    cproj = jnp.dot(x, p["wc"])                          # (B,S,G*N)
    dt = jnp.dot(x, p["wdt"])                            # (B,S,H)
    return z, xc, bproj, cproj, dt


def _broadcast_groups(t: jax.Array, n_heads: int, s: SSMConfig) -> jax.Array:
    """(B,S,G*N) -> (B,S,H,N) by repeating each group across its heads."""
    bshape = t.shape[:-1]
    g = s.n_groups
    t = t.reshape(*bshape, g, s.d_state)
    rep = n_heads // g
    t = jnp.broadcast_to(t[..., :, None, :], (*bshape, g, rep, s.d_state))
    return t.reshape(*bshape, n_heads, s.d_state)


def mamba_forward(x: jax.Array, p: dict, cfg: ModelConfig,
                  state0: jax.Array | None = None,
                  return_state: bool = False,
                  return_cache: bool = False):
    """Full-sequence Mamba-2 mixer. x (B,S,D) -> (B,S,D).

    ``return_cache`` returns ``(out, MambaCache(conv_tail, final_state))``
    — the exact cache :func:`mamba_decode` would hold after consuming the
    sequence token by token: the last W-1 raw ``conv_in`` rows plus the
    final SSD state (fused cache-filling prefill). NB: unlike attention,
    the SSD recurrence runs *through* every input token, so callers must
    feed exact-length prompts — right-padding would corrupt the state.
    """
    s = cfg.ssm
    assert s is not None
    bsz, seq, _ = x.shape
    nh = s.n_heads(cfg.d_model)
    d_in = s.d_inner(cfg.d_model)

    z, xc, bp, cp, dt = _split_proj(x, p, cfg)
    conv_in = jnp.concatenate([xc, bp, cp], axis=-1)
    conv_out = _conv1d_causal(conv_in, p["conv_w"], p["conv_b"])
    # the f32 conv bias promotes the chain — pin back to the compute dtype
    conv_out = jax.nn.silu(conv_out).astype(x.dtype)
    xs = conv_out[..., :d_in]
    bs_ = conv_out[..., d_in : d_in + s.n_groups * s.d_state]
    cs = conv_out[..., d_in + s.n_groups * s.d_state :]

    xh = xs.reshape(bsz, seq, nh, s.head_dim)
    bg = bs_.reshape(bsz, seq, s.n_groups, s.d_state)
    cg = cs.reshape(bsz, seq, s.n_groups, s.d_state)
    dt_sp = jax.nn.softplus(dt.astype(jnp.float32)
                            + p["dt_bias"].astype(jnp.float32))

    with jax.named_scope("ssd"):
        y, final = ssd_chunked(xh, dt_sp, p["a_log"], bg, cg,
                               min(s.chunk, seq), state0=state0)
    y = y + xh * p["d_skip"].astype(jnp.float32)[None, None, :, None].astype(y.dtype)
    y = y.reshape(bsz, seq, d_in)
    # gated RMSNorm (mamba-2): norm(y * silu(z))
    y = y * jax.nn.silu(z)
    y = rmsnorm(y, p["gate_norm"], cfg.norm_eps)
    out = jnp.dot(y, p["out_proj"])
    if return_cache:
        pad = jnp.zeros((bsz, s.conv_width - 1, conv_in.shape[-1]),
                        conv_in.dtype)
        tail = jnp.concatenate([pad, conv_in], axis=1)[:, -(s.conv_width - 1):]
        return out, MambaCache(tail.astype(jnp.bfloat16), final)
    if return_state:
        return out, final
    return out


def mamba_decode(x: jax.Array, p: dict, cfg: ModelConfig,
                 cache: MambaCache) -> tuple[jax.Array, MambaCache]:
    """One-token decode. x (B,1,D)."""
    s = cfg.ssm
    assert s is not None
    bsz = x.shape[0]
    nh = s.n_heads(cfg.d_model)
    d_in = s.d_inner(cfg.d_model)

    z, xc, bp, cp, dt = _split_proj(x, p, cfg)
    conv_in = jnp.concatenate([xc, bp, cp], axis=-1)     # (B,1,C)
    window = jnp.concatenate([cache.conv, conv_in], axis=1)  # (B,W,C)
    conv_out = jnp.einsum(
        "bwc,cw->bc", window.astype(jnp.float32),
        p["conv_w"].astype(jnp.float32)) + p["conv_b"].astype(jnp.float32)
    conv_out = jax.nn.silu(conv_out).astype(x.dtype)
    new_conv = window[:, 1:, :]

    xs = conv_out[:, :d_in]
    bs_ = conv_out[:, d_in : d_in + s.n_groups * s.d_state]
    cs = conv_out[:, d_in + s.n_groups * s.d_state :]
    xh = xs.reshape(bsz, nh, s.head_dim)
    bh = _broadcast_groups(bs_, nh, s)
    ch = _broadcast_groups(cs, nh, s)
    dt_sp = jax.nn.softplus(dt[:, 0].astype(jnp.float32)
                            + p["dt_bias"].astype(jnp.float32))

    y, new_state = ssd_decode_step(xh, dt_sp, p["a_log"], bh, ch, cache.state)
    y = y + xh * p["d_skip"].astype(jnp.float32)[None, :, None].astype(y.dtype)
    y = y.reshape(bsz, 1, d_in)
    y = y * jax.nn.silu(z)
    y = rmsnorm(y, p["gate_norm"], cfg.norm_eps)
    return jnp.dot(y, p["out_proj"]), MambaCache(new_conv, new_state)
