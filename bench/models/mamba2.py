"""Mamba-2 (SSD): the plain reference, its parameter layout, and the
operations its shapes imply.

The reference follows Dao & Gu 2024 (arXiv:2405.21060): per layer an
RMSNorm, projections to z, x, B, C and dt, a causal depthwise
convolution (with bias) over x, B and C followed by SiLU,
dt = softplus(dt + dt_bias), A = -exp(A_log), the SSD recurrence
h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T, y_t = C_t . h_t, computed
with the paper's own chunked "SSD minimal" algorithm (Listing 1), the
skip y + D x, the gated RMSNorm norm(y * silu(z)), the output projection
and the residual; then a final RMSNorm and a head tied to the embedding.
Float32 throughout (``mm="f32"``, every product at ``Precision.HIGHEST``);
``mm="f8"`` rounds the forward operands of every product to
float8_e4m3fn (gradients pass through in float32), the lower-precision
control. Imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.weights import Leaf, stored_bytes

__all__ = ["layout", "logits", "train_flops_per_token", "vocab",
           "param_bytes"]

HIGHEST = jax.lax.Precision.HIGHEST


def vocab(c: dict) -> int:
    return c["vocab_size"]


def _dims(c: dict):
    d = c["d_model"]
    d_in = c["expand"] * d
    h = d_in // c["headdim"]
    g, n = c["ngroups"], c["d_state"]
    return d, d_in, h, c["headdim"], g, n, c["n_layer"]


def layout(c: dict) -> dict:
    d, d_in, h, p, g, n, layers = _dims(c)
    w = c["d_conv"]
    conv_dim = d_in + 2 * g * n
    v = c["vocab_size"]
    bf, f32 = "bfloat16", "float32"
    block = {
        "ln1": Leaf((layers, d), f32, "norm", 0.1),
        "mamba": {
            "wz": Leaf((layers, d, d_in), bf, "fan_in"),
            "wx": Leaf((layers, d, d_in), bf, "fan_in"),
            "wb": Leaf((layers, d, g * n), bf, "fan_in"),
            "wc": Leaf((layers, d, g * n), bf, "fan_in"),
            "wdt": Leaf((layers, d, h), bf, "fan_in"),
            "conv_w": Leaf((layers, conv_dim, w), bf, "normal", w ** -0.5),
            "conv_b": Leaf((layers, conv_dim), f32, "normal", 0.05),
            "a_log": Leaf((layers, h), f32, "a_log"),
            "d_skip": Leaf((layers, h), f32, "norm", 0.1),
            "dt_bias": Leaf((layers, h), f32, "dt_bias"),
            "gate_norm": Leaf((layers, d_in), f32, "norm", 0.1),
            "out_proj": Leaf((layers, d_in, d), bf, "fan_in"),
        },
    }
    return {"embed": Leaf((-(-v // 512) * 512, d), bf, "normal", 0.02,
                          rows=v),
            "final_norm": Leaf((d,), f32, "norm", 0.1),
            "segments": [(block,)]}


def _cast(mode):
    def cast(x):
        x = x.astype(jnp.float32)
        if mode != "f8":
            return x
        # forward operands rounded to float8_e4m3fn, gradients passed
        # through in float32 (an unscaled fp8 backward would flush most
        # gradients to zero and test nothing)
        low = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return x + jax.lax.stop_gradient(low - x)
    return cast


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _segsum(x):
    """x (..., T) -> (..., T, T): sum of x[j+1..i] below the diagonal,
    -inf above it (the paper's stable segment sum)."""
    t = x.shape[-1]
    x = jnp.broadcast_to(x[..., None], (*x.shape, t))
    below = jnp.tril(jnp.ones((t, t), bool), -1)
    x = jnp.where(below, x, 0.0)
    out = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), out, -jnp.inf)


def _ssd(x, a, b, c, chunk, ein):
    """SSD minimal (Listing 1 of the paper). x (B,L,H,P) already times
    dt, a (B,L,H) = dt * A, b and c (B,L,H,N). Returns y (B,L,H,P)."""
    bs, length, h, p = x.shape
    nc = length // chunk

    def blocks(t):
        return t.reshape(bs, nc, chunk, *t.shape[2:])
    x, a, b, c = blocks(x), blocks(a), blocks(b), blocks(c)
    a = a.transpose(0, 3, 1, 2)                      # (B,H,C,L)
    a_cum = jnp.cumsum(a, axis=-1)
    # 1. within each chunk
    big_l = jnp.exp(_segsum(a))                      # (B,H,C,L,L)
    cb = ein("bclhn,bcshn->bclsh", c, b)
    y_diag = ein("bclsh,bcshp->bclhp",
                 cb * big_l.transpose(0, 2, 3, 4, 1), x)
    # 2. each chunk's final state
    decay = jnp.exp(a_cum[..., -1:] - a_cum)         # (B,H,C,L)
    states = ein("bclhn,bclhp->bchpn",
                 b * decay.transpose(0, 2, 3, 1)[..., None], x)
    # 3. the recurrence between chunks
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    last = jnp.pad(a_cum[..., -1], ((0, 0), (0, 0), (1, 0)))
    decay_chunk = jnp.exp(_segsum(last))             # (B,H,C+1,C+1)
    states = jnp.einsum("bhzc,bchpn->bzhpn", decay_chunk, states,
                        precision=HIGHEST)[:, :-1]
    # 4. states to outputs
    out_decay = jnp.exp(a_cum).transpose(0, 2, 3, 1)  # (B,C,L,H)
    y_off = ein("bclhn,bchpn->bclhp", c, states) * out_decay[..., None]
    return (y_diag + y_off).reshape(bs, length, h, p)


def logits(params, tokens, cfg: dict, mm: str = "f32", remat: bool = False):
    """Logits (B, S, vocab) in float32 for tokens (B, S)."""
    d, d_in, h, p, g, n, _ = _dims(cfg)
    eps, v, w = cfg["rms_norm_eps"], cfg["vocab_size"], cfg["d_conv"]
    cast = _cast(mm)

    def dot(a, b):
        return jnp.dot(cast(a), cast(b), precision=HIGHEST,
                       preferred_element_type=jnp.float32)

    def ein(spec, a, b):
        return jnp.einsum(spec, cast(a), cast(b), precision=HIGHEST,
                          preferred_element_type=jnp.float32)

    bsz, s = tokens.shape

    def heads(t):                                    # (B,S,G*N)->(B,S,H,N)
        t = t.reshape(bsz, s, g, 1, n)
        return jnp.broadcast_to(t, (bsz, s, g, h // g, n)).reshape(
            bsz, s, h, n)

    def layer(x, prm):
        m = prm["mamba"]
        hn = _rms(x, prm["ln1"], eps)
        z = dot(hn, m["wz"])
        xbc = jnp.concatenate([dot(hn, m["wx"]), dot(hn, m["wb"]),
                               dot(hn, m["wc"])], -1)
        dt = dot(hn, m["wdt"])
        pad = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
        cw = m["conv_w"].astype(jnp.float32)
        conv = sum(pad[:, i:i + s] * cw[:, i] for i in range(w))
        xbc = jax.nn.silu(conv + m["conv_b"])
        xs = xbc[..., :d_in].reshape(bsz, s, h, p)
        bm = heads(xbc[..., d_in:d_in + g * n])
        cm = heads(xbc[..., d_in + g * n:])
        dt = jax.nn.softplus(dt + m["dt_bias"])      # (B,S,H)
        a = -jnp.exp(m["a_log"])
        y = _ssd(xs * dt[..., None], dt * a, bm, cm,
                 min(cfg["chunk_size"], s), ein)
        y = y + xs * m["d_skip"][:, None]
        y = y.reshape(bsz, s, d_in) * jax.nn.silu(z)
        y = _rms(y, m["gate_norm"], eps)
        return x + dot(y, m["out_proj"]), None

    if remat:
        layer = jax.checkpoint(layer)
    table = params["embed"][:v]
    x = table[tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, params["segments"][0][0])
    x = _rms(x, params["final_norm"], eps)
    return dot(x, table.T)


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward + backward FLOPs per token: 6 per matmul parameter (head
    included) plus 3x the forward SSD contractions of the chunked
    algorithm (chunk Q): C B^T per group at half (causal), the masked
    product with x at half, each chunk's state and its read-out."""
    d, d_in, h, p, g, n, layers = _dims(c)
    q = min(c["chunk_size"], seq)
    matmul = layers * (d * (2 * d_in + 2 * g * n + h) + d_in * d) \
        + d * c["vocab_size"]
    ssd = layers * (q * n * g + q * p * h + 2 * p * n * h + 2 * p * n * h)
    return 6.0 * matmul + 3.0 * ssd


def param_bytes(c: dict) -> int:
    return stored_bytes(layout(c))
