"""Qwen2 (Qwen2.5): the plain reference, its parameter layout, and the
operations and bytes its shapes imply.

The reference follows the published architecture (Qwen2ForCausalLM):
RMSNorm before attention and MLP, GQA with a bias on q, k and v, rotary
embedding rotating the two halves of each head (theta from the config),
causal softmax attention scaled by head_dim ** -0.5, a SwiGLU MLP, a
final RMSNorm and an output head tied to the embedding. It is written in
float32 (``mm="f32"``: every product at ``Precision.HIGHEST``) with no
cache, no chunking and no batching tricks, and imports nothing of the
program. ``mm="f8"`` rounds the forward operands of every product to
float8_e4m3fn (gradients pass through in float32):
the lower-precision control that the correctness limits must reject.

Parameters are the program's layer-stacked layout (``segments[0][0]``
holds every layer's block with a leading layer axis); the embedding is
stored padded to a multiple of 512 rows, and the reference reads only
the first ``vocab_size`` of them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.weights import Leaf, stored_bytes

__all__ = ["layout", "logits", "train_flops_per_token", "prefill_flops",
           "decode_bytes", "vocab", "param_bytes"]

HIGHEST = jax.lax.Precision.HIGHEST


def vocab(c: dict) -> int:
    return c["vocab_size"]


def _dims(c: dict):
    d, h, kv = (c["hidden_size"], c["num_attention_heads"],
                c["num_key_value_heads"])
    return d, h, kv, d // h, c["intermediate_size"], c["num_hidden_layers"]


def layout(c: dict) -> dict:
    d, h, kv, dh, f, n = _dims(c)
    v = c["vocab_size"]
    bf, f32 = "bfloat16", "float32"
    block = {
        "ln1": Leaf((n, d), f32, "norm", 0.1),
        "attn": {
            "wq": Leaf((n, d, h * dh), bf, "fan_in"),
            "wk": Leaf((n, d, kv * dh), bf, "fan_in"),
            "wv": Leaf((n, d, kv * dh), bf, "fan_in"),
            "wo": Leaf((n, h * dh, d), bf, "fan_in"),
            "bq": Leaf((n, h * dh), f32, "normal", 0.05),
            "bk": Leaf((n, kv * dh), f32, "normal", 0.05),
            "bv": Leaf((n, kv * dh), f32, "normal", 0.05),
        },
        "ln2": Leaf((n, d), f32, "norm", 0.1),
        "mlp": {
            "w_gate": Leaf((n, d, f), bf, "fan_in"),
            "w_up": Leaf((n, d, f), bf, "fan_in"),
            "w_down": Leaf((n, f, d), bf, "fan_in"),
        },
    }
    return {"embed": Leaf((-(-v // 512) * 512, d), bf, "normal", 0.02,
                          rows=v),
            "final_norm": Leaf((d,), f32, "norm", 0.1),
            "segments": [(block,)]}


def _mm(mode: str):
    """Matrix product (and einsum) in the reference's precision."""
    def cast(x):
        x = x.astype(jnp.float32)
        if mode != "f8":
            return x
        # forward operands rounded to float8_e4m3fn, gradients passed
        # through in float32 (an unscaled fp8 backward would flush most
        # gradients to zero and test nothing)
        low = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return x + jax.lax.stop_gradient(low - x)

    def dot(a, b):
        return jnp.dot(cast(a), cast(b), precision=HIGHEST,
                       preferred_element_type=jnp.float32)

    def ein(spec, a, b):
        return jnp.einsum(spec, cast(a), cast(b), precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    return dot, ein


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, theta):
    """x (B, S, H, dh): rotate the two halves by position * frequency."""
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(params, tokens, c: dict, mm: str = "f32", remat: bool = False):
    """Logits (B, S, vocab) in float32 for tokens (B, S)."""
    d, h, kv, dh, _, _ = _dims(c)
    eps, theta, v = c["rms_norm_eps"], c["rope_theta"], c["vocab_size"]
    dot, ein = _mm(mm)
    b, s = tokens.shape
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        a = p["attn"]
        hn = _rms(x, p["ln1"], eps)
        q = (dot(hn, a["wq"]) + a["bq"]).reshape(b, s, h, dh)
        k = (dot(hn, a["wk"]) + a["bk"]).reshape(b, s, kv, dh)
        val = (dot(hn, a["wv"]) + a["bv"]).reshape(b, s, kv, dh)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, h // kv, axis=2)
        val = jnp.repeat(val, h // kv, axis=2)
        scores = ein("bqhd,bkhd->bhqk", q, k) * dh ** -0.5
        scores = jnp.where(causal, scores, -jnp.inf)
        o = ein("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), val)
        x = x + dot(o.reshape(b, s, h * dh), a["wo"])
        m = p["mlp"]
        hn = _rms(x, p["ln2"], eps)
        x = x + dot(jax.nn.silu(dot(hn, m["w_gate"])) * dot(hn, m["w_up"]),
                    m["w_down"])
        return x, None

    if remat:
        layer = jax.checkpoint(layer)
    table = params["embed"][:v]
    x = table[tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, params["segments"][0][0])
    x = _rms(x, params["final_norm"], eps)
    return dot(x, table.T)


def _matmul_params(c: dict) -> int:
    d, h, kv, dh, f, n = _dims(c)
    per_layer = d * h * dh * 2 + d * kv * dh * 2 + 3 * d * f
    return n * per_layer + d * c["vocab_size"]


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward + backward FLOPs per token: 6 per matmul parameter (head
    included), plus QK^T and PV over causal keys counted at half (seq/2
    keys on average); recomputation under remat is not counted."""
    d, h, kv, dh, f, n = _dims(c)
    attn = n * 2 * 2 * h * dh * (seq / 2)
    return 6.0 * _matmul_params(c) + 3.0 * attn


def prefill_flops(c: dict, length: int) -> float:
    """Forward FLOPs a prompt of ``length`` tokens needs: every layer at
    every position, causal attention at half, and the output head at the
    last position only (the one whose logits pick the first token)."""
    d, h, kv, dh, f, n = _dims(c)
    head = d * c["vocab_size"]
    attn = n * 2 * 2 * h * dh * (length / 2)
    return length * (2.0 * (_matmul_params(c) - head) + attn) + 2.0 * head


def param_bytes(c: dict) -> int:
    return stored_bytes(layout(c))


def decode_bytes(c: dict, live_positions: int, active: int) -> float:
    """Bytes one decode step must move: every stored weight once, the
    keys and values of the live positions, and the new keys and values
    (bf16)."""
    d, h, kv, dh, f, n = _dims(c)
    per_pos = n * 2 * kv * dh * 2
    return float(param_bytes(c) + per_pos * (live_positions + active))
