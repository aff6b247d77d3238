"""Pure-jnp oracle for the Pallas kernel (the ``assert_allclose`` ground
truth; deliberately naive and readable)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["int8_ef_ref"]


def int8_ef_ref(grad: jax.Array, error: jax.Array
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Int8 error-feedback quantization, fp32 throughout: the invariant
    ``q * scale + new_error == grad + error`` holds exactly.

    Returns ``(q int8, scale f32 scalar, new_error f32)``.
    """
    x = grad.astype(jnp.float32) + error.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x)) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(x / safe), -127.0, 127.0).astype(jnp.int8)
    return q, scale, x - q.astype(jnp.float32) * scale
