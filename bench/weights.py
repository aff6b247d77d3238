"""Weights from the seed, made on the device in one jitted call.

A model module's ``layout(config)`` gives a pytree of :class:`Leaf`
descriptions in the program's parameter layout (the names and shapes its
``Model.init`` makes, layer-stacked). :func:`generate` turns it into
arrays in the stored dtypes; the same seed gives the same weights, so the
plain reference makes its own copy after the program's state is freed
instead of taking anything the program held.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Leaf", "generate", "leaves", "leaf_names", "seed_key",
           "stored_bytes"]


@dataclass(frozen=True)
class Leaf:
    """One parameter array: shape, stored dtype and how it is drawn.

    ``law``: ``fan_in`` (truncated normal, std = shape[-2] ** -0.5),
    ``normal`` (std ``scale``), ``norm`` (1 + ``scale`` * normal),
    ``a_log`` (log of uniform [1, 16], Mamba-2's A), ``dt_bias``
    (softplus-inverse of a log-uniform step in [1e-3, 0.1], Mamba-2's).
    ``rows`` > 0 keeps only the first ``rows`` rows non-zero (a table
    stored padded beyond the vocabulary).
    """

    shape: tuple
    dtype: str
    law: str
    scale: float = 0.0
    rows: int = 0


def _is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def leaves(layout):
    return jax.tree.leaves(layout, is_leaf=_is_leaf)


def leaf_names(layout) -> list[str]:
    """``keystr`` of every leaf, in ``jax.tree.leaves`` order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(layout, is_leaf=_is_leaf)
    return [jax.tree_util.keystr(path) for path, _ in flat]


def stored_bytes(layout) -> int:
    """Bytes of the weights in their stored dtypes, without the rows
    that pad a table beyond the vocabulary."""
    total = 0
    for leaf in leaves(layout):
        rows = leaf.rows or leaf.shape[0]
        total += rows * math.prod(leaf.shape[1:]) \
            * jnp.dtype(leaf.dtype).itemsize
    return total


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    key = jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(stream))


def _draw(key, leaf: Leaf) -> jax.Array:
    shape, f32 = tuple(leaf.shape), jnp.float32
    if leaf.law == "fan_in":
        x = jax.random.truncated_normal(key, -2.0, 2.0, shape, f32)
        x = x * shape[-2] ** -0.5
    elif leaf.law == "normal":
        x = jax.random.normal(key, shape, f32) * leaf.scale
    elif leaf.law == "norm":
        x = 1.0 + jax.random.normal(key, shape, f32) * leaf.scale
    elif leaf.law == "a_log":
        x = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    elif leaf.law == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, np.log(1e-3),
                                        np.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))
    else:
        raise ValueError(f"unknown law {leaf.law!r}")
    if leaf.rows:
        keep = jnp.arange(shape[0]) < leaf.rows
        x = jnp.where(keep.reshape((-1,) + (1,) * (len(shape) - 1)), x, 0.0)
    return x.astype(leaf.dtype)


def generate(layout, seed: int):
    """All weights of ``layout`` from ``seed``, in one jitted call."""
    flat, treedef = jax.tree.flatten(layout, is_leaf=_is_leaf)

    def make(key):
        return [_draw(jax.random.fold_in(key, i), leaf)
                for i, leaf in enumerate(flat)]

    arrays = jax.jit(make)(seed_key(seed))
    return jax.tree.unflatten(treedef, arrays)
