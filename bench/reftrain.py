"""The plain reference of a training cell's first steps.

Vanilla data parallelism, written out: every shard type's sequences
once a step, the loss the mean over them of each sequence's mean token
cross-entropy, its gradient in float32 (one sequence at a time, summed),
then AdamW as the mix's ``optimizer`` states it: global-norm clipping,
m and v in float32, bias correction, decoupled weight decay on arrays of
rank ``decay_min_rank`` or more as stored (the layer-stacked layout),
and each parameter rounded back to its stored dtype.

It reads the weights from the seed itself (``bench.weights``), the
tokens from the mix's rule (``traffic/train_schedule.py``), and nothing
of the program. ``mm="f8"`` runs the same steps with float8 forward
operands, the lower-precision control; ``rows="half"`` leaves the second
half of every batch out, a fault the comparison must catch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec
from bench.weights import generate, leaf_names, leaves

__all__ = ["reference_steps", "leaf_norms", "diff_norms"]


def _slice_rows(layout, tree):
    """Drop the rows that pad a table beyond the vocabulary."""
    return jax.tree.map(lambda leaf, x: x[:leaf.rows] if leaf.rows else x,
                        layout, tree,
                        is_leaf=lambda x: hasattr(x, "law"))


def leaf_norms(layout, tree, scale: float = 1.0) -> np.ndarray:
    """Float32 norm of every leaf (table padding left out), times
    ``scale``; one jitted call, one small host copy."""
    @jax.jit
    def norms(t):
        t = _slice_rows(layout, t)
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                          for x in jax.tree.leaves(t)])
    return np.asarray(norms(tree), np.float64) * scale


def diff_norms(layout, a, b) -> np.ndarray:
    """Norm of ``a - b`` per leaf, in float32 (table padding left out)."""
    @jax.jit
    def norms(x, y):
        x, y = _slice_rows(layout, x), _slice_rows(layout, y)
        return jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(p.astype(jnp.float32)
                                        - q.astype(jnp.float32))))
            for p, q in zip(jax.tree.leaves(x), jax.tree.leaves(y))])
    return np.asarray(norms(a, b), np.float64)


def reference_steps(cell: dict, seed: int, mm: str = "f32",
                    rows: str = "all") -> dict:
    """Losses of the checked steps, the first step's clipped gradient
    norm per leaf, and each leaf's change over the checked steps."""
    from bench.traffic.train_schedule import learning_rate, vanilla_batch

    c, mix = cell["config_spec"], cell["traffic_spec"]
    opt = mix["optimizer"]
    model = spec.module("models", c["model_type"])
    layout = model.layout(c)
    vocab = model.vocab(c)
    dtypes = [jnp.dtype(leaf.dtype) for leaf in leaves(layout)]
    treedef = jax.tree.structure(layout, is_leaf=lambda x: hasattr(x, "law"))

    def seq_loss(p32, row):
        lg = model.logits(p32, row[None, :-1], c, mm=mm, remat=True)[0]
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, row[1:, None], axis=-1)[:, 0]
        return jnp.mean(lse - picked)

    def step(params, m, v, batch, lr, t):
        p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
        zero = jax.tree.map(jnp.zeros_like, p32)

        def body(carry, row):
            loss, grad = jax.value_and_grad(seq_loss)(p32, row)
            return (carry[0] + loss,
                    jax.tree.map(jnp.add, carry[1], grad)), None
        (loss, grads), _ = jax.lax.scan(body, (jnp.float32(0), zero), batch)
        n = batch.shape[0]
        loss = loss / n
        grads = jax.tree.map(lambda g: g / n, grads)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, opt["clip_norm"] / (gnorm + 1e-12))
        b1, b2 = opt["b1"], opt["b2"]
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        flat_p, flat_g = jax.tree.leaves(p32), jax.tree.leaves(grads)
        flat_m, flat_v = jax.tree.leaves(m), jax.tree.leaves(v)
        new_p, new_m, new_v, seen = [], [], [], []
        for p, g, mi, vi, dt in zip(flat_p, flat_g, flat_m, flat_v, dtypes):
            g = g * scale
            mi = b1 * mi + (1 - b1) * g
            vi = b2 * vi + (1 - b2) * g * g
            upd = (mi / bc1) / (jnp.sqrt(vi / bc2) + opt["eps"])
            if p.ndim >= opt["decay_min_rank"] and opt["weight_decay"]:
                upd = upd + opt["weight_decay"] * p
            new_p.append((p - lr * upd).astype(dt))
            new_m.append(mi)
            new_v.append(vi)
            seen.append(g)
        un = jax.tree.unflatten
        return (un(treedef, new_p), un(treedef, new_m), un(treedef, new_v),
                loss, un(treedef, seen))

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    params = generate(layout, seed)
    m = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    v = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    losses, first_grad = [], None
    for t in range(mix["checked_steps"]):
        batch = vanilla_batch(mix, seed, t, vocab)
        if rows == "half":
            batch = batch[:batch.shape[0] // 2]
        params, m, v, loss, seen = step(
            params, m, v, jnp.asarray(batch),
            jnp.float32(learning_rate(opt, t + 1)), jnp.float32(t + 1))
        losses.append(float(loss))
        if t == 0:
            first_grad = leaf_norms(layout, seen)
        del seen
    del m, v
    change = diff_norms(layout, params, generate(layout, seed))
    return {"names": leaf_names(layout), "losses": losses,
            "grad": first_grad, "change": change}

