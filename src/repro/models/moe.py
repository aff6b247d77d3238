"""Mixture-of-Experts FFN (DeepSeek V2/V3 + Jamba style).

TPU-native realization of expert parallelism under a fixed
(``pod``, ``data``, ``model``) mesh:

* routed experts are sharded over the ``model`` axis (EP);
* activations stay batch-sharded over the data axes and *replicated*
  over ``model`` (exactly the layout Megatron-style TP leaves them in);
* every model-rank routes the token block it already holds to its local
  experts through a **static-capacity sort-free dispatch** (cumsum
  position + scatter), computes the grouped GEMMs, and the partial
  outputs combine with one ``psum`` over ``model`` — the same collective
  the TP MLP would have issued, so EP costs no extra collective phase;
* shared experts are plain TP (ffn hidden sharded over ``model``) and
  ride the same psum.

This avoids GShard's (T, E, C) one-hot dispatch einsums entirely — those
cost O(T*E*C*d) MACs and at DeepSeek-V3 scale (E=256) would rival the
expert GEMMs themselves (we measured this; see EXPERIMENTS.md §Perf).

Two entry points:
  * :func:`moe_ffn_reference` — dense-dispatch oracle (tiny configs/tests);
  * :func:`moe_ffn` — the production path (requires mesh axes in scope via
    shard_map; falls back to the reference when no mesh is active).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


from .config import ModelConfig, MoEConfig

__all__ = ["route_topk", "moe_ffn_reference", "moe_ffn", "expert_ffn_local"]


def route_topk(x_flat: jax.Array, router_w: jax.Array, top_k: int):
    """Router: top-k softmax gating with renormalized weights.

    x_flat: (T, D); router_w: (D, E). Returns (idx (T,k) int32, w (T,k)).
    Router math in fp32 (routing decisions are precision-sensitive).
    """
    gates = jnp.dot(x_flat.astype(jnp.float32), router_w.astype(jnp.float32))
    top_vals, top_idx = jax.lax.top_k(gates, top_k)
    top_w = jax.nn.softmax(top_vals, axis=-1)
    return top_idx, top_w


def _swiglu_expert(h_in, w_gate, w_up, w_down):
    g = jnp.einsum("ecd,edf->ecf", h_in, w_gate)
    u = jnp.einsum("ecd,edf->ecf", h_in, w_up)
    h = jax.nn.silu(g) * u          # bf16 activation math (§Perf iter 5)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def expert_ffn_local(x_flat: jax.Array, top_idx: jax.Array, top_w: jax.Array,
                     experts: dict, e_first: int, e_local: int,
                     capacity: int) -> jax.Array:
    """Dispatch a token block to ``e_local`` local experts and combine.

    Static-shape scatter dispatch: each (token, k-slot) routed to a local
    expert gets a position inside that expert's capacity buffer via a
    cumulative count; overflow slots are dropped (capacity_factor slack
    keeps drops rare — matches Switch/GShard semantics).

    x_flat (T, D); experts' leaves (E_local, D, F). Returns the *partial*
    combine (T, D): contributions of local experts only (psum upstream).
    """
    t, d = x_flat.shape
    k = top_idx.shape[1]
    local = (top_idx >= e_first) & (top_idx < e_first + e_local)
    eid = jnp.where(local, top_idx - e_first, 0)            # (T, k)

    flat_eid = eid.reshape(-1)                              # (T*k,)
    flat_local = local.reshape(-1)
    flat_w = top_w.reshape(-1)
    token_of = jnp.repeat(jnp.arange(t), k)

    # position of each slot within its expert's buffer: running count
    onehot = (jax.nn.one_hot(flat_eid, e_local, dtype=jnp.int32)
              * flat_local[:, None].astype(jnp.int32))      # (T*k, E_loc)
    pos = jnp.cumsum(onehot, axis=0) - onehot               # exclusive
    slot_pos = jnp.sum(pos * onehot, axis=1)                # (T*k,)
    keep = flat_local & (slot_pos < capacity)

    dump = e_local * capacity                               # overflow row
    dest = jnp.where(keep, flat_eid * capacity + slot_pos, dump)

    buf = jnp.zeros((e_local * capacity + 1, d), x_flat.dtype)
    buf = buf.at[dest].set(x_flat[token_of])
    h = buf[:-1].reshape(e_local, capacity, d)

    y = _swiglu_expert(h, experts["w_gate"], experts["w_up"], experts["w_down"])
    y_flat = y.reshape(e_local * capacity, d)

    gathered = jnp.where(keep[:, None], y_flat[jnp.minimum(dest, dump - 1)], 0.0)
    combined = jnp.zeros((t, d), x_flat.dtype)
    combined = combined.at[token_of].add(
        gathered * flat_w[:, None].astype(x_flat.dtype))
    return combined


def moe_ffn_reference(x: jax.Array, p: dict, cfg: ModelConfig) -> jax.Array:
    """Dense-dispatch oracle: every expert computed for every token, masked
    combine. O(T * E * d * f) — only for tiny test configs."""
    moe = cfg.moe
    assert moe is not None
    b, s, d = x.shape
    x_flat = x.reshape(-1, d)
    top_idx, top_w = route_topk(x_flat, p["router"], moe.top_k)
    ex = p["experts"]
    # (E, T, F) for all experts
    g = jnp.einsum("td,edf->etf", x_flat, ex["w_gate"])
    u = jnp.einsum("td,edf->etf", x_flat, ex["w_up"])
    h = jax.nn.silu(g) * u
    y_all = jnp.einsum("etf,efd->etd", h, ex["w_down"])     # (E, T, D)
    combine = jnp.zeros((x_flat.shape[0], moe.n_experts), jnp.float32)
    combine = combine.at[jnp.arange(x_flat.shape[0])[:, None], top_idx].add(top_w)
    y = jnp.einsum("te,etd->td", combine.astype(x.dtype), y_all)
    y = y + _shared_ffn(x_flat, p)
    return y.reshape(b, s, d)


def _shared_ffn(x_flat: jax.Array, p: dict) -> jax.Array:
    if "shared" not in p:
        return jnp.zeros_like(x_flat)
    sh = p["shared"]
    g = jnp.dot(x_flat, sh["w_gate"])
    u = jnp.dot(x_flat, sh["w_up"])
    h = jax.nn.silu(g) * u
    return jnp.dot(h, sh["w_down"])


def moe_ffn(x: jax.Array, p: dict, cfg: ModelConfig,
            mesh: jax.sharding.Mesh | None = None,
            dp_axes: tuple[str, ...] = ("data",),
            ep_axis: str = "model") -> jax.Array:
    """Production MoE FFN. x: (B, S, D) batch-sharded over ``dp_axes`` and
    replicated over ``ep_axis``; routed experts sharded over ``ep_axis``.

    Without a mesh (unit tests, smoke configs) falls back to the dense
    reference — bitwise-comparable up to capacity drops.
    """
    if mesh is None or ep_axis not in mesh.axis_names:
        return moe_ffn_reference(x, p, cfg)

    moe = cfg.moe
    assert moe is not None
    ep = mesh.shape[ep_axis]
    assert moe.n_experts % ep == 0, (
        f"{moe.n_experts} experts not divisible by EP degree {ep}")
    e_local = moe.n_experts // ep

    # batch-shard over dp when divisible (train/prefill/decode batches);
    # replicate for tiny serve batches (long_500k: B=1)
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]
    batch_spec = tuple(dp_axes) if x.shape[0] % dp_size == 0 else None

    def body(xb, router_w, experts, shared):
        # xb: (B_loc, S, D) — replicated over ep_axis by in_spec
        b, s, d = xb.shape
        x_flat = xb.reshape(-1, d)
        t = x_flat.shape[0]
        capacity = max(8, int(moe.capacity_factor * t * moe.top_k
                              / moe.n_experts))
        top_idx, top_w = route_topk(x_flat, router_w, moe.top_k)
        rank = jax.lax.axis_index(ep_axis)
        y = expert_ffn_local(x_flat, top_idx, top_w, experts,
                             rank * e_local, e_local, capacity)
        if shared is not None:
            # shared experts are TP-sharded on hidden: partial contribution
            y = y + _shared_ffn(x_flat, {"shared": shared})
        y = jax.lax.psum(y, ep_axis)
        return y.reshape(b, s, d)

    shared = p.get("shared")
    x_spec = P(batch_spec, None, None)
    expert_specs = {k: P(ep_axis, None, None) for k in p["experts"]}
    args = [x, p["router"], p["experts"]]
    in_specs = [x_spec, P(None, None), expert_specs]
    if shared is not None:
        # shared experts: TP on the ffn hidden dim — w_down contracts over it
        in_specs.append({"w_gate": P(None, ep_axis), "w_up": P(None, ep_axis),
                         "w_down": P(ep_axis, None)})
        args.append(shared)
        fn = jax.shard_map(
            lambda a, b, c, dsh: body(a, b, c, dsh), mesh=mesh,
            in_specs=tuple(in_specs), out_specs=x_spec, check_vma=False)
    else:
        fn = jax.shard_map(
            lambda a, b, c: body(a, b, c, None), mesh=mesh,
            in_specs=tuple(in_specs), out_specs=x_spec, check_vma=False)
    return fn(*args)
