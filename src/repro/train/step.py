"""Device-side SPARe step functions.

``make_train_step(model)`` builds the jitted SPMD training step:

    (params, opt, batch) -> (params, opt, metrics)

``batch`` carries a leading *stack* axis (``S_A x grad_accum`` micro
steps). The SPARe failure-masking weights ride along as a per-example
weight vector — a dead group's slots weigh 0, the designated supplier of
each shard type weighs 1/N — so the accumulated gradient equals vanilla
DP's batch gradient for every survivor set (the §3.1 invariant; the
weighted psum over the data axis is issued by XLA from the same einsum it
would emit for plain DP: failure masking costs *zero* extra collectives).

The stack axis is scanned (gradient accumulation): activation memory is
one microbatch deep regardless of S_A, and a recompile happens only when
S_A itself changes (S_A in {1..4} in practice; each depth is compiled
once and cached).

Each part of the step runs under one ``jax.named_scope`` of
:data:`repro.obs.DEVICE_SCOPES` (op metadata only, no run-time cost):
the model's layers name themselves, the loss's fp32 cross-entropy is
``head``, the accumulator ``grad_accum``, the update ``optimizer``.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.dist.collectives import all_reduce_grads, weighted_all_reduce
from repro.models.model import Model
from repro.optim import adamw_update, cosine_lr

__all__ = ["weighted_loss", "make_train_step", "make_serve_step",
           "make_prefill"]


def weighted_loss(model: Model, params: Any, micro: dict,
                  axis_name: str | None = None) -> jax.Array:
    """Per-example-weighted CE over one microbatch.

    micro: tokens/embeds (b, S[, D]), labels (b, S), weights (b,).
    Returns sum_b weights[b] * mean-CE(example b). With SPARe weights this
    sums to (1/N) * sum-over-types of per-type mean loss == vanilla DP loss.

    The supplier-weighted reduction routes through
    :func:`repro.dist.collectives.weighted_all_reduce` — the single place
    the §3.1 weighted all-reduce is issued. Host-side (the emulated
    trainer) it is a weighted contraction; on a real mesh pass
    ``axis_name`` and it additionally psums across the data axis.
    """
    logits = model.forward(params, tokens=micro.get("tokens"),
                           embeds=micro.get("embeds"))
    with jax.named_scope("head"):
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, micro["labels"][..., None],
                                     axis=-1)[..., 0]
        ce = jnp.mean(lse - picked, axis=-1)       # (b,) per-example mean
        return weighted_all_reduce(ce, micro["weights"],
                                   axis_name=axis_name)


def make_train_step(model: Model, *, base_lr: float = 3e-4,
                    warmup: int = 100, total_steps: int = 10_000,
                    weight_decay: float = 0.1, clip_norm: float = 1.0,
                    grad_shardings=None, axis_name: str | None = None,
                    grad_sync=None):
    """Build the pure train_step; caller jits with shardings.

    ``grad_shardings`` (pytree of NamedSharding matching params) pins the
    gradient accumulator to the parameter sharding — without it GSPMD
    replicates the fp32 accumulator and all-reduces the *full* gradient
    every microbatch (measured +300 GiB/step of all-reduce on a 3B model);
    with it the backward lowers to reduce-scatters into the shard.

    ``axis_name`` is the ``shard_map`` spelling (the mesh executor):
    each device computes its *local* supplier-weighted partial gradient
    over its slice of the stacked batch, and the accumulated partials
    are psummed ONCE per step after the microbatch scan — the §3.1
    weighted all-reduce. Because the masking weights ride in the batch,
    a failure re-weight changes neither the program nor its collectives.

    ``grad_sync`` replaces the default per-leaf
    :func:`~repro.dist.collectives.all_reduce_grads` with a custom
    post-scan reduction — :class:`~repro.dist.collectives
    .BucketedAllReduce` (O(1) flat-bucket psums) or
    :class:`~repro.dist.collectives.CompressedBucketSync` (int8 EF over
    the wire). A *stateful* sync (``grad_sync.stateful``) changes the
    step signature to ``(params, opt, batch, ef_state) -> (params, opt,
    metrics, ef_state)``: the error-feedback residuals are device-local
    sharded state the caller threads (and snapshots) alongside params.
    """

    def micro_grads(params, micro):
        return jax.value_and_grad(partial(weighted_loss, model))(
            params, micro, axis_name=axis_name)

    acc_dtype = jnp.dtype(model.cfg.grad_accum_dtype)
    stateful = getattr(grad_sync, "stateful", False)

    def accumulate(params, batch):
        # batch leaves: (n_micro, b, ...) — scan-accumulate gradients
        with jax.named_scope("grad_accum"):
            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dtype), params)
            if grad_shardings is not None:
                zero = jax.tree.map(jax.lax.with_sharding_constraint, zero,
                                    grad_shardings)

        def acc(carry, micro):
            loss_acc, g_acc = carry
            loss, g = micro_grads(params, micro)
            if grad_shardings is not None:
                # pin the per-microbatch gradient too: the accumulator
                # constraint alone still lets GSPMD all-reduce each micro
                # gradient to replicated form before the (sharded) add
                g = jax.tree.map(jax.lax.with_sharding_constraint, g,
                                 grad_shardings)
            with jax.named_scope("grad_accum"):
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(acc_dtype), g_acc, g)
            return (loss_acc + loss, g_acc), None

        (loss, grads), _ = jax.lax.scan(acc, (jnp.zeros((), jnp.float32), zero),
                                        batch)
        return loss, grads

    @jax.named_scope("optimizer")
    def update(params, opt_state, loss, grads):
        # step+1: opt.step counts *completed* updates; lr(0)=0 would make
        # the first update a silent no-op
        lr = cosine_lr(opt_state.step + 1, base_lr, warmup, total_steps)
        params, opt_state, gnorm = adamw_update(
            grads, opt_state, params, lr,
            weight_decay=weight_decay, clip_norm=clip_norm)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        return params, opt_state, metrics

    def train_step(params, opt_state, batch):
        loss, grads = accumulate(params, batch)
        if grad_sync is not None:
            # the one gradient sync of the step, bucketed: O(1) psums
            # (or the compressed int8-EF wire protocol via train_step_ef)
            grads = grad_sync(grads)
        elif axis_name is not None:
            # per-leaf spelling: sum the accumulated (already
            # supplier-weighted) partials across the data axis
            grads = all_reduce_grads(grads, axis_name)
        return update(params, opt_state, loss, grads)

    def train_step_ef(params, opt_state, batch, ef_state):
        loss, grads = accumulate(params, batch)
        grads, ef_state = grad_sync(grads, ef_state)
        return (*update(params, opt_state, loss, grads), ef_state)

    return train_step_ef if stateful else train_step


def make_serve_step(model: Model, *, paged: bool = False):
    """One-token decode step. Greedy sampling left to the caller.

    Default (dense): ``(params, state, pos, tokens/embeds) ->
    (next_token_logits, new_state)`` with scalar ``pos`` — every row at
    the same position (the dry-run/analyze spelling).

    ``paged=True``: ``(params, state, table, pos, tokens/embeds)`` with
    ``table (B, max_pages)`` page ids and ``pos (B,)`` per-row positions
    over :meth:`Model.init_paged_state` pools — the continuous-batching
    spelling (``repro.serve.engine``), where admission/eviction are pure
    data and the step compiles exactly once.
    """
    if paged:
        def serve_step_paged(params, state, table, pos,
                             tokens=None, embeds=None):
            logits, new_state = model.decode_step_paged(
                params, state, table, pos, tokens=tokens, embeds=embeds)
            return logits[:, -1, :], new_state

        return serve_step_paged

    def serve_step(params, state, pos, tokens=None, embeds=None):
        logits, new_state = model.decode_step(
            params, state, pos, tokens=tokens, embeds=embeds)
        return logits[:, -1, :], new_state

    return serve_step


def make_prefill(model: Model, *, return_cache: bool = False):
    """Batched prefill.

    Default: run the full prompt through the train forward and return
    last-position logits only (the dry-run lowers this exact
    computation; no cache materializes).

    ``return_cache=True``: the fused cache-filling prefill —
    ``(params, tokens/embeds) -> (all_logits (B, S, V), state)`` where
    ``state`` matches :meth:`Model.init_decode_state` leaf for leaf, so
    decode can continue from position S without re-running the prompt
    token by token. Prompts must be exact-length (no right-padding): the
    SSM recurrence runs through every input token.
    """
    if return_cache:
        def prefill_cached(params, tokens=None, embeds=None):
            return model.prefill(params, tokens=tokens, embeds=embeds)

        return prefill_cached

    def prefill(params, tokens=None, embeds=None):
        logits = model.forward(params, tokens=tokens, embeds=embeds)
        return logits[:, -1, :]

    return prefill
