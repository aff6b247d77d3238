"""Training traffic: the SPARe job's schedule and its tokens.

A mix file names ``n_groups`` (N data-parallel groups, one shard type
each), ``redundancy`` (r, stacks hosted per group), ``per_type_batch``
and ``seq`` (each shard type is per_type_batch sequences of seq tokens a
step), ``kill`` (the groups killed before the measured window, masked by
the program's recovery so that S_A reaches ``s_a``), ``checked_steps``
(the first steps, which the plain reference repeats) and ``optimizer``
(AdamW with global-norm clipping and a linear-warmup cosine schedule,
as the job states it).

The tokens of shard type i at step t are a pure function of (seed, i, t):
Philox keyed by the seed with counter (i, t, 0, 0), drawn uniformly over
the vocabulary. This is the same rule as the program's own input path
(``repro.data.ShardedTokenPipeline``), written out here so that the
reference builds vanilla data-parallel batches without the program.
"""
from __future__ import annotations

import math

import numpy as np

__all__ = ["shard", "vanilla_batch", "useful_tokens", "computed_tokens",
           "learning_rate"]


def shard(seed: int, shard_type: int, step: int, per_type_batch: int,
          seq: int, vocab: int) -> np.ndarray:
    """Tokens (per_type_batch, seq + 1) of one shard type at one step."""
    rng = np.random.Generator(np.random.Philox(
        key=seed, counter=[shard_type, step, 0, 0]))
    return rng.integers(0, vocab, (per_type_batch, seq + 1), dtype=np.int32)


def vanilla_batch(mix: dict, seed: int, step: int, vocab: int) -> np.ndarray:
    """Vanilla data parallelism's batch at ``step``: every shard type
    once, in type order. (N * per_type_batch, seq + 1)."""
    return np.concatenate([
        shard(seed, i, step, mix["per_type_batch"], mix["seq"], vocab)
        for i in range(mix["n_groups"])])


def useful_tokens(mix: dict) -> int:
    """Tokens a step trains on: vanilla DP's batch."""
    return mix["n_groups"] * mix["per_type_batch"] * mix["seq"]


def computed_tokens(mix: dict, s_a: int) -> int:
    """Tokens a stacked step at depth ``s_a`` runs through the model."""
    return s_a * useful_tokens(mix)


def learning_rate(opt: dict, update: int) -> float:
    """Rate of the ``update``-th update (1-based): linear warm-up, then
    cosine decay to ``min_frac`` of ``base_lr``."""
    if update < opt["warmup"]:
        return opt["base_lr"] * update / opt["warmup"]
    prog = min(max((update - opt["warmup"])
                   / max(opt["total_steps"] - opt["warmup"], 1), 0.0), 1.0)
    cos = opt["min_frac"] + (1 - opt["min_frac"]) * 0.5 * (
        1 + math.cos(math.pi * prog))
    return opt["base_lr"] * cos
