"""Open-loop chat traffic: independent users whose requests arrive on a
schedule, whatever the server is doing.

A mix file names ``rate_per_s``, the prompt ``buckets`` with their
``bucket_weights``, and the output-length law: log-normal with
``out_median`` and ``out_sigma``, rounded and clipped to
[``out_min``, ``out_max``]. Every request decodes greedily.

For a run of ``seconds`` the schedule holds K = round(rate * seconds)
requests. Their gaps are the exponential law's K quantiles (a Poisson
stream's gaps), their prompt lengths the buckets in the weights' exact
proportions, their output lengths the law's K quantiles; the seed only
shuffles each list. So every seed offers the same work in another order,
and runs differ by the order and not by how much was asked. The prompt
of request i is Philox keyed by the seed with counter (i, 7, 0, 0).
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

__all__ = ["Request", "schedule", "warmup_requests"]


@dataclass
class Request:
    idx: int
    arrival_s: float
    tokens: np.ndarray          # (prompt_len,) int32
    max_new: int


def _apportion(k: int, weights) -> list[int]:
    w = np.asarray(weights, float) / np.sum(weights)
    counts = np.floor(w * k).astype(int)
    rest = np.argsort(-(w * k - counts), kind="stable")
    counts[rest[:k - counts.sum()]] += 1
    return counts.tolist()


def schedule(mix: dict, seed: int, seconds: float, vocab: int,
             rate_per_s: float | None = None) -> list[Request]:
    rate = mix["rate_per_s"] if rate_per_s is None else rate_per_s
    k = max(1, round(rate * seconds))
    u = (np.arange(k) + 0.5) / k
    rng = np.random.Generator(np.random.Philox(key=seed))
    gaps = rng.permutation(-np.log1p(-u) / rate)
    lengths = rng.permutation(np.repeat(
        mix["buckets"], _apportion(k, mix["bucket_weights"])))
    z = np.array([NormalDist().inv_cdf(x) for x in u])
    outs = np.clip(np.round(mix["out_median"] * np.exp(mix["out_sigma"] * z)),
                   mix["out_min"], mix["out_max"]).astype(int)
    outs = rng.permutation(outs)
    arrivals = np.cumsum(gaps)
    reqs = []
    for i in range(k):
        prompt = np.random.Generator(np.random.Philox(
            key=seed, counter=[i, 7, 0, 0])).integers(
                0, vocab, int(lengths[i]), dtype=np.int32)
        reqs.append(Request(i, float(arrivals[i]), prompt, int(outs[i])))
    return reqs


def warmup_requests(mix: dict, seed: int, vocab: int) -> list[Request]:
    """One short request per bucket, so that every program the window
    uses has run once before it opens."""
    return [Request(-1 - j, 0.0, np.random.Generator(np.random.Philox(
        key=seed, counter=[j, 9, 0, 0])).integers(0, vocab, int(b),
                                                   dtype=np.int32), 2)
            for j, b in enumerate(mix["buckets"])]
