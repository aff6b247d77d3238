"""Serving cells: one ``ServeEngine`` under open-loop traffic.

Set-up makes the weights from the seed, builds the engine the cell's
file describes, compiles its programs (``warmup``) and runs one short
request per prompt bucket so that every program has run once. The
window then offers the mix's requests at their scheduled times: the
harness submits every request that is due, calls ``engine.step()`` while
there is work, and sleeps only when there is none. Time to first token
runs from a request's scheduled arrival; tokens are observed on the host
after each step.

Correctness: once the window has closed and the engine is freed, a
sample of the finished requests drawn from the seed, the one with most
served tokens among them, is run through the plain reference's full
forward over prompt and served tokens; the widest gap by which a served
token's logit lies below the reference's best at its position is
compared with the cell's limit (greedy decoding serves the best token up
to rounding).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import spec
from bench.device import annotate, memory_peak_bytes
from bench.weights import generate

__all__ = ["setup", "serve_window", "reference_gaps", "run"]


def setup(cell: dict, seed: int, log=None):
    """The warmed engine and its compile cache."""
    import jax

    from repro.data.pipeline import ServeRequest
    from repro.models import build_model
    from repro.serve import ExecutableCache, ServeEngine, pool_pages_for

    from bench.drivers.train import _check_params
    from bench.traffic.open_loop_chat import warmup_requests

    c, mix, eng = cell["config_spec"], cell["traffic_spec"], cell["engine"]
    cfg = spec.module("adapters", c["model_type"]).program_config(c)
    model_mod = spec.module("models", c["model_type"])
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = generate(model_mod.layout(c), seed)
    _check_params(jax.eval_shape(model.init, jax.random.PRNGKey(0)), params)
    jax.block_until_ready(params)
    t1 = time.perf_counter()
    buckets = tuple(mix["buckets"])
    cache = ExecutableCache()
    engine = ServeEngine(
        model, params, n_slots=eng["slots"], page_size=eng["page_size"],
        max_new=eng["max_new"], buckets=buckets, exec_cache=cache,
        n_pages=pool_pages_for(eng["slots"], max(buckets) + eng["max_new"],
                               eng["page_size"]))
    engine.warmup()
    t2 = time.perf_counter()
    for r in warmup_requests(mix, seed, model_mod.vocab(c)):
        engine.submit(ServeRequest(r.idx, r.tokens, r.max_new))
    engine.run()
    jax.block_until_ready(engine.pools)
    if log is not None:
        log(f"set-up: weights {t1 - t0:.1f} s, engine and programs "
            f"{t2 - t1:.1f} s, warm-up requests "
            f"{time.perf_counter() - t2:.1f} s")
    return engine, cache


def serve_window(engine, reqs, seconds: float) -> dict:
    """Offer ``reqs`` at their arrival times for ``seconds``."""
    from repro.data.pipeline import ServeRequest

    first, last, count, finished = {}, {}, {}, {}
    itl, late, decode_steps, queue = [], [], [], []
    by_id = {r.idx: r for r in reqs}
    i, n = 0, len(reqs)

    def observe(t, done):
        grown, keys = 0, 0
        seen = [(s.request.req_id, len(s.generated))
                for s in engine.slots if s is not None]
        seen += [(f.req_id, len(f.tokens)) for f in done]
        for rid, k in seen:
            if k <= count.get(rid, 0):
                continue
            if rid in first:
                itl.append(t - last[rid])
            else:
                first[rid] = t
            last[rid], count[rid] = t, k
            grown += 1
            keys += len(by_id[rid].tokens) + k - 1
        for f in done:
            finished[f.req_id] = f.tokens
        if grown:
            decode_steps.append((grown, keys))

    with annotate("window"):
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if now >= seconds:
                break
            with annotate("submit"):
                while i < n and reqs[i].arrival_s <= now:
                    r = reqs[i]
                    engine.submit(ServeRequest(r.idx, r.tokens, r.max_new))
                    late.append(now - r.arrival_s)
                    i += 1
            if engine.pending or engine.in_flight:
                with annotate("engine.step"):
                    done = engine.step()
                t = time.perf_counter() - t0
                observe(t, done)
                queue.append((t, engine.pending))
            else:
                nxt = reqs[i].arrival_s if i < n else seconds
                with annotate("wait_arrival"):
                    time.sleep(max(0.0, min(nxt, seconds)
                                   - (time.perf_counter() - t0)))
        end = time.perf_counter() - t0
    due = [r for r in reqs if r.arrival_s < end]
    ttft = [first[r.idx] - r.arrival_s if r.idx in first
            else end - r.arrival_s for r in due]
    return {"t0": t0, "window_s": end, "due": due, "ttft_s": ttft,
            "itl_s": itl, "late_s": late, "finished": finished,
            "decode_steps": decode_steps, "queue": queue,
            "prefills": [len(r.tokens) for r in due if r.idx in first]}


def _sample(w: dict, by_id: dict, seed: int, k: int) -> list[int]:
    """Up to ``k`` finished requests drawn from the seed, the one with
    most served tokens always among them."""
    ids = sorted(w["finished"])
    if not ids:
        return []
    longest = max(ids, key=lambda r: (len(w["finished"][r]),
                                      len(by_id[r].tokens)))
    rng = np.random.Generator(np.random.Philox(key=seed, counter=[0, 11, 0,
                                                                  0]))
    rest = [r for r in ids if r != longest]
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


def reference_gaps(cell: dict, seed: int, items, mm: str = "f32",
                   judge_mm: str | None = None) -> list[float]:
    """For each (prompt, served tokens): the widest gap, in the float32
    reference's logits, between its best token and the served one at
    each served position. With ``judge_mm`` set, the served tokens are
    not used: the tokens that a ``judge_mm`` reference puts first are
    judged instead (the lower-precision control)."""
    import jax
    import jax.numpy as jnp

    c, mix, eng = cell["config_spec"], cell["traffic_spec"], cell["engine"]
    model_mod = spec.module("models", c["model_type"])
    params = generate(model_mod.layout(c), seed)
    total = max(mix["buckets"]) + eng["max_new"]

    @jax.jit
    def gaps(params, toks, lo, n):
        pos = jnp.arange(total - 1)
        sel = (pos >= lo - 1) & (pos < lo - 1 + n)
        ref = model_mod.logits(params, toks[None, :-1], c, mm=mm)[0]
        if judge_mm is None:
            chosen = toks[1:]
        else:
            low = model_mod.logits(params, toks[None, :-1], c,
                                   mm=judge_mm)[0]
            chosen = jnp.argmax(low, axis=-1)
        got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
        return jnp.max(jnp.where(sel, jnp.max(ref, axis=-1) - got, 0.0))

    out = []
    for prompt, served in items:
        seq = np.zeros(total, np.int32)
        body = np.concatenate([prompt, served])
        seq[:len(body)] = body
        out.append(float(gaps(params, jnp.asarray(seq), len(prompt),
                              len(served))))
    return out


def run(ctx) -> dict:
    import jax

    from bench.correct import judge
    from bench.traffic.open_loop_chat import schedule

    cell, seed = ctx.cell, ctx.seed
    c = cell["config_spec"]
    vocab = spec.module("models", c["model_type"]).vocab(c)
    engine, cache = setup(cell, seed, ctx.log)
    frozen = cache.misses
    reqs = schedule(cell["traffic_spec"], seed, ctx.seconds, vocab)
    with ctx.profiled() as traced:
        w = serve_window(engine, reqs, ctx.seconds)
    jax.block_until_ready(engine.pools)
    late = np.asarray(w["late_s"] or [0.0])
    ctx.log(f"window: {len(w['due'])} requests due, {len(w['finished'])} "
            f"finished, {len(w['decode_steps'])} steps; generator lateness "
            f"p50 {float(np.percentile(late, 50))!r} s, p95 "
            f"{float(np.percentile(late, 95))!r} s, max {float(late.max())!r} s; "
            f"compiles after warm-up {cache.misses - frozen}")
    rec = {
        "setup_s": w["t0"] - ctx.t_start,
        "window_s": w["window_s"],
        "attempted": len(w["due"]),
        "failed": 0,
        "ttft_s": w["ttft_s"],
        "itl_s": w["itl_s"] or [0.0],
        "decode_steps": w["decode_steps"],
        "prefills": w["prefills"],
        "memory_peak_bytes": memory_peak_bytes(ctx.devices),
        "trace_files": traced,
    }
    by_id = {r.idx: r for r in reqs}
    picked = _sample(w, by_id, seed, cell["sample_requests"])
    items = [(by_id[r].tokens, np.asarray(w["finished"][r]))
             for r in picked]
    del engine, cache
    gc.collect()
    t_ref = time.perf_counter()
    gaps = reference_gaps(cell, seed, items)
    ctx.log(f"reference: {len(items)} requests, "
            f"{sum(len(s) for _, s in items)} served tokens, gaps {gaps} "
            f"in {time.perf_counter() - t_ref:.1f} s")
    numbers = {"served_gap": max(gaps) if gaps else float("inf")}
    rec["correct"], rec["checks"] = judge(numbers, cell["limits"])
    return rec

