"""Unit + property tests for the MoE dispatch and SSD layers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                      # pragma: no cover
    from _hypothesis_compat import given, settings, st

from repro.models.config import ModelConfig, MoEConfig, SSMConfig
from repro.models.moe import expert_ffn_local, moe_ffn_reference, route_topk
from repro.models import ssm
from repro.models.ssm import ssd_chunked, ssd_decode_step

RNG = np.random.default_rng(0)


def _moe_cfg(e=8, k=2, d=16, fe=32, shared=0):
    return ModelConfig(
        name="t", family="moe", n_layers=1, d_model=d, n_heads=2,
        n_kv_heads=2, d_ff=0, vocab=64,
        moe=MoEConfig(n_experts=e, top_k=k, d_expert=fe, n_shared=shared))


def test_route_topk_properties():
    x = jnp.asarray(RNG.normal(size=(32, 16)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(16, 8)), jnp.float32)
    idx, wts = route_topk(x, w, 3)
    assert idx.shape == (32, 3) and wts.shape == (32, 3)
    np.testing.assert_allclose(np.asarray(wts).sum(-1), 1.0, atol=1e-6)
    # indices unique per token
    for row in np.asarray(idx):
        assert len(set(row.tolist())) == 3


def test_expert_dispatch_equals_dense_when_capacity_ample():
    """Sharded local dispatch (all experts local) == dense reference when
    nothing is dropped."""
    cfg = _moe_cfg()
    t, d = 24, 16
    x = jnp.asarray(RNG.normal(size=(t, d)), jnp.float32)
    router = jnp.asarray(RNG.normal(size=(d, 8)), jnp.float32)
    experts = {
        "w_gate": jnp.asarray(RNG.normal(size=(8, d, 32)) * 0.1, jnp.float32),
        "w_up": jnp.asarray(RNG.normal(size=(8, d, 32)) * 0.1, jnp.float32),
        "w_down": jnp.asarray(RNG.normal(size=(8, 32, d)) * 0.1, jnp.float32),
    }
    idx, wts = route_topk(x, router, 2)
    got = expert_ffn_local(x, idx, wts, experts, e_first=0, e_local=8,
                           capacity=t * 2)
    ref = moe_ffn_reference(x[None], {"router": router, "experts": experts},
                            cfg)[0]
    np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)


def test_expert_dispatch_partial_ranks_sum_to_whole():
    """EP invariant: sum of per-rank partial combines == full combine
    (this is what the psum over 'model' computes)."""
    t, d, e = 16, 8, 4
    x = jnp.asarray(RNG.normal(size=(t, d)), jnp.float32)
    router = jnp.asarray(RNG.normal(size=(d, e)), jnp.float32)
    experts = {
        "w_gate": jnp.asarray(RNG.normal(size=(e, d, 16)) * 0.1, jnp.float32),
        "w_up": jnp.asarray(RNG.normal(size=(e, d, 16)) * 0.1, jnp.float32),
        "w_down": jnp.asarray(RNG.normal(size=(e, 16, d)) * 0.1, jnp.float32),
    }
    idx, wts = route_topk(x, router, 2)
    full = expert_ffn_local(x, idx, wts, experts, 0, e, capacity=64)
    half = sum(
        expert_ffn_local(
            x, idx, wts,
            jax.tree.map(lambda a: a[r * 2:(r + 1) * 2], experts),
            e_first=r * 2, e_local=2, capacity=64)
        for r in range(2))
    np.testing.assert_allclose(half, full, atol=1e-5, rtol=1e-5)


def test_capacity_drop_bounded():
    """With capacity C, each expert processes <= C slots; dropped tokens
    produce zero contribution (never garbage)."""
    t, d, e = 64, 8, 2
    x = jnp.ones((t, d), jnp.float32)
    idx = jnp.zeros((t, 1), jnp.int32)          # all tokens -> expert 0
    wts = jnp.ones((t, 1), jnp.float32)
    experts = {
        "w_gate": jnp.ones((e, d, 4), jnp.float32),
        "w_up": jnp.ones((e, d, 4), jnp.float32),
        "w_down": jnp.ones((e, 4, d), jnp.float32),
    }
    out = expert_ffn_local(x, idx, wts, experts, 0, e, capacity=8)
    nonzero_rows = int((np.abs(np.asarray(out)).sum(-1) > 0).sum())
    assert nonzero_rows == 8                     # exactly capacity survived


# ------------------------------------------------------------------ #
# SSD                                                                 #
# ------------------------------------------------------------------ #
def test_ssd_chunked_equals_stepwise():
    b, s, h, p, n = 1, 64, 2, 8, 16
    x = jnp.asarray(RNG.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.1, (b, s, h)), jnp.float32)
    a_log = jnp.zeros((h,), jnp.float32)
    bb = jnp.asarray(RNG.normal(size=(b, s, h, n)), jnp.float32)
    cc = jnp.asarray(RNG.normal(size=(b, s, h, n)), jnp.float32)
    y_chunk, final = ssd_chunked(x, dt, a_log, bb, cc, chunk=16)

    state = jnp.zeros((b, h, p, n), jnp.float32)
    ys = []
    for t in range(s):
        y_t, state = ssd_decode_step(
            x[:, t], dt[:, t], a_log, bb[:, t], cc[:, t], state)
        ys.append(y_t)
    y_step = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(y_chunk, y_step, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(final, state, atol=2e-4, rtol=2e-4)


@given(st.integers(1, 4), st.sampled_from([16, 32, 64]))
@settings(max_examples=10, deadline=None)
def test_ssd_state_continuation(nchunks, chunk):
    """Splitting a sequence and feeding state0 across the split equals the
    unsplit scan (the decode/prefill handoff invariant)."""
    b, h, p, n = 1, 2, 4, 8
    s = nchunks * chunk
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, (b, s, h)), jnp.float32)
    a_log = jnp.zeros((h,), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    cc = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    y_full, st_full = ssd_chunked(x, dt, a_log, bb, cc, chunk=chunk)
    half = s // 2
    if half % chunk:
        return
    y1, st1 = ssd_chunked(x[:, :half], dt[:, :half], a_log,
                          bb[:, :half], cc[:, :half], chunk=chunk)
    y2, st2 = ssd_chunked(x[:, half:], dt[:, half:], a_log,
                          bb[:, half:], cc[:, half:], chunk=chunk,
                          state0=st1)
    np.testing.assert_allclose(
        jnp.concatenate([y1, y2], axis=1), y_full, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(st2, st_full, atol=1e-4, rtol=1e-4)


# The published init (A in [1, 16], dt in [0.001, 0.1]) at chunk 256: a
# head's decay summed over a chunk reaches 16 * 0.1 * 256 = 410, and
# exp(cum_i - cum_j) above the diagonal overflows fp32 past about 88.
OVERFLOW_DT = [0.05, 0.1]                # decay over the chunk: 205, 410


def _witness(dt_value, s=256, h=2, p=8, n=16):
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(1, s, h, p)), jnp.float32)
    dt = jnp.full((1, s, h), dt_value, jnp.float32)
    a_log = jnp.full((h,), np.log(16.0), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(1, s, h, n)), jnp.float32)
    cc = jnp.asarray(rng.normal(size=(1, s, h, n)), jnp.float32)
    ry = jnp.asarray(rng.normal(size=(1, s, h, p)), jnp.float32)
    rs = jnp.asarray(rng.normal(size=(1, h, p, n)), jnp.float32)
    return (x, dt, a_log, bb, cc), (ry, rs)


def _stepwise(x, dt, a_log, bb, cc):
    """The recurrence one token at a time (``ssd_decode_step``)."""
    state = jnp.zeros((x.shape[0], x.shape[2], x.shape[3], bb.shape[-1]),
                      jnp.float32)

    def step(state, t):
        y, state = ssd_decode_step(t[0], t[1], a_log, t[2], t[3], state)
        return state, y
    final, ys = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, bb, cc)))
    return jnp.moveaxis(ys, 0, 1), final


def _grads(fn, args, weights):
    ry, rs = weights

    def loss(*a):
        y, final = fn(*a)
        return jnp.sum(y * ry) + jnp.sum(final * rs)
    return jax.grad(loss, argnums=tuple(range(5)))(*args)


@pytest.mark.parametrize("dt_value", OVERFLOW_DT)
def test_ssd_chunked_backward_finite_at_published_decay(dt_value):
    """One chunk of 256 whose decay overflows exp above the diagonal:
    the gradients of x, dt, a_log, b and c are finite and equal the
    stepwise recurrence's within fp32 rounding.

    The chunked form takes each decay as a difference of cumulative
    sums that reach 410, whose fp32 spacing is 3e-5: gradients read at
    most 1e-4 of their largest element off the stepwise ones (da_log,
    a sum over every pair of positions), so 2e-4 of it is allowed."""
    args, weights = _witness(dt_value)
    chunked = _grads(lambda *a: ssd_chunked(*a, chunk=256), args, weights)
    stepwise = _grads(_stepwise, args, weights)
    for name, g, ref in zip(("x", "dt", "a_log", "b", "c"), chunked,
                            stepwise):
        assert bool(jnp.all(jnp.isfinite(g))), f"d{name} not finite"
        scale = float(jnp.max(jnp.abs(ref)))
        np.testing.assert_allclose(g, ref, rtol=0, atol=2e-4 * scale,
                                   err_msg=f"d{name}")


def _exp_then_mask(cum):
    """``ssm._segment_decay`` as it read before the log-decay was masked
    ahead of the exponential (``where(mask, exp(logl), 0)``)."""
    q = cum.shape[-1]
    mask = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    return jnp.where(mask, jnp.exp(cum[..., :, None] - cum[..., None, :]),
                     0.0)


@pytest.mark.parametrize("dt_value,chunk", [(0.01, 64), (0.05, 256),
                                            (0.1, 256)])
def test_ssd_chunked_forward_unchanged_by_the_mask(dt_value, chunk,
                                                   monkeypatch):
    """exp(-inf) is exactly 0: masking the log-decay first leaves the
    forward bit for bit as the exp-then-mask formula gave it, which is
    finite here."""
    args, _ = _witness(dt_value)
    y, final = ssd_chunked(*args, chunk=chunk)
    monkeypatch.setattr(ssm, "_segment_decay", _exp_then_mask)
    y_old, final_old = ssd_chunked(*args, chunk=chunk)
    assert bool(jnp.all(jnp.isfinite(y_old)))
    np.testing.assert_array_equal(y, y_old)
    np.testing.assert_array_equal(final, final_old)


def _group_inputs(g, s=64, h=4, p=8, n=16):
    """SSD inputs with B and C per group, (1, s, g, n), and A per head
    spread over the published init's [1, 16]."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(1, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, (1, s, h)), jnp.float32)
    a_log = jnp.asarray(np.log(np.linspace(1.0, 16.0, h)), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(1, s, g, n)), jnp.float32)
    cc = jnp.asarray(rng.normal(size=(1, s, g, n)), jnp.float32)
    state0 = jnp.asarray(rng.normal(size=(1, h, p, n)), jnp.float32)
    ry = jnp.asarray(rng.normal(size=(1, s, h, p)), jnp.float32)
    rs = jnp.asarray(rng.normal(size=(1, h, p, n)), jnp.float32)
    return (x, dt, a_log, bb, cc), state0, (ry, rs)


def _assert_same(got, want, what):
    """Equal within fp32 rounding: 1e-5 of the largest element."""
    for name, g, w in zip(("x", "dt", "a_log", "b", "c"), got, want):
        np.testing.assert_allclose(
            g, w, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(w))),
            err_msg=f"{what} d{name}")


@pytest.mark.parametrize("g", [1, 2])
def test_ssd_chunked_group_level_equals_head_broadcast(g):
    """B and C given per group (G = 1, 2 of H = 4 heads) give the same y,
    final state and gradients as the same B and C repeated to every head
    of their group (G = H), from a given state."""
    args, state0, weights = _group_inputs(g)
    h = args[0].shape[2]

    @jax.jit
    def per_head(x, dt, a_log, bb, cc):
        rep = lambda t: jnp.repeat(t, h // g, axis=2)
        return ssd_chunked(x, dt, a_log, rep(bb), rep(cc), chunk=16,
                           state0=state0)

    per_group = jax.jit(lambda *a: ssd_chunked(*a, chunk=16, state0=state0))

    y, final = per_group(*args)
    y_ref, final_ref = per_head(*args)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(final, final_ref, rtol=1e-5, atol=1e-6)
    _assert_same(_grads(per_group, args, weights),
                 _grads(per_head, args, weights), f"G={g}")


@pytest.mark.parametrize("with_state0", [False, True])
@pytest.mark.parametrize("budget,blocks", [(2 * 4096, 2), (4096, 4)])
def test_ssd_chunked_blocks_equal_one_block(budget, blocks, with_state0,
                                            monkeypatch):
    """A call whose (Q, Q) working set passes the budget runs its four
    chunks in blocks under a scan, and equals the one-block call forward
    and in every gradient."""
    args, state0, weights = _group_inputs(1)
    kw = {"chunk": 16, "state0": state0 if with_state0 else None}
    fn = lambda *a: ssd_chunked(*a, **kw)
    assert ssm.ssd_blocks(1, 64, 4, 16) == 1
    y, final = fn(*args)
    grads = _grads(fn, args, weights)

    # one chunk's working set is 1 * 4 * 16 * 16 * 4 = 4096 bytes
    monkeypatch.setattr(ssm, "QQ_BUDGET_BYTES", budget)
    assert ssm.ssd_blocks(1, 64, 4, 16) == blocks
    y_blk, final_blk = fn(*args)
    np.testing.assert_allclose(y_blk, y, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(final_blk, final, rtol=1e-5, atol=1e-6)
    _assert_same(_grads(fn, args, weights), grads, f"{blocks} blocks")


@pytest.mark.parametrize("arch,budget,blocks", [
    ("mamba2-1.3b", None, 1), ("mamba2-1.3b", 1, 2), ("qwen2.5-3b", None, 0)])
def test_ssd_chunk_blocks_gauge(arch, budget, blocks, monkeypatch):
    """The trainer's step reports how many blocks each SSD call runs its
    chunks in: 1 on a small Mamba-2 model, as many as its chunks where
    one chunk passes the budget, 0 on a dense model."""
    from repro.configs import smoke_config
    from repro.obs.trace import Telemetry
    from repro.train.trainer import SpareTrainer

    if budget is not None:
        monkeypatch.setattr(ssm, "QQ_BUDGET_BYTES", budget)
    tel = Telemetry(trace=False)
    tr = SpareTrainer(smoke_config(arch), n_groups=4, redundancy=2, seq=64,
                      per_type_batch=1, total_steps=100, telemetry=tel)
    tr.run(1)
    assert tel.snapshot()["gauges"]["train.ssd_chunk_blocks"] == blocks


@pytest.mark.parametrize("arch,dtype", [("mamba2-1.3b", jnp.float32),
                                        ("jamba-v0.1-52b", jnp.bfloat16),
                                        ("qwen2.5-3b", jnp.bfloat16)])
def test_residual_stream_dtype(arch, dtype):
    """Mamba-2's residual stream between blocks is fp32 as its config
    states (``residual_in_fp32``); Jamba's and the dense models' stay in
    the compute dtype. The head is fed in the compute dtype either way."""
    from repro.configs import smoke_config
    from repro.models import build_model
    cfg = smoke_config(arch)
    assert cfg.residual_fp32 is (dtype == jnp.float32)
    m = build_model(cfg)
    params = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    x = jax.eval_shape(lambda p, t: m._embed(p, t, None), params, tokens)
    assert x.dtype == dtype
    logits = jax.eval_shape(lambda p, t: m.forward(p, tokens=t), params,
                            tokens)
    assert logits.dtype == jnp.bfloat16
