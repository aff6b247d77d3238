"""The causal GQA flash kernel on the attention path, checked on the CPU.

``attend_flash`` runs splash attention's MQA kernel; here in interpret
mode, against ``attend_chunked`` (the CPU path and the oracle), forward
and backward. ``flash_blocks`` decides where the model takes it: only on
the TPU, unpartitioned, at a head size of whole 128 lanes and a length
the block divides. The Mosaic compile of the same path for a v5e chip is
in ``test_tpu_compile.py``.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.ops as ops
import repro.models.attention as attention
from repro.models.attention import (_repeat_kv, attend_chunked, attend_flash,
                                    flash_blocks, gqa_forward)


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _chunked(q, k, v):
    g = q.shape[2] // k.shape[2]
    return attend_chunked(q, _repeat_kv(k, g), _repeat_kv(v, g),
                          chunk=q.shape[1])


def _attention_params(cfg, key):
    from repro.models.model import _init_attn
    return _init_attn(key, cfg)


def _blocks(s):
    """The kernel's blocks as the model picks them on the TPU."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "on_tpu", lambda: True)
        return flash_blocks(s, 128, partitioned=False)


# Relative L2 error allowed against attend_chunked. fp32: both paths
# compute the same sums in another order. bf16: attend_chunked rounds
# the scores to bf16 and the kernel does not; its extra error against an
# fp32 oracle may pass the jnp path's own by at most BF16_SLACK.
FP32_TOL = 1e-5
BF16_TOL = 2e-2
BF16_SLACK = 1.5

# each interpreted kernel compiles for 2-3 s on a CPU: two cases cover
# both lengths, both group sizes (8 is the training cell's) and both dtypes
CASES = [pytest.param(256, 8, jnp.bfloat16, id="s256-g8-bf16"),
         pytest.param(512, 2, jnp.float32, id="s512-g2-fp32")]


@pytest.mark.parametrize("s,g,dtype", CASES)
def test_flash_kernel_matches_attend_chunked(s, g, dtype, monkeypatch):
    """Output, gradients of q, k, v, and gradients through
    ``gqa_forward``'s weights (batch 2, 2 KV heads, head 128)."""
    from repro.configs import smoke_config
    b, kv, dh = 2, 2, 128
    h = kv * g
    ks = jax.random.split(jax.random.PRNGKey(s + g), 8)
    q = jax.random.normal(ks[0], (b, s, h, dh), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, dh), dtype)
    v = jax.random.normal(ks[2], (b, s, kv, dh), dtype)
    ct = jax.random.normal(ks[3], (b, s, h, dh), jnp.float32)
    flash = partial(attend_flash, interpret=True)
    core = partial(flash, blocks=_blocks(s))

    def with_grads(core, *args):
        def loss(*a):
            out = core(*a)
            return jnp.sum(out.astype(jnp.float32) * ct), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(*args)
        return (out, *grads)

    got = with_grads(core, q, k, v)
    want = with_grads(_chunked, q, k, v)
    if dtype == jnp.float32:
        for x, y in zip(got, want):
            assert _rel(x, y) < FP32_TOL
    else:
        f32 = lambda t: t.astype(jnp.float32)       # noqa: E731
        truth = with_grads(_chunked, f32(q), f32(k), f32(v))
        for x, y, t in zip(got, want, truth):
            assert _rel(x, y) < BF16_TOL
            assert _rel(x, t) < BF16_SLACK * _rel(y, t) + 1e-3

    # the same through gqa_forward's projections: the kernel path taken
    # as on the TPU (interpreted here)
    cfg = smoke_config("qwen2.5-3b").scaled(
        d_model=256, n_heads=h, n_kv_heads=kv, head_dim=dh)
    p = jax.tree.map(lambda w: w.astype(dtype),
                     _attention_params(cfg, ks[4]))
    x = jax.random.normal(ks[5], (b, s, cfg.d_model), dtype)
    y_ct = jax.random.normal(ks[6], (b, s, cfg.d_model), jnp.float32)

    def loss(p, x):
        return jnp.sum(gqa_forward(x, p, cfg).astype(jnp.float32) * y_ct)

    def grad():     # a new function each time: traced anew
        return jax.jit(jax.grad(loss, argnums=(0, 1)))

    want = grad()(p, x)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(attention, "attend_flash", flash)
    got = grad()(p, x)
    tol = FP32_TOL if dtype == jnp.float32 else BF16_TOL
    for name in ("wq", "wk", "wv", "wo", "bq", "bk", "bv"):
        assert _rel(got[0][name], want[0][name]) < tol, name
    assert _rel(got[1], want[1]) < tol


# ------------------------------------------------------------------ #
# where the model takes the kernel                                    #
# ------------------------------------------------------------------ #
def _cell_model(**kw):
    """qwen2.5-3b's attention widths at 4 layers, tiny elsewhere."""
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("qwen2.5-3b").scaled(n_layers=4, d_ff=256, vocab=512)
    return build_model(cfg, **kw)


def test_flash_chosen_for_the_training_cell_on_tpu(monkeypatch):
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    blocks = flash_blocks(1024, 128, partitioned=False)
    assert blocks is not None
    assert 1024 % blocks.block_q == 0 and 1024 % blocks.block_kv == 0
    assert _cell_model().flash_layers(1024) == 4


@pytest.mark.parametrize("why", ["mesh", "partitioned", "head64",
                                 "ragged-seq", "short-seq", "cpu"])
def test_flash_refused(why, monkeypatch):
    if why != "cpu":
        monkeypatch.setattr(ops, "on_tpu", lambda: True)
    s, dh, partitioned = 1024, 128, False
    if why == "mesh":
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                                 ("data", "model"))
        assert _cell_model(mesh=mesh).flash_layers(s) == 0
        partitioned = True          # what the mesh hands gqa_forward
    if why == "partitioned":
        import dataclasses
        model = dataclasses.replace(_cell_model(), partitioned=True)
        assert model.flash_layers(s) == 0
        partitioned = True
    if why == "head64":
        dh = 64
    if why == "ragged-seq":
        s = 1000
    if why == "short-seq":
        s = 96
    assert flash_blocks(s, dh, partitioned) is None


def test_gqa_forward_keeps_attend_chunked_on_cpu(monkeypatch):
    """On the CPU the kernel is never reached."""
    from repro.configs import smoke_config

    def boom(*a, **k):
        raise AssertionError("flash kernel taken on the CPU")

    monkeypatch.setattr(attention, "attend_flash", boom)
    cfg = smoke_config("qwen2.5-3b").scaled(d_model=256, n_heads=2,
                                            n_kv_heads=1, head_dim=128)
    p = _attention_params(cfg, jax.random.PRNGKey(0))
    x = jnp.ones((1, 256, cfg.d_model), jnp.float32)
    assert gqa_forward(x, p, cfg).shape == x.shape


def _trainer(tel, seq=32):
    from repro.configs import smoke_config
    from repro.train.trainer import SpareTrainer
    cfg = smoke_config("qwen2.5-3b").scaled(
        d_model=256, n_heads=2, n_kv_heads=1, head_dim=128, grad_accum=1)
    return SpareTrainer(cfg, n_groups=4, redundancy=2, seq=seq,
                        per_type_batch=1, total_steps=100, telemetry=tel)


def test_attention_kernel_layers_gauge(monkeypatch):
    """The gauge reads 0 on the CPU trainer's step; with the TPU's choice
    it counts the model's attention layers (2 here); the trainer without
    telemetry never asks. The layer scan's remat gauges read 0 on the
    CPU, which reports no memory limit; with a chip's limit the step
    keeps its matmul outputs in both layers, and reports their bytes."""
    import repro.models.model as model_mod
    from repro.models.model import Model
    from repro.obs.trace import Telemetry

    tel = Telemetry(trace=False)
    tr = _trainer(tel)
    tr.run(1)
    gauges = tel.snapshot()["gauges"]
    assert gauges["train.attention_kernel_layers"] == 0
    assert gauges["train.remat_saved_bytes"] == 0
    assert gauges["train.remat_dots_layers"] == 0

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(model_mod, "device_bytes_limit",
                        lambda: 16_900_000_000)
    tr = _trainer(Telemetry(trace=False), seq=512)
    tr._count_compile(None)
    gauges = tr.telemetry.snapshot()["gauges"]
    assert gauges["train.attention_kernel_layers"] == 2
    assert gauges["train.remat_dots_layers"] == 2
    # q, k, v, the out-projection, gate and up of 4 x 512 tokens in bf16
    features = 2 * 128 + 2 * 128 + 256 + 2 * tr.cfg.d_ff
    assert gauges["train.remat_saved_bytes"] == \
        tr.model.saved_dot_bytes(4, 512) == features * 2 * 4 * 512 * 2

    def asked(self, seq):
        raise AssertionError("flash_layers called without telemetry")

    monkeypatch.setattr(ops, "on_tpu", lambda: False)
    monkeypatch.setattr(Model, "flash_layers", asked)
    tr = _trainer(None)
    tr._compiled(tr.state.s_a)      # builds the step program: no gauge
    assert tr.telemetry is None
