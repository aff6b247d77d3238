"""The layer scan's checkpoint policy (``ModelConfig.remat_policy``).

``nothing`` recomputes every layer's forward in the backward, ``dots``
keeps the matmul outputs, ``none`` checkpoints nothing; ``auto``, the
default, picks ``dots`` where :meth:`Model.saved_dot_bytes` fits
:data:`SAVED_DOTS_SHARE` of the device's memory (:meth:`Model.remat_for`).
The policy moves only what the backward reads from the forward, never
the arithmetic: loss and gradients agree under every policy.
"""
import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.model as model_mod
from repro.configs import smoke_config
from repro.models import build_model

TINY = {"dense": smoke_config("qwen2.5-3b"),
        "mamba2": smoke_config("mamba2-1.3b"),
        # Jamba's blocks in a period of two: attention with a dense MLP,
        # then Mamba with a MoE MLP
        "hybrid": smoke_config("jamba-v0.1-52b").scaled(
            n_layers=2, hybrid_period=2, hybrid_attn_pos=0)}
V5E_BYTES_LIMIT = 16_900_000_000     # what a v5e chip's memory_stats() says
CONFIGS = Path(__file__).resolve().parents[1] / "bench" / "configs"


@functools.cache
def _loss_and_grads(family: str, policy: str):
    """Loss and gradients of one micro-batch of 2 x 64 tokens."""
    cfg = TINY[family].scaled(remat_policy=policy)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, cfg.vocab)
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    return jax.jit(jax.value_and_grad(model.loss))(params, batch)


@pytest.mark.parametrize("policy", ["nothing", "dots", "none"])
@pytest.mark.parametrize("family", list(TINY))
def test_policy_keeps_loss_and_gradients(family, policy):
    """Each policy gives the loss and gradients of ``nothing`` within
    bf16 rounding (relative L2 per leaf)."""
    loss, grads = _loss_and_grads(family, policy)
    want_loss, want_grads = _loss_and_grads(family, "nothing")
    assert abs(float(loss) - float(want_loss)) < 1e-2 * abs(float(want_loss))
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        err = np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30)
        assert err < 2e-2, jax.tree_util.keystr(path)


# ------------------------------------------------------------------ #
# resolving "auto"                                                    #
# ------------------------------------------------------------------ #
def _cell_config(name: str, **kw):
    """The program's config of a benchmark configuration file, built as
    the benchmark builds it."""
    from bench import spec
    c = json.loads((CONFIGS / f"{name}.json").read_text())
    cfg = spec.module("adapters", c["model_type"]).program_config(c)
    return cfg.scaled(**kw) if kw else cfg


@pytest.fixture
def v5e_limit(monkeypatch):
    monkeypatch.setattr(model_mod, "device_bytes_limit",
                        lambda: V5E_BYTES_LIMIT)


def test_saved_bytes_of_the_qwen_cell(v5e_limit):
    """qwen2.5-3b-L4 at 4 x 1,024: the q, k, v, gate and up outputs, and
    the attention out-projection's, whose sum with the layer's input is
    the residual stream that the MLP's norm reads in the backward; in
    bf16 over 4 layers. About 0.87 GB, a fifth of the quarter: ``dots``."""
    cfg = _cell_config("qwen2.5-3b-L4")
    dh = cfg.resolved_head_dim
    features = ((cfg.n_heads + 2 * cfg.n_kv_heads) * dh + cfg.d_model
                + 2 * cfg.d_ff)
    model = build_model(cfg)
    assert model.saved_dot_bytes(4, 1024) == features * 2 * 4 * 1024 * 4
    assert model.saved_dot_bytes(4, 1024) == pytest.approx(0.872e9, rel=1e-3)
    assert model.remat_for(4, 1024) == "dots"


def test_saved_bytes_of_the_mamba_cell(v5e_limit):
    """mamba2-1.3b-L12 at 4 x 2,048: the z, x, B, C and dt projections in
    bf16 over 12 layers, about 1.67 GB: ``dots``. The out-projection's
    output is not kept: the next layer's input is the scan's own carry."""
    cfg = _cell_config("mamba2-1.3b-L12")
    s = cfg.ssm
    features = (2 * s.d_inner(cfg.d_model) + 2 * s.n_groups * s.d_state
                + s.n_heads(cfg.d_model))
    model = build_model(cfg)
    assert model.saved_dot_bytes(4, 2048) == features * 2 * 4 * 2048 * 12
    assert model.saved_dot_bytes(4, 2048) == pytest.approx(1.67e9, rel=5e-3)
    assert model.remat_for(4, 2048) == "dots"


def test_published_depth_recomputes(v5e_limit):
    """Qwen2.5-3B at its 36 layers would keep about 7.9 GB, past a
    quarter of the chip: every layer is recomputed."""
    model = build_model(_cell_config("qwen2.5-3b-L4", n_layers=36))
    assert model.saved_dot_bytes(4, 1024) > model_mod.SAVED_DOTS_SHARE \
        * V5E_BYTES_LIMIT
    assert model.remat_for(4, 1024) == "nothing"


def test_no_device_limit_recomputes(monkeypatch):
    """A backend that reports no memory limit resolves ``auto`` to
    ``nothing`` without counting."""
    def counted(self, batch, seq):
        raise AssertionError("saved bytes counted with no device limit")

    monkeypatch.setattr(model_mod, "device_bytes_limit", lambda: None)
    monkeypatch.setattr(model_mod.Model, "saved_dot_bytes", counted)
    model = build_model(_cell_config("mamba2-1.3b-L12"))
    assert model.remat_for(4, 2048) == "nothing"


def test_cpu_resolves_auto_to_nothing():
    """The CPU reports no memory limit, so on it every configuration
    keeps recomputing its layers, as before ``auto``."""
    if jax.default_backend() != "cpu":
        pytest.skip("the CPU backend's resolution")
    assert model_mod.device_bytes_limit() is None
    for cfg in TINY.values():
        assert build_model(cfg).remat_for(4, 64) == "nothing"


@pytest.mark.parametrize("policy", ["nothing", "dots", "none"])
def test_explicit_policy_passes_through(policy, v5e_limit):
    model = build_model(smoke_config("qwen2.5-3b").scaled(
        remat_policy=policy))
    assert model.remat_for(4, 64) == policy


def test_no_remat_is_none(v5e_limit):
    model = build_model(smoke_config("qwen2.5-3b").scaled(remat=False))
    assert model.remat_for(4, 64) == "none"


def test_auto_step_is_the_chosen_policys(v5e_limit):
    """The lowered step under ``auto`` is the one under the policy that
    ``remat_for`` resolves to at its shapes."""
    def lowered(policy):
        model = build_model(smoke_config("mamba2-1.3b").scaled(
            remat_policy=policy))
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        batch = {"tokens": jax.ShapeDtypeStruct((2, 64), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((2, 64), jnp.int32)}
        return jax.jit(jax.grad(model.loss)).lower(params, batch).as_text()

    auto = lowered("auto")
    assert auto == lowered("dots")
    assert auto != lowered("nothing")
