"""Model builder: config -> (init, loss, decode) pure functions.

Layer layout is expressed as *segments*: ``(pattern, n_rep)`` where
``pattern`` is a tuple of block kinds executed in order and the segment
repeats ``n_rep`` times under one ``lax.scan`` (per-kind parameter stacks
carry the leading ``n_rep`` axis). This keeps compile time independent of
depth while representing every assigned family:

  dense / ssm            [(single-kind,), L]
  deepseek moe           [(attn_dense,), k] + [(attn_moe,), L-k]
  jamba hybrid           [(attn_moe, mamba_dense, mamba_moe, ...), L/8]

Decode threads per-layer caches through the same scans (cache stacks are
the scanned xs/ys; the hidden state is the carry).

The layer scan is checkpointed under ``cfg.remat_policy``; its default
``auto`` keeps each layer's matmul outputs for the backward where they
fit the device's memory (:meth:`Model.remat_for`), else recomputes them.

Each layer runs under a ``jax.named_scope`` of
:data:`repro.obs.DEVICE_SCOPES` (``embed``, ``attention``/``ssm``,
``mlp``/``moe``, ``head``; ``ssm`` holds the SSD scan's ``ssd``), which
names its device operations in a profiler trace at no run-time cost.

The residual stream between blocks is fp32 where ``cfg.residual_fp32``
(Mamba-2's ``residual_in_fp32``), else the compute dtype; every block
and the head are fed in the compute dtype.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp

from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod
from .config import ModelConfig
from .layers import ACT_DTYPE, cross_entropy, embed_lookup, init_linear, rmsnorm

__all__ = ["Model", "build_model", "segments_of", "device_bytes_limit",
           "SAVED_DOTS_SHARE"]

# block kind's mixer -> its device scope (repro.obs.DEVICE_SCOPES)
_MIXER_SCOPE = {"attn": "attention", "mamba": "ssm"}

# checkpoint policy of the layer scan -> what its backward reads from
# the forward instead of recomputing ("none": no checkpoint at all)
_REMAT_POLICIES = {
    "nothing": jax.checkpoint_policies.nothing_saveable,
    # keep matmul outputs; recompute only cheap elementwise
    "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}

# The most of a device's memory that ``remat_policy="auto"`` lets the
# layer scan keep as matmul outputs. The rest holds what the step needs
# under either policy: the training state (16 bytes a parameter: bf16
# weights and micro-gradient, the fp32 accumulator, Adam's m and v) and
# the step's working set, and XLA's temporaries grow by up to about the
# kept bytes again when the outputs are kept.
SAVED_DOTS_SHARE = 0.25


def device_bytes_limit() -> int | None:
    """Bytes of memory the first local device lets this process use, or
    None where its backend reports no limit (the CPU)."""
    stats = jax.local_devices()[0].memory_stats()
    return stats.get("bytes_limit") if stats else None


def segments_of(cfg: ModelConfig) -> list[tuple[tuple[str, ...], int]]:
    """Compress cfg.block_kinds() into scan segments."""
    kinds = cfg.block_kinds()
    if cfg.family == "hybrid":
        p = cfg.hybrid_period
        assert cfg.n_layers % p == 0, "hybrid depth must be divisible by period"
        pattern = tuple(kinds[:p])
        assert kinds == list(pattern) * (cfg.n_layers // p)
        return [(pattern, cfg.n_layers // p)]
    # maximal runs of equal kind
    segs: list[tuple[tuple[str, ...], int]] = []
    i = 0
    while i < len(kinds):
        j = i
        while j < len(kinds) and kinds[j] == kinds[i]:
            j += 1
        segs.append(((kinds[i],), j - i))
        i = j
    return segs


# ------------------------------------------------------------------ #
# block init                                                          #
# ------------------------------------------------------------------ #
def _init_attn(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    if cfg.attn_kind == "mla":
        h, dn, dr, dv = cfg.n_heads, cfg.mla_d_nope, cfg.mla_d_rope, cfg.mla_d_v
        p: dict = {
            "wkv_a": init_linear(ks[2], (d, cfg.kv_lora_rank + dr)),
            "kv_norm": jnp.ones((cfg.kv_lora_rank,), jnp.float32),
            "wk_b": init_linear(ks[3], (cfg.kv_lora_rank, h * dn)),
            "wv_b": init_linear(ks[4], (cfg.kv_lora_rank, h * dv)),
            "wo": init_linear(ks[5], (h * dv, d)),
        }
        if cfg.q_lora_rank:
            p["wq_a"] = init_linear(ks[0], (d, cfg.q_lora_rank))
            p["q_norm"] = jnp.ones((cfg.q_lora_rank,), jnp.float32)
            p["wq_b"] = init_linear(ks[1], (cfg.q_lora_rank, h * (dn + dr)))
        else:
            p["wq"] = init_linear(ks[0], (d, h * (dn + dr)))
        return p
    dh = cfg.resolved_head_dim
    p = {
        "wq": init_linear(ks[0], (d, cfg.n_heads * dh)),
        "wk": init_linear(ks[1], (d, cfg.n_kv_heads * dh)),
        "wv": init_linear(ks[2], (d, cfg.n_kv_heads * dh)),
        "wo": init_linear(ks[3], (cfg.n_heads * dh, d)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * dh,), jnp.float32)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * dh,), jnp.float32)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * dh,), jnp.float32)
    return p


def _init_mlp(key, cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_kind != "swiglu":
        return {
            "w_in": init_linear(ks[0], (d, f)),
            "w_out": init_linear(ks[1], (f, d)),
        }
    return {
        "w_gate": init_linear(ks[0], (d, f)),
        "w_up": init_linear(ks[1], (d, f)),
        "w_down": init_linear(ks[2], (f, d)),
    }


def _init_moe(key, cfg: ModelConfig) -> dict:
    m = cfg.moe
    assert m is not None
    d, fe = cfg.d_model, m.d_expert
    ks = jax.random.split(key, 7)
    p = {
        "router": init_linear(ks[0], (d, m.n_experts), dtype=jnp.float32),
        "experts": {
            "w_gate": init_linear(ks[1], (m.n_experts, d, fe)),
            "w_up": init_linear(ks[2], (m.n_experts, d, fe)),
            "w_down": init_linear(ks[3], (m.n_experts, fe, d)),
        },
    }
    if m.n_shared:
        fs = m.n_shared * fe
        p["shared"] = {
            "w_gate": init_linear(ks[4], (d, fs)),
            "w_up": init_linear(ks[5], (d, fs)),
            "w_down": init_linear(ks[6], (fs, d)),
        }
    return p


def _init_mamba(key, cfg: ModelConfig) -> dict:
    s = cfg.ssm
    assert s is not None
    d = cfg.d_model
    d_in = s.d_inner(d)
    nh = s.n_heads(d)
    conv_dim = d_in + 2 * s.n_groups * s.d_state
    ks = jax.random.split(key, 7)
    return {
        "wz": init_linear(ks[0], (d, d_in)),
        "wx": init_linear(ks[1], (d, d_in)),
        "wb": init_linear(ks[2], (d, s.n_groups * s.d_state)),
        "wc": init_linear(ks[3], (d, s.n_groups * s.d_state)),
        "wdt": init_linear(ks[4], (d, nh)),
        "conv_w": init_linear(ks[5], (conv_dim, s.conv_width),
                              scale=s.conv_width ** -0.5),
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "a_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.full((nh,), -4.6, jnp.float32),  # softplus^-1(0.01)
        "gate_norm": jnp.ones((d_in,), jnp.float32),
        "out_proj": init_linear(ks[6], (d_in, d)),
    }


def _init_block(key, kind: str, cfg: ModelConfig) -> dict:
    mixer, _, mlp = kind.partition("_")
    k1, k2 = jax.random.split(key)
    p: dict = {"ln1": jnp.ones((cfg.d_model,), jnp.float32)}
    if mixer == "attn":
        p["attn"] = _init_attn(k1, cfg)
    else:
        p["mamba"] = _init_mamba(k1, cfg)
    if mlp:
        p["ln2"] = jnp.ones((cfg.d_model,), jnp.float32)
        p["mlp" if mlp == "dense" else "moe"] = (
            _init_mlp(k2, cfg) if mlp == "dense" else _init_moe(k2, cfg))
    return p


# ------------------------------------------------------------------ #
# block apply                                                         #
# ------------------------------------------------------------------ #
@dataclass
class Model:
    """Bundle of pure functions for one architecture.

    ``mesh``/``dp_axes`` drive the expert-parallel MoE path and the
    activation sharding constraints; None falls back to the single-device
    reference behavior (tests, smoke configs). ``partitioned`` says a
    caller splits the step over devices itself (``repro.exec``'s
    ``MeshExecutor``) without giving the model a mesh; with a mesh or
    with it, GQA attention keeps its jnp path (:meth:`flash_layers`).
    """

    cfg: ModelConfig
    mesh: jax.sharding.Mesh | None = None
    dp_axes: tuple[str, ...] = ("data",)
    ep_axis: str = "model"
    attn_chunk: int = 1024
    partitioned: bool = False
    # (batch, seq) -> saved_dot_bytes, filled at trace time
    _saved_dots: dict = field(default_factory=dict, init=False,
                              repr=False, compare=False)

    def flash_layers(self, seq: int) -> int:
        """How many attention layers of a forward over ``seq`` positions
        run the flash kernel: every GQA layer where
        :func:`repro.models.attention.flash_blocks` admits the shapes,
        else none."""
        cfg = self.cfg
        if cfg.attn_kind == "mla" or attn.flash_blocks(
                seq, cfg.resolved_head_dim,
                self.partitioned or self.mesh is not None) is None:
            return 0
        return sum(k.startswith("attn") for k in cfg.block_kinds())

    def ssd_chunk_blocks(self, batch: int, seq: int) -> int:
        """How many blocks each SSD call of a forward over ``batch``
        sequences of ``seq`` positions runs its chunks in
        (:func:`repro.models.ssm.ssd_blocks`); 0 with no SSM layer."""
        if not any(k.startswith("mamba") for k in self.cfg.block_kinds()):
            return 0
        s = self.cfg.ssm
        return ssm_mod.ssd_blocks(batch, seq, s.n_heads(self.cfg.d_model),
                                  min(s.chunk, seq))

    def saved_dot_bytes(self, batch: int, seq: int) -> int:
        """Bytes the ``dots`` policy keeps for the backward across every
        scanned layer of one micro-batch of ``batch`` sequences of
        ``seq`` positions: the residuals of each checkpointed layer body
        under it, less those under ``nothing`` (the layer's input and
        parameters). Per device where the model's mesh shards the
        batch."""
        key = (batch, seq)
        if key not in self._saved_dots:
            total = sum(
                n_rep * (self._residual_bytes(pattern, batch, seq, "dots")
                         - self._residual_bytes(pattern, batch, seq,
                                                "nothing"))
                for pattern, n_rep in segments_of(self.cfg))
            axes = self._batch_axes(batch)
            if axes is not None:
                total //= math.prod(self.mesh.shape[a] for a in axes)
            self._saved_dots[key] = total
        return self._saved_dots[key]

    def _residual_bytes(self, pattern, batch: int, seq: int,
                        policy: str) -> int:
        """Bytes of the residuals one layer of ``pattern`` hands its
        backward under ``policy``, from shapes alone."""
        cfg = self.cfg
        x = jax.ShapeDtypeStruct(
            (batch, seq, cfg.d_model),
            jnp.float32 if cfg.residual_fp32 else ACT_DTYPE)
        layer_p = jax.eval_shape(
            lambda k: tuple(_init_block(kk, kind, cfg) for kk, kind in
                            zip(jax.random.split(k, len(pattern)), pattern)),
            jax.ShapeDtypeStruct((2,), jnp.uint32))

        def residuals(x, layer_p):
            positions = jnp.broadcast_to(jnp.arange(seq)[None], (batch, seq))
            body = jax.checkpoint(self._layer(pattern, positions),
                                  policy=_REMAT_POLICIES[policy])
            return jax.vjp(body, x, layer_p)[1]

        return sum(r.size * r.dtype.itemsize for r in
                   jax.tree.leaves(jax.eval_shape(residuals, x, layer_p)))

    def remat_for(self, batch: int, seq: int) -> str:
        """Checkpoint policy of the layer scan for a micro-batch of
        ``batch`` sequences of ``seq`` positions (the device-local shapes
        under ``shard_map``): ``none`` without remat, else
        ``cfg.remat_policy`` unless it is ``auto``. ``auto`` keeps the
        matmul outputs (``dots``) where :meth:`saved_dot_bytes` is at
        most :data:`SAVED_DOTS_SHARE` of the device's memory, and
        recomputes them (``nothing``) where it is more or the device
        reports no limit (the CPU)."""
        cfg = self.cfg
        if not cfg.remat:
            return "none"
        if cfg.remat_policy != "auto":
            return cfg.remat_policy
        limit = device_bytes_limit()
        if limit is None or (self.saved_dot_bytes(batch, seq)
                             > SAVED_DOTS_SHARE * limit):
            return "nothing"
        return "dots"

    # ---------------- sharding constraints ---------------- #
    def _batch_axes(self, batch: int):
        if self.mesh is None:
            return None
        size = 1
        for a in self.dp_axes:
            size *= self.mesh.shape[a]
        return self.dp_axes if batch % size == 0 else None

    def _constrain(self, x: jax.Array, *tail) -> jax.Array:
        """Pin the batch axis to the data axes (GSPMD otherwise loses it at
        the embedding gather — conflicting 'data' use between table FSDP
        and batch sharding replicates the whole forward; measured 16x
        activation blow-up, see EXPERIMENTS.md §Dry-run)."""
        if self.mesh is None:
            return x
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        spec = P(self._batch_axes(x.shape[0]),
                 *(tail if tail else (None,) * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def _mask_pad(self, logits: jax.Array) -> jax.Array:
        """Mask padded vocab columns to -inf (padding exists only so the
        table shards evenly; it must never win a softmax)."""
        cfg = self.cfg
        if cfg.padded_vocab == cfg.vocab:
            return logits
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        return jnp.where(col < cfg.vocab, logits,
                         jnp.asarray(-2.0 ** 20, logits.dtype))

    def _pin_layer_grads(self, layer_p):
        """Pin each weight's *gradient* sharding at its production point
        (inside the backward of the layer scan) so GSPMD reduce-scatters
        weight grads to their FSDP shard instead of all-reducing them to
        replicated inside the loop. Identity in the forward pass."""
        if self.mesh is None:
            return layer_p
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.dist.collectives import constrain_grad
        from repro.dist.sharding import _rule

        def pin(path, leaf):
            name = None
            for entry in reversed(path):
                if isinstance(entry, jax.tree_util.DictKey):
                    name = entry.key
                    break
            spec = P(*_rule(name, leaf.ndim, self.dp_axes))
            return constrain_grad(leaf, NamedSharding(self.mesh, spec))

        return jax.tree_util.tree_map_with_path(pin, layer_p)

    # ---------------- init ---------------- #
    def init(self, key: jax.Array) -> dict:
        cfg = self.cfg
        segs = segments_of(cfg)
        keys = jax.random.split(key, len(segs) + 3)
        params: dict = {
            "embed": init_linear(keys[0], (cfg.padded_vocab, cfg.d_model),
                                 scale=0.02),
            "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = init_linear(keys[1],
                                            (cfg.d_model, cfg.padded_vocab))
        seg_params = []
        for si, (pattern, n_rep) in enumerate(segs):
            def init_one(k):
                kk = jax.random.split(k, len(pattern))
                return tuple(_init_block(kk[i], kind, cfg)
                             for i, kind in enumerate(pattern))
            rep_keys = jax.random.split(keys[2 + si], n_rep)
            stacked = jax.vmap(init_one)(rep_keys)
            seg_params.append(stacked)
        params["segments"] = seg_params
        return params

    # ---------------- blocks ---------------- #
    def _norm(self, x, w):
        """RMSNorm of the residual stream, in the compute dtype (the
        stream itself is fp32 where ``cfg.residual_fp32``)."""
        return rmsnorm(x, w, self.cfg.norm_eps).astype(ACT_DTYPE)

    def _mlp_part(self, x, p, kind):
        _, _, mlp = kind.partition("_")
        if not mlp:
            return x
        if mlp != "dense":
            with jax.named_scope("moe"):
                h = self._norm(x, p["ln2"])
                return x + moe_mod.moe_ffn(
                    h, p["moe"], self.cfg, mesh=self.mesh,
                    dp_axes=self.dp_axes, ep_axis=self.ep_axis)
        from .layers import mlp2, swiglu
        with jax.named_scope("mlp"):
            h = self._norm(x, p["ln2"])
            if self.cfg.mlp_kind != "swiglu":
                return x + mlp2(h, p["mlp"]["w_in"], p["mlp"]["w_out"],
                                kind=self.cfg.mlp_kind)
            return x + swiglu(h, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                              p["mlp"]["w_down"])

    def _block_forward(self, x, p, kind, positions):
        cfg = self.cfg
        mixer = kind.partition("_")[0]
        with jax.named_scope(_MIXER_SCOPE[mixer]):
            h = self._norm(x, p["ln1"])
            if mixer == "attn":
                if cfg.attn_kind == "mla":
                    x = x + attn.mla_forward(h, p["attn"], cfg, positions,
                                             chunk=self.attn_chunk)
                else:
                    hc = None
                    if self.mesh is not None:
                        hc = lambda t: self._constrain(t, None, "model",
                                                       None)
                    x = x + attn.gqa_forward(h, p["attn"], cfg, positions,
                                             chunk=self.attn_chunk,
                                             head_constrain=hc,
                                             partitioned=self.partitioned)
            else:
                x = x + ssm_mod.mamba_forward(h, p["mamba"], cfg)
        return self._mlp_part(x, p, kind)

    def _block_decode(self, x, p, kind, cache, pos):
        cfg = self.cfg
        mixer = kind.partition("_")[0]
        with jax.named_scope(_MIXER_SCOPE[mixer]):
            h = self._norm(x, p["ln1"])
            if mixer == "attn":
                dec = (attn.mla_decode if cfg.attn_kind == "mla"
                       else attn.gqa_decode)
                y, cache = dec(h, p["attn"], cfg, cache, pos)
            else:
                y, cache = ssm_mod.mamba_decode(h, p["mamba"], cfg, cache)
            x = x + y
        return self._mlp_part(x, p, kind), cache

    def _block_prefill(self, x, p, kind, positions):
        """Forward one block AND capture its decode cache (fused prefill)."""
        cfg = self.cfg
        mixer = kind.partition("_")[0]
        with jax.named_scope(_MIXER_SCOPE[mixer]):
            h = self._norm(x, p["ln1"])
            if mixer == "attn":
                if cfg.attn_kind == "mla":
                    y, cache = attn.mla_forward(h, p["attn"], cfg, positions,
                                                chunk=self.attn_chunk,
                                                return_kv=True)
                else:
                    hc = None
                    if self.mesh is not None:
                        hc = lambda t: self._constrain(t, None, "model",
                                                       None)
                    y, cache = attn.gqa_forward(h, p["attn"], cfg, positions,
                                                chunk=self.attn_chunk,
                                                head_constrain=hc,
                                                return_kv=True,
                                                partitioned=self.partitioned)
            else:
                y, cache = ssm_mod.mamba_forward(h, p["mamba"], cfg,
                                                 return_cache=True)
            x = x + y
        return self._mlp_part(x, p, kind), cache

    def _block_decode_paged(self, x, p, kind, cache, table, pos):
        cfg = self.cfg
        mixer = kind.partition("_")[0]
        with jax.named_scope(_MIXER_SCOPE[mixer]):
            h = self._norm(x, p["ln1"])
            if mixer == "attn":
                dec = (attn.mla_decode_paged if cfg.attn_kind == "mla"
                       else attn.gqa_decode_paged)
                y, cache = dec(h, p["attn"], cfg, cache, table, pos)
            else:
                # SSD state is O(1) per sequence — the slot IS the page;
                # the dense decode path already advances every row
                # independently
                y, cache = ssm_mod.mamba_decode(h, p["mamba"], cfg, cache)
            x = x + y
        return self._mlp_part(x, p, kind), cache

    # ---------------- embedding / head ---------------- #
    def _embed(self, params, tokens, embeds) -> jax.Array:
        with jax.named_scope("embed"):
            if embeds is not None:
                x = embeds.astype(ACT_DTYPE)
            else:
                assert tokens is not None
                x = embed_lookup(params["embed"], tokens)
            if self.cfg.residual_fp32:
                x = x.astype(jnp.float32)
            return self._constrain(x)

    def _head(self, params, x) -> jax.Array:
        """Final norm, the tied or untied head and pad masking."""
        cfg = self.cfg
        with jax.named_scope("head"):
            x = self._norm(x, params["final_norm"])
            head = (params["embed"].T if cfg.tie_embeddings
                    else params["lm_head"])
            return self._mask_pad(jnp.dot(x, head))

    # ---------------- forward / loss ---------------- #
    def _layer(self, pattern, positions):
        """One step of a segment's layer scan: ``(x, layer params) ->
        x`` through the blocks of ``pattern``."""
        def layer(xc, layer_p):
            layer_p = self._pin_layer_grads(layer_p)
            for kind, bp in zip(pattern, layer_p):
                xc = self._block_forward(xc, bp, kind, positions)
            return self._constrain(xc)
        return layer

    def forward(self, params: dict, tokens: jax.Array | None = None,
                embeds: jax.Array | None = None) -> jax.Array:
        """Training forward. Returns logits (B, S, V)."""
        cfg = self.cfg
        x = self._embed(params, tokens, embeds)
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

        policy = self.remat_for(b, s)
        for (pattern, n_rep), seg in zip(segments_of(cfg), params["segments"]):
            layer = self._layer(pattern, positions)
            if policy != "none":
                layer = jax.checkpoint(layer, policy=_REMAT_POLICIES[policy])
            x, _ = jax.lax.scan(lambda xc, p: (layer(xc, p), None), x, seg)

        logits = self._head(params, x)
        return self._constrain(logits, None, "model")

    def loss(self, params: dict, batch: dict) -> jax.Array:
        logits = self.forward(params, tokens=batch.get("tokens"),
                              embeds=batch.get("embeds"))
        return cross_entropy(logits, batch["labels"])

    # ---------------- prefill ---------------- #
    def prefill(self, params: dict, tokens: jax.Array | None = None,
                embeds: jax.Array | None = None) -> tuple[jax.Array, list]:
        """Fused cache-filling prefill.

        Runs the full forward once and returns ``(logits (B, S, V),
        state)`` where ``state`` matches :meth:`init_decode_state`
        (batch=B, s_max=S) leaf for leaf — the per-layer caches are
        byproducts of the forward (post-rope k/v, compressed MLA rows,
        conv tails + final SSD states), so prefill costs one forward, not
        S decode steps. Feed *exact-length* prompts: the SSD recurrence
        runs through every input token, so right-padding corrupts the
        state (the serve engine jits one executable per prompt-length
        bucket for this reason).
        """
        cfg = self.cfg
        x = self._embed(params, tokens, embeds)
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

        states = []
        for (pattern, n_rep), seg in zip(segments_of(cfg), params["segments"]):
            def body(xc, layer_p):
                caches = []
                for kind, bp in zip(pattern, layer_p):
                    xc, c = self._block_prefill(xc, bp, kind, positions)
                    caches.append(c)
                return self._constrain(xc), tuple(caches)
            # scan ys stack the per-layer caches with a leading n_rep axis
            # — exactly the init_decode_state layout
            x, seg_cache = jax.lax.scan(body, x, seg)
            states.append(seg_cache)

        logits = self._head(params, x)
        return self._constrain(logits, None, "model"), states

    # ---------------- decode ---------------- #
    def init_decode_state(self, batch: int, s_max: int) -> list:
        """Per-segment stacked caches (leading axis n_rep)."""
        cfg = self.cfg
        states = []
        for pattern, n_rep in segments_of(cfg):
            per_pos = []
            for kind in pattern:
                mixer = kind.partition("_")[0]
                if mixer == "attn":
                    c = (attn.init_mla_cache(cfg, batch, s_max)
                         if cfg.attn_kind == "mla"
                         else attn.init_gqa_cache(cfg, batch, s_max))
                else:
                    c = ssm_mod.init_mamba_cache(cfg, batch)
                per_pos.append(jax.tree.map(
                    lambda t: jnp.broadcast_to(t[None], (n_rep, *t.shape)), c))
            states.append(tuple(per_pos))
        return states

    def decode_step(self, params: dict, state: list, pos: jax.Array,
                    tokens: jax.Array | None = None,
                    embeds: jax.Array | None = None
                    ) -> tuple[jax.Array, list]:
        """One-token step. tokens (B, 1) or embeds (B, 1, D); pos () int32.
        Returns (logits (B, 1, V), new state)."""
        cfg = self.cfg
        x = self._embed(params, tokens, embeds)

        new_states = []
        for (pattern, n_rep), seg, seg_cache in zip(
                segments_of(cfg), params["segments"], state):
            def body(xc, inp):
                layer_p, layer_c = inp
                new_c = []
                for kind, bp, c in zip(pattern, layer_p, layer_c):
                    xc, nc = self._block_decode(xc, bp, kind, c, pos)
                    new_c.append(nc)
                return xc, tuple(new_c)
            x, new_cache = jax.lax.scan(body, x, (seg, seg_cache))
            new_states.append(new_cache)

        return self._head(params, x), new_states

    # ---------------- paged decode ---------------- #
    def init_paged_state(self, n_slots: int, n_pages: int,
                         page_size: int) -> list:
        """Paged decode state: per-layer physical page pools.

        Attention caches become page pools ``(n_rep, n_pages, PS, ...)``
        shared by all decode slots; Mamba caches stay slot-dense
        ``(n_rep, n_slots, ...)`` because SSD state is O(1) per sequence
        (the slot is the page). One ``(n_slots, max_pages)`` int32 block
        table — managed host-side by ``repro.serve.kvcache`` — addresses
        every layer's pools identically; page 0 is the trash page.
        """
        cfg = self.cfg
        states = []
        for pattern, n_rep in segments_of(cfg):
            per_pos = []
            for kind in pattern:
                mixer = kind.partition("_")[0]
                if mixer == "attn":
                    c = (attn.init_mla_pool(cfg, n_pages, page_size)
                         if cfg.attn_kind == "mla"
                         else attn.init_gqa_pool(cfg, n_pages, page_size))
                else:
                    c = ssm_mod.init_mamba_cache(cfg, n_slots)
                per_pos.append(jax.tree.map(
                    lambda t: jnp.broadcast_to(t[None], (n_rep, *t.shape)), c))
            states.append(tuple(per_pos))
        return states

    def decode_step_paged(self, params: dict, state: list,
                          table: jax.Array, pos: jax.Array,
                          tokens: jax.Array | None = None,
                          embeds: jax.Array | None = None
                          ) -> tuple[jax.Array, list]:
        """One-token step over paged pools, per-row positions.

        tokens (B, 1) or embeds (B, 1, D); table (B, max_pages) int32
        physical page ids; pos (B,) int32 — row b generates token
        ``pos[b]``. B is the fixed decode-slot count: admission and
        eviction change only table/pos *data*, never this program, which
        is what keeps continuous batching recompile-free.
        """
        cfg = self.cfg
        x = self._embed(params, tokens, embeds)

        new_states = []
        for (pattern, n_rep), seg, seg_cache in zip(
                segments_of(cfg), params["segments"], state):
            def body(xc, inp):
                layer_p, layer_c = inp
                new_c = []
                for kind, bp, c in zip(pattern, layer_p, layer_c):
                    xc, nc = self._block_decode_paged(
                        xc, bp, kind, c, table, pos)
                    new_c.append(nc)
                return xc, tuple(new_c)
            x, new_cache = jax.lax.scan(body, x, (seg, seg_cache))
            new_states.append(new_cache)

        return self._head(params, x), new_states


def build_model(cfg: ModelConfig, mesh=None, dp_axes=("data",),
                attn_chunk: int = 1024) -> Model:
    return Model(cfg=cfg, mesh=mesh, dp_axes=tuple(dp_axes),
                 attn_chunk=attn_chunk)
