"""Main-path programs compiled for a TPU v5e chip, without a chip.

The TPU compiler ships with the installed jax, and compiles for a chip
that is described rather than attached. These tests hand it shapes on
described v5e devices: what Mosaic or XLA would refuse on the chip (a
block shape off the tiling, a program that does not fit 16 GiB of HBM)
fails here, at no chip time. Nothing runs, so they say nothing about
results or speed.

Only one process at a time may load the TPU library, and it keeps it
until it exits. So the topology is described inside a module-scoped
fixture, never while a module is imported, and every compile test lives
in this one file: under pytest-xdist only the worker given this file
loads the library, and every worker collects the same tests.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

V5E_HBM_BYTES = 16 * 2 ** 30
V5E_BYTES_LIMIT = 16_900_000_000     # a v5e chip's memory_stats() limit


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs in /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure: no compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_step(cfg, one_chip, stacks: int, seq: int):
    """The single-chip SPARe train step of ``cfg`` over ``stacks`` stacks
    of N=4 groups of one ``seq``-token sequence, compiled for v5e."""
    from repro.models import build_model
    from repro.optim import adamw_init
    from repro.train.step import make_train_step

    model = build_model(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt = jax.eval_shape(adamw_init, params)
    batch = {"tokens": jax.ShapeDtypeStruct((stacks, 4, seq), jnp.int32),
             "labels": jax.ShapeDtypeStruct((stacks, 4, seq), jnp.int32),
             "weights": jax.ShapeDtypeStruct((stacks, 4), jnp.float32)}
    step = jax.jit(make_train_step(model, total_steps=100),
                   donate_argnums=(0, 1))
    return step.lower(on_chip(params), on_chip(opt),
                      on_chip(batch)).compile()


def _chip_memory(mp) -> None:
    """Let the program see a v5e chip's memory limit, which the CPU
    device it runs on here does not report."""
    import repro.models.model as model_mod
    mp.setattr(model_mod, "device_bytes_limit", lambda: V5E_BYTES_LIMIT)


def _peak_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


@pytest.mark.parametrize("n", [8 << 20, 1_000_003],
                         ids=["bucket-8Mi", "odd-padded"])
def test_int8_ef_kernel_compiles_for_v5e(one_chip, n):
    """The int8-EF kernel at the mesh executor's real bucket size
    (``max_bucket_elems`` = 8 Mi fp32) and at an odd size that needs
    padding, compiled by Mosaic (not interpreted)."""
    from repro.kernels.int8_ef import int8_ef_pallas

    g = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(int8_ef_pallas).lower(g, g).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def qwen_step(one_chip):
    """The single-chip SPARe train step of qwen2.5-3b at every published
    width (1 layer; N=4 groups of one 1024-token sequence), compiled for
    v5e."""
    from repro.configs import get_config

    cfg = get_config("qwen2.5-3b").scaled(n_layers=1, grad_accum=1)
    return _compiled_step(cfg, one_chip, stacks=1, seq=1024)


def test_qwen_train_step_fits_one_v5e(qwen_step):
    """The step compiles for v5e and fits its HBM."""
    assert 0 < _peak_bytes(qwen_step) < V5E_HBM_BYTES


def test_qwen_train_step_matmuls_are_scoped_on_v5e(qwen_step):
    """Every instruction of the v5e program that runs a matmul, as most
    run inside fusions, gets a layer from ``repro.obs.hlo_scopes``: what
    the profiler trace's operations are joined to."""
    import re

    from repro.obs import hlo_scopes

    text = qwen_step.as_text()
    _, scopes = hlo_scopes(text)
    with_matmul = set()
    for body in re.split(r"\n(?=(?:ENTRY )?%)", text):
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) ", body)
        if head and re.search(r" (?:dot|convolution)\(", body):
            with_matmul.add(head.group(1))
    callers = re.findall(r"^\s+(?:ROOT )?%([\w.\-]+) = .*calls=%([\w.\-]+)",
                         text, re.M)
    matmuls = [name for name, called in callers if called in with_matmul]
    matmuls += re.findall(
        r"^\s+(?:ROOT )?%([\w.\-]+) = \S+ (?:dot|convolution)\(", text, re.M)
    assert len(matmuls) >= 10
    assert [m for m in matmuls if scopes[m] is None] == []
    assert {scopes[m] for m in matmuls} == {"attention", "mlp", "head"}


def test_int8_ef_sync_compiles_over_v5e_mesh(topo, monkeypatch):
    """The compressed bucket sync over a 4-chip v5e mesh with the Pallas
    kernel in it. Its int8 all-to-all and all-gather must move rows of
    ``CompressedBucketSync.LANES``: on flat int8 chunks the TPU compile
    time grows with the bucket size."""
    import re

    import numpy as np

    import repro.kernels.ops as ops
    from repro.dist.collectives import CompressedBucketSync, bucket_layout

    # code that asks jax.default_backend() sees the CPU here: steer the
    # kernel choice to the chip's, and compile it for real (not interpret)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    mesh = jax.sharding.Mesh(np.asarray(topo.devices).reshape(4, 1),
                             ("data", "model"))
    n = 8 << 20
    layout = bucket_layout({"w": jax.ShapeDtypeStruct((n,), jnp.float32)},
                           max_bucket_elems=n,
                           pad_to=CompressedBucketSync.LANES * 4)
    sync = CompressedBucketSync(layout, 4, "data")
    specs = sync.state_specs()
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        sync.init_state(), specs)
    grads = {"w": jax.ShapeDtypeStruct((n,), jnp.float32,
                                       sharding=NamedSharding(mesh, P()))}
    fn = jax.shard_map(sync, mesh=mesh, in_specs=(P(), specs),
                       out_specs=(P(), specs), check_vma=False)
    text = jax.jit(fn).lower(grads, state).compile().as_text()
    assert "tpu_custom_call" in text
    # int8 collectives in the compiled module, e.g.
    # %all_to_all.3 = s8[4,16384,128]{...} all-to-all(...)
    int8_colls = re.findall(
        r"= s8\[([\d,]+)\]\S* (all-to-all|all-gather)(?:-start)?\(", text)
    assert {op for _, op in int8_colls} == {"all-to-all", "all-gather"}
    for dims, op in int8_colls:
        assert int(dims.split(",")[-1]) == CompressedBucketSync.LANES, \
            f"int8 {op} of s8[{dims}] is not lane-aligned"


# the training cell's attention: qwen2.5-3b widths, N=4 groups of one
# 1024-token sequence a stack
CELL_ATTN = dict(b=4, h=16, kv=2, s=1024, dh=128)


def test_flash_attention_fwd_bwd_compile_for_v5e(one_chip, monkeypatch):
    """The kernel path of ``gqa_forward`` at the training cell's shapes,
    forward and backward, lowered by Mosaic: the forward, dq and dkv
    kernels are custom calls of the compiled program."""
    import re

    import repro.kernels.ops as ops
    from repro.models.attention import attend_flash, flash_blocks

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    b, h, kv, s, dh = (CELL_ATTN[k] for k in ("b", "h", "kv", "s", "dh"))
    blocks = flash_blocks(s, dh, partitioned=False)
    assert blocks is not None

    def loss(q, k, v):
        return jnp.sum(attend_flash(q, k, v, blocks).astype(jnp.float32))

    def arg(heads):
        return jax.ShapeDtypeStruct((b, s, heads, dh), jnp.bfloat16,
                                    sharding=one_chip)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg(h), arg(kv), arg(kv)).compile().as_text()
    names = set(re.findall(r"splash_mqa_(fwd|dq|dkv)", text))
    assert names == {"fwd", "dq", "dkv"}
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def cell_step(one_chip):
    """The training cell's step (qwen2.5-3b widths, 4 layers, S_A = 2
    stacks of N=4 groups of one 1024-token sequence), compiled for v5e
    with the attention kernel and the layer scan's remat policy chosen
    as on the chip."""
    from repro.configs import get_config
    from repro.models import build_model

    import repro.kernels.ops as ops

    cfg = get_config("qwen2.5-3b").scaled(n_layers=4, grad_accum=1)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "on_tpu", lambda: True)
        _chip_memory(mp)
        assert build_model(cfg).flash_layers(1024) == 4
        assert build_model(cfg).remat_for(4, 1024) == "dots"
        return _compiled_step(cfg, one_chip, stacks=2, seq=1024)


def test_cell_step_with_flash_attention_fits_one_v5e(cell_step):
    """With the kernel on its path the cell's step fits 16 GiB, runs the
    forward, dq and dkv kernels, and holds no fp32 (B, H, S, S) score
    tensor."""
    text = cell_step.as_text()
    assert 0 < _peak_bytes(cell_step) < V5E_HBM_BYTES
    for phase in ("fwd", "dq", "dkv"):
        assert f"splash_mqa_{phase}" in text
    assert "f32[4,16,1024,1024]" not in text


@pytest.fixture(scope="module")
def mamba_cell_step(one_chip):
    """The Mamba-2 training cell's step (mamba2-1.3b widths, 12 layers,
    S_A = 2 stacks of N=4 groups of one 2048-token sequence), compiled
    for v5e with the layer scan's remat policy chosen as on the chip."""
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config("mamba2-1.3b").scaled(n_layers=12, grad_accum=1)
    with pytest.MonkeyPatch.context() as mp:
        _chip_memory(mp)
        assert build_model(cfg).remat_for(4, 2048) == "dots"
        return _compiled_step(cfg, one_chip, stacks=2, seq=2048)


def test_mamba_cell_step_fits_one_v5e(mamba_cell_step):
    """The cell's step fits 16 GiB, and its SSD scan holds no fp32
    (B, Q, Q, H) tensor: the (Q, Q) blocks are head-major, and C·Bᵀ is
    taken once per group, not once per head."""
    assert 0 < _peak_bytes(mamba_cell_step) < V5E_HBM_BYTES
    assert "f32[4,256,256,64]" not in mamba_cell_step.as_text()


def test_mamba_cell_step_scan_is_scoped_on_v5e(mamba_cell_step):
    """Every matmul of the v5e program carries ``ssm``, ``ssd`` or
    ``head``, and the SSD scan's, forward and backward, carry ``ssd``.
    Every exponential of the mixer but its SiLU and softplus is the
    scan's and carries ``ssd``."""
    import re

    from repro.obs import scope_of

    found = []
    for m in re.finditer(r"^\s+(?:ROOT )?%[\w.\-]+ = \S+ "
                         r"(dot|convolution|exponential)\(.*"
                         r'op_name="([^"]*)"', mamba_cell_step.as_text(),
                         re.M):
        found.append((m.group(1), m.group(2), scope_of(m.group(2))))
    matmuls = [(op, s) for kind, op, s in found if kind != "exponential"]
    assert len(matmuls) >= 20
    assert {s for _, s in matmuls} == {"ssm", "ssd", "head"}
    ssd = [op for op, s in matmuls if s == "ssd"]
    assert any("transpose(" not in op for op in ssd), "no forward scan"
    assert any("transpose(" in op for op in ssd), "no backward scan"
    mixer_exps = [(op, s) for kind, op, s in found
                  if kind == "exponential" and s in ("ssm", "ssd")]
    scan_exps = [(op, s) for op, s in mixer_exps
                 if not re.search(r"jit\((silu|softplus)\)", op)]
    assert len(scan_exps) >= 5
    assert {s for _, s in scan_exps} == {"ssd"}
