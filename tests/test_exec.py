"""repro.exec: the SPARe protocol executed on a real SPMD mesh.

All tests are ``spmd``-marked: they need >= 8 devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``; see
tests/conftest.py). What they prove, per the ISSUE-4 acceptance points:

* the ``shard_map`` mesh step's gradients match the host-side
  emulated trainer within fp32-reduction tolerance — for the healthy
  schedule and for EVERY recoverable survivor set;
* failure masking is pure weight-table data: a rack burst re-weights
  the live mesh run with no recompile at constant ``S_A``, and the
  masked step's compiled HLO carries exactly the same all-reduce count
  as the unmasked step (zero extra collectives);
* a wipe-out on the mesh rolls back to correctly re-sharded params.
"""
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.scenarios import ClusterTopology

pytestmark = pytest.mark.spmd

# fp32 summation-order noise across psum trees, amplified by bf16
# activations in the backward — same scale the emulated trainer allows
# for reorder noise (tests/test_trainer.py)
TOL = 5e-3


@pytest.fixture(scope="module")
def cfg():
    return smoke_config("qwen2.5-3b").scaled(grad_accum=1)


@pytest.fixture(scope="module")
def host_trainer(cfg):
    from repro.train.trainer import SpareTrainer
    return SpareTrainer(cfg, n_groups=4, redundancy=2, seq=32,
                        per_type_batch=2, total_steps=50)


def _executor(cfg, **kw):
    from repro.exec import MeshExecutor
    kw.setdefault("n_groups", 4)
    kw.setdefault("redundancy", 2)
    kw.setdefault("model_degree", 2)
    kw.setdefault("seq", 32)
    kw.setdefault("per_type_batch", 2)
    kw.setdefault("total_steps", 50)
    return MeshExecutor(cfg, **kw)


@pytest.fixture(scope="module")
def executor(cfg):
    return _executor(cfg)


@pytest.fixture(scope="module")
def compressed(cfg):
    return _executor(cfg, grad_compress="int8_ef")


# ------------------------------------------------------------------ #
# mesh-vs-host §3.1 equivalence                                      #
# ------------------------------------------------------------------ #
def test_mesh_matches_host_healthy(executor, host_trainer):
    from repro.exec import tree_max_rel_err
    mesh = executor.mesh_grads(0)
    host = host_trainer.spare_grads(0)
    assert tree_max_rel_err(mesh, host) < TOL


def test_params_placed_as_declared(executor):
    embed = executor.params["embed"]
    assert embed.sharding.mesh.shape == {"data": 4, "model": 2}
    spec = tuple(embed.sharding.spec)
    # manual program: per-device replicas
    assert all(s is None for s in spec) or spec == ()


def test_survivor_set_enumeration_matches_host(executor, host_trainer):
    """The full §3.1 sweep: every recoverable failure set's mesh gradient
    equals both the host gradient under the same schedule and the
    vanilla-DP oracle."""
    from repro.exec import survivor_set_sweep
    checks = survivor_set_sweep(executor, host_trainer)
    assert checks, "n=4, r=2 must have recoverable failure sets"
    # n=4, r=2 (cyclic Golomb): all 4 singles recover; doubles survive
    # only when no type loses both hosts
    assert len([c for c in checks if len(c.victims) == 1]) == 4
    assert any(c.s_a == 2 for c in checks), \
        "recovery at n=4,r=2 must raise the committed stack depth"
    bad = [c for c in checks if not c.ok(TOL)]
    assert not bad, f"survivor sets violating §3.1 on the mesh: {bad}"


# ------------------------------------------------------------------ #
# zero extra collectives + no recompile on re-weight                 #
# ------------------------------------------------------------------ #
def test_masked_step_has_identical_collectives(cfg, executor):
    """Masking a failure changes the weight *data*, never the program:
    compiled HLO of the masked step carries exactly the collectives of
    the unmasked step at the same S_A."""
    from repro.core import Rectlr, SpareState
    from repro.launch.hlo import collective_report

    ex = executor
    masked = SpareState(4, 2)
    outcome = Rectlr().on_failures(masked, [0])
    assert not outcome.wipeout
    healthy = SpareState(4, 2)
    healthy.s_a = masked.s_a          # same depth => same batch shapes

    rep_healthy = collective_report(ex.compiled_step_text(state=healthy))
    rep_masked = collective_report(ex.compiled_step_text(state=masked))
    assert rep_healthy["counts"] == rep_masked["counts"]
    assert rep_healthy["bytes"] == rep_masked["bytes"]
    assert rep_healthy["counts"].get("all-reduce", 0) >= 1, \
        "the step must actually sync gradients over the wire"


def test_failure_reweights_live_run_without_recompile(cfg):
    """Rack-burst events from the scenario engine re-weight the live
    mesh step; executables are cached per S_A only."""
    from repro.train.injection import ScenarioInjector

    # n=8 groups on an (8, 1) mesh: r=3 needs the wider Golomb ruler,
    # and racks of 2 groups make every burst a genuine multi-group kill
    ex = _executor(cfg, n_groups=8, redundancy=3,
                   model_degree=1, per_type_batch=1)
    topo = ClusterTopology(n_groups=8, hosts_per_group=2,
                           hosts_per_rack=4)   # 2 DP groups per rack
    inj = ScenarioInjector(
        {"kind": "correlated", "scope": "rack", "burst_prob": 1.0,
         "mtbf": 600.0}, topo, n_groups=8, seconds_per_step=100.0, seed=3)
    rep = ex.run(12, injector=inj, verify_equivalence=True)
    assert rep.steps_done == 12
    assert rep.failures >= 1, "hot regime must hit inside 12 steps"
    assert rep.max_grad_check_err < 1e-2
    assert all(np.isfinite(rep.losses))
    # every compiled executable corresponds to a distinct S_A depth the
    # run actually visited — re-weights alone never recompile
    depths = {e.s_a_after for e in rep.events} | {1}
    assert set(ex.compiled_depths) <= depths
    assert rep.recompiles == len(ex.compiled_depths)


def test_dryrun_production_shardings_compile(cfg):
    """The launch/dryrun.py production cell wiring — FSDP x TP
    ``param_specs``, ``opt_specs``, ``batch_spec``, and the
    ``constrain_grad`` gradient pinning inside the layer scan — lowers,
    SPMD-partitions, and compiles on an emulated (4, 2) mesh. (This path
    imported modules that did not exist before repro.exec; keep it
    compiling.)"""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.dist.sharding import batch_spec, opt_specs, param_specs
    from repro.launch.hlo import collective_report
    from repro.launch.mesh import dp_axes, make_emulated_mesh
    from repro.models import build_model
    from repro.optim import adamw_init
    from repro.train import make_train_step

    mesh = make_emulated_mesh(4, 2)
    model = build_model(cfg, mesh=mesh, dp_axes=dp_axes(False))
    p_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_spec = param_specs(p_shapes, cfg, multi_pod=False)
    p_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), p_spec)
    opt_shapes = jax.eval_shape(
        lambda p: adamw_init(p, moment_dtype=cfg.moment_dtype), p_shapes)
    o_shard = jax.tree.map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s,
        opt_specs(opt_shapes, p_spec), is_leaf=lambda x: isinstance(x, P))
    bspec = batch_spec(8, mesh, False)
    batch = {"tokens": jax.ShapeDtypeStruct((1, 8, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((1, 8, 32), jnp.int32),
             "weights": jax.ShapeDtypeStruct((1, 8), jnp.float32)}
    b_shard = {"tokens": NamedSharding(mesh, P(None, bspec, None)),
               "labels": NamedSharding(mesh, P(None, bspec, None)),
               "weights": NamedSharding(mesh, P(None, bspec))}
    with mesh:
        step = make_train_step(model, grad_shardings=p_shard)
        compiled = jax.jit(
            step, in_shardings=(p_shard, o_shard, b_shard),
            out_shardings=(p_shard, o_shard, None),
            donate_argnums=(0, 1),
        ).lower(p_shapes, opt_shapes, batch).compile()
    counts = collective_report(compiled.as_text())["counts"]
    assert counts.get("all-reduce", 0) >= 1
    # FSDP is live: weight grads reduce-scatter/all-gather, not only AR
    assert counts.get("all-gather", 0) >= 1


def test_per_host_feeding_matches_global_batch(executor):
    """``jax.make_array_from_callback`` feeding (each host materializes
    only its addressable rows, double-buffered) assembles byte-for-byte
    the global stacked batch — healthy and masked schedules alike."""
    from repro.core import Rectlr, SpareState
    from repro.data import spare_batch

    ex = executor
    masked = SpareState(4, 2)
    Rectlr().on_failures(masked, [2])
    for state in (ex.state, masked):
        batch = ex._device_batch(step=3, state=state)
        full = spare_batch(ex.pipeline, state, 3)
        assert set(batch) == set(full)
        for k in full:
            np.testing.assert_array_equal(np.asarray(batch[k]), full[k],
                                          err_msg=k)


def test_bucketed_sync_collectives_independent_of_leaf_count(cfg):
    """The flat-bucket sync issues O(n_buckets) gradient all-reduces —
    a function of total gradient bytes and the bucket cap, never of how
    many parameter leaves the model has."""
    import jax

    from repro.launch.hlo import collective_report

    big = _executor(cfg)                    # one bucket
    small = _executor(cfg, bucket_mb=0.125)   # ~32k elems/bkt
    assert big._layout.n_buckets == 1
    assert small._layout.n_buckets > 1
    n_leaves = len(jax.tree.leaves(big.params))
    assert small._layout.n_buckets < n_leaves
    ar_big = collective_report(big.compiled_step_text())["counts"][
        "all-reduce"]
    ar_small = collective_report(small.compiled_step_text())["counts"][
        "all-reduce"]
    # same program otherwise (loss psums etc.); the only delta is the
    # extra bucket psums
    assert ar_small - ar_big == small._layout.n_buckets - 1
    # HLO inspection warmed the cache outside any run: the executor-level
    # counter sees it, and a later run at the same S_A will not recompile
    assert big.total_recompiles == 1
    assert big.compiled_depths == [1]


# ------------------------------------------------------------------ #
# compressed sync (grad_compress="int8_ef")                          #
# ------------------------------------------------------------------ #
def test_compressed_mesh_matches_host_within_quantization(
        compressed, host_trainer):
    from repro.exec import int8_sweep_tolerance, tree_max_rel_err
    err = tree_max_rel_err(compressed.mesh_grads(0),
                           host_trainer.spare_grads(0))
    assert err < int8_sweep_tolerance(4)
    assert err > 0, "compression must actually have happened"


def test_compressed_survivor_set_sweep(compressed, host_trainer):
    """§3.1 under compression: every recoverable survivor set's
    compressed mesh gradient equals the host/vanilla oracles within the
    quantization-tolerance oracle (single step, zero EF residuals)."""
    from repro.exec import int8_sweep_tolerance, survivor_set_sweep
    checks = survivor_set_sweep(compressed, host_trainer)
    assert len([c for c in checks if len(c.victims) == 1]) == 4
    tol = int8_sweep_tolerance(4)
    bad = [c for c in checks if not c.ok(tol)]
    assert not bad, f"survivor sets violating §3.1 under int8-EF: {bad}"


def test_compressed_masked_step_schedule_and_wire_ratio(cfg, executor,
                                                        compressed):
    """The two ISSUE-5 HLO gates at once: (a) masked and unmasked
    compressed steps carry the identical collective schedule (masking
    stays weight data under compression); (b) the compressed step's
    gradient-sync wire bytes come in at <= 0.3x of the fp32 bucketed
    baseline, with the payload actually int8 on the wire."""
    from repro.core import Rectlr, SpareState
    from repro.launch.hlo import (collective_report, same_collective_schedule,
                                  wire_byte_ratio)

    masked = SpareState(4, 2)
    outcome = Rectlr().on_failures(masked, [0])
    assert not outcome.wipeout
    healthy = SpareState(4, 2)
    healthy.s_a = masked.s_a

    t_healthy = compressed.compiled_step_text(state=healthy)
    t_masked = compressed.compiled_step_text(state=masked)
    assert same_collective_schedule(t_healthy, t_masked)

    rep = collective_report(t_healthy)
    int8_bytes = sum(v for k, v in rep["by_dtype"].items()
                     if k.endswith("/s8"))
    assert int8_bytes > 0.5 * rep["total_bytes"], \
        f"int8 payload should dominate the wire: {rep['by_dtype']}"

    t_base = executor.compiled_step_text(state=healthy)
    ratio = wire_byte_ratio(t_healthy, t_base)
    assert ratio <= 0.3, \
        f"compressed sync wire bytes {ratio:.3f}x of fp32 (> 0.3x)"


def test_compressed_run_recompiles_only_on_depth_and_keeps_ef(cfg):
    """Live compressed run: EF residuals are real device-local state
    (threaded, donated, nonzero after a step), snapshot/rollback
    restores them with shardings intact, and failure re-weights still
    never recompile at constant S_A."""
    import jax

    ex = _executor(cfg, grad_compress="int8_ef")
    rep = ex.run(3)
    assert all(np.isfinite(rep.losses))
    assert rep.recompiles == 1
    flat_ef = jax.tree.leaves(ex._ef_state)
    assert any(np.asarray(e).any() for e in flat_ef), \
        "EF residuals should be nonzero after real steps"

    ex._snapshot_now()
    saved = jax.tree.map(np.asarray, ex._ef_state)
    ex.run(2)
    changed = jax.tree.leaves(jax.tree.map(
        lambda a, b: not np.array_equal(np.asarray(a), b),
        ex._ef_state, saved))
    assert any(changed), "EF residuals should evolve step to step"

    step, (params, opt) = ex._rollback()
    for leaf, ref, shard in zip(jax.tree.leaves(ex._ef_state),
                                jax.tree.leaves(saved),
                                jax.tree.leaves(ex._ef_shard)):
        np.testing.assert_array_equal(np.asarray(leaf), ref)
        assert leaf.sharding == shard

    # wipe-out through the real loop: rollback + continue, EF intact
    fired = []

    def kill_adjacent(state):
        if not fired and state is ex.state:
            fired.append(True)
            return [0, 1]
        return []

    rep2 = ex.run(4, injector=kill_adjacent)
    assert rep2.wipeouts == 1
    assert all(np.isfinite(rep2.losses))
    assert jax.tree.leaves(ex._ef_state)[0].sharding == \
        jax.tree.leaves(ex._ef_shard)[0]


def test_wipeout_rolls_back_resharded_params(cfg):
    """A wipe-out mid-run restores snapshot params/opt with the mesh
    shardings intact and keeps training."""
    ex = _executor(cfg, n_groups=4, redundancy=2)

    fired = []

    def kill_adjacent(state):
        # groups 0 and 1 are both hosts of type 0 at r=2 -> wipe-out
        if not fired and state is ex.state:
            fired.append(True)
            return [0, 1]
        return []

    rep = ex.run(6, injector=lambda st: kill_adjacent(st))
    assert rep.wipeouts == 1
    assert rep.steps_done >= 6
    assert ex.params["embed"].sharding == ex._pshard["embed"]
    assert all(np.isfinite(rep.losses))
