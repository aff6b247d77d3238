"""MeshExecutor — SPARe's Alg. 1 running on a real SPMD device mesh.

:class:`MeshExecutor` is :class:`repro.train.trainer.SpareTrainer` with
the device plane swapped from one-process emulation to a sharded program
over a ``(data, model)`` mesh (:func:`repro.launch.mesh
.make_emulated_mesh` / :func:`~repro.launch.mesh.make_production_mesh`).
The same pure ``make_train_step`` runs as one manual ``shard_map``
program — the §3.1 wire protocol made explicit: one SPARe DP group per
``data`` slice, supplier-weighted local gradients reduced ONCE per step
via ``weighted_all_reduce(..., axis_name="data")`` + a **bucketed flat
gradient sync** (:class:`~repro.dist.collectives.BucketedAllReduce`):
the gradient pytree is flattened into a handful of size-capped
contiguous fp32 buckets, so the per-step sync costs O(1) collectives
regardless of leaf count, with a bit-transparent unflatten. Per-device
parameters are replicas (pure DP): the ``model`` axis holds copies of
the step, and the program carries no tensor-parallel collectives.

``grad_compress="int8_ef"`` swaps the bucketed psum for the two-phase
int8 error-feedback wire protocol (:class:`~repro.dist.collectives
.CompressedBucketSync`): int8 payloads + per-bucket fp32 scales over the
wire (~4x fewer gradient-sync bytes, gated on compiled HLO by
``launch/hlo.py``), dequant-accumulated in fp32 inside the ``shard_map``
program — never int-psummed, so no overflow at any DP degree. The EF
residuals are device-local sharded state threaded through the step
(donated like params/opt) and preserved across wipe-out rollback.

Input feeding is **per-host**: each batch leaf is built with
``jax.make_array_from_callback``, so a host materializes only the
example rows its addressable shards cover (the pipeline is counter-based
and coordination-free), and the next step's rows are prefetched on a
builder thread while the dispatched step executes (double buffering).

Failure masking is identical with and without compression: recovery is
pure weight-table data. After ``scheme.recover`` re-plans the schedule,
the next step feeds the new ``SpareState.device_schedule()`` weights
through the batch — no resharding, no new collectives, no recompile
(executables are cached per ``S_A``). The paper's zero-extra-collectives
property is asserted on compiled HLO in ``tests/test_exec.py`` — with
and without compression — and the whole :class:`~repro.train.injection
.ScenarioInjector` bridge is inherited, so rack/pod burst events from
the scenario engine re-weight the live mesh step mid-run.

Runs anywhere: ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
fans a CPU host out into 8 emulated devices executing the same SPMD
program (partitioner, collectives, HLO) a TPU pod would run.
"""
from __future__ import annotations

import dataclasses
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.data import spare_batch_rows
from repro.dist.collectives import (BucketedAllReduce, CompressedBucketSync,
                                    bucket_layout)
from repro.launch.mesh import make_emulated_mesh
from repro.models.config import ModelConfig
from repro.obs.trace import maybe_span
from repro.train.step import make_train_step, weighted_loss
from repro.train.trainer import SpareTrainer, TrainReport


__all__ = ["MeshExecutor"]

_COMPRESS = (None, "int8_ef")


class MeshExecutor(SpareTrainer):
    """Drop-in :class:`SpareTrainer` whose step runs sharded on a mesh.

    Extra parameters on top of the trainer's:

    mesh: a ``(data, model)`` mesh to run on; by default an emulated one
        with ``data == n_groups`` slices (requires
        ``n_groups * model_degree`` visible devices).
    model_degree: size of the default mesh's ``model`` axis, along
        which the manual program replicates the step.
    sync: only ``"shard_map"``, the one spelling of the gradient sync;
        any other value raises ``ValueError``. A check, not a knob: it
        stays while ``bench/tests/bench_tiny.py`` still passes it.
    grad_compress: ``None`` (fp32 buckets on the wire) or ``"int8_ef"``
        (two-phase int8 error-feedback compressed sync).
    bucket_mb: flat-bucket size cap in MiB of fp32 — the gradient sync
        issues O(total_params / bucket) collectives per step, never one
        per leaf.
    """

    def __init__(self, cfg: ModelConfig, *, n_groups: int, redundancy: int,
                 mesh: jax.sharding.Mesh | None = None,
                 model_degree: int = 1, sync: str = "shard_map",
                 grad_compress: str | None = None, bucket_mb: float = 32.0,
                 base_lr: float = 3e-4, total_steps: int = 1000,
                 **kwargs: Any):
        if sync != "shard_map":
            raise ValueError(f"sync must be 'shard_map', the one spelling "
                             f"of the gradient sync, got {sync!r}")
        if grad_compress not in _COMPRESS:
            raise ValueError(f"grad_compress must be one of {_COMPRESS}, "
                             f"got {grad_compress!r}")
        if mesh is None:
            mesh = make_emulated_mesh(n_groups, model_degree)
        if "model" not in mesh.axis_names or "data" not in mesh.axis_names:
            raise ValueError(f"mesh must carry (data, model) axes, "
                             f"got {mesh.axis_names}")
        self.mesh = mesh
        self.grad_compress = grad_compress
        self.data_degree = mesh.shape["data"]
        self.model_degree = mesh.shape["model"]
        super().__init__(cfg, n_groups=n_groups, redundancy=redundancy,
                         base_lr=base_lr, total_steps=total_steps, **kwargs)
        examples = n_groups * self.pipeline.per_type_batch
        if examples % self.data_degree != 0:
            raise ValueError(
                f"{examples} stacked examples do not divide the data axis "
                f"({self.data_degree}); pick per_type_batch so that "
                f"N*per_type_batch % data == 0")
        # bucketed flat sync: the manual program's per-step gradient
        # reduction is O(n_buckets) collectives (fp32 psum, or the int8
        # EF wire protocol), never one per parameter leaf. The layout is
        # built ONCE, padded to the compressed sync's lane width times the
        # construction-time DP degree (whole int8 lanes per device), and
        # kept across elastic reshapes: any shrunken data axis that
        # divides the original degree still tiles every bucket, so EF
        # residuals move between meshes bit-transparently (repro.elastic)
        self._ef_state = None
        self._ef_snapshot = None
        # the step is split over the mesh here, not by the model: the
        # mesh path keeps the pure-jnp attention until a four-chip cell
        # times the kernel inside the manual program
        self.model = dataclasses.replace(self.model, partitioned=True)
        acc = jnp.dtype(cfg.grad_accum_dtype)
        gtree = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, acc), self.params)
        self._layout = bucket_layout(
            gtree, max_bucket_elems=max(int(bucket_mb * (1 << 20) // 4),
                                        self.data_degree),
            pad_to=CompressedBucketSync.LANES * self.data_degree)
        self._bind_mesh(mesh)
        self.params = jax.device_put(self.params, self._pshard)
        self.opt_state = jax.device_put(self.opt_state, self._oshard)
        if grad_compress:
            self._ef_state = jax.device_put(self._grad_sync.init_state(),
                                            self._ef_shard)
        # per-host feeding plumbing: the one-slot double buffer (the
        # builder thread materializes the next step's rows while the
        # dispatched step executes)
        self._feed_pool = ThreadPoolExecutor(max_workers=1)
        self.total_recompiles = 0   # cache misses, run-driven or not
        # live wire accounting: launch/hlo.py's byte audit of the
        # compiled step, memoized per (mesh shape, S_A) cache key
        self._wire_info: dict[tuple, dict] = {}

    def _bind_mesh(self, mesh: jax.sharding.Mesh) -> None:
        """(Re)build every mesh-shape-dependent piece of the step
        plumbing: gradient sync, step fn, param/opt/EF/batch shardings.
        Called at construction and by the elastic reshaper
        (:class:`repro.elastic.ElasticMeshExecutor`) after it swaps the
        mesh for a survivor submesh — the executable cache itself is
        keyed on the mesh shape (:meth:`_cache_key`), so executables for
        other shapes stay warm."""
        self.mesh = mesh
        self.data_degree = mesh.shape["data"]
        self.model_degree = mesh.shape["model"]
        if self.grad_compress == "int8_ef":
            self._grad_sync = CompressedBucketSync(
                self._layout, self.data_degree, "data")
            if self.telemetry is not None and self.telemetry.deep:
                # deep mode: in-jit per-bucket markers (changes the
                # compiled program)
                self._grad_sync.tel = self.telemetry
        else:
            self._grad_sync = BucketedAllReduce(self._layout, "data")
        # the sharded spelling of the step the parent already built: the
        # same pure function, with the named-axis gradient sync
        self._step_fn = make_train_step(
            self.model, base_lr=self._base_lr, total_steps=self.total_steps,
            axis_name="data", grad_sync=self._grad_sync)
        # per-device replicas, pure DP
        self._pshard = jax.tree.map(
            lambda _: NamedSharding(mesh, P()), self.params)
        self._oshard = type(self.opt_state)(
            step=NamedSharding(mesh, P()),
            mu=jax.tree.map(lambda s: s, self._pshard),
            nu=jax.tree.map(lambda s: s, self._pshard))
        if self.grad_compress:
            self._ef_shard = jax.tree.map(
                lambda s: NamedSharding(mesh, s),
                self._grad_sync.state_specs())
        # batch shardings hoisted out of the per-step path
        self._bshard = {k: NamedSharding(mesh, s)
                        for k, s in self._batch_specs().items()}
        self._prefetch: tuple[tuple, Future] | None = None
        self._mesh_grad_fn = None

    # ------------------------------------------------------------- #
    # sharded step plumbing                                         #
    # ------------------------------------------------------------- #
    def _batch_specs(self) -> dict:
        """PartitionSpec per batch leaf: microbatch axis replicated (it
        is scanned), example axis on ``data``."""
        specs = {"labels": P(None, "data", None),
                 "weights": P(None, "data")}
        if self.cfg.frontend is not None:
            specs["embeds"] = P(None, "data", None, None)
        else:
            specs["tokens"] = P(None, "data", None)
        return specs

    def _wrap_step(self, fn):
        """The jit-able sharded step: ``fn`` as a manual program."""
        in_specs = [P(), P(), self._batch_specs()]
        out_specs = [P(), P(), P()]
        if self.grad_compress:
            ef = self._grad_sync.state_specs()
            in_specs.append(ef)
            out_specs.append(ef)
        # replication checking off: the replicated out_specs rest on
        # psum_partial's custom VJP, which the checker cannot see
        return jax.shard_map(fn, mesh=self.mesh, in_specs=tuple(in_specs),
                             out_specs=tuple(out_specs), check_vma=False)

    def _cache_key(self, s_a: int) -> tuple[int, int, int]:
        """Executable-cache key: ``(data_degree, model_degree, s_a)``.
        Keying on the mesh shape (not just ``S_A``) lets the elastic
        recovery tier swap in a survivor submesh and back without ever
        invalidating warm executables — a reshape costs exactly one new
        cache entry per (shape, depth) it visits."""
        return (self.data_degree, self.model_degree, s_a)

    def _micro_rows(self) -> int:
        """Sequences of a micro-batch on one device: ``shard_map`` hands
        each its slice of the example axis."""
        return super()._micro_rows() // self.data_degree

    def _compiled(self, s_a: int, report: TrainReport | None = None):
        # Donation contract (analyzer-enforced): params, opt_state, and —
        # under int8_ef — the EF residuals are donated, and every donated
        # leaf must surface as an input/output alias in the compiled
        # module. ``python -m repro.launch.lint`` replays this jit site
        # via ``compiled_step_text`` and fails CI on any unaliased
        # donated buffer (repro.analysis donation-audit pass).
        key = self._cache_key(s_a)
        if key not in self._jitted:
            donate = (0, 1, 3) if self.grad_compress else (0, 1)
            self._jitted[key] = jax.jit(self._wrap_step(self._step_fn),
                                        donate_argnums=donate)
            # total_recompiles is the order-independent count (HLO
            # inspection can warm the cache outside any run); a run's
            # report counts only the compiles that run triggered
            self.total_recompiles += 1
            self._count_compile(report)
        return self._jitted[key]

    # ------------------------------------------------------------- #
    # per-host input feeding                                        #
    # ------------------------------------------------------------- #
    def _batch_shapes(self, s_a: int) -> dict[str, tuple[int, ...]]:
        e = self.state.n * self.pipeline.per_type_batch
        seq = self.pipeline.seq
        shapes = {"labels": (s_a, e, seq), "weights": (s_a, e)}
        if self.cfg.frontend is not None:
            shapes["embeds"] = (s_a, e, seq, self.cfg.d_model)
        else:
            shapes["tokens"] = (s_a, e, seq)
        return shapes

    def _feed_ranges(self, s_a: int) -> list[tuple[int, int]]:
        """Example-row ranges [lo, hi) this host must materialize — the
        union of its addressable shards of the example axis."""
        shape = self._batch_shapes(s_a)["weights"]
        imap = self._bshard["weights"].addressable_devices_indices_map(shape)
        ranges = set()
        for idx in imap.values():
            sl = idx[1]
            ranges.add((sl.start or 0,
                        shape[1] if sl.stop is None else sl.stop))
        return sorted(ranges)

    def _host_slabs(self, schedule, s_a: int, step: int,
                    ranges: list[tuple[int, int]]) -> dict:
        """Materialize only this host's example rows: {(lo, hi) -> np
        batch dict}. Runs on the builder thread for the prefetched step;
        ``ranges`` is snapshotted by the caller (``_feed_ranges`` reads
        the mesh-shape-dependent batch shardings, which an elastic
        reshape rebinds)."""
        return {(lo, hi): spare_batch_rows(self.pipeline, schedule, s_a,
                                           step, lo, hi)
                for lo, hi in ranges}

    def _place_slabs(self, s_a: int, slabs: dict) -> dict:
        """Assemble the sharded global batch without ever materializing
        it: each addressable shard's callback serves a view of the slab
        covering its rows."""
        shapes = self._batch_shapes(s_a)

        def maker(key):
            shape = shapes[key]

            def cb(index):
                sl = index[1]
                lo = sl.start or 0
                hi = shape[1] if sl.stop is None else sl.stop
                for (rlo, rhi), slab in slabs.items():
                    if rlo <= lo and hi <= rhi:
                        rows = slice(lo - rlo, hi - rlo)
                        return slab[key][(index[0], rows) + tuple(index[2:])]
                raise KeyError(f"no host slab covers rows [{lo}, {hi})")

            return jax.make_array_from_callback(shape, self._bshard[key], cb)

        return {k: maker(k) for k in shapes}

    def _batch_key(self, state, step: int):
        """Prefetch identity: the batch is a pure function of (step,
        schedule). The schedule arrays are snapshotted so the builder
        thread never reads mutable trainer state."""
        stack_types, wts = state.device_schedule()
        key = (step, state.s_a, stack_types.tobytes(), wts.tobytes())
        return key, (stack_types, wts)

    def _device_batch(self, step: int | None = None, state=None) -> dict:
        state = self.state if state is None else state
        step = self.step if step is None else step
        key, schedule = self._batch_key(state, step)
        tel = self.telemetry
        hit = False
        with maybe_span(tel, "feed"):
            slabs = None
            if self._prefetch is not None:
                pkey, fut = self._prefetch
                self._prefetch = None
                if pkey == key:
                    slabs = fut.result()
                    hit = True
                # else: a failure re-planned the schedule (or the caller
                # asked for a different step) — the prefetched rows are
                # stale; drop them and build synchronously
            if slabs is None:
                slabs = self._host_slabs(schedule, state.s_a, step,
                                         self._feed_ranges(state.s_a))
            out = self._place_slabs(state.s_a, slabs)
        if tel is not None:
            tel.counter("feed.prefetch_hits" if hit
                        else "feed.prefetch_misses").inc()
        return out

    def _prefetch_next(self):
        """Double buffer: queue the NEXT step's row materialization on
        the builder thread while the current step executes on device."""
        key, schedule = self._batch_key(self.state, self.step + 1)
        self._prefetch = (key, self._feed_pool.submit(
            self._host_slabs, schedule, self.state.s_a, self.step + 1,
            self._feed_ranges(self.state.s_a)))

    def _dispatch(self, report: TrainReport):
        batch = self._device_batch()
        fn = self._compiled(self.state.s_a, report)
        with maybe_span(self.telemetry, "dispatch"):
            if self.grad_compress:
                out = fn(self.params, self.opt_state, batch,
                         self._ef_state)
                params, opt_state, metrics, self._ef_state = out
                result = (params, opt_state, metrics)
            else:
                result = fn(self.params, self.opt_state, batch)
        # the step is dispatched (async); overlap the next batch build
        self._prefetch_next()
        if self.telemetry is not None:
            self._observe_sync(self.telemetry)
        return result

    def _observe_sync(self, tel) -> None:
        """Publish the per-step gradient-sync wire accounting as live
        metrics: ``launch/hlo.py``'s byte audit of the compiled step,
        memoized per ``S_A`` (the executable is already compiled when
        this runs, so the one-time lowering cost per depth is the only
        overhead — steady-state steps just bump a counter). Deep mode
        adds the int8-EF residual norms, which synchronize the device."""
        key = self._cache_key(self.state.s_a)
        info = self._wire_info.get(key)
        if info is None:
            from repro.launch.hlo import collective_report
            # compiled_step_text builds its own batch — keep the live
            # run's prefetched slabs out of its reach
            saved, self._prefetch = self._prefetch, None
            try:
                text = self.compiled_step_text()
            finally:
                self._prefetch = saved
            info = self._wire_info[key] = collective_report(text)
        tel.gauge("sync.wire_bytes_per_step").set(info["total_bytes"])
        tel.gauge("sync.collectives_per_step").set(
            int(sum(info["counts"].values())))
        tel.counter("sync.wire_bytes_total").inc(info["total_bytes"])
        if tel.deep and self._ef_state is not None:
            for fam in ("err1", "err2"):
                sq = sum(float(jnp.vdot(b, b))
                         for b in self._ef_state[fam])
                tel.gauge(f"sync.ef_residual_norm.{fam}").set(sq ** 0.5)

    def run(self, *args, **kwargs):
        try:
            return super().run(*args, **kwargs)
        finally:
            # the last dispatched step speculatively built rows for a
            # step that will never execute — do not pin those slabs
            self._prefetch = None

    def close(self) -> None:
        """Release the feeding plumbing (builder thread + any pending
        prefetched slabs). The executor stays usable for HLO inspection
        but must not dispatch further steps."""
        self._prefetch = None
        self._feed_pool.shutdown(wait=False)

    # ------------------------------------------------------------- #
    # snapshot / rollback (EF residuals ride along)                 #
    # ------------------------------------------------------------- #
    def _snapshot_now(self) -> None:
        super()._snapshot_now()
        if self._ef_state is not None:
            self._ef_snapshot = jax.tree.map(np.asarray, self._ef_state)

    def _rollback(self):
        """Wipe-out restore: the snapshot tiers hand back host arrays —
        re-place them under the mesh shardings before training resumes.
        The EF residuals roll back to the same step as params (the
        untransmitted signal belongs to the discarded trajectory)."""
        step, (params, opt_state) = super()._rollback()
        if self._ef_snapshot is not None:
            self._ef_state = jax.device_put(self._ef_snapshot,
                                            self._ef_shard)
        return step, (jax.device_put(params, self._pshard),
                      jax.device_put(opt_state, self._oshard))

    # ------------------------------------------------------------- #
    # gradient oracle (mesh spelling)                               #
    # ------------------------------------------------------------- #
    def mesh_grads(self, step: int | None = None, state=None):
        """Total-batch gradient of the given (default: current) schedule
        computed BY THE MESH: the sharded forward/backward with the
        per-step gradient sync. The §3.1 oracle for mesh-vs-host
        equivalence — must match :meth:`SpareTrainer.spare_grads` (same
        params, same deterministic batch) up to all-reduce
        summation-order noise (plus one step's bounded quantization
        error when ``grad_compress`` is on — zero EF residuals, see
        ``exec/equivalence.py::int8_sweep_tolerance``)."""
        if self._mesh_grad_fn is None:
            model = self.model
            sync = self._grad_sync

            def total_loss(params, batch):
                def body(acc, micro):
                    return acc + weighted_loss(model, params, micro,
                                               axis_name="data"), None
                out, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                      batch)
                return out

            def grads(params, batch):
                g = jax.grad(total_loss)(params, batch)
                if self.grad_compress:
                    return sync.sync_once(g)
                return sync(g)

            fn = jax.shard_map(grads, mesh=self.mesh,
                               in_specs=(P(), self._batch_specs()),
                               out_specs=P(), check_vma=False)
            self._mesh_grad_fn = jax.jit(fn)
        batch = self._device_batch(step, state)
        return self._mesh_grad_fn(self.params, batch)

    # ------------------------------------------------------------- #
    # HLO inspection (the zero-extra-collectives proof)             #
    # ------------------------------------------------------------- #
    def compiled_step_text(self, state=None) -> str:
        """Post-SPMD HLO of the step for the given (default: current)
        schedule — feed to :func:`repro.launch.hlo.collective_report` to
        count the sync collectives masked vs unmasked. Routed through
        the per-``S_A`` ``_jitted`` cache, so repeated calls (and the
        live run) share one executable per stack depth; a cache warm-up
        here counts toward ``total_recompiles`` but not toward any
        run's ``report.recompiles``."""
        state = self.state if state is None else state
        batch = self._device_batch(state=state)
        fn = self._compiled(state.s_a)
        args = [self.params, self.opt_state, batch]
        if self.grad_compress:
            args.append(self._ef_state)
        return fn.lower(*args).compile().as_text()

    def prewarm_depths(self, depths) -> None:
        """Compile the step executable for each stack depth in
        ``depths`` ahead of need. A SPARe demotion on a cyclic Golomb
        hosting typically forces ``S_A`` one deeper (the supplier
        reassignment cascades around the hosting cycle), so a
        latency-sensitive run warms both depths up front and the
        demote itself is a pure weight-table edit — zero
        run-attributed recompiles, like any mask at constant shape.
        Warm-up compiles count toward ``total_recompiles`` only (the
        :meth:`compiled_step_text` contract)."""
        import copy
        probe = copy.deepcopy(self.state)
        for s_a in sorted(set(int(d) for d in depths)):
            if not 1 <= s_a <= self.state.r:
                raise ValueError(f"stack depth {s_a} outside "
                                 f"[1, r={self.state.r}]")
            probe.s_a = s_a
            self.compiled_step_text(state=probe)

    def donated_leaves(self) -> int:
        """Flat leaf count across the step's donated argnums — the
        number of input/output aliases the donation-audit pass expects
        in :meth:`compiled_step_text`'s module header."""
        n = len(jax.tree_util.tree_leaves(self.params)) + \
            len(jax.tree_util.tree_leaves(self.opt_state))
        if self.grad_compress:
            n += len(jax.tree_util.tree_leaves(self._ef_state))
        return n

    @property
    def compiled_depths(self) -> list[int]:
        """S_A depths with a live compiled executable for the CURRENT
        mesh shape — a failure re-weight at constant S_A must not grow
        this. Executables compiled for other mesh shapes (elastic
        reshapes) live under their own keys; see :attr:`cache_keys`."""
        shape = (self.data_degree, self.model_degree)
        return sorted(s_a for (d, m, s_a) in self._jitted
                      if (d, m) == shape)

    @property
    def cache_keys(self) -> list[tuple[int, int, int]]:
        """Every live executable-cache key, ``(data, model, s_a)`` —
        the full picture across mesh shapes the run has visited."""
        return sorted(self._jitted)
