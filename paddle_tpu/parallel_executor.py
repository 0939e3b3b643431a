"""ParallelExecutor: the fluid multi-device data-parallel API, compiled SPMD.

Reference analog: python/paddle/fluid/parallel_executor.py:32 +
framework/parallel_executor.cc:92 + framework/details/ (SURVEY.md §2.2). The
reference rewrites the program into a per-device SSA graph with explicit
ncclAllReduce nodes executed by a thread pool. The TPU-native equivalent is
GSPMD: ONE XLA module jitted over a jax.sharding.Mesh with the batch sharded
on the 'dp' axis and parameters replicated — the partitioner inserts the
gradient all-reduce over ICI automatically at the param-update seam, replacing
threads/streams/NCCL with compiled collectives.

BuildStrategy / ExecutionStrategy are kept API-compatible; most knobs are
no-ops by construction (XLA already fuses, orders collectives
deterministically, and GCs buffers), documented per-field.
"""

import weakref

import numpy as np

import jax
from jax.sharding import Mesh

from . import framework
from . import profiler as _prof
from .executor import (
    _CompiledBlock,
    _MultiStepBlock,
    _PipelinedBlock,
    _apply_pass_pipeline,
    _as_feed_array,
    _flags_opprof,
    _telemetry_begin,
    _telemetry_record,
    global_scope,
)
from .framework import Variable

__all__ = ["ParallelExecutor", "BuildStrategy", "ExecutionStrategy"]


class ReduceStrategy:
    """reference details/build_strategy.h ReduceStrategy"""

    AllReduce = 0
    Reduce = 1


class BuildStrategy:
    """Knobs from reference details/build_strategy.h (pybind.cc:746-833).
    On TPU: reduce_strategy maps AllReduce→gradient all-reduce with fully
    replicated optimizer state, Reduce→the ZeRO-1 tier (the reference's
    Reduce strategy likewise updated each parameter on ONE device and
    broadcast it back — reduce_op_handle.cc; here the update is sharded
    1/dp per rank instead of whole-param per owner): gradients
    reduce-scatter over 'dp', each rank updates its param+moment shard,
    params all-gather back, optimizer state stored sharded (÷dp memory).
    Fusion knobs are no-ops (XLA fuses); sequential/debug knobs are honored
    where meaningful."""

    ReduceStrategy = ReduceStrategy

    def __init__(self):
        self.reduce_strategy = ReduceStrategy.AllReduce
        self.gradient_scale_strategy = 0
        self.debug_graphviz_path = ""
        self.enable_data_balance = False
        self.fuse_elewise_add_act_ops = False  # XLA fuses; kept for compat
        self.fuse_broadcast_op = False
        self.enable_sequential_execution = False
        self.memory_optimize = False
        self.num_trainers = 1
        self.trainer_id = 0
        # pipeline parallelism depth: >1 makes ParallelExecutor build a
        # dp×pp mesh (all remaining devices on 'dp') and lower the program
        # through the pipeline partitioner (executor._PipelinedBlock).
        # Ignored when an explicit mesh_config is passed — set MeshConfig(pp=)
        # there instead.
        self.pipeline_stages = 1
        # graph-pass pipeline applied before lowering (paddle_tpu/passes,
        # docs/passes.md): a manager.PRESETS name or comma-separated pass
        # list; "" disables. None (default) defers to FLAGS_pass_pipeline.
        self.pass_pipeline = None
        # True -> lower tagged matmul+bias[+act] / layer_norm(+residual) /
        # adam-run chains through the hand-tuned Pallas kernels (the
        # "training_fused" preset; docs/passes.md kernel substitution).
        # Only consulted when pass_pipeline is None — an explicit pipeline
        # always wins.
        self.fuse_kernels = False
        # declarative placement over the dp×fsdp×tp×sp×ep×pp mesh: a
        # parallel.ShardingRules (or an iterable of (regex, spec) pairs)
        # mapping param/activation names -> PartitionSpec tuples, LAST match
        # wins. Merged AFTER any program-attached rules
        # (parallel.program_rules), so these win ties. This is how tensor
        # parallelism and FSDP are requested — see docs/parallelism.md
        # "Sharding rules". Requires a mesh_config naming the axes used
        # (e.g. MeshConfig(fsdp=4, tp=2)); axes the mesh lacks degrade to
        # replication.
        self.sharding_rules = None

    def resolved_pass_pipeline(self):
        """The pipeline the executor should apply: pass_pipeline verbatim
        when set (even ""), else "training_fused" when fuse_kernels, else
        None (defer to FLAGS_pass_pipeline)."""
        if self.pass_pipeline is not None:
            return self.pass_pipeline
        if self.fuse_kernels:
            return "training_fused"
        return None


class ExecutionStrategy:
    """reference ExecutionStrategy (pybind.cc:746): thread counts and scope
    reuse are meaningless under one compiled XLA module; kept for compat."""

    def __init__(self):
        self.num_threads = 0
        self.use_cuda = False
        self.allow_op_delay = False
        self.num_iteration_per_drop_scope = 1
        # pp-tier knobs (no-ops unless the mesh has pp > 1):
        # pipeline_schedule: "gpipe" (all forwards then all backwards; O(m)
        # live activations per rank) or "1f1b" (interleaved one-forward-
        # one-backward; O(pp) live activations — same bubble fraction,
        # (pp-1)/(m+pp-1), much flatter memory at large m).
        self.pipeline_schedule = "gpipe"
        # microbatch count m per dp-local batch; None → pp (the minimum that
        # fills the pipeline once).
        self.num_microbatches = None


class ParallelExecutor:
    """Drop-in for fluid.ParallelExecutor (reference parallel_executor.py:32).

    use_cuda is accepted and ignored (we always target the jax default
    backend: TPU on hardware, the forced CPU mesh in tests)."""

    def __init__(
        self,
        use_cuda=False,
        loss_name=None,
        main_program=None,
        share_vars_from=None,
        exec_strategy=None,
        build_strategy=None,
        num_trainers=1,
        trainer_id=0,
        scope=None,
        devices=None,
        mesh_config=None,
    ):
        self._program = main_program or framework.default_main_program()
        self._loss_name = loss_name
        self._build_strategy = build_strategy or BuildStrategy()
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._scope = scope or global_scope()
        if share_vars_from is not None:
            self._scope = share_vars_from._scope
        devices = devices if devices is not None else jax.devices()
        # reference: one rank per GPU per trainer (nccl_helper.h:115-120);
        # here: the mesh spans all local devices — pure 'dp' by default, or a
        # full dp×tp×sp×ep mesh via mesh_config (parallel.MeshConfig).
        # Multi-host (num_trainers>1) extends the mesh across processes (DCN).
        if mesh_config is not None:
            from .parallel import make_mesh

            self._mesh = make_mesh(mesh_config, devices)
        elif self._build_strategy.pipeline_stages > 1:
            from .parallel import MeshConfig, make_mesh

            self._mesh = make_mesh(
                MeshConfig(dp=-1, pp=self._build_strategy.pipeline_stages),
                devices,
            )
        else:
            self._mesh = Mesh(np.asarray(devices), ("dp",))
        self._cache = {}
        self._run_seq = 0  # run() calls: the `iter` tag of its spans

    @property
    def device_count(self):
        """Number of ways the batch is split: dp × fsdp (FSDP shards the
        batch too — it is data parallelism with sharded storage)."""
        dp = self._mesh.shape.get("dp", self._mesh.size)
        return dp * self._mesh.shape.get("fsdp", 1)

    @property
    def _data_axes(self):
        """Mesh axes the batch dim shards over. Extent-1 axes are dropped so
        the default Mesh(devices, ('dp',)) and fsdp-less configs keep their
        exact old specs."""
        axes = tuple(
            a for a in ("dp", "fsdp") if self._mesh.shape.get(a, 1) > 1
        )
        return axes or ("dp",)

    @property
    def topology(self):
        """Mesh axis extents + host count, the identity an elastic
        checkpoint manifest records (resilience/async_ckpt.py): a later
        resume compares its own topology against the saved one only for
        bookkeeping — restore itself is topology-blind."""
        import jax

        out = {name: int(ext) for name, ext in self._mesh.shape.items()}
        try:
            out["num_hosts"] = int(jax.process_count())
        except RuntimeError:
            out["num_hosts"] = 1
        return out

    def _install_reader_sharding(self):
        """Hand this PE's data-parallel placement to the program's readers
        (data-runtime mode stages batches with it, so they arrive on the
        mesh already split over 'dp' — no gather/re-scatter between the
        staging thread and the compiled step). Per-array callable: fields
        whose batch dim doesn't divide the mesh stay replicated."""
        dp = self.device_count
        if dp <= 1:
            return
        mesh = self._mesh
        axes = self._data_axes
        from jax.sharding import NamedSharding, PartitionSpec

        def shard_for(arr):
            shape = getattr(arr, "shape", None)
            if not shape or shape[0] % dp != 0:
                return None
            spec = PartitionSpec(axes, *([None] * (len(shape) - 1)))
            return NamedSharding(mesh, spec)

        for reader in getattr(self._program, "_py_readers", []):
            if hasattr(reader, "set_device_sharding"):
                reader.set_device_sharding(shard_for)

    def run(self, fetch_list, feed=None, feed_dict=None, return_numpy=True,
            steps_per_run=1):
        """steps_per_run > 1 compiles k iterations into one SPMD XLA call
        (executor._MultiStepBlock over this mesh): `feed` is then a dict of
        stacked arrays with leading axis k (a feed LIST keeps its reference
        meaning of per-DEVICE dicts and is only valid for k=1); fetches come
        back stacked [k, ...]."""
        self._run_seq += 1
        seq = {"iter": self._run_seq}
        # the same spans as Executor.run: fluid.executor.run and its three
        # phases prepare, call, finish
        with _prof.RecordEvent(span="executor.run", **seq):
            with _prof.RecordEvent(span="executor.prepare", **seq):
                feed = feed if feed is not None else (feed_dict or {})
                _obs, _obs_t0 = _telemetry_begin()
                force_multi = False  # 1-batch epoch tail keeps the [k, ...] contract
                if not feed:
                    # pull staged batches from started py_readers, like Executor.run
                    from .executor import _resolve_reader_feed

                    self._install_reader_sharding()
                    feed, steps_per_run, force_multi = _resolve_reader_feed(
                        self._program, steps_per_run
                    )
                is_multi = steps_per_run > 1 or force_multi
                if isinstance(feed, (list, tuple)):
                    if steps_per_run > 1:
                        raise TypeError(
                            "with steps_per_run>1 feed must be a dict of stacked "
                            "arrays (leading axis k); a feed list means per-device "
                            "dicts (reference parallel_executor.py:183-213)"
                        )
                    # reference API form: one dict per device (reference
                    # parallel_executor.py:183-213) — concatenate along the batch dim
                    merged = {}
                    for d in feed:
                        if not isinstance(d, dict):
                            raise TypeError(
                                "feed must be a dict or a list of per-device dicts; got "
                                "list of %r" % type(d).__name__
                            )
                        for k, v in d.items():
                            merged.setdefault(k, []).append(np.asarray(v))
                    feed = {k: np.concatenate(vs, axis=0) for k, vs in merged.items()}
                program = self._program
                fetch_names = [
                    f.name if isinstance(f, Variable) else str(f) for f in fetch_list
                ]
                # graph-pass choke point, mirroring Executor.run (docs/passes.md);
                # BuildStrategy.pass_pipeline overrides FLAGS_pass_pipeline when set
                program = _apply_pass_pipeline(
                    program, self._scope, list(feed.keys()), fetch_names,
                    pipeline=self._build_strategy.resolved_pass_pipeline(),
                )
                block = program.global_block()
                feed_arrays = {}
                batch_dim = 1 if is_multi else 0  # stacked feeds: [k, N, ...]
                for name, value in feed.items():
                    var = block.vars.get(name)
                    arr = _as_feed_array(value, var)
                    if (
                        len(arr.shape) > batch_dim
                        and arr.shape[batch_dim] % self.device_count != 0
                    ):
                        raise ValueError(
                            "batch dim %d of feed %r not divisible by device count %d "
                            "(reference PE splits the batch across devices the same way)"
                            % (arr.shape[batch_dim], name, self.device_count)
                        )
                    feed_arrays[name] = arr

                pp = self._mesh.shape.get("pp", 1)
                if pp > 1 and is_multi:
                    raise NotImplementedError(
                        "steps_per_run > 1 is not supported with pipeline "
                        "parallelism yet; run one step per call on a pp mesh"
                    )
                # declarative sharding rules: BuildStrategy's own (normalized to a
                # ShardingRules), merged by the compiled block AFTER any
                # program-attached rules. Both fingerprints go into the cache key —
                # rules hang off live objects and may grow between runs.
                from .parallel.sharding_rules import ShardingRules

                bs_rules = self._build_strategy.sharding_rules
                if bs_rules is not None and not isinstance(bs_rules, ShardingRules):
                    bs_rules = ShardingRules(bs_rules)
                prog_rules = getattr(program, "_sharding_rules", None)
                key = (
                    program._uid,
                    program._version,
                    tuple(sorted((n, a.shape, str(a.dtype)) for n, a in feed_arrays.items())),
                    tuple(fetch_names),
                    self._scope._uid,
                    steps_per_run,
                    force_multi and steps_per_run == 1,
                    (
                        self._exec_strategy.pipeline_schedule,
                        self._exec_strategy.num_microbatches,
                    )
                    if pp > 1
                    else None,
                    # toggling FLAGS_tensor_stats must recompile (executor.py key
                    # carries the same term)
                    _flags_opprof()["tensor_stats"],
                    bs_rules.fingerprint() if bs_rules is not None else None,
                    prog_rules.fingerprint() if prog_rules is not None else None,
                )
                compiled = self._cache.get(key)
                _obs_cache_hit = compiled is not None
                if compiled is None:
                    # FLAGS_static_verify (docs/static_analysis.md): mesh-aware lint —
                    # the analyzer resolves sharding specs through the same Resolver
                    # precedence the compile below uses
                    from .analysis import maybe_static_verify

                    maybe_static_verify(
                        program, list(feed_arrays.keys()), fetch_names,
                        scope=self._scope, mesh=self._mesh, rules=bs_rules,
                        mode="inference" if getattr(program, "_is_test", False)
                        else "training",
                        where="parallel_executor",
                    )
                    # feed_ranks are UNSTACKED ranks: rank 0 (scalars) replicate
                    feed_ranks = {
                        n: np.ndim(a) - batch_dim for n, a in feed_arrays.items()
                    }
                    # ReduceStrategy.Reduce → ZeRO-1 over the dp axis (BuildStrategy
                    # docstring); degrades to the replicated path when dp == 1
                    zero1_axis = (
                        "dp"
                        if self._build_strategy.reduce_strategy == ReduceStrategy.Reduce
                        and self._mesh.shape.get("dp", 1) > 1
                        else None
                    )
                    if pp > 1:
                        compiled = _PipelinedBlock(
                            program, block, list(feed_arrays.keys()), fetch_names,
                            self._scope, mesh=self._mesh, feed_ranks=feed_ranks,
                            zero1_axis=zero1_axis, sharding_rules=bs_rules,
                            loss_name=self._loss_name,
                            n_micro=self._exec_strategy.num_microbatches,
                            schedule=self._exec_strategy.pipeline_schedule,
                        )
                    elif is_multi:
                        compiled = _MultiStepBlock(
                            program, block, list(feed_arrays.keys()), fetch_names,
                            self._scope, steps_per_run, mesh=self._mesh,
                            data_axes=self._data_axes, feed_ranks=feed_ranks,
                            zero1_axis=zero1_axis, sharding_rules=bs_rules,
                        )
                    else:
                        compiled = _CompiledBlock(
                            program,
                            block,
                            list(feed_arrays.keys()),
                            fetch_names,
                            self._scope,
                            mesh=self._mesh,
                            data_axes=self._data_axes,
                            feed_ranks=feed_ranks,
                            zero1_axis=zero1_axis,
                            sharding_rules=bs_rules,
                        )
                    self._cache[key] = compiled
                    self._place_state(compiled)

                # place the global batch sharded over the mesh before dispatch;
                # rank-0 feeds (scalars like a lr) cannot be batch-sharded — replicate
                from jax.sharding import NamedSharding, PartitionSpec as P

                repl = NamedSharding(self._mesh, P())
                sharded = {
                    n: jax.device_put(
                        a,
                        compiled._feed_sharding
                        if np.ndim(a) > batch_dim
                        else repl,
                    )
                    for n, a in feed_arrays.items()
                }
            with _prof.RecordEvent(span="executor.call", **seq):
                fetches = compiled(self._scope, sharded)
            with _prof.RecordEvent(span="executor.finish", **seq):
                # correlation seed for compiled_hlo(): abstract feed shapes only
                # (concrete arrays would pin a batch of device memory), same
                # contract as Executor._last_run
                self._last_run = (
                    compiled,
                    weakref.ref(self._scope),
                    {
                        n: jax.ShapeDtypeStruct(a.shape, a.dtype)
                        for n, a in sharded.items()
                    },
                )
                result = [np.asarray(f) for f in fetches] if return_numpy else fetches
                if _obs is not None:
                    # pp runs carry their schedule so the collector can group step
                    # times by (pp, schedule, m) for the two-m-slope bubble gauge
                    plan = getattr(compiled, "stage_plan", None)
                    _telemetry_record(
                        _obs, _obs_t0, compiled, _obs_cache_hit, False,
                        steps_per_run if is_multi else 1, result, return_numpy,
                        pp=pp if pp > 1 else None,
                        n_micro=plan["n_micro"] if plan else None,
                        schedule=plan["schedule"] if plan else None,
                    )
                return result

    def _place_state(self, compiled):
        """Put the scope's state and its rng key on the mesh as the block's
        in_shardings will, before the block's first call. What a plain
        Executor's startup program left on one device has no mesh in its
        type, the step's own outputs have one, and jit keys its traces on
        that: without this the step is traced and compiled once for the first
        call and again for the second, two executables a program where one
        does (tbig_train_dp4: four of 46 MB a run, 184 MB of the 192 MiB the
        chip machine's compile cache may hold; PERF.md, PR 37)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        repl = NamedSharding(self._mesh, P())
        if not repl.is_fully_addressable:
            return  # a mesh over several processes: jit places its inputs
        wanted = dict(getattr(compiled, "_ro_sh", None) or {})
        wanted.update(getattr(compiled, "_mut_sh", None) or {})
        scope = self._scope
        for name, sharding in wanted.items():
            value = scope.vars.get(name)
            if isinstance(value, jax.Array) and value.sharding != sharding:
                scope.vars[name] = jax.device_put(value, sharding)
        if scope.rng_key.sharding != repl:
            scope.rng_key = jax.device_put(scope.rng_key, repl)

    def compiled_hlo(self):
        """Post-optimization HLO text of the most recently run SPMD block
        (Executor.compiled_hlo analog). Every collective the GSPMD partition
        inserted — the gradient all-reduce, or the ZeRO-1 reduce-scatter /
        all-gather pair under ReduceStrategy.Reduce — is visible here with
        shapes and replica_groups (tests/test_parallel_executor.py reads the
        ZeRO-1 gather from this text). Served from the backend's
        compilation cache after a run, so this does not recompile."""
        last = getattr(self, "_last_run", None)
        if last is None:
            raise RuntimeError("compiled_hlo() needs a prior ParallelExecutor.run")
        compiled, scope_ref, feed_avals = last
        scope = scope_ref()
        if scope is None:
            raise RuntimeError(
                "compiled_hlo(): the scope of the last run no longer exists"
            )
        ro = {n: scope.vars[n] for n in compiled.ro_names}
        mut = {n: scope.vars[n] for n in compiled.mut_names}
        lowered = compiled.jitted.lower(feed_avals, ro, mut, scope.rng_key)
        return lowered.compile().as_text()

    def drop_local_exe_scopes(self):  # compat no-op: no per-device scopes
        pass
