"""Executor: lowers a whole block to ONE jitted XLA computation and runs it.

Reference analog: paddle/fluid/framework/executor.cc:158 — but where the
reference interprets the block op-by-op (executor.cc:389-396, each op a
separate kernel launch), this executor generalizes the reference's nGraph seam
(executor.cc:91-107, its only "compile a region" precedent) to the WHOLE block:
every op's JAX lowering is stitched into a single traced function, jitted once
per (program version, feed shapes) and cached like the reference's Python
program cache (reference executor.py:285).

Mutability model: reference ops mutate named Variables in a Scope. Here the
trace threads an immutable name->array environment; an op "writes" a var by
rebinding the name. Persistable vars written by the block (params, optimizer
state, batch-norm running stats) come in as a donated pytree argument and go
out as updated state — giving in-place buffer semantics on TPU without mutable
aliasing inside XLA.

Scope (reference framework/scope.h) holds name -> jax.Array plus the PRNG key
that stochastic ops consume.
"""

import itertools
import sys
import threading
import time
import weakref

import numpy as np

import jax
import jax.numpy as jnp

from . import framework
from . import profiler as _prof
from .framework import Program, Variable, convert_np_dtype
from .ops import registry
from .ops.registry import EMPTY_VAR_NAME
from .place import TPUPlace

__all__ = [
    "Executor",
    "Scope",
    "global_scope",
    "scope_guard",
    "aot_serve_lowering",
]


def _flags_profile_ops():
    from . import flags as _flags

    return _flags.get_flags("profile_ops")["profile_ops"]


def _flags_opprof():
    """The op-attribution flags (observability/opprof.py) in ONE flags
    lookup — the entire hot-path cost of the feature when disabled."""
    from . import flags as _flags

    return _flags.get_flags(("tensor_stats", "nan_provenance"))


def _apply_pass_pipeline(program, scope, feed_names, fetch_names, pipeline=None):
    """The single choke point where graph passes rewrite a program before
    lowering (paddle_tpu/passes, docs/passes.md). Both executors and
    aot_serve_lowering route through here. `pipeline` None defers to
    FLAGS_pass_pipeline; ""/"off"/() disables and returns the program
    untouched. The transformed program is memoized per (program version,
    pipeline, scope, feed/fetch), so repeated runs hand the executor the
    SAME object and its compile cache stays hot."""
    if pipeline is None:
        from . import flags as _flags

        pipeline = _flags.get_flags("pass_pipeline")["pass_pipeline"]
    from .passes import manager as _pm

    if not _pm.resolve_pipeline(pipeline):
        return program
    out = _pm.apply_cached(
        program, pipeline, scope=scope,
        feed_names=feed_names, fetch_names=fetch_names,
    )
    # sharding rules live on the Program OBJECT (parallel.sharding_rules
    # .program_rules); the rewritten program shares the source's rule set so
    # placement survives the pipeline (mutations propagate — the executor
    # cache key carries the rules' fingerprint)
    rules = getattr(program, "_sharding_rules", None)
    if out is not program and rules is not None:
        out._sharding_rules = rules
    return out


def _compiled_ops(compiled):
    """The fluid op list behind any compiled-block flavor (for NaN
    provenance and the check_nan_inf last-writer report)."""
    ops = getattr(compiled, "ops", None)
    if ops is None:
        inner = getattr(compiled, "_inner", None)  # _MultiStepBlock
        ops = getattr(inner, "ops", None)
    if ops is None:
        blk = getattr(compiled, "block", None)  # _SegmentedBlock
        ops = blk.ops if blk is not None else None
    return ops or ()


def _last_writer(compiled, var_name):
    """Display name of the LAST op in program order writing `var_name`, or
    None — names the suspect in the check_nan_inf report (ops are anonymous;
    the variable is the only handle the error has)."""
    from .observability import opprof as _opprof

    found = None
    try:
        for op in _compiled_ops(compiled):
            if var_name in op.output_arg_names:
                found = _opprof.op_display_name(op)
    except Exception:
        return None
    return found


def _localize_nan(compiled, scope, feed_arrays, rng_key, reason, step=None,
                  mut_override=None):
    """FLAGS_nan_provenance driver: replay the failed step's feed through
    opprof.localize_nonfinite over the block's op list, against the step's
    PRE-state (`mut_override` = the guard's pre-step snapshot when it has
    one, else the scope as-is) and pre-step rng key. Returns the written
    provenance record or None; never raises (diagnosis must not mask the
    original failure)."""
    ops = _compiled_ops(compiled)
    if not ops:
        return None
    if isinstance(compiled, _MultiStepBlock):
        # a k-step scan's feed is stacked [k, ...]; replaying it as one
        # step would walk garbage shapes — provenance needs steps_per_run=1
        if not getattr(_localize_nan, "_warned_multi", False):
            _localize_nan._warned_multi = True
            print(
                "[nan_provenance] skipped: steps_per_run>1 runs cannot be "
                "replayed per-op (rerun the failing step with "
                "steps_per_run=1)", file=sys.stderr,
            )
        return None
    from .observability import opprof as _opprof

    try:
        env = {n: v for n, v in scope.vars.items() if v is not None}
        if mut_override:
            for n, v in mut_override.items():
                env[n] = jnp.asarray(v)
        feed_want = getattr(compiled, "_feed_want", {})
        for n, v in feed_arrays.items():
            a = v if isinstance(v, jax.Array) else jnp.asarray(v)
            want = feed_want.get(n)
            if want is not None and a.dtype != want:
                a = a.astype(want)
            env[n] = a
        prov = _opprof.localize_nonfinite(
            ops, env, rng_key if rng_key is not None else scope.rng_key,
            step=step,
        )
        if prov is None:
            return None
        return _opprof.write_provenance(prov, reason=reason)
    except Exception as e:
        if not getattr(_localize_nan, "_warned", False):
            _localize_nan._warned = True
            print("[nan_provenance] replay failed: %r" % e, file=sys.stderr)
        return None


_ELASTIC_HB = None


def _elastic_heartbeat():
    """Beat the elastic watchdog (resilience/elastic.py). The import is
    resolved once and cached; afterwards the disabled path is one function
    call + one empty-list probe per run."""
    global _ELASTIC_HB
    hb = _ELASTIC_HB
    if hb is None:
        from .resilience.elastic import heartbeat as hb

        _ELASTIC_HB = hb
    hb()


def _telemetry_begin():
    """(collector, t0) when telemetry is active, else (None, None) — the
    disabled path costs one flags lookup per run (observability.stepstats)."""
    from .observability import stepstats as _ss

    if not _ss.active():
        return None, None
    return _ss.collector(), time.perf_counter()


def _telemetry_record(obs, t0, compiled, cache_hit, nan_trip, n_steps,
                      result, return_numpy, pp=None, n_micro=None,
                      schedule=None):
    """Shared Executor/ParallelExecutor step-record tail. Loss is extracted
    best-effort from the first fetch ONLY when it is already host-side
    (return_numpy) — telemetry must never add a device sync of its own. A
    telemetry failure (e.g. export-dir IO) must never fail the step: it is
    reported once and swallowed."""
    wall_ms = (time.perf_counter() - t0) * 1e3
    loss = None
    if return_numpy and result:
        try:
            a = np.asarray(result[0])
            if a.size >= 1 and a.dtype.kind == "f":
                # multi-step fetches come back [k, ...]: report the last step
                loss = float(a.reshape(-1)[-1])
        except (TypeError, ValueError):
            pass
    try:
        obs.record_step(
            wall_ms, n_steps=n_steps, cache_hit=cache_hit, nan_trip=nan_trip,
            pp=pp, n_micro=n_micro, schedule=schedule, loss=loss,
            training=bool(getattr(compiled, "mut_names", ())),
        )
    except Exception as e:
        if not getattr(_telemetry_record, "_warned", False):
            _telemetry_record._warned = True
            print("telemetry record failed (disabled for this message): %r"
                  % e, file=sys.stderr)


class Scope:
    """name -> device array store (reference scope.h:134, flat not hierarchical
    — per-iteration locals are SSA temporaries inside the jitted function, so
    child scopes are unnecessary)."""

    _uid_counter = itertools.count()

    def __init__(self, seed=0):
        self.vars = {}
        self._seed = seed
        self._rng_key = None  # lazy: creating a key initializes the backend
        # monotonic uid for executable-cache keys (id() can be reused after GC)
        self._uid = next(Scope._uid_counter)

    @property
    def rng_key(self):
        if self._rng_key is None:
            self._rng_key = jax.random.key(self._seed)
        return self._rng_key

    @rng_key.setter
    def rng_key(self, value):
        self._rng_key = value

    def find_var(self, name):
        return self.vars.get(name)

    def var_names(self):
        """reference scope.h LocalVarNames()"""
        return list(self.vars)

    def var(self, name):
        return self.vars.setdefault(name, None)

    def set_var(self, name, value):
        self.vars[name] = value

    def drop_kids(self):  # compat no-op
        pass


_global_scope = Scope()
_scope_tls = threading.local()


def _scope_stack():
    # per-thread stack (pserver serving loops and AsyncExecutor workers each
    # run under their own scope_guard concurrently; the reference's Scope use
    # is likewise per-thread)
    st = getattr(_scope_tls, "stack", None)
    if st is None:
        st = _scope_tls.stack = [_global_scope]
    return st


def global_scope():
    return _scope_stack()[-1]


class scope_guard:
    def __init__(self, scope):
        self.scope = scope

    def __enter__(self):
        _scope_stack().append(self.scope)

    def __exit__(self, *args):
        _scope_stack().pop()


def _as_feed_array(value, var):
    want = None
    if var is not None and var.dtype is not None:
        want = jnp.bfloat16 if var.dtype == "bfloat16" else np.dtype(var.dtype)
    if isinstance(value, jax.Array):
        # device-resident feed: any needed cast happens inside the compiled
        # block (_CompiledBlock.run), where it fuses into the step instead of
        # costing an eager per-step device dispatch. Paths that do NOT run
        # through _CompiledBlock.run (per-op profiling, host-op segmented
        # programs) eager-cast via _eager_cast_feeds below.
        return value
    arr = np.asarray(value)
    if want is not None:
        arr = arr.astype(want)
    return arr


def _eager_cast_feeds(block, feed_arrays):
    """Cast device-resident feeds to their declared var dtypes NOW — for
    execution paths that bypass _CompiledBlock.run's fused trace-time cast
    (_PerOpProfiledBlock, _SegmentedBlock), which consume env values
    directly."""
    out = {}
    for name, value in feed_arrays.items():
        if isinstance(value, jax.Array):
            var = block.vars.get(name)
            if var is not None and var.dtype is not None:
                want = jnp.dtype(
                    jnp.bfloat16 if var.dtype == "bfloat16" else np.dtype(var.dtype)
                )
                if value.dtype != want:
                    value = value.astype(want)
        out[name] = value
    return out


class _CompiledBlock:
    """A lowered + jitted block: knows its state split (read-only vs mutated
    persistables) and fetch names.

    With `mesh`, the same lowering compiles SPMD (the TPU-native replacement
    for the reference's ParallelExecutor SSA graph + NCCL, SURVEY.md §2.2):
    feeds are batch-sharded over the mesh's data axes, state is replicated,
    and XLA's GSPMD partitioner inserts the gradient all-reduce over ICI at
    the same seam where the reference's multi_devices_graph_pass inserted
    ncclAllReduce ops.

    With `zero1_axis` (ParallelExecutor under ReduceStrategy.Reduce), the
    optimizer tier runs ZeRO-1 sharded over that axis: optimizer-state
    tensors (momentum velocities, Adam moments — core_ops.ZERO1_STATE_SLOTS)
    are STORED sharded 1/dp per rank via their in/out_shardings, and the
    optimizer lowerings (core_ops._opt_f32 reading ctx.zero1_axis) constrain
    grad/param/moments so GSPMD emits reduce-scatter + sharded update +
    param all-gather in place of the gradient all-reduce — identical wire
    volume, optimizer-state memory and HBM traffic ÷ dp
    (docs/parallelism.md)."""

    def __init__(self, program, block, feed_names, fetch_names, scope, mesh=None,
                 data_axes=("dp",), feed_ranks=None, ops_override=None,
                 zero1_axis=None, sharding_rules=None, instrument=True):
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        src_ops = block.ops if ops_override is None else ops_override
        ops = [
            op
            for op in src_ops
            if not registry.get(op.type).skip_exec
        ] if all(registry.is_registered(op.type) for op in src_ops) else None
        if ops is None:
            unknown = [op.type for op in src_ops if not registry.is_registered(op.type)]
            raise NotImplementedError("ops without lowering: %s" % sorted(set(unknown)))
        if any(registry.get(op.type).is_host for op in ops):
            raise RuntimeError(
                "host ops (send/recv/listen_and_serv...) cannot be jitted; "
                "run this block through Executor, which partitions at host ops"
            )
        self.ops = ops

        # classify external inputs: fed names are args; persistable names found
        # in the scope are state; anything else must be produced by the block.
        produced = set()
        state_names = []
        fed = set(self.feed_names)
        for op in self.ops:
            for name in op.input_arg_names:
                if name == EMPTY_VAR_NAME:
                    continue
                if name in fed or name in produced or name in state_names:
                    continue
                if scope.find_var(name) is not None:
                    state_names.append(name)
                else:
                    v = block.has_var_recursive(name) and block._var_recursive(name)
                    raise RuntimeError(
                        "variable %r used by op %s is neither fed, in scope, nor "
                        "produced earlier in the block (var=%s)" % (name, op, v)
                    )
            produced.update(n for n in op.output_arg_names if n != EMPTY_VAR_NAME)
        # fetches may be state too (e.g. fetch a param without running ops on it)
        for name in self.fetch_names:
            if name not in fed and name not in produced and name not in state_names:
                if scope.find_var(name) is not None:
                    state_names.append(name)
                else:
                    raise RuntimeError("fetch var %r has no value" % name)

        persistable = {
            name
            for name in state_names + list(produced)
            if block.has_var_recursive(name) and block._var_recursive(name).persistable
        }
        written = set()
        for op in self.ops:
            written.update(n for n in op.output_arg_names if n != EMPTY_VAR_NAME)
        # state already in scope and rewritten by the block → donated + returned
        self.mut_names = sorted(set(state_names) & written)
        self.ro_names = sorted(set(state_names) - written)
        # persistables created inside the block (e.g. startup initializers)
        self.created_persistables = sorted((persistable & produced) - set(state_names) - fed)

        # cross-check against the inplace_donation_plan pass when one rode in
        # on this program AND it analyzed this exact lowering (same scope,
        # feed, fetch, nothing unanalyzable). The plan is the verified source
        # of truth at this seam: divergence means a pass corrupted def-use or
        # the classifications drifted — fail loudly, not with silent
        # mis-donation (docs/passes.md).
        plan = getattr(program, "_donation_plan", None)
        if (
            plan
            and ops_override is None  # segments lower op SUBSETS the plan never saw
            and not plan.get("unknown")
            and plan.get("scope_uid") == scope._uid
            and plan.get("feed") == sorted(self.feed_names)
            and list(plan.get("fetch", ())) == list(self.fetch_names)
        ):
            if plan["mut"] != self.mut_names or plan["ro"] != self.ro_names:
                raise RuntimeError(
                    "inplace_donation_plan disagrees with the lowering's "
                    "state classification: plan mut=%s ro=%s vs lowered "
                    "mut=%s ro=%s — a pass likely corrupted def-use edges"
                    % (plan["mut"], plan["ro"], self.mut_names, self.ro_names)
                )

        ops_ = self.ops

        # declared feed-var dtypes: device-resident feeds arrive uncast (see
        # _as_feed_array) and are cast here at trace time, so the convert
        # fuses into the compiled step
        feed_want = {}
        for _n in self.feed_names:
            _v = block.vars.get(_n)
            if _v is None and block.has_var_recursive(_n):
                _v = block._var_recursive(_n)
            if _v is not None and getattr(_v, "dtype", None) is not None:
                feed_want[_n] = jnp.dtype(
                    jnp.bfloat16 if _v.dtype == "bfloat16" else np.dtype(_v.dtype)
                )
        self._feed_want = feed_want

        # ZeRO-1 active only when the mesh actually has >1 rank on the axis
        # (a dp=1 mesh degrades to the plain replicated path, same program)
        z1 = (
            zero1_axis
            if mesh is not None
            and zero1_axis
            and mesh.shape.get(zero1_axis, 1) > 1
            else None
        )
        self.zero1_axis = z1
        self._feed_ranks = dict(feed_ranks or {})

        # FLAGS_tensor_stats instrumentation pass (observability/opprof.py):
        # matched ops get output stats computed INSIDE the compiled step.
        # The flag value is part of the executor cache key, so toggling it
        # recompiles rather than serving a stale (un)instrumented block.
        # instrument=False for wrappers that would drop the side output
        # (_MultiStepBlock's scan body discards created).
        self._tstat_spec = ()
        self._tstat_traced = ()
        if instrument:
            pat = _flags_opprof()["tensor_stats"]
            if pat:
                from .observability import opprof as _opprof

                self._tstat_spec = _opprof.stats_spec(self.ops, pat)

        run = self._build_run(ops_, feed_want, mesh, z1)

        self.fn = run  # un-jitted lowering, reusable by __graft_entry__ et al.
        # donate the mutated-state pytree: params update in place on device
        if mesh is None:
            self.jitted = jax.jit(run, donate_argnums=(2,))
            self._feed_sharding = None
            self.zero1_state_names = []
            self._resolver = None
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P

            batch = NamedSharding(mesh, P(data_axes))
            repl = NamedSharding(mesh, P())
            self._feed_sharding = batch

            # the declarative sharding-rule engine (parallel/sharding_rules):
            # program-attached rules first, then BuildStrategy rules so the
            # caller wins ties under last-match. The resolver also layers the
            # legacy per-var sharding_spec attr and (below) the zero1 tier,
            # making it the block's single placement source of truth.
            from .parallel.sharding_rules import Resolver, ShardingRules

            combined = ShardingRules()
            combined.extend(getattr(program, "_sharding_rules", None))
            combined.extend(sharding_rules)

            def var_lookup(name):
                try:
                    return block._var_recursive(name)
                except KeyError:
                    return None

            resolver = Resolver(mesh, rules=combined, var_lookup=var_lookup)
            resolver.add_aliases(self.ops)
            self._resolver = resolver
            # dead-rule audit (analysis/sharding_dead_rules): a pattern that
            # matches neither a declared var nor a scope resident is a typo
            # silently replicating its target — surface it once per compile
            if len(combined):
                audit_names = set(scope.vars)
                for b in program.blocks:
                    audit_names.update(b.vars)
                resolver.audit(audit_names)

            # ZeRO-1: optimizer-state tensors live sharded 1/dp per rank —
            # the ÷dp state-memory/HBM win. Names come from the optimizer
            # ops' state input slots; only tensors whose leading dim divides
            # the axis shard (scalars like Beta*Pow stay replicated). State
            # whose PARAM has a rule/attr layout is excluded: the rule tier
            # (FSDP/TP) stores it in the param's spec instead.
            zero1_names = set()
            if z1 is not None:
                from .ops.core_ops import ZERO1_STATE_SLOTS
                from .parallel.collectives import zero1_shardable

                for op in self.ops:
                    for slot in ZERO1_STATE_SLOTS.get(op.type, ()):
                        for name in op.inputs.get(slot, ()):
                            val = scope.find_var(name)
                            if (
                                val is not None
                                and zero1_shardable(np.shape(val), mesh, z1)
                                and resolver.rule_spec(name, np.shape(val))
                                is None
                            ):
                                zero1_names.add(name)
            self.zero1_state_names = sorted(zero1_names)
            resolver.set_zero1(z1, zero1_names)

            def state_sharding(name):
                """Resolver verdict for one state tensor: explicit rules >
                accumulator alias > legacy shard_parameter attr > ZeRO-1
                state > replicated, pruned so axes the current mesh doesn't
                have degrade to replication (the same program runs on any
                mesh — e.g. distributed_embedding under a dp-only PE)."""
                val = scope.find_var(name)
                shape = np.shape(val) if val is not None else None
                return resolver.named_sharding(name, shape)

            # rank-0 feeds (scalars) cannot be batch-sharded — replicate them
            feed_ranks = feed_ranks or {}
            feed_sh = {
                n: (batch if feed_ranks.get(n, 1) else repl)
                for n in self.feed_names
            }
            ro_sh = {n: state_sharding(n) for n in self.ro_names}
            mut_sh = {n: state_sharding(n) for n in self.mut_names}
            # stashed for _MultiStepBlock, which reuses this block's analysis
            self._ro_sh, self._mut_sh = ro_sh, mut_sh
            # created dict's membership is only known at trace time (ops may
            # omit declared outputs), so its sharding is left to XLA (None)
            out_sh = (
                [repl] * len(self.fetch_names),
                {n: state_sharding(n) for n in self.mut_names},
                None,
                repl,
            )
            self.jitted = jax.jit(
                run,
                donate_argnums=(2,),
                in_shardings=(feed_sh, ro_sh, mut_sh, repl),
                out_shardings=out_sh,
            )

    def _build_run(self, ops_, feed_want, mesh, z1):
        """The block's lowering closure (overridden by _PipelinedBlock, which
        replaces the straight-line interpretation with the pp schedule)."""

        def run(feeds, ro_state, mut_state, rng_key):
            feeds = {
                n: (
                    v.astype(feed_want[n])
                    if n in feed_want and v.dtype != feed_want[n]
                    else v
                )
                for n, v in feeds.items()
            }
            env = {}
            env.update(ro_state)
            env.update(mut_state)
            env.update(feeds)
            ctx = registry.LowerCtx(
                rng_key, mesh=mesh, zero1_axis=z1,
                sharding=getattr(self, "_resolver", None),
            )
            registry.lower_ops(ctx, ops_, env)
            fetches = [env[n] for n in self.fetch_names]
            new_mut = {n: env[n] for n in self.mut_names}
            # an op may legally omit a declared output slot (lowering returns
            # None) — only bind names that actually materialized
            created = {n: env[n] for n in self.created_persistables if n in env}
            if self._tstat_spec:
                stats = self._trace_tensor_stats(env)
                if stats is not None:
                    # ride the created dict out of the jit: its sharding is
                    # already None (XLA's choice) and __call__ pops the key
                    # before it can reach the scope — ONE host sync per run,
                    # same trick as the nan-guard stacked reduce
                    from .observability.opprof import TENSOR_STATS_KEY

                    created[TENSOR_STATS_KEY] = stats
            return fetches, new_mut, created, ctx.key

        return run

    def _trace_tensor_stats(self, env):
        """FLAGS_tensor_stats: stats rows [mean, std, absmax, nonfinite] for
        every instrumented output present in the traced env, stacked into ONE
        [n, 4] f32 array. Runs AT TRACE TIME inside _build_run; the matched
        display names land on self (trace-time self mutation, the same
        pattern as _PipelinedBlock.stage_plan) so __call__ can label the
        host-side rows without retracing."""
        names, rows = [], []
        for display, var in self._tstat_spec:
            v = env.get(var)
            if v is None:
                continue
            a = jnp.asarray(v)
            if not jnp.issubdtype(a.dtype, jnp.floating):
                continue
            x = a.astype(jnp.float32)
            names.append(display)
            rows.append(
                jnp.stack([
                    x.mean(),
                    x.std(),
                    jnp.abs(x).max() if x.size else jnp.float32(0),
                    jnp.sum(~jnp.isfinite(x)).astype(jnp.float32),
                ])
            )
        self._tstat_traced = tuple(names)
        if not rows:
            return None
        return jnp.stack(rows)

    def __call__(self, scope, feed_arrays):
        ro = {n: scope.vars[n] for n in self.ro_names}
        mut = {n: scope.vars[n] for n in self.mut_names}
        fetches, new_mut, created, new_key = self.jitted(
            feed_arrays, ro, mut, scope.rng_key
        )
        stats = None
        if self._tstat_spec and isinstance(created, dict):
            from .observability.opprof import TENSOR_STATS_KEY

            stats = created.pop(TENSOR_STATS_KEY, None)
        scope.vars.update(new_mut)
        scope.vars.update(created)
        scope.rng_key = new_key
        if stats is not None:
            from .observability import opprof as _opprof

            try:
                # the leg's single host sync: one small [n, 4] transfer
                _opprof.record_tensor_stats(
                    self._tstat_traced, np.asarray(stats)
                )
            except Exception as e:
                if not getattr(_CompiledBlock, "_tstat_warned", False):
                    _CompiledBlock._tstat_warned = True
                    print(
                        "tensor_stats record failed (disabled for this "
                        "message): %r" % e, file=sys.stderr,
                    )
        return fetches


def aot_serve_lowering(program, feed_names, fetch_names, scope,
                       pass_pipeline="inference", return_state=False):
    """Donation-free forward lowering for ahead-of-time serving.

    The serving side (inference.export_compiled, serving.engine) needs the
    block's pure lowering WITHOUT the training executor's buffer-donation
    jit: a serving replica calls the same compiled variant from many request
    threads, so parameters must stay valid across calls. Returns
    (serve, ro, mut) where `serve(feeds, ro, mut) -> [fetches]` is a
    jit/export-able closure over the block's op lowerings, and ro/mut are the
    scope's read-only / block-rewritten persistables, passed as ARGUMENTS
    (not baked constants) so one artifact serves any parameter values of the
    same shapes. The scope's rng key is captured at trace time — inference
    programs are pruned of training-only stochastic ops by clone(for_test),
    so the key never advances.

    `return_state=True` is the decode-state mode (serving.generation): the
    closure becomes `serve(feeds, ro, mut) -> ([fetches], new_mut)` so a
    stateful caller (KV-cache pools) can thread the rewritten state dict to
    the next step and jit the wrapper with `donate_argnums=(2,)` — the
    single-shot default stays donation-free by construction.

    `pass_pipeline` (default: the "inference" preset, docs/passes.md) runs
    fold/DCE/fusion-tagging over the program before lowering; pass "" / None
    to lower the program verbatim.
    """
    program = _apply_pass_pipeline(
        program, scope, list(feed_names), list(fetch_names),
        pipeline=pass_pipeline if pass_pipeline else "off",
    )
    from .analysis import maybe_static_verify

    maybe_static_verify(
        program, list(feed_names), list(fetch_names), scope=scope,
        mode="serving", where="aot_serve",
    )
    block = program.global_block()
    compiled = _CompiledBlock(
        program, block, list(feed_names), list(fetch_names), scope,
        instrument=False,
    )
    ro = {n: scope.vars[n] for n in compiled.ro_names}
    mut = {n: scope.vars[n] for n in compiled.mut_names}
    rng_key = scope.rng_key

    if return_state:

        def serve(feeds, ro_, mut_):
            fetches, new_mut, _, _ = compiled.fn(feeds, ro_, mut_, rng_key)
            return fetches, new_mut

    else:

        def serve(feeds, ro_, mut_):
            # compiled.fn is the un-jitted lowering: (feeds, ro, mut, key) ->
            # (fetches, new_mut, created, key); serving wants fetches only
            fetches, _, _, _ = compiled.fn(feeds, ro_, mut_, rng_key)
            return fetches

    return serve, ro, mut


class _PipelinedBlock(_CompiledBlock):
    """Pipeline-parallel lowering of a whole training block over the mesh's
    'pp' axis (ParallelExecutor with MeshConfig(pp>1)).

    Where _CompiledBlock interprets the block straight-line under GSPMD,
    this block re-expresses it as a microbatch pipeline:

    1. ops split by op_role: forward (Forward/Loss) vs backward (skipped —
       the schedule differentiates the forward itself) vs optimizer
       (Optimize/LRSched, re-run verbatim after the pipeline so ZeRO-1,
       bf16 moments, lr schedules and clipping compose unchanged);
    2. the forward op list is cut into pp contiguous stages — explicit
       `device_guard("pp:k")` annotations win, otherwise
       parallel.partition balances analytic per-op roofline time + param
       bytes over the LEGAL cut points (every value crossing a cut must be
       microbatch-major so it can ride the packed boundary buffer);
    3. each stage's params stay canonical named tensors (replicated, the
       scope's layout) and enter the shard_map as a plain dict with P()
       specs — per-stage param pytrees are heterogeneous, and each rank's
       branch reads only its own stage's entries; inter-stage boundary
       activations are packed into a uniform [mb, K] f32 buffer;
    4. inside one shard_map over the full mesh, lax.switch on
       axis_index('pp') dispatches this rank's stage subgraph
       (registry.lower_ops on its op slice), and parallel.pipeline's
       GPipe or 1F1B engine runs the schedule; 'dp' keeps its meaning —
       each dp slice pipelines its own batch shard, grads pmean over dp;
    5. gradients come back as a dict (assembled across stages by the
       shard_map transpose / an explicit psum over 'pp'), are bound under
       the program's own `<param>@GRAD` names, and the block's optimizer
       ops run through registry.lower_ops exactly as in _CompiledBlock —
       same scope layout, so checkpoint save/resume, donation and the
       ZeRO-1 dp tier are untouched.

    Contracts/limits (all raised with guidance): the loss (and any fetched
    forward value) must land in the LAST stage; forward ops may not write
    persistable state (running stats); a parameter may be read by only one
    stage; fetched last-stage values are combined across microbatches by
    MEAN (exact for batch-mean losses/metrics).
    """

    def __init__(self, program, block, feed_names, fetch_names, scope,
                 mesh, feed_ranks=None, zero1_axis=None, sharding_rules=None,
                 loss_name=None, n_micro=None, schedule="gpipe"):
        if schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                "pipeline schedule must be 'gpipe' or '1f1b', got %r"
                % (schedule,)
            )
        if "pp" not in mesh.shape or mesh.shape["pp"] < 2:
            raise ValueError("_PipelinedBlock needs a mesh with pp >= 2")
        self._pp_opts = {
            "loss_name": loss_name, "n_micro": n_micro, "schedule": schedule,
        }
        self.stage_plan = None  # filled at first trace
        # instrument=False: the pp schedule's shard_map body has no place
        # for the straight-line stats side output (FLAGS_tensor_stats is a
        # single-device/dp diagnosis knob, docs/observability.md)
        super().__init__(
            program, block, feed_names, fetch_names, scope,
            mesh=mesh, feed_ranks=feed_ranks, zero1_axis=zero1_axis,
            sharding_rules=sharding_rules, instrument=False,
        )

    # packable boundary dtypes: everything is carried as f32 in the boundary
    # buffer via value-preserving casts (bf16/f16/bool/small ints are exact;
    # int32 is exact below 2^24 — larger ids crossing a cut need device_guard)
    _PACK_DTYPES = frozenset([
        "float32", "bfloat16", "float16", "bool",
        "int8", "uint8", "int16", "int32", "uint32",
    ])

    def _build_run(self, ops_, feed_want, mesh, z1):
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from .framework import GRAD_VAR_SUFFIX, OpRole
        from .parallel import partition as pp_partition
        from .parallel.collectives import shard_map
        from .parallel.pipeline import pipeline_1f1b_spmd, pipeline_fwd_spmd

        pp = mesh.shape["pp"]
        opts = self._pp_opts
        self_ = self

        def role(op):
            return int(op.attrs.get(OpRole.OP_ROLE_KEY, 0))

        skip_mask = OpRole.Backward | OpRole.Optimize | OpRole.LRSched
        fwd_ops = [op for op in ops_ if not role(op) & skip_mask]
        opt_ops = [
            op for op in ops_
            if role(op) & (OpRole.Optimize | OpRole.LRSched)
        ]
        if not fwd_ops:
            raise RuntimeError("pipeline lowering: block has no forward ops")

        # trainable params = the optimizer section's Param slots
        param_set = set()
        for op in opt_ops:
            param_set.update(op.inputs.get("Param", ()))
        state_set = set(self.ro_names) | set(self.mut_names)
        param_set &= state_set

        fwd_written_state = sorted(
            {n for op in fwd_ops for n in op.output_arg_names} & state_set
        )
        if fwd_written_state:
            raise NotImplementedError(
                "pipeline-parallel lowering cannot thread forward-op state "
                "updates (%s) through the microbatch schedule; run these on "
                "a non-pp mesh" % (fwd_written_state,)
            )

        loss_name = opts["loss_name"]
        if loss_name is None:
            for op in fwd_ops:
                if role(op) & OpRole.Loss:
                    outs = [
                        n for n in op.output_arg_names if n != EMPTY_VAR_NAME
                    ]
                    if outs:
                        loss_name = outs[0]
                        break
        if loss_name is None:
            raise ValueError(
                "pipeline parallelism needs the loss: pass loss_name= to "
                "ParallelExecutor (no op in the block carries the Loss role)"
            )

        feed_ranks = self._feed_ranks
        fetch_names = list(self.fetch_names)

        def run(feeds, ro_state, mut_state, rng_key):
            feeds = {
                n: (
                    v.astype(feed_want[n])
                    if n in feed_want and v.dtype != feed_want[n]
                    else v
                )
                for n, v in feeds.items()
            }
            dp = mesh.shape.get("dp", 1)
            batch_feeds = {
                n: v for n, v in feeds.items()
                if np.ndim(v) > 0 and feed_ranks.get(n, np.ndim(v)) > 0
            }
            scalar_feeds = {
                n: v for n, v in feeds.items() if n not in batch_feeds
            }
            if not batch_feeds:
                raise ValueError(
                    "pipeline lowering needs at least one batch-major feed"
                )
            B = next(iter(batch_feeds.values())).shape[0]
            for n, v in batch_feeds.items():
                if v.shape[0] != B:
                    raise ValueError(
                        "batch feeds disagree on batch size: %r has %d, "
                        "expected %d" % (n, v.shape[0], B)
                    )
            if B % dp:
                raise ValueError(
                    "global batch %d not divisible by dp=%d" % (B, dp)
                )
            b_local = B // dp
            m = opts["n_micro"] or pp
            if b_local % m:
                raise ValueError(
                    "dp-local batch %d not divisible into %d microbatches "
                    "(set ExecutionStrategy.num_microbatches)" % (b_local, m)
                )
            mb = b_local // m

            state_env = {}
            state_env.update(ro_state)
            state_env.update(mut_state)

            # ---- abstract forward pass at microbatch scale: per-op output
            # avals drive cut legality, cost weights and packing layouts
            mb_feed_avals = {
                n: jax.ShapeDtypeStruct((mb,) + tuple(v.shape[1:]), v.dtype)
                for n, v in batch_feeds.items()
            }

            def absrun(bf, sf, st, key):
                env = {}
                env.update(st)
                env.update(sf)
                env.update(bf)
                ctx = registry.LowerCtx(key, mesh=None)
                recs = []
                for op in fwd_ops:
                    registry.lower_ops(ctx, [op], env)
                    recs.append({
                        n: env[n]
                        for n in op.output_arg_names
                        if n != EMPTY_VAR_NAME and n in env
                    })
                return recs

            recs = jax.eval_shape(
                absrun, mb_feed_avals, scalar_feeds, state_env, rng_key
            )

            producers = {}  # name -> [(op_idx, aval)] in program order
            for i, rec in enumerate(recs):
                for n, av in rec.items():
                    producers.setdefault(n, []).append((i, av))
            if loss_name not in producers:
                raise ValueError(
                    "loss %r is not produced by the forward ops" % loss_name
                )
            loss_idx = producers[loss_name][-1][0]
            n_ops = len(fwd_ops)

            # live values crossing each candidate cut k (between op k, k+1)
            crossing = [dict() for _ in range(max(n_ops - 1, 0))]
            for j, op in enumerate(fwd_ops):
                for n in op.input_arg_names:
                    if n == EMPTY_VAR_NAME:
                        continue
                    plist = [
                        (i, av) for (i, av) in producers.get(n, []) if i < j
                    ]
                    if not plist:
                        continue  # fed / state: available on every rank
                    i, av = plist[-1]
                    for k in range(i, min(j, n_ops - 1)):
                        crossing[k][n] = av

            def packable(av):
                return (
                    len(av.shape) >= 1
                    and av.shape[0] == mb
                    and str(jnp.dtype(av.dtype)) in self_._PACK_DTYPES
                )

            legal = [
                k for k in range(n_ops - 1)
                if k < loss_idx  # the loss must stay in the LAST stage
                and all(packable(av) for av in crossing[k].values())
            ]

            # ---- stage assignment: device_guard override, else balanced cut
            stages = pp_partition.stages_from_attrs(fwd_ops, pp)
            if stages is None:
                def aval_of(n, j):
                    plist = [
                        (i, av) for (i, av) in producers.get(n, []) if i < j
                    ]
                    if plist:
                        return plist[-1][1]
                    v = feeds.get(n)
                    if v is not None and n in mb_feed_avals:
                        return mb_feed_avals[n]
                    if v is None:
                        v = state_env.get(n)
                    if v is None:
                        return None
                    return jax.ShapeDtypeStruct(np.shape(v), v.dtype)

                weights = []
                for j, op in enumerate(fwd_ops):
                    in_avals = {
                        slot: [
                            aval_of(n, j)
                            for n in names if n != EMPTY_VAR_NAME
                        ]
                        for slot, names in op.inputs.items()
                    }
                    out_avals = {
                        slot: [recs[j].get(n) for n in names]
                        for slot, names in op.outputs.items()
                    }
                    weights.append(
                        pp_partition.analytic_op_time_us(
                            op.type, in_avals, out_avals
                        )
                    )
                # param read bytes, charged to the op of first use, so a
                # weight-heavy stage is as expensive as a FLOP-heavy one
                first_use = {}
                for j, op in enumerate(fwd_ops):
                    for n in op.input_arg_names:
                        if n in param_set and n not in first_use:
                            first_use[n] = j
                for n, j in first_use.items():
                    v = state_env[n]
                    pbytes = (
                        int(np.prod(np.shape(v)))
                        * np.dtype(v.dtype).itemsize
                    )
                    weights[j] += pbytes / 676.0e3
                stages = pp_partition.balanced_partition(weights, legal, pp)
            else:
                legal_set = set(legal)
                for k in range(n_ops - 1):
                    if stages[k + 1] != stages[k] and k not in legal_set:
                        bad = {
                            n: (tuple(av.shape), str(av.dtype))
                            for n, av in crossing[k].items()
                            if not packable(av)
                        }
                        raise ValueError(
                            "device_guard cut after op %d (%s) is illegal: "
                            "values crossing it are not microbatch-major or "
                            "the loss would leave the last stage: %s"
                            % (k, fwd_ops[k].type, bad or {"loss": loss_name})
                        )
            used = sorted(set(stages))
            if used != list(range(pp)):
                raise ValueError(
                    "pipeline partition produced stages %s for pp=%d; every "
                    "pp rank needs a non-empty stage (annotate with "
                    "device_guard('pp:k') or lower pp)" % (used, pp)
                )

            stage_of_op = stages
            param_stage = {}
            for j, op in enumerate(fwd_ops):
                for n in op.input_arg_names:
                    if n in param_set:
                        s0 = param_stage.setdefault(n, stage_of_op[j])
                        if s0 != stage_of_op[j]:
                            raise ValueError(
                                "parameter %r is read by pipeline stages %d "
                                "and %d; pin its consumers to one stage with "
                                "device_guard" % (n, s0, stage_of_op[j])
                            )

            stage_ops = [[] for _ in range(pp)]
            for op, s in zip(fwd_ops, stage_of_op):
                stage_ops[s].append(op)

            # boundary packing tables: cut s = after the last op of stage s
            cut_entries = []
            for s in range(pp - 1):
                k = max(j for j in range(n_ops) if stage_of_op[j] == s)
                ents = []
                for n in sorted(crossing[k]):
                    av = crossing[k][n]
                    w = int(np.prod(av.shape[1:])) if len(av.shape) > 1 else 1
                    ents.append(
                        (n, tuple(av.shape), jnp.dtype(av.dtype), w)
                    )
                cut_entries.append(ents)
            K = max([sum(e[3] for e in ents) for ents in cut_entries] + [1])

            # scalar outputs: loss first, then fetched last-stage values
            produced_fwd = set(producers)
            ext = set(feeds) | state_set
            scal_names = [loss_name] + [
                n for n in fetch_names
                if n != loss_name and n in produced_fwd and n not in ext
            ]
            scal_entries = []
            for n in scal_names:
                i, av = producers[n][-1]
                if stage_of_op[i] != pp - 1:
                    raise ValueError(
                        "the pp lowering can only fetch values computed in "
                        "the LAST pipeline stage; %r is computed in stage %d "
                        "— pin its ops with device_guard or drop the fetch"
                        % (n, stage_of_op[i])
                    )
                sz = int(np.prod(av.shape)) if av.shape else 1
                scal_entries.append(
                    (n, tuple(av.shape), jnp.dtype(av.dtype), sz)
                )
            if scal_entries[0][3] != 1:
                raise ValueError(
                    "loss %r must be scalar, got shape %s"
                    % (loss_name, scal_entries[0][1])
                )
            Ks = sum(e[3] for e in scal_entries)

            opt_out = {n for op in opt_ops for n in op.output_arg_names}
            grad_names_all = {n + GRAD_VAR_SUFFIX for n in param_set}
            for n in fetch_names:
                if (
                    n in ext or n in scal_names or n in opt_out
                    or n in grad_names_all
                ):
                    continue
                raise ValueError(
                    "fetch %r is a non-last-stage intermediate; under pp the "
                    "block returns only last-stage scalars, state, feeds and "
                    "optimizer outputs" % n
                )

            # per-stage parameter name lists (first-use order). The params
            # enter the shard_map REPLICATED (in_spec P()) and each rank's
            # switch branch reads only its own stage's entries — they are
            # jit arguments, so the manual-region entry is an identity.
            # (A packed [pp, S] buffer sharded P('pp') was tried first: a
            # jit-internal value entering a shard_map with a partial spec is
            # resharded by XLA as dynamic-update-slice + all-reduce over the
            # WHOLE mesh, which double-counts the dp replicas — scope params
            # are stored replicated anyway, so the dict costs no extra HBM.)
            stage_params = [[] for _ in range(pp)]
            for j, op in enumerate(fwd_ops):
                s = stage_of_op[j]
                for n in op.input_arg_names:
                    if n in param_set and n not in stage_params[s]:
                        stage_params[s].append(n)
            fwd_param_names = [n for ns in stage_params for n in ns]
            params_fwd = {n: state_env[n] for n in fwd_param_names}

            self_.stage_plan = {
                "schedule": opts["schedule"],
                "n_micro": int(m),
                "microbatch": int(mb),
                "op_stage": [int(s) for s in stage_of_op],
                "stages": [[op.type for op in ops] for ops in stage_ops],
                "stage_params": [list(ns) for ns in stage_params],
                "boundaries": [
                    [e[0] for e in ents] for ents in cut_entries
                ],
                "boundary_width": int(K),
            }

            # read-only state the forward consumes: replicated to all stages
            ro_for_fwd = {}
            for op in fwd_ops:
                for n in op.input_arg_names:
                    if (
                        n in state_set and n not in param_set
                        and n not in ro_for_fwd
                    ):
                        ro_for_fwd[n] = state_env[n]

            key_fwd, key_opt = jax.random.split(rng_key)

            def make_branches(feeds_micro, sfeeds, ro_vals, key):
                def make_branch(s):
                    in_ents = cut_entries[s - 1] if s > 0 else []
                    out_ents = cut_entries[s] if s < pp - 1 else []
                    s_ops = stage_ops[s]
                    s_params = stage_params[s]

                    def branch(params, bin_buf, mb_idx):
                        env = {}
                        env.update(sfeeds)
                        env.update(ro_vals)
                        for n in s_params:
                            env[n] = params[n]
                        for n, v in feeds_micro.items():
                            env[n] = lax.dynamic_index_in_dim(
                                v, mb_idx, axis=0, keepdims=False
                            )
                        off = 0
                        for (n, shp, dt, w) in in_ents:
                            env[n] = (
                                bin_buf[:, off:off + w].reshape(shp)
                                .astype(dt)
                            )
                            off += w
                        ctx = registry.LowerCtx(
                            jax.random.fold_in(
                                jax.random.fold_in(key, s), mb_idx
                            ),
                            mesh=None,
                        )
                        registry.lower_ops(ctx, s_ops, env)
                        if out_ents:
                            buf = jnp.concatenate([
                                env[n].reshape(mb, -1).astype(jnp.float32)
                                for (n, _, _, _) in out_ents
                            ], axis=1)
                            out = jnp.pad(
                                buf, ((0, 0), (0, K - buf.shape[1]))
                            )
                        else:
                            out = jnp.zeros((mb, K), jnp.float32)
                        if s == pp - 1:
                            scal = jnp.concatenate([
                                env[n].reshape(-1).astype(jnp.float32)
                                for (n, _, _, _) in scal_entries
                            ])
                        else:
                            scal = jnp.zeros((Ks,), jnp.float32)
                        return out, scal

                    return branch

                return [make_branch(s) for s in range(pp)]

            in_specs = (P(), P("dp"), P(), P(), P())

            if opts["schedule"] == "gpipe":
                def spmd_fwd(params, bfeeds_l, sfeeds, ro_vals, key):
                    feeds_micro = {
                        n: v.reshape((m, mb) + v.shape[1:])
                        for n, v in bfeeds_l.items()
                    }
                    branches = make_branches(feeds_micro, sfeeds, ro_vals, key)

                    def stage_f(bin_buf, mb_idx):
                        return lax.switch(
                            lax.axis_index("pp"), branches,
                            params, bin_buf, mb_idx,
                        )

                    scal = pipeline_fwd_spmd(
                        stage_f, m, (mb, K), Ks, axis_name="pp"
                    )
                    return lax.pmean(scal, "dp")

                sm = shard_map(
                    spmd_fwd, mesh=mesh, in_specs=in_specs, out_specs=P(),
                    check_vma=False,
                )

                def lossf(params):
                    scal = sm(
                        params, batch_feeds, scalar_feeds, ro_for_fwd,
                        key_fwd,
                    )
                    return scal[0], scal

                (_, scal), gdict = jax.value_and_grad(lossf, has_aux=True)(
                    params_fwd
                )
            else:  # 1f1b
                seed = jnp.zeros((Ks,), jnp.float32).at[0].set(1.0 / m)

                def spmd_both(params, bfeeds_l, sfeeds, ro_vals, key):
                    feeds_micro = {
                        n: v.reshape((m, mb) + v.shape[1:])
                        for n, v in bfeeds_l.items()
                    }
                    branches = make_branches(feeds_micro, sfeeds, ro_vals, key)

                    def stage_f(p, bin_buf, mb_idx):
                        return lax.switch(
                            lax.axis_index("pp"), branches,
                            p, bin_buf, mb_idx,
                        )

                    scal, gacc = pipeline_1f1b_spmd(
                        stage_f, params, m, (mb, K), seed, axis_name="pp"
                    )
                    # each rank's vjp is nonzero only for its own stage's
                    # params: psum over 'pp' assembles the full dict, pmean
                    # over 'dp' matches GPipe's dp-mean gradient
                    gacc = jax.tree_util.tree_map(
                        lambda g: lax.pmean(lax.psum(g, "pp"), "dp"), gacc
                    )
                    return lax.pmean(scal, "dp"), gacc

                sm = shard_map(
                    spmd_both, mesh=mesh, in_specs=in_specs,
                    out_specs=(P(), P()),
                    check_vma=False,
                )
                scal, gdict = sm(
                    params_fwd, batch_feeds, scalar_feeds, ro_for_fwd,
                    key_fwd,
                )

            # ---- bind grads under the program's own @GRAD names and run
            # the block's optimizer section verbatim
            env = {}
            env.update(ro_state)
            env.update(mut_state)
            env.update(feeds)
            for n in fwd_param_names:
                env[n + GRAD_VAR_SUFFIX] = gdict[n].astype(
                    state_env[n].dtype
                )
            for n in param_set:
                gname = n + GRAD_VAR_SUFFIX
                if gname not in env:  # param unused by the forward: zero grad
                    v = state_env[n]
                    env[gname] = jnp.zeros(np.shape(v), v.dtype)
            off = 0
            for (n, shp, dt, sz) in scal_entries:
                env[n] = scal[off:off + sz].reshape(shp).astype(dt)
                off += sz
            ctx = registry.LowerCtx(
                key_opt, mesh=mesh, zero1_axis=z1,
                sharding=getattr(self_, "_resolver", None),
            )
            registry.lower_ops(ctx, opt_ops, env)
            fetches = [env[n] for n in fetch_names]
            new_mut = {n: env[n] for n in self_.mut_names}
            created = {
                n: env[n] for n in self_.created_persistables if n in env
            }
            return fetches, new_mut, created, ctx.key

        return run


class _MultiStepBlock:
    """k iterations of a training block compiled into ONE XLA call.

    `jax.lax.scan` drives the block's lowering over a stacked feed (leading
    axis k), threading the donated mutated-state pytree (params, optimizer
    state, running stats) and the PRNG key through the loop carry. Per-step
    fetches come back stacked [k, ...].

    Reference analog: scope_buffered_ssa_graph_executor.h:37
    `num_iteration_per_drop_scope` — the reference amortizes per-iteration
    host work (scope GC) over k iterations without leaving the executor. Here
    the amortized cost is the dispatch itself: a training step carries ~480
    state buffers per call, and one multi-step call pays that host work once
    for k steps without hand-packing state into per-dtype arenas. (What a
    dispatch costs on the current machine is not measured.)

    RNG equivalence: the scan body threads the key exactly as k sequential
    Executor.run calls would (registry.lower_ops splits per stochastic op),
    so dropout-bearing programs produce bitwise-identical trajectories either
    way — asserted by tests/test_multistep.py.
    """

    def __init__(self, program, block, feed_names, fetch_names, scope,
                 steps_per_run, mesh=None, data_axes=("dp",), feed_ranks=None,
                 zero1_axis=None, sharding_rules=None):
        if steps_per_run < 1:
            raise ValueError("steps_per_run must be >= 1")
        self.steps_per_run = steps_per_run
        # reuse _CompiledBlock's whole analysis (state split, shardings) and
        # its raw lowering closure; its own .jitted is lazy and never compiled
        # instrument=False: the scan body discards the created dict, which
        # is the stats side channel (FLAGS_tensor_stats needs
        # steps_per_run=1 — its one-sync-per-RUN contract is per run anyway)
        inner = _CompiledBlock(
            program, block, feed_names, fetch_names, scope,
            mesh=mesh, data_axes=data_axes, feed_ranks=feed_ranks,
            zero1_axis=zero1_axis, sharding_rules=sharding_rules,
            instrument=False,
        )
        if inner.created_persistables:
            raise RuntimeError(
                "steps_per_run>1 requires a block that creates no new "
                "persistables (run the startup program separately first); "
                "this block creates %s" % inner.created_persistables
            )
        self._inner = inner
        self.feed_names = inner.feed_names
        self.fetch_names = inner.fetch_names
        self.ro_names = inner.ro_names
        self.mut_names = inner.mut_names
        self.zero1_axis = inner.zero1_axis
        self.zero1_state_names = inner.zero1_state_names
        self._ro_sh = getattr(inner, "_ro_sh", None)
        self._mut_sh = getattr(inner, "_mut_sh", None)
        self._feed_sharding = None

        def run_k(stacked_feeds, ro_state, mut_state, rng_key):
            def body(carry, feeds):
                mut, key = carry
                fetches, new_mut, _created, new_key = inner.fn(
                    feeds, ro_state, mut, key
                )
                return (new_mut, new_key), fetches

            (mut, key), stacked_fetches = jax.lax.scan(
                body, (mut_state, rng_key), stacked_feeds, length=steps_per_run
            )
            return stacked_fetches, mut, key

        if mesh is None:
            self.jitted = jax.jit(run_k, donate_argnums=(2,))
        else:
            from jax.sharding import NamedSharding, PartitionSpec as P

            repl = NamedSharding(mesh, P())
            # stacked feeds: scan axis unsharded, batch dim on the data axes
            batch = NamedSharding(mesh, P(None, data_axes))
            self._feed_sharding = batch
            feed_ranks = feed_ranks or {}
            feed_sh = {
                n: (batch if feed_ranks.get(n, 1) else repl)
                for n in self.feed_names
            }
            out_sh = ([repl] * len(self.fetch_names), inner._mut_sh, repl)
            self.jitted = jax.jit(
                run_k,
                donate_argnums=(2,),
                in_shardings=(feed_sh, inner._ro_sh, inner._mut_sh, repl),
                out_shardings=out_sh,
            )

    def __call__(self, scope, stacked_feed_arrays):
        ro = {n: scope.vars[n] for n in self.ro_names}
        mut = {n: scope.vars[n] for n in self.mut_names}
        stacked_fetches, new_mut, new_key = self.jitted(
            stacked_feed_arrays, ro, mut, scope.rng_key
        )
        scope.vars.update(new_mut)
        scope.rng_key = new_key
        return stacked_fetches


def _pull_reader_steps(readers, steps_per_run):
    """Pull up to k staged batches from started py_readers and stack them.
    If the epoch ends mid-pull, the completed steps are NOT discarded: the
    call proceeds as a shorter multi-step run (the sequential path would
    have trained on them before raising EOF) and EOFException surfaces on
    the NEXT run, once nothing is left. Returns (stacked_feed, k_actual);
    the feed is ALWAYS stacked [k, ...] — even a 1-batch tail keeps the
    multi-step fetch contract (fetches come back [k, ...])."""
    from .py_reader import EOFException

    step_feeds = []
    try:
        for _ in range(steps_per_run):
            d = {}
            pulled = []  # (reader, batch) of this incomplete step
            for rd in readers:
                b = rd.next_batch()
                pulled.append((rd, b))
                d.update(b)
            pulled = None  # step completed
            step_feeds.append(d)
    except EOFException:
        # one reader of the group ended mid-step: the sibling batches
        # already pulled for the INCOMPLETE step go back to their readers
        # (they were never trained on), and the whole group defers the EOF
        if pulled:
            for rd, b in pulled:
                rd.push_back(b)
        if not step_feeds:
            raise
        # tail consumed now; surface the EOF on the NEXT run
        for rd in readers:
            rd._eof_deferred = True
    return _stack_feed_steps(step_feeds), len(step_feeds)


def _started_readers(program):
    """Started py_readers of the program; raises the EOFException a previous
    partial multi-step pull deferred (its tail batches were trained on, so
    the epoch end belongs to THIS call). The program's readers are treated
    as a UNIT: a deferred EOF on any of them ends the epoch for the group —
    proceeding with the remaining readers would silently feed steps missing
    the exhausted reader's slots."""
    from .py_reader import EOFException

    readers, deferred = [], False
    for rd in getattr(program, "_py_readers", []):
        if getattr(rd, "_eof_deferred", False):
            rd._eof_deferred = False
            deferred = True
        elif rd.started:
            readers.append(rd)
    if deferred:
        raise EOFException(
            "reader exhausted (tail consumed by the previous multi-step run)"
        )
    return readers


def _resolve_reader_feed(program, steps_per_run):
    """Shared Executor/ParallelExecutor path for feed=None: pull from the
    program's started py_readers — k batches stacked for a multi-step run
    (force_multi keeps the [k, ...] fetch contract even for a 1-batch epoch
    tail), one batch otherwise. Returns (feed, steps_per_run, force_multi)."""
    readers = _started_readers(program)
    if steps_per_run > 1 and readers:
        feed, k = _pull_reader_steps(readers, steps_per_run)
        return feed, k, True
    feed = {}
    for rd in readers:
        feed.update(rd.next_batch())
    return feed, steps_per_run, False


def _stack_feed_steps(feed_list):
    """List of k per-step feed dicts -> one dict of stacked arrays
    (leading axis k). Device-resident values stack on device."""
    if not feed_list:
        raise ValueError("empty feed list")
    names = set(feed_list[0])
    for d in feed_list[1:]:
        if set(d) != names:
            raise ValueError(
                "per-step feeds must share the same names; got %s vs %s"
                % (sorted(names), sorted(d))
            )
    out = {}
    for name in names:
        vals = [d[name] for d in feed_list]
        if any(isinstance(v, jax.Array) for v in vals):
            out[name] = jnp.stack([jnp.asarray(v) for v in vals])
        else:
            out[name] = np.stack([np.asarray(v) for v in vals])
    return out


def _all_finite(values):
    """True iff every floating array in `values` is fully finite. One stacked
    device reduce + a single host sync (same trick as FLAGS_check_nan_inf)."""
    flags_ = [
        jnp.isfinite(a).all()
        for v in values
        for a in (jnp.asarray(v),)
        if jnp.issubdtype(a.dtype, jnp.floating)
    ]
    return (not flags_) or bool(jnp.stack(flags_).all())


def _poison_nan(feed_arrays):
    """`nan_grad` fault payload: overwrite the first floating feed with NaN,
    which propagates through loss -> grads -> every updated parameter — the
    realistic shape of a bad-numerics step. Returns (feed, poison_after);
    poison_after=True means no float feed existed (int-only models), so the
    caller poisons the updated state after the run instead."""
    out = dict(feed_arrays)
    for name in sorted(out):
        a = out[name]
        if not jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating):
            continue
        if isinstance(a, jax.Array):
            out[name] = jnp.full_like(a, jnp.nan)
        else:
            out[name] = np.full_like(np.asarray(a), np.nan)
        return out, False
    return out, True


def _poison_scope_state(scope, mut_names):
    """Fallback nan_grad payload: NaN the first floating mutated persistable
    (post-run), so the guard still sees a poisoned step."""
    for name in sorted(mut_names):
        v = scope.vars.get(name)
        if v is not None:
            a = jnp.asarray(v)
            if jnp.issubdtype(a.dtype, jnp.floating):
                scope.vars[name] = jnp.full_like(a, jnp.nan)
                return


class _SegmentedBlock:
    """A block containing host ops (RPC send/recv, listen_and_serv — the
    reference's non-kernel OperatorBase ops), executed as alternating XLA
    segments and host calls.

    Reference analog: the reference's per-op interpreter runs host ops
    in-line with kernels (executor.cc:389-396); here the block is partitioned
    AT host-op boundaries, each maximal device run is one jitted XLA segment
    (same _CompiledBlock machinery), and values cross segments through the
    Scope. Segments compile lazily at first execution so vars produced by
    earlier host ops (e.g. recv outputs) are in scope by then."""

    def __init__(self, program, block, feed_names, fetch_names):
        self.program = program
        self.block = block
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        # partition: [("xla", [ops]) | ("host", op)]
        self.segments = []
        cur = []
        for op in block.ops:
            opdef = registry.get(op.type)
            if opdef.is_host:
                if cur:
                    self.segments.append(("xla", cur))
                    cur = []
                self.segments.append(("host", op))
            else:
                cur.append(op)
        if cur:
            self.segments.append(("xla", cur))

        # per-xla-segment exports: produced names consumed by later segments,
        # host ops, or the final fetch list — these leave the jit via fetches
        # and land in the scope (persistable mutations are handled by
        # _CompiledBlock's donated-state path independently).
        later_consumed = set(self.fetch_names)
        self._exports = [None] * len(self.segments)
        for i in range(len(self.segments) - 1, -1, -1):
            kind, payload = self.segments[i]
            if kind == "xla":
                produced = set()
                for op in payload:
                    produced.update(op.output_arg_names)
                self._exports[i] = sorted(produced & later_consumed)
                for op in payload:
                    later_consumed.update(op.input_arg_names)
            else:
                later_consumed.update(payload.input_arg_names)
        self._compiled = [None] * len(self.segments)
        # persistables any op writes — lets FLAGS_check_nan_inf scan updated
        # state on segmented (host-op) programs too, like _CompiledBlock
        self.mut_names = sorted(
            {
                n
                for op in block.ops
                for n in op.output_arg_names
                if n != registry.EMPTY_VAR_NAME
                and block.has_var_recursive(n)
                and block._var_recursive(n).persistable
            }
        )

    def __call__(self, scope, feed_arrays):
        # feeds enter the scope directly (segments read them as state), so
        # declared-dtype casts must happen eagerly here — the fused
        # trace-time cast only covers _CompiledBlock-run feeds
        feed_arrays = _eager_cast_feeds(self.block, feed_arrays)
        for name, value in feed_arrays.items():
            scope.set_var(
                name, value if isinstance(value, jax.Array) else jnp.asarray(value)
            )
        from . import profiler as _prof

        for i, (kind, payload) in enumerate(self.segments):
            if kind == "host":
                with _prof.RecordEvent("host_op/%s" % payload.type):
                    registry.get(payload.type).host_fn(payload, scope)
                continue
            if not payload:
                continue
            compiled = self._compiled[i]
            if compiled is None:
                with _prof.RecordEvent("compile/segment_%d" % i):
                    compiled = _CompiledBlock(
                        self.program,
                        self.block,
                        [],
                        self._exports[i],
                        scope,
                        ops_override=payload,
                    )
                self._compiled[i] = compiled
            with _prof.RecordEvent("xla_segment_%d" % i):
                vals = compiled(scope, {})
                if _prof.is_profiling():
                    # XLA dispatch is async; block so the event spans compute
                    # (reference FLAGS_benchmark dev_ctx->Wait, operator.cc:769)
                    vals = [jax.block_until_ready(v) for v in vals]
            for name, val in zip(self._exports[i], vals):
                scope.set_var(name, val)
        return [scope.find_var(n) for n in self.fetch_names]


class Executor:
    """Drop-in for fluid.Executor (reference python/paddle/fluid/executor.py:256).

    A TPUPlace (or its CUDAPlace alias) is resolved to its chip once, here:
    on a machine without that chip construction raises, and every run()
    executes with that chip as JAX's default device. CPUPlace / None pin
    nothing — the block runs wherever JAX runs by default.
    """

    def __init__(self, place=None):
        self.place = place
        self._device = place.jax_device() if isinstance(place, TPUPlace) else None
        self._cache = {}
        # monotonically counts run() calls — the "step index" the
        # check_nan_inf / nan-provenance reports cite (the telemetry step
        # counter only advances when telemetry is on)
        self._run_seq = 0
        # collections are spans and a histogram (profiler.watch_gc); run()
        # folds the pauses since the last one into the registry
        self._gc = _prof.watch_gc()

    def close(self):
        """Reference Executor::Close (executor.cc:111-119): notify pservers
        this trainer is done (SendComplete), letting their sync loops exit."""
        self._cache.clear()
        from .distributed.rpc import RPCClient

        client = RPCClient._instance
        if client is not None:
            for ep in list(client._socks):
                client.send_complete(ep)
            client.close()

    def run(
        self,
        program=None,
        feed=None,
        fetch_list=None,
        feed_var_name="feed",
        fetch_var_name="fetch",
        scope=None,
        return_numpy=True,
        use_program_cache=True,
        steps_per_run=1,
    ):
        """steps_per_run > 1 compiles k iterations into ONE XLA call
        (_MultiStepBlock): `feed` is then either a list of k per-step dicts
        or a dict of stacked arrays with leading axis k, and each fetch comes
        back stacked [k, ...]. With no feed, k staged batches are pulled from
        the program's started py_readers."""
        self._run_seq += 1
        with jax.default_device(self._device), _prof.RecordEvent(
            span="executor.run", iter=self._run_seq
        ):
            result = self._run(
                program, feed, fetch_list, scope, return_numpy,
                use_program_cache, steps_per_run,
            )
            self._gc.fold()
            return result

    def _run(self, program, feed, fetch_list, scope, return_numpy,
             use_program_cache, steps_per_run):
        # the three phases of a run, spans fluid.executor.{prepare,call,
        # finish} under run()'s fluid.executor.run; `iter` is the run's index
        seq = {"iter": self._run_seq}
        with _prof.RecordEvent(span="executor.prepare", **seq):
            if program is None:
                program = framework.default_main_program()
            # elastic step-deadline watchdog: every run entry is a progress beat
            # (resilience/elastic.py heartbeat — one list probe when no
            # Supervisor is active)
            _elastic_heartbeat()
            # telemetry (observability.stepstats): t0 brackets the WHOLE run —
            # reader pull, dispatch, and the fetch conversion (which is where
            # the device sync lands under return_numpy / FLAGS_benchmark)
            _obs, _obs_t0 = _telemetry_begin()
            # force_multi: a reader pull that returned a 1-batch epoch tail still
            # runs through _MultiStepBlock so fetches keep their [k, ...] axis
            force_multi = False
            if feed is None:
                # pull staged batches from started py_readers (reference read_op
                # popping the LoDTensorBlockingQueue); raises EOFException at end
                feed, steps_per_run, force_multi = _resolve_reader_feed(
                    program, steps_per_run
                )
            elif isinstance(feed, (list, tuple)):
                if steps_per_run == 1:
                    steps_per_run = len(feed)
                if len(feed) != steps_per_run:
                    raise ValueError(
                        "feed list has %d entries but steps_per_run=%d"
                        % (len(feed), steps_per_run)
                    )
                if steps_per_run == 1:
                    feed = dict(feed[0])  # single step: no stacking, no scan
                else:
                    feed = _stack_feed_steps(list(feed))
            if fetch_list is None:
                fetch_list = []
            scope = scope or global_scope()
            # the lazy rng_key property covers the fresh-scope case from the
            # scope's own seed; only an explicit program.random_seed overrides it
            if program.random_seed and not getattr(scope, "_seeded", False):
                scope.rng_key = jax.random.key(program.random_seed)
                scope._seeded = True

            fetch_names = [
                f.name if isinstance(f, Variable) else str(f) for f in fetch_list
            ]
            # graph-pass choke point (docs/passes.md): FLAGS_pass_pipeline rewrites
            # the program here, before any lowering below sees it. Reader/feed
            # resolution above ran on the ORIGINAL program (its _py_readers);
            # everything from here down uses the (memoized) transformed one.
            program = _apply_pass_pipeline(
                program, scope, list(feed.keys()), fetch_names
            )
            block = program.global_block()

            feed_arrays = {}
            for name, value in feed.items():
                var = block.vars.get(name)
                feed_arrays[name] = _as_feed_array(value, var)

            _opf = _flags_opprof()
            key = (
                program._uid,
                program._version,
                tuple(sorted((n, a.shape, str(a.dtype)) for n, a in feed_arrays.items())),
                tuple(fetch_names),
                scope._uid,
                steps_per_run,
                # only the k==1 case needs disambiguating from single-step; for
                # k>1 an explicit stacked feed and a reader pull share the
                # compiled scan
                force_multi and steps_per_run == 1,
                # toggling FLAGS_tensor_stats must recompile, not serve a stale
                # (un)instrumented block
                _opf["tensor_stats"],
            )
            is_multi = steps_per_run > 1 or force_multi
            # per-op attribution mode: never cached (diagnosis path), no guard,
            # no telemetry; it shares only the nan-check/return tail. Multi-step
            # runs skip it — unfused per-op eager execution is the opposite of
            # what steps_per_run exists to measure.
            per_op = _prof.is_profiling() and _flags_profile_ops() and not is_multi
            if per_op:
                compiled = _PerOpProfiledBlock(
                    program, block, list(feed_arrays.keys()), fetch_names
                )
            else:
                compiled = self._cache.get(key) if use_program_cache else None
            _obs_cache_hit = compiled is not None
            if compiled is None:
                # FLAGS_static_verify (docs/static_analysis.md): prove the program
                # against the fluidlint suite before paying for the trace below
                from .analysis import maybe_static_verify

                maybe_static_verify(
                    program, list(feed_arrays.keys()), fetch_names, scope=scope,
                    mode="inference" if getattr(program, "_is_test", False)
                    else "training",
                    where="executor",
                )
                has_host = any(
                    registry.is_registered(op.type) and registry.get(op.type).is_host
                    for op in block.ops
                )
                with _prof.RecordEvent("prepare/block0"):
                    if has_host:
                        if is_multi:
                            raise RuntimeError(
                                "steps_per_run>1 cannot span host ops (send/recv/"
                                "listen_and_serv): the k-step scan is one XLA "
                                "computation with no host re-entry"
                            )
                        compiled = _SegmentedBlock(
                            program, block, list(feed_arrays.keys()), fetch_names
                        )
                    elif is_multi:
                        compiled = _MultiStepBlock(
                            program, block, list(feed_arrays.keys()), fetch_names,
                            scope, steps_per_run,
                        )
                    else:
                        compiled = _CompiledBlock(
                            program, block, list(feed_arrays.keys()), fetch_names, scope
                        )
                if use_program_cache:
                    self._cache[key] = compiled

            from . import flags as _flags

            # --- resilience: NaN injection + step guard (docs/resilience.md) ---
            # only runs that mutate persistable state count as training steps;
            # startup/eval programs pass through untouched
            mut_names = () if per_op else getattr(compiled, "mut_names", ()) or ()
            poison_after = False
            guard_snapshot = None
            if mut_names:
                from .resilience import faults as _faults

                if _faults.fires("nan_grad"):
                    feed_arrays, poison_after = _poison_nan(feed_arrays)
                if _flags.get_flags("resilience_nan_guard")["resilience_nan_guard"]:
                    # host copies taken BEFORE the step: the donated in-place
                    # update invalidates the old device buffers, so these copies
                    # are the only way back when the step turns out poisoned.
                    # np.array on top of the __array__ view — on the CPU backend
                    # np.asarray of a jax array is zero-copy, so the donated
                    # update would rewrite the "snapshot" underneath us
                    guard_snapshot = {
                        n: np.array(np.asarray(scope.vars[n]))
                        for n in mut_names
                        if scope.vars.get(n) is not None
                    }

            # pre-step rng key: scope.rng_key is consumed by the run; the
            # provenance replay must start from the same key to reproduce the
            # step's randomness op for op
            pre_key = scope.rng_key if _opf["nan_provenance"] else None

        if per_op:
            with _prof.RecordEvent("run/block0", span="executor.call", **seq):
                fetches = compiled(scope, _eager_cast_feeds(block, feed_arrays))
            with _prof.RecordEvent(span="executor.finish", **seq):
                return self._finish_run(
                    compiled, scope, fetch_names, fetches, return_numpy,
                    step=self._run_seq,
                )

        with _prof.RecordEvent("run/block0", span="executor.call", **seq):
            fetches = compiled(scope, feed_arrays)
            if _prof.is_profiling() or _flags.get_flags("benchmark")["benchmark"]:
                # reference FLAGS_benchmark: wait so host timing is real step
                # time (operator.cc:769 dev_ctx->Wait)
                fetches = [jax.block_until_ready(f) for f in fetches]

        with _prof.RecordEvent(span="executor.finish", **seq):
            nan_ok = False
            if poison_after:
                # integer-only feeds can't carry the injected NaN through the
                # loss; poison the updated state directly instead
                _poison_scope_state(scope, mut_names)
            if guard_snapshot is not None:
                watched = list(fetches) + [
                    scope.vars[n] for n in mut_names if scope.vars.get(n) is not None
                ]
                if not _all_finite(watched):
                    from .observability import flightrec as _flightrec

                    _flightrec.trigger("nan_guard", step=self._run_seq)
                    if _opf["nan_provenance"]:
                        # localize BEFORE the rollback erases the poisoned state;
                        # the replay itself runs against the pre-step snapshot
                        _localize_nan(
                            compiled, scope, feed_arrays, pre_key,
                            "resilience_nan_guard", step=self._run_seq,
                            mut_override=guard_snapshot,
                        )
                    nan_ok = self._skip_nan_step(scope, guard_snapshot)
            # correlation seed for profiler.device_op_profile: the block + feed
            # AVALS of the latest run (abstract shapes only — storing the
            # concrete arrays would pin a whole batch of device memory), from
            # which compiled_hlo() lowers the metadata-carrying HLO text
            if isinstance(compiled, (_CompiledBlock, _MultiStepBlock)):
                # weakref: _last_run must not keep a dropped scope's parameters
                # alive in device memory
                self._last_run = (
                    compiled,
                    weakref.ref(scope),
                    {
                        n: jax.ShapeDtypeStruct(a.shape, a.dtype)
                        for n, a in feed_arrays.items()
                    },
                )
            result = self._finish_run(
                compiled, scope, fetch_names, fetches, return_numpy, nan_ok=nan_ok,
                step=self._run_seq, feed_arrays=feed_arrays, pre_key=pre_key,
            )
            if _obs is not None:
                _telemetry_record(
                    _obs, _obs_t0, compiled, _obs_cache_hit, nan_ok,
                    steps_per_run if is_multi else 1, result, return_numpy,
                )
            return result

    def _last_compiled(self, what):
        """The XLA executable of the most recently run compiled block, served
        from the backend's compilation cache after a run (no recompile)."""
        last = getattr(self, "_last_run", None)
        if last is None:
            raise RuntimeError("%s() needs a prior Executor.run" % what)
        compiled, scope_ref, feed_avals = last
        scope = scope_ref()
        if scope is None:
            raise RuntimeError(
                "%s(): the scope of the last run no longer exists" % what
            )
        ro = {n: scope.vars[n] for n in compiled.ro_names}
        mut = {n: scope.vars[n] for n in compiled.mut_names}
        with jax.default_device(self._device):
            lowered = compiled.jitted.lower(feed_avals, ro, mut, scope.rng_key)
            return lowered.compile()

    def compiled_hlo(self):
        """Post-optimization HLO text of the most recently run compiled
        block. Every instruction carries op_name=".../<op type>/..." metadata
        (registry.lower_ops wraps each lowering in jax.named_scope), so
        profiler.device_op_profile can fold an xla_trace's per-HLO device
        timings back onto framework op types — the TPU analog of the
        reference's CUPTI kernel→op correlation (platform/device_tracer.cc).
        The compile is served from the backend's compilation cache after a
        run, so this does not recompile."""
        return self._last_compiled("compiled_hlo").as_text()

    def memory_plan(self):
        """What the compiler planned for the most recently run compiled block
        on one device: XLA's memory_analysis() of its executable, with
        argument / output / temp / generated_code / alias sizes in bytes. The
        allocator's peak_bytes_in_use leaves XLA's temporaries out; this does
        not."""
        return self._last_compiled("memory_plan").memory_analysis()

    def _skip_nan_step(self, scope, snapshot):
        """The NaN/Inf step guard tripped: roll the mutated persistables back
        to their pre-step values, decay any loss-scale / learning-rate vars
        (graceful degradation — repeated NaNs usually mean the scale or lr is
        too hot), and count the event. The run then returns the poisoned
        fetches to the caller, but the MODEL state is as if the step never
        happened, so training continues."""
        import jax.numpy as jnp

        from . import flags as _flags
        from .resilience import health as _health

        for name, saved in snapshot.items():
            scope.vars[name] = jnp.asarray(saved)
        decay = float(
            _flags.get_flags("resilience_lr_decay")["resilience_lr_decay"]
        )
        decayed = 0
        for name, val in list(scope.vars.items()):
            base = name.rsplit("/", 1)[-1]
            if val is not None and (
                base.startswith("learning_rate") or "loss_scaling" in base
            ):
                scope.vars[name] = jnp.asarray(val) * decay
                decayed += 1
        if decayed:
            _health.incr("lr_decays", decayed)
        _health.incr("nan_steps_skipped")
        return True

    @staticmethod
    def _finish_run(compiled, scope, fetch_names, fetches, return_numpy,
                    nan_ok=False, step=None, feed_arrays=None, pre_key=None):
        """Shared run tail: FLAGS_check_nan_inf scan + numpy conversion.
        nan_ok: the resilience guard already handled this step's NaNs (state
        rolled back) — don't let the check_nan_inf scan abort over them.
        step/feed_arrays/pre_key feed the error report: the run index for the
        message, and (under FLAGS_nan_provenance) the replay inputs for
        first-bad-op localization."""
        from . import flags as _flags

        if not nan_ok and _flags.get_flags("check_nan_inf")["check_nan_inf"]:
            # reference FLAGS_check_nan_inf (operator.cc:778): finiteness
            # reduces ON DEVICE into one stacked scalar (a single host sync
            # per step); only when it trips does the per-var rescan run to
            # name the culprit
            watched = list(zip(fetch_names, fetches)) + [
                (n, scope.vars[n])
                for n in getattr(compiled, "mut_names", ())
                if n in scope.vars
            ]
            finite_flags = [
                jnp.isfinite(a).all()
                for _, v in watched
                for a in (jnp.asarray(v),)
                if jnp.issubdtype(a.dtype, jnp.floating)
            ]
            if finite_flags and not bool(jnp.stack(finite_flags).all()):
                for name, val in watched:
                    arr = jnp.asarray(val)
                    if jnp.issubdtype(arr.dtype, jnp.floating) and not bool(
                        jnp.isfinite(arr).all()
                    ):
                        msg = (
                            "check_nan_inf: variable %r contains NaN/Inf"
                            % name
                        )
                        writer = _last_writer(compiled, name)
                        if writer is not None:
                            msg += ", last written by op %s" % writer
                        if step is not None:
                            msg += " (run step %d)" % step
                        if (
                            feed_arrays is not None
                            and _flags_opprof()["nan_provenance"]
                        ):
                            # best-effort: the donated step already advanced
                            # the state, so this replays against POST-step
                            # values — right op for a feed/activation NaN,
                            # approximate for one born inside the update
                            prov = _localize_nan(
                                compiled, scope, feed_arrays, pre_key,
                                "check_nan_inf", step=step,
                            )
                            if prov is not None:
                                msg += (
                                    "; first non-finite output at op #%s %s"
                                    % (prov.get("op_index"), prov.get("op"))
                                )
                        raise FloatingPointError(msg)
        if return_numpy:
            return [np.asarray(f) for f in fetches]
        return fetches


class _PerOpProfiledBlock:
    """Op-by-op EAGER execution with a RecordEvent + device sync per op —
    the reference's per-op profiler tables (platform/profiler wraps every
    op->Run, operator.cc:157). Fusion is deliberately lost: this exists to
    attribute time per op type under FLAGS_profile_ops, not to train fast."""

    def __init__(self, program, block, feed_names, fetch_names):
        self.block = block
        self.fetch_names = list(fetch_names)
        unknown = sorted(
            {op.type for op in block.ops if not registry.is_registered(op.type)}
        )
        if unknown:
            # same diagnosis-quality error as the jitted path
            raise NotImplementedError("ops without lowering: %s" % unknown)
        self.ops = [
            op for op in block.ops if not registry.get(op.type).skip_exec
        ]
        # nan-check contract shared with _CompiledBlock/_SegmentedBlock
        self.mut_names = sorted(
            {
                n
                for op in self.ops
                for n in op.output_arg_names
                if n != registry.EMPTY_VAR_NAME
                and block.has_var_recursive(n)
                and block._var_recursive(n).persistable
            }
        )

    def __call__(self, scope, feed_arrays):
        from . import profiler as _prof

        env = dict(scope.vars)
        for name, value in feed_arrays.items():
            env[name] = value if isinstance(value, jax.Array) else jnp.asarray(value)
        ctx = registry.LowerCtx(scope.rng_key)
        from .observability import opprof as _opprof

        for op in self.ops:
            opdef = registry.get(op.type)
            # display form ("<type>:<first output>") so the host-events table
            # distinguishes op INSTANCES like the xplane leg does
            with _prof.RecordEvent("op/%s" % _opprof.op_display_name(op)):
                if opdef.is_host:
                    # host ops see a scratch scope view so env temporaries
                    # never leak into the real scope
                    before = set(scope.vars)
                    scope.vars.update(env)
                    opdef.host_fn(op, scope)
                    env.update(scope.vars)
                    for name in set(scope.vars) - before:
                        if name not in self.mut_names:
                            scope.vars.pop(name, None)
                    continue
                # the shared interpreter body (one op at a time), then sync
                # this op's outputs so the event brackets its device time
                registry.lower_ops(ctx, [op], env)
                for name in op.output_arg_names:
                    val = env.get(name)
                    if isinstance(val, jax.Array):
                        env[name] = jax.block_until_ready(val)
        scope.rng_key = ctx.key
        # persist block-written persistables like the jitted path does
        for name in self.mut_names:
            if name in env:
                scope.vars[name] = env[name]
        return [env[n] for n in self.fetch_names]
