"""Runtime flags (reference gflags tier, SURVEY.md §5.6: ~45 DEFINE_* knobs
surfaced to Python via core.init_gflags and FLAGS_* env vars,
fluid/__init__.py:125-150).

TPU-first mapping: most reference flags governed machinery XLA now owns
(memory fractions → XLA allocator; cudnn knobs → compiler choices), so the
surviving knobs are debug/determinism switches. Flags initialize from
FLAGS_* environment variables exactly like the reference, and can be set
programmatically with set_flags (the modern fluid API shape).

Honored flags:
- check_nan_inf: executor scans every fetch/updated state for NaN/Inf after
  each run and raises (reference operator.cc:778 FLAGS_check_nan_inf).
- benchmark: executor blocks until device work completes each run, so host
  timing brackets real step time (reference operator.cc:769 FLAGS_benchmark).
- rpc_max_retry / rpc_deadline: socket RPC reconnect-retry count and call
  timeout (reference grpc_client.cc FLAGS_max_retry / FLAGS_rpc_deadline).
- rpc_op_deadline: per-operation connect/read deadline (seconds) inside one
  RPC attempt — a hung peer surfaces as a typed resilience.DeadlineExceeded
  instead of blocking forever; rpc_deadline remains the OVERALL retry budget.
- resilience_nan_guard: executor skips a training step whose fetches/updated
  state went NaN/Inf — restores the pre-step state, decays the loss scale /
  learning rate by resilience_lr_decay, and counts the event in
  resilience.health instead of crashing (docs/resilience.md).
- resilience_lr_decay: multiplicative decay the NaN guard applies to
  loss-scale / learning-rate vars on each skipped step.
- dist_init_max_retry: retry attempts for the multi-host rendezvous
  (parallel/multihost.py init_distributed) before surfacing the error.
- profile_ops: while the profiler is on, run blocks op-by-op EAGERLY with a
  device sync per op, so the profiler table attributes time per op type —
  the reference's per-op RecordEvent tables (operator.cc:157). Slower and
  unfused by construction; a diagnosis mode, never a training mode.
- telemetry_dir: when set, the observability layer exports per-step
  telemetry (JSONL event shards + a Prometheus scrape file) into this
  directory — docs/observability.md; empty (default) disables export.
- telemetry_interval_steps: steps between snapshot records / Prometheus
  rewrites / the rank-0 shard merge (observability/export.py).
- telemetry_log_every: > 0 prints one structured health line to stderr
  every N recorded steps (step ms, steps/s, loss if fetched, health counter
  deltas) — the "is it alive" signal for long runs; 0 (default) disables.
- tensor_stats: glob over op display names ("<type>:<first output>"), op
  types, or output var names. Matching ops get on-device output statistics
  (mean/std/absmax/nonfinite count) computed INSIDE the compiled step and
  streamed through the telemetry path with one host sync per run
  (observability/opprof.py, docs/observability.md); "" (default) disables
  and compiles the unmodified step.
- nan_provenance: when the resilience NaN guard or FLAGS_check_nan_inf
  trips, re-run that step's feed through an op-by-op interpreter walk to
  localize the FIRST op emitting non-finite output, and write a provenance
  record (op type/name, input stats, attrs, step) to the telemetry dir plus
  a health/nan_provenance counter. Off (default): failures name only the
  variable, as before.
- trace_dir: when set, the distributed request tracer
  (observability/tracing.py) exports kept trace segments as per-process
  rotation-safe JSONL shards ``trace-host<k>-p<pid>.jsonl`` into this
  directory — the fleet's per-request causality record (router attempt/
  hedge spans, replica server spans, batcher/scheduler lifecycles, engine
  execute spans). "" (default) disables shard export; span creation stays
  on only if flightrec_dir needs the ring. With both unset the hot path
  allocates nothing (NULL_SPAN).
- trace_sample: fraction of OK traces kept by tail sampling, decided by a
  deterministic hash of the trace id so every process keeps the same
  traces. Error, slow and hedged traces are ALWAYS kept. 1.0 (default)
  keeps everything.
- trace_slow_ms: a trace segment containing any span at least this slow is
  exempt from sampling (always kept).
- trace_ring: per-process ring capacity (ended spans, sampled or not) —
  the flight recorder's lookback window.
- flightrec_dir: when set, anomaly triggers (replica 5xx, breaker
  transition, NaN-guard trip, watchdog stall, staleness throttle) dump an
  atomic flight-recorder bundle directory (spans.jsonl + metrics.json +
  event.json + env.json) here — observability/flightrec.py,
  docs/observability.md. "" (default) disables; trigger() is then a no-op.
- flightrec_max_bundles: newest bundles kept on disk (oldest pruned).
- flightrec_min_interval_s: per-reason rate limit between bundles.
- serving_cache_dir: default persistent compile-cache directory for the
  serving runtime (serving/compile_cache.py): ServingEngine instances built
  without an explicit cache_dir store/load serialized jax.export artifacts
  here — a warm replica cold-starts without tracing (docs/serving.md); ""
  (default) disables this layer (variants still cache in-process). JAX's
  persistent XLA-executable cache is not placed by this flag: it lives
  where JAX_COMPILATION_CACHE_DIR says, else in <checkout>/.jax_cache
  (platform_setup.place_compile_cache).
- paged_flash: dispatch tier for the paged flash-attention serving kernel
  (ops/pallas_kernels.paged_flash_attention, the decode/chunked-prefill
  fast path behind the paged_attention lowering). "auto" (default) takes
  the Pallas kernel on a real TPU when a page is a multiple of 8 rows (all
  Mosaic asks of the geometry; and at most 128 heads) and the dense
  flat-gather reference elsewhere (an interpreted kernel in the decode hot
  loop is slower than dense XLA on the CPU test mesh); "on" forces the
  kernel everywhere —
  interpret mode off-TPU, how the hermetic parity tests pin it; "off"
  forces the dense reference. paged_flash_path_taken mirrors the decision.
- gemm_double_buffer: dispatch tier for the manual double-buffered k-loop
  DMA variant of the fused GEMM kernel (overlaps the HBM→VMEM tile fetch
  of iteration k+1 with the MXU contraction of iteration k). Same
  "auto"/"on"/"off" semantics as paged_flash; outputs are bit-identical
  to the grid-pipelined kernel either way (same accumulation order).
- quantized_gemm: dispatch tier for the quantized GEMM tile paths
  (ops/pallas_kernels.quant_gemm_bias_act — int8×int8→i32 and
  fp8(e4m3)×fp8→f32 with the dequantize multiply folded into the GEMM
  epilogue). Same "auto"/"on"/"off" semantics as paged_flash; the dense
  fallback keeps the same wide-accumulate/round-once numerics either way.
  quant_gemm_path_taken mirrors the decision.
- data_num_workers: default worker count for the native data runtime
  (paddle_tpu/data/, docs/data.md): PyReader.decorate_* calls that do not
  pass num_workers explicitly use this many multiprocess decode workers;
  0 (default) keeps the single-threaded feeder path.
- data_ring_slots: shared-memory ring capacity in batch slabs; 0 (default)
  auto-sizes to max(4, 2 * num_workers).
- data_prefetch: device-staged batches held ahead of the consumer (the
  double-buffer depth — batch k+1..k+prefetch transfer while step k runs).
- data_start_method: multiprocessing start method for decode workers.
  "fork" (default) is fast and accepts closures; use "spawn" when the
  parent process already initialized a TPU backend (decode fns must then
  be picklable module-level callables).
- data_max_worker_restarts: respawn budget per worker slot under the
  resilience retry policy before the runtime surfaces a fatal error.
- elastic_step_deadline_s: step-deadline for the elastic Supervisor's
  watchdog (resilience/elastic.py): a supervised step with no heartbeat for
  this many seconds counts a watchdog stall, takes an emergency checkpoint
  when the step returns, and raises FatalError; 0.0 (default) disables.
- elastic_nan_budget: consecutive bad (NaN-skipped / non-finite-loss) steps
  the Supervisor tolerates before rolling back to the last committed
  elastic checkpoint.
- elastic_rollback_budget: NaN-storm rollbacks before the Supervisor gives
  up with FatalError (progress is impossible from this state).
- elastic_barrier_timeout_s: how long the elastic checkpoint writers wait
  on cross-host markers (neighbor shard for the replica copy, rank 0's
  commit barrier) before DeadlineExceeded.
- pass_pipeline: graph-pass pipeline both executors apply at the lowering
  choke point (paddle_tpu/passes, docs/passes.md): a preset name
  ("training_default", "inference", or "training_fused" — the latter adds
  the Pallas kernel-substitution taggers) or a comma-separated pass list;
  "" (default) disables. ParallelExecutor's BuildStrategy.pass_pipeline
  (or BuildStrategy.fuse_kernels=True) overrides this per executor when
  set.
- pass_debug_dir: when set, the PassManager writes per-pass debug dumps
  into this directory — before/after graphviz of block 0 (via
  debugger.draw_block_graphviz) and a textual op diff, named
  <NN>_<pass>_{before,after}.dot / <NN>_<pass>_ops.diff; "" (default)
  disables.
- static_verify: run the whole-program static analyzer (paddle_tpu/analysis,
  docs/static_analysis.md) at every compile seam — Executor.run and
  ParallelExecutor.run executable-cache misses, aot_serve_lowering (the
  serving/generation model-load path), and the pass pipeline (stage 0 plus a
  structural re-verification after every pass). Error-severity fluidlint
  findings raise StaticVerifyError with op/var provenance BEFORE tracing;
  warnings count into the observability registry. Verification never
  mutates the program, so outputs are bit-identical with the flag off.
  False (default) skips the gate entirely.
- eager_delete_tensor_gb / fraction_of_gpu_memory_to_use /
  paddle_num_threads: accepted for API compatibility; storage lifetime and
  threading are XLA/PJRT-owned here (documented no-ops).
"""

import os

__all__ = ["get_flags", "set_flags"]

_DEFAULTS = {
    "check_nan_inf": False,
    "benchmark": False,
    "eager_delete_tensor_gb": -1.0,
    "fraction_of_gpu_memory_to_use": 0.92,
    "paddle_num_threads": 1,
    "cpu_deterministic": False,
    "rpc_max_retry": 3,
    "rpc_deadline": 120.0,
    "rpc_op_deadline": 30.0,
    "resilience_nan_guard": False,
    "resilience_lr_decay": 0.5,
    "dist_init_max_retry": 3,
    "profile_ops": False,
    "telemetry_dir": "",
    "telemetry_interval_steps": 50,
    "telemetry_log_every": 0,
    "tensor_stats": "",
    "nan_provenance": False,
    "trace_dir": "",
    "trace_sample": 1.0,
    "trace_slow_ms": 500.0,
    "trace_ring": 4096,
    "flightrec_dir": "",
    "flightrec_max_bundles": 16,
    "flightrec_min_interval_s": 2.0,
    "serving_cache_dir": "",
    "paged_flash": "auto",
    "gemm_double_buffer": "auto",
    "quantized_gemm": "auto",
    "data_num_workers": 0,
    "data_ring_slots": 0,
    "data_prefetch": 2,
    "data_start_method": "fork",
    "data_max_worker_restarts": 4,
    "elastic_step_deadline_s": 0.0,
    "elastic_nan_budget": 3,
    "elastic_rollback_budget": 2,
    "elastic_barrier_timeout_s": 120.0,
    "pass_pipeline": "",
    "pass_debug_dir": "",
    "static_verify": False,
}

_flags = {}


def _coerce(template, raw):
    if isinstance(template, bool):
        return str(raw).lower() in ("1", "true", "yes", "on")
    return type(template)(raw)


def _init():
    import warnings

    for name, default in _DEFAULTS.items():
        env = os.environ.get("FLAGS_" + name)
        if env is None:
            _flags[name] = default
            continue
        try:
            _flags[name] = _coerce(default, env)
        except (TypeError, ValueError):
            # a malformed env var must not break `import paddle_tpu`
            warnings.warn(
                "ignoring malformed FLAGS_%s=%r (expected %s)"
                % (name, env, type(default).__name__)
            )
            _flags[name] = default


_init()


def get_flags(names=None):
    if names is None:
        return dict(_flags)
    if isinstance(names, str):
        return {names: _flags[names]}
    return {n: _flags[n] for n in names}


def set_flags(flags):
    for name, value in flags.items():
        name = name[len("FLAGS_"):] if name.startswith("FLAGS_") else name
        if name not in _flags:
            raise KeyError("unknown flag %r (known: %s)" % (name, sorted(_flags)))
        _flags[name] = _coerce(_DEFAULTS[name], value)
