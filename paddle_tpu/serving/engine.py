"""AOT serving engine: one model, a bounded set of shape-bucketed compiled
variants, no hot-path recompiles.

The training executors compile per exact feed shape and donate state — both
wrong for serving: request batch sizes vary per call (an unbounded compile
set), and a replica's parameters must survive every call. The engine
instead:

- loads a `save_inference_model` directory into a private Scope and lowers
  it ONCE through executor.aot_serve_lowering (donation-free, params as
  arguments);
- pads every request's batch dim to a small set of power-of-two buckets and
  slices outputs back to the true rows, so the number of compiled variants
  is bounded by the bucket grid, never by traffic;
- builds each variant through serving.compile_cache: a warm replica
  deserializes `jax.export` artifacts and replays XLA executables from disk
  instead of tracing (cold-start-from-cache, the SERVING bench's 5× bar);
Batch-dim padding is invisible to callers: every op in a forward program is
row-independent along the batch dim, so padded rows never contaminate real
rows and slicing them away restores the exact unpadded result.

Declared-dynamic TRAILING dims (-1 in the program's var shape — sequence
lengths) are a different story. Zero-padding a sequence changes the output
of any model that reduces across it (softmax attention, mean-pooling,
layernorm over time): the engine has no mask plumbing, so the padded
positions would participate in the math. The `trailing_pad` policy makes
that hazard explicit:

- ``"exact"`` (default): dynamic trailing dims are never padded — each
  distinct trailing shape compiles its own variant, so results are correct
  for EVERY model. The variant count is bounded by the bucket grid times
  the distinct trailing shapes in traffic; clients wanting a bounded set
  should quantize sequence lengths themselves (that quantization belongs
  where the mask/real-length knowledge lives).
- ``"pow2"``: trailing dims pad to the next power of two with zeros —
  bounded variants under arbitrary lengths, but ONLY sound for models
  proven padding-invariant along those dims (e.g. masked attention that
  consumes an explicit length feed). Opting in asserts that proof; the
  engine cannot check it.

Thread-safety: variant construction is locked; the compiled calls themselves
are jax jitted functions, safe to invoke from any thread (the batcher
serializes device work anyway). Telemetry (device-time histogram, batch-fill
histogram, padded-rows counter, trace counter) rides the PR 4 registry under
`serving/<model>/...`.
"""

import threading
import time

import numpy as np

from .. import io as _io
from ..executor import Executor, Scope, aot_serve_lowering, scope_guard
from ..observability import tracing as _tracing
from . import compile_cache as _cc

__all__ = ["ServingEngine", "DEFAULT_BATCH_BUCKETS"]

DEFAULT_BATCH_BUCKETS = (1, 2, 4, 8, 16, 32)

# batch-fill ratio buckets: 0..1 in tenths
_FILL_BUCKETS = tuple(i / 10.0 for i in range(1, 11))


def _next_pow2(n):
    n = max(int(n), 1)
    p = 1
    while p < n:
        p *= 2
    return p


class ServingEngine:
    """Shape-bucketed, donation-free forward executor for one saved model."""

    def __init__(self, model_dir, name=None, place=None, params_filename=None,
                 batch_buckets=None, cache_dir=None, trailing_pad="exact",
                 precision="native", calibration_feeds=None):
        import jax

        if trailing_pad not in ("exact", "pow2"):
            raise ValueError(
                "trailing_pad must be 'exact' or 'pow2', got %r" % (trailing_pad,)
            )
        self.trailing_pad = trailing_pad
        if precision not in ("native", "int8"):
            raise ValueError(
                "precision must be 'native' or 'int8', got %r" % (precision,)
            )
        if precision == "int8" and not calibration_feeds:
            raise ValueError(
                "precision='int8' needs calibration_feeds (a list of "
                "representative feed dicts) to set activation scales"
            )
        self.precision = precision

        self.name = name or model_dir.rstrip("/").rsplit("/", 1)[-1]
        self.scope = Scope()
        exe = Executor(place)
        with scope_guard(self.scope):
            program, feed_names, fetch_vars = _io.load_inference_model(
                model_dir, exe, params_filename=params_filename
            )
        self.program = program
        self.feed_names = list(feed_names)
        self.fetch_names = [v.name for v in fetch_vars]
        self.fingerprint = _io.inference_model_fingerprint(model_dir)

        block = program.global_block()
        self._var_shapes = {}
        self._feed_dtypes = {}
        for n in self.feed_names:
            v = block.vars.get(n)
            if v is None:
                continue
            self._var_shapes[n] = (
                tuple(v.shape) if v.shape is not None else None
            )
            if v.dtype is not None:
                self._feed_dtypes[n] = v.dtype

        # FLAGS_static_verify: lint the loaded artifact as-deserialized (the
        # aot_serve_lowering gate below re-verifies post-pipeline), so a
        # corrupt or mis-exported model names its defect at load, not at the
        # first request
        from ..analysis import maybe_static_verify

        maybe_static_verify(
            program, self.feed_names, self.fetch_names, scope=self.scope,
            mode="serving", where="serving:%s" % self.name,
        )
        self.quant_results = None
        if precision == "int8":
            # calibrated-int8 pipeline (passes/quant.py): calibrate with the
            # representative feeds, freeze weights + bake static scales into
            # this engine's PRIVATE scope, tag the int8 chains — then lower
            # the rewritten program verbatim (the pipeline already ran, so
            # aot_serve_lowering must not re-apply "inference" on top)
            from ..passes.manager import PassManager

            program = PassManager("inference_int8").apply(
                program, scope=self.scope, feed_names=self.feed_names,
                fetch_names=self.fetch_names,
                attrs={"calibrate": {"feeds": list(calibration_feeds)}},
            )
            self.program = program
            self.quant_results = {
                k: program._pass_results.get(k)
                for k in ("calibrate", "quantize_serving", "fuse_quant_gemm")
            }
            if not (self.quant_results["quantize_serving"] or {}).get(
                "quantized"
            ):
                raise ValueError(
                    "precision='int8': no mul op quantized — the model has "
                    "no fc/mul layers with scope weights and calibrated "
                    "inputs (ranges recorded: %d)"
                    % len(
                        (self.quant_results["calibrate"] or {}).get(
                            "ranges", {}
                        )
                    )
                )
        with scope_guard(self.scope):
            self._serve, self._ro, self._mut = aot_serve_lowering(
                program, self.feed_names, self.fetch_names, self.scope,
                pass_pipeline="off" if precision == "int8" else "inference",
            )

        # hot-swap state (docs/online.md): set_params atomically replaces
        # the _ro/_mut dict OBJECTS under _swap_lock; _run_bucket snapshots
        # (ro, mut, version) under the same lock, so an in-flight call
        # finishes on the params it started with and a swap never waits on
        # device work. version 0 = as-loaded from disk.
        self.model_version = 0
        self.version_stamp = {}
        self._swap_lock = threading.Lock()
        self._served_tls = threading.local()

        buckets = batch_buckets or DEFAULT_BATCH_BUCKETS
        self.batch_buckets = tuple(sorted(set(int(b) for b in buckets)))
        if not self.batch_buckets or self.batch_buckets[0] < 1:
            raise ValueError("batch_buckets must be positive: %r" % (buckets,))
        self.max_batch = self.batch_buckets[-1]

        if cache_dir is None:
            from .. import flags as _flags

            cache_dir = _flags.get_flags("serving_cache_dir")["serving_cache_dir"]
        self.cache = _cc.CompileCache(cache_dir) if cache_dir else None

        self._variants = {}
        self._variant_tags = {}  # id(compiled fn) -> trace display string
        self._build_lock = threading.Lock()
        self.traces = 0  # variants traced+compiled (not served from cache)
        self.cache_hits = 0  # variants deserialized from the compile cache

        from ..observability import registry as _registry

        reg = _registry.default_registry()
        p = "serving/%s" % self.name
        self._m_device_ms = reg.histogram(
            p + "/device_ms",
            "per-engine-call host wall ms (padded bucket): dispatch, the "
            "device's work and the outputs' copy to the host; the name is "
            "historical",
        )
        self._m_fill = reg.histogram(
            p + "/batch_fill", "real rows / bucket rows per engine call",
            buckets=_FILL_BUCKETS,
        )
        self._m_rows = reg.counter(p + "/rows", "real request rows executed")
        self._m_padded = reg.counter(
            p + "/padded_rows", "padding-waste rows added to fill buckets"
        )
        self._m_traces = reg.counter(
            p + "/traces", "serving variants traced (compile-cache misses)"
        )
        self._m_variants = reg.gauge(
            p + "/variants", "compiled serving variants resident"
        )
        self._m_version = reg.gauge(
            p + "/model_version", "live hot-swapped parameter version"
        )
        self._m_swaps = reg.counter(
            p + "/hot_swaps", "set_params hot swaps applied"
        )
        self._m_version.set(0.0)
        self._m_precision = reg.gauge(
            p + "/precision",
            "serving numeric tier (0 = native float, 1 = calibrated int8)",
        )
        self._m_precision.set(1.0 if self.precision == "int8" else 0.0)

    # ---- bucketing --------------------------------------------------------
    def bucket_batch(self, n):
        """Smallest configured bucket >= n (n > max_batch is chunked by
        run())."""
        for b in self.batch_buckets:
            if n <= b:
                return b
        return self.max_batch

    def _bucket_shape(self, name, shape):
        """Padded shape for one feed: batch dim -> bucket; trailing dims pass
        through exactly unless trailing_pad='pow2', in which case dims the
        program declares dynamic (-1) pad to the next power of two — sound
        ONLY for padding-invariant models (see the module docstring)."""
        out = [self.bucket_batch(shape[0])]
        if self.trailing_pad == "pow2":
            declared = self._var_shapes.get(name)
            for i, d in enumerate(shape[1:], start=1):
                dd = (
                    declared[i]
                    if declared is not None and len(declared) == len(shape)
                    else None
                )
                out.append(_next_pow2(d) if dd in (-1, None) else int(d))
        else:
            out.extend(int(d) for d in shape[1:])
        return tuple(out)

    def _feed_dtype(self, name, default=None):
        """The program's declared dtype for a feed, or `default` when the
        program declares none (the request array then keeps its own dtype —
        an undeclared integer id feed must not silently become float32)."""
        dt = self._feed_dtypes.get(name)
        if dt is None:
            return default
        if dt == "bfloat16":
            import jax.numpy as jnp

            return jnp.bfloat16
        return np.dtype(dt)

    # ---- variants ---------------------------------------------------------
    def _variant(self, avals):
        """Compiled callable for one padded-shape signature, building through
        the persistent cache on first sight. `avals` is {feed name:
        jax.ShapeDtypeStruct}."""
        import jax
        from jax import export as jax_export

        vkey = tuple(
            sorted((n, s.shape, str(s.dtype)) for n, s in avals.items())
        )
        fn = self._variants.get(vkey)
        if fn is not None:
            return fn
        with self._build_lock:
            fn = self._variants.get(vkey)
            if fn is not None:
                return fn

            def build():
                self.traces += 1
                self._m_traces.inc()
                return jax_export.export(jax.jit(self._serve))(
                    avals, self._ro, self._mut
                )

            if self.cache is not None:
                # int8 variants key on a precision geometry: the rewritten
                # program shares the model dir's fingerprint with the native
                # lowering, so without it an int8 boot could replay a native
                # executable (and vice versa). Native keys stay unchanged.
                ck = _cc.variant_key(
                    self.fingerprint,
                    {n: (s.shape, s.dtype) for n, s in avals.items()},
                    self.fetch_names,
                    geometry=(
                        {"precision": self.precision}
                        if self.precision != "native"
                        else None
                    ),
                )
                exported, hit = self.cache.get_or_build(
                    ck, build,
                    meta={
                        "model": self.name,
                        "feeds": {
                            n: [list(s.shape), str(s.dtype)]
                            for n, s in avals.items()
                        },
                        "fetches": self.fetch_names,
                    },
                )
                if hit:
                    self.cache_hits += 1
            else:
                exported = build()

            # AOT-compile the wrapper for this exact signature: the variant
            # is a jax Compiled object, so warmup pays the full
            # StableHLO->executable step up front (a disk hit when JAX's
            # persistent cache is warm) and the hot path can never retrace
            fn = jax.jit(
                lambda feeds, ro, mut, _call=exported.call: _call(feeds, ro, mut)
            ).lower(avals, self._ro, self._mut).compile()
            self._variants[vkey] = fn
            self._m_variants.set(len(self._variants))
            return fn

    def warmup(self, example_feed=None):
        """Precompile every batch bucket so the hot path never traces.

        Builds (does not execute) each bucket's variant — compilation is what
        the hot path must never re-pay; running zeros through the model would
        only add device time. Trailing dims come from the program's declared
        var shapes; models with dynamic (-1) trailing dims need
        `example_feed` (one array per feed name) to pin them. Returns the
        number of variants built."""
        import jax

        shapes = {}
        dtypes = {}
        for n in self.feed_names:
            if example_feed is not None and n in example_feed:
                ex = np.asarray(example_feed[n])
                shapes[n] = tuple(ex.shape[1:])
                dtypes[n] = self._feed_dtype(n, default=ex.dtype)
                continue
            declared = self._var_shapes.get(n)
            if declared is None or any(d in (-1, None) for d in declared[1:]):
                raise ValueError(
                    "feed %r has dynamic non-batch dims %r: warmup needs an "
                    "example_feed to pin them" % (n, declared)
                )
            shapes[n] = tuple(int(d) for d in declared[1:])
            dtypes[n] = self._feed_dtype(n, default=np.dtype("float32"))
        for b in self.batch_buckets:
            avals = {
                n: jax.ShapeDtypeStruct(
                    self._bucket_shape(n, (b,) + shapes[n]), dtypes[n]
                )
                for n in self.feed_names
            }
            self._variant(avals)
        return len(self._variants)

    # ---- hot swap ---------------------------------------------------------
    def param_names(self):
        """Every live parameter/state name a hot swap may target."""
        with self._swap_lock:
            return sorted(set(self._ro) | set(self._mut))

    def set_params(self, updates, version=None, stamp=None):
        """Hot-swap parameter values WITHOUT recompiling or dropping
        requests. `updates` maps name -> new full array; names the lowering
        doesn't close over are ignored (a publisher may ship a superset).
        Values are cast to the stored dtype; a shape mismatch raises — a
        geometry change would invalidate every compiled variant, which is a
        new model, not a swap (compile_cache.variant_key hashes avals, never
        values, so same-aval swaps keep the cache and variants valid).

        The swap is two dict replacements under _swap_lock — O(params)
        host-side pointer updates, no device sync. Returns the number of
        arrays applied."""
        import jax.numpy as jnp

        new_ro = dict(self._ro)
        new_mut = dict(self._mut)
        applied = 0
        for name, val in updates.items():
            tgt = new_ro if name in new_ro else (
                new_mut if name in new_mut else None
            )
            if tgt is None:
                continue
            old = tgt[name]
            arr = jnp.asarray(np.asarray(val), dtype=old.dtype)
            if tuple(arr.shape) != tuple(np.shape(old)):
                raise ValueError(
                    "set_params(%r): shape %s != lowered aval %s — geometry "
                    "changes need a model reload, not a hot swap"
                    % (name, tuple(arr.shape), tuple(np.shape(old)))
                )
            tgt[name] = arr
            self.scope.vars[name] = arr
            applied += 1
        with self._swap_lock:
            self._ro = new_ro
            self._mut = new_mut
            self.model_version = (
                int(version) if version is not None else self.model_version + 1
            )
            self.version_stamp = dict(stamp or {})
            ver = self.model_version
        self._m_version.set(float(ver))
        self._m_swaps.inc()
        return applied

    def last_served_version(self):
        """The model_version the CALLING thread's most recent engine call
        executed against (the batcher's dispatcher reads this right after
        run() to stamp each response)."""
        return getattr(self._served_tls, "version", self.model_version)

    # ---- serving ----------------------------------------------------------
    def run(self, feed):
        """Serve one feed dict (or list zipped with feed_names): pad to the
        bucket, execute the compiled variant, slice outputs back to the true
        row count. Returns numpy arrays for the model's fetch targets."""
        if isinstance(feed, (list, tuple)):
            feed = dict(zip(self.feed_names, feed))
        missing = [n for n in self.feed_names if n not in feed]
        if missing:
            raise ValueError("missing feeds: %s" % missing)
        unknown = sorted(set(feed) - set(self.feed_names))
        if unknown:
            raise ValueError(
                "unknown feeds: %s (model takes %s)" % (unknown, self.feed_names)
            )
        arrays = {n: np.asarray(feed[n]) for n in self.feed_names}
        rows = {np.shape(a)[0] if np.ndim(a) else 1 for a in arrays.values()}
        if len(rows) != 1:
            raise ValueError(
                "feeds disagree on batch rows: %s"
                % {n: np.shape(a) for n, a in arrays.items()}
            )
        n = rows.pop()
        if n == 0:
            raise ValueError("empty batch")
        if n > self.max_batch:
            # oversize request: chunk through the largest bucket. Batch-major
            # outputs concatenate; non-batch outputs (rare for inference —
            # e.g. a scalar mean) keep the last chunk's value.
            outs = None
            for lo in range(0, n, self.max_batch):
                part = self._run_bucket(
                    {k: a[lo:lo + self.max_batch] for k, a in arrays.items()}
                )
                if outs is None:
                    outs = [[o] for o in part]
                else:
                    for acc, o in zip(outs, part):
                        acc.append(o)
            return [
                np.concatenate(acc) if np.ndim(acc[0]) else acc[-1]
                for acc in outs
            ]
        return self._run_bucket(arrays)

    def _run_bucket(self, arrays):
        import jax

        n = next(iter(arrays.values())).shape[0]
        padded = {}
        avals = {}
        for name, a in arrays.items():
            dt = self._feed_dtype(name)
            a = (
                np.ascontiguousarray(a)
                if dt is None
                else np.ascontiguousarray(a, dtype=dt)
            )
            shape = self._bucket_shape(name, a.shape)
            if tuple(a.shape) != shape:
                buf = np.zeros(shape, dtype=a.dtype)
                buf[tuple(slice(0, d) for d in a.shape)] = a
                a = buf
            padded[name] = a
            avals[name] = jax.ShapeDtypeStruct(shape, a.dtype)
        bucket = next(iter(padded.values())).shape[0]

        fn = self._variant(avals)
        # snapshot the param dicts + version together: a concurrent
        # set_params replaces the dict objects, so this call runs entirely
        # on one coherent version and reports it faithfully
        with self._swap_lock:
            ro, mut, ver = self._ro, self._mut, self.model_version
        # execute span under the caller's activated span (the batcher's
        # serving.batch); truthiness-gated so the tracing-off path never
        # builds the variant-key string
        span = _tracing.current()
        if span:
            # the variant display string is a pure function of the compiled
            # variant: build it once per variant, not per request
            vtag = self._variant_tags.get(id(fn))
            if vtag is None:
                vtag = ",".join(
                    "%s:%s:%s" % (nm, "x".join(map(str, s.shape)), s.dtype)
                    for nm, s in sorted(avals.items())
                )
                self._variant_tags[id(fn)] = vtag
            span = span.child(
                "engine.execute", variant=vtag, bucket=bucket, rows=n,
                precision=self.precision, model_version=ver,
            )
        t0 = time.perf_counter()
        outs = fn(padded, ro, mut)
        self._served_tls.version = ver
        outs = [np.asarray(o) for o in outs]
        host_ms = (time.perf_counter() - t0) * 1e3
        span.tag(host_ms=round(host_ms, 3)).end()
        self._m_device_ms.observe(host_ms)
        self._m_rows.inc(n)
        self._m_padded.inc(bucket - n)
        self._m_fill.observe(n / float(bucket))
        # slice only batch-major outputs back to the true rows; outputs that
        # don't carry the padded batch dim (scalar stats) pass through
        return [
            o[:n] if np.ndim(o) and o.shape[0] == bucket else o for o in outs
        ]

    def stats(self):
        """Variant/compile accounting for benches and smoke tests."""
        out = {
            "variants": len(self._variants),
            "traces": self.traces,
            "cache_hits": self.cache_hits,
            "trailing_pad": self.trailing_pad,
            "model_version": self.model_version,
            "precision": self.precision,
        }
        if self.quant_results is not None:
            qs = self.quant_results.get("quantize_serving") or {}
            fq = self.quant_results.get("fuse_quant_gemm") or {}
            out["quant"] = {
                "quantized_muls": qs.get("quantized", 0),
                "weights_frozen": len(qs.get("weights_frozen", ())),
                "fused_groups": fq.get("groups", 0),
                "calibrated_ranges": len(
                    (self.quant_results.get("calibrate") or {}).get(
                        "ranges", {}
                    )
                ),
            }
        if self.cache is not None:
            out["cache"] = self.cache.stats()
        return out
