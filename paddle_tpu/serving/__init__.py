"""Production serving runtime (ROADMAP item 1): the serving-side counterpart
of the training executors.

- engine.ServingEngine — AOT, donation-free, shape-bucketed forward executor
  over a `save_inference_model` directory; bounded compiled-variant set, no
  hot-path recompiles.
- batcher.ContinuousBatcher — continuous dynamic request batching
  (deadline-or-fill admission, bounded-queue backpressure, per-request
  timeout, drain/shutdown).
- compile_cache.CompileCache — persistent on-disk cache of serialized
  jax.export artifacts (+ XLA executable cache) so replicas cold-start in
  seconds; also owns the export_compiled artifact format.
- server.ModelServer — stdlib multi-model HTTP front end
  (`/v1/models/<name>:predict`, `/v1/models/<name>:generate`, `/healthz`,
  `/metrics`).
- generation.GenerationEngine / GenerationScheduler — autoregressive
  serving (ROADMAP item 3): AOT prefill buckets + one fixed-shape decode
  step over a paged KV-cache pool (kv_cache.PagedKVPool), token-level
  continuous batching with mid-batch admission and EOS/max-len retirement;
  a model's sliding-window layers keep their own page space
  (kv_cache.WindowPages), only each row's last window of it.

docs/serving.md covers the architecture, bucketing policy, cache layout,
generation slot/page lifecycle, and flags.
"""

from . import batcher, compile_cache, engine, generation, kv_cache, server  # noqa: F401
from .batcher import (  # noqa: F401
    ContinuousBatcher,
    QueueFullError,
    RequestTimeout,
    ServingFuture,
    ShutdownError,
)
from .compile_cache import CompileCache  # noqa: F401
from .engine import ServingEngine  # noqa: F401
from .generation import (  # noqa: F401
    GenerationEngine,
    GenerationScheduler,
    GenRequest,
    GenResult,
)
from .kv_cache import PagedKVPool, PoolExhausted, PrefixCache, WindowPages  # noqa: F401
from .server import ModelServer  # noqa: F401

__all__ = [
    "ServingEngine",
    "ContinuousBatcher",
    "CompileCache",
    "ModelServer",
    "ServingFuture",
    "QueueFullError",
    "RequestTimeout",
    "ShutdownError",
    "GenerationEngine",
    "GenerationScheduler",
    "GenRequest",
    "GenResult",
    "PagedKVPool",
    "PrefixCache",
    "PoolExhausted",
    "WindowPages",
]
