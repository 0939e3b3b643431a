"""Autoregressive generation serving: prefill/decode split over a paged
KV-cache pool, with token-level continuous batching.

The PR 6 ServingEngine serves *single-shot* programs — one compiled call
per request. A decode loop breaks that model twice: sequence lengths grow
every step (an unbounded retrace set), and a whole-sequence-per-request
loop wastes nearly all decode FLOPs on finished or padded positions. This
module is the Orca/vLLM answer, built from the same parts:

**GenerationEngine** AOT-compiles exactly TWO variant families through
`executor.aot_serve_lowering(return_state=True)`:

- *prefill* — one CHUNK program per pow2 bucket up to `prefill_chunk`
  (batch 1): the chunk's rows take positions `gen_start + [0, t)`, write
  their K/V into the slot's pages, and attend the pool causally-by-position
  through the same `paged_attention` path decode uses — so a long prompt
  prefills as a sequence of fixed-shape chunk calls (interleaved with
  decode steps by the scheduler: short requests keep streaming while a long
  prompt works through its chunks), and a chunk at start 0 covering the
  whole prompt IS whole-prompt prefill. One family, zero new retraces.
- *decode* — ONE fixed shape, `[max_slots]`: every live slot advances one
  token through `paged_attention` gather/scatter. Idle slots ride along
  pointing at the scratch page.

Admission consults a **PrefixCache** (kv_cache.py): requests whose prompt
shares full cached pages with an earlier prompt start prefill at the first
uncached position, with the shared (refcounted, immutable) pages filling
the leading block-table entries — the system-prompt workload prefills its
common prefix once.

Every variant builds through the persistent CompileCache with the decode
state avals and page geometry folded into the key, then AOT-compiles
(`.lower().compile()`) at warmup — the hot loop calls only precompiled
executables, so it can never retrace regardless of the prompt/output
length mix (`stats()["traces"]` is the proof the tests assert).
Prefill/decode wrappers are jitted with `donate_argnums=(2,)`: the pool
buffers update in place, verified by input-output aliasing in
tests/test_generation.py; single-shot serving stays donation-free.

**GenerationScheduler** extends ContinuousBatcher into a token-level
scheduler: the worker loop admits queued requests into free decode slots
*mid-batch* between steps (admission is host-only; prefill CHUNKS are
interleaved with decode under a queue-pressure policy — one chunk per step
when idle, draining every pending prompt when the queue is deep), dispatches
the next decode step for all live slots, reads the step before it, and
retires slots on EOS/max-len, releasing their pages for reuse.

**The host reads a decode step's results one step late.** The decode
variant takes each row's token either from the host or from the ids the
step before it left on the device (`_take_ids`), so step n+1 is dispatched
before step n's ids have crossed to the host and the device goes from one
step into the next (`decode_step(runs, ahead=True)`, the scheduler's form).
A row that ends by length is known before the dispatch and is left out; a
row that ends by eos is seen one step late and has ridden one row-step
whose id is thrown away, inside pages it still holds. A step with a row
that has a temperature is synchronous: the host samples that token.

A greedy row's token is chosen on the device: every variant ends in an
`arg_max` over the vocabulary of the logits it computes (`_emit_ids`), and
a step's fetch is those ids (and the routers' picks), not the logits, which
stay on the device for whoever asks (`last_logits`). A row with a
temperature (temperature / top-k) is sampled host-side from that row's
logits with a per-request counter-based RNG stream seeded from the scope
seed — so a request's tokens are a pure function of (params, prompt,
sampling config, seed), independent of which slot it lands in or who
shares the batch. That determinism is the parity contract the tests pin.
"""

import hashlib
import json
import sys
import threading
import time

import numpy as np

from ..executor import Scope, aot_serve_lowering, scope_guard
from ..observability import flightrec as _flightrec
from ..observability import tracing as _tracing
from ..profiler import RecordEvent, watch_gc
from ..observability.tracing import NULL_SPAN
from ..ops import generation_ops as _gen_ops
from ..ops import pallas_kernels as _pk
from .batcher import (
    ContinuousBatcher,
    QueueFullError,
    RequestTimeout,
    ServingFuture,
    ShutdownError,
)
from .kv_cache import PagedKVPool, PoolExhausted, PrefixCache
from . import compile_cache as _cc

__all__ = [
    "GenerationEngine",
    "GenerationScheduler",
    "GenRequest",
    "GenResult",
]


def _pow2_buckets(lo, hi):
    out = []
    b = max(2, lo)
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return tuple(sorted(set(out)))


def program_fingerprint(program, scope, extra=None):
    """Content hash of a program built in memory (no model_dir to
    fingerprint): op list (type/slots/attrs) + the scope avals of every
    persistable the ops touch. Mirrors io.inference_model_fingerprint's
    role for the compile-cache key."""

    def _jsonable(v):
        if isinstance(v, np.ndarray):
            return ["ndarray", str(v.dtype), list(v.shape),
                    hashlib.sha256(np.ascontiguousarray(v).tobytes()).hexdigest()]
        if isinstance(v, (list, tuple)):
            return [_jsonable(x) for x in v]
        if isinstance(v, (bool, int, float, str)) or v is None:
            return v
        return repr(v)

    ops = []
    touched = set()
    for op in program.global_block().ops:
        ops.append([
            op.type,
            sorted((k, list(v)) for k, v in op.inputs.items()),
            sorted((k, list(v)) for k, v in op.outputs.items()),
            sorted((k, _jsonable(v)) for k, v in op.attrs.items()),
        ])
        touched.update(op.input_arg_names)
    avals = sorted(
        (n, list(np.shape(scope.vars[n])), str(np.asarray(scope.vars[n]).dtype)
         if not hasattr(scope.vars[n], "dtype") else str(scope.vars[n].dtype))
        for n in touched
        if n in scope.vars
    )
    doc = {"ops": ops, "avals": avals, "extra": extra}
    return hashlib.sha256(json.dumps(doc, sort_keys=True).encode()).hexdigest()


class GenRequest:
    """One generation request (validated by scheduler/engine entry points).
    temperature None/0 means greedy; top_k limits sampling to the k most
    likely tokens; seed pins the request's sample stream (defaults to a
    per-engine counter so concurrent requests draw independent streams)."""

    __slots__ = ("prompt", "max_new_tokens", "eos_id", "temperature",
                 "top_k", "seed")

    def __init__(self, prompt, max_new_tokens=16, eos_id=None,
                 temperature=None, top_k=None, seed=None):
        self.prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = None if eos_id is None else int(eos_id)
        self.temperature = None if not temperature else float(temperature)
        self.top_k = None if not top_k else int(top_k)
        self.seed = None if seed is None else int(seed)
        if not self.prompt:
            raise ValueError("empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")


class GenResult:
    __slots__ = ("tokens", "finish_reason", "prompt_len")

    def __init__(self, tokens, finish_reason, prompt_len):
        self.tokens = tokens
        self.finish_reason = finish_reason
        self.prompt_len = prompt_len


class _SlotRun:
    """Engine-side state of one admitted request occupying a decode slot."""

    __slots__ = ("req", "slot", "table", "tokens", "next_pos", "rng",
                 "pf_pos", "done", "finish_reason", "future", "t_submit",
                 "t_first", "span", "cut", "snaps", "picks", "chunk_picks",
                 "selections")

    def __init__(self, req, slot, table, rng):
        self.req = req
        self.slot = slot
        self.table = table
        self.tokens = []
        self.next_pos = len(req.prompt)
        self.rng = rng
        self.pf_pos = 0  # next prompt position to prefill (past prefix hits)
        # a model with a per-sequence state: the prompt position at which a
        # chunk must end so that a snapshot lands on a boundary other
        # prompts share (0: none), and the snapshots this prefill has left,
        # {prompt tokens: state handle}
        self.cut = 0
        self.snaps = {}
        # engine.record_picks: the routers' picks of each chunk and step,
        # [(first position, picks [n_moe, rows, k])]
        self.picks = []
        # ... and the full dsa layers' selections, [(first position,
        # selections [n_full, rows, topk])]
        self.selections = []
        # the prefill chunks' fetches still on the device, [(first position,
        # live rows, picks, selections, dsa counts)], each None where the
        # model has none: read with the prompt's last chunk's logits
        self.chunk_picks = []
        self.done = False
        self.finish_reason = None
        self.future = None
        self.t_submit = None
        self.t_first = None
        self.span = NULL_SPAN

    def result(self):
        return GenResult(list(self.tokens), self.finish_reason,
                         len(self.req.prompt))


class _Variant:
    __slots__ = ("fn", "ro", "mut_names", "feed_names", "avals",
                 "moe_kernel_layers")

    def __init__(self, fn, ro, mut_names, feed_names, avals,
                 moe_kernel_layers=0):
        self.fn = fn
        self.ro = ro
        self.mut_names = mut_names
        self.feed_names = feed_names
        self.avals = avals
        self.moe_kernel_layers = moe_kernel_layers


class _Logits:
    """One step's logits, left on the device where the step put them: copied
    to the host on the first read and once (the parity surface's reads, no
    part of a step's fetch)."""

    __slots__ = ("dev", "_host")

    def __init__(self, dev):
        self.dev = dev
        self._host = None

    def host(self):
        if self._host is None:
            self._host = np.asarray(self.dev)
        return self._host


class _Step:
    """One decode step dispatched and not yet read: what its read needs."""

    __slots__ = ("by_slot", "positions", "variant", "logits", "ids", "picks",
                 "sel", "counts", "rows", "fetch_bytes", "ctx_tokens",
                 "walked_tokens", "index_rows", "span", "host_ms",
                 "dispatch_ms", "seq")

    def __init__(self, runs):
        self.by_slot = {run.slot: run for run in runs}

    @property
    def runs(self):
        return list(self.by_slot.values())

    def rides(self, run):
        """Whether this step advances `run` (the run, not a later tenant of
        its slot)."""
        return self.by_slot.get(run.slot) is run


class _Sum:
    """A running sum with a histogram's observe(): the stall of the host-only
    phases of one scheduler iteration, taken once per iteration."""

    __slots__ = ("total",)

    def __init__(self):
        self.total = 0.0

    def observe(self, value):
        self.total += value

    def take(self):
        total, self.total = self.total, 0.0
        return total


# gen_ctx_tokens: positions one decode step attends, summed over its runs
# (a step of 24 slots at full 1024-position context reads 24576);
# gen_walked_tokens: positions the paged kernel walks for them, idle slots'
# too (whole compute blocks: at most as many as the tables hold)
CTX_TOKEN_BUCKETS = (
    16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192, 16384, 32768, 65536,
    131072, 262144, 524288, 1048576,
)


# gen_experts_touched: small counts (of the experts this chip holds: a pick
# of an expert held elsewhere is no work here)
EXPERT_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)


# gen_fetch_bytes: what one decode step copies to the host (32 ids are 128
# bytes; a sampled row of 65536 float32 logits 262144)
FETCH_BYTE_BUCKETS = tuple(4 ** i for i in range(2, 14))


def _emit_ids(main, fetch_names):
    """The greedy token of every row, chosen where the logits are: append
    arg_max over the vocabulary axis of the variant's logits (its first
    fetch) to `main`, and its int32 ids to the fetches. The one place that
    turns logits into a token for a greedy row; tie: the first index, as
    numpy's argmax."""
    block = main.global_block()
    logits = fetch_names[0]
    ids = block.create_var(name=logits + ".ids", dtype="int32")
    block.append_op(
        type="arg_max", inputs={"X": [logits]}, outputs={"Out": [ids.name]},
        attrs={"axis": -1},
    )
    return list(fetch_names) + [ids.name]


# a row of dec_tokens that holds this takes the device's id, not the host's
FROM_DEVICE = -1


def _take_ids(serve):
    """The decode variant takes each row's token where it was born: beside
    the host's dec_tokens it is fed dec_prev_ids [slots] int32, the ids the
    decode step before it emitted (a device array the host need not have
    read). A row whose host token is FROM_DEVICE takes the device's id; any
    other row (its first token came from its prefill, or the step before
    was read) keeps the host's. The merge is part of the variant's one
    program."""
    import jax.numpy as jnp

    def serve_merged(feeds, ro, mut):
        feeds = dict(feeds)
        prev = feeds.pop("dec_prev_ids")
        tokens = feeds["dec_tokens"]
        feeds["dec_tokens"] = jnp.where(
            tokens == FROM_DEVICE, prev[:, None].astype(tokens.dtype), tokens)
        return serve(feeds, ro, mut)

    return serve_merged


def cache_specs(model):
    """A model's cache tensors as per-layer specs (ROADMAP R-A4):
    {name, kind, width, dtype}, kind "paged" (rows indexed through block
    tables) or "state" (one fixed row a slot, row 0 scratch; `shape`, where
    a row is not flat). A model that
    only names its K/V pools (GPTDecoder) gets d_model-wide paged rows in
    its kv_dtype and, in int8 mode, its scale pools (width 0: one float a
    row)."""
    own = getattr(model, "cache_specs", None)
    if own is not None:
        return own()
    dtype = getattr(model, "kv_dtype", "float32")
    specs = [
        {"name": n, "kind": "paged", "width": model.d_model, "dtype": dtype}
        for pair in model.kv_pool_names() for n in pair]
    specs += [
        {"name": n, "kind": "paged", "width": 0, "dtype": "float32"}
        for pair in getattr(model, "kv_scale_names", lambda: [])()
        for n in pair]
    return specs


def _moe_kernel_layers(block):
    """How many of a program's moe_experts ops lower to the Pallas grouped
    kernel, by the mirror of the op's own decision (an exported variant out
    of the cache is never traced, so KERNEL_DISPATCHES cannot say)."""
    n = 0
    for op in block.ops:
        if op.type == "moe_experts":
            x = block._var_recursive(op.inputs["X"][0])
            w1 = block._var_recursive(op.inputs["W1"][0])
            n += bool(_pk.grouped_ffn_path_taken(
                x.shape[-1], w1.shape[-1], x.dtype, w1.dtype))
    return n


def _experts_touched(picks, held=None):
    """Distinct experts picked, summed over the expert layers, of picks
    [n_moe, live rows, k]. `held`: the global ids of the experts this chip
    holds (None: all); a pick of an expert held elsewhere is no work here
    and is not counted, as moe_experts does not visit it."""
    n_moe = picks.shape[0]
    width = int(picks.max()) + 1
    counts = np.bincount(
        (picks.reshape(n_moe, -1)
         + np.arange(n_moe, dtype=picks.dtype)[:, None] * width).ravel(),
        minlength=n_moe * width).reshape(n_moe, width)
    if held is not None:
        counts = counts[:, [e for e in held if e < width]]
    return int(np.count_nonzero(counts))


class GenerationEngine:
    """AOT prefill/decode engine for one decoder model over one paged pool.

    `model` implements the GPTDecoder protocol: build_prefill / build_decode
    / kv_pool_names / ensure_params / d_model / max_context / eos_id (see
    models/gpt_decoder.py — the hook point for other decode-loop models).
    """

    def __init__(self, model, name="generation", scope=None, place=None,
                 max_slots=4, page_size=8, pool_pages=None, max_context=None,
                 prefill_buckets=None, prefill_chunk=None, prefix_cache=True,
                 cache_dir=None, snapshot_bytes=None):
        """`snapshot_bytes`: the device memory the prefix cache's snapshots
        of the per-slot sequence state may take together (a model with conv
        or recurrent layers; default four a slot)."""
        import jax.numpy as jnp

        self.model = model
        self.name = name
        self.max_context = int(max_context or model.max_context)
        if self.max_context > model.max_context:
            raise ValueError(
                "max_context %d exceeds the model's position table %d"
                % (self.max_context, model.max_context)
            )
        self.page_size = int(page_size)
        self.max_slots = int(max_slots)
        if self.max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        self.max_pages = -(-self.max_context // self.page_size)
        if pool_pages is None:
            # full reservation capacity for every slot, plus scratch page 0
            pool_pages = self.max_slots * self.max_pages + 1
        self.pool_pages = int(pool_pages)
        self.pool = PagedKVPool(
            self.pool_pages, self.page_size, self.max_slots, self.max_pages,
            storage_dtype=getattr(model, "kv_dtype", "float32"),
        )
        # prefill compiles one chunk program per pow2 bucket up to
        # prefill_chunk; prompts longer than the largest bucket run as a
        # sequence of chunk calls, so buckets stop growing with the context
        # window (default cap 32 rows: past that a chunk's FLOPs amortize
        # its launch and chunking wins back scheduler interleaving)
        chunk = int(prefill_chunk) if prefill_chunk else min(self.max_context, 32)
        self.prefill_buckets = tuple(sorted(set(
            int(b)
            for b in (
                prefill_buckets
                or _pow2_buckets(2, min(self.max_context, chunk))
            )
        )))
        if self.prefill_buckets[-1] > self.max_context:
            raise ValueError("prefill bucket > max_context")
        self.prefill_chunk = self.prefill_buckets[-1]
        # longest admissible prompt must leave room for >= 1 generated
        # token; chunking covers any prompt up to the context bound
        self.max_prompt_len = self.max_context - 1

        self.scope = scope or Scope()
        model.ensure_params(self.scope, place)
        pool_rows = self.pool_pages * self.page_size
        # int8 pool mode (model.kv_dtype == "int8"): level pools are int8
        # and each gains a [pool_rows] f32 per-row scale pool sibling
        # (model.kv_scale_names) — ~1/4 the f32 bytes per cached token
        self.kv_dtype = getattr(model, "kv_dtype", "float32")
        # per-layer cache specs: paged rows of each layer's own width, and
        # for a layer whose state is fixed (a short convolution's last
        # inputs, a recurrence's carry) one row a slot in a [max_slots + 1,
        # width] pool, row 0 scratch: a decode step's idle and mid-prefill
        # rows write there, as they write the scratch page (a recurrence's
        # step skips them: its row is megabytes)
        specs = cache_specs(model)
        self._state = {}
        self._state_names = []  # the fixed per-slot state pools
        self.kv_state_bytes = self.seq_state_bytes = 0
        self.state_row_bytes = 0  # one slot's state: what a snapshot holds
        # its recurrent part (form "recurrent": a state-space layer's
        # carry), which a decode step reads and writes for every live row
        self.recurrent_row_bytes = 0
        for spec in specs:
            dtype = jnp.dtype(spec["dtype"])
            if spec["kind"] == "state":
                arr = jnp.zeros(
                    (self.max_slots + 1,)
                    + tuple(spec.get("shape") or (spec["width"],)), dtype)
                self._state_names.append(spec["name"])
                self.seq_state_bytes += arr.size * dtype.itemsize
                self.state_row_bytes += spec["width"] * dtype.itemsize
                if spec.get("form") == "recurrent":
                    self.recurrent_row_bytes += spec["width"] * dtype.itemsize
            else:
                if spec["width"]:
                    arr = jnp.zeros((pool_rows, spec["width"]), dtype)
                else:
                    # scale 1.0 everywhere: scratch-page reads dequantize to
                    # in-range garbage instead of inf/nan before being masked
                    arr = jnp.ones((pool_rows,), dtype)
                self.kv_state_bytes += arr.size * dtype.itemsize
            self.scope.vars[spec["name"]] = arr
            self._state[spec["name"]] = arr
        # one pooled row of one layer of each form of paged pool, as its
        # kernel copies it: None, a K or a V pool (paged_attention); "latent"
        # (one a layer, read once as key and value: mla_attention, or
        # mla_sparse_attention at its selection); "index" (a full dsa
        # layer's index keys, dsa_index). Pools of every form share the page
        # ids and the block tables, so a prefix hit brings all of them
        self.pool_row_bytes = {}
        for spec in specs:
            if spec["kind"] == "paged" and spec["width"]:
                self.pool_row_bytes.setdefault(
                    spec.get("form"),
                    spec["width"] * jnp.dtype(spec["dtype"]).itemsize)
        self._kv_row_bytes = self.pool_row_bytes.get(None, 0)
        self._latent = "latent" in self.pool_row_bytes
        # learned sparse attention: [(full layer, the layers that attend its
        # selection)], and the positions a query keeps
        self._dsa_groups = getattr(model, "dsa_groups", list)()
        self._dsa_topk = model.dsa["topk"] if self._dsa_groups else 0
        # the routed experts this chip holds (None: all of them)
        self._experts_held = getattr(model, "experts_held", None)
        self.pool.row_bytes = self.kv_state_bytes // pool_rows
        self.pool.state_bytes = self.seq_state_bytes
        self.prefix_cache = (
            PrefixCache(self.pool, state_bytes=self.state_row_bytes,
                        snapshot_bytes=snapshot_bytes)
            if prefix_cache else None)
        self._build_kw = (
            {"state_rows": self.max_slots + 1} if self._state_names else {})
        self._state_fns = None  # (snapshot, restore), compiled at warm-up
        self._row_fn = None  # a sampled row's logits, compiled at warm-up
        # the last decode step's and the last prompt's logits (_Logits)
        self._last_logits = self._last_prefill_logits = None
        self.fetch_bytes = 0  # what the last decode step copied to the host
        # record_picks: keep each run's router picks (run.picks) and copy a
        # prefill chunk's to the host; off in serving (a chunk's fetch stays
        # on the device until the prompt's last)
        self.record_picks = False

        if cache_dir is None:
            from .. import flags as _flags

            cache_dir = _flags.get_flags("serving_cache_dir")["serving_cache_dir"]
        self.cache = _cc.CompileCache(cache_dir) if cache_dir else None

        # persistent decode-step feed buffers: the hot loop allocates
        # nothing. Rows are slot-owned — armed when a slot's prefill
        # completes, refreshed for the runs in each step, zeroed (back to
        # the scratch page) at finish(). A mid-prefill slot therefore keeps
        # writing scratch during interleaved decode steps (its table row is
        # still zeros), and a live slot skipped by one step merely rewrites
        # its last K/V row with identical bits.
        self._dec_feeds = {
            "dec_tokens": np.zeros((self.max_slots, 1), np.int64),
            "dec_positions": np.zeros((self.max_slots, 1), np.int64),
            "dec_block_table": np.zeros(
                (self.max_slots, self.max_pages), np.int32
            ),
            # set anew every step for the runs it advances (a model without
            # experts or a fixed state does not feed them): 1 / the slot's
            # state row for those, 0 / the scratch row for every other slot
            "dec_live": np.zeros((self.max_slots,), np.int32),
            "dec_state_rows": np.zeros((self.max_slots,), np.int32),
        }
        # the ids of the decode step dispatched last, on the device: what the
        # next one is fed as dec_prev_ids, read by the host or not
        self._ids = jnp.zeros((self.max_slots,), jnp.int32)
        # the decode step dispatched and not yet read (_Step), if any
        self._in_flight = None

        self._variants = {}
        self._build_lock = threading.Lock()
        self._sample_counter = 0
        self.traces = 0
        self.cache_hits = 0
        self.tokens_generated = 0

        from ..observability import registry as _registry

        reg = _registry.default_registry()
        p = "serving/%s" % self.name
        self._m_tokens = reg.counter(p + "/gen_tokens", "tokens generated")
        self._m_prefills = reg.counter(p + "/gen_prefills", "prompts prefilled")
        self._m_steps = reg.counter(p + "/gen_steps", "decode steps executed")
        self._m_traces = reg.counter(
            p + "/traces", "generation variants traced (compile-cache misses)"
        )
        self._m_slots = reg.gauge(p + "/gen_slots_live", "live decode slots")
        self._m_slots_total = reg.gauge(
            p + "/gen_slots_total", "decode slot capacity of the KV pool"
        )
        self._m_slots_total.set(float(self.max_slots))
        self._m_occ = reg.gauge(
            p + "/gen_slot_occupancy", "live slots / max_slots"
        )
        self._m_pages = reg.gauge(
            p + "/gen_kv_pages_used", "KV pool pages in use"
        )
        self._m_step_ms = reg.histogram(
            p + "/gen_step_ms", "one decode step, wall ms"
        )
        self._m_prefill_ms = reg.histogram(
            p + "/gen_prefill_ms", "one prefill chunk call, wall ms"
        )
        self._m_chunks = reg.counter(
            p + "/gen_prefill_chunks", "prefill chunk calls executed"
        )
        # the decode step's four phases (fluid.engine.* spans), decode steps
        # only: a prefill chunk blocks only when it is a prompt's last
        self._m_phase_ms = {
            phase: reg.histogram(p + "/gen_%s_ms" % phase, help)
            for phase, help in (
                ("feed", "decode step: writing the feed buffers, wall ms"),
                ("dispatch", "decode step: the compiled call returning "
                             "(enqueue), wall ms"),
                ("wait", "decode step: the host blocked on the step's ids "
                         "and picks (read one step late: while the next "
                         "step runs), wall ms"),
                ("sample", "decode step: appending every run's token (a "
                           "row with a temperature: host sampling), wall ms"),
            )
        }
        # what an admission costs once a prompt, where the work happens: the
        # slot and pages, the prefix cache's three entry points (the cache
        # gets its events from _prefix_phase), the last chunk's blocking
        # read and its first token
        self._m_admit_ms = reg.histogram(
            p + "/gen_admit_ms",
            "admit(): a slot and pages for one request, the prefix lookup, "
            "an eviction for room and a state restore included (span "
            "fluid.engine.admit), wall ms",
        )
        self._m_prefix_ms = {
            what: reg.histogram(p + "/prefix_%s_ms" % what, help)
            for what, help in (
                ("lookup", "PrefixCache.lookup_state of one prompt, its "
                           "tokens' tuple included (span fluid.prefix."
                           "lookup), wall ms"),
                ("insert", "PrefixCache.insert of one prefilled prompt's "
                           "pages, an eviction at the trie's cap included "
                           "(span fluid.prefix.insert), wall ms"),
                ("evict", "one eviction pass of the prefix cache, from "
                          "insert at the trie's cap or from admit for room "
                          "(span fluid.prefix.evict), wall ms"),
            )
        }
        if self.prefix_cache is not None:
            self.prefix_cache.phase = self._prefix_phase
        self._m_prefill_phase_ms = {
            "wait": reg.histogram(
                p + "/gen_prefill_wait_ms",
                "a prompt's last chunk: the host blocked on its first "
                "token's id and the chunks' picks, the pipeline's drain "
                "once a prompt (span fluid.engine.wait, kind=prefill), "
                "wall ms"),
            "sample": reg.histogram(
                p + "/gen_prefill_sample_ms",
                "a prompt's last chunk: its first token, the prefix "
                "cache's insert, arming the slot (span fluid.engine.sample, "
                "kind=prefill), wall ms"),
        }
        # the device run dry: programs dispatched so far (_dispatch numbers
        # them), and the instant a blocking read returned for the one
        # dispatched last, until the next dispatch
        self._n_dispatched = 0
        self._last_ids = None  # the ids of the program dispatched last
        self._dry_since = None
        self._m_starved_ms = reg.histogram(
            p + "/gen_device_starved_ms",
            "from a blocking read of the program dispatched last (the device "
            "has nothing) to the next dispatch's return, wall ms: a lower "
            "bound of the device's idle time (it misses the launch, and a "
            "device that ran dry while the host read nothing); the time with "
            "no request at all is in it",
        )
        self._m_found_dry = reg.histogram(
            p + "/gen_dispatch_found_dry",
            "at a pipelined decode dispatch, before its feed: 1 where the "
            "program dispatched last had already finished (the device ran "
            "dry while the host was still in its pass), else 0",
            buckets=(0, 1),
        )
        self._m_in_flight = reg.histogram(
            p + "/gen_steps_in_flight",
            "decode steps dispatched and unread at a decode dispatch, this "
            "one included: 1 = synchronous, 2 = one ahead",
            buckets=(1, 2),
        )
        self._m_drains = reg.counter(
            p + "/gen_pipeline_drains",
            "times the scheduler's step in flight was read with none behind "
            "it, by reason: temperature (the host samples the token), idle "
            "(no row goes on), close",
        )
        self._m_fetch_bytes = reg.histogram(
            p + "/gen_fetch_bytes",
            "bytes one decode step copied to the host: its ids, the routers' "
            "picks, and the logits of each row that has a temperature",
            buckets=FETCH_BYTE_BUCKETS,
        )
        self._m_ctx_tokens = reg.histogram(
            p + "/gen_ctx_tokens",
            "positions one decode step has to attend, summed over its runs",
            buckets=CTX_TOKEN_BUCKETS,
        )
        self._m_walked_tokens = reg.histogram(
            p + "/gen_walked_tokens",
            "positions the paged kernel walks in one decode step, summed "
            "over all max_slots rows (ops.pallas_kernels."
            "paged_positions_walked)",
            buckets=CTX_TOKEN_BUCKETS,
        )
        self._m_chunk_ctx_tokens = reg.histogram(
            p + "/gen_chunk_ctx_tokens",
            "distinct positions one prefill chunk attends: its own rows and "
            "the prefix before them",
            buckets=CTX_TOKEN_BUCKETS,
        )
        self._m_chunk_ctx_pairs = reg.histogram(
            p + "/gen_chunk_ctx_pairs",
            "(row, position) pairs one prefill chunk attends: each real row "
            "and every position up to its own",
            buckets=tuple(b * 64 for b in CTX_TOKEN_BUCKETS),
        )
        # learned sparse attention, decode steps and prefill chunks alike:
        # what the full layers' index scans and the layers' selected reads
        # had to touch (a pool row two query rows share counted once), and
        # the (query row, position) pairs they computed
        self._m_dsa = {
            what: reg.histogram(p + "/gen_dsa_%s" % what, help, buckets=b)
            for what, help, b in (
                ("index_positions",
                 "distinct index-key rows one decode step or prefill chunk's "
                 "index scans have to read, summed over the full dsa layers",
                 CTX_TOKEN_BUCKETS),
                ("index_pairs",
                 "(query row, position) pairs one decode step or prefill "
                 "chunk's index scans score, summed over the full dsa layers",
                 tuple(b * 64 for b in CTX_TOKEN_BUCKETS)),
                ("selected_positions",
                 "distinct latent rows one decode step or prefill chunk's "
                 "selections name, summed over the dsa layers (a shared layer "
                 "reads its own pool at its full layer's selection)",
                 CTX_TOKEN_BUCKETS),
                ("selected_pairs",
                 "(query row, selected position) pairs one decode step or "
                 "prefill chunk attends, summed over the dsa layers",
                 tuple(b * 64 for b in CTX_TOKEN_BUCKETS)),
                ("selected_share",
                 "one decode step: positions selected over positions that "
                 "could be attended, mean over its rows, percent",
                 (1, 2, 5, 10, 20, 50, 100)),
                ("walk_rows",
                 "query rows one decode step's sparse latent walk gathers "
                 "for: its live rows in whole blocks (ops.generation_ops."
                 "sparse_walk_blocks)",
                 (8, 16, 32, 64, 128, 256)),
            )
        }
        self._m_feed_bytes = reg.histogram(
            p + "/gen_feed_bytes",
            "bytes one decode step feeds the device: tokens, positions, the "
            "block tables (max_slots x max_context / page_size ids), the "
            "live and state rows",
            buckets=FETCH_BYTE_BUCKETS,
        )
        # whoever drives the engine step by step (the scheduler) writes its
        # iteration count here; every fluid.engine.* span and the request
        # tracer's engine.decode / engine.prefill spans carry it as `iter`
        self.iter = 0
        # wall minus thread CPU time of the host-only phases (feed, sample,
        # and the scheduler's admit and retire), summed until taken
        self.host_stall = _Sum()
        self._m_prefix_hit = reg.gauge(
            p + "/gen_prefix_hit_rate",
            "prefix-cache page hit rate (pages hit / pages eligible)",
        )
        self._m_pages_shared = reg.gauge(
            p + "/gen_pages_shared", "KV pool pages held by > 1 reference"
        )
        self._m_paged_flash = reg.gauge(
            p + "/gen_paged_flash_dispatches",
            "paged_attention lowerings that chose the Pallas kernel",
        )
        self._m_kv_bytes = reg.gauge(
            p + "/gen_kv_bytes",
            "resident KV state bytes (level pools + scale pools)",
        )
        self._m_kv_bytes.set(float(self.kv_state_bytes))
        self._m_state_bytes = reg.gauge(
            p + "/gen_state_bytes",
            "resident bytes of the fixed per-slot sequence state (conv "
            "and state-space layers' rows), scratch row included",
        )
        self._m_state_bytes.set(float(self.seq_state_bytes))
        self._m_snap_bytes = reg.gauge(
            p + "/gen_state_snapshot_bytes",
            "bytes of the sequence-state snapshots the prefix cache holds "
            "(what=held) and may hold (what=reserved)",
        )
        self._m_snap_copy_bytes = reg.counter(
            p + "/gen_state_snapshot_copy_bytes",
            "bytes of sequence state copied on the device for the prefix "
            "cache, by event: taken (a slot's rows into a snapshot), "
            "restored (a snapshot into a slot's rows)",
        )
        self._m_ssm_rows = reg.histogram(
            p + "/gen_ssm_rows",
            "live rows whose recurrent state one decode step updates",
            buckets=EXPERT_BUCKETS,
        )
        self._m_ssm_state_bytes = reg.histogram(
            p + "/gen_ssm_state_bytes",
            "bytes of recurrent state one decode step's live rows have to "
            "read and write, summed over the state-space layers (rows x 2 "
            "x a slot's recurrent bytes)",
            buckets=tuple(b * 2 ** 20 for b in CTX_TOKEN_BUCKETS),
        )
        self._m_chunk_ssm_tokens = reg.histogram(
            p + "/gen_chunk_ssm_tokens",
            "positions one prefill chunk scans through the state-space "
            "layers (its live rows)",
            buckets=EXPERT_BUCKETS,
        )
        self._m_restore_ms = reg.histogram(
            p + "/gen_state_restore_ms",
            "a prefix hit's copy of the cached sequence state into the "
            "slot's state rows (span fluid.engine.state_restore), wall ms",
        )
        self._m_states = reg.counter(
            p + "/gen_state_snapshots",
            "sequence-state snapshots by event: taken (kept by the prefix "
            "cache), restored (a prefix hit), evicted",
        )
        self._states_seen = {"taken": 0, "restored": 0, "evicted": 0}
        self._m_experts_touched = reg.histogram(
            p + "/gen_experts_touched",
            "distinct experts with a live row in one decode step, summed "
            "over the expert layers",
            buckets=EXPERT_BUCKETS,
        )
        self._m_chunk_experts = reg.histogram(
            p + "/gen_chunk_experts_touched",
            "distinct experts with a live row in one prefill chunk, summed "
            "over the expert layers (observed with the prompt's last chunk)",
            buckets=EXPERT_BUCKETS,
        )
        self._m_moe_kernel_layers = reg.histogram(
            p + "/gen_moe_kernel_layers",
            "expert layers of one decode step whose grouped product is the "
            "Pallas kernel (ops.pallas_kernels.grouped_expert_ffn) and not "
            "jax.lax.ragged_dot",
            buckets=EXPERT_BUCKETS,
        )
        # precision label for the monitor's serve rows: 0 = fp32 pools,
        # 1 = int8 pools (tools/monitor.py maps it back to a string)
        self._m_precision = reg.gauge(
            p + "/precision",
            "KV storage precision (0 = fp32, 1 = int8)",
        )
        self._m_precision.set(1.0 if self.kv_dtype == "int8" else 0.0)
        # hot-swap state (docs/online.md): each _Variant holds its own ro
        # dict; set_params swaps them (and the scope) under _swap_lock.
        self.model_version = 0
        self.version_stamp = {}
        self._swap_lock = threading.Lock()
        self._m_version = reg.gauge(
            p + "/model_version", "live hot-swapped parameter version"
        )
        self._m_swaps = reg.counter(
            p + "/hot_swaps", "set_params hot swaps applied"
        )
        self._m_version.set(0.0)

    # ---- geometry / cache keys --------------------------------------------
    def geometry(self):
        geo = {
            "page_size": self.page_size,
            "pool_pages": self.pool_pages,
            "max_slots": self.max_slots,
            "max_pages": self.max_pages,
            "max_context": self.max_context,
            "kv_dtype": self.kv_dtype,
        }
        spec = getattr(self.model, "spec", None)
        if spec is not None:  # a block-specified decoder: its layers are data
            geo["block_spec"] = spec()
        return geo

    def _canon_dtype(self, dtype):
        import jax.numpy as jnp

        return jnp.asarray(np.zeros((), np.dtype(dtype))).dtype

    # ---- variants ---------------------------------------------------------
    def _variant(self, kind):
        """Compiled stateful callable for 'decode' or 'prefill:<bucket>',
        building through the persistent cache on first sight."""
        v = self._variants.get(kind)
        if v is not None:
            return v
        with self._build_lock:
            v = self._variants.get(kind)
            if v is not None:
                return v
            pool_rows = self.pool_pages * self.page_size
            if kind == "decode":
                main, _, feeds, fetches = self.model.build_decode(
                    self.max_slots, self.page_size, self.max_pages, pool_rows,
                    **self._build_kw
                )
            elif kind.startswith("prefill:"):
                t = int(kind.split(":", 1)[1])
                main, _, feeds, fetches = self.model.build_prefill(
                    t, self.page_size, self.max_pages, pool_rows,
                    **self._build_kw
                )
            else:
                raise ValueError("unknown variant kind %r" % kind)
            v = self._build_variant(kind, main, feeds, _emit_ids(main, fetches))
            self._variants[kind] = v
            return v

    def _build_variant(self, kind, main, feed_names, fetch_names):
        import jax
        from jax import export as jax_export

        from ..analysis import maybe_static_verify

        maybe_static_verify(
            main, feed_names, fetch_names, scope=self.scope,
            mode="serving", where="generation:%s" % kind,
        )
        with scope_guard(self.scope):
            serve, ro, mut = aot_serve_lowering(
                main, feed_names, fetch_names, self.scope, return_state=True
            )
        block = main.global_block()
        avals = {}
        for n in feed_names:
            var = block.vars[n]
            avals[n] = jax.ShapeDtypeStruct(
                tuple(int(d) for d in var.shape), self._canon_dtype(var.dtype)
            )
        if kind == "decode":
            serve = _take_ids(serve)
            avals["dec_prev_ids"] = jax.ShapeDtypeStruct(
                (self.max_slots,), np.int32)

        def build():
            self.traces += 1
            self._m_traces.inc()
            return jax_export.export(jax.jit(serve))(avals, ro, mut)

        if self.cache is not None:
            fp = program_fingerprint(main, self.scope, extra=kind)
            ck = _cc.variant_key(
                fp,
                {n: (s.shape, s.dtype) for n, s in avals.items()},
                fetch_names,
                state_avals={
                    n: (tuple(a.shape), str(a.dtype)) for n, a in mut.items()
                },
                geometry=self.geometry(),
            )
            exported, hit = self.cache.get_or_build(
                ck, build,
                meta={
                    "model": self.name,
                    "variant": kind,
                    "geometry": self.geometry(),
                    "feeds": {
                        n: [list(s.shape), str(s.dtype)]
                        for n, s in avals.items()
                    },
                    "fetches": list(fetch_names),
                },
            )
            if hit:
                self.cache_hits += 1
        else:
            exported = build()

        # decode-state donation: the KV pool buffers (arg 2) are consumed
        # each call and replaced by the returned new state, so XLA may alias
        # them in place — the aliasing test asserts this on the executable
        fn = jax.jit(
            lambda feeds, ro_, mut_, _call=exported.call: _call(feeds, ro_, mut_),
            donate_argnums=(2,),
        ).lower(avals, ro, {n: self._state[n] for n in mut}).compile()
        return _Variant(fn, ro, sorted(mut), list(feed_names), avals,
                        _moe_kernel_layers(block))

    def warmup(self):
        """Precompile the decode step and every prefill bucket. Returns the
        variant count; after this the hot loop never traces."""
        self._variant("decode")
        for b in self.prefill_buckets:
            self._variant("prefill:%d" % b)
        self._state_programs()
        self._row_program()
        return len(self._variants)

    # ---- the parity surface, and a sampled row's logits --------------------
    @property
    def last_logits(self):
        """The last decode step's logits [max_slots, vocab], read on demand
        from the step's own output (tests and the benchmark's probe assert
        these rows bit-stable under batching / admission / chunking changes:
        the docs/serving.md contract). The first read copies, once."""
        held = self._last_logits
        return None if held is None else held.host()

    @property
    def last_prefill_logits(self):
        """The logits [vocab] of the last prompt's last position, likewise."""
        held = self._last_prefill_logits
        return None if held is None else held.host()[0]

    def _row_program(self):
        """row(logits, slot) -> logits[slot] of the decode step's logits, on
        the device: what a row with a temperature fetches (262 KB of the 8.4
        MB at 32 x 65536). Compiled once (at warm-up, so that a sampled row
        in the hot loop compiles nothing)."""
        if self._row_fn is None:
            import jax

            (logits, *_), _ = self._variant("decode").fn.out_info
            self._row_fn = jax.jit(lambda logits, slot: logits[slot]).lower(
                logits, np.int32(0)).compile()
        return self._row_fn

    # ---- the fixed per-slot state: snapshot and restore --------------------
    def _state_programs(self):
        """(snapshot, restore) over the state pools, compiled once (at
        warm-up, so that a prefix hit in the hot loop compiles nothing), or
        None for a model whose every layer's state is pages. snapshot(pools,
        row) -> a tuple of the row of each pool (a copy, state_row_bytes of
        it: whatever the model's layers keep a sequence); restore(pools,
        snapshot, row) -> the pools with that row set, the pools donated."""
        if not self._state_names or self._state_fns is not None:
            return self._state_fns
        import jax

        pools = tuple(self._state[n] for n in self._state_names)
        row = np.int32(0)
        snapshot = jax.jit(
            lambda pools, row: tuple(p[row] for p in pools)
        ).lower(pools, row).compile()
        restore = jax.jit(
            lambda pools, snap, row: tuple(
                p.at[row].set(v) for p, v in zip(pools, snap)),
            donate_argnums=(0,),
        ).lower(pools, snapshot(pools, row), row).compile()
        self._state_fns = (snapshot, restore)
        return self._state_fns

    def state_rows(self, slot):
        """{pool name: a copy of the slot's row} of the fixed per-slot
        sequence state, as the pools hold it now (a parity surface, like
        last_logits: tests and the benchmark's probe hold a recurrent state
        to the reference's)."""
        return dict(zip(self._state_names, self._snapshot_state(slot)))

    def _snapshot_state(self, slot):
        snapshot, _ = self._state_programs()
        pools = tuple(self._state[n] for n in self._state_names)
        return snapshot(pools, np.int32(slot + 1))

    def _restore_state(self, slot, snap):
        """A prefix hit: the cached boundary's state into the slot's rows."""
        _, restore = self._state_programs()
        with RecordEvent(span="engine.state_restore",
                         hist=self._m_restore_ms, iter=self.iter):
            pools = tuple(self._state[n] for n in self._state_names)
            new = restore(pools, snap, np.int32(slot + 1))
            self._state.update(zip(self._state_names, new))
        self._m_snap_copy_bytes.inc(self.state_row_bytes, event="restored")

    # ---- hot swap ---------------------------------------------------------
    def set_params(self, updates, version=None, stamp=None):
        """Hot-swap parameter values without recompiling or dropping
        requests. KV-pool state names (self._state) never swap — a publisher
        shipping them by accident must not clobber live caches. Each
        variant's ro dict is replaced wholesale (one attribute store;
        _call reads variant.ro exactly once per step, so an in-flight decode
        step finishes coherently on the old params) and the scope is updated
        so variants built later capture the new values. Returns the number
        of arrays applied."""
        import jax.numpy as jnp

        with self._swap_lock:
            conv = {}
            for name, val in updates.items():
                if name in self._state:
                    continue
                cur = self.scope.vars.get(name)
                if cur is None:
                    continue
                arr = jnp.asarray(np.asarray(val), dtype=np.asarray(cur).dtype)
                if tuple(arr.shape) != tuple(np.shape(cur)):
                    raise ValueError(
                        "set_params(%r): shape %s != live %s — geometry "
                        "changes need a model reload, not a hot swap"
                        % (name, tuple(arr.shape), tuple(np.shape(cur)))
                    )
                conv[name] = arr
            for v in self._variants.values():
                if any(n in v.ro for n in conv):
                    nro = dict(v.ro)
                    nro.update({n: a for n, a in conv.items() if n in v.ro})
                    v.ro = nro
            self.scope.vars.update(conv)
            self.model_version = (
                int(version) if version is not None else self.model_version + 1
            )
            self.version_stamp = dict(stamp or {})
            ver = self.model_version
        self._m_version.set(float(ver))
        self._m_swaps.inc()
        return len(conv)

    def _phase(self, phase, kind):
        """One leaf of a model step: the span fluid.engine.<phase>, the
        phase's histogram (a decode step's four; of a prefill chunk the
        wait and sample a prompt's last has), the stall of the host-only
        ones."""
        hists = (self._m_phase_ms if kind == "decode"
                 else self._m_prefill_phase_ms)
        return RecordEvent(
            span="engine." + phase, hist=hists.get(phase),
            stall=self.host_stall if phase in ("feed", "sample") else None,
            kind=kind, iter=self.iter,
        )

    def _prefix_phase(self, what):
        """The prefix cache's event for its lookup, insert or evict: the span
        fluid.prefix.<what> and the model's prefix_<what>_ms."""
        return RecordEvent(
            span="prefix." + what, hist=self._m_prefix_ms[what],
            iter=self.iter)

    def _feeds(self, variant, np_feeds):
        """(feeds, mut_in) for one call of a variant: the feed phase's end.
        Copies: the call returns before the device has taken its feeds, and
        the decode step's buffers are written again for the next step while
        this one is in flight."""
        feeds = {}
        for n in variant.feed_names:
            s = variant.avals[n]
            feeds[n] = np.array(np_feeds[n], dtype=s.dtype, order="C")
        return feeds, {n: self._state[n] for n in variant.mut_names}

    def _dispatch(self, variant, feeds, mut_in):
        """Every program the engine gives the device goes through here, and
        is numbered; its ids are its last fetch."""
        fetches, new_mut = variant.fn(feeds, variant.ro, mut_in)
        self._state.update(new_mut)
        self._n_dispatched += 1
        self._last_ids = fetches[-1]
        if self._dry_since is not None:
            self._m_starved_ms.observe(
                (time.perf_counter() - self._dry_since) * 1e3)
            self._dry_since = None
        return fetches

    def _note_read(self, seq):
        """A blocking read of program `seq` has returned: if none was
        dispatched behind it, the device has nothing from now on."""
        if seq == self._n_dispatched:
            self._dry_since = time.perf_counter()

    # ---- admission / prefill / decode / retire -----------------------------
    def prefill_bucket(self, n):
        """Smallest chunk bucket covering `n` remaining prompt tokens, or
        the largest (= prefill_chunk) when the remainder spans chunks."""
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return self.prefill_buckets[-1]

    def can_admit(self, req):
        """Whether a free slot + pages exist for this request right now."""
        budget = len(req.prompt) + self._max_new(req)
        return self.pool.can_admit(budget)

    def _max_new(self, req):
        # a request can never run past the context window
        return min(req.max_new_tokens, self.max_context - len(req.prompt))

    def free_slots(self):
        return self.max_slots - self.pool.stats()["slots_in_use"]

    def admit(self, req):
        """Reserve a slot + pages for one request — host work only, no
        device call. Prefix-cache hits fill the leading block-table entries
        and skip those pages' prefill; the caller then advances the prompt
        with prefill_step() until it returns True. Raises PoolExhausted
        when no capacity (after trying to evict cold cached pages),
        ValueError on an inadmissible request."""
        L = len(req.prompt)
        if L > self.max_prompt_len:
            raise ValueError(
                "prompt of %d tokens exceeds max_prompt_len %d"
                % (L, self.max_prompt_len)
            )
        with RecordEvent(span="engine.admit", hist=self._m_admit_ms,
                         iter=self.iter):
            max_new = self._max_new(req)
            shared, state, matched = [], None, 0
            if self.prefix_cache is not None:
                # pages pinned; with a per-sequence state, only down to a
                # boundary whose snapshot comes with them
                shared, state, matched = self.prefix_cache.lookup_state(
                    req.prompt)
            try:
                try:
                    slot, table = self.pool.acquire(L + max_new, shared)
                except PoolExhausted:
                    need = self.pool.pages_for(L + max_new) - len(shared)
                    if (self.prefix_cache is None
                            or not self.prefix_cache.evict_for(need)):
                        raise
                    slot, table = self.pool.acquire(L + max_new, shared)
            finally:
                if shared:
                    self.pool.unpin_pages(shared)  # slot ref (or nothing) holds now
            seed = req.seed
            if seed is None:
                seed = (self.scope._seed, self._sample_counter)
                self._sample_counter += 1
            run = _SlotRun(req, slot, table, np.random.default_rng(seed))
            run.pf_pos = len(shared) * self.page_size
            if self._state_names and self.prefix_cache is not None:
                if state is not None:
                    self._restore_state(slot, state)
                if matched > len(shared):
                    # other prompts share more of this one than any snapshot
                    # covers: end a chunk at that boundary and leave one there
                    run.cut = matched * self.page_size
            self._set_pool_gauges()
            return run

    def prefill_step(self, run):
        """Advance one admitted run by ONE prefill chunk (one device call).
        Returns True when the prompt is fully prefilled — the first token
        has then been sampled and the run is decodable (or already done)."""
        req = run.req
        L = len(req.prompt)
        start = run.pf_pos
        remaining = L - start
        if remaining <= 0:
            raise ValueError("prefill_step on a fully prefilled run")
        if start < run.cut:
            remaining = min(remaining, run.cut - start)
        c = self.prefill_bucket(remaining)
        n_real = min(c, remaining)
        span = _tracing.current()
        if span:
            span = span.child(
                "engine.prefill", chunk=c, start=start, rows=n_real,
                kv_dtype=self.kv_dtype, model_version=self.model_version,
                iter=self.iter,
            )
        t0 = time.perf_counter()
        try:
            with self._phase("feed", "prefill"):
                tokens = np.zeros((1, c, 1), np.int64)
                tokens[0, :n_real, 0] = req.prompt[start:start + n_real]
                variant = self._variant("prefill:%d" % c)
                feeds, mut_in = self._feeds(variant, {
                    "gen_tokens": tokens,
                    "gen_start": np.array([start], np.int64),
                    "gen_last": np.array([n_real - 1], np.int64),
                    "gen_pages": run.table,
                    # padding rows route to no expert and stay out of the
                    # conv state
                    "gen_live": np.arange(c) < n_real,
                    "gen_state_row": np.array([run.slot + 1], np.int32),
                })
            with self._phase("dispatch", "prefill") as dispatch:
                logits, *extra, ids = self._dispatch(variant, feeds, mut_in)
                picks, sel, counts = self._extras(extra)
        except Exception as e:
            span.error(e).end()
            raise
        prefill_ms = (time.perf_counter() - t0) * 1e3
        span.tag(
            host_ms=round(prefill_ms, 3), dispatch_ms=round(dispatch.ms, 3)
        ).end()
        self._m_prefill_ms.observe(prefill_ms)
        self._m_chunks.inc()
        self._m_chunk_ctx_tokens.observe(start + n_real)
        self._m_chunk_ctx_pairs.observe(
            n_real * start + n_real * (n_real + 1) // 2)
        if self.recurrent_row_bytes:
            self._m_chunk_ssm_tokens.observe(n_real)
        run.pf_pos = start + n_real
        if extra:  # the selections only where they are recorded
            run.chunk_picks.append((
                start, n_real, picks, sel if self.record_picks else None,
                counts))
        if run.pf_pos == run.cut:
            # the chunk ended on the boundary other prompts were seen to
            # share (admit set it): keep the state there, for the prefix
            # cache to hold. Only there: a snapshot is a copy of the slot's
            # whole state (gen_state_snapshot_copy_bytes counts it), and one
            # at every page-aligned chunk end is mostly of boundaries inside
            # a prompt's own tail, which no other prompt reaches
            run.snaps[run.pf_pos] = self._snapshot_state(run.slot)
            self._m_snap_copy_bytes.inc(self.state_row_bytes, event="taken")
        if run.pf_pos < L:
            return False
        self._m_prefills.inc()
        seq = self._n_dispatched
        with self._phase("wait", "prefill"):
            self._last_prefill_logits = _Logits(logits)
            ids = np.asarray(ids)
            self._note_read(seq)
            for first, n_live, picks, sel, counts in run.chunk_picks:
                if picks is not None:
                    picks = np.asarray(picks)[:, :n_live]
                    self._m_chunk_experts.observe(
                        _experts_touched(picks, self._experts_held))
                    if self.record_picks:
                        run.picks.append((first, picks))
                if counts is not None:
                    # one table: the scan reads every position up to the
                    # chunk's last
                    self._observe_dsa(
                        np.asarray(counts), np.arange(first, first + n_live),
                        first + n_live)
                    if sel is not None:
                        run.selections.append(
                            (first, np.asarray(sel)[:, :n_live]))
            run.chunk_picks = []
        with self._phase("sample", "prefill"):
            # one row: a prompt with a temperature reads its logits whole
            tok = (self._sample(self.last_prefill_logits, req, run.rng)
                   if req.temperature else int(ids[0]))
            self._append_token(run, tok, self._max_new(req))
            if self.prefix_cache is not None:
                self.prefix_cache.insert(req.prompt, run.table, run.snaps)
                run.snaps = {}
            # arm the slot's persistent decode-feed rows only now: until the
            # last chunk lands, an interleaved decode step must keep this
            # slot on the scratch page, never writing a page a chunk already
            # filled
            self._dec_feeds["dec_block_table"][run.slot] = run.table
            self._dec_feeds["dec_tokens"][run.slot, 0] = run.tokens[-1]
            self._dec_feeds["dec_positions"][run.slot, 0] = run.next_pos
            self._set_pool_gauges()
        return True

    def start(self, req):
        """Admit one request and run its whole prefill back-to-back,
        sampling the first token. Returns a _SlotRun (possibly already
        done). Raises PoolExhausted when no capacity, ValueError on an
        inadmissible request. The scheduler instead interleaves
        prefill_step() chunks with decode steps."""
        run = self.admit(req)
        try:
            while not self.prefill_step(run):
                pass
            return run
        except Exception:
            self.finish(run)
            raise

    def decode_step(self, runs, ahead=False):
        """One fixed-shape decode step advancing every run in `runs` by one
        token. Finished runs are NOT auto-released — the caller retires them
        via finish(). Returns the runs whose tokens this call read.

        `ahead=False`: dispatch, then read the same step (all must be live).

        `ahead=True`, the scheduler's form: dispatch the step, then read the
        one dispatched by the call before and leave this one in flight, so
        that the device goes from one into the other. A run that rode the
        step in flight takes that step's id on the device; a run whose token
        in flight is its last by length is left out (no position past its
        reserved pages is written); a run whose eos the late read finds has
        ridden this step for nothing, inside pages it still holds, and the
        step's read throws its id away. With no run to go on, the step in
        flight is read and none dispatched. A step with a run that has a
        temperature is dispatched after its predecessor is read and read
        before it returns: the host samples that token."""
        prev, self._in_flight = self._in_flight, None
        if ahead:
            runs = self._going_on(runs, prev)
        elif any(run.done for run in runs):
            raise ValueError("decode_step on a finished run")
        sampled = any(run.req.temperature for run in runs)
        read = []
        if prev is not None and sampled:
            read += self._read_step(prev)
            prev = None
            runs = [run for run in runs if not run.done]
        step = self._dispatch_step(runs, prev) if runs else None
        if prev is not None:
            read += self._read_step(prev)
        if step is not None and (sampled or not ahead):
            read += self._read_step(step)
            step = None
        if ahead and sampled:
            self._m_drains.inc(reason="temperature")
        elif ahead and prev is not None and step is None:
            self._m_drains.inc(reason="idle")
        self._in_flight = step
        return list(dict.fromkeys(read))

    @property
    def steps_in_flight(self):
        """Decode steps dispatched and not yet read (0 or 1 between calls):
        whoever drives decode_step(ahead=True) reads the last one before it
        sleeps or closes."""
        return 0 if self._in_flight is None else 1

    def drain(self, reason):
        """Read the step in flight, if any (reason: gen_pipeline_drains')."""
        step, self._in_flight = self._in_flight, None
        if step is not None:
            self._m_drains.inc(reason=reason)
            self._read_step(step)

    def _going_on(self, runs, prev):
        """Those of `runs` that a step dispatched now advances: not a
        finished one, and not one whose token in flight is its last by
        length; the latter's feed rows drop back to the scratch page."""
        out = []
        for run in runs:
            if run.done:
                continue
            riding = prev is not None and prev.rides(run)
            if len(run.tokens) + riding >= self._max_new(run.req):
                self._disarm(run.slot)
                continue
            out.append(run)
        return out

    def _dispatch_step(self, runs, prev):
        """Feed and dispatch one decode step for `runs`; `prev` is the step
        in flight, whose ids its runs take. Returns the _Step to read."""
        span = _tracing.current()
        if span:
            span = span.child(
                "engine.decode", slots=len(runs),
                kv_dtype=self.kv_dtype, model_version=self.model_version,
                iter=self.iter,
            )
        step = _Step(runs)
        step.span = span
        if prev is not None:
            self._m_found_dry.observe(1 if self._last_ids.is_ready() else 0)
        t0 = time.perf_counter()
        try:
            with self._phase("feed", "decode"):
                feeds = self._dec_feeds
                tokens, positions = feeds["dec_tokens"], feeds["dec_positions"]
                live, state_rows = feeds["dec_live"], feeds["dec_state_rows"]
                live[:] = 0
                state_rows[:] = 0
                step.positions = [run.next_pos for run in runs]
                for run in runs:
                    tokens[run.slot, 0] = (
                        FROM_DEVICE if prev is not None and prev.rides(run)
                        else run.tokens[-1])
                    positions[run.slot, 0] = run.next_pos
                    live[run.slot] = 1
                    state_rows[run.slot] = run.slot + 1
                    run.next_pos += 1
                step.ctx_tokens = sum(step.positions)
                if self._dsa_groups:
                    walked = _pk.index_positions_walked(
                        positions, self.page_size, self.max_pages)
                    step.index_rows = _pk.index_rows_scanned(
                        feeds["dec_block_table"], positions, self.page_size)
                elif self._latent:
                    walked = _pk.latent_positions_walked(
                        positions, self.page_size, self.max_pages)
                else:
                    walked = _pk.paged_positions_walked(
                        positions, self.page_size, self.max_pages,
                        self._kv_row_bytes)
                step.walked_tokens = walked
                step.variant = variant = self._variant("decode")
                feeds, mut_in = self._feeds(variant, feeds)
                self._m_feed_bytes.observe(
                    sum(a.nbytes for a in feeds.values()))
                feeds["dec_prev_ids"] = self._ids
            with self._phase("dispatch", "decode") as dispatch:
                self._m_in_flight.observe(1 if prev is None else 2)
                # the step's fetch: the ids [slots], the routers' picks
                # [n_moe, slots, k] where the model has experts, and the
                # logits of each row that has a temperature; the copies
                # start when the step ends, not when the host asks
                step.logits, *extra, step.ids = self._dispatch(
                    variant, feeds, mut_in)
                step.seq = self._n_dispatched
                self._ids = step.ids
                step.picks, step.sel, step.counts = self._extras(extra)
                step.rows = {
                    run.slot: self._row_program()(
                        step.logits, np.int32(run.slot))
                    for run in runs if run.req.temperature}
                # the selections (n_full x max_slots x topk ids) only where
                # they are recorded
                fetch = tuple(a for a in (
                    step.ids, step.picks, step.counts,
                    step.sel if self.record_picks else None, *step.rows.values())
                    if a is not None)
                for arr in fetch:
                    arr.copy_to_host_async()
                step.fetch_bytes = sum(arr.nbytes for arr in fetch)
        except Exception as e:
            span.error(e).end()
            raise
        step.host_ms = (time.perf_counter() - t0) * 1e3
        step.dispatch_ms = dispatch.ms
        return step

    def _read_step(self, step):
        """Read a dispatched step: block on its fetch, count it, append each
        run's token. Returns its runs."""
        runs = step.runs
        t0 = time.perf_counter()
        try:
            with self._phase("wait", "decode") as wait:
                ids = np.asarray(step.ids)
                self._note_read(step.seq)
                picks = None if step.picks is None else np.asarray(step.picks)
                counts = None if step.counts is None else np.asarray(step.counts)
                rows = {slot: np.asarray(row)
                        for slot, row in step.rows.items()}
        except Exception as e:
            step.span.error(e).end()
            raise
        self._last_logits = _Logits(step.logits)
        self.fetch_bytes = step.fetch_bytes
        self._m_fetch_bytes.observe(self.fetch_bytes)
        # the host's time in the step: its dispatch's and its read's, not
        # what the scheduler did between the two
        step_ms = step.host_ms + (time.perf_counter() - t0) * 1e3
        step.span.tag(
            host_ms=round(step_ms, 3), dispatch_ms=round(step.dispatch_ms, 3),
            wait_ms=round(wait.ms, 3),
        ).end()
        self._m_step_ms.observe(step_ms)
        self._m_ctx_tokens.observe(step.ctx_tokens)
        self._m_walked_tokens.observe(step.walked_tokens)
        if self.recurrent_row_bytes:
            self._m_ssm_rows.observe(len(runs))
            self._m_ssm_state_bytes.observe(
                len(runs) * 2 * self.recurrent_row_bytes)
        self._m_steps.inc()
        if picks is not None:
            self._note_picks(picks, step)
            self._m_moe_kernel_layers.observe(step.variant.moe_kernel_layers)
        if counts is not None:
            self._observe_dsa(counts, step.positions, step.index_rows,
                              step=True)
            if self.record_picks:
                sel = np.asarray(step.sel)
                for run, pos in zip(runs, step.positions):
                    if not run.done:
                        run.selections.append(
                            (pos, sel[:, run.slot:run.slot + 1]))
        with self._phase("sample", "decode"):
            for run in runs:
                if run.done:
                    # its eos was read after this step went out: the id of
                    # the row-step it rode for nothing is thrown away
                    continue
                tok = (self._sample(rows[run.slot], run.req, run.rng)
                       if run.req.temperature else int(ids[run.slot]))
                self._append_token(run, tok, self._max_new(run.req))
        return runs

    def _extras(self, extra):
        """(picks, selections, dsa counts): a variant's fetches between its
        logits and its ids, each None where the model has none (picks: expert
        layers; the other two: full dsa layers, BlockDecoder._fetches)."""
        picks = sel = counts = None
        if self._dsa_groups:
            *extra, sel, counts = extra
        if extra:
            picks = extra[0]
        return picks, sel, counts

    def _observe_dsa(self, counts, positions, scanned, step=False):
        """The gen_dsa_* histograms of one decode step (`step`) or prefill
        chunk: counts [n_full] int32 from its program (dsa_count: the rows
        a full layer's selection names), the positions of its query rows
        (each attends 0..position; a step's are its live rows) and the
        distinct index-key rows one scan reads (the host's count: every full
        layer scans the same)."""
        counts = np.asarray(counts, np.int64).reshape(-1)
        used = np.array([len(g[1]) for g in self._dsa_groups], np.int64)
        attended = np.asarray(positions, np.int64) + 1
        picked = np.minimum(attended, self._dsa_topk)
        m = self._m_dsa
        m["index_positions"].observe(int(scanned) * len(used))
        m["index_pairs"].observe(int(attended.sum()) * len(used))
        m["selected_positions"].observe(int((counts * used).sum()))
        m["selected_pairs"].observe(int(picked.sum() * used.sum()))
        if step:
            block, trips = _gen_ops.sparse_walk_blocks(
                attended.size, self.max_slots)
            m["walk_rows"].observe(block * trips)
        if step and attended.size:
            m["selected_share"].observe(float(100.0 * np.mean(picked / attended)))

    def _note_picks(self, picks, step):
        """Experts touched by one decode step's live rows, from its picks."""
        self.last_picks = picks  # parity surface, like last_logits
        runs = step.runs
        self._m_experts_touched.observe(_experts_touched(
            picks[:, [run.slot for run in runs], :], self._experts_held))
        if self.record_picks:
            for run, pos in zip(runs, step.positions):
                if not run.done:
                    run.picks.append((pos, picks[:, run.slot:run.slot + 1]))

    def finish(self, run):
        """Retire a run's slot: pages return to the pool for reuse (cached
        prefix pages stay alive under the trie's reference) and the slot's
        persistent decode-feed rows drop back to the scratch page so the
        next tenant can't inherit a stale table."""
        self.pool.release(run.slot)
        self._disarm(run.slot)
        self._set_pool_gauges()

    def _disarm(self, slot):
        """A slot's persistent decode-feed rows back to the scratch page."""
        self._dec_feeds["dec_block_table"][slot] = 0
        self._dec_feeds["dec_tokens"][slot] = 0
        self._dec_feeds["dec_positions"][slot] = 0

    def _append_token(self, run, tok, max_new):
        run.tokens.append(tok)
        self.tokens_generated += 1
        self._m_tokens.inc()
        eos = run.req.eos_id
        if eos is None:
            eos = getattr(self.model, "eos_id", None)
        if eos is not None and tok == eos:
            run.done, run.finish_reason = True, "eos"
        elif len(run.tokens) >= max_new:
            run.done, run.finish_reason = True, "length"

    def _sample(self, logits, req, rng):
        """The token of a row that has a temperature, from its logits and
        the request's own seeded stream (a greedy row's is _emit_ids')."""
        logits = np.asarray(logits, np.float64)
        z = logits / req.temperature
        if req.top_k and req.top_k < z.size:
            kth = np.partition(z, -req.top_k)[-req.top_k]
            z = np.where(z < kth, -np.inf, z)
        z = z - z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(rng.choice(z.size, p=p))

    def _set_pool_gauges(self):
        st = self.pool.stats()
        self._m_slots.set(st["slots_in_use"])
        self._m_occ.set(st["slot_occupancy"])
        self._m_pages.set(st["pages_in_use"])
        self._m_pages_shared.set(st["pages_shared"])
        self._m_paged_flash.set(_pk.KERNEL_DISPATCHES.get("paged_flash", 0))
        if self.prefix_cache is not None:
            st = self.prefix_cache.stats()
            self._m_prefix_hit.set(st["hit_rate"])
            if self._state_names:
                self._m_snap_bytes.set(float(st["state_bytes"]), what="held")
                self._m_snap_bytes.set(
                    float(st["state_bytes_reserved"]), what="reserved")
                for event, key in (("taken", "states_taken"),
                                   ("restored", "states_hit"),
                                   ("evicted", "states_evicted")):
                    new = st[key] - self._states_seen[event]
                    if new:
                        self._m_states.inc(new, event=event)
                        self._states_seen[event] = st[key]

    # ---- convenience / stats ----------------------------------------------
    def generate(self, prompt, max_new_tokens=16, **kw):
        """Serial one-request decode (no scheduler): admit, step to
        completion, retire. The whole-sequence tests' reference path."""
        req = GenRequest(prompt, max_new_tokens=max_new_tokens, **kw)
        run = self.start(req)
        try:
            while not run.done:
                self.decode_step([run])
        finally:
            self.finish(run)
        return run.result()

    def compiled_hlo(self):
        """{variant kind: post-optimization HLO text} of every variant built
        so far ('decode', 'prefill:<bucket>'); each instruction carries the
        op_name metadata that names the framework op behind it (see
        Executor.compiled_hlo)."""
        return {k: v.fn.as_text() for k, v in sorted(self._variants.items())}

    def stats(self):
        out = {
            "variants": len(self._variants),
            "traces": self.traces,
            "cache_hits": self.cache_hits,
            "model_version": self.model_version,
            "tokens_generated": self.tokens_generated,
            "prefill_buckets": list(self.prefill_buckets),
            "prefill_chunk": self.prefill_chunk,
            "prefill_chunks": self._m_chunks.value(),
            "geometry": self.geometry(),
            # the most the paged kernel can walk in a decode step (every
            # slot at full context); gen_walked_tokens has what it walks
            # and gen_ctx_tokens the positions it has to attend
            "positions_walked_per_step": (
                self.max_slots * self.max_pages * self.page_size
            ),
            # bytes the last decode step copied to the host (ids + picks +
            # the logits of each sampled row); gen_fetch_bytes has every
            # step's
            "decode_fetch_bytes": self.fetch_bytes,
            "pool": self.pool.stats(),
            # lowering-time kernel choices (counts are per trace, not per
            # call): the smoke/bench stages assert paged_flash shows up here
            # when the flag forces it
            "kernel_dispatches": {
                k: v
                for k, v in _pk.KERNEL_DISPATCHES.items()
                if k in ("paged_flash", "paged_flash_int8", "paged_latent",
                         "dsa_index", "hyper_maps", "moe_grouped", "ssm_step",
                         "gemm_dbuf", "gemm_epilogue", "gemm_int8",
                         "gemm_fp8")
            },
            "kv": {
                "dtype": self.kv_dtype,
                "resident_bytes": self.kv_state_bytes,
                # the fixed per-slot state beside the pages (conv and
                # state-space layers' rows, scratch row included), one
                # slot's share of it (what a prefix-cache snapshot holds),
                # and the recurrent part of that (what a decode step reads
                # and writes for every live row)
                "state_bytes": self.seq_state_bytes,
                "state_row_bytes": self.state_row_bytes,
                "recurrent_row_bytes": self.recurrent_row_bytes,
            },
        }
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.stats()
        if self.cache is not None:
            out["cache"] = self.cache.stats()
        return out


# A scheduler pass at or over this is a stall: it is observed a second time
# (sched_slow_iter_ms) and leaves a line on stderr. A constant: the slowest
# benchmark cell's pass is 22.5 ms (PERF.md).
SLOW_PASS_MS = 100.0
# at most one such line in this many seconds; the next says how many it held
SLOW_PASS_LINE_EVERY_S = 1.0


class _Pending:
    __slots__ = ("req", "future", "t_submit", "span")

    def __init__(self, req, span=NULL_SPAN):
        self.req = req
        self.future = ServingFuture()
        self.t_submit = time.perf_counter()
        self.span = span


class GenerationScheduler(ContinuousBatcher):
    """Token-level continuous scheduler over a GenerationEngine.

    Reuses the ContinuousBatcher shell (bounded queue, condition variable,
    worker thread, outcome metrics, drain/shutdown) but replaces the batch
    dispatcher with a step loop:

      1. admit queued requests into free slots — normally at most
         `prefill_per_step` prefills per step (prefill latency rides on top
         of every live slot's token latency), escalating to ALL free slots
         when the queue is deeper than `pressure_queue` (throughput beats
         tail latency once a backlog forms);
      2. dispatch ONE fixed-shape decode step for every live slot that
         goes on, from the ids the step before it left on the device, then
         read that step before it (engine.decode_step(ahead=True): the
         host is one step behind the device, except where a row has a
         temperature);
      3. retire the slots the read found finished (EOS, seen one step
         late / max-new / context bound), releasing their pages, and
         resolve their futures with GenResult.

    The queue is bounded in REQUESTS (one row each — a generation request's
    device debt is a slot, not its prompt length).
    """

    def __init__(self, engine, max_queue_requests=64, timeout_ms=30000.0,
                 prefill_per_step=1, pressure_queue=4):
        self.prefill_per_step = max(1, int(prefill_per_step))
        self.pressure_queue = int(pressure_queue)
        self._runs = {}  # slot -> _SlotRun
        self._prefills = []  # admitted runs still working through chunks
        self._drain_flag = True
        from ..observability import registry as _registry

        reg = _registry.default_registry()
        p = "serving/%s" % engine.name
        self._m_ttft_ms = reg.histogram(
            p + "/gen_ttft_ms", "submit -> first token, wall ms"
        )
        self._m_token_ms = reg.histogram(
            p + "/gen_token_ms", "per-token latency (decode step wall)"
        )
        # the scheduler thread's time by phase (fluid.sched.* spans): idle is
        # the wait for work outside an iteration, the other five partition it
        self._m_sched_ms = {
            phase: reg.histogram(p + "/sched_%s_ms" % phase, help)
            for phase, help in (
                ("iter", "one scheduler iteration, wall ms"),
                ("idle", "waiting with nothing to serve, wall ms"),
                ("lock", "iteration: acquiring the queue's lock, wall ms"),
                ("admit", "iteration: admission (queue pop, slot and page "
                          "reservation), wall ms"),
                ("prefill", "iteration: prefill chunks and first-token "
                            "bookkeeping, wall ms"),
                ("decode", "iteration: the decode step, wall ms"),
                ("retire", "iteration: retiring finished runs and resolving "
                           "their futures, wall ms"),
            )
        }
        self._m_stall_ms = reg.histogram(
            p + "/sched_host_stall_ms",
            "iteration: wall minus thread CPU time of its host-only phases "
            "(admit, retire, the engine's feed and sample): the scheduler "
            "thread waiting for the interpreter lock, a mutex or the OS",
        )
        self._m_slow_ms = reg.histogram(
            p + "/sched_slow_iter_ms",
            "an iteration of SLOW_PASS_MS (100) or more, wall ms: observed "
            "here a second time, beside the [fluid.slow_pass] line on stderr",
        )
        self._iter = 0
        # what a pass leaves for the slow-pass line: its phases' events, the
        # prompts it admitted, the chunks it ran, its host stall
        self._phases = {}
        self._admitted = self._chunks = 0
        self._host_stall_ms = 0.0
        self._slow_line_at = float("-inf")
        self._slow_held_back = 0
        self._gc = watch_gc()
        # the engine's histograms whose sum inside a slow pass its line gives
        self._m_inside = {
            "prefill_wait_ms": engine._m_prefill_phase_ms["wait"],
            "prefill_sample_ms": engine._m_prefill_phase_ms["sample"],
            "decode_dispatch_ms": engine._m_phase_ms["dispatch"],
            "decode_wait_ms": engine._m_phase_ms["wait"],
            "prefix_evict_ms": engine._m_prefix_ms["evict"],
        }
        super().__init__(
            engine,
            max_queue_rows=max_queue_requests,
            max_batch_delay_ms=0.0,
            timeout_ms=timeout_ms,
        )

    # ---- client side ------------------------------------------------------
    def submit(self, prompt, max_new_tokens=16, eos_id=None, temperature=None,
               top_k=None, seed=None, parent=None):
        """Enqueue one generation request; returns a ServingFuture resolving
        to a GenResult. `parent` optionally links the request's trace span
        under a caller span (or an X-Fleet-Trace header value)."""
        req = GenRequest(
            prompt, max_new_tokens=max_new_tokens, eos_id=eos_id,
            temperature=temperature, top_k=top_k, seed=seed,
        )
        if len(req.prompt) > self.engine.max_prompt_len:
            raise ValueError(
                "prompt of %d tokens exceeds max_prompt_len %d"
                % (len(req.prompt), self.engine.max_prompt_len)
            )
        pending = _Pending(req, span=_tracing.tracer().start_span(
            "serving.genrequest", parent=parent, model=self.engine.name,
            prompt_len=len(req.prompt), max_new=req.max_new_tokens,
        ))
        with self._cond:
            if not self._alive or self._draining:
                self._m_requests.inc(outcome="shutdown")
                pending.span.tag(outcome="shutdown").end("error")
                raise ShutdownError("scheduler is shut down")
            if self._queued_rows + 1 > self.max_queue_rows:
                self._m_requests.inc(outcome="rejected")
                pending.span.tag(outcome="rejected").end("error")
                raise QueueFullError(
                    "queue full (%d requests queued, limit %d)"
                    % (self._queued_rows, self.max_queue_rows)
                )
            pending.span.event("queued", depth=self._queued_rows)
            self._queue.append(pending)
            self._queued_rows += 1
            self._m_depth.set(self._queued_rows)
            self._cond.notify_all()
        return pending.future

    def run(self, prompt, timeout=None, **kw):
        return self.submit(prompt, **kw).result(
            self.timeout * 2 if timeout is None else timeout
        )

    # ---- step loop --------------------------------------------------------
    def _phase(self, phase, stall=None):
        event = self._phases[phase] = RecordEvent(
            span="sched." + phase, hist=self._m_sched_ms[phase], stall=stall,
            iter=self._iter,
        )
        return event

    def _loop(self):
        while True:
            # an unlocked peek (only this thread empties these), checked
            # again under the lock: a busy scheduler takes the lock once a
            # pass, inside the pass, where its wait is measured
            if not self._busy():
                self._wait_for_work()
            self._iter += 1
            self.engine.iter = self._iter
            self._phases.clear()
            self._admitted = self._chunks = 0
            self._host_stall_ms = 0.0
            gc_ms = self._gc.ms
            before = [h.sum for h in self._m_inside.values()]
            with self._phase("iter") as it:
                alive = self._pass()
            self._gc.fold()
            if it.ms >= SLOW_PASS_MS:
                self._slow_pass(it.ms, self._gc.ms - gc_ms, {
                    name: round(h.sum - was, 3) for (name, h), was
                    in zip(self._m_inside.items(), before)})
            if not alive:
                return

    def _slow_pass(self, ms, gc_ms, inside):
        """A pass that stalled: sched_slow_iter_ms, and the record an
        untraced run keeps of it: one line on stderr (as the telemetry
        health line is written), the same fields to the flight recorder (a
        no-op without FLAGS_flightrec_dir)."""
        self._m_slow_ms.observe(ms)
        now = time.monotonic()
        if now - self._slow_line_at < SLOW_PASS_LINE_EVERY_S:
            self._slow_held_back += 1
            return
        fields = {"model": self.engine.name, "iter": self._iter,
                  "ms": round(ms, 3)}
        for phase in ("lock", "admit", "prefill", "decode", "retire"):
            event = self._phases.get(phase)  # None: the pass did not reach it
            fields[phase + "_ms"] = round(event.ms, 3) if event else 0.0
        fields.update(
            host_stall_ms=round(self._host_stall_ms, 3),
            gc_ms=round(gc_ms, 3), admitted=self._admitted,
            chunks=self._chunks, **inside,
            live=len(self._runs), queued=self._queued_rows,
            held_back=self._slow_held_back,
        )
        self._slow_line_at, self._slow_held_back = now, 0
        print("[fluid.slow_pass] "
              + " ".join("%s=%s" % kv for kv in fields.items()),
              file=sys.stderr, flush=True)
        _flightrec.trigger("slow_pass", **fields)

    def _busy(self):
        """Whether a pass has anything to do: a request queued, admitted or
        decoding, or a decode step dispatched and not yet read."""
        return bool(self._queue or self._runs or self._prefills
                    or self.engine.steps_in_flight)

    def _wait_for_work(self):
        with self._cond:
            if self._alive and not self._busy():
                with self._phase("idle"):
                    while self._alive and not self._busy():
                        self._cond.wait()

    def _pass(self):
        """One iteration, in five phases that partition it: lock, admit,
        prefill, decode (dispatch the next step, read the one in flight),
        retire. False when the scheduler is closed and has nothing left to
        do."""
        stall = self.engine.host_stall
        with self._phase("lock"):
            self._cond.acquire()
        with self._phase("admit", stall):
            try:
                if not self._alive:
                    if not self._drain_flag:
                        self._fail_runs_locked()
                        return False
                    if not self._busy():
                        return False
                admits = self._admit_requests_locked()
            finally:
                self._cond.release()
            self._admitted = len(admits)
            self._admit(admits)
        with self._phase("prefill"):
            self._prefill_chunks()
        if self._runs or self.engine.steps_in_flight:
            with self._phase("decode") as decode:
                read = self._decode(list(self._runs.values()))
            with self._phase("retire", stall):
                for run in read:
                    if self._runs.get(run.slot) is not run:
                        continue  # retired at its eos, one step ago
                    self._m_token_ms.observe(decode.ms)
                    if run.done:
                        del self._runs[run.slot]
                        self._retire(run)
        self._host_stall_ms = stall.take()
        self._m_stall_ms.observe(self._host_stall_ms)
        return True

    def _admit_requests_locked(self):
        """Pop queued requests that fit free capacity right now. Admission
        is host-only (slot + page reservation); the chunk budget in _step
        governs device-side prefill pacing, so an in-flight chunked
        prefill never blocks admitting the next request — a short prompt
        admitted behind a long one overtakes it in the
        shortest-remaining-first chunk order. Pages held only by the
        prefix cache count as free — admit() evicts them on demand."""
        budget = self.prefill_per_step
        if len(self._queue) >= self.pressure_queue:
            budget = self.engine.max_slots
        pool = self.engine.pool
        st = pool.stats()
        slots_left = st["slots_total"] - st["slots_in_use"]
        pages_left = st["pages_total"] - st["pages_in_use"]
        if self.engine.prefix_cache is not None:
            pages_left += self.engine.prefix_cache.reclaimable()
        admits = []
        while self._queue and len(admits) < min(budget, slots_left):
            nxt = self._queue[0]
            if now_expired(nxt, self.timeout):
                self._queue.pop(0)
                self._queued_rows -= 1
                self._m_requests.inc(outcome="timeout")
                nxt.span.tag(outcome="timeout").end("error")
                nxt.future._set_error(RequestTimeout(
                    "queued %.0f ms > timeout %.0f ms"
                    % ((time.perf_counter() - nxt.t_submit) * 1e3,
                       self.timeout * 1e3)
                ))
                continue
            # reservation-aware: each admit here WILL acquire pages before
            # the pool state refreshes, so account for the whole batch
            need = pool.pages_for(
                len(nxt.req.prompt) + self.engine._max_new(nxt.req)
            )
            if need > pages_left:
                break
            pages_left -= need
            admits.append(self._queue.pop(0))
            self._queued_rows -= 1
        self._m_depth.set(self._queued_rows)
        return admits

    def _admit(self, admits):
        eng = self.engine
        for pending in admits:
            queue_ms = (time.perf_counter() - pending.t_submit) * 1e3
            self._m_queue_ms.observe(queue_ms)
            try:
                run = eng.admit(pending.req)
            except PoolExhausted as e:
                # capacity raced away (shouldn't happen single-threaded,
                # but never drop a request on the floor)
                self._m_requests.inc(outcome="error")
                pending.span.error(e).tag(outcome="error").end()
                pending.future._set_error(e)
                continue
            except Exception as e:
                self._m_requests.inc(outcome="error")
                pending.span.error(e).tag(outcome="error").end()
                err = RuntimeError("admit failed: %s" % (repr(e),))
                err.__cause__ = e
                pending.future._set_error(err)
                continue
            run.future = pending.future
            run.t_submit = pending.t_submit
            run.span = pending.span
            run.span.tag(
                prefix_hit=run.pf_pos > 0, prefix_tokens=run.pf_pos,
                kv_dtype=eng.kv_dtype,
            ).event("admitted", slot=run.slot, queue_ms=round(queue_ms, 3))
            self._prefills.append(run)

    def _prefill_chunks(self):
        """Advance prefill chunk-by-chunk: normally one chunk per step (its
        latency rides on every live slot's token), draining every pending
        prompt when the queue is deep OR when no slot is decoding (then
        there is nobody to stall). Chunks go shortest-remaining-first, so
        a short prompt admitted behind a half-prefilled long one
        overtakes it and samples its first token next step — the
        queue-pressure escalation bounds how long the long prompt can be
        overtaken. TTFT starts at the chunk that samples the first token."""
        if not self._prefills:
            return
        eng = self.engine
        n_chunks = self.prefill_per_step
        if not self._runs or self._queued_rows >= self.pressure_queue:
            n_chunks = len(self._prefills)
        order = sorted(self._prefills,
                       key=lambda r: len(r.req.prompt) - r.pf_pos)
        for run in order[:n_chunks]:
            self._chunks += 1
            try:
                with _tracing.tracer().activate(run.span):
                    finished = eng.prefill_step(run)
            except Exception as e:
                self._prefills.remove(run)
                self._m_requests.inc(outcome="error")
                run.span.error(e).tag(outcome="error").end()
                err = RuntimeError("prefill failed: %s" % (repr(e),))
                err.__cause__ = e
                run.future._set_error(err)
                eng.finish(run)
                continue
            if finished:
                self._prefills.remove(run)
                run.t_first = time.perf_counter()
                ttft_ms = (run.t_first - run.t_submit) * 1e3
                self._m_ttft_ms.observe(ttft_ms)
                run.span.event("first_token", ttft_ms=round(ttft_ms, 3))
                if run.done:
                    self._retire(run)
                else:
                    self._runs[run.slot] = run

    def _decode(self, live):
        """Dispatch the next decode step for the live runs that go on and
        read the one in flight; returns the runs read. When either failed,
        none: every live run (those of the step in flight are among them)
        then holds the error and its slot is released."""
        eng = self.engine
        try:
            # the decode step is shared across slots; its engine.decode
            # span hangs off one representative request's trace
            with _tracing.tracer().activate(
                    live[0].span if live else NULL_SPAN):
                return eng.decode_step(live, ahead=True)
        except Exception as e:
            for run in live:
                self._m_requests.inc(outcome="error")
                run.span.error(e).tag(outcome="error").end()
                err = RuntimeError("decode failed: %s" % (repr(e),))
                err.__cause__ = e
                run.future._set_error(err)
                eng.finish(run)
            self._runs.clear()
            return []

    def _retire(self, run):
        self.engine.finish(run)
        self._m_requests.inc(outcome="ok")
        self._m_latency_ms.observe((time.perf_counter() - run.t_submit) * 1e3)
        run.span.tag(
            outcome="ok", finish_reason=run.finish_reason,
            tokens=len(run.tokens),
            decode_steps=max(0, len(run.tokens) - 1),
            model_version=self.engine.model_version,
        ).end()
        run.future._set_result(run.result())

    def _fail_runs_locked(self):
        try:
            self.engine.drain("close")  # no slot is released under a step
        except Exception:
            pass  # its runs fail below either way
        for run in list(self._runs.values()) + self._prefills:
            self._m_requests.inc(outcome="shutdown")
            run.span.tag(outcome="shutdown").end("error")
            run.future._set_error(ShutdownError("scheduler closed"))
            self.engine.finish(run)
        self._runs.clear()
        del self._prefills[:]

    def close(self, drain=True, timeout=30.0):
        self._drain_flag = bool(drain)
        return super().close(drain=drain, timeout=timeout)

    def stats(self):
        with self._cond:
            return {
                "queued_requests": self._queued_rows,
                "live_slots": len(self._runs),
                "prefilling": len(self._prefills),
                "alive": self._alive,
            }


def now_expired(pending, timeout):
    return (time.perf_counter() - pending.t_submit) > timeout
