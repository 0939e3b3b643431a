"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py            one TPU chip: phases train, serve, kernels
    python chip_smoke.py --chips 4  one four-chip host: the train model under
                                    ParallelExecutor, dp=4 and fsdp=2 x tp=2

One process, no network, inputs from a seed, nothing read that git would not
commit. It drives the two main paths once through the entry points a user
calls, at the full width of a model the repository supports, and checks what
comes out by the repository's own means:

  train    the transformer of build_transformer below (enc-dec,
           4+4 layers, d_model 2048, 16 heads, d_inner 8192, vocab 32000,
           b 8, t 1024, flash attention, bf16 with bf16 Adam moments) under
           pass_pipeline="training_fused" through
           fluid.Executor(fluid.TPUPlace()): eight single-dispatch steps on
           one batch, one steps_per_run=4 call, and the first step again
           with the passes off as the reference.
  serve    GPTDecoder at GPT-2-small width (12 layers, 12 heads, d_model
           768, d_inner 3072, vocab 50257, context 1024) in a
           GenerationEngine with max_slots=8 behind a ModelServer, answering
           six POST :generate requests; first-token logits against an engine
           built with paged_flash="off".
  kernels  every Pallas family the two phases did not reach, compiled by
           Mosaic (interpret=False), run once at a shape a cell would use
           and compared with its dense reference.

It exits non-zero, and prints no result line, unless JAX's first device is a
TPU; no exception is caught on the way to a zero exit. Times it prints are
set-up facts and single observations, not metrics. What each phase saw goes
out as one "chip_smoke: facts {...}" line; the last line of standard output
is exactly {"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}
with the device as JAX reports it.

The phase functions take their sizes as arguments so tests/test_chip_smoke.py
can run them at toy width on the CPU (Pallas in interpret mode); only main()
insists on the chip.
"""

import argparse
import gc
import json
import os
import re
import sys
import threading
import time

import numpy as np

TRAIN = dict(b=8, t=1024, d=2048, n_layer=4, vocab=32000)
SERVE = dict(
    model=dict(vocab_size=50257, n_layer=12, n_head=12, d_model=768,
               d_inner=3072, max_context=1024),
    engine=dict(max_slots=8),  # otherwise GenerationEngine's defaults
    # six prompts; the 200- and 700-token ones cross the default 32-row
    # prefill chunk many times, and the 96-token one repeats the first 64
    # tokens of the 200-token one (eight whole pages for the prefix cache)
    prompt_lens=(200, 96, 700, 5, 40, 40),
    shared_prefix=64,
    new_tokens=32,
)
# the bar the on-chip op-test lane uses for f32 values that crossed the MXU
# at default precision (tests/test_pallas_kernels.py)
MXU_RTOL = MXU_ATOL = 2e-2


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(ok, what):
    if not ok:
        raise SmokeFailure(what)


def say(msg):
    print("chip_smoke: " + msg, flush=True)


def custom_call_families(hlo_text):
    """{family: count} over the tpu_custom_call instructions of a compiled
    module, read from the op_name each lowering scope wrote
    (registry.lower_ops: ".../<op type>/..." and ".../pallas_kernel=<family>.<group>/...").
    An interpreted Pallas kernel leaves no such instruction."""
    fams = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        name = m.group(1) if m else ""
        m = re.search(r"pallas_kernel=([a-z0-9_]+)\.", name) or re.search(
            r"/(flash_attention(?:_grad)?|paged_attention)/", name
        )
        fam = m.group(1) if m else "other"
        fams[fam] = fams.get(fam, 0) + 1
    return fams


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _cache_entries():
    import jax

    d = jax.config.jax_compilation_cache_dir
    try:
        return d, len(os.listdir(d))
    except OSError:
        return d, 0


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------


def build_transformer(b=8, t=1024, d=2048, n_layer=4, vocab=32000,
                      moment_dtype=None):
    """Build the enc-dec Transformer train step. Returns
    (main, startup, feed, loss) with the feed already staged on device."""
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu import framework
    from paddle_tpu.models import transformer as T

    n_head, d_inner = 16, 4 * d
    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            feeds = {}
            for name, shape, dtype in [
                ("src_word", [t], "int64"), ("src_pos", [t], "int64"),
                ("trg_word", [t], "int64"), ("trg_pos", [t], "int64"),
                ("label", [t], "int64"), ("label_weight", [t, 1], "float32"),
            ]:
                feeds[name] = fluid.layers.data(name=name, shape=shape, dtype=dtype)
            loss, _ = T.transformer(
                feeds["src_word"], feeds["src_pos"], feeds["trg_word"],
                feeds["trg_pos"], None, None, None,
                feeds["label"], feeds["label_weight"],
                src_vocab_size=vocab, trg_vocab_size=vocab,
                n_layer=n_layer, n_head=n_head, d_model=d, d_inner=d_inner,
                d_key=d // n_head, d_value=d // n_head,
                dropout=0.0, max_length=t + 1, use_flash=True, padded=False,
            )
            fluid.optimizer.Adam(
                learning_rate=1e-4, moment_dtype=moment_dtype
            ).minimize(loss)

    rng = np.random.RandomState(0)
    pos = np.tile(np.arange(t), (b, 1)).astype("int64")
    feed = {
        "src_word": jax.device_put(rng.randint(0, vocab, (b, t)).astype("int64")),
        "src_pos": jax.device_put(pos),
        "trg_word": jax.device_put(rng.randint(0, vocab, (b, t)).astype("int64")),
        "trg_pos": jax.device_put(pos.copy()),
        "label": jax.device_put(rng.randint(0, vocab, (b, t)).astype("int64")),
        "label_weight": jax.device_put(np.ones((b, t, 1), "float32")),
    }
    return main, startup, feed, loss


def _train_run(cfg, pipeline, n_steps, multistep):
    """Build the train transformer afresh, train `n_steps` single-dispatch
    steps on its fixed batch under `pipeline`, then (multistep > 0) one
    steps_per_run call. Everything it put on the device is dropped before it
    returns."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu.fluid as fluid
    from paddle_tpu import flags
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.transpiler.bf16_transpiler import Bf16Transpiler

    platform = jax.devices()[0].platform
    main, startup, feed, loss = build_transformer(
        moment_dtype="bfloat16", **cfg
    )
    prev = flags.get_flags("pass_pipeline")
    flags.set_flags({"pass_pipeline": pipeline})
    pk.KERNEL_DISPATCHES.clear()
    exe = fluid.Executor(
        fluid.TPUPlace() if platform == "tpu" else fluid.CPUPlace()
    )
    scope = Scope(seed=0)
    out = {}
    try:
        with scope_guard(scope):
            exe.run(startup)
            Bf16Transpiler().transpile(main)
            losses, secs = [], []
            for _ in range(n_steps):
                t0 = time.perf_counter()
                (l,) = exe.run(main, feed=feed, fetch_list=[loss.name],
                               return_numpy=False)
                jax.block_until_ready(l)
                secs.append(time.perf_counter() - t0)
                losses.append(float(np.asarray(l).reshape(-1)[0]))
            out["losses"] = losses
            out["first_step_s"] = secs[0]  # trace + compile + one step
            if n_steps > 1:
                out["step_ms"] = float(np.median(secs[1:])) * 1e3
            out["dispatches"] = dict(pk.KERNEL_DISPATCHES)
            if platform == "tpu":
                out["custom_calls"] = custom_call_families(exe.compiled_hlo())
            out["off_device"] = sorted(
                n for n, a in scope.vars.items()
                if isinstance(a, jax.Array)
                and any(d.platform != platform for d in a.devices())
            )
            if multistep:
                stacked = {n: jnp.stack([v] * multistep) for n, v in feed.items()}
                t0 = time.perf_counter()
                (l,) = exe.run(main, feed=stacked, fetch_list=[loss.name],
                               return_numpy=False, steps_per_run=multistep)
                jax.block_until_ready(l)
                out["multistep_s"] = time.perf_counter() - t0
                out["multistep_losses"] = [
                    float(v) for v in np.asarray(l).reshape(-1)
                ]
    finally:
        flags.set_flags(prev)
        scope.vars.clear()
        exe.close()
        del feed, scope, exe
        gc.collect()
    return out


def phase_train(cfg=TRAIN, n_steps=8, multistep=4):
    import jax

    t0 = time.perf_counter()
    ref = _train_run(cfg, "", 1, 0)  # the passes off: the reference step
    say("train: unfused first step %.1f s (compile included), loss %.4f"
        % (ref["first_step_s"], ref["losses"][0]))
    run = _train_run(cfg, "training_fused", n_steps, multistep)
    losses = run["losses"]
    say("train: fused first step %.1f s (compile included), then %.1f ms a "
        "step; steps_per_run=%d call %.1f s (compile included); losses %s"
        % (run["first_step_s"], run["step_ms"], multistep, run["multistep_s"],
           " ".join("%.4f" % v for v in losses)))

    every = losses + run["multistep_losses"]
    check(np.isfinite(every).all(), "train: a loss is not finite: %s" % every)
    check(len(run["multistep_losses"]) == multistep,
          "train: steps_per_run=%d returned %d losses"
          % (multistep, len(run["multistep_losses"])))
    check(losses[-1] < losses[0],
          "train: loss did not fall over %d steps on one batch: %s"
          % (n_steps, losses))
    rel = abs(losses[0] - ref["losses"][0]) / abs(ref["losses"][0])
    check(rel <= 1e-2,
          "train: first-step loss %.6f under training_fused is %.2e relative "
          "from %.6f with the passes off" % (losses[0], rel, ref["losses"][0]))
    # the flash tiers are the op's own lowering, not a pass's substitution
    fused = {k: n for k, n in ref["dispatches"].items()
             if not k.startswith("flash_")}
    check(not fused,
          "train: kernels dispatched with the passes off: %s" % fused)
    disp = run["dispatches"]
    for fam in ("layer_norm", "layer_norm_grad", "gemm_epilogue"):
        check(disp.get(fam, 0) > 0,
              "train: no %s dispatch under training_fused: %s" % (fam, disp))
    check(not run["off_device"] and not ref["off_device"],
          "train: scope arrays off the device: %s"
          % (run["off_device"] or ref["off_device"])[:5])
    if jax.devices()[0].platform == "tpu":
        calls = run["custom_calls"]
        for fam in ("flash_attention", "flash_attention_grad", "gemm_epilogue",
                    "layer_norm", "layer_norm_grad"):
            check(calls.get(fam, 0) > 0,
                  "train: no tpu_custom_call for %s in the compiled step: %s"
                  % (fam, calls))
        for fam in ("flash_attention", "flash_attention_grad"):
            check(ref["custom_calls"].get(fam, 0) > 0,
                  "train: no tpu_custom_call for %s in the unfused step: %s"
                  % (fam, ref["custom_calls"]))
    facts = {
        "seconds": round(time.perf_counter() - t0, 1),
        "first_step_s_fused": round(run["first_step_s"], 1),
        "first_step_s_unfused": round(ref["first_step_s"], 1),
        "multistep_call_s": round(run["multistep_s"], 1),
        "step_ms_observed": round(run["step_ms"], 1),
        "loss_first": losses[0],
        "loss_last": losses[-1],
        "loss_first_unfused": ref["losses"][0],
        "dispatches": disp,
        "custom_calls": run.get("custom_calls"),
        "peak_bytes_in_use": _peak_bytes(),
    }
    say("train: ok, peak_bytes_in_use %s" % facts["peak_bytes_in_use"])
    return facts


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------


def _prompts(cfg, rng):
    vocab = cfg["model"]["vocab_size"]
    prompts = [
        [int(t) for t in rng.randint(2, vocab, n)] for n in cfg["prompt_lens"]
    ]
    shared = cfg["shared_prefix"]
    prompts[1][:shared] = prompts[0][:shared]
    return prompts


def _post_generate(port, name, prompt, new_tokens):
    import urllib.request

    req = urllib.request.Request(
        "http://127.0.0.1:%d/v1/models/%s:generate" % (port, name),
        data=json.dumps(
            {"prompt": prompt, "max_new_tokens": new_tokens}
        ).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=900) as resp:
        return resp.status, json.loads(resp.read().decode())


def phase_serve(cfg=SERVE):
    import jax

    from paddle_tpu import flags
    from paddle_tpu.executor import Scope
    from paddle_tpu.models.gpt_decoder import GPTDecoder
    from paddle_tpu.observability import registry
    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.serving import ModelServer
    from paddle_tpu.serving.generation import GenerationEngine, GenRequest

    t_phase = time.perf_counter()
    on_tpu = jax.devices()[0].platform == "tpu"
    # an eos id no token has: every request runs its full length
    model = GPTDecoder(eos_id=-1, **cfg["model"])
    prompts = _prompts(cfg, np.random.RandomState(0))
    new_tokens = cfg["new_tokens"]
    probe = prompts[4]  # the first 40-token prompt

    pk.KERNEL_DISPATCHES.clear()
    server = ModelServer(request_timeout_ms=900e3)
    t0 = time.perf_counter()
    engine = server.add_generation_model(
        "gpt", model=model, scope=Scope(seed=0), **cfg["engine"]
    )  # warmup=True: the decode step and every prefill bucket compile here
    warm_s = time.perf_counter() - t0
    traces = engine.traces
    n_variants = len(engine.prefill_buckets) + 1
    say("serve: warmup compiled %d variants in %.1f s" % (n_variants, warm_s))
    check(engine.stats()["variants"] == n_variants,
          "serve: warmup built %d variants, expected %d"
          % (engine.stats()["variants"], n_variants))
    paged = pk.KERNEL_DISPATCHES.get("paged_flash", 0)
    check(paged > 0,
          "serve: the paged flash kernel was not selected: %s"
          % pk.KERNEL_DISPATCHES)
    if on_tpu:
        for kind, v in sorted(engine._variants.items()):
            check("tpu_custom_call" in v.fn.as_text(),
                  "serve: executable %r holds no tpu_custom_call" % kind)

    # first-token logits of one prompt, against the dense gather
    run = engine.start(GenRequest(probe, max_new_tokens=1))
    logits = np.array(engine.last_prefill_logits, np.float32)
    engine.finish(run)
    prev = flags.get_flags("paged_flash")
    flags.set_flags({"paged_flash": "off"})
    try:
        ref_engine = GenerationEngine(
            model, name="gpt_ref", scope=Scope(seed=0), **cfg["engine"]
        )
        run = ref_engine.start(GenRequest(probe, max_new_tokens=1))
        ref_logits = np.array(ref_engine.last_prefill_logits, np.float32)
        ref_engine.finish(run)
    finally:
        flags.set_flags(prev)
    check(pk.KERNEL_DISPATCHES.get("paged_flash", 0) == paged,
          "serve: the reference engine dispatched the paged kernel")
    check(logits.shape == (cfg["model"]["vocab_size"],)
          and np.isfinite(logits).all(),
          "serve: first-token logits are not finite %s values" % (logits.shape,))
    err = float(np.max(np.abs(logits - ref_logits)))
    check(np.allclose(logits, ref_logits, rtol=MXU_RTOL, atol=MXU_ATOL),
          "serve: first-token logits differ from the paged_flash=off engine "
          "by up to %.3e (|ref| up to %.3e)"
          % (err, float(np.max(np.abs(ref_logits)))))
    del ref_engine
    gc.collect()

    # six requests over HTTP, three client threads, each in order: the
    # 96-token prompt follows the 200-token one whose prefix it shares
    port = server.start()
    replies = [None] * len(prompts)
    errors = []

    def client(indices):
        try:
            for i in indices:
                replies[i] = _post_generate(port, "gpt", prompts[i], new_tokens)
        except Exception as e:  # noqa: BLE001 — re-raised below, on the main thread
            errors.append(e)

    threads = [
        threading.Thread(target=client, args=(ix,))
        for ix in ((0, 1), (2, 3), (4, 5))
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    serve_s = time.perf_counter() - t0
    stopped = server.stop(drain=True)
    if errors:
        raise errors[0]
    for i, (status, doc) in enumerate(replies):
        check(status == 200 and len(doc["tokens"]) == new_tokens,
              "serve: request %d (%d-token prompt) answered %s with %d tokens"
              % (i, len(prompts[i]), status, len(doc.get("tokens", ()))))
    check(stopped, "serve: the scheduler did not drain")
    check(engine.traces == traces,
          "serve: %d traces after warmup" % (engine.traces - traces))
    pool = engine.pool.stats()
    check(pool["slots_in_use"] == 0
          and pool["pages_in_use"] == engine.prefix_cache.reclaimable(),
          "serve: the pool did not drain to the prefix cache's pages: %s, "
          "reclaimable %d" % (pool, engine.prefix_cache.reclaimable()))
    prefix = engine.prefix_cache.stats()
    check(prefix["pages_hit"] >= cfg["shared_prefix"] // engine.page_size,
          "serve: the shared prefix was not served from the cache: %s" % prefix)

    reg = registry.default_registry()
    step = reg.get("serving/gpt/gen_step_ms")
    chunk = reg.get("serving/gpt/gen_prefill_ms")
    facts = {
        "seconds": round(time.perf_counter() - t_phase, 1),
        "warmup_s": round(warm_s, 1),
        "variants": n_variants,
        "requests_s": round(serve_s, 2),
        "request_latency_ms_observed": [
            round(doc["latency_ms"], 1) for _, doc in replies
        ],
        "decode_step_ms_observed_p50": round(step.percentile(50), 2),
        "prefill_chunk_ms_observed_p50": round(chunk.percentile(50), 2),
        "first_token_logits_max_abs_err": err,
        "prefix_pages_hit": prefix["pages_hit"],
        "prefill_chunks": engine.stats()["prefill_chunks"],
        "paged_flash_dispatches": paged,
        "peak_bytes_in_use": _peak_bytes(),
    }
    say("serve: ok, 6 requests in %.2f s, decode step p50 %.2f ms, prefill "
        "chunk p50 %.2f ms (single observations)"
        % (serve_s, facts["decode_step_ms_observed_p50"],
           facts["prefill_chunk_ms_observed_p50"]))
    engine._state.clear()
    engine.scope.vars.clear()
    del engine, server
    gc.collect()
    return facts


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------


def _close(name, got, want, rtol, atol):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape and np.isfinite(got).all(),
          "kernels: %s gave non-finite or mis-shaped values" % name)
    check(np.allclose(got, want, rtol=rtol, atol=atol),
          "kernels: %s differs from its dense reference by up to %.3e "
          "(|ref| up to %.3e)" % (name, float(np.max(np.abs(got - want))),
                                  float(np.max(np.abs(want)))))


def _compiled(name, fn, *args):
    """Compile fn(*args) on the chip, insist its module holds a Mosaic
    kernel, run it once."""
    import jax

    exe = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in exe.as_text(),
          "kernels: %s compiled without a tpu_custom_call" % name)
    return jax.block_until_ready(exe(*args))


def phase_kernels():
    """The Pallas families neither train nor serve reached, each compiled by
    Mosaic at a shape a cell would use, run once and compared with its dense
    reference. Returns {family: "compiled"}: no family is withdrawn today
    (a family Mosaic refuses fails the smoke until it is repaired or
    withdrawn from `auto`)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import flags
    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.ops import registry

    rng = np.random.RandomState(0)
    bf16, f32 = jnp.bfloat16, jnp.float32
    done = {}

    # streamed flash attention (the long-context tier) at t 16384
    t, d = 16384, 128
    q, k, v, do = (
        jnp.asarray(rng.randn(1, 1, t, d) * 0.5, bf16) for _ in range(4)
    )
    for causal in (False, True):
        def loss(q, k, v, causal=causal):
            return (pk.flash_attention(q, k, v, causal).astype(f32) * do).sum()

        def ref_loss(q, k, v, causal=causal):
            out = pk._attention_reference(q, k, v, causal, d ** -0.5)
            return (out.astype(f32) * do).sum()

        out = _compiled("flash_streamed", lambda q, k, v, c=causal:
                        pk.flash_attention(q, k, v, c), q, k, v)
        ref = jax.jit(lambda q, k, v, c=causal:
                      pk._attention_reference(q, k, v, c, d ** -0.5))(q, k, v)
        _close("flash_streamed forward causal=%s" % causal, out, ref, 2e-2, 2e-2)
        grads = _compiled("flash_streamed_grad",
                          jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
        refs = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(q, k, v)
        for g, r, n in zip(grads, refs, "qkv"):
            _close("flash_streamed d%s causal=%s" % (n, causal), g, r, 5e-2, 5e-2)
        del out, ref, grads, refs
    done["flash_streamed"] = done["flash_streamed_grad"] = "compiled"
    del q, k, v, do

    # GEMM epilogue, both forms, the activations train did not use
    m, kk, n = 8192, 2048, 2048
    x = jnp.asarray(rng.randn(m, kk) * 0.05, bf16)
    w = jnp.asarray(rng.randn(kk, n) * 0.05, bf16)
    b = jnp.asarray(rng.randn(1, n), bf16)
    z32 = jnp.dot(x, w, preferred_element_type=f32) + b.astype(f32)
    prev = flags.get_flags(["gemm_double_buffer", "quantized_gemm", "paged_flash"])
    try:
        for mode, fam in (("on", "gemm_dbuf"), ("off", "gemm_epilogue")):
            flags.set_flags({"gemm_double_buffer": mode})
            for act, ref_fn in (("tanh", jnp.tanh), ("sigmoid", jax.nn.sigmoid)):
                z, y = _compiled(
                    "%s %s" % (fam, act),
                    lambda x, w, b, a=act: pk.gemm_bias_act(x, w, b, a), x, w, b,
                )
                _close("%s %s z" % (fam, act), z, z32, 2e-2, 2e-2)
                _close("%s %s y" % (fam, act), y, ref_fn(z32), 2e-2, 2e-2)
            done["%s tanh,sigmoid" % fam] = "compiled"
        del x, w, b, z32

        # int8 and fp8 GEMM tiles at a decode-sized and a prefill-sized m
        kk, n = 3072, 768
        bias = jnp.asarray(rng.randn(1, n), f32)
        for dt, fam in ((jnp.int8, "gemm_int8"), (jnp.float8_e4m3fn, "gemm_fp8")):
            for m in (64, 1024):
                if dt == jnp.int8:
                    x = jnp.asarray(rng.randint(-127, 128, (m, kk)), dt)
                    w = jnp.asarray(rng.randint(-127, 128, (kk, n)), dt)
                    scale = 1e-4
                else:
                    x = jnp.asarray(rng.randn(m, kk), dt)
                    w = jnp.asarray(rng.randn(kk, n), dt)
                    scale = 0.05
                flags.set_flags({"quantized_gemm": "on"})
                z, y = _compiled(
                    "%s m=%d" % (fam, m),
                    lambda x, w, b: pk.quant_gemm_bias_act(x, w, scale, b, "relu"),
                    x, w, bias,
                )
                flags.set_flags({"quantized_gemm": "off"})  # the dense form
                zr, yr = jax.jit(
                    lambda x, w, b: pk.quant_gemm_bias_act(x, w, scale, b, "relu")
                )(x, w, bias)
                _close("%s m=%d z" % (fam, m), z, zr, 1e-3, 1e-3)
                _close("%s m=%d y" % (fam, m), y, yr, 1e-3, 1e-3)
            done["%s m=64,1024" % fam] = "compiled"

        # the int8-pool paged kernels, against the op's dense lowering
        n_head, dh, ps, n_pages, slots = 12, 64, 8, 128, 8
        feat, pool_rows = n_head * dh, (slots * n_pages + 1) * ps
        paged = registry.get("paged_attention").lower
        pools = {
            "KPool": [jnp.asarray(rng.randint(-127, 128, (pool_rows, feat)), jnp.int8)],
            "VPool": [jnp.asarray(rng.randint(-127, 128, (pool_rows, feat)), jnp.int8)],
            "KScales": [jnp.asarray(rng.rand(pool_rows) * 0.02 + 1e-3, f32)],
            "VScales": [jnp.asarray(rng.rand(pool_rows) * 0.02 + 1e-3, f32)],
        }
        attrs = {"n_head": n_head, "page_size": ps}
        table = rng.permutation(np.arange(1, slots * n_pages + 1)).astype("int32")
        cases = {
            "paged_flash_int8 decode": (
                slots, table.reshape(slots, n_pages),
                np.array([0, 7, 8, 100, 511, 777, 1023, -1], "int32")),
            "paged_flash_int8 shared": (
                32, table[:n_pages], (600 + np.arange(32)).astype("int32")),
        }
        for name, (rows, bt, pos) in cases.items():
            ins = dict(
                pools, Q=[jnp.asarray(rng.randn(rows, feat), f32)],
                BlockTable=[jnp.asarray(bt)], Pos=[jnp.asarray(pos)],
            )
            flags.set_flags({"paged_flash": "on"})
            out = _compiled(name, lambda ins: paged(None, ins, attrs)["Out"][0], ins)
            flags.set_flags({"paged_flash": "off"})
            ref = jax.jit(lambda ins: paged(None, ins, attrs)["Out"][0])(ins)
            _close(name, out, ref, MXU_RTOL, MXU_ATOL)
            done[name] = "compiled"

        # the latent paged walk (one pool read once as key and value) at
        # xing4_docs_chat's widths, against the op's dense lowering
        n_head, width, dv, ps, n_pages, slots = 32, 640, 512, 64, 32, 8
        latent = registry.get("mla_attention").lower
        pool = jnp.asarray(
            rng.randn((slots * n_pages + 1) * ps, width) * 0.5, bf16
        ).at[:, 576:].set(0)
        attrs = {"page_size": ps, "d_value": dv, "sm_scale": 0.13}
        table = rng.permutation(np.arange(1, slots * n_pages + 1)).astype("int32")
        cases = {
            "paged_latent decode": (
                slots, table.reshape(slots, n_pages),
                np.array([0, 63, 64, 511, 512, 1500, 2047, -1], "int32")),
            "paged_latent shared": (
                64, table[:n_pages], (1300 + np.arange(64)).astype("int32")),
        }
        for name, (rows, bt, pos) in cases.items():
            ins = {
                "Q": [jnp.asarray(rng.randn(rows, n_head * width) * 0.5, bf16)],
                "Pool": [pool], "BlockTable": [jnp.asarray(bt)],
                "Pos": [jnp.asarray(pos)],
            }
            flags.set_flags({"paged_flash": "on"})
            out = _compiled(name, lambda ins: latent(None, ins, attrs)["Out"][0], ins)
            flags.set_flags({"paged_flash": "off"})
            ref = jax.jit(lambda ins: latent(None, ins, attrs)["Out"][0])(ins)
            _close(name, out, ref, MXU_RTOL, MXU_ATOL)
            done[name] = "compiled"
    finally:
        flags.set_flags(prev)
    _kernel_ssm_state_step(done)
    _kernel_dsa(done)

    say("kernels: ok, %s" % ", ".join(sorted(done)))
    return done


def _kernel_dsa(done):
    """The learned-sparse-attention kernels at glm52_longdocs' widths, each
    against its dense lowering (the paged_flash flag "off"): the index scan
    (32 heads of 128 over a bfloat16 index-key pool, pages of 64; decode
    rows, one idle, and a 256-row chunk), the exact top-2048 (tie-free
    scores, -inf past each row; positions equal, not close), and the sparse
    latent walk (64 heads over 640-wide latent rows, 2048 selected a row)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import flags
    from paddle_tpu.ops import registry

    rng = np.random.RandomState(0)
    bf16 = jnp.bfloat16
    prev = flags.get_flags("paged_flash")
    lower = lambda op, ins, attrs: registry.get(op).lower(None, ins, attrs)["Out"][0]

    def both(name, op, ins, attrs):
        """The op with paged_flash "on" (dsa_index: the Pallas scan; the
        other two are XLA's) and "off"."""
        flags.set_flags({"paged_flash": "on"})
        fn = lambda ins: lower(op, ins, attrs)
        out = (_compiled(name, fn, ins) if op == "dsa_index"
               else jax.block_until_ready(jax.jit(fn)(ins)))
        flags.set_flags({"paged_flash": "off"})
        return np.asarray(out), np.asarray(jax.jit(lambda ins: lower(op, ins, attrs))(ins))

    try:
        ps, n_pages, slots, heads, width = 64, 64, 8, 32, 128
        table = rng.permutation(np.arange(1, slots * n_pages + 1)).astype("int32")
        pool = jnp.asarray(rng.randn((slots * n_pages + 1) * ps, width), bf16)
        cases = {
            "dsa_index decode": (slots, table.reshape(slots, n_pages), np.array(
                [0, 63, 64, 511, 2047, 3000, n_pages * ps - 1, -1], "int32")),
            "dsa_index shared": (256, table[:n_pages],
                                 (3000 + np.arange(256)).astype("int32")),
        }
        for name, (rows, bt, pos) in cases.items():
            got, want = both(name, "dsa_index", {
                "Q": [jnp.asarray(rng.randn(rows, heads * width) * 0.3, bf16)],
                "W": [jnp.asarray(rng.randn(rows, heads), jnp.float32)],
                "Pool": [pool], "BlockTable": [jnp.asarray(bt)],
                "Pos": [jnp.asarray(pos)]}, {"page_size": ps})
            check(np.array_equal(np.isinf(got), np.isinf(want)),
                  "kernels: %s masks other positions than its dense lowering" % name)
            live = np.isfinite(want)
            _close(name, got[live], want[live], MXU_RTOL, MXU_ATOL)
            done[name] = "compiled"

        n, k = 65664, 2048
        scores = rng.randn(32, n).astype("float32")
        scores[np.arange(n)[None, :] > rng.randint(100, n, 32)[:, None]] = -np.inf
        scores[0, 1000:] = -np.inf  # fewer than k positions
        got, want = both("dsa_topk", "dsa_topk", {"X": [jnp.asarray(scores)]}, {"k": k})
        check(np.array_equal(got, want),
              "kernels: dsa_topk's selection differs from jax.lax.top_k's")
        done["dsa_topk"] = "compiled"

        heads, width, dv = 64, 640, 512
        latent = jnp.asarray(
            rng.randn((slots * n_pages + 1) * ps, width) * 0.5, bf16).at[:, 576:].set(0)
        pos = np.array([5, 2047, 2048, 3000, 4000, 4095, 100, 0], "int32")
        sel = np.full((slots, k), -1, "int32")
        for r, p in enumerate(pos):
            pick = np.sort(rng.permutation(p + 1)[:k])
            sel[r, :pick.size] = pick
        got, want = both("mla_sparse_attention", "mla_sparse_attention", {
            "Q": [jnp.asarray(rng.randn(slots, heads * width) * 0.5, bf16)],
            "Pool": [latent], "BlockTable": [jnp.asarray(table.reshape(slots, n_pages))],
            "Selected": [jnp.asarray(sel)]},
            {"page_size": ps, "d_value": dv, "sm_scale": 256 ** -0.5})
        _close("mla_sparse_attention", got.astype("float32"),
               want.astype("float32"), MXU_RTOL, MXU_ATOL)
        done["mla_sparse_attention"] = "compiled"
    finally:
        flags.set_flags(prev)


def _kernel_ssm_state_step(done):
    """The state-space decode step's in-place kernel at granite4h_burst_chat's
    widths (48 slots, a state row [128, 4096] float32, the pool donated)
    against XLA's gather, update and scatter of the same step: the live
    slots' rows updated, every other row of the pool bit for bit as it was,
    each live slot's y in its own row, over patterns of live slots with idle
    ones before, among and behind them."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_kernels as pk

    slots, hp, n = 48, 4096, 128
    rng = np.random.default_rng(0)
    mk = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    check(pk.ssm_step_path_taken(hp, n, 1),
          "kernels: ssm_scan's step declines the kernel at the cell's widths")

    def xla_step(pool, rows, xdt, dec, b, c):
        prev = jnp.take(pool, rows, axis=0)
        new = prev * dec[:, None, :] + b[:, :, None] * xdt[:, None, :]
        keep = (rows != 0)[:, None, None]
        return (jnp.where(keep[:, 0], jnp.sum(new * c[:, :, None], axis=1), 0.0),
                pool.at[rows].set(jnp.where(keep, new, prev)))

    kernel = jax.jit(pk.ssm_state_step, donate_argnums=(0,))
    exe = kernel.lower(mk(slots + 1, n, hp), jnp.zeros(slots, jnp.int32),
                       mk(slots, hp), mk(slots, hp), mk(slots, n),
                       mk(slots, n)).compile()
    check("tpu_custom_call" in exe.as_text(),
          "kernels: ssm_state_step compiled without a tpu_custom_call")
    state_rows = np.arange(1, slots + 1)
    every = np.arange(slots)
    patterns = {
        "none": np.zeros(slots, bool), "all": np.ones(slots, bool),
        "first": every == 0, "last": every == slots - 1,
        "every other": every % 2 == 0,
        "thirty, rows permuted": rng.permutation(slots) < 30,
    }
    for name, live in patterns.items():
        rows = np.where(live, state_rows, 0).astype(np.int32)
        if "permuted" in name:  # a slot's state row is not its own number
            rows = np.where(live, rng.permutation(state_rows), 0).astype(np.int32)
        pool = mk(slots + 1, n, hp)
        args = (jnp.asarray(rows), mk(slots, hp),
                jnp.asarray(rng.uniform(0.5, 1, (slots, hp)), jnp.float32),
                mk(slots, n), mk(slots, n))
        want_y, want_pool = jax.jit(xla_step)(pool, *args)
        y, new = exe(pool, *args)  # the pool is donated: `pool` is gone
        new, want_pool = np.asarray(new), np.asarray(want_pool)
        idle = np.setdiff1d(state_rows, rows)
        check(np.array_equal(new[idle], want_pool[idle]),
              "kernels: ssm_state_step (%s): a row no live slot holds changed" % name)
        if live.any():
            _close("ssm_state_step live rows (%s)" % name, new[rows[live]],
                   want_pool[rows[live]], 1e-6, 1e-6)
        check(bool(np.isfinite(new[0]).all()),
              "kernels: ssm_state_step (%s): the scratch row is not finite" % name)
        check(bool((np.asarray(y)[~live] == 0).all()),
              "kernels: ssm_state_step (%s): an idle slot's y is not 0" % name)
        _close("ssm_state_step y (%s)" % name, y, want_y, 1e-5, 1e-4)
    done["ssm_state_step %d patterns" % len(patterns)] = "compiled"


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------


def transformer_sharding_rules(n_layer):
    """SpecLayout roles for the parameters of build_transformer, whose
    fc layers are named by position: six to an encoder layer (q, k, v, out,
    ffn up, ffn down), ten to a decoder layer (self and cross attention, then
    the ffn), and the vocabulary projection last."""
    from paddle_tpu.parallel import SpecLayout

    column, row = [], []
    for layer in range(n_layer):
        base = 6 * layer
        column += [base, base + 1, base + 2, base + 4]
        row += [base + 3, base + 5]
    for layer in range(n_layer):
        base = 6 * n_layer + 10 * layer
        column += [base, base + 1, base + 2, base + 4, base + 5, base + 6, base + 8]
        row += [base + 3, base + 7, base + 9]
    column.append(16 * n_layer)
    names = lambda ids: r"^fc_(%s)\.w_0$" % "|".join(str(i) for i in ids)
    return SpecLayout().transformer_rules(
        column=[names(column)],
        row=[names(row)],
        vector=[r"^fc_\d+\.b_0$", r"^layer_norm_\d+\.[wb]_0$"],
        embedding=[r"^(src|trg)_word_emb$"],
    )


def _chips4_run(cfg, mesh_axes, with_rules):
    """One ParallelExecutor step of the train model on a mesh of all four
    chips. Startup runs through the single-device Executor, so every
    parameter and moment is born on chip 0 and resharded by the first step."""
    import jax

    import paddle_tpu.fluid as fluid
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.parallel import MeshConfig
    from paddle_tpu.parallel_executor import BuildStrategy
    from paddle_tpu.transpiler.bf16_transpiler import Bf16Transpiler

    main, startup, feed, loss = build_transformer(
        moment_dtype="bfloat16", **cfg
    )
    strat = BuildStrategy()
    if with_rules:
        strat.sharding_rules = transformer_sharding_rules(cfg["n_layer"])
    scope = Scope(seed=0)
    devices = jax.devices()
    out = {}
    try:
        with scope_guard(scope):
            fluid.Executor(fluid.TPUPlace() if devices[0].platform == "tpu"
                           else fluid.CPUPlace()).run(startup)
            Bf16Transpiler().transpile(main)
            pe = fluid.ParallelExecutor(
                loss_name=loss.name, main_program=main, build_strategy=strat,
                scope=scope, mesh_config=MeshConfig(**mesh_axes),
                devices=devices[:4],
            )
            t0 = time.perf_counter()
            (l,) = pe.run(fetch_list=[loss.name], feed=feed, return_numpy=False)
            jax.block_until_ready(l)
            out["first_step_s"] = time.perf_counter() - t0
            out["loss"] = float(np.asarray(l).reshape(-1)[0])
            state = {
                n: scope.vars[n] for n in pe._last_run[0].mut_names
                if isinstance(scope.vars.get(n), jax.Array)
                and scope.vars[n].ndim >= 1
            }
            out["narrow"] = sorted(
                n for n, a in state.items()
                if len({s.device for s in a.addressable_shards}) != 4
            )
            out["n_state"] = len(state)
            del state
            out["bytes_in_use"] = [
                (d.memory_stats() or {}).get("bytes_in_use") for d in devices[:4]
            ]
            hlo = pe.compiled_hlo()
            out["collectives"] = {
                c: hlo.count(" %s(" % c) + hlo.count(" %s-start(" % c)
                for c in ("all-reduce", "all-gather", "reduce-scatter",
                          "all-to-all", "collective-permute")
            }
            out["custom_calls"] = custom_call_families(hlo)
    finally:
        scope.vars.clear()
        del feed, scope
        gc.collect()
    return out


def phase_chips4(cfg=TRAIN):
    """The train phase's model through fluid.ParallelExecutor on four chips,
    as MeshConfig(dp=4) and as MeshConfig(fsdp=2, tp=2) under
    transformer_rules, against the one-chip first-step loss."""
    import jax

    on_tpu = jax.devices()[0].platform == "tpu"
    ref = _train_run(cfg, "", 1, 0)["losses"][0]
    say("chips4: one-chip first-step loss %.4f" % ref)
    facts = {"loss_one_chip": ref}
    layouts = (
        ("dp4", dict(dp=4), False, "all-reduce"),
        ("fsdp2_tp2", dict(dp=1, fsdp=2, tp=2), True, "all-gather"),
    )
    for name, axes, with_rules, collective in layouts:
        run = _chips4_run(cfg, axes, with_rules)
        say("chips4 %s: first step %.1f s (compile included), loss %.4f, "
            "bytes_in_use per device %s, collectives %s"
            % (name, run["first_step_s"], run["loss"], run["bytes_in_use"],
               run["collectives"]))
        rel = abs(run["loss"] - ref) / abs(ref)
        check(np.isfinite(run["loss"]) and rel <= 1e-2,
              "chips4 %s: first-step loss %.6f is %.2e relative from the "
              "one-chip %.6f" % (name, run["loss"], rel, ref))
        check(run["n_state"] > 0 and not run["narrow"],
              "chips4 %s: parameters or moments not on four devices: %s"
              % (name, run["narrow"][:5]))
        check(run["collectives"][collective] > 0,
              "chips4 %s: no %s in the step's HLO: %s"
              % (name, collective, run["collectives"]))
        if on_tpu:
            used = run["bytes_in_use"]
            check(used[0] <= 1.5 * (sum(used) / len(used)),
                  "chips4 %s: chip 0 holds %d bytes, over 1.5x the mean of %s"
                  % (name, used[0], used))
            for fam in ("flash_attention", "flash_attention_grad"):
                check(run["custom_calls"].get(fam, 0) > 0,
                      "chips4 %s: no tpu_custom_call for %s: %s"
                      % (name, fam, run["custom_calls"]))
        facts[name] = {
            "first_step_s": round(run["first_step_s"], 1),
            "loss": run["loss"],
            "bytes_in_use": run["bytes_in_use"],
            "collectives": run["collectives"],
            "state_arrays_on_four_devices": run["n_state"],
        }
    say("chips4: ok")
    return facts


# --------------------------------------------------------------------------
# main
# --------------------------------------------------------------------------


def result_line(ok, devices):
    """The last line of standard output: these two keys and no other, the
    device as JAX reports it. The driver reads it."""
    dev = devices[0]
    return json.dumps({"ok": bool(ok), "device": {
        "platform": str(dev.platform), "kind": str(dev.device_kind),
        "count": len(devices),
    }})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print("chip_smoke: needs a TPU and JAX found none: its first device "
              "is %s (%s)" % (dev.platform, dev.device_kind), file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print("chip_smoke: --chips %d, but JAX sees %d TPU device(s)"
              % (args.chips, len(devices)), file=sys.stderr)
        return 2

    from importlib import metadata

    import jaxlib
    import paddle_tpu  # noqa: F401 — places the compile cache

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": libtpu, "python": sys.version.split()[0]}
    say("device %s x%d (%s), versions %s" % (
        dev.device_kind, len(devices), dev.platform, json.dumps(versions)))

    cache_dir, before = _cache_entries()
    say("compile cache %s (%s), %d entries before"
        % (cache_dir,
           "JAX_COMPILATION_CACHE_DIR" if os.environ.get("JAX_COMPILATION_CACHE_DIR")
           else "default <checkout>/.jax_cache", before))

    phases = {}
    if args.chips == 4:
        phases["chips4"] = phase_chips4()
    else:
        phases["train"] = phase_train()
        phases["serve"] = phase_serve()
        phases["kernels"] = phase_kernels()

    _, after = _cache_entries()
    say("compile cache: %d entries before, %d after" % (before, after))
    say("facts " + json.dumps({
        "versions": versions,
        "seconds": round(time.perf_counter() - t_start, 1),
        "compile_cache": {"dir": cache_dir, "entries_before": before,
                          "entries_after": after},
        "phases": phases,
    }))
    print(result_line(True, devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
