"""The serve runner: a decoder in a GenerationEngine behind a ModelServer,
answering POST :generate requests that an open-loop generator sends on a
schedule made from the seed."""

import os
import threading
import time

import numpy as np

from .. import data, loadgen, reference, trace as trace_mod

PROBE_TOKENS, PROBE_STEPS = 40, 8
# a traced run: seconds under the profiler after the window, and how long
# the offered traffic goes on past the window so that it covers them (the
# profiler takes a few seconds to start)
TRACED_SECONDS, TRACED_TRAFFIC_SECONDS = 3.0, 9.0
# the bar the repository's on-chip checks use for f32 values that crossed the
# MXU at default precision (chip_smoke.py, tests/test_pallas_kernels.py)
RTOL = ATOL = 2e-2


def _probe(engine, model, seed, problems):
    """First-token logits and the logits of the eighth decode step, through
    the engine's own entry points, against the plain reference run on the
    same tokens with the engine's own weights."""
    from paddle_tpu.serving.generation import GenRequest

    rng = np.random.default_rng(seed)
    probe = [int(v) for v in rng.integers(2, model.vocab_size, PROBE_TOKENS)]
    run = engine.start(GenRequest(probe, max_new_tokens=PROBE_STEPS + 2))
    try:
        got_first = np.array(engine.last_prefill_logits, np.float32)
        for _ in range(PROBE_STEPS):
            engine.decode_step([run])
        got_last = np.array(engine.last_logits[run.slot], np.float32)
        chosen = list(run.tokens[:PROBE_STEPS])
    finally:
        engine.finish(run)
    weights = {n: engine.scope.vars[n] for n in model.param_names()}
    wants = reference.logits_after(
        weights, model.prefix, model.n_layer, model.n_head, probe + chosen,
        [len(probe), len(probe) + len(chosen)])
    errs = {}
    for what, got, want in (
        ("first-token", got_first, wants[0]),
        ("decode-step-%d" % PROBE_STEPS, got_last, wants[1]),
    ):
        want = np.asarray(want)
        errs[what] = float(np.max(np.abs(got - want)))
        if not (np.isfinite(got).all()
                and np.allclose(got, want, rtol=RTOL, atol=ATOL)):
            problems.append(
                "%s logits differ from the reference by up to %.3e (|ref| up "
                "to %.3e)" % (what, errs[what], float(np.max(np.abs(want)))))
    return errs


# the engine's public entry points that a traced run puts a span around
SPANS = {"decode_step": "engine_decode_step", "prefill_step": "engine_prefill_step"}


def _annotate(engine):
    """Trace runs only: the benchmark's own spans around the engine's public
    entry points, so that an idle gap on the device can be labelled by where
    the scheduler thread was: inside a decode step (waiting for the logits,
    then sampling on the host), inside a prefill chunk, or in neither (the
    scheduler's own code between the engine's calls, or waiting for a
    request). Instance attributes; the program is not edited, no private
    name is touched, and an entry point a later version lacks is skipped.
    Returns the names wrapped."""
    from jax.profiler import TraceAnnotation

    def wrap(name, label):
        fn = getattr(engine, name)

        def wrapped(*a, **k):
            with TraceAnnotation(trace_mod.SPAN_PREFIX + label):
                return fn(*a, **k)
        setattr(engine, name, wrapped)

    found = [n for n in SPANS if callable(getattr(engine, n, None))]
    for n in found:
        wrap(n, SPANS[n])
    return found


def _variant_hlo(engine, notes):
    """The compiled variants' texts, read only for the op_name of each
    instruction (which framework op emitted a fusion). The engine has no
    public way to them (PERF.md, Open questions); without them a traced
    operation keeps its own name, and the run says so."""
    try:
        return [v.fn.as_text() for _, v in sorted(engine._variants.items())]
    except (AttributeError, TypeError) as e:
        notes["trace_names"] = "instruction names only: %r" % (e,)
        return []


class PoolSampler(threading.Thread):
    """Reads the engine's pool a few times a second: the slots held, the
    share of the pool's pages in use (live requests' and the prefix cache's
    hold on finished prompts) and the share that live requests hold (the
    pages in the held slots' block tables, a shared page counted once)."""

    def __init__(self, pool, period_s=0.25):
        super().__init__(name="chipbench-pool-sampler", daemon=True)
        self.pool, self.period_s = pool, period_s
        self.slots, self.fill, self.live, self.resident_bytes = [], [], [], 0
        self._halt = threading.Event()

    def run(self):
        from paddle_tpu.serving.kv_cache import SCRATCH_PAGE

        while not self._halt.wait(self.period_s):
            st = self.pool.stats()
            tables = [self.pool.block_table(i) for i in range(st["slots_total"])]
            held = {int(p) for t in tables if t is not None
                    for p in t if p != SCRATCH_PAGE}
            self.slots.append(st["slots_in_use"])
            self.fill.append(st["pages_in_use"] / float(st["pages_total"]))
            self.live.append(len(held) / float(st["pages_total"]))
            self.resident_bytes = st["resident_bytes"]

    def stop(self):
        self._halt.set()
        self.join()


def snapshot(engine):
    from paddle_tpu.observability import registry

    hist = {
        n: (m["sum"], m["count"])
        for n, m in registry.default_registry().snapshot().items()
        if m.get("kind") == "histogram"
    }
    counters = {"traces": engine.traces}
    if engine.prefix_cache is not None:
        st = engine.prefix_cache.stats()
        counters["prefix_pages_hit"] = st["pages_hit"]
        counters["prefix_pages_eligible"] = st["pages_eligible"]
    return hist, counters


def _sleep_until(t):
    d = t - time.perf_counter()
    if d > 0:
        time.sleep(d)


def build(cell, seed):
    """(server, engine, model, name): the configuration as it is served."""
    from paddle_tpu.executor import Scope
    from paddle_tpu.models.gpt_decoder import GPTDecoder
    from paddle_tpu.serving import ModelServer

    config = cell["config"]
    s = config["sizes"]
    model = GPTDecoder(
        vocab_size=s["vocab_size"], n_layer=s["n_layer"], n_head=s["n_head"],
        d_model=s["n_embd"], d_inner=s["n_inner"],
        max_context=s["n_positions"], eos_id=-1,
    )
    name = config["served_name"]
    server = ModelServer(**config["server"])
    engine = server.add_generation_model(
        name, model=model, scope=Scope(seed=seed % (2 ** 31 - 1)),
        cache_dir=os.path.join(data.SCRATCH, "serving_export"),
        **config["engine"]
    )  # warmup=True: the decode step and every prefill bucket compile here
    return server, engine, model, name


def generator(port, name, cell, seed, phases, timeout_s):
    """The open-loop generator for the cell's traffic over `phases`, not yet
    started."""
    tr = cell["traffic"]
    gen = data.module("traffic", tr["generator"])
    sched = gen.schedule(tr, seed, phases, cell["config"]["sizes"]["vocab_size"])
    return loadgen.OpenLoop(
        "127.0.0.1", port, "/v1/models/%s:generate" % name, sched, timeout_s)


def run(cell, seed, seconds, trace, clock, devices):
    import jax

    config, tr = cell["config"], cell["traffic"]
    problems = []
    out = {"problems": problems, "notes": {}}
    compiles = trace_mod.CompileCounter()

    marks = out["notes"]["setup_marks_s"] = {"runner_start": clock()}
    server, engine, model, name = build(cell, seed)
    marks["engine_warm"] = clock()
    out["notes"]["variants"] = engine.stats()["variants"]
    out["notes"]["traces_in_setup"] = engine.traces
    out["notes"]["logit_errors"] = _probe(engine, model, seed, problems)
    marks["probe_checked"] = clock()
    if trace:
        out["notes"]["spans_around"] = _annotate(engine)

    phases = [("ramp", tr["ramp_seconds"]), ("window", seconds)]
    if trace:  # traffic goes on under the profiler, after the window
        phases.append(("traced", TRACED_TRAFFIC_SECONDS))
    timeout_s = config["server"]["request_timeout_ms"] / 1e3
    gen = generator(server.start(), name, cell, seed, phases, timeout_s + 5.0)
    sampler = PoolSampler(engine.pool)
    try:
        compiles.start()
        out["setup_s"] = clock()
        t0 = gen.start()
        w0 = t0 + tr["ramp_seconds"]
        w1 = w0 + seconds
        _sleep_until(w0)
        hist0, count0 = snapshot(engine)
        sampler.start()
        _sleep_until(w1)
        sampler.stop()
        hist1, count1 = snapshot(engine)
        in_window = compiles.count
        if trace:
            log_dir = trace_mod.start(cell["workload"]["name"])
            try:
                time.sleep(TRACED_SECONDS)
            finally:
                jax.profiler.stop_trace()
        measured = [i for i, r in enumerate(gen.schedule)
                    if r["phase"] == "window"]
        gen.join(measured, timeout_s + 10.0)
        gen.stop_dispatch()
    finally:
        compiles.stop()
        if sampler.is_alive():
            sampler.stop()
        server.stop(drain=False)
    gen.join(gen.started(), 15.0)

    if trace:
        out["trace"] = trace_mod.reduce_dir(
            log_dir, _variant_hlo(engine, out["notes"]), cell["workload"]["chips"])

    # ---- the window, from the client's side
    recs = gen.records
    lat, late, failed = [], [], 0
    for i in measured:
        r, s = recs[i], gen.schedule[i]
        ok = (r is not None and r["status"] == 200
              and r["tokens"] == s["max_new_tokens"]
              and r["done_s"] - s["due_s"] <= timeout_s)
        if not ok:
            failed += 1
            if len(problems) < 5:
                problems.append("request %d (due %.2f s): %s" % (i, s["due_s"], r))
            continue
        lat.append((r["done_s"] - s["due_s"]) * 1e3 / r["tokens"])
        late.append((r["sent_s"] - s["due_s"]) * 1e3)
    out["attempted"], out["failed"] = len(measured), failed
    counters = {k: count1[k] - count0[k] for k in count1}
    counters["compiles_in_window"] = in_window
    if counters["traces"]:
        problems.append("%d traces inside the window" % counters["traces"])
    if in_window:
        problems.append("%d compilations inside the window" % in_window)
    out["counters"] = counters
    out["histograms"] = {n: (hist0.get(n, (0.0, 0)), hist1[n]) for n in hist1}
    out["names"] = {"model": name}
    out["samples"] = {"loadgen_late_ms": late, "ms_per_token": lat,
                      "slots_in_use": sampler.slots, "pool_fill": sampler.fill,
                      "pool_live": sampler.live}
    out["window"] = {"seconds": seconds, "chips": cell["workload"]["chips"]}
    out["notes"]["requests_in_window"] = len(measured)
    if lat:
        out["notes"]["ms_per_token"] = {
            "mean": float(np.mean(lat)),
            **{"p%d" % q: float(np.percentile(lat, q)) for q in (50, 75, 90, 99)}}
    if sampler.slots:
        live = float(np.mean(sampler.live))
        out["notes"]["pool"] = {
            "live_slots_mean": float(np.mean(sampler.slots)),
            "live_slots_max": int(max(sampler.slots)),
            "fill_mean": float(np.mean(sampler.fill)),
            "fill_max": float(max(sampler.fill)),
            "live_mean": live, "live_max": float(max(sampler.live)),
            "reserved_bytes": sampler.resident_bytes,
            "live_bytes_mean": live * sampler.resident_bytes}
    out["correct"] = not problems
    return out
