"""The serve runner of a block-specified decoder (paddle_tpu
models/block_decoder.py; today the lfm2_moe architecture): the model in a
GenerationEngine behind a ModelServer, answering POST :generate requests
that an open-loop generator sends on a schedule, exactly as runners/serve.py
drives GPTDecoder. What differs is how the model is built from the
configuration's file (its keys are the source's own), and the comparison
that decides `correct`: against chipbench/reference_lfm2_moe.py, with the
routers' discontinuity handled in the open (below). The load generator, the
pool sampler, the spans of a traced run and the variants' texts are
runners/serve.py's own."""

import os
import time

import numpy as np

from .. import data, reference_lfm2_moe as reference, trace as trace_mod
from . import serve

# the probe: a cold prompt, a second that shares its first SHARED tokens (it
# finds pages there and no state, so it cuts its prefill at the boundary and
# leaves a snapshot), and a third that is admitted through that boundary's
# prefix hit, prefilled in two more chunks and decoded PROBE_STEPS steps
SHARED, TAILS, PROBE_STEPS = 64, (150, 100, 170), 8

# ---- the limits of `correct`. Two readings each (my chip runs, PR 27;
# PERF.md §4 has the table): what this change read over fourteen seeds, and
# what the reference computed one precision lower (`precision="bfloat16"`:
# its norms, router, softmax and conv sums in bfloat16 like the rest) reads
# when it is put in the system's place and held to the float32 reference.
# The engine rounds activations, pages and conv state to bfloat16 between
# its float32 computations, and at these widths that alone is most of the
# distance to float32: the lower precision adds only a third to it. So the
# lower precision fails by one limit, the share of rows, and the other two
# are set for the change's own seeds (a false `correct: false` refuses a PR).
#
# The share of (layer, position) rows where the engine picked another set of
# experts than the reference's own scores would. This change 160-200 of 2904
# (5.5-6.9 %); lower precision 241 and 249 (8.3, 8.6 %): not correct.
PICK_ROWS_SHARE = 0.08
# Logits, max abs difference on logits of up to ~4.7 (std 0.9), following
# the engine's picks. This change 0.105-0.142 (28 readings, mean 0.122);
# lower precision 0.133-0.173: the limit does not part the two.
LOGIT_ATOL = 0.2
# Every differing pick has to be a near tie: its selection score at most
# PICK_EPS under the reference's 4th largest. This change 1.1e-2 - 3.4e-2
# (the largest of ~180 rows: a long tail; float32 scores of bfloat16
# activations ~1 % off the reference's); lower precision 2.0e-2 - 2.7e-2. A
# router that picks by something else differs in most rows, not by a margin.
PICK_EPS = 8e-2


def model_of(config):
    from paddle_tpu.models.block_decoder import lfm2_moe

    if config["model_type"] != "lfm2_moe":
        raise ValueError("no block decoder for model_type %r" % config["model_type"])
    return lfm2_moe(
        config, max_context=config["engine"]["max_context"],
        dtype="bfloat16", eos_id=-1)


def build(cell, seed):
    """(server, engine, model, name): the configuration as it is served."""
    from paddle_tpu.executor import Scope
    from paddle_tpu.serving import ModelServer

    config = cell["config"]
    model = model_of(config)
    name = config["served_name"]
    server = ModelServer(**config["server"])
    engine = server.add_generation_model(
        name, model=model, scope=Scope(seed=seed % (2 ** 31 - 1)),
        cache_dir=os.path.join(data.SCRATCH, "serving_export"),
        **config["engine"]
    )  # warmup=True: the decode step and every prefill bucket compile here
    return server, engine, model, name


def _picks_by_position(run):
    parts = sorted(run.picks, key=lambda sp: sp[0])
    return parts[0][0], np.concatenate([p for _, p in parts], axis=1)


def _probe(engine, model, seed, problems, precision="float32"):
    """First-token logits after a chunked prefill that begins at a prefix
    hit (pages shared, conv state restored), and the logits of the
    PROBE_STEPS-th decode step, through the engine's own entry points,
    against the plain reference's one pass over the same tokens with the
    engine's own weights, following the engine's picks."""
    from paddle_tpu.serving.generation import GenRequest

    rng = np.random.default_rng(seed)
    draw = lambda n: [int(v) for v in rng.integers(2, model.vocab_size, n)]
    shared = draw(SHARED)
    prompts = [shared + draw(n) for n in TAILS]
    engine.record_picks = True
    try:
        runs = []
        for prompt in prompts[:2]:
            runs.append(engine.start(GenRequest(prompt, max_new_tokens=1)))
            engine.finish(runs[-1])
        hits0 = engine.prefix_cache.stats()["states_hit"]
        run = engine.start(GenRequest(prompts[2], max_new_tokens=PROBE_STEPS + 2))
        try:
            got_first = np.array(engine.last_prefill_logits, np.float32)
            for _ in range(PROBE_STEPS):
                engine.decode_step([run])
            got_last = np.array(engine.last_logits[run.slot], np.float32)
            chosen = list(run.tokens[:PROBE_STEPS])
        finally:
            engine.finish(run)
    finally:
        engine.record_picks = False
    hit_at, own = _picks_by_position(run)
    notes = {"prefix_hit_at": hit_at,
             "states_hit": engine.prefix_cache.stats()["states_hit"] - hits0}
    if hit_at != SHARED or notes["states_hit"] != 1:
        problems.append(
            "the probe's third prompt was not admitted through a prefix hit "
            "at %d tokens with its state restored: %s" % (SHARED, notes))
        return notes
    # the shared positions' picks are the first prompt's, which no cache
    # could have helped: the same tokens at the same positions
    _, before = _picks_by_position(runs[0])
    picks = np.concatenate([before[:, :SHARED], own], axis=1)
    tokens = prompts[2] + chosen
    picks = picks[:, :len(tokens)]
    rows = [len(prompts[2]) - 1, len(tokens) - 1]
    weights = {n: engine.scope.vars[n] for n in model.param_names()}
    want, ref_picks, gaps = reference.forward(
        weights, tokens, reference.spec_of(model), picks=picks, rows=rows,
        precision=precision)
    differ = (np.sort(np.asarray(ref_picks), -1) != np.sort(picks, -1)).any(-1)
    notes.update(
        pick_rows=int(differ.size), pick_rows_differing=int(differ.sum()),
        pick_gap_max=float(np.max(gaps)), logit_abs_max=float(
            np.max(np.abs(np.asarray(want)))))
    if notes["pick_gap_max"] > PICK_EPS:
        problems.append(
            "a router pick lies %.3e under the reference's 4th largest score "
            "(most allowed %.1e): not a tie" % (notes["pick_gap_max"], PICK_EPS))
    if differ.mean() > PICK_ROWS_SHARE:
        problems.append(
            "%d of %d (layer, position) rows pick other experts than the "
            "reference" % (differ.sum(), differ.size))
    for what, got, ref_row in (
        ("first-token", got_first, want[0]),
        ("decode-step-%d" % PROBE_STEPS, got_last, want[1]),
    ):
        ref_row = np.asarray(ref_row)
        notes[what] = float(np.max(np.abs(got - ref_row)))
        if not (np.isfinite(got).all() and notes[what] <= LOGIT_ATOL):
            problems.append(
                "%s logits differ from the reference by up to %.3e (most "
                "allowed %.1e; |ref| up to %.3e)" % (
                    what, notes[what], LOGIT_ATOL, notes["logit_abs_max"]))
    return notes


def snapshot(engine):
    """serve.snapshot's, and the prefix cache's state counters."""
    hist, counters = serve.snapshot(engine)
    st = engine.prefix_cache.stats()
    for key in ("states_taken", "states_hit", "states_evicted"):
        counters[key] = st.get(key, 0)
    return hist, counters


def _moe_work(config, model, hist0, hist1, name):
    """What the routing of the steps and chunks between two snapshots asked
    of the expert layers: experts touched (summed over layers and calls) and
    the expert-layer calls made."""
    touched = calls = 0
    for h in ("gen_experts_touched", "gen_chunk_experts_touched"):
        key = "serving/%s/%s" % (name, h)
        (s0, c0), (s1, c1) = hist0.get(key, (0.0, 0)), hist1.get(key, (0.0, 0))
        touched += s1 - s0
        calls += (c1 - c0) * len(model.moe_layers())
    return {
        "experts_touched": touched, "calls": calls,
        "hidden_size": config["hidden_size"],
        "moe_intermediate_size": config["moe_intermediate_size"],
        "num_experts": config["num_experts"], "weight_bytes": 2,
    }


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
    out["notes"]["kv"] = engine.stats()["kv"]
    out["notes"]["probe"] = _probe(engine, model, seed, problems)
    marks["probe_checked"] = clock()
    if trace:
        out["notes"]["spans_around"] = serve._annotate(engine)

    phases = [("ramp", tr["ramp_seconds"]), ("window", seconds)]
    if trace:  # traffic goes on under the profiler, after the window
        phases.append(("traced", serve.TRACED_TRAFFIC_SECONDS))
    timeout_s = config["server"]["request_timeout_ms"] / 1e3
    gen = serve.generator(server.start(), name, cell, seed, phases, timeout_s + 5.0)
    sampler = serve.PoolSampler(engine.pool)
    try:
        compiles.start()
        out["setup_s"] = clock()
        t0 = gen.start()
        w0 = t0 + tr["ramp_seconds"]
        w1 = w0 + seconds
        serve._sleep_until(w0)
        hist0, count0 = snapshot(engine)
        sampler.start()
        serve._sleep_until(w1)
        sampler.stop()
        hist1, count1 = snapshot(engine)
        in_window = compiles.count
        if trace:
            log_dir = trace_mod.start(cell["workload"]["name"])
            try:
                # inside the profile on both sides: the steps these two
                # readings count are all in the trace
                traced0, _ = snapshot(engine)
                time.sleep(serve.TRACED_SECONDS)
                traced1, _ = snapshot(engine)
            finally:
                jax.profiler.stop_trace()
            out["moe_traced"] = _moe_work(config, model, traced0, traced1, name)
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
            log_dir, serve._variant_hlo(engine, out["notes"]),
            cell["workload"]["chips"])

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
    out["notes"]["moe_window"] = _moe_work(config, model, hist0, hist1, name)
    out["notes"]["state_counters"] = {
        k: counters[k] for k in counters if k.startswith("states_")}
    if lat:
        out["notes"]["ms_per_token"] = {
            "mean": float(np.mean(lat)),
            **{"p%d" % q: float(np.percentile(lat, q)) for q in (50, 75, 90, 99)}}
    if sampler.slots:
        out["notes"]["pool"] = {
            "live_slots_mean": float(np.mean(sampler.slots)),
            "live_slots_max": int(max(sampler.slots)),
            "fill_mean": float(np.mean(sampler.fill)),
            "live_mean": float(np.mean(sampler.live)),
            "reserved_bytes": sampler.resident_bytes}
    out["correct"] = not problems
    return out
