"""The serve runner of the ``xing4_0`` architecture (paddle_tpu
models/block_decoder.py ``xing4_0``: latent attention over one paged pool a
layer, a hyper-connection residual, a shared expert beside the routed
experts this chip holds): the model in a GenerationEngine behind a
ModelServer, answering POST :generate requests that an open-loop generator
sends on a schedule, as runners/serve.py drives GPTDecoder. What differs:
the model is built from the configuration's file (its keys are the source's
own; ``experts_held`` is the chip's share and the router keeps its published
width); the traffic's documents are prefilled once each in set-up, so the
prefix cache holds them before the ramp; and the comparison that decides
`correct` is against chipbench/reference_xing4.py, through a prefix hit on a
document, with the routers' discontinuity handled in the open (below). The
load generator, the pool sampler, the spans of a traced run and the
variants' texts are runners/serve.py's own."""

import os
import time

import numpy as np

from .. import data, reference_xing4 as reference, trace as trace_mod
from . import serve

# the probe: a prompt of the traffic's first document (4096 tokens, in the
# prefix cache since set-up) and PROBE_TAIL more tokens, so it is admitted
# through a prefix hit on the whole document and prefills its tail in two
# chunks (256 + 44); then PROBE_STEPS decode steps
PROBE_TAIL, PROBE_STEPS = 300, 8

# ---- the limits of `correct`. Two readings each (my chip runs, PR 31;
# PERF.md section 4 has the table): what this change read over four weight
# seeds and eight token seeds, and what the reference computed one
# precision lower (`precision="bfloat16"`: its norms, router, softmax and
# hyper-connection maps, Sinkhorn included, in bfloat16 like the rest) reads
# when it is put in the system's place and held to the float32 reference
# (three token seeds). As in lfm2moe_chat, rounding activations and the
# cache to bfloat16 between float32 computations is itself most of the
# distance to float32, so the lower precision is told apart by one limit,
# the share of rows, which lies between the two readings; the other two are
# set for the change's own seeds (a false `correct: false` refuses a PR).
#
# The share of (layer, position) rows where the system picked another set of
# experts than the reference's own scores would. This change 4299-4921 of
# 83676 (5.1-5.9 %, ten readings); lower precision 7546-7797 (9.0-9.3 %):
# not correct.
PICK_ROWS_SHARE = 0.075
# Logits, max abs difference on logits of up to ~5.8, following the system's
# picks. This change 0.065-0.137 (eighteen readings, mean 0.096); lower
# precision 0.150-0.235 (one of three seeds passes 0.2): the limit leaves
# the change half as much again above its largest reading.
LOGIT_ATOL = 0.2
# Every differing pick has to be a near tie: its selection score at most
# PICK_EPS under the reference's 4th largest. This change 1.3e-2 - 2.1e-2
# (the largest of ~4500 rows: a long tail); lower precision 2.6e-2 - 2.9e-2.
# A router that picks by something else differs in most rows, not by a
# margin.
PICK_EPS = 8e-2
# Of the window's prompt pages that could have come from the prefix cache,
# the share that did, against what the schedule says it should be: every
# whole page of every document (held since set-up) and none of a question's
# (at most 3 of ~125 a request). The requests admitted inside the window are
# not exactly those due in it, hence the slack.
PREFIX_HIT_SLACK = 0.95


def model_of(config):
    from paddle_tpu.models.block_decoder import xing4_0

    if config["model_type"] != "xing4_0":
        raise ValueError("not a xing4_0 configuration: %r" % config["model_type"])
    # the file's n_routed_experts is what this chip holds; the router and the
    # picks are over the published count
    full = dict(config, **{
        k: config["published"][k] for k in ("n_routed_experts",)})
    return xing4_0(
        full, experts_held=config["experts_held"],
        max_context=config["engine"]["max_context"], dtype="bfloat16",
        eos_id=-1)


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


def prefill_documents(engine, cell, seed):
    """Each of the traffic's documents through the engine once, alone, one
    new token: afterwards the prefix cache holds every whole page of every
    document. Returns the documents and the first one's picks by position
    [n_moe, tokens, k] (the probe follows them)."""
    from paddle_tpu.serving.generation import GenRequest

    tr = cell["traffic"]
    docs = data.module("traffic", tr["generator"]).documents(
        tr, seed, cell["config"]["sizes"]["vocab_size"])
    first = None
    for i, doc in enumerate(docs):
        engine.record_picks = i == 0
        try:
            run = engine.start(GenRequest(doc, max_new_tokens=1))
            engine.finish(run)
        finally:
            engine.record_picks = False
        if i == 0:
            first = _picks_by_position(run)[1]
    return docs, first


def _picks_by_position(run):
    parts = sorted(run.picks, key=lambda sp: sp[0])
    return parts[0][0], np.concatenate([p for _, p in parts], axis=1)


def compare(notes, problems, got_first, got_last, picks, weights, spec,
            tokens, rows):
    """Hold first-token and last-step logits and the picks that led to them
    (a system's, or a stand-in's) to the float32 reference over the same
    tokens and weights, following those picks: the three limits above."""
    want, ref_picks, gaps = reference.forward(
        weights, tokens, spec, picks=picks, rows=rows)
    differ = (np.sort(np.asarray(ref_picks), -1) != np.sort(picks, -1)).any(-1)
    notes.update(
        pick_rows=int(differ.size), pick_rows_differing=int(differ.sum()),
        pick_gap_max=float(np.max(gaps)),
        logit_abs_max=float(np.max(np.abs(np.asarray(want)))))
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


def probe_run(engine, model, seed, doc):
    """The probe's requests through the engine's own entry points: what it
    was fed, what came out, and how it was admitted."""
    from paddle_tpu.serving.generation import GenRequest

    rng = np.random.default_rng([int(seed), 0x9A0B])
    prompt = doc + [int(v) for v in rng.integers(2, model.vocab_size, PROBE_TAIL)]
    engine.record_picks = True
    chunks0 = engine.stats()["prefill_chunks"]
    try:
        run = engine.start(GenRequest(prompt, max_new_tokens=PROBE_STEPS + 2))
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
    return {"prompt": prompt, "tokens": prompt + chosen, "first": got_first,
            "last": got_last, "hit_at": hit_at, "picks": own,
            "tail_chunks": engine.stats()["prefill_chunks"] - chunks0}


def _probe(engine, model, seed, problems, doc, doc_picks, stand_in=None,
           ran=None):
    """First-token logits after a chunked prefill that begins at a prefix
    hit on the whole document, and the logits of the PROBE_STEPS-th decode
    step, through the engine's own entry points (probe_run; `ran`: one made
    earlier), against the plain reference's one pass over the same tokens
    with the engine's own weights, following the engine's picks. `stand_in`
    ("bfloat16"): the reference at that precision in the system's place,
    over the same tokens: the reading that shows the limits tell a lower
    precision apart."""
    ran = ran or probe_run(engine, model, seed, doc)
    hit_at, tokens = ran["hit_at"], ran["tokens"]
    notes = {"prefix_hit_at": hit_at, "tail_chunks": ran["tail_chunks"]}
    if hit_at != len(doc) or notes["tail_chunks"] < 2:
        problems.append(
            "the probe was not admitted through a prefix hit on its %d-token "
            "document with its tail in two chunks or more: %s"
            % (len(doc), notes))
        return notes
    rows = [len(ran["prompt"]) - 1, len(tokens) - 1]
    weights = {n: engine.scope.vars[n] for n in model.param_names()}
    spec = reference.spec_of(model)
    if stand_in:
        low, picks, _ = reference.forward(
            weights, tokens, spec, rows=rows, precision=stand_in)
        low = np.asarray(low)
        return compare(notes, problems, low[0], low[1], np.asarray(picks),
                       weights, spec, tokens, rows)
    # the document's positions' picks are those of its prefill in set-up,
    # which no cache could have helped: the same tokens at the same positions
    picks = np.concatenate(
        [doc_picks[:, :hit_at], ran["picks"]], axis=1)[:, :len(tokens)]
    return compare(notes, problems, ran["first"], ran["last"], picks, weights,
                   spec, tokens, rows)


def _delta(hist0, hist1, key):
    (s0, c0), (s1, c1) = hist0.get(key, (0.0, 0)), hist1.get(key, (0.0, 0))
    return s1 - s0, c1 - c0


def _moe_work(config, model, hist0, hist1, name):
    """What the routing of the steps and chunks between two snapshots asked
    of the expert layers here: held experts touched (summed over layers and
    calls) and the expert-layer calls made (readers/moe_roofline.py)."""
    touched = calls = 0
    for h in ("gen_experts_touched", "gen_chunk_experts_touched"):
        s, c = _delta(hist0, hist1, "serving/%s/%s" % (name, h))
        touched += s
        calls += c * len(model.moe_layers())
    return {
        "experts_touched": touched, "calls": calls,
        "hidden_size": config["hidden_size"],
        "moe_intermediate_size": config["moe_intermediate_size"],
        "num_experts": config["published"]["n_routed_experts"],
        "weight_bytes": 2,
    }


def _latent_work(config, model, hist0, hist1, name):
    """What the decode steps and prefill chunks between two snapshots asked
    of latent attention (readers/latent_attn_roofline.py): positions whose
    cached row had to be read, (row, position) pairs attended, a layer."""
    key = lambda h: "serving/%s/%s" % (name, h)
    decode, steps = _delta(hist0, hist1, key("gen_ctx_tokens"))
    chunk, chunks = _delta(hist0, hist1, key("gen_chunk_ctx_tokens"))
    pairs, _ = _delta(hist0, hist1, key("gen_chunk_ctx_pairs"))
    return {
        "positions": decode + chunk, "pairs": decode + pairs,
        "calls": steps + chunks, "layers": model.n_layer,
        "heads": config["num_attention_heads"],
        "key_values": config["kv_lora_rank"] + config["qk_rope_head_dim"],
        "value_values": config["kv_lora_rank"], "cache_bytes": 2,
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
    docs, doc_picks = prefill_documents(engine, cell, seed)
    marks["documents_prefilled"] = clock()
    out["notes"]["probe"] = _probe(
        engine, model, seed, problems, docs[0], doc_picks)
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
        hist0, count0 = serve.snapshot(engine)
        sampler.start()
        serve._sleep_until(w1)
        sampler.stop()
        hist1, count1 = serve.snapshot(engine)
        in_window = compiles.count
        if trace:
            log_dir = trace_mod.start(cell["workload"]["name"])
            try:
                # inside the profile on both sides: the steps these two
                # readings count are all in the trace
                traced0, _ = serve.snapshot(engine)
                time.sleep(serve.TRACED_SECONDS)
                traced1, _ = serve.snapshot(engine)
            finally:
                jax.profiler.stop_trace()
            out["moe_traced"] = _moe_work(config, model, traced0, traced1, name)
            out["latent_traced"] = _latent_work(
                config, model, traced0, traced1, name)
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
    ps = engine.page_size
    doc_pages = sum(len(docs[gen.schedule[i]["document"]]) // ps for i in measured)
    all_pages = sum((len(gen.schedule[i]["prompt"]) - 1) // ps for i in measured)
    eligible = counters.get("prefix_pages_eligible", 0)
    if not eligible or (counters["prefix_pages_hit"] * all_pages
                        < PREFIX_HIT_SLACK * doc_pages * eligible):
        problems.append(
            "%d of the window's %d eligible prompt pages came from the prefix "
            "cache, where the documents are %d of the schedule's %d: the "
            "documents were prefilled again" % (
                counters.get("prefix_pages_hit", 0), eligible, doc_pages,
                all_pages))
    out["counters"] = counters
    out["histograms"] = {n: (hist0.get(n, (0.0, 0)), hist1[n]) for n in hist1}
    out["names"] = {"model": name}
    out["samples"] = {"loadgen_late_ms": late, "ms_per_token": lat,
                      "slots_in_use": sampler.slots, "pool_fill": sampler.fill,
                      "pool_live": sampler.live}
    out["window"] = {"seconds": seconds, "chips": cell["workload"]["chips"]}
    out["notes"]["requests_in_window"] = len(measured)
    out["notes"]["moe_window"] = _moe_work(config, model, hist0, hist1, name)
    out["notes"]["latent_window"] = _latent_work(config, model, hist0, hist1, name)
    out["notes"]["kernel_dispatches"] = engine.stats()["kernel_dispatches"]
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
