"""The serve runner of the ``afmoe`` architecture (paddle_tpu
models/block_decoder.py ``afmoe``: grouped-query attention in sliding-window
layers, whose cache is a page space of its own that keeps only each row's
last window, beside full layers that keep every position; a gate on
attention, sandwich norms, a shared expert beside the routed experts this
chip holds): the model in a GenerationEngine behind a ModelServer,
answering POST :generate requests that an open-loop generator sends on a
schedule, as runners/serve_glm52.py does for ``glm_moe_dsa``. Its load
generator, pool sampler, snapshots and traced spans are runners/serve.py's,
the documents' set-up step serve_glm52's; what is its own: the model, built
from the configuration's file (its keys are the source's; ``experts_held``
is the chip's share, the router keeps its published width), the comparison
that decides `correct` against chipbench/reference_trinity.py, which follows
the engine's picks and bounds how far they lie from the reference's own, and
the window layers' work for their roofline."""

import os
import time

import numpy as np

from .. import data, reference_trinity as reference, trace as trace_mod
from . import serve, serve_glm52, serve_xing4

# the probe, decoded in the window's decode program among other rows:
# FILLERS questions on the traffic's other documents (each admitted through
# a prefix hit on its document, its question alone prefilled), a chat of
# CHAT_USER tokens behind the shared system prompt (under the window: the
# live row in the highest slot, held to the reference too), every third
# filler retired again, then the probe's own prompt, the traffic's longest
# document (26,624 tokens, in the prefix cache since set-up) and PROBE_TAIL
# more tokens, admitted through a prefix hit on the whole document into a
# slot a filler left, its tail prefilled in two chunks (256 + 44: the first
# chunk's window reaches back 2,047 positions into the document's kept
# window pages); then PROBE_STEPS decode steps of all live rows together,
# idle slots among and behind them
PROBE_TAIL, PROBE_STEPS, FILLERS, CHAT_USER = 300, 8, 16, 500

# ---- the limits of `correct` (PERF.md section 4 has the table: what the
# system read on one v5e chip, and what two stand-ins read when each is put
# in the system's place and held to the float32 reference: (a) the
# reference with its norms, router, scores and softmax in bfloat16, the
# nearest precision below the configuration's; (b) the reference with every
# window layer attending its whole context). The system: the probe's row
# (807,960 (layer, position) rows) and the chat beside it (17,160), over
# six weight seeds drawn near 2**31, and the cell's own runs; the stand-ins
# over the same six. Rounding activations to bfloat16 between float32
# computations is most of the distance to float32, as in the other MoE
# cells: what parts stand-in (a) from the system is how far its router's
# picks lie from the reference's own (PICK_FAR_SHARE, by the widest factor over
# the first expert layers: PICK_FAR_EARLY_SHARE); stand-in (b) fails
# every limit but the share of differing rows' by a factor of 5 or more.
# Every limit is at least 1.5x the system's largest reading, so that a
# correct run is not read as incorrect.
#
# The share of (layer, position) rows where the system picked another set of
# experts than the reference's own scores would (128 experts, 8 a token:
# near ties abound). The system 10.26-10.52 % (the probe), 10.37-12.04 %
# (the chat); (a) 16.19-16.42 %; (b) 92.2 %.
PICK_ROWS_SHARE = 0.19
# The share of rows whose followed picks lie more than PICK_FAR under the
# reference's own 8th largest score: beyond what the system's bfloat16
# activations move a router's scores (0.1-0.4 % at the first expert layers,
# 3.3-3.9 % at the last), within what rounding the scores themselves to
# bfloat16 (2^-8 from 0.5 to 1) does. The system 1.89-2.03 % (the probe),
# 2.15-2.75 % (the chat); (a) 5.80-5.91 %: not correct, each seed; (b)
# 92.1 %.
PICK_FAR, PICK_FAR_SHARE = 3e-3, 0.042
# The same share over the first PICK_FAR_EARLY_LAYERS expert layers alone
# (published layers 2-5), where the system's activations have drifted
# little from float32 and rounding the router's scores themselves moves a
# pick as much as anywhere: the line that parts stand-in (a) by the widest
# factor. The system 0.29-0.34 % (the probe), 0.31-0.61 % (the chat); (a)
# 3.29-3.55 %: not correct, each seed, at 5.4x the system's largest; (b)
# 92.0 %.
PICK_FAR_EARLY_LAYERS, PICK_FAR_EARLY_SHARE = 4, 0.014
# Every differing pick has to be a near tie: its selection score at most
# PICK_EPS under the reference's own 8th largest. The system 0.0101-0.0176;
# (a) 0.0187-0.0229; (b) 0.876-0.896. A router that picks by something else
# differs in most rows, not by a margin.
PICK_EPS = 8e-2
# Logits, max abs difference, following the system's picks (logits up to
# ~4.3). The system 0.056-0.079; (a) 0.071-0.097; (b) 4.18-5.27.
LOGIT_ATOL = 0.2
# Of the window's prompt pages that could have come from the prefix cache,
# the share that did, against what the schedule says (serve_xing4's rule).
PREFIX_HIT_SLACK = serve_xing4.PREFIX_HIT_SLACK
STAND_INS = ("bfloat16", "full_window")


def model_of(config):
    from paddle_tpu.models.block_decoder import afmoe

    if config["model_type"] != "afmoe":
        raise ValueError("not an afmoe configuration: %r" % config["model_type"])
    # the file's num_experts is what this chip holds; the router and the
    # picks are over the published count
    full = dict(config, num_experts=config["published"]["num_experts"])
    return afmoe(
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
    document and the window pages of its last 2,048 positions. Returns the
    documents and the longest one's picks [n_moe, tokens, k] by position
    (the probe's, which follows them)."""
    from paddle_tpu.serving.generation import GenRequest

    tr = cell["traffic"]
    docs = data.module("traffic", tr["generator"]).documents(
        tr, seed, cell["config"]["sizes"]["vocab_size"])
    probe_doc = int(np.argmax([len(d) for d in docs]))
    picks = None
    for i, doc in enumerate(docs):
        engine.record_picks = i == probe_doc
        try:
            run = engine.start(GenRequest(doc, max_new_tokens=1))
            engine.finish(run)
        finally:
            engine.record_picks = False
        if i == probe_doc:
            picks = serve_glm52._by_position(run.picks)
    return docs, probe_doc, picks


def compare(notes, problems, got_first, got_last, picks, weights, spec, tokens,
            rows, what="the probe"):
    """Hold a row's first-token and last-step logits and the picks that led
    to them (a system's, or a stand-in's) to the float32 reference over the
    same tokens and weights, following those picks: the five limits above."""
    out = reference.forward(weights, tokens, spec, picks=picks, rows=rows)
    want = np.asarray(out["logits"])
    differ = (np.sort(np.asarray(out["picks"]), -1) != np.sort(picks, -1)).any(-1)
    gaps = np.asarray(out["pick_gaps"])
    far = gaps > PICK_FAR
    notes.update(
        pick_rows=int(differ.size), pick_rows_differing=int(differ.sum()),
        pick_rows_far=int(far.sum()),
        pick_far_early=float(far[:PICK_FAR_EARLY_LAYERS].mean()),
        pick_gap_max=float(np.max(gaps)),
        logit_abs_max=float(np.max(np.abs(want))),
        # for the record, no limit: the same shares layer by layer
        by_layer={"pick_differing": differ.mean(1).round(6).tolist(),
                  "pick_far": far.mean(1).round(6).tolist()})
    if notes["pick_gap_max"] > PICK_EPS:
        problems.append(
            "%s: a router pick lies %.3e under the reference's 8th largest "
            "score (most allowed %.1e): not a tie"
            % (what, notes["pick_gap_max"], PICK_EPS))
    if notes["pick_rows_far"] > PICK_FAR_SHARE * differ.size:
        problems.append(
            "%s: %d of %d (layer, position) rows follow a pick more than %.0e "
            "under the reference's 8th largest score" % (
                what, notes["pick_rows_far"], differ.size, PICK_FAR))
    if notes["pick_far_early"] > PICK_FAR_EARLY_SHARE:
        problems.append(
            "%s: %.2f %% of the first %d expert layers' rows follow a pick "
            "more than %.0e under the reference's 8th largest score (most "
            "allowed %.1f %%)" % (
                what, 100 * notes["pick_far_early"], PICK_FAR_EARLY_LAYERS,
                PICK_FAR, 100 * PICK_FAR_EARLY_SHARE))
    if differ.mean() > PICK_ROWS_SHARE:
        problems.append(
            "%s: %d of %d (layer, position) rows pick other experts than the "
            "reference" % (what, differ.sum(), differ.size))
    for name, got, ref_row in (
        ("first-token", got_first, want[0]),
        ("decode-step-%d" % PROBE_STEPS, got_last, want[1]),
    ):
        notes[name] = float(np.max(np.abs(got - ref_row)))
        if not (np.isfinite(got).all() and notes[name] <= LOGIT_ATOL):
            problems.append(
                "%s: %s logits differ from the reference by up to %.3e (most "
                "allowed %.1e; |ref| up to %.3e)" % (
                    what, name, notes[name], LOGIT_ATOL, notes["logit_abs_max"]))
    return notes


def probe_run(engine, model, seed, docs, probe_doc, system):
    """The probe's requests through the engine's own entry points (the
    comment on PROBE_TAIL): for the probe's row and the chat in the highest
    live slot, what it was fed, what came out, its own picks by position;
    how the probe was admitted. `docs`: the traffic's documents, the probe's
    at `probe_doc`; `system`: the chats' shared system prompt."""
    from paddle_tpu.serving.generation import GenRequest

    rng = np.random.default_rng([int(seed), 0x7A1])
    draw = lambda n: [int(v) for v in rng.integers(2, model.vocab_size, n)]
    start = lambda prompt: engine.start(
        GenRequest(prompt, max_new_tokens=PROBE_STEPS + 2))
    others = [d for i, d in enumerate(docs) if i != probe_doc]
    firsts, live = {}, []

    def admit(prompt):
        run = start(prompt)
        live.append(run)
        firsts[id(run)] = np.array(engine.last_prefill_logits, np.float32)
        return run

    engine.record_picks = True
    try:
        fillers = [admit(others[i % len(others)] + draw(16 + 8 * (i % 6)))
                   for i in range(min(FILLERS, engine.max_slots - 3))]
        chat = admit(system + draw(CHAT_USER))
        for run in reversed(fillers[1::3]):  # the probe takes the slot retired last
            live.remove(run)
            engine.finish(run)
        chunks0 = engine.stats()["prefill_chunks"]
        probe = admit(docs[probe_doc] + draw(PROBE_TAIL))
        tail_chunks = engine.stats()["prefill_chunks"] - chunks0
        window_held = np.flatnonzero(engine.window_pool.block_table(probe.slot))
        for _ in range(PROBE_STEPS):
            engine.decode_step(live)
        logits = np.array(engine.last_logits, np.float32)
        rows = [{"slot": r.slot, "prompt_len": len(r.req.prompt),
                 "tokens": list(r.req.prompt) + list(r.tokens[:PROBE_STEPS]),
                 "first": firsts[id(r)], "last": logits[r.slot],
                 "picks": serve_glm52._by_position(r.picks)}
                for r in (probe, chat)]
    finally:
        engine.record_picks = False
        for run in live:
            engine.finish(run)
    return {"rows": rows, "hit_at": min(p for p, _ in probe.picks),
            "tail_chunks": tail_chunks, "live_slots": sorted(r.slot for r in live),
            "window_pages_first": int(window_held.min()),
            "window_pages_held": int(len(window_held))}


def _probe(engine, model, seed, problems, docs, probe_doc, doc_picks, system,
           stand_in=None, ran=None):
    """First-token logits after a chunked prefill that begins at a prefix
    hit on the longest document, and the logits of the PROBE_STEPS-th decode
    step among the other live rows, through the engine's own entry points,
    against the plain reference's pass over the same tokens with the
    engine's own weights, following the engine's picks (the document's
    positions' are those of its prefill in set-up); the chat in the highest
    live slot likewise (notes["other_row"]). `stand_in` (one of STAND_INS):
    the reference so put in the system's place, over the probe's tokens,
    and nothing else."""
    ran = ran or probe_run(engine, model, seed, docs, probe_doc, system)
    hit_at, doc = ran["hit_at"], docs[probe_doc]
    notes = {k: ran[k] for k in ("tail_chunks", "live_slots",
                                 "window_pages_first", "window_pages_held")}
    notes["prefix_hit_at"] = hit_at
    if hit_at != len(doc) or notes["tail_chunks"] < 2:
        problems.append(
            "the probe was not admitted through a prefix hit on its %d-token "
            "document with its tail in two chunks or more: %s"
            % (len(doc), notes))
        return notes
    mine, other = ran["rows"]
    notes["slot"] = mine["slot"]
    tokens = mine["tokens"]
    at = lambda row: [row["prompt_len"] - 1, len(row["tokens"]) - 1]
    weights = {n: engine.scope.vars[n] for n in model.param_names()}
    spec = reference.spec_of(model)
    if stand_in:
        low = reference.forward(
            weights, tokens, spec, rows=at(mine),
            precision="bfloat16" if stand_in == "bfloat16" else "float32",
            full_window=stand_in == "full_window")
        logits = np.asarray(low["logits"])
        return compare(notes, problems, logits[0], logits[1],
                       np.asarray(low["picks"]), weights, spec, tokens,
                       at(mine), stand_in)
    picks = np.concatenate([doc_picks[:, :hit_at], mine["picks"]],
                           axis=1)[:, :len(tokens)]
    compare(notes, problems, mine["first"], mine["last"], picks, weights,
            spec, tokens, at(mine))
    n = len(other["tokens"])
    notes["other_row"] = compare(
        {"slot": other["slot"]}, problems, other["first"], other["last"],
        other["picks"][:, :n], weights, spec, other["tokens"], at(other),
        "the chat in slot %d beside the probe" % other["slot"])
    return notes


def _window_work(config, model, hist0, hist1, name):
    """What the decode steps and prefill chunks between two snapshots asked
    of the sliding-window layers' attention (readers/window_attn_roofline.py),
    a layer: the positions whose K and V rows had to be read, the (row,
    position) pairs attended."""
    key = lambda h: "serving/%s/gen_%s" % (name, h)
    decode, steps = serve_xing4._delta(hist0, hist1, key("window_ctx_tokens"))
    chunk, chunks = serve_xing4._delta(hist0, hist1, key("chunk_window_tokens"))
    pairs, _ = serve_xing4._delta(hist0, hist1, key("chunk_window_pairs"))
    return {
        "positions": decode + chunk, "pairs": decode + pairs,
        "calls": steps + chunks, "layers": sum(1 for w in model.windows if w),
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "d_head": config["head_dim"], "cache_bytes": 2,
    }


def _moe_work(config, model, hist0, hist1, name):
    """serve_xing4._moe_work under this model type's keys."""
    return serve_xing4._moe_work(
        dict(config, published={"n_routed_experts": config["published"][
            "num_experts"]}), model, hist0, hist1, name)


def _system_prompt(cell):
    from ..traffic.open_loop_chat import SYSTEM_SEED

    tr = cell["traffic"]
    return [int(v) for v in np.random.default_rng(SYSTEM_SEED).integers(
        2, cell["config"]["sizes"]["vocab_size"], tr["system_tokens"])]


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
    docs, probe_doc, doc_picks = prefill_documents(engine, cell, seed)
    marks["documents_prefilled"] = clock()
    out["notes"]["probe"] = _probe(
        engine, model, seed, problems, docs, probe_doc, doc_picks,
        _system_prompt(cell))
    del doc_picks
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
        serve._sleep_until(w0)
        hist0, count0 = serve.snapshot(engine)
        sampler.start()
        serve._sleep_until(w0 + seconds)
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
            out["window_traced"] = _window_work(
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
    # what the schedule makes eligible from the cache: a question's whole
    # document, a chat's system prompt
    ps = engine.page_size
    cached = lambda s: (len(docs[s["document"]]) if s["document"] is not None
                        else tr["system_tokens"]) // ps
    doc_pages = sum(cached(gen.schedule[i]) for i in measured)
    all_pages = sum((len(gen.schedule[i]["prompt"]) - 1) // ps for i in measured)
    eligible = counters.get("prefix_pages_eligible", 0)
    if not eligible or (counters["prefix_pages_hit"] * all_pages
                        < PREFIX_HIT_SLACK * doc_pages * eligible):
        problems.append(
            "%d of the window's %d eligible prompt pages came from the prefix "
            "cache, where the documents and system prompts are %d of the "
            "schedule's %d: they were prefilled again" % (
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
    out["notes"]["window_attn_window"] = _window_work(
        config, model, hist0, hist1, name)
    out["notes"]["window_pool"] = engine.stats()["window_pool"]
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
