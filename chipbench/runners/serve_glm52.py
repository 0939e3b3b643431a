"""The serve runner of the ``glm_moe_dsa`` architecture (paddle_tpu
models/block_decoder.py ``glm_moe_dsa``: latent attention over a learned
sparse selection, a lightning indexer on the "full" layers and a paged pool
of index keys beside the latent pools, a shared expert beside the routed
experts this chip holds): the model in a GenerationEngine behind a
ModelServer, answering POST :generate requests that an open-loop generator
sends on a schedule, as runners/serve_xing4.py does for ``xing4_0``. Its
load generator, pool sampler, snapshots and traced spans are
runners/serve.py's, its documents' set-up step and the expert layers' work
serve_xing4's; what is its own: the model, built from the configuration's
file (its keys are the source's; ``experts_held`` is the chip's share, the
router keeps its published width), the comparison that decides `correct`
against chipbench/reference_glm52.py, which follows the engine's picks AND
its selections (both discontinuous) and bounds how far they lie from the
reference's own, and the selection path's work for the two rooflines."""

import os
import time

import numpy as np

from .. import data, reference_glm52 as reference, trace as trace_mod
from . import serve, serve_xing4

# the probe, decoded in the window's decode program among other rows:
# FILLERS requests on the traffic's other documents (each admitted through
# a prefix hit on its document, its question alone prefilled), a cold
# prompt of OTHER_PROMPT tokens behind them (the live row in the highest
# slot, held to the reference too), every third filler retired again, then
# the probe's own prompt, the traffic's first document (32,768 tokens, in
# the prefix cache since set-up) and PROBE_TAIL more tokens, admitted
# through a prefix hit on the whole document into a slot a filler left, its
# tail prefilled in two chunks (256 + 44); then PROBE_STEPS decode steps of
# all thirteen live rows together, idle slots among and behind them (the
# sparse walk's gather through each row's own table, each row's selection
# carried to the shared layers and its way back to its own slot are
# per-row paths that one live row would not exercise)
PROBE_TAIL, PROBE_STEPS, FILLERS, OTHER_PROMPT = 300, 8, 16, 2600

# ---- the limits of `correct`. Two readings each (PERF.md section 4 has the
# table): what the system read on one v5e chip, and what the reference one
# precision lower (`precision="bfloat16"`: the indexer's scores, the
# selection, the norms, the router and the softmax in bfloat16 like the
# rest) reads when it is put in the system's place and held to the float32
# reference. The system: the probe's row and the cold row beside it, ten
# readings each over seven weight seeds drawn near 2**31 with the probe
# among thirteen live rows, and twelve of the probe decoded alone over seven
# more; the stand-in: nine readings over seven weight seeds. Rounding
# activations and the caches to bfloat16 between float32 computations is
# itself most of the distance to float32, as in xing4_docs_chat: what parts
# the lower precision from the system is how far its router's picks lie
# from the reference's own (PICK_FAR_SHARE, six times apart), and the share
# of rows it picks differently (twice). Every limit is at least 1.5x the
# system's largest reading, so that a correct run is not read as incorrect.
#
# The share of (layer, position) rows where the system picked another set of
# experts than the reference's own scores would (256 experts, 8 a token:
# near ties abound). The system 8.84-9.10 % (the probe's row), 8.96-9.68 %
# (the cold row, 10,432 rows); lower precision 18.49-19.2 %: not correct,
# each seed.
PICK_ROWS_SHARE = 0.15
# The share of those rows whose followed picks lie more than PICK_FAR under
# the reference's own 8th largest score: beyond what the system's bfloat16
# activations move a router's scores, within what rounding the scores
# themselves to bfloat16 (2^-8 from 0.5 to 1) does. The system 0.33-0.40 %
# (the probe's row), 0.33-0.60 % (the cold row); lower precision 3.78 %:
# not correct, each seed.
PICK_FAR, PICK_FAR_SHARE = 3e-3, 0.01
# Every differing pick has to be a near tie: its selection score at most
# PICK_EPS under the reference's own 8th largest. The system 0.6e-2 -
# 1.2e-2; lower precision 1.2e-2 - 1.5e-2. A router that picks by something
# else differs in most rows, not by a margin.
PICK_EPS = 8e-2
# Of the positions the system's full layers selected, the share that the
# reference's own top-2048 (its float32 index scores) does not hold. The
# system 0.87-0.88 % (0.05 % for the cold row, whose positions under 2048
# select everything); lower precision 1.18-1.19 %. An indexer that scores
# by something else differs in most of its 2048.
SEL_OUTSIDE_SHARE = 0.02
# Every such position has to be a near tie: its reference index score at
# most SEL_EPS under the reference's own 2048th. The system 0.067-0.124;
# lower precision 0.143-0.186 (bfloat16 keys and queries).
SEL_EPS = 0.3
# Logits, max abs difference on logits of up to ~8, following the system's
# picks and selections. The system 0.069-0.100; lower precision
# 0.090-0.130.
LOGIT_ATOL = 0.2
# Of the window's prompt pages that could have come from the prefix cache,
# the share that did, against what the schedule says (serve_xing4's rule).
PREFIX_HIT_SLACK = serve_xing4.PREFIX_HIT_SLACK


def model_of(config):
    from paddle_tpu.models.block_decoder import glm_moe_dsa

    if config["model_type"] != "glm_moe_dsa":
        raise ValueError("not a glm_moe_dsa configuration: %r" % config["model_type"])
    # the file's n_routed_experts is what this chip holds; the router and the
    # picks are over the published count
    full = dict(config, n_routed_experts=config["published"]["n_routed_experts"])
    return glm_moe_dsa(
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


def _by_position(parts):
    """[(first position, array [layers, rows, ...])] -> [layers, tokens,
    ...], in position order."""
    parts = sorted(parts, key=lambda sp: sp[0])
    return np.concatenate([p for _, p in parts], axis=1)


def prefill_documents(engine, cell, seed):
    """Each of the traffic's documents through the engine once, alone, one
    new token, as serve_xing4.prefill_documents: afterwards the prefix cache
    holds every whole page of every document. Returns the documents and the
    first one's picks [n_moe, tokens, k] and selections [n_full, tokens,
    topk] by position (the probe follows them)."""
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
            first = _by_position(run.picks), _by_position(run.selections)
    return (docs,) + first


def compare(notes, problems, got_first, got_last, picks, selections, weights,
            spec, tokens, rows, what="the probe"):
    """Hold a row's first-token and last-step logits, the picks and the
    selections that led to them (a system's, or a stand-in's) to the float32
    reference over the same tokens and weights, following those picks and
    selections: the six limits above."""
    out = reference.forward(weights, tokens, spec, picks=picks,
                            selections=selections, rows=rows)
    want = np.asarray(out["logits"])
    differ = (np.sort(np.asarray(out["picks"]), -1) != np.sort(picks, -1)).any(-1)
    chosen = np.asarray(selections) >= 0
    outside = np.asarray(out["sel_outside"])
    gaps = np.asarray(out["pick_gaps"])
    share = lambda a: a.mean(1).round(6).tolist()
    notes.update(
        pick_rows=int(differ.size), pick_rows_differing=int(differ.sum()),
        pick_rows_far=int((gaps > PICK_FAR).sum()),
        pick_gap_max=float(np.max(gaps)),
        selected=int(chosen.sum()), selected_outside=int(outside.sum()),
        sel_gap_max=float(np.max(out["sel_gaps"])),
        logit_abs_max=float(np.max(np.abs(want))),
        # for the record, no limit: the same shares layer by layer (the
        # first expert layer's inputs have the least rounding behind them)
        by_layer={
            "pick_differing": share(differ),
            "pick_far": share(gaps > PICK_FAR),
            "sel_outside": (outside.sum(1) / chosen.sum((1, 2))).round(7).tolist()})
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
    if differ.mean() > PICK_ROWS_SHARE:
        problems.append(
            "%s: %d of %d (layer, position) rows pick other experts than the "
            "reference" % (what, differ.sum(), differ.size))
    if notes["sel_gap_max"] > SEL_EPS:
        problems.append(
            "%s: a selected position's index score lies %.3e under the "
            "reference's 2048th (most allowed %.1e): not a tie"
            % (what, notes["sel_gap_max"], SEL_EPS))
    if notes["selected_outside"] > SEL_OUTSIDE_SHARE * max(notes["selected"], 1):
        problems.append(
            "%s: %d of %d selected positions are not in the reference's own "
            "top-2048" % (what, notes["selected_outside"], notes["selected"]))
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


def probe_run(engine, model, seed, docs):
    """The probe's requests through the engine's own entry points (the
    comment on PROBE_TAIL): for the probe's row and the live row in the
    highest slot, what it was fed, what came out, its own picks and
    selections by position; how the probe was admitted. `docs`: the
    traffic's documents, the first the probe's."""
    from paddle_tpu.serving.generation import GenRequest

    rng = np.random.default_rng([int(seed), 0x9A0B])
    draw = lambda n: [int(v) for v in rng.integers(2, model.vocab_size, n)]
    start = lambda prompt: engine.start(
        GenRequest(prompt, max_new_tokens=PROBE_STEPS + 2))
    firsts, live = {}, []

    def admit(prompt):
        run = start(prompt)
        live.append(run)
        firsts[id(run)] = np.array(engine.last_prefill_logits, np.float32)
        return run

    engine.record_picks = True
    try:
        fillers = [admit(docs[1 + i % (len(docs) - 1)] + draw(16 + 8 * (i % 6)))
                   for i in range(min(FILLERS, engine.max_slots - 3))]
        other = admit(draw(min(OTHER_PROMPT, engine.max_context // 2)))
        for run in reversed(fillers[1::3]):  # the probe takes the slot retired last
            live.remove(run)
            engine.finish(run)
        chunks0 = engine.stats()["prefill_chunks"]
        probe = admit(docs[0] + draw(PROBE_TAIL))
        tail_chunks = engine.stats()["prefill_chunks"] - chunks0
        for _ in range(PROBE_STEPS):
            engine.decode_step(live)
        logits = np.array(engine.last_logits, np.float32)
        rows = [{"slot": r.slot, "prompt_len": len(r.req.prompt),
                 "tokens": list(r.req.prompt) + list(r.tokens[:PROBE_STEPS]),
                 "first": firsts[id(r)], "last": logits[r.slot],
                 "picks": _by_position(r.picks),
                 "selections": _by_position(r.selections)}
                for r in (probe, other)]
    finally:
        engine.record_picks = False
        for run in live:
            engine.finish(run)
    return {"rows": rows, "hit_at": min(p for p, _ in probe.picks),
            "tail_chunks": tail_chunks, "live_slots": sorted(r.slot for r in live)}


def _probe(engine, model, seed, problems, docs, doc_picks, doc_sel,
           stand_in=None, ran=None):
    """First-token logits after a chunked prefill that begins at a prefix
    hit on the whole document, and the logits of the PROBE_STEPS-th decode
    step among twelve other live rows, through the engine's own entry points,
    against the plain reference's pass over the same tokens with the
    engine's own weights, following the engine's picks and selections (the
    document's positions' are those of its prefill in set-up); the row in
    the highest live slot likewise (notes["other_row"]). `stand_in`
    ("bfloat16"): the reference at that precision in the system's place,
    over the probe's tokens, and nothing else."""
    ran = ran or probe_run(engine, model, seed, docs)
    hit_at, doc = ran["hit_at"], docs[0]
    notes = {k: ran[k] for k in ("tail_chunks", "live_slots")}
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
        low = reference.forward(weights, tokens, spec, rows=at(mine),
                                precision=stand_in)
        logits = np.asarray(low["logits"])
        return compare(notes, problems, logits[0], logits[1],
                       np.asarray(low["picks"]), np.asarray(low["selections"]),
                       weights, spec, tokens, at(mine), stand_in)
    join = lambda first, own: np.concatenate(
        [first[:, :hit_at], own], axis=1)[:, :len(tokens)]
    compare(notes, problems, mine["first"], mine["last"],
            join(doc_picks, mine["picks"]), join(doc_sel, mine["selections"]),
            weights, spec, tokens, at(mine))
    n = len(other["tokens"])
    notes["other_row"] = compare(
        {"slot": other["slot"]}, problems, other["first"], other["last"],
        other["picks"][:, :n], other["selections"][:, :n], weights, spec,
        other["tokens"], at(other),
        "the row in slot %d beside the probe" % other["slot"])
    return notes


def _dsa_work(config, model, hist0, hist1, name):
    """What the decode steps and prefill chunks between two snapshots asked
    of the selection path (readers/dsa_roofline.py), summed over the
    layers: distinct index-key rows the scans read, the (row, position)
    pairs they scored, distinct latent rows the selections name, the (row,
    selected position) pairs attended."""
    key = lambda h: "serving/%s/gen_dsa_%s" % (name, h)
    work = {}
    for h in ("index_positions", "index_pairs", "selected_positions",
              "selected_pairs"):
        work[h], calls = serve_xing4._delta(hist0, hist1, key(h))
    work.update(
        calls=calls, index_heads=config["index_n_heads"],
        index_width=config["index_head_dim"], index_bytes=2,
        heads=config["num_attention_heads"],
        key_values=config["kv_lora_rank"] + config["qk_rope_head_dim"],
        value_values=config["kv_lora_rank"], cache_bytes=2)
    return work


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
    docs, doc_picks, doc_sel = prefill_documents(engine, cell, seed)
    marks["documents_prefilled"] = clock()
    out["notes"]["probe"] = _probe(
        engine, model, seed, problems, docs, doc_picks, doc_sel)
    del doc_picks, doc_sel
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
            out["moe_traced"] = serve_xing4._moe_work(
                config, model, traced0, traced1, name)
            out["dsa_traced"] = _dsa_work(config, model, traced0, traced1, name)
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
    out["notes"]["moe_window"] = serve_xing4._moe_work(config, model, hist0, hist1, name)
    out["notes"]["dsa_window"] = _dsa_work(config, model, hist0, hist1, name)
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
