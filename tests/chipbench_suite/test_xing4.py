"""The xing4_0 configuration in chipbench, on the CPU at toy width: the
benchmark's reference against the program's, the configuration file against
the catalog's entry, the documents generator, the serve_xing4 runner end to
end through run.main() (untraced and traced), the comparison that decides
`correct` catching a wrong weight and telling a lower precision's stand-in
from the system, and the latent walk's byte and FLOP counts."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import data, reference_xing4 as bench_ref, trace as cb_trace  # noqa: E402
from test_chipbench import (  # noqa: E402,F401 — fixtures
    SYNTH_DEVICE, SYNTH_HOST, check_result, on_cpu, run_main, tree)

TOY_XING4 = {
    "name": "toy_xing4", "source": "test", "runner": "serve_xing4",
    "served_name": "toyxing4", "sizes": {"vocab_size": 128},
    "model_type": "xing4_0", "vocab_size": 128, "hidden_size": 64,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "n_routed_experts": 4, "published": {"n_routed_experts": 8},
    "experts_held": [0, 1, 2, 3], "n_shared_experts": 1,
    "num_experts_per_tok": 2, "n_group": 1, "topk_group": 1,
    "topk_method": "noaux_tc", "scoring_func": "sigmoid",
    "norm_topk_prob": True, "routed_scaling_factor": 2, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 4, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64},
    "tie_word_embeddings": False, "max_position_embeddings": 1024,
    "num_nextn_predict_layers": 0,
    "engine": {"max_slots": 4, "max_context": 512, "page_size": 16,
               "pool_pages": 120, "prefill_buckets": [32, 64]},
    "server": {"request_timeout_ms": 60000}, "reduced": [], "assumed": {},
}
TOY_DOCS = {
    "generator": "open_loop_docs", "rate_per_s": 4.0, "order_seed": 3,
    "ramp_seconds": 1,
    "documents": {"count": 3, "first_tokens": 64, "step_tokens": 16},
    "question_tokens": {"median": 12, "sigma": 0.8, "min": 4, "max": 40},
    "output_tokens": {"median": 6, "sigma": 0.6, "min": 2, "max": 12},
}
SERVE_E2E = {m["name"] for m in data.cell_metrics(
    data.manifest(), "xing4_docs_chat", "end_to_end")}


def _toy_cell(tree):
    tree.add("configs", "toy_xing4", TOY_XING4)
    tree.add("traffic", "toy_docs", TOY_DOCS)
    tree.add_cell("toy_xing4_docs", "toy_xing4", "toy_docs", like="xing4_docs_chat")


def test_the_two_references_agree():
    """chipbench's copy and the program's reference: the same text below the
    first paragraph, and the same logits, picks and gaps on seeded weights,
    own picks and followed ones, both precisions, a held share."""
    from paddle_tpu.executor import Scope
    from paddle_tpu.models import xing4_reference as prog_ref
    from chipbench.runners import serve_xing4

    below = lambda mod: open(mod.__file__).read().split("whole sequence", 1)[1]
    assert below(prog_ref) == below(bench_ref) and "def forward(" in below(bench_ref)
    model = serve_xing4.model_of(dict(TOY_XING4, engine={"max_context": 64}))
    assert model.dtype == "bfloat16" and model.moe["num_experts"] == 8
    assert model.experts_held == [0, 1, 2, 3]
    scope = Scope(seed=11)
    model.ensure_params(scope)
    weights = {n: scope.vars[n] for n in model.param_names()}
    tokens = [int(v) for v in np.random.default_rng(0).integers(2, 128, 19)]
    spec = prog_ref.spec_of(model)
    assert spec == bench_ref.spec_of(model)
    a = prog_ref.forward(weights, tokens, spec)
    b = bench_ref.forward(weights, tokens, spec)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
    follow = np.asarray(a[1])[:, :, ::-1].copy()
    follow[0, 3, 0] = (follow[0, 3, 0] + 1) % 8  # one pick the scores do not bear out
    for precision in ("float32", "bfloat16"):
        a = prog_ref.forward(weights, tokens, spec, picks=follow, rows=[5, 18],
                             precision=precision)
        b = bench_ref.forward(weights, tokens, spec, picks=follow, rows=[5, 18],
                              precision=precision)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert np.asarray(a[0]).shape == (2, 128)
    assert float(np.asarray(a[2]).max()) >= 0.0


def test_configuration_file_is_the_catalog_s_config_with_only_the_reduced_keys_changed():
    c = data.named("configs", "xing4_29b_a4b")
    reduced = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
               "num_nextn_predict_layers"]
    assert c["reduced"] == reduced
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = [json.loads(l) for l in open(catalog)
               if '"Xing4.0-29B-A4B"' in l][0]
        assert c["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in reduced:
                assert c["published"][key] == value and c[key] != value
            else:
                assert c[key] == value, key
    assert c["published"] == {"num_hidden_layers": 40, "first_k_dense_replace": 2,
                              "n_routed_experts": 64, "num_nextn_predict_layers": 1}
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["n_routed_experts"], c["num_nextn_predict_layers"]) == (20, 1, 8, 0)
    assert c["experts_held"] == list(range(8))
    widths = {"hidden_size": 3584, "intermediate_size": 9216,
              "moe_intermediate_size": 1024, "q_lora_rank": 768,
              "kv_lora_rank": 512, "qk_nope_head_dim": 128,
              "qk_rope_head_dim": 64, "v_head_dim": 128,
              "num_attention_heads": 32, "num_experts_per_tok": 4,
              "n_shared_experts": 1, "hc_mult": 4, "vocab_size": 131072}
    assert {k: c[k] for k in widths} == widths
    entry = [e for e in data.manifest()["configs"] if e["name"] == "xing4_29b_a4b"][0]
    assert entry["reduced"] == reduced and entry["source"] == c["source"]
    for key in ("assumed", "deployment", "engine_why", "precision"):
        assert c[key]
    e = c["engine"]
    assert (e["max_slots"], e["max_context"], e["prefill_chunk"]) == (32, 12800, 256)
    assert (e["pool_pages"] - 1) * e["page_size"] == 196608
    from chipbench.runners import serve_xing4

    model = serve_xing4.model_of(c)
    assert model.blocks == [("mla", "dense")] + [("mla", "moe")] * 19
    assert model.moe["num_experts"] == 64 and model.experts_held == list(range(8))
    assert model.latent_width() == (576, 640) and model.vocab_size == 131072
    assert model.mla["sm_scale"] == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)
    # parameters: the cut's arithmetic (ISSUE 31), from the names' shapes
    d = 3584
    attention = d * 768 + 768 * 32 * 192 + d * 576 + 512 * 32 * 256 + 4096 * d
    assert attention == 28409856
    hyper = 2 * (4 * d * 24 + 3 + 24)
    norms = 2 * d + 768 + 512
    dense = attention + 3 * d * 9216 + hyper + norms
    expert = attention + 3 * d * 1024 + d * 64 + 64 + hyper + norms + 8 * 3 * d * 1024
    total = dense + 19 * expert + 2 * 131072 * d + d
    assert round(total / 1e7) == 351  # 3.51 G parameters, 7.02 GB in bfloat16


def test_documents_generator_replays_one_schedule_whatever_the_seed():
    gen = data.module("traffic", "open_loop_docs")
    tr = data.named("traffic", "docs_replay")
    lengths = gen.document_lengths(tr)
    assert lengths == [4096 + 704 * i for i in range(12)]
    assert sum(lengths) / 12 == 7968 and all(n % 64 == 0 for n in lengths)
    small = dict(TOY_DOCS, rate_per_s=6.0)
    a = gen.schedule(small, 5, [("ramp", 2), ("window", 4)], 128)
    b = gen.schedule(small, 2 ** 31 + 11, [("ramp", 2), ("window", 4)], 128)
    assert len(a) == 36 and [r["phase"] for r in a].count("window") == 24
    docs_a, docs_b = gen.documents(small, 5, 128), gen.documents(small, 2 ** 31 + 11, 128)
    assert [len(x) for x in docs_a] == [64, 80, 96] and docs_a != docs_b
    for ra, rb in zip(a, b):  # the schedule is the mix's, the tokens the seed's
        assert (ra["due_s"], ra["max_new_tokens"], ra["document"], len(ra["prompt"])) == (
            rb["due_s"], rb["max_new_tokens"], rb["document"], len(rb["prompt"]))
        assert ra["prompt"][:len(docs_a[ra["document"]])] == docs_a[ra["document"]]
        assert 4 <= len(ra["prompt"]) - len(docs_a[ra["document"]]) <= 40
    assert a[0]["prompt"] != b[0]["prompt"]
    # each document by every third request, in schedule order within a phase
    by_phase = [r["document"] for r in sorted(a, key=lambda r: r["due_s"])]
    assert sorted(np.bincount(by_phase).tolist()) == [12, 12, 12]


def test_serve_xing4_runner_toy_cell(tree, on_cpu):
    _toy_cell(tree)
    rc, doc = run_main(["--workload", "toy_xing4_docs", "--seed", "2147483659",
                        "--seconds", "2", "--trace", "0"])
    assert rc == 0
    check_result(doc, SERVE_E2E, traced=False)
    assert set(doc["metrics"]) == SERVE_E2E
    assert doc["attempted"] == 8  # rate 4/s x 2 s, whatever the seed


@pytest.fixture
def synthetic_latent_trace(monkeypatch):
    """As test_chipbench.synthetic_trace, with this configuration's
    operations among the device's."""
    from chipbench.runners import serve

    device = {0: SYNTH_DEVICE[0] + [
        ("op:moe_router fusion.7 fusion jit(f)/moe_router/out=picks/top_k", 400, 10),
        ("op:moe_experts fusion.9 custom-call jit(f)/moe_experts/out=y/pallas_call", 410, 40),
        ("op:mla_attention out_att.4 custom-call jit(f)/mla_attention/out=ctx/pallas_call", 450, 60),
        ("op:hyper_connection fusion.12 fusion jit(f)/hyper_connection/out=u/mul", 510, 15),
        ("op:hyper_connection_post fusion.13 fusion jit(f)/hyper_connection_post/out=x/add", 525, 15),
    ]}
    monkeypatch.setattr(serve, "TRACED_SECONDS", 0.6)
    monkeypatch.setattr(serve, "TRACED_TRAFFIC_SECONDS", 1.6)
    monkeypatch.setattr(
        cb_trace, "reduce_dir",
        lambda log_dir, hlo_texts, chips: (
            os.listdir(log_dir),
            cb_trace.reduce_events(device, SYNTH_HOST, chips))[1])


def test_serve_xing4_runner_traced(tree, on_cpu, synthetic_latent_trace):
    """Every per-layer metric the cell lists is read: the accepted serve.*
    ones from the same engine, and the four this configuration brings."""
    _toy_cell(tree)
    rc, doc = run_main(["--workload", "toy_xing4_docs", "--seed", "7",
                        "--seconds", "2", "--trace", "1"])
    assert rc == 0
    names = {m["name"] for m in data.cell_metrics(
        data.manifest(), "toy_xing4_docs", "per_layer")}
    check_result(doc, names, traced=True)
    missing = names - set(doc["metrics"])
    assert missing <= {n for n in names if ".idle_" in n}, missing
    m = doc["metrics"]
    busy = 250 + 140.0
    assert m["serve.latent_attn_time_share"]["value"] == pytest.approx(100 * 60 / busy)
    assert m["serve.hyper_conn_time_share"]["value"] == pytest.approx(100 * 30 / busy)
    assert m["serve.moe_time_share"]["value"] == pytest.approx(100 * 50 / busy)
    assert m["serve.latent_attn_roofline"]["value"] > 0
    assert m["serve.moe_roofline"]["value"] > 0
    # 4 held of 8 experts, two expert layers: never more than 8 a step
    assert 0 < m["serve.moe_experts_touched_mean"]["value"] <= 8
    assert m["serve.decode_feed_bytes_mean"]["value"] == 4 * (4 + 4 + 4 * 32 + 4 + 4)
    assert m["serve.prefix_hit_share"]["value"] > 75
    assert m["serve.traces_in_window"]["value"] == 0
    assert "serve.paged_attn_time_share" not in names


def test_latent_roofline_counts_what_attention_requires():
    """A hand-counted case: one decode step of two rows at 100 and 300
    positions and one 16-row chunk behind a 64-position prefix, 20 layers."""
    reader = data.module("readers", "latent_attn_roofline")
    positions = 100 + 300 + (64 + 16)
    pairs = 100 + 300 + (16 * 64 + 16 * 17 // 2)
    work = {"positions": positions, "pairs": pairs, "calls": 2, "layers": 20,
            "heads": 32, "key_values": 576, "value_values": 512, "cache_bytes": 2}
    assert reader.required_bytes(work) == 480 * 1152 * 20 == 11059200
    assert reader.required_flops(work) == 1560 * 32 * 1088 * 2 * 20 == 2172518400
    args = data.named("layer_metrics", "serve.latent_attn_roofline")["args"]
    events = [("op:mla_attention out_att.4 custom-call jit(f)/mla_attention/out=ctx/pallas_call", 0, 40_000),
              ("op:mla_attention fusion.3 fusion jit(f)/mla_attention/out=ctx/broadcast", 0, 10_000),
              ("op:matmul fusion.2 fusion jit(f)/matmul/out=q_lat/dot_general", 0, 500_000)]
    run = {"trace": {"events": events}, "latent_traced": work,
           "peaks": {"hbm_gbs": 819.0, "bf16_tflops": 197.0}}
    by_bytes = 11059200 / 819e9
    by_flops = 2172518400 / 197e12
    assert by_bytes > by_flops  # decode-heavy: the cache's bytes bound it
    assert reader.read(args, run) == pytest.approx(100 * by_bytes / 50e-6)
    assert 0 < reader.read(args, run) < 100
    heavy = dict(work, pairs=pairs * 100)  # a long chunk: the products bound it
    assert reader.read(args, dict(run, latent_traced=heavy)) == pytest.approx(
        100 * by_flops * (heavy["pairs"] / pairs) / 50e-6)
    # nothing to read: an untraced run, the parent's program
    assert reader.read(args, {"trace": {"events": events}, "peaks": run["peaks"]}) is None
    assert reader.read(args, dict(run, latent_traced=dict(work, calls=0))) is None
    assert reader.read(args, dict(run, trace={"events": events[2:]})) is None
    assert reader.read(args, {}) is None


def test_probe_catches_a_wrong_weight_and_tells_a_lower_precision_apart(tree, on_cpu):
    """The comparison that decides `correct` can fail: after a router bias
    is changed in the scope only (the compiled variants hold the old one),
    the reference and the engine disagree, and the probe says so. The probe
    itself goes through a prefix hit on the first document, and the
    reference in bfloat16 can stand in the system's place."""
    import jax.numpy as jnp

    from chipbench.runners import serve_xing4

    _toy_cell(tree)
    cell = data.cell("toy_xing4_docs")
    server, engine, model, _ = serve_xing4.build(cell, 5)
    docs, doc_picks = serve_xing4.prefill_documents(engine, cell, 5)
    assert [len(d) for d in docs] == [64, 80, 96]
    assert doc_picks.shape == (2, 64, 2)
    assert engine.prefix_cache.stats()["cached_pages"] == 4 + 5 + 6
    problems = []
    ran = serve_xing4.probe_run(engine, model, 5, docs[0])
    notes = serve_xing4._probe(
        engine, model, 5, problems, docs[0], doc_picks, ran=ran)
    assert problems == [] and notes["prefix_hit_at"] == 64
    assert notes["tail_chunks"] >= 2 and notes["pick_rows"] == 2 * (64 + 300 + 8)
    low = serve_xing4._probe(
        engine, model, 5, [], docs[0], doc_picks, stand_in="bfloat16", ran=ran)
    assert low["first-token"] > 0 and low["pick_rows"] == notes["pick_rows"]
    name = [n for n in model.param_names() if n.endswith("router_b")][0]
    engine.scope.vars[name] = jnp.flip(engine.scope.vars[name]) + 0.3
    problems = []
    serve_xing4._probe(engine, model, 6, problems, docs[0], doc_picks)
    assert any("router pick" in p or "logits differ" in p for p in problems)
    # a probe that finds no cached document says so
    engine.prefix_cache.clear()
    problems = []
    serve_xing4._probe(engine, model, 7, problems, docs[0], doc_picks)
    assert any("prefix hit" in p for p in problems)
