"""The glm_5_2 configuration in chipbench, on the CPU at toy width: the
benchmark's reference against the program's, the configuration file against
the catalog's entry, the serve_glm52 runner end to end through run.main()
(untraced and traced), the comparison that decides `correct` catching a
wrong weight and telling a lower precision's stand-in from the system, and
the selection path's byte and FLOP counts."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import data, reference_glm52 as bench_ref, trace as cb_trace  # noqa: E402
from test_chipbench import (  # noqa: E402,F401 — fixtures
    SYNTH_DEVICE, SYNTH_HOST, check_result, on_cpu, run_main, tree)

TOY_GLM = {
    "name": "toy_glm", "source": "test", "runner": "serve_glm52",
    "served_name": "toyglm", "sizes": {"vocab_size": 128},
    "model_type": "glm_moe_dsa", "vocab_size": 128, "hidden_size": 64,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "qk_head_dim": 24, "v_head_dim": 16, "num_hidden_layers": 5,
    "first_k_dense_replace": 1,
    "mlp_layer_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
    "indexer_types": ["full", "shared", "shared", "shared", "full"],
    "index_n_heads": 2, "index_head_dim": 16, "index_topk": 16,
    "indexer_rope_interleave": True, "n_routed_experts": 4,
    "published": {"n_routed_experts": 8}, "experts_held": [0, 1, 2, 3],
    "n_shared_experts": 1, "num_experts_per_tok": 2, "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc", "scoring_func": "sigmoid",
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "rms_norm_eps": 1e-5, "rope_interleave": True,
    "rope_parameters": {"rope_theta": 8000000, "rope_type": "default"},
    "tie_word_embeddings": False, "max_position_embeddings": 1024,
    "num_nextn_predict_layers": 0,
    "engine": {"max_slots": 8, "max_context": 512, "page_size": 16,
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
    data.manifest(), "glm52_longdocs", "end_to_end")}
NEW = {"serve.dsa_index_time_share", "serve.dsa_index_roofline",
       "serve.sparse_attn_time_share", "serve.sparse_attn_roofline",
       "serve.dsa_selected_share"}


def _toy_cell(tree):
    tree.add("configs", "toy_glm", TOY_GLM)
    tree.add("traffic", "toy_docs", TOY_DOCS)
    tree.add_cell("toy_glm_docs", "toy_glm", "toy_docs", like="glm52_longdocs")


def test_the_two_references_agree():
    """chipbench's copy and the program's reference: the same text from the
    equations on, and the same logits, picks, selections and gaps on seeded
    weights, own and followed, both precisions, a held share."""
    from paddle_tpu.executor import Scope
    from paddle_tpu.models import glm_dsa_reference as prog_ref
    from chipbench.runners import serve_glm52

    below = lambda mod: open(mod.__file__).read().split("The equations (", 1)[1]
    assert below(prog_ref) == below(bench_ref) and "def forward(" in below(bench_ref)
    model = serve_glm52.model_of(dict(TOY_GLM, engine={"max_context": 64}))
    assert model.dtype == "bfloat16" and model.moe["num_experts"] == 8
    assert model.experts_held == [0, 1, 2, 3]
    scope = Scope(seed=11)
    model.ensure_params(scope)
    weights = {n: scope.vars[n] for n in model.param_names()}
    tokens = [int(v) for v in np.random.default_rng(0).integers(2, 128, 29)]
    spec = prog_ref.spec_of(model)
    assert spec == bench_ref.spec_of(model)
    a = prog_ref.forward(weights, tokens, spec)
    b = bench_ref.forward(weights, tokens, spec)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    picks = np.asarray(a["picks"])[:, :, ::-1].copy()
    picks[0, 3, 0] = (picks[0, 3, 0] + 1) % 8  # one the scores do not bear out
    sels = np.asarray(a["selections"]).copy()
    sels[1, 25, 0] = 26 if sels[1, 25, 0] != 26 else 27  # past the row: -inf
    for precision in ("float32", "bfloat16"):
        a = prog_ref.forward(weights, tokens, spec, picks=picks, selections=sels,
                             rows=[5, 28], precision=precision)
        b = bench_ref.forward(weights, tokens, spec, picks=picks, selections=sels,
                              rows=[5, 28], precision=precision)
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
        assert np.asarray(a["logits"]).shape == (2, 128)
    assert float(np.asarray(a["pick_gaps"]).max()) > 0.0


def test_configuration_file_is_the_catalog_s_config_with_only_the_reduced_keys_changed():
    c = data.named("configs", "glm_5_2")
    reduced = ["num_hidden_layers", "first_k_dense_replace", "mlp_layer_types",
               "indexer_types", "n_routed_experts", "vocab_size",
               "num_nextn_predict_layers"]
    assert c["reduced"] == reduced
    catalog = os.environ.get("MODEL_CONFIG_CATALOG", "")
    if catalog and os.path.exists(catalog):
        row = [json.loads(l) for l in open(catalog) if '"GLM-5.2"' in l][0]
        assert c["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in reduced:
                assert c["published"][key] == value and c[key] != value
            else:
                assert c[key] == value, key
    assert c["published"]["indexer_types"][2:7] == c["indexer_types"] == [
        "full", "shared", "shared", "shared", "full"]
    assert c["published"]["mlp_layer_types"][2:7] == c["mlp_layer_types"]
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["n_routed_experts"], c["vocab_size"]) == (5, 1, 16, 154880 // 8)
    assert c["experts_held"] == list(range(16))
    entry = [e for e in data.manifest()["configs"] if e["name"] == "glm_5_2"][0]
    assert entry["reduced"] == reduced and entry["source"] == c["source"]
    for key in ("assumed", "deployment", "engine_why", "precision"):
        assert c[key]
    e = c["engine"]
    assert (e["max_slots"], e["max_context"], e["prefill_chunk"]) == (32, 65664, 256)
    assert e["prefill_buckets"] == [64, 256]
    assert (e["pool_pages"] - 1) * e["page_size"] == 466944
    # the prefix cache's cap (the pool less one slot's worst case and the
    # scratch page) holds the eight documents whole
    assert e["pool_pages"] - 1 - 1026 >= sum(32768 // 64 + 72 * i for i in range(8))
    from chipbench.runners import serve_glm52

    model = serve_glm52.model_of(c)
    assert model.blocks == [("dsa", "dense")] + [("dsa", "moe")] * 4
    assert model.moe["num_experts"] == 256 and model.experts_held == list(range(16))
    assert model.latent_width() == (576, 640) and model.vocab_size == 19360
    assert model.mla["sm_scale"] == pytest.approx(256 ** -0.5)
    assert model.dsa["topk"] == 2048 and model.rope_theta == 8e6
    # parameters: the cut's arithmetic, from the widths
    d = 6144
    mla = d * 2048 + 2048 * 64 * 256 + d * 576 + 64 * 192 * 512 + 64 * 512 * 256 + 64 * 256 * d
    indexer = 2048 * 32 * 128 + d * 128 + d * 32 + 2 * 128
    norms = 2 * d + 2048 + 512
    expert = 3 * d * 2048
    dense = mla + indexer + 3 * d * 12288 + norms
    shared = mla + d * 256 + 256 + 17 * expert + norms
    total = dense + 3 * shared + (shared + indexer) + 2 * 19360 * d + d
    assert round(total / 1e6) == 3882  # 7.76 GB in bfloat16
    # a position's cache: five latent rows and two index keys
    assert 5 * 640 * 2 + 2 * 128 * 2 == 6912


def test_serve_glm52_runner_toy_cell(tree, on_cpu):
    _toy_cell(tree)
    rc, doc = run_main(["--workload", "toy_glm_docs", "--seed", "2147483659",
                        "--seconds", "2", "--trace", "0"])
    assert rc == 0
    check_result(doc, SERVE_E2E, traced=False)
    assert set(doc["metrics"]) == SERVE_E2E
    assert doc["attempted"] == 8  # rate 4/s x 2 s, whatever the seed


@pytest.fixture
def synthetic_dsa_trace(monkeypatch):
    """As test_chipbench.synthetic_trace, with this configuration's
    operations among the device's."""
    from chipbench.runners import serve

    device = {0: SYNTH_DEVICE[0] + [
        ("op:moe_router fusion.7 fusion jit(f)/moe_router/out=picks/top_k", 400, 10),
        ("op:moe_experts fusion.9 custom-call jit(f)/moe_experts/out=y/pallas_call", 410, 40),
        ("op:dsa_index scan.4 custom-call jit(f)/dsa_index/out=s/pallas_call", 450, 20),
        ("op:dsa_topk fusion.5 fusion jit(f)/dsa_topk/out=sel/reduce", 470, 20),
        ("op:mla_sparse_attention fusion.6 fusion jit(f)/mla_sparse_attention/out=ctx/gather", 490, 30),
    ]}
    monkeypatch.setattr(serve, "TRACED_SECONDS", 0.6)
    monkeypatch.setattr(serve, "TRACED_TRAFFIC_SECONDS", 1.6)
    monkeypatch.setattr(
        cb_trace, "reduce_dir",
        lambda log_dir, hlo_texts, chips: (
            os.listdir(log_dir),
            cb_trace.reduce_events(device, SYNTH_HOST, chips))[1])


def test_serve_glm52_runner_traced(tree, on_cpu, synthetic_dsa_trace):
    """Every per-layer metric the cell lists is read: the accepted serve.*
    ones from the same engine, and the five this configuration brings."""
    _toy_cell(tree)
    rc, doc = run_main(["--workload", "toy_glm_docs", "--seed", "7",
                        "--seconds", "2", "--trace", "1"])
    assert rc == 0
    names = {m["name"] for m in data.cell_metrics(
        data.manifest(), "toy_glm_docs", "per_layer")}
    assert NEW <= names and "serve.latent_attn_time_share" not in names
    check_result(doc, names, traced=True)
    missing = names - set(doc["metrics"])
    assert missing <= {n for n in names if ".idle_" in n}, missing
    m = doc["metrics"]
    busy = 250 + 120.0
    assert m["serve.dsa_index_time_share"]["value"] == pytest.approx(100 * 40 / busy)
    assert m["serve.sparse_attn_time_share"]["value"] == pytest.approx(100 * 30 / busy)
    assert m["serve.moe_time_share"]["value"] == pytest.approx(100 * 50 / busy)
    assert m["serve.dsa_index_roofline"]["value"] > 0
    assert m["serve.sparse_attn_roofline"]["value"] > 0
    assert 0 < m["serve.dsa_selected_share"]["value"] <= 100
    assert m["serve.traces_in_window"]["value"] == 0


def test_dsa_rooflines_count_what_the_work_requires():
    """A hand-counted step: two rows at positions 9999 and 39999 on one
    document of 30000 positions (10000 of it shared) and their own pages,
    two full layers, five layers attending, 2048 selected a row."""
    reader = data.module("readers", "dsa_roofline")
    index_positions = 2 * (40000 + 10000 - 10000)  # rows scanned once each
    work = {"index_positions": index_positions, "index_pairs": 2 * (10000 + 40000),
            "selected_positions": 5 * 3500, "selected_pairs": 5 * 2 * 2048,
            "calls": 1, "index_heads": 32, "index_width": 128, "index_bytes": 2,
            "heads": 64, "key_values": 576, "value_values": 512, "cache_bytes": 2}
    assert reader.required_bytes(work, "index") == 80000 * 256
    assert reader.required_flops(work, "index") == 100000 * 32 * 128 * 2
    assert reader.required_bytes(work, "sparse") == 17500 * 1152
    assert reader.required_flops(work, "sparse") == 20480 * 64 * 1088 * 2
    args = data.named("layer_metrics", "serve.sparse_attn_roofline")["args"]
    events = [("op:mla_sparse_attention fusion.6 fusion jit(f)/mla_sparse_attention/out=ctx/gather", 0, 100_000),
              ("op:dsa_index scan.4 custom-call jit(f)/dsa_index/out=s/pallas_call", 0, 50_000)]
    run = {"trace": {"events": events}, "dsa_traced": work,
           "peaks": {"hbm_gbs": 819.0, "bf16_tflops": 197.0}}
    by_bytes = 17500 * 1152 / 819e9
    assert by_bytes > 20480 * 64 * 1088 * 2 / 197e12  # decode: the rows bound it
    assert reader.read(args, run) == pytest.approx(100 * by_bytes / 100e-6)
    index_args = data.named("layer_metrics", "serve.dsa_index_roofline")["args"]
    assert reader.read(index_args, run) == pytest.approx(
        100 * max(80000 * 256 / 819e9, 100000 * 32 * 128 * 2 / 197e12) / 50e-6)
    # nothing to read: an untraced run, a program without the sparse attention ops
    assert reader.read(args, {"trace": {"events": events}, "peaks": run["peaks"]}) is None
    assert reader.read(args, dict(run, dsa_traced=dict(work, calls=0))) is None
    assert reader.read(args, dict(run, trace={"events": events[1:]})) is None


def test_probe_catches_a_wrong_weight_and_tells_a_lower_precision_apart(tree, on_cpu):
    """The comparison that decides `correct` can fail: after an index key
    matrix is changed in the scope only (the compiled variants hold the old
    one), the reference's own selections and the engine's disagree, and the
    probe says so. The probe itself goes through a prefix hit on the first
    document into a slot a filler left and decodes among the other live
    rows, the row in the highest live slot is held to the reference too, and
    the reference in bfloat16 can stand in the system's place."""
    from chipbench.runners import serve_glm52

    _toy_cell(tree)
    cell = data.cell("toy_glm_docs")
    server, engine, model, _ = serve_glm52.build(cell, 5)
    docs, doc_picks, doc_sel = serve_glm52.prefill_documents(engine, cell, 5)
    assert [len(d) for d in docs] == [64, 80, 96]
    assert doc_picks.shape == (4, 64, 2) and doc_sel.shape == (2, 64, 16)
    problems = []
    ran = serve_glm52.probe_run(engine, model, 5, docs)
    notes = serve_glm52._probe(
        engine, model, 5, problems, docs, doc_picks, doc_sel, ran=ran)
    assert problems == [] and notes["prefix_hit_at"] == 64
    # five fillers in slots 0-4, the cold row in 5; fillers 1 and 4 retired,
    # the probe in the slot retired last
    assert notes["live_slots"] == [0, 1, 2, 3, 5] and notes["slot"] == 1
    assert notes["other_row"]["slot"] == 5
    n, m = 64 + 300 + 8, 256 + 8
    assert notes["tail_chunks"] >= 2 and notes["pick_rows"] == 4 * n
    assert notes["selected"] == 2 * sum(min(16, p + 1) for p in range(n))
    assert notes["other_row"]["pick_rows"] == 4 * m
    assert notes["other_row"]["selected"] == 2 * sum(min(16, p + 1) for p in range(m))
    low = serve_glm52._probe(
        engine, model, 5, [], docs, doc_picks, doc_sel, stand_in="bfloat16",
        ran=ran)
    assert low["first-token"] > 0 and low["selected"] == notes["selected"]
    name = [n for n in model.param_names() if n.endswith("l0_idx_k_w")][0]
    engine.scope.vars[name] = -engine.scope.vars[name]
    problems = []
    serve_glm52._probe(engine, model, 6, problems, docs, doc_picks, doc_sel)
    assert any("top-2048" in p or "not a tie" in p for p in problems), problems
    engine.scope.vars[name] = -engine.scope.vars[name]
    # a probe that finds no cached document says so
    engine.prefix_cache.clear()
    problems = []
    serve_glm52._probe(engine, model, 7, problems, docs, doc_picks, doc_sel)
    assert any("prefix hit" in p for p in problems)
