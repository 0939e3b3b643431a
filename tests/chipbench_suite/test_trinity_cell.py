"""The trinity_mini configuration in chipbench, on the CPU at toy width: the
benchmark's reference against the program's, the configuration file against
the catalog's entry, the mixed traffic's schedule, the serve_trinity runner
end to end through run.main() (untraced and traced), the comparison that
decides `correct` catching a wrong weight and telling both stand-ins from
the system, and the window layers' byte and FLOP counts."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import data, reference_trinity as bench_ref, trace as cb_trace  # noqa: E402
from test_chipbench import (  # noqa: E402,F401 — fixtures
    SYNTH_DEVICE, SYNTH_HOST, check_result, on_cpu, run_main, tree)

TOY_TRINITY = {
    "name": "toy_trinity", "source": "test", "runner": "serve_trinity",
    "served_name": "toytrinity", "sizes": {"vocab_size": 128},
    "model_type": "afmoe", "vocab_size": 128, "hidden_size": 32,
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "num_hidden_layers": 5,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention",
                                                "sliding_attention"],
    "sliding_window": 32, "num_dense_layers": 1, "num_experts": 4,
    "published": {"num_experts": 8}, "experts_held": [0, 1, 2, 3],
    "num_experts_per_tok": 2, "num_shared_experts": 1, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "n_group": 1,
    "topk_group": 1, "mup_enabled": True, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "rope_scaling": None, "tie_word_embeddings": False,
    "max_position_embeddings": 1024, "hidden_act": "silu",
    "engine": {"max_slots": 8, "max_context": 640, "page_size": 16,
               "pool_pages": 120, "window_pool_pages": 60,
               "prefill_buckets": [32, 64]},
    "server": {"request_timeout_ms": 60000}, "reduced": [], "assumed": {},
}
TOY_MIXED = {
    "generator": "open_loop_mixed", "rate_per_s": 4.0, "order_seed": 3,
    "ramp_seconds": 1,
    "documents": {"count": 3, "first_tokens": 64, "step_tokens": 16},
    "question_tokens": {"median": 12, "sigma": 0.8, "min": 4, "max": 40},
    "doc_output_tokens": {"median": 6, "sigma": 0.6, "min": 2, "max": 12},
    "system_tokens": 16,
    "user_tokens": {"median": 12, "sigma": 0.8, "min": 4, "max": 40},
    "chat_output_tokens": {"median": 6, "sigma": 0.6, "min": 2, "max": 12},
}
SERVE_E2E = {m["name"] for m in data.cell_metrics(
    data.manifest(), "trinity_mixed_docs", "end_to_end")}
NEW = {"serve.window_attn_time_share", "serve.window_attn_roofline",
       "serve.window_ctx_tokens_mean", "serve.window_pages_held_mean"}


def _toy_cell(tree):
    tree.add("configs", "toy_trinity", TOY_TRINITY)
    tree.add("traffic", "toy_mixed", TOY_MIXED)
    tree.add_cell("toy_trinity_mixed", "toy_trinity", "toy_mixed",
                  like="trinity_mixed_docs")


def test_the_two_references_agree():
    """chipbench's copy and the program's reference: the same text from the
    equations on, and the same logits, picks and gaps on seeded weights,
    own and followed, both precisions and the whole-context stand-in, a
    held share."""
    from paddle_tpu.executor import Scope
    from paddle_tpu.models import afmoe_reference as prog_ref
    from chipbench.runners import serve_trinity

    below = lambda mod: open(mod.__file__).read().split("The equations (", 1)[1]
    assert below(prog_ref) == below(bench_ref) and "def forward(" in below(bench_ref)
    model = serve_trinity.model_of(dict(TOY_TRINITY, engine={"max_context": 96}))
    assert model.dtype == "bfloat16" and model.moe["num_experts"] == 8
    assert model.experts_held == [0, 1, 2, 3]
    scope = Scope(seed=11)
    model.ensure_params(scope)
    weights = {n: scope.vars[n] for n in model.param_names()}
    tokens = [int(v) for v in np.random.default_rng(0).integers(2, 128, 45)]
    spec = prog_ref.spec_of(model)
    assert spec == bench_ref.spec_of(model)
    a = prog_ref.forward(weights, tokens, spec)
    b = bench_ref.forward(weights, tokens, spec)
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    picks = np.asarray(a["picks"])[:, :, ::-1].copy()
    picks[0, 3, 0] = (picks[0, 3, 0] + 1) % 8  # one the scores do not bear out
    for kw in ({"precision": "float32"}, {"precision": "bfloat16"},
               {"full_window": True}):
        a = prog_ref.forward(weights, tokens, spec, picks=picks, rows=[5, 44], **kw)
        b = bench_ref.forward(weights, tokens, spec, picks=picks, rows=[5, 44], **kw)
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
        assert np.asarray(a["logits"]).shape == (2, 128)
    assert float(np.asarray(a["pick_gaps"]).max()) > 0.0


def test_configuration_file_is_the_catalog_s_config_with_only_the_reduced_keys_changed():
    c = data.named("configs", "trinity_mini")
    reduced = ["num_experts", "vocab_size"]
    assert c["reduced"] == reduced
    catalog = os.environ.get("MODEL_CONFIG_CATALOG", "")
    if catalog and os.path.exists(catalog):
        row = [json.loads(l) for l in open(catalog) if '"Trinity-Mini"' in l][0]
        assert c["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in reduced:
                assert c["published"][key] == value and c[key] != value
            else:
                assert c[key] == value, key
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        32, 16, 200192 // 8)
    assert c["experts_held"] == list(range(16))
    entry = [e for e in data.manifest()["configs"] if e["name"] == "trinity_mini"][0]
    assert entry["reduced"] == reduced and entry["source"] == c["source"]
    for key in ("assumed", "deployment", "engine_why", "precision"):
        assert c[key]
    e = c["engine"]
    assert (e["max_slots"], e["max_context"], e["prefill_chunk"]) == (32, 27264, 256)
    assert e["prefill_buckets"] == [64, 256] and e["page_size"] == 64
    # the prefix cache's cap (the pool less one slot's worst case and the
    # scratch page) holds the eight documents whole
    assert e["pool_pages"] - 1 - 27264 // 64 >= sum(
        12288 // 64 + 32 * i for i in range(8))
    # the window pool holds the documents' last windows and then some
    assert e["window_pool_pages"] - 1 > 8 * 2048 // 64 + 2 * 37
    from chipbench.runners import serve_trinity
    model = serve_trinity.model_of(c)
    assert model.blocks == [("attention", "dense")] * 2 + [("attention", "moe")] * 30
    assert model.windows == [2048, 2048, 2048, 0] * 8
    assert model.positions == ["rotary", "rotary", "rotary", "none"] * 8
    assert model.moe["num_experts"] == 128 and model.experts_held == list(range(16))
    assert model.moe["k"] == 8 and model.moe["scale"] == 2.826
    assert model.vocab_size == 25024 and model.embed_scale == pytest.approx(2048 ** 0.5)
    # parameters: the cut's arithmetic, from the widths
    d = 2048
    attn = d * 4096 + 2 * d * 512 + 4096 * d + d * 4096 + 2 * 128
    norms = 4 * d
    expert = 3 * d * 1024
    dense = attn + 3 * d * 6144 + norms
    moe = attn + d * 128 + 128 + 17 * expert + norms
    total = 2 * dense + 30 * moe + 2 * 25024 * d + d
    assert round(total / 1e6) == 4267  # 8.53 GB in bfloat16
    # a position's cache: 8 full layers and 24 window layers of K and V
    assert 8 * 4 * 128 * 2 * 2 == 16384 and 24 * 4 * 128 * 2 * 2 == 49152


def test_the_mixed_schedule_is_the_mix_s_and_half_each_kind():
    gen = data.module("traffic", "open_loop_mixed")
    a = gen.schedule(TOY_MIXED, 1, [("ramp", 1), ("window", 4)], 128)
    b = gen.schedule(TOY_MIXED, 2, [("ramp", 1), ("window", 4)], 128)
    assert [(r["due_s"], len(r["prompt"]), r["max_new_tokens"], r["document"])
            for r in a] == [(r["due_s"], len(r["prompt"]), r["max_new_tokens"],
                             r["document"]) for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    window = [r for r in a if r["phase"] == "window"]
    docs = [r for r in window if r["document"] is not None]
    assert len(window) == 16 and len(docs) == 8
    lengths = gen.document_lengths(TOY_MIXED)
    for r in docs:
        assert len(r["prompt"]) - lengths[r["document"]] in range(4, 41)
    for r in window:
        if r["document"] is None:
            assert r["prompt"][:16] == a[0]["prompt"][:16] or a[0]["document"] is not None
    counts = np.bincount([r["document"] for r in a if r["document"] is not None])
    assert counts.max() - counts.min() <= 1


def test_serve_trinity_runner_toy_cell(tree, on_cpu):
    _toy_cell(tree)
    rc, doc = run_main(["--workload", "toy_trinity_mixed", "--seed",
                        "2147483659", "--seconds", "2", "--trace", "0"])
    assert rc == 0
    check_result(doc, SERVE_E2E, traced=False)
    assert set(doc["metrics"]) == SERVE_E2E
    assert doc["attempted"] == 8  # rate 4/s x 2 s, whatever the seed


@pytest.fixture
def synthetic_window_trace(monkeypatch):
    """As test_chipbench.synthetic_trace, with this configuration's
    operations among the device's."""
    from chipbench.runners import serve

    device = {0: SYNTH_DEVICE[0] + [
        ("op:moe_router fusion.7 fusion jit(f)/moe_router/out=picks/top_k", 400, 10),
        ("op:moe_experts fusion.9 custom-call jit(f)/moe_experts/out=y/pallas_call", 410, 40),
        ("op:window_attention out_att.8 custom-call jit(f)/window_attention/out=att/pallas_call", 450, 30),
    ]}
    monkeypatch.setattr(serve, "TRACED_SECONDS", 0.6)
    monkeypatch.setattr(serve, "TRACED_TRAFFIC_SECONDS", 1.6)
    monkeypatch.setattr(
        cb_trace, "reduce_dir",
        lambda log_dir, hlo_texts, chips: (
            os.listdir(log_dir),
            cb_trace.reduce_events(device, SYNTH_HOST, chips))[1])


def test_serve_trinity_runner_traced(tree, on_cpu, synthetic_window_trace):
    """Every per-layer metric the cell lists is read: the accepted serve.*
    ones from the same engine, and the four this configuration brings."""
    _toy_cell(tree)
    rc, doc = run_main(["--workload", "toy_trinity_mixed", "--seed", "7",
                        "--seconds", "2", "--trace", "1"])
    assert rc == 0
    names = {m["name"] for m in data.cell_metrics(
        data.manifest(), "toy_trinity_mixed", "per_layer")}
    assert NEW <= names and "serve.latent_attn_time_share" not in names
    assert "serve.paged_attn_time_share" in names
    check_result(doc, names, traced=True)
    missing = names - set(doc["metrics"])
    assert missing <= {n for n in names if ".idle_" in n}, missing
    m = doc["metrics"]
    busy = 250 + 80.0
    assert m["serve.window_attn_time_share"]["value"] == pytest.approx(100 * 30 / busy)
    assert m["serve.paged_attn_time_share"]["value"] == pytest.approx(100 * 60 / busy)
    assert 0 < m["serve.window_attn_roofline"]["value"]
    live = m["serve.live_slots_mean"]["value"]
    assert 0 < m["serve.window_ctx_tokens_mean"]["value"] <= 32 * max(live, 8)
    assert m["serve.window_pages_held_mean"]["value"] > 0
    assert m["serve.traces_in_window"]["value"] == 0


def test_the_window_roofline_counts_what_the_work_requires():
    """A hand-counted traced span: one decode step of rows at 30,000 and 500
    (2,048 + 501 positions a window layer) and one chunk of 256 rows at
    4,096 (2,303 distinct positions, 256 x 2,048 pairs), 24 window layers."""
    reader = data.module("readers", "window_attn_roofline")
    work = {"positions": 2048 + 501 + 2303, "pairs": 2048 + 501 + 256 * 2048,
            "calls": 2, "layers": 24, "heads": 32, "kv_heads": 4,
            "d_head": 128, "cache_bytes": 2}
    assert reader.required_bytes(work) == 4852 * 2048 * 24
    assert reader.required_flops(work) == (2549 + 524288) * 32 * 128 * 4 * 24
    args = data.named("layer_metrics", "serve.window_attn_roofline")["args"]
    events = [("op:window_attention out_att.8 custom-call jit(f)/window_attention/out=att/pallas_call", 0, 1_000_000),
              ("op:paged_attention out_att.4 custom-call jit(f)/paged_attention/out=att/pallas_call", 0, 50_000)]
    run = {"trace": {"events": events}, "window_traced": work,
           "peaks": {"hbm_gbs": 819.0, "bf16_tflops": 197.0}}
    by_flops = (2549 + 524288) * 32 * 128 * 4 * 24 / 197e12
    assert by_flops > 4852 * 2048 * 24 / 819e9  # the chunk's products bound it
    assert reader.read(args, run) == pytest.approx(100 * by_flops / 1e-3)
    # nothing to read: an untraced run, no window ops, no window work
    assert reader.read(args, {"trace": {"events": events}, "peaks": run["peaks"]}) is None
    assert reader.read(args, dict(run, window_traced=dict(work, calls=0))) is None
    assert reader.read(args, dict(run, trace={"events": events[1:]})) is None


def test_probe_catches_a_wrong_weight_and_tells_the_stand_ins_apart(tree, on_cpu):
    """The comparison that decides `correct` can fail: after a window
    layer's value matrix is changed in the scope only (the compiled variants
    hold the old one), the logits disagree, and the probe says so. The probe
    itself goes through a prefix hit on the longest document into a slot a
    filler left, holding only its window's pages, and decodes among the
    other live rows; the chat in the highest live slot is held to the
    reference too; both stand-ins can take the system's place."""
    from chipbench.runners import serve_trinity

    _toy_cell(tree)
    cell = data.cell("toy_trinity_mixed")
    server, engine, model, _ = serve_trinity.build(cell, 5)
    docs, probe_doc, doc_picks = serve_trinity.prefill_documents(engine, cell, 5)
    assert [len(d) for d in docs] == [64, 80, 96] and probe_doc == 2
    assert doc_picks.shape == (4, 96, 2)
    system = serve_trinity._system_prompt(cell)
    problems = []
    ran = serve_trinity.probe_run(engine, model, 5, docs, probe_doc, system)
    notes = serve_trinity._probe(engine, model, 5, problems, docs, probe_doc,
                                 doc_picks, system, ran=ran)
    assert problems == [] and notes["prefix_hit_at"] == 96
    # five fillers in slots 0-4, the chat in 5; fillers 1 and 4 retired,
    # the probe in the slot retired last
    assert notes["live_slots"] == [0, 1, 2, 3, 5] and notes["slot"] == 1
    assert notes["other_row"]["slot"] == 5
    # after its tail's prefill the probe holds its last window's pages only
    assert notes["window_pages_first"] >= (96 + 300 - 64 - 32) // 16 - 1
    assert notes["tail_chunks"] >= 2 and notes["pick_rows"] == 4 * (96 + 300 + 8)
    for stand_in in serve_trinity.STAND_INS:
        low = serve_trinity._probe(engine, model, 5, [], docs, probe_doc,
                                   doc_picks, system, stand_in=stand_in, ran=ran)
        assert low["first-token"] > 0 and low["pick_rows"] == notes["pick_rows"]
    name = [n for n in model.param_names() if n.endswith("l4_v_w")][0]
    engine.scope.vars[name] = -engine.scope.vars[name]
    problems = []
    serve_trinity._probe(engine, model, 6, problems, docs, probe_doc,
                         doc_picks, system)
    assert any("logits differ" in p for p in problems), problems
    engine.scope.vars[name] = -engine.scope.vars[name]
    # a probe that finds no cached document says so
    engine.prefix_cache.clear()
    problems = []
    serve_trinity._probe(engine, model, 7, problems, docs, probe_doc,
                         doc_picks, system)
    assert any("prefix hit" in p for p in problems)
    server.stop(drain=False)
