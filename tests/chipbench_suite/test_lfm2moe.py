"""The lfm2_moe configuration in chipbench, on the CPU at toy width: the
benchmark's reference against the program's, the configuration file's cut,
the serve_blocks runner end to end through run.main() (untraced and traced),
the comparison that decides `correct` catching a wrong weight, the expert
layers' byte count, and the sweep for a cell of another runner."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import data, reference_lfm2_moe as bench_ref, trace as cb_trace  # noqa: E402
from test_chipbench import (  # noqa: E402,F401 — fixtures
    SYNTH_DEVICE, SYNTH_HOST, TOY_CHAT, check_result, on_cpu, run_main, tree)

TOY_LFM2 = {
    "name": "toy_lfm2", "source": "test", "runner": "serve_blocks",
    "served_name": "toylfm2", "sizes": {"vocab_size": 128},
    "model_type": "lfm2_moe", "vocab_size": 128, "hidden_size": 32,
    "intermediate_size": 48, "moe_intermediate_size": 24,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 4, "num_dense_layers": 1,
    "layer_types": ["conv", "full_attention", "conv", "conv"],
    "num_experts": 8, "num_experts_per_tok": 2, "use_expert_bias": True,
    "norm_topk_prob": True, "routed_scaling_factor": 1, "conv_L_cache": 3,
    "conv_bias": False, "norm_eps": 1e-05, "rope_theta": 1000000,
    "max_position_embeddings": 512,
    "engine": {"max_slots": 4, "max_context": 512, "page_size": 16,
               "prefill_buckets": [32]},
    "server": {"request_timeout_ms": 60000}, "reduced": [], "assumed": {},
}
TOY_CHAT_FAST = dict(TOY_CHAT, rate_per_s=4.0)
SERVE_E2E = {m["name"] for m in data.cell_metrics(
    data.manifest(), "lfm2moe_chat", "end_to_end")}


def _toy_cell(tree):
    tree.add("configs", "toy_lfm2", TOY_LFM2)
    tree.add("traffic", "toy_chat", TOY_CHAT_FAST)
    tree.add_cell("toy_lfm2_chat", "toy_lfm2", "toy_chat", like="lfm2moe_chat")


def test_the_two_references_agree():
    """chipbench's copy and the program's reference: the same logits, picks
    and gaps on seeded weights, own picks and followed ones, both precisions."""
    from paddle_tpu.executor import Scope
    from paddle_tpu.models import lfm2_moe_reference as prog_ref
    from paddle_tpu.models.block_decoder import lfm2_moe

    model = lfm2_moe(TOY_LFM2, max_context=64, dtype="float32")
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


def test_configuration_file_is_the_published_model_cut_in_depth_only():
    c = data.named("configs", "lfm2_8b_a1b")
    assert c["reduced"] == ["num_hidden_layers", "layer_types", "num_dense_layers"]
    pub = c["published"]
    assert (pub["num_hidden_layers"], pub["num_dense_layers"]) == (24, 2)
    assert c["layer_types"] == pub["layer_types"][1:14]
    assert c["layer_types"] == ["conv"] + ["full_attention", "conv", "conv", "conv"] * 3
    assert (c["num_hidden_layers"], c["num_dense_layers"]) == (13, 1)
    widths = {"hidden_size": 2048, "intermediate_size": 7168,
              "moe_intermediate_size": 1792, "num_attention_heads": 32,
              "num_key_value_heads": 8, "num_experts": 32,
              "num_experts_per_tok": 4, "vocab_size": 65536, "conv_L_cache": 3}
    assert {k: c[k] for k in widths} == widths
    entry = [e for e in data.manifest()["configs"] if e["name"] == "lfm2_8b_a1b"][0]
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    for key in ("assumed", "deployment", "engine_why"):
        assert c[key]
    from chipbench.runners import serve_blocks

    model = serve_blocks.model_of(c)
    n = lambda shape: int(np.prod(shape))
    experts = 12 * 32 * 3 * 2048 * 1792
    assert model.blocks.count(("conv", "moe")) == 9
    assert model.blocks.count(("attention", "moe")) == 3
    assert model.blocks[0] == ("conv", "dense")
    assert experts == 12 * 352321536 and n([65536, 2048]) == 134217728


def test_serve_blocks_runner_toy_cell(tree, on_cpu):
    _toy_cell(tree)
    rc, doc = run_main(["--workload", "toy_lfm2_chat", "--seed", "2147483659",
                        "--seconds", "2", "--trace", "0"])
    assert rc == 0
    check_result(doc, SERVE_E2E, traced=False)
    assert set(doc["metrics"]) == SERVE_E2E
    assert doc["attempted"] == 8  # rate 4/s x 2 s, whatever the seed


@pytest.fixture
def synthetic_moe_trace(monkeypatch):
    """As test_chipbench.synthetic_trace, with the expert layers' operations
    among the device's: the ragged products under XLA's own names."""
    from chipbench.runners import serve

    device = {0: SYNTH_DEVICE[0] + [
        ("op:moe_router fusion.7 fusion jit(f)/moe_router/out=picks/top_k", 400, 10),
        ("hlo:ragged-dot-none ragged-dot-none.3 custom-call ragged-dot-none", 410, 50),
        ("op:moe_experts fusion.9 fusion jit(f)/moe_experts/out=y/scatter", 460, 20),
        ("op:short_conv fusion.11 fusion jit(f)/short_conv/out=c/mul", 480, 20),
    ]}
    monkeypatch.setattr(serve, "TRACED_SECONDS", 0.6)
    monkeypatch.setattr(serve, "TRACED_TRAFFIC_SECONDS", 1.6)
    monkeypatch.setattr(
        cb_trace, "reduce_dir",
        lambda log_dir, hlo_texts, chips: (
            os.listdir(log_dir),
            cb_trace.reduce_events(device, SYNTH_HOST, chips))[1])


def test_serve_blocks_runner_traced(tree, on_cpu, synthetic_moe_trace):
    """Every per-layer metric the cell lists is read: the accepted serve.*
    ones from the same engine, and the five this configuration brings."""
    _toy_cell(tree)
    rc, doc = run_main(["--workload", "toy_lfm2_chat", "--seed", "7",
                        "--seconds", "2", "--trace", "1"])
    assert rc == 0
    names = {m["name"] for m in data.cell_metrics(
        data.manifest(), "toy_lfm2_chat", "per_layer")}
    check_result(doc, names, traced=True)
    missing = names - set(doc["metrics"])
    # the program's spans come from the run's own profile, which the CPU's
    # trace holds too; nothing else may be missing
    assert missing <= {n for n in names if ".idle_" in n}, missing
    m = doc["metrics"]
    assert m["serve.moe_time_share"]["value"] == pytest.approx(100.0 * 80 / 350)
    assert m["serve.short_conv_time_share"]["value"] == pytest.approx(100.0 * 20 / 350)
    assert 0 < m["serve.moe_experts_touched_mean"]["value"] <= 3 * 8
    assert m["serve.moe_roofline"]["value"] > 0
    assert m["serve.state_restore_mean_ms"]["value"] > 0
    assert m["serve.prefix_hit_share"]["value"] > 0
    assert m["serve.traces_in_window"]["value"] == 0


def test_moe_roofline_counts_the_bytes_the_routing_requires():
    reader = data.module("readers", "moe_roofline")
    work = {"experts_touched": 240, "calls": 12, "hidden_size": 2048,
            "moe_intermediate_size": 1792, "num_experts": 32, "weight_bytes": 2}
    expert = 3 * 2048 * 1792 * 2
    assert expert == 22020096
    assert reader.required_bytes(work) == 240 * expert + 12 * 2049 * 32 * 4
    args = data.named("layer_metrics", "serve.moe_roofline")["args"]
    events = [("hlo:ragged-dot-none ragged-dot-none.3 custom-call ragged-dot-none", 0, 8_000_000),
              ("op:moe_experts fusion.9 fusion jit(f)/moe_experts/out=y/scatter", 0, 1_000_000),
              ("op:mul fusion.2 fusion jit(f)/mul/out=fc_0.tmp_0/dot_general", 0, 5_000_000)]
    run = {"trace": {"events": events}, "moe_traced": work,
           "peaks": {"hbm_gbs": 819.0}}
    want = 100.0 * reader.required_bytes(work) / (9e-3 * 819e9)
    assert reader.read(args, run) == pytest.approx(want)
    assert 0 < want < 100
    # nothing to read: an untraced run, the parent's program
    assert reader.read(args, {"trace": {"events": events}, "peaks": run["peaks"]}) is None
    assert reader.read(args, dict(run, moe_traced=dict(work, calls=0))) is None
    assert reader.read(args, {}) is None


def test_probe_catches_a_weight_the_engine_does_not_serve(tree, on_cpu):
    """The comparison that decides `correct` can fail: after a router bias
    is changed in the scope only (the compiled variants hold the old one),
    the reference and the engine disagree, and the probe says so."""
    import jax.numpy as jnp

    from chipbench.runners import serve_blocks

    _toy_cell(tree)
    cell = data.cell("toy_lfm2_chat")
    server, engine, model, _ = serve_blocks.build(cell, 5)
    problems = []
    notes = serve_blocks._probe(engine, model, 5, problems)
    assert problems == [] and notes["prefix_hit_at"] == serve_blocks.SHARED
    assert notes["states_hit"] == 1
    name = [n for n in model.param_names() if n.endswith("router_b")][0]
    engine.scope.vars[name] = jnp.flip(engine.scope.vars[name]) + 0.3
    problems = []
    serve_blocks._probe(engine, model, 6, problems)
    assert any("router pick" in p or "logits differ" in p for p in problems)


def test_sweep_runner_builds_through_the_cells_own_runner(tree, on_cpu, capsys):
    from chipbench import sweep_runner

    _toy_cell(tree)
    assert sweep_runner.main(["--workload", "toy_lfm2_chat", "--rates", "2,4",
                              "--seconds", "1.5"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 3 and all(l.startswith("sweep: ") for l in lines)
    assert "rate=2.00" in lines[0] and "highest sustained rate" in lines[2]
