"""The granitemoehybrid configuration in chipbench, on the CPU at toy width:
the configuration file against the catalog's entry, the benchmark's
reference against the program's, the burst generator, the serve_granite4h
runner end to end through run.main() (untraced and traced), the state-space
roofline's byte and FLOP counts, and the comparison that decides `correct`
catching a wrong weight and telling the two lower precisions apart."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import data, reference_granite4h as bench_ref, trace as cb_trace  # noqa: E402
from test_chipbench import (  # noqa: E402,F401 — fixtures
    SYNTH_DEVICE, SYNTH_HOST, check_result, on_cpu, run_main, tree)

TOY_GRANITE = {
    "name": "toy_granite4h", "source": "test", "runner": "serve_granite4h",
    "served_name": "toygranite", "sizes": {"vocab_size": 128},
    "model_type": "granitemoehybrid", "vocab_size": 128, "hidden_size": 64,
    "intermediate_size": 96, "shared_intermediate_size": 96,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "mamba_n_heads": 8, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "attention_bias": False,
    "attention_multiplier": 0.0625, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 8, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "position_embedding_type": "nope",
    "num_local_experts": 0, "num_experts_per_tok": 0,
    "tie_word_embeddings": True, "max_position_embeddings": 2048,
    "engine": {"max_slots": 4, "max_context": 1024, "page_size": 16,
               "prefill_chunk": 256, "prefill_buckets": [32, 64, 128, 256],
               "snapshot_bytes": 4 * 3 * (8 * 16 * 16 * 4 + 3 * 160 * 2)},
    "server": {"request_timeout_ms": 60000}, "reduced": [], "assumed": {},
}
TOY_BURSTS = {
    "generator": "open_loop_bursts", "rate_per_s": 4.0, "order_seed": 3,
    "ramp_seconds": 1, "burst_share": 0.25, "burst_every_s": 1,
    "burst_first_s": 0.5, "burst_within_s": 0.1, "system_tokens": 32,
    "user_tokens": {"median": 12, "sigma": 0.8, "min": 4, "max": 40},
    "output_tokens": {"median": 6, "sigma": 0.6, "min": 2, "max": 12},
}
CELL = "granite4h_burst_chat"
SERVE_E2E = {m["name"] for m in data.cell_metrics(
    data.manifest(), CELL, "end_to_end")}


def _toy_cell(tree, max_slots=4):
    tree.add("configs", "toy_granite4h", dict(
        TOY_GRANITE, engine=dict(TOY_GRANITE["engine"], max_slots=max_slots)))
    tree.add("traffic", "toy_bursts", TOY_BURSTS)
    tree.add_cell("toy_granite_bursts", "toy_granite4h", "toy_bursts", like=CELL)


def test_configuration_file_is_the_catalog_s_config_key_for_key():
    c = data.named("configs", "granite_4_0_h_micro")
    assert c["reduced"] == []
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = [json.loads(l) for l in open(catalog)
               if '"granite-4.0-h-micro"' in l][0]
        assert c["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert c[key] == value, key
    widths = {"hidden_size": 2048, "shared_intermediate_size": 8192,
              "mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128,
              "mamba_d_conv": 4, "num_attention_heads": 32,
              "num_key_value_heads": 8, "num_hidden_layers": 40,
              "vocab_size": 100352}
    assert {k: c[k] for k in widths} == widths
    assert c["layer_types"].count("mamba") == 36
    assert [i for i, t in enumerate(c["layer_types"]) if t == "attention"] == [
        5, 15, 25, 35]
    entry = [e for e in data.manifest()["configs"]
             if e["name"] == "granite_4_0_h_micro"][0]
    assert entry["reduced"] == [] and entry["source"] == c["source"]
    for key in ("assumed", "deployment", "engine_why", "precision"):
        assert c[key]
    from chipbench.runners import serve_granite4h

    model = serve_granite4h.model_of(c)
    assert model.blocks == [(t, "dense") for t in c["layer_types"]]
    assert (model.position, model.qk_norm, model.tied_head) == ("none", False, True)
    assert (model.attn_scale, model.embed_scale, model.residual_scale,
            model.logit_scale) == (1 / 64, 12.0, 0.22, 8.0)
    assert model.ssm_widths() == (4096, 4352)
    # a slot's state: 36 x (float32 recurrent state + three conv rows)
    state = [s for s in model.cache_specs() if s["kind"] == "state"]
    row = sum(s["width"] * (4 if s["dtype"] == "float32" else 2) for s in state)
    assert row == 36 * (2097152 + 26112) == 76437504
    e = c["engine"]
    assert e["snapshot_bytes"] == 8 * row
    assert (e["max_slots"], e["max_context"], e["prefill_chunk"],
            e["page_size"]) == (48, 2048, 256, 16)
    # parameters: ISSUE 33's arithmetic, from the widths
    d = 2048
    ff = d * 16384 + 8192 * d
    mamba = d * 8512 + 4096 * d + 4352 * 4 + 4352 + 3 * 64 + 4096 + 2 * d + ff
    attention = d * (2048 + 512 + 512) + 2048 * d + 2 * d + ff
    total = 36 * mamba + 4 * attention + 100352 * d + d
    assert round(total / 1e7) == 319  # 3.19 G parameters, 6.38 GB in bfloat16


def test_the_two_references_agree():
    """chipbench's copy and the program's reference: the same text below the
    first paragraph, and the same logits on seeded weights, every
    precision."""
    from paddle_tpu.executor import Scope
    from paddle_tpu.models import granite4h_reference as prog_ref
    from chipbench.runners import serve_granite4h

    below = lambda mod: open(mod.__file__).read().split("whole sequence", 1)[1]
    assert below(prog_ref) == below(bench_ref) and "def forward(" in below(bench_ref)
    model = serve_granite4h.model_of(TOY_GRANITE)
    assert model.dtype == "bfloat16" and model.ssm["heads"] == 8
    scope = Scope(seed=11)
    model.ensure_params(scope)
    weights = {n: scope.vars[n] for n in model.param_names()}
    tokens = [int(v) for v in np.random.default_rng(0).integers(2, 128, 23)]
    spec = prog_ref.spec_of(model)
    assert spec == bench_ref.spec_of(model)
    for precision in bench_ref.PRECISIONS:
        a = prog_ref.forward(weights, tokens, spec, rows=[5, 22], precision=precision)
        b = bench_ref.forward(weights, tokens, spec, rows=[5, 22], precision=precision)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.asarray(a).shape == (2, 128)
    whole = np.asarray(bench_ref.forward(weights, tokens, spec))
    np.testing.assert_allclose(whole[[5, 22]], np.asarray(a), atol=0.05)


def test_burst_generator_replays_one_schedule_whatever_the_seed():
    gen = data.module("traffic", "open_loop_bursts")
    tr = data.named("traffic", "chat_burst_replay")
    assert (tr["burst_share"], tr["burst_every_s"], tr["burst_first_s"],
            tr["order_seed"], tr["ramp_seconds"], tr["system_tokens"]) == (
        0.25, 5, 2.5, 33, 15, 256)
    assert tr["burst_within_s"] in (0.25, 1.0)
    phases = [("ramp", 15), ("window", 50)]
    a = gen.schedule(tr, 5, phases, 100352)
    b = gen.schedule(tr, 2 ** 31 + 11, phases, 100352)
    window = [r for r in a if r["phase"] == "window"]
    assert len(window) == round(tr["rate_per_s"] * 50)
    for ra, rb in zip(a, b):  # the schedule is the mix's, the tokens the seed's
        assert (ra["due_s"], ra["max_new_tokens"], ra["burst"], len(ra["prompt"])) == (
            rb["due_s"], rb["max_new_tokens"], rb["burst"], len(rb["prompt"]))
        assert ra["prompt"][:256] == rb["prompt"][:256] == a[0]["prompt"][:256]
        assert 16 <= len(ra["prompt"]) - 256 <= 576 and 32 <= ra["max_new_tokens"] <= 512
    assert a[0]["prompt"][256:] != b[0]["prompt"][256:]
    # a quarter of the window's requests, in ten bursts at 17.5, 22.5, ...,
    # each within burst_within_s of its start
    burst = [r for r in window if r["burst"]]
    assert len(burst) == round(0.25 * len(window))
    starts = [15 + 2.5 + 5 * k for k in range(10)]
    assert gen.burst_starts(tr, 50) == [s - 15 for s in starts]
    by_start = {s: [r for r in burst if s <= r["due_s"] < s + tr["burst_within_s"]]
                for s in starts}
    assert sum(len(v) for v in by_start.values()) == len(burst)
    sizes = [len(v) for v in by_start.values()]
    assert max(sizes) - min(sizes) <= 1 and sizes == sorted(sizes, reverse=True)
    steady = [r["due_s"] for r in window if not r["burst"]]
    assert 15 <= min(steady) and max(steady) < 65
    assert [r["due_s"] for r in a] == sorted(r["due_s"] for r in a)


def test_serve_granite4h_runner_toy_cell(tree, on_cpu):
    _toy_cell(tree)
    rc, doc = run_main(["--workload", "toy_granite_bursts", "--seed",
                        "2147483659", "--seconds", "2", "--trace", "0"])
    assert rc == 0
    check_result(doc, SERVE_E2E, traced=False)
    assert set(doc["metrics"]) == SERVE_E2E
    assert doc["attempted"] == 8  # rate 4/s x 2 s, whatever the seed


@pytest.fixture
def synthetic_ssm_trace(monkeypatch):
    """As test_chipbench.synthetic_trace, with this configuration's
    operations among the device's."""
    from chipbench.runners import serve

    device = {0: SYNTH_DEVICE[0] + [
        ("op:short_conv fusion.11 fusion jit(f)/short_conv/out=c/mul", 400, 20),
        ("op:ssm_scan out_y.4 custom-call jit(f)/ssm_scan/out=y/pallas_call", 420, 90),
        ("op:ssm_scan fusion.13 fusion jit(f)/ssm_scan/out=y/mul", 510, 10),
    ]}
    monkeypatch.setattr(serve, "TRACED_SECONDS", 0.6)
    monkeypatch.setattr(serve, "TRACED_TRAFFIC_SECONDS", 1.6)
    monkeypatch.setattr(
        cb_trace, "reduce_dir",
        lambda log_dir, hlo_texts, chips: (
            os.listdir(log_dir),
            cb_trace.reduce_events(device, SYNTH_HOST, chips))[1])


def test_serve_granite4h_runner_traced(tree, on_cpu, synthetic_ssm_trace):
    """Every per-layer metric the cell lists is read: the accepted serve.*
    ones from the same engine, and the three this configuration brings."""
    _toy_cell(tree)
    rc, doc = run_main(["--workload", "toy_granite_bursts", "--seed", "7",
                        "--seconds", "2", "--trace", "1"])
    assert rc == 0
    names = {m["name"] for m in data.cell_metrics(
        data.manifest(), "toy_granite_bursts", "per_layer")}
    check_result(doc, names, traced=True)
    missing = names - set(doc["metrics"])
    assert missing <= {n for n in names if ".idle_" in n}, missing
    m = doc["metrics"]
    busy = 250 + 120.0
    assert m["serve.ssm_time_share"]["value"] == pytest.approx(100 * 100 / busy)
    assert m["serve.short_conv_time_share"]["value"] == pytest.approx(100 * 20 / busy)
    assert m["serve.paged_attn_time_share"]["value"] == pytest.approx(100 * 60 / busy)
    assert m["serve.ssm_roofline"]["value"] > 0
    # live rows x 2 x three layers' 8 x 16 x 16 float32 entries
    per_row = 2 * 3 * 8 * 16 * 16 * 4
    assert m["serve.ssm_state_bytes_mean"]["value"] % per_row == 0
    assert per_row <= m["serve.ssm_state_bytes_mean"]["value"] <= 4 * per_row
    assert m["serve.state_restore_mean_ms"]["value"] > 0
    assert m["serve.prefix_hit_share"]["value"] > 40
    assert m["serve.traces_in_window"]["value"] == 0
    assert {"serve.moe_roofline", "serve.latent_attn_roofline"}.isdisjoint(names)


def test_ssm_roofline_counts_what_the_recurrence_requires():
    """A hand-counted case at the published widths: ten decode steps of 30
    live rows and two prefill chunks of 256 and 44 positions. A kernel that
    moves three times the bytes takes three times the time and reads a
    third, not more."""
    reader = data.module("readers", "ssm_roofline")
    row = 36 * 64 * 64 * 128 * 4  # a slot's recurrent bytes, all layers
    work = {"step_state_bytes": 10 * 30 * 2 * row, "step_rows": 300,
            "steps": 10, "chunks": 2, "chunk_tokens": 300, "layers": 36,
            "state_values": 64 * 64 * 128, "state_bytes": row}
    assert reader.required_bytes(work) == (300 + 2) * 2 * 75497472
    assert reader.required_flops(work) == 600 * 36 * 524288 * 5
    args = data.named("layer_metrics", "serve.ssm_roofline")["args"]
    by_bytes = reader.required_bytes(work) / 819e9
    assert by_bytes > reader.required_flops(work) / 197e12  # memory-bound
    events = lambda kernel_ns: [
        ("op:ssm_scan out_y.4 custom-call jit(f)/ssm_scan/out=y/pallas_call", 0, kernel_ns),
        ("op:ssm_scan fusion.3 fusion jit(f)/ssm_scan/out=y/mul", 0, 1_000_000),
        ("op:short_conv fusion.2 fusion jit(f)/short_conv/out=c/mul", 0, 9_000_000)]
    run = {"trace": {"events": events(69_000_000)}, "ssm_traced": work,
           "peaks": {"hbm_gbs": 819.0, "bf16_tflops": 197.0}}
    assert reader.read(args, run) == pytest.approx(100 * by_bytes / 70e-3)
    assert 75 < reader.read(args, run) < 100
    thrice = dict(run, trace={"events": events(3 * 70_000_000 - 1_000_000)})
    assert reader.read(args, thrice) == pytest.approx(reader.read(args, run) / 3)
    # nothing to read: an untraced run, the parent's program
    assert reader.read(args, {"trace": run["trace"], "peaks": run["peaks"]}) is None
    idle = dict(work, steps=0, chunks=0)
    assert reader.read(args, dict(run, ssm_traced=idle)) is None
    assert reader.read(args, dict(run, trace={"events": events(1)[2:]})) is None
    assert reader.read(args, {}) is None


def test_probe_catches_a_wrong_weight_and_tells_the_lower_precisions_apart(
        tree, on_cpu, monkeypatch):
    """The comparison that decides `correct` can fail: after a state-space
    layer's rates are changed in the scope only (the compiled variants hold
    the old ones), the reference and the engine disagree, and the probe says
    so. The probe goes through a prefix hit that restores the state, and
    each lower precision of the reference can stand in the system's
    place. Its decode steps run nine rows in slots with idle ones among
    them, and a second row is held to the reference too: a state that ends
    up in another row's slot is caught."""
    import jax.numpy as jnp

    from chipbench.runners import serve_granite4h as runner

    _toy_cell(tree, max_slots=12)
    cell = data.cell("toy_granite_bursts")
    server, engine, model, _ = runner.build(cell, 5)
    assert engine.stats()["kv"]["recurrent_row_bytes"] == 3 * 8 * 16 * 16 * 4
    problems = []
    notes = runner._probe(engine, model, 5, problems,
                          stand_ins=("state_bfloat16", "dt_bfloat16"))
    assert problems == [], problems
    assert (notes["states_hit"], notes["pages_hit"], notes["tail_chunks"]) == (1, 16, 2)
    # the cold prompt, the one that cut, six of nine fillers, the probe in a
    # slot a filler left: idle slots among the live ones and behind them
    assert notes["live_slots"] == [0, 1, 2, 3, 4, 5, 7, 8, 10]
    assert (notes["slot"], notes["other_row"]["slot"]) == (3, 10)
    assert 0 < notes["other_row"]["state"] <= runner.STATE_RTOL
    assert engine.pool.stats()["slots_in_use"] == 0
    # one snapshot: the cold prompt left none, the second one at 256 tokens
    assert notes["states_held"] == 1
    low = notes["stand_in"]
    assert set(low) == {"state_bfloat16", "dt_bfloat16"}
    # each lower precision moves the first state-space layer's state and the
    # logits, each its own way
    for key in ("state", "decode-step-32"):
        assert low["state_bfloat16"][key] > 0 and low["dt_bfloat16"][key] > 0
        assert low["state_bfloat16"][key] != low["dt_bfloat16"][key]
    assert low["state_bfloat16"]["state"] > notes["state"]
    # a limit between a reading and a stand-in's tells the two apart (the
    # toy's logits are a quarter of the cell's: its limits are its own)
    with monkeypatch.context() as mp:
        mp.setattr(runner, "STATE_RTOL", 0.5 * (
            notes["state"] + low["state_bfloat16"]["state"]))
        problems = []
        said = runner._probe(engine, model, 8, problems,
                             stand_ins=("state_bfloat16",))
        assert problems == [], problems
        assert any("state (head" in p for p in
                   said["stand_in"]["state_bfloat16"]["not_correct"])
        # two live rows' states in each other's slots before the first step
        step, swapped = engine.decode_step, []

        def swap_then_step(runs):
            if not swapped:
                a, b = runs[-1].slot, max(r.slot for r in runs[:-1])
                snaps = [engine._snapshot_state(s) for s in (a, b)]
                engine._restore_state(a, snaps[1])
                engine._restore_state(b, snaps[0])
                swapped.append((a, b))
            return step(runs)

        mp.setattr(engine, "decode_step", swap_then_step)
        problems = []
        runner._probe(engine, model, 9, problems)
        assert len(swapped) == 1
        assert any(p.startswith("the probe: the first state-space") for p in problems)
        assert any(p.startswith("the row in slot %d beside" % swapped[0][1])
                   for p in problems)
        mp.setattr(engine, "decode_step", step)
        name = [n for n in model.param_names() if n.endswith("ssm_a_log")][0]
        engine.scope.vars[name] = jnp.flip(engine.scope.vars[name]) + 0.5
        problems = []
        runner._probe(engine, model, 6, problems)
        assert any("state (head" in p for p in problems)
    # a probe that finds no snapshot to restore says so
    monkeypatch.setattr(engine.prefix_cache, "snapshot_bytes", 0)
    problems = []
    runner._probe(engine, model, 7, problems)
    assert any("prefix hit" in p for p in problems)
