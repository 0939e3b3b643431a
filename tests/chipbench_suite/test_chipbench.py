"""chipbench on the CPU at toy width: both runners end to end through
run.main() (the TPU check replaced here, in the test), the mesh branch of the
train runner on four virtual devices, the result line's keys, the load
generator's schedule, the trace reduction on a synthetic event list, and the
refusal to run without a TPU. Cells are added the way README.md says: new
data files and manifest entries in a copy of the benchmark's tree."""

import io
import json
import os
import shutil
import sys
from contextlib import redirect_stdout

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import data, run as cb_run, trace as cb_trace  # noqa: E402

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}

TOY_TRAIN_CONFIG = {
    "name": "toy_encdec", "source": "test", "runner": "train",
    "program": "transformer_encdec",
    "sizes": {"n_layer": 1, "d_model": 32, "d_inner": 64, "n_head": 2,
              "d_key": 16, "d_value": 16, "vocab_size": 64,
              "label_smoothing": 0.1},
    "optimizer": {"kind": "adam", "learning_rate": 1e-3,
                  "moment_dtype": "bfloat16"},
    "pass_pipeline": "training_fused", "reduced": [], "assumed": {},
}
TOY_RING = {"generator": "staged_ring", "batch": 4, "positions": 16,
            "ring": 2, "in_flight": 2}


@pytest.fixture
def tree(tmp_path, monkeypatch):
    """A copy of the benchmark's data files that a test may add to, with the
    harness pointed at it; code (runners, readers, generators) stays where
    it is."""
    here = tmp_path / "chipbench"
    for d in ("configs", "workloads", "traffic", "layer_metrics", "end_to_end"):
        shutil.copytree(os.path.join(data.HERE, d), here / d,
                        ignore=shutil.ignore_patterns("*.py", "__pycache__"))
    shutil.copy(os.path.join(data.HERE, "peaks.json"), here / "peaks.json")
    man = data.manifest()
    monkeypatch.setattr(data, "HERE", str(here))
    monkeypatch.setattr(data, "ROOT", str(tmp_path))
    monkeypatch.setattr(data, "SCRATCH", str(tmp_path / ".chipbench_cache"))

    def add(kind, name, doc):
        (here / kind / (name + ".json")).write_text(json.dumps(doc))

    def add_cell(name, config, traffic, like, **extra):
        """A manifest entry for a new cell reporting the metrics of `like`."""
        add("workloads", name, dict(
            {"config": config, "traffic": traffic, "chips": 1,
             "who": "test", "why": "test"}, **extra))
        man["workloads"].append({"name": name, "config": config,
                                 "traffic": traffic, "chips": 1, "why": "test"})
        for group in ("end_to_end", "per_layer"):
            for m in man[group]:
                if like in m.get("workloads", ()):
                    m["workloads"].append(name)
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))

    return type("Tree", (), {"add": staticmethod(add),
                             "add_cell": staticmethod(add_cell)})


@pytest.fixture
def on_cpu(tree, monkeypatch):
    """The TPU check, injected off: the test's own devices instead."""
    import jax

    monkeypatch.setattr(cb_run, "devices_or_exit", lambda chips: jax.devices())
    # toy CPU programs must not fill the checkout's compile cache
    monkeypatch.setattr(cb_run, "place_caches", lambda: None)
    peaks = data.load_json("peaks.json")
    kind = str(jax.devices()[0].device_kind)
    peaks["peaks"][kind] = peaks["peaks"]["TPU v5 lite"]
    with open(os.path.join(data.HERE, "peaks.json"), "w") as f:
        json.dump(peaks, f)


def run_main(argv):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cb_run.main(argv)
    lines = buf.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)


def check_result(doc, metric_names, traced):
    assert set(doc) == RESULT_KEYS | ({"breakdown"} if traced else set())
    assert set(doc["device"]) == DEVICE_KEYS | (
        {"busy_s", "window_s"} if traced else set())
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["attempted"] > 0
    assert set(doc["metrics"]) <= set(metric_names)
    for m in doc["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], (int, float))


def test_train_runner_toy_cell(tree, on_cpu):
    tree.add("configs", "toy_encdec", TOY_TRAIN_CONFIG)
    tree.add("traffic", "toy_ring", TOY_RING)
    tree.add_cell("toy_train", "toy_encdec", "toy_ring", like="tbig_train_1chip")
    rc, doc = run_main(["--workload", "toy_train", "--seed", "2147483659",
                        "--seconds", "1", "--trace", "0"])
    assert rc == 0
    check_result(doc, {"train_tokens_per_s", "setup_s"}, traced=False)
    assert set(doc["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert doc["metrics"]["train_tokens_per_s"]["value"] > 0


TOY_SERVE_CONFIG = {
    "name": "toy_gpt", "source": "test", "runner": "serve",
    "served_name": "toygpt",
    "sizes": {"n_layer": 2, "n_head": 2, "n_embd": 32, "n_inner": 64,
              "vocab_size": 128, "n_positions": 128},
    "engine": {"max_slots": 4}, "server": {"request_timeout_ms": 60000},
    "reduced": [], "assumed": {},
}
TOY_CHAT = {
    "generator": "open_loop_chat", "rate_per_s": 5.0, "order_seed": 1,
    "ramp_seconds": 1,
    "system_tokens": 16,
    "user_tokens": {"median": 12, "sigma": 0.8, "min": 4, "max": 40},
    "output_tokens": {"median": 6, "sigma": 0.6, "min": 2, "max": 12},
}
SERVE_E2E = {m["name"] for m in data.cell_metrics(
    data.manifest(), "gpt2l_chat", "end_to_end")}


def test_serve_runner_toy_cell(tree, on_cpu):
    tree.add("configs", "toy_gpt", TOY_SERVE_CONFIG)
    tree.add("traffic", "toy_chat", TOY_CHAT)
    tree.add_cell("toy_chat_cell", "toy_gpt", "toy_chat", like="gpt2l_chat")
    rc, doc = run_main(["--workload", "toy_chat_cell", "--seed", "3",
                        "--seconds", "2", "--trace", "0"])
    assert rc == 0
    check_result(doc, SERVE_E2E, traced=False)
    assert set(doc["metrics"]) == SERVE_E2E
    assert doc["attempted"] == 10  # rate 5/s x 2 s, whatever the seed


def test_sweep_prints_a_table_and_no_result_line(tree, on_cpu, capsys):
    from chipbench import sweep

    tree.add("configs", "toy_gpt", TOY_SERVE_CONFIG)
    tree.add("traffic", "toy_chat", TOY_CHAT)
    tree.add_cell("toy_chat_cell", "toy_gpt", "toy_chat", like="gpt2l_chat")
    assert sweep.main(["--workload", "toy_chat_cell", "--rates", "2,4",
                       "--seconds", "1.5"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 3 and all(l.startswith("sweep: ") for l in lines)
    assert "rate=2.00" in lines[0] and "rate=4.00" in lines[1]
    assert "highest sustained rate" in lines[2]


def test_train_check_catches_a_wrong_update_and_a_wrong_loss():
    """The comparison that decides `correct` for a train cell, on made-up
    parameters: an update that points elsewhere and a loss that is off are
    both named; the same update with rounding noise passes."""
    import numpy as np

    from chipbench.runners import train

    rng = np.random.default_rng(0)
    start = {"w": rng.normal(size=(8, 8)).astype("float32"),
             "b": np.zeros(8, "float32"), "unused": np.ones(3, "float32")}
    d = {"w": rng.normal(size=(8, 8)).astype("float32") * 1e-3,
         "b": rng.normal(size=8).astype("float32") * 1e-3,
         "unused": np.zeros(3, "float32")}
    ref = {"losses": [10.4375, 10.375], "start": start,
           "end": {n: start[n] + d[n] for n in start}}
    good = {"problems": [], "notes": {}}
    noisy = {n: start[n] + d[n] * (1 + 0.05 * rng.normal(size=d[n].shape)).astype("float32")
             for n in start}
    train._compare(good, [10.4375, 10.4375], noisy, ref)
    assert good["problems"] == []
    assert good["notes"]["update_cosines"]["parameters"] == 2  # not 'unused'
    assert good["notes"]["update_cosines"]["least"][0][1] > 0.99
    bad = {"problems": [], "notes": {}}
    wrong = dict(noisy, w=start["w"] + rng.normal(size=(8, 8)).astype("float32") * 1e-3)
    train._compare(bad, [10.4375, 9.0], wrong, ref)
    assert "loss of step 1" in bad["problems"][0]
    assert "median cosine" in bad["problems"][1]
    assert "% of the parameters' updates" in bad["problems"][2]
    against = {"problems": [], "notes": {}}
    train._compare(against, [10.4375, 10.375],
                   dict(noisy, b=start["b"] - d["b"]), ref)
    assert any("points against" in p and "parameter b" in p
               for p in against["problems"])


def test_memory_plan_fallback_is_loud_and_spans_skip_missing_names(capsys):
    from chipbench.runners import serve, train

    notes = {}
    assert train._planned_bytes(object(), notes) is None
    assert "allocator" in notes["memory_peak_source"]
    assert "memory_peak_bytes is the allocator" in capsys.readouterr().err

    class Engine:  # a later version with one of the entry points renamed
        def decode_step(self, runs):
            return len(runs)

    e = Engine()
    assert serve._annotate(e) == ["decode_step"]
    assert e.decode_step([1, 2]) == 2
    assert serve._variant_hlo(e, notes) == [] and "trace_names" in notes


# ------------------------------------------------------------ traced runs

SYNTH_DEVICE = {0: [
    ("op:flash_attention out_ctx_0.1 custom-call jit(f)/flash_attention/out=ctx_0/pallas_call", 0, 100),
    ("op:mul fusion.2 fusion jit(f)/mul/out=fc_0.tmp_0/dot_general", 50, 100),
    ("op:paged_attention out_att.4 custom-call jit(f)/paged_attention/out=att/pallas_call", 300, 60),
    ("pallas:multi_adam out_p.9 custom-call jit(f)/pallas_kernel=multi_adam.0/adam", 360, 40),
]}
SYNTH_HOST = [
    ("chipbench.wait_loss", 140, 170),  # holds the gap's midpoint, 225
    ("chipbench.dispatch", 210, 30),    # and so does this, shorter: innermost
    ("chipbench.traced", 0, 1000),
]


def test_trace_reduction_on_a_synthetic_event_list():
    merged = cb_trace.union([(5, 10), (0, 6), (20, 30), (30, 31)])
    assert merged == [[0, 10], [20, 31]]
    red = cb_trace.reduce_events(SYNTH_DEVICE, SYNTH_HOST, chips=1)
    assert red["busy_s"] == pytest.approx(250e-9)
    assert red["window_s"] == pytest.approx(400e-9)
    assert red["breakdown"]["idle_gaps"] == [["chipbench.dispatch", pytest.approx(150e-9)]]
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["op:flash_attention"] == pytest.approx(100e-9)
    assert len(red["breakdown"]["device_ops"]) <= 10
    flash = data.named("layer_metrics", "train.flash_roofline")["args"]["pattern"]
    secs, n = cb_trace.pattern_seconds(red["events"], flash)
    assert (n, secs) == (1, pytest.approx(100e-9))
    assert cb_trace.pattern_seconds(red["events"], "no_such_kernel") == (0, 0)
    # the readers on it
    run = {"trace": dict(red, steps=2), "peaks": {"bf16_tflops": 197.0},
           "window": {"chips": 1, "attention_flops_per_step": 1e6}}
    idle = data.module("readers", "trace_idle").read({}, run)
    assert idle == pytest.approx(37.5)
    share = data.module("readers", "trace_op_time").read(
        data.named("layer_metrics", "serve.paged_attn_time_share")["args"], run)
    assert share == pytest.approx(100.0 * 60 / 250)
    roof = data.module("readers", "trace_op_time").read(
        {"pattern": "^op:flash_attention", "flops": "attention_flops_per_step",
         "peak": "bf16_tflops"}, run)
    assert roof == pytest.approx(100.0 * 2e6 / (100e-9 * 197e12))
    assert data.module("readers", "trace_idle").read({}, {}) is None


def test_hlo_names_and_categories():
    hlo = '''
  %fusion.3 = bf16[8,16]{1,0} fusion(%p0), kind=kLoop, calls=%f, metadata={op_type="x" op_name="jit(step)/layer_norm/out=ln_0.tmp_2/add" source_file="a.py"}
  ROOT %custom-call.7 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/pallas_kernel=gemm_epilogue.12/mul/out=fc_1.tmp_0/pallas_call"}
  %copy.1 = f32[8]{0} copy(%b)
'''
    names = cb_trace.hlo_op_names(hlo)
    assert set(names) == {"fusion.3", "custom-call.7"}
    assert cb_trace.category("fusion.3", names["fusion.3"]) == "op:layer_norm"
    assert cb_trace.category("custom-call.7", names["custom-call.7"]) == "pallas:gemm_epilogue"
    assert cb_trace.category("copy.1", "") == "hlo:copy"
    # two programs reuse 'fusion.3': each traced module gets the map that
    # knows most of the instructions seen inside it
    other = {"fusion.3": "jit(d)/paged_attention/out=att/x", "fusion.9": "jit(d)/mul/out=y/dot"}
    ops = [(cb_trace.instr_and_opcode(n), s, d) for n, s, d in (
        ("%fusion.3 = bf16[8,16]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[8]{0} %p0), kind=kLoop", 10, 5),
        ("%custom-call.7 = (f32[8]{0:T(128)S(1)}, u32[]{:S(2)}) custom-call(f32[8]{0} %a), custom_call_target=\"tpu_custom_call\"", 16, 5),
        ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %q)", 110, 5), ("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %r)", 116, 5))]
    assert [o for o, _, _ in ops][:2] == [("fusion.3", "fusion"), ("custom-call.7", "custom-call")]
    modules = [("jit_a(1)", 0, 100), ("jit_b(2)", 100, 100)]
    named = cb_trace.name_ops(ops, modules, [names, other])
    assert [t.split(" ")[0] for t, _, _ in named] == [
        "op:layer_norm", "pallas:gemm_epilogue", "op:paged_attention", "op:mul"]


@pytest.fixture
def synthetic_trace(monkeypatch):
    """The CPU's profile has no device plane: the runners' traced branch runs
    for real (profiler on, spans written, for a shorter span than on the
    chip) and only the reduction's input is replaced."""
    from chipbench.runners import serve, train

    monkeypatch.setattr(train, "TRACED_STEPS", 2)
    monkeypatch.setattr(serve, "TRACED_SECONDS", 0.5)
    monkeypatch.setattr(serve, "TRACED_TRAFFIC_SECONDS", 1.5)
    monkeypatch.setattr(
        cb_trace, "reduce_dir",
        lambda log_dir, hlo_texts, chips: (
            os.listdir(log_dir),
            cb_trace.reduce_events(SYNTH_DEVICE, SYNTH_HOST, chips))[1])


def test_train_runner_traced_and_mesh_branch(tree, on_cpu, synthetic_trace):
    """The traced run of a toy cell whose workload file carries a mesh: the
    runner builds ParallelExecutor over four of the virtual CPU devices."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual CPU devices (tests/conftest.py)")
    tree.add("configs", "toy_encdec", TOY_TRAIN_CONFIG)
    tree.add("traffic", "toy_ring", TOY_RING)
    tree.add_cell("toy_train_dp4", "toy_encdec", "toy_ring",
                  like="tbig_train_1chip", mesh={"dp": 4})
    wl = data.named("workloads", "toy_train_dp4")
    wl["chips"] = 4
    tree.add("workloads", "toy_train_dp4", wl)
    rc, doc = run_main(["--workload", "toy_train_dp4", "--seed", "5",
                        "--seconds", "1", "--trace", "1"])
    assert rc == 0
    names = {m["name"] for m in data.cell_metrics(
        data.manifest(), "toy_train_dp4", "per_layer")}
    check_result(doc, names, traced=True)
    assert set(doc["metrics"]) == names  # every train reader found its input
    assert doc["metrics"]["train.compiles_in_window"]["value"] == 0
    assert doc["metrics"]["train.device_idle_share"]["value"] == pytest.approx(37.5)
    assert set(doc["breakdown"]) == {"device_ops", "idle_gaps"}


def test_serve_runner_traced(tree, on_cpu, synthetic_trace):
    tree.add("configs", "toy_gpt", TOY_SERVE_CONFIG)
    tree.add("traffic", "toy_chat", TOY_CHAT)
    tree.add_cell("toy_chat_cell", "toy_gpt", "toy_chat", like="gpt2l_chat")
    rc, doc = run_main(["--workload", "toy_chat_cell", "--seed", "4",
                        "--seconds", "2", "--trace", "1"])
    assert rc == 0
    names = {m["name"] for m in data.cell_metrics(
        data.manifest(), "toy_chat_cell", "per_layer")}
    check_result(doc, names, traced=True)
    assert set(doc["metrics"]) == names
    assert doc["metrics"]["serve.traces_in_window"]["value"] == 0
    assert doc["metrics"]["serve.prefix_hit_share"]["value"] > 0
    assert doc["metrics"]["serve.decode_step_host_mean_ms"]["value"] > 0
    assert doc["metrics"]["serve.ms_per_token_p90"]["value"] > 0
    assert 0 <= doc["metrics"]["serve.live_slots_mean"]["value"] <= 4  # toy replies beat the sampler
    assert 0 < doc["metrics"]["serve.pool_fill_share"]["value"] <= 100
    assert (0 <= doc["metrics"]["serve.pool_live_share"]["value"]
            <= doc["metrics"]["serve.pool_fill_share"]["value"])


# ------------------------------------------------------- generator, refusal


def test_chat_schedule_is_the_mix_s_and_the_seed_makes_the_tokens():
    gen = data.module("traffic", "open_loop_chat")
    params = data.named("traffic", "chat_replay")
    phases = [("ramp", 15), ("window", 50)]
    a = gen.schedule(params, 2147483659, phases, 50257)
    b = gen.schedule(params, 2147483659, phases, 50257)
    c = gen.schedule(params, 7, phases, 50257)
    d = gen.schedule(dict(params, order_seed=params["order_seed"] + 1), 7,
                     phases, 50257)
    assert a == b and a != c
    shape = lambda s: [(r["due_s"], len(r["prompt"]), r["max_new_tokens"],
                        r["phase"]) for r in s]
    assert shape(a) == shape(c)  # every seed: the same arrivals and sizes
    assert shape(c) != shape(d)
    w = [r for r in a if r["phase"] == "window"]
    assert len(w) == round(params["rate_per_s"] * 50)
    assert all(15 <= r["due_s"] < 65 for r in w)
    assert all(r["prompt"][:64] == a[0]["prompt"][:64] for r in a + c)
    assert all(64 + 16 <= len(r["prompt"]) <= 64 + 576 for r in a)
    assert all(16 <= r["max_new_tokens"] <= 256 for r in a)
    # another order holds the same sizes
    sizes = lambda s: (sorted(len(r["prompt"]) for r in s),
                       sorted(r["max_new_tokens"] for r in s))
    assert sizes(c) == sizes(d)
    gaps = sorted(round(y["due_s"] - x["due_s"], 6) for x, y in zip(a, a[1:]))
    assert gaps[0] < 0.1 * gaps[-1]  # exponential-like, not a metronome


def test_load_generator_times_from_the_due_time_and_reports_lateness():
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from chipbench import loadgen

    class H(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_POST(self):
            n = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
            body = json.dumps({"tokens": [0] * n["max_new_tokens"]}).encode()
            self.send_response(200 if n["prompt"][0] else 503)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    try:
        sched = [{"due_s": 0.05 * i, "prompt": [i % 3], "max_new_tokens": 4,
                  "phase": "window"} for i in range(6)]
        gen = loadgen.OpenLoop("127.0.0.1", httpd.server_address[1], "/x", sched, 5.0)
        gen.start()
        assert gen.join(range(6), 10.0)
        gen.stop_dispatch()
    finally:
        httpd.shutdown()
        httpd.server_close()
        t.join(5)
    for r, s in zip(gen.records, sched):
        assert r["sent_s"] >= s["due_s"]  # never early; lateness is sent - due
        assert r["done_s"] >= r["sent_s"]
        assert r["status"] == (200 if s["prompt"][0] else 503)
        assert r["tokens"] == (4 if s["prompt"][0] else 0)


def test_run_refuses_to_run_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        cb_run.main(["--workload", "tbig_train_1chip", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err


def test_manifest_names_only_files_that_are_there():
    man = data.manifest()
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    for w in man["workloads"]:
        cell = data.cell(w["name"])
        assert cell["workload"]["chips"] == w["chips"]
        assert (cell["workload"]["config"], cell["workload"]["traffic"]) == (
            w["config"], w["traffic"])
        data.module("runners", cell["config"]["runner"])
        data.module("traffic", cell["traffic"]["generator"])
        assert [m["name"] for m in data.cell_metrics(man, w["name"], "end_to_end")
                ].count("setup_s") == 1
    for c in man["configs"]:
        assert os.path.exists(os.path.join(data.ROOT, c["file"]))
    e2e = {m["name"] for m in man["end_to_end"]}
    assert {"serve_ms_per_token_p50", "train_tokens_per_s", "setup_s"} <= e2e
    for group, keys in (("end_to_end", ("unit", "better", "source")),
                        ("per_layer", ("unit", "better", "source", "layer", "moves"))):
        for m in man[group]:
            spec = data.named(cb_run.METRIC_DIRS[group], m["name"])
            data.module("readers", spec["reader"])
            assert m.get("moves", "setup_s") in e2e
            for k in keys:
                assert spec[k] == m[k]
    with pytest.raises(RuntimeError):
        data.peaks("TPU v99")
