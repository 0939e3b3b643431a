#!/usr/bin/env bash
# CI entry (reference paddle/scripts/paddle_build.sh: build, ctest, python
# unittests, API-diff gate). Builds the native runtime, runs the full pytest
# suite on a virtual 8-device CPU mesh, and regenerates+diffs the API spec.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== build native runtime =="
python - <<'PY'
from paddle_tpu import native
native.lib()
print("native runtime built:", native._lib_path())
PY

echo "== python unittests (8-device CPU mesh, sharded) =="
# Sharded into fresh pytest processes with one retry per shard (reference
# paddle_build.sh retries its flaky ctest tier the same way,
# retry_times=3): the XLA *CPU* compiler in this jax build segfaults
# intermittently (~1 in several hundred compile-heavy tests, observed in
# scan/while compiles across unrelated tests — pe_crf, pe_while_train,
# dynamic_lstm grad). Bisection shows it needs ~8+ test files of
# accumulated compile state in one process (every ≤5-file subset of a
# crashing shard passes), so small shards avoid it almost entirely and the
# retry absorbs the residue; a real test failure still fails the build
# (it fails twice).
run_shard () {
    JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
        python -m pytest "$@" -q
}
mapfile -t TEST_FILES < <(ls tests/test_*.py | sort)
NSHARDS=${NSHARDS:-8}
for ((s = 0; s < NSHARDS; s++)); do
    SHARD=()
    for ((i = s; i < ${#TEST_FILES[@]}; i += NSHARDS)); do
        SHARD+=("${TEST_FILES[$i]}")
    done
    echo "-- shard $((s + 1))/$NSHARDS: ${#SHARD[@]} files"
    if ! run_shard "${SHARD[@]}"; then
        echo "-- shard $((s + 1)) failed; retrying once in a fresh process"
        run_shard "${SHARD[@]}"
    fi
done

echo "== fault-injection smoke (resilience; docs/resilience.md) =="
# the MNIST book test must converge with its 10th training step poisoned
# (nan_grad, skipped by FLAGS_resilience_nan_guard), and the 2-trainer
# cluster must complete with ~8% of RPC attempts dropped and retried under
# the unified policy
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    FLAGS_resilience_nan_guard=1 \
    PADDLE_TPU_FAULTS="nan_grad:step=10,rpc_drop:0.05@seed=7" \
    python -m pytest -q \
        tests/test_mnist.py::test_mnist_lenet_converges \
        tests/test_resilience.py::test_cluster_completes_under_seeded_rpc_drop

echo "== zero1 + comm-volume smoke (docs/parallelism.md) =="
# compiles the dp, zero1 (ReduceStrategy.Reduce), fsdp, and tp (declarative
# sharding rules) MLP train steps on the 8-device mesh, parses every
# collective out of the HLO, and asserts the reduce-combined / gathered
# bytes match the analytic wire signatures of each strategy within 10%
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python tools/comm_audit.py --check

echo "== sharding-rules smoke (docs/parallelism.md) =="
# the same MLP+Adam trained under Megatron-TP (dp4×tp2) and FSDP (dp2×fsdp4)
# sharding rules must reproduce the plain single-device trajectory to
# < 1e-4, with params AND Adam moments stored in the rule layouts and the
# FSDP per-chip resident bytes at ~1/4 of replicated
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python - <<'PY'
import numpy as np
import paddle_tpu.fluid as fluid
from paddle_tpu import framework
from paddle_tpu.executor import Scope, scope_guard
from paddle_tpu.parallel import MeshConfig
from paddle_tpu.parallel_executor import BuildStrategy

def build():
    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, size=32, act="relu")
        logits = fluid.layers.fc(h, size=4)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    return main, startup, loss

rng = np.random.RandomState(0)
batches = [(rng.randn(64, 16).astype("float32"),
            rng.randint(0, 4, (64, 1)).astype("int64")) for _ in range(4)]

def train(mesh_cfg=None, rules=None):
    main, startup, loss = build()
    exe = fluid.Executor()
    losses, resident = [], 0
    scope = Scope(seed=3)
    with scope_guard(scope):
        exe.run(startup)
        pe = None
        if mesh_cfg is not None:
            strat = BuildStrategy()
            strat.sharding_rules = rules
            pe = fluid.ParallelExecutor(
                loss_name=loss.name, main_program=main,
                build_strategy=strat, scope=scope, mesh_config=mesh_cfg)
        for x, y in batches:
            if pe is not None:
                (l,) = pe.run(fetch_list=[loss.name], feed={"x": x, "y": y})
            else:
                (l,) = exe.run(main, feed={"x": x, "y": y},
                               fetch_list=[loss.name])
            losses.append(float(np.asarray(l).reshape(-1)[0]))
        pnames = {p.name for p in main.global_block().all_parameters()}
        for name, val in scope.vars.items():
            if name in pnames or "_acc" in name:
                shards = getattr(val, "addressable_shards", None)
                # replicated / host values hold one full copy per chip
                resident += (shards[0].data.nbytes if shards
                             else np.asarray(val).nbytes)
    return np.asarray(losses), resident

tp_rules = [(r"^fc_0\.w_0$", (None, "tp")), (r"^fc_0\.b_0$", ("tp",)),
            (r"^fc_1\.w_0$", ("tp", None))]
fsdp_rules = [(r"^fc_\d+\.(w|b)_0$", ("fsdp",))]

base, rep_bytes = train()
tp, _ = train(MeshConfig(dp=4, tp=2), tp_rules)
fsdp, shd_bytes = train(MeshConfig(dp=2, fsdp=4), fsdp_rules)
d_tp = float(np.max(np.abs(tp - base)))
d_fsdp = float(np.max(np.abs(fsdp - base)))
assert d_tp < 1e-4, "tp parity: max |d| %.2e" % d_tp
assert d_fsdp < 1e-4, "fsdp parity: max |d| %.2e" % d_fsdp
assert shd_bytes <= rep_bytes / 4 * 1.1, (shd_bytes, rep_bytes)
print("sharding-rules smoke ok: tp |d|=%.2e fsdp |d|=%.2e, "
      "fsdp resident %d B vs replicated %d B" %
      (d_tp, d_fsdp, shd_bytes, rep_bytes))
PY

echo "== pp through ParallelExecutor (docs/parallelism.md) =="
# a fluid Program must train on the dp2×pp4 mesh purely via
# ParallelExecutor — loss parity vs single-device for both schedules,
# device_guard override, checkpoint round-trip under stage partitioning
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest -q tests/test_pp_program.py

echo "== telemetry smoke (docs/observability.md) =="
# short training loop twice — telemetry off, then on into a tmp dir; asserts
# every JSONL record carries the schema (kind/step/ts/host), the Prometheus
# scrape file parses, the monitor renders, and telemetry-on stays within
# 3x + 0.25s of telemetry-off over 40 cached steps (generous: the disabled
# path is one flags lookup, the enabled path one JSON line per step)
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python - <<'PY'
import json, os, re, subprocess, sys, tempfile, time
import numpy as np
import paddle_tpu as pt
import paddle_tpu.fluid as fluid
from paddle_tpu.executor import Scope, scope_guard

main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = fluid.layers.data(name="x", shape=[8], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    p = fluid.layers.fc(x, size=1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(input=p, label=y))
    fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
rng = np.random.RandomState(0)
feed = {"x": rng.randn(16, 8).astype("float32"),
        "y": rng.randn(16, 1).astype("float32")}

def run_n(n):
    t0 = time.perf_counter()
    for _ in range(n):
        exe.run(main, feed=feed, fetch_list=[loss.name])
    return time.perf_counter() - t0

d = tempfile.mkdtemp()
with scope_guard(Scope(seed=0)):
    exe.run(startup)
    run_n(5)                       # warm the compile cache
    t_off = run_n(40)
    pt.set_flags({"telemetry_dir": d, "telemetry_interval_steps": 10})
    run_n(2)
    t_on = run_n(40)
from paddle_tpu.observability import stepstats
stepstats.collector().flush()

shard = os.path.join(d, "telemetry-host0.jsonl")
records = [json.loads(l) for l in open(shard) if l.strip()]
assert records, "no telemetry records written"
for r in records:
    for field in ("kind", "step", "ts", "host"):
        assert field in r, (field, r)
    if r["kind"] == "step":
        assert "wall_ms" in r and "cache_hit" in r, r
kinds = {r["kind"] for r in records}
assert kinds == {"step", "snapshot"}, kinds

prom = open(os.path.join(d, "metrics-host0.prom")).read()
sample = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9.eE+-]+$|^# (HELP|TYPE) .+$")
for line in prom.strip().splitlines():
    assert sample.match(line), "bad prometheus line: %r" % line
assert "step_ms_count" in prom

r = subprocess.run([sys.executable, "tools/monitor.py", "--dir", d, "--once"],
                   capture_output=True, text=True, timeout=60)
assert r.returncode == 0 and "p95 step ms" in r.stdout, r.stderr

assert t_on < t_off * 3 + 0.25, "telemetry overhead: off=%.3fs on=%.3fs" % (
    t_off, t_on)
print("telemetry smoke ok: %d records, off=%.3fs on=%.3fs" % (
    len(records), t_off, t_on))
PY

echo "== op attribution + nan provenance smoke (docs/observability.md) =="
# leg 1: FLAGS_profile_ops host-events profile of a LeNet step, folded into
# an op_profile record whose summed device ms must cover the measured step
# time within 20%, exported through telemetry and rendered by the
# tools/op_profile.py CLI and a tools/timeline.py op-attribution track.
# leg 2: a seeded nan_grad fault (poisons the "img" feed) under
# FLAGS_nan_provenance must localize the first non-finite output to the
# feed's consumer (conv2d) and write the provenance record + health counter.
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    FLAGS_resilience_nan_guard=1 FLAGS_nan_provenance=1 \
    PADDLE_TPU_FAULTS="nan_grad:step=3" \
    python - <<'PY'
import json, os, subprocess, sys, tempfile, time
import numpy as np
import paddle_tpu as pt
import paddle_tpu.fluid as fluid
from paddle_tpu import profiler
from paddle_tpu.executor import Scope, scope_guard
from paddle_tpu.observability import opprof

sys.path.insert(0, "tests")
from test_mnist import lenet, make_batch

main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    img = fluid.layers.data(name="img", shape=[1, 28, 28], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    avg_loss, acc = lenet(img, label)
    fluid.optimizer.SGD(learning_rate=0.01).minimize(avg_loss)

d = tempfile.mkdtemp()
exe = fluid.Executor(fluid.CPUPlace())
rng = np.random.RandomState(7)
with scope_guard(Scope(seed=7)):
    exe.run(startup)
    imgs, labels = make_batch(rng, 32)
    feed = {"img": imgs, "label": labels}

    # -- leg 1: per-op profile (host-events fallback works on any backend) --
    pt.set_flags({"telemetry_dir": d, "profile_ops": True})
    profiler.start_profiler("All")
    exe.run(main, feed=feed, fetch_list=[avg_loss.name])   # warm per-op jits
    profiler.reset_profiler()                              # drop compile time
    t0 = time.perf_counter()
    exe.run(main, feed=feed, fetch_list=[avg_loss.name])
    step_ms = (time.perf_counter() - t0) * 1e3
    rec = opprof.host_profile(step_ms=step_ms, block=main.global_block(),
                              feed_avals=feed)
    profiler.stop_profiler()
    pt.set_flags({"profile_ops": False})
    total = rec["total_device_ms"]
    cover = total / step_ms
    assert 0.8 <= cover <= 1.2, \
        "op profile covers %.0f%% of step time (ops %.2fms, step %.2fms)" % (
            100 * cover, total, step_ms)
    assert any(r["type"] == "conv2d" and r["flops"] > 0 for r in rec["ops"]), \
        "conv2d row missing analytic FLOPs: %s" % rec["ops"][:3]

    # -- leg 2: seeded nan_grad -> provenance names the feed's consumer --
    for _ in range(4):   # fault plan fires on the 3rd mutating run
        exe.run(main, feed=feed, fetch_list=[avg_loss.name])
    prov = opprof.last_provenance()
    assert prov is not None, "nan_grad fired but no provenance record"
    assert prov["op_type"] == "conv2d", prov
    assert prov["reason"] == "resilience_nan_guard", prov
    from paddle_tpu.resilience import health
    assert health.snapshot().get("nan_provenance", 0) >= 1
    from paddle_tpu.observability import stepstats
    stepstats.collector().flush()

shard = os.path.join(d, "telemetry-host0.jsonl")
kinds = [json.loads(l)["kind"] for l in open(shard) if l.strip()]
assert "op_profile" in kinds and "nan_provenance" in kinds, kinds

r = subprocess.run([sys.executable, "tools/op_profile.py", "--dir", d,
                    "--top", "10"], capture_output=True, text=True, timeout=60)
assert r.returncode == 0 and "conv2d" in r.stdout, (r.stdout, r.stderr)

tl = os.path.join(d, "timeline.json")
r = subprocess.run([sys.executable, "tools/timeline.py",
                    "--telemetry_path", shard, "--timeline_path", tl],
                   capture_output=True, text=True, timeout=60)
assert r.returncode == 0, r.stderr
trace = json.load(open(tl))["traceEvents"]
assert any(e.get("cat") == "op_profile" for e in trace), \
    "no op attribution track in timeline"
print("op attribution smoke ok: %d op rows, coverage %.0f%%, provenance %s"
      % (len(rec["ops"]), 100 * cover, prov["op"]))
PY

echo "== serving smoke (docs/serving.md) =="
# boots a 2-model ModelServer (MLP + LeNet) with a shared persistent compile
# cache, fires concurrent mixed-shape HTTP requests from threads, and
# asserts: every request served, ZERO variants traced after warmup (the
# engines' trace counters — the no-hot-path-recompiles guarantee), p99
# request latency under a generous CPU bound, and a clean drain on stop
JAX_PLATFORMS=cpu python - <<'PY'
import json, pathlib, sys, tempfile, threading, urllib.request
import numpy as np

sys.path.insert(0, "tests")
from test_serving import _save_mlp
sys.path.insert(0, ".")
from bench import _save_lenet_inference
from paddle_tpu.observability import registry as _registry
from paddle_tpu.serving import ModelServer

tmp = pathlib.Path(tempfile.mkdtemp(prefix="serving-smoke-"))
mlp_dir, _, _, xname, _ = _save_mlp(tmp, name="mlp", prefix="smoke")
lenet_dir = str(tmp / "lenet")
_save_lenet_inference(lenet_dir)

srv = ModelServer()
cache = str(tmp / "cache")
eng_mlp = srv.add_model("mlp", model_dir=mlp_dir, cache_dir=cache,
                        batch_buckets=(1, 2, 4, 8))
eng_lenet = srv.add_model("lenet", model_dir=lenet_dir, cache_dir=cache,
                          batch_buckets=(1, 2, 4, 8))
port = srv.start()
base = "http://127.0.0.1:%d" % port
traces0 = eng_mlp.traces + eng_lenet.traces

assert json.load(urllib.request.urlopen(base + "/healthz"))["status"] == "ok"

rng = np.random.RandomState(0)
errors = []

def client(k):
    for i in range(12):
        rows = 1 + (k + i) % 3          # mixed shapes: 1..3 rows
        if (k + i) % 2:
            name, feed = "mlp", {xname: rng.rand(rows, 6).tolist()}
        else:
            name, feed = "lenet", {"img": rng.rand(rows, 1, 28, 28).tolist()}
        req = urllib.request.Request(
            base + "/v1/models/%s:predict" % name,
            data=json.dumps({"inputs": feed}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            out = json.load(urllib.request.urlopen(req, timeout=30))
            assert len(out["outputs"]) >= 1
        except Exception as e:       # noqa: BLE001 - collected and asserted
            errors.append((name, rows, repr(e)))

threads = [threading.Thread(target=client, args=(k,)) for k in range(6)]
for t in threads:
    t.start()
for t in threads:
    t.join()

assert not errors, "failed requests: %s" % errors[:5]
traced = (eng_mlp.traces + eng_lenet.traces) - traces0
assert traced == 0, "%d hot-path recompiles" % traced
p99 = _registry.default_registry().get("serving/mlp/latency_ms").percentile(99)
assert p99 < 500.0, "p99 %.1f ms over bound" % p99
assert srv.stop(drain=True), "drain did not complete"
print("serving smoke ok: 72 requests, 0 hot-path recompiles, p99 %.1f ms"
      % p99)
PY

echo "== generation smoke (docs/serving.md) =="
# autoregressive serving: mixed-length greedy requests under Poisson
# arrivals through GenerationEngine + GenerationScheduler (prefill/decode
# split over the paged KV pool, chunked prefill, prefix KV cache).
# Asserts: every request served, ZERO variants traced after warmup (the
# zero-steady-state-retrace guarantee), positive token throughput, the
# naive whole-sequence ablation is token-identical, the shared-prefix
# workload actually hits the prefix cache, long prompts went through the
# chunked prefill path, and the pool drains clean — every page still held
# after drain is a reclaimable prefix-cache page, not a leak
JAX_PLATFORMS=cpu python - <<'PY'
import sys
sys.path.insert(0, ".")
from bench import run_generation_bench
rec = run_generation_bench(smoke=True)
assert rec["served_fraction"] == 1.0, rec
assert rec["traces_after_warmup"] == 0, \
    "%d hot-loop retraces" % rec["traces_after_warmup"]
assert rec["value"] > 0, rec
assert rec["naive_token_parity_ok"], "ablation token divergence"
assert rec["prefix_hit_rate"] > 0, rec["prefix_cache"]
assert rec["prefill_chunks"] >= rec["requests"], rec
assert rec["pool"]["slots_in_use"] == 0, rec["pool"]
assert rec["pool"]["pages_in_use"] == rec["prefix_cache"]["cached_pages"], \
    (rec["pool"], rec["prefix_cache"])
print("generation smoke ok: %d requests, %.0f tok/s (%.1fx naive "
      "whole-sequence), 0 retraces, prefix hit %.0f%%, %d prefill chunks, "
      "ttft p50 %.1f ms, token p50 %.2f ms"
      % (rec["requests"], rec["value"], rec["continuous_vs_naive_x"],
         100.0 * rec["prefix_hit_rate"], rec["prefill_chunks"],
         rec["p50_ttft_ms"], rec["p50_token_ms"]))
PY

echo "== data-runtime smoke (docs/data.md) =="
# a small uncached uint8 + token dataset streams through the native data
# runtime (num_workers=2): the feed-stall fraction must stay under 0.2 on
# CPU, and a SIGKILLed decode worker must lose/duplicate ZERO samples
# (exactly-once crash replay). Long soak variants are marked `slow` in
# tests/test_data_runtime.py and excluded from the tier-1 lane.
JAX_PLATFORMS=cpu python - <<'PY'
import sys
sys.path.insert(0, ".")
from bench import run_reader_bench
rec = run_reader_bench(smoke=True)
img, tok = rec["image"], rec["tokens"]
assert img["pyreader_frac_runtime"] < 0.2, img
assert tok["pyreader_frac_tokens_runtime"] < 0.2, tok
print("data smoke ok: runtime feed-stall frac uint8=%.3f tokens=%.3f "
      "(%d workers, %d batches/epoch)"
      % (img["pyreader_frac_runtime"], tok["pyreader_frac_tokens_runtime"],
         rec["num_workers"], img["batches_per_epoch"]))
PY
JAX_PLATFORMS=cpu python -m pytest -q \
    tests/test_data_runtime.py::test_worker_kill_mid_epoch_loses_and_duplicates_nothing \
    tests/test_data_runtime.py::test_pyreader_reset_generation_guard_regression

echo "== recsys smoke (docs/embedding.md) =="
# sparse embedding engine: DeepFM through the ep-sharded EmbeddingEngine on
# the 8-device CPU mesh must report positive embedding throughput and the
# sparse ep-sharded SGD trajectory must match dense single-device (the
# SelectedRows path changes gradient layout, not math)
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python - <<'PY'
import sys
sys.path.insert(0, ".")
from bench import run_recsys_bench
rec = run_recsys_bench(smoke=True)
assert rec["embedding_rows_per_sec"] > 0, rec
assert rec["parity_max_loss_diff"] < 1e-4, rec
print("recsys smoke ok: %.0f embedding rows/s (ep=%d), "
      "sparse/dense parity diff %.2g over %d steps"
      % (rec["embedding_rows_per_sec"], rec["devices"],
         rec["parity_max_loss_diff"], rec["parity_steps"]))
PY

echo "== elastic smoke (docs/resilience.md) =="
# elastic preemption-tolerant training: the async checkpoint's step-visible
# stall must stay <= 20% of a synchronous save at equal state size, a
# preempted trainer must resume bit-exact losing at most ckpt_every steps,
# and the acceptance scenario — SIGKILL one of two hosts mid-step, delete
# its shards, resume dp=1 from shard+replica — must hold in subprocesses
JAX_PLATFORMS=cpu python - <<'PY'
import sys
sys.path.insert(0, ".")
from bench import run_recovery_bench
rec = run_recovery_bench(smoke=True)
assert rec["async_stall_frac_of_sync"] <= 0.20, rec
assert rec["resume_bit_exact"], rec
assert rec["steps_lost"] <= rec["ckpt_every"], rec
print("elastic smoke ok: async stall %.2f ms = %.1f%% of sync %.2f ms "
      "(state %d MB), recover %.3f s, %d step(s) lost"
      % (rec["async_save_stall_ms"],
         100 * rec["async_stall_frac_of_sync"], rec["sync_save_stall_ms"],
         rec["state_mb"], rec["time_to_recover_s"], rec["steps_lost"]))
PY
JAX_PLATFORMS=cpu python -m pytest -q \
    tests/test_elastic.py::test_sigkill_one_of_two_hosts_resumes_bit_exact \
    tests/test_elastic.py::test_dp2_to_dp1_resume_parity

echo "== pass-framework smoke (docs/passes.md) =="
# graph pass pipeline: LeNet trains with FLAGS_pass_pipeline=training_default
# and FLAGS_pass_debug_dir set; asserts the round-trip is bit-lossless, the
# per-pass debug dumps exist, and pipeline-on losses match pipeline-off
# within 1e-6 (they are in fact bit-identical; tests/test_passes.py holds
# the strict form)
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python - <<'PY'
import json, os, sys, tempfile
import numpy as np
import paddle_tpu as pt
import paddle_tpu.fluid as fluid
from paddle_tpu import passes
from paddle_tpu.executor import Scope, scope_guard

sys.path.insert(0, "tests")
from test_mnist import lenet, make_batch

def build():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[1, 28, 28], dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        avg_loss, _ = lenet(img, label)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(avg_loss)
    return main, startup, avg_loss.name

main, _, _ = build()
fp = lambda p: json.dumps(p.to_dict(), sort_keys=True)
assert fp(passes.Graph(main).to_program()) == fp(main), "round-trip not lossless"

d = tempfile.mkdtemp(prefix="pass-dumps-")
def losses(pipeline, debug_dir=""):
    pt.set_flags({"pass_pipeline": pipeline, "pass_debug_dir": debug_dir})
    try:
        main, startup, loss_name = build()
        exe = fluid.Executor(fluid.CPUPlace())
        rng = np.random.RandomState(3)
        out = []
        with scope_guard(Scope(seed=11)):
            exe.run(startup)
            for _ in range(4):
                imgs, labels = make_batch(rng, 32)
                (lv,) = exe.run(main, feed={"img": imgs, "label": labels},
                                fetch_list=[loss_name])
                out.append(float(np.asarray(lv).ravel()[0]))
        return np.asarray(out)
    finally:
        pt.set_flags({"pass_pipeline": "", "pass_debug_dir": ""})

off = losses("")
on = losses("training_default", debug_dir=d)
delta = float(np.abs(off - on).max())
assert delta < 1e-6, "pipeline on/off loss diverged: %r vs %r" % (off, on)
dumps = sorted(os.listdir(d))
for i, name in enumerate(passes.PRESETS["training_default"]):
    for suffix in ("before.dot", "after.dot", "ops.diff"):
        want = "%02d_%s_%s" % (i, name, suffix)
        assert want in dumps, "missing debug dump %s (have %s)" % (want, dumps)
print("pass smoke ok: lossless round-trip, %d debug dumps, "
      "on/off max loss delta %.2g over 4 steps" % (len(dumps), delta))
PY

echo "== pallas kernel-substitution smoke (docs/passes.md) =="
# training_fused preset: a residual+layer_norm MLP whose shapes satisfy every
# path predicate must dispatch all four kernel families (GEMM epilogue,
# layer_norm fwd/bwd, multi-tensor Adam) and hold trajectory parity with the
# unfused run; tests/test_fused_kernels.py holds the full contract incl. the
# ZeRO-1 decline rule
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python - <<'PY'
import numpy as np
import paddle_tpu as pt
import paddle_tpu.fluid as fluid
from paddle_tpu import framework
from paddle_tpu.executor import Scope, scope_guard
from paddle_tpu.ops import pallas_kernels as pk

def build():
    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[256], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, size=256, act="gelu")
        h2 = fluid.layers.fc(h, size=256)
        ln = fluid.layers.layer_norm(
            fluid.layers.elementwise_add(h2, h), begin_norm_axis=1)
        pred = fluid.layers.fc(ln, size=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return main, startup, loss.name

def losses(pipeline):
    pt.set_flags({"pass_pipeline": pipeline})
    try:
        main, startup, loss_name = build()
        exe = fluid.Executor(fluid.CPUPlace())
        rng = np.random.RandomState(3)
        W = rng.randn(256, 1).astype("float32")
        out = []
        with scope_guard(Scope(seed=11)):
            exe.run(startup)
            for _ in range(4):
                xs = rng.randn(128, 256).astype("float32")
                (lv,) = exe.run(main, feed={"x": xs, "y": xs @ W},
                                fetch_list=[loss_name])
                out.append(float(np.asarray(lv).ravel()[0]))
        return np.asarray(out)
    finally:
        pt.set_flags({"pass_pipeline": ""})

pk.KERNEL_DISPATCHES.clear()
off = losses("")
assert not pk.KERNEL_DISPATCHES, pk.KERNEL_DISPATCHES
on = losses("training_fused")
for fam in ("gemm_epilogue", "layer_norm", "layer_norm_grad", "multi_adam"):
    assert pk.KERNEL_DISPATCHES.get(fam, 0) > 0, (fam, pk.KERNEL_DISPATCHES)
delta = float(np.abs(off - on).max() / np.abs(off).max())
assert delta < 1e-4, "fused/unfused diverged: %r vs %r" % (off, on)
print("pallas smoke ok: dispatched %s, fused/unfused rel loss delta %.2g"
      % (dict(pk.KERNEL_DISPATCHES), delta))
PY

echo "== quant smoke (docs/passes.md) =="
# calibrated-int8 serving end to end (ISSUE 18): zoo classifiers fit on
# synthetic clusters must hold int8 top-1 within 0.5% of the fp32 oracle,
# every fc mul must quantize and fuse, the kv-int8 GenerationEngine must
# hold 2x max_slots in fewer pool bytes with the last-step logit drift
# bounded, and the FLAGS_fp8_matmul path must actually dispatch
JAX_PLATFORMS=cpu python - <<'PY'
import sys
sys.path.insert(0, ".")
from bench import run_quant_bench
rec = run_quant_bench(smoke=True)
assert rec["top1_delta_max"] <= 0.005, rec["zoo"]
for name, z in rec["zoo"].items():
    assert z["quantized_muls"] > 0 and z["fused_groups"] > 0, (name, z)
    assert z["agreement"] >= 0.98, (name, z)
kv = rec["kv_int8"]
assert kv["max_slots_x"] >= 2.0, kv
assert kv["pool_bytes_x"] < 0.75, kv
assert kv["max_rel_logit_drift"] < 0.05, kv
assert kv["token_agreement"] >= 0.95, kv
assert kv["requests_ok"] == kv["requests"], kv
assert rec["fp8_transformer"]["matmul_fp8_dispatches_per_step"] > 0, rec
print("quant smoke ok: top-1 delta %.3f (zoo: %s), kv-int8 %dx slots at "
      "%.2fx bytes, drift %.3f, token agreement %.3f, fp8 %d matmuls/step"
      % (rec["top1_delta_max"], ",".join(sorted(rec["zoo"])),
         int(kv["max_slots_x"]), kv["pool_bytes_x"],
         kv["max_rel_logit_drift"], kv["token_agreement"],
         rec["fp8_transformer"]["matmul_fp8_dispatches_per_step"]))
PY

echo "== fluidlint smoke (docs/static_analysis.md) =="
# the whole model zoo — incl. the NMT beam-search while-loop and the gpt
# prefill/decode serving programs — must lint at zero findings under
# --strict, and the FLAGS_static_verify compile gate must be
# bit-transparent through the Executor (tests/test_fluidlint.py holds the
# per-seam strict form incl. ParallelExecutor and aot_serve_lowering)
JAX_PLATFORMS=cpu python tools/fluidlint.py --zoo --strict
# the seeded-defect corpus: every checker must name its planted defect
JAX_PLATFORMS=cpu python -m pytest -q tests/test_fluidlint.py -k "seeded"
JAX_PLATFORMS=cpu python - <<'PY'
import numpy as np
import paddle_tpu as pt
import paddle_tpu.fluid as fluid
from paddle_tpu.executor import Scope, scope_guard
from paddle_tpu.observability import registry as obs_registry

def run(verify_on):
    pt.set_flags({"static_verify": verify_on})
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            loss = fluid.layers.mean(fluid.layers.fc(x, size=4, act="relu"))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        xv = np.random.RandomState(0).randn(6, 8).astype("float32")
        with scope_guard(Scope(seed=7)):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            return [np.asarray(exe.run(main, feed={"x": xv},
                                       fetch_list=[loss.name])[0])
                    for _ in range(3)]
    finally:
        pt.set_flags({"static_verify": False})

off = run(False)
on = run(True)
for a, b in zip(off, on):
    assert (a == b).all(), "static_verify gate perturbed results"
verifies = obs_registry.default_registry().counter(
    "analysis/verifies", "").value(where="executor")
assert verifies > 0, "gate never ran with the flag on"
print("fluidlint smoke ok: zoo clean, gate bit-transparent "
      "(%d verifications)" % verifies)
PY

echo "== online smoke (docs/online.md) =="
# the full online-learning loop in one process: a DeepFM trainer streams
# synthetic clickstream batches and publishes base+delta versions into a
# model repository while a ModelServer serves the same model under
# concurrent client load and a HotReloader hot-swaps each version in.
# Asserts: zero 5xx across >= 3 swaps, served-version monotonicity,
# staleness within the contract bound, and bit-parity between the served
# prediction at version k and an offline engine restored from
# base+deltas(<=k) (all asserted inside run_online_bench)
JAX_PLATFORMS=cpu python - <<'PY'
import sys
sys.path.insert(0, ".")
from bench import run_online_bench
rec = run_online_bench(smoke=True)
assert rec["errors_5xx"] == 0, rec
assert rec["hot_swaps"] >= 3, rec
assert rec["max_staleness_steps_observed"] <= rec["max_staleness_steps"], rec
assert rec["parity_bit_exact"], "served != offline base+delta replay"
print("online smoke ok: %d swaps, %d requests, 0 5xx, staleness<=%g, "
      "parity@%s bit-exact, %.0f rows/s while serving"
      % (rec["hot_swaps"], rec["requests_total"],
         rec["max_staleness_steps_observed"],
         rec["parity_versions_checked"], rec["rows_per_sec"]))
PY

echo "== fleet chaos smoke (docs/fleet.md) =="
# the fault-tolerant serving fleet end to end: 3 replica ModelServer
# subprocesses (predict MLP + tiny :generate decoder, shared model repo)
# behind the health-aware Router under mixed client load. Mid-run one
# replica is SIGKILLed and restarted — it rejoins only after its
# HotReloader acks the published version — then conn_reset and
# slow_response rounds must trip and re-close the armed replica's circuit
# breaker. Asserts (inside run_fleet_bench + re-checked here): zero 5xx,
# served_fraction 1.0, failover p99 <= 5x steady p99, breaker opened and
# re-closed per fault round
JAX_PLATFORMS=cpu python - <<'PY'
import sys
sys.path.insert(0, ".")
from bench import run_fleet_bench
rec = run_fleet_bench(smoke=True)
assert rec["errors_5xx"] == 0, rec
assert rec["served_fraction"] == 1.0, rec
assert rec["rejoined_at_version"] >= rec["target_model_version"], rec
assert rec["conn_reset_breaker_opens"] >= 1, rec
assert rec["slow_response_breaker_opens"] >= 1, rec
assert rec["conn_reset_breaker_reclosed"], rec
assert rec["slow_response_breaker_reclosed"], rec
print("fleet smoke ok: %d requests, 0 5xx, served 100%%, failover p99 "
      "%.1f ms (%.2fx steady), rejoined@v%d, breaker opens reset=%d "
      "slow=%d (both re-closed)"
      % (rec["requests_total"], rec["failover_p99_ms"] or 0.0,
         rec["failover_p99_over_steady"] or 0.0,
         rec["rejoined_at_version"], rec["conn_reset_breaker_opens"],
         rec["slow_response_breaker_opens"]))
PY

echo "== tracing + flight recorder smoke (docs/observability.md) =="
# distributed request tracing end to end: serving p99 with tracing on vs
# off, then a 3-replica chaos round (conn_reset faults + SIGKILL) with
# every process exporting spans into one shared trace dir. Asserts (inside
# run_tracing_bench + re-checked here): served_fraction 1.0, at least one
# failover trace whose spans come from >= 3 OS processes with a failed
# attempt AND the successful retry, a flight-recorder bundle whose span
# ring shows that failover, and both tools/timeline.py --trace_path and
# tools/trace_view.py rendering the shards
JAX_PLATFORMS=cpu python - <<'PY'
import sys
sys.path.insert(0, ".")
from bench import run_tracing_bench
rec = run_tracing_bench(smoke=True)
assert rec["served_fraction"] == 1.0, rec
assert rec["failover_trace_processes"] >= 3, rec
assert rec["bundles"] >= 1 and rec["bundle_shows_failover"], rec
assert rec["timeline_events"] >= rec["spans"], rec
print("tracing smoke ok: %d requests served 100%%, %d traces / %d spans, "
      "failover trace %s across %d processes, %d bundle(s) [%s], "
      "p99 on/off %.2f/%.2f ms"
      % (rec["requests"], rec["traces"], rec["spans"],
         rec["failover_trace"], rec["failover_trace_processes"],
         rec["bundles"], ",".join(rec["bundle_reasons"]),
         rec["p99_ms_tracing_on"], rec["p99_ms_tracing_off"]))
PY

echo "== fleet SLO engine smoke (docs/observability.md) =="
# the fleet-wide SLO plane end to end: Prometheus exposition round-trip
# (parse(to_prometheus()) == snapshot(), bit for bit) and fleet p99 from
# merged buckets bit-equal to the pooled-observation p99; a steady-state
# round behind Router(fleet_metrics=True) with ZERO false alerts; a
# slow_response chaos round whose fast-burn latency page fires, leaves an
# slo_alert flight-recorder bundle carrying the offending window's merged
# series, and resolves after the fault clears; plus the EWMA drift
# sentinel staying quiet on a stationary stream
JAX_PLATFORMS=cpu python - <<'PY'
import sys
sys.path.insert(0, ".")
from bench import run_slo_bench
rec = run_slo_bench(smoke=True)
assert rec["roundtrip_exact"] and rec["merged_p99_bit_equal"], rec
assert rec["steady"]["alerts_fired"] == 0, rec["steady"]
assert rec["chaos"]["fired"] and rec["chaos"]["fired_after_s"] < 60, \
    rec["chaos"]
assert rec["chaos"]["resolved"] and rec["chaos"]["slo_alert_bundle"], \
    rec["chaos"]
assert rec["drift"]["stationary_false_positives"] == 0, rec["drift"]
print("slo smoke ok: round-trip exact, merged p99 bit-equal, steady round "
      "0 false alerts (goodput %.2fx roofline), chaos page fired %.1fs in "
      "/ resolved %.1fs after clear (bundle %s), scrape+eval p99 on/off "
      "%.2f/%.2f ms"
      % (rec["steady"]["goodput_vs_roofline"],
         rec["chaos"]["fired_after_s"], rec["chaos"]["resolved_after_s"],
         rec["chaos"]["slo_alert_bundle"],
         rec["p99_ms_slo_on"], rec["p99_ms_slo_off"]))
PY

echo "== API diff gate =="
python tools/print_signatures.py > /tmp/API.spec.current
diff -u paddle_tpu/API.spec /tmp/API.spec.current \
    || { echo "API surface changed; regenerate paddle_tpu/API.spec"; exit 1; }

echo "== graft entry compile checks =="
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('multichip dryrun ok')"
echo "ALL GREEN"
