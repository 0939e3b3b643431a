"""Telemetry stack tests: metric registry, health-counter shim, StepStats
collection + the runtime pipeline-bubble estimator, JSONL/Prometheus export,
tools/monitor.py rendering, and the dp2×pp4 integration path (the ISSUE's
acceptance bar: a pipelined run with FLAGS_telemetry_dir set produces a
stream whose bubble gauge matches the two-m-slope estimator, and the monitor
renders it)."""

import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.fluid as fluid
from paddle_tpu import framework
from paddle_tpu.executor import Scope, scope_guard
from paddle_tpu.observability import export as obs_export
from paddle_tpu.observability import registry as obs_registry
from paddle_tpu.observability import stepstats as obs_stepstats
from paddle_tpu.parallel import MeshConfig
from paddle_tpu.parallel_executor import ExecutionStrategy
from paddle_tpu.resilience import health

HERE = os.path.dirname(os.path.abspath(__file__))
TOOLS = os.path.join(HERE, "..", "tools")

FLAG_DEFAULTS = {
    "telemetry_dir": "",
    "telemetry_interval_steps": 50,
    "telemetry_log_every": 0,
}


def _clear_global_telemetry():
    pt.set_flags(dict(FLAG_DEFAULTS))
    col = obs_stepstats.collector()
    col.close()
    col.reset()
    health.reset()
    # zero the shared default registry WITHOUT dropping registrations — the
    # collector caches its metric objects, so deleting them would orphan its
    # counters out of future snapshots
    reg = obs_registry.default_registry()
    for name in reg.names():
        reg.get(name).clear()


@pytest.fixture(autouse=True)
def _telemetry_defaults():
    """Telemetry flags off and the process-global collector/registry/health
    state clean around every test (all are process singletons)."""
    _clear_global_telemetry()
    yield
    _clear_global_telemetry()


# ---- registry ------------------------------------------------------------


def test_counter_gauge_basics():
    reg = obs_registry.MetricRegistry()
    c = reg.counter("reqs", "help text")
    assert c.inc() == 1
    assert c.inc(4) == 5
    assert c.value() == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    c.inc(2, kind="rpc")
    assert c.value(kind="rpc") == 2
    assert c.value() == 5  # labelled series are independent

    g = reg.gauge("depth")
    g.set(3.5)
    assert g.value() == 3.5
    g.set(1.0, stage="fwd")
    assert g.value(stage="fwd") == 1.0
    assert reg.counter("reqs") is c  # idempotent re-registration


def test_registry_kind_mismatch_is_error():
    reg = obs_registry.MetricRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")


def test_registry_get_does_not_create():
    reg = obs_registry.MetricRegistry()
    assert reg.get("nope") is None
    assert reg.names() == []


def test_histogram_percentiles_bounded():
    reg = obs_registry.MetricRegistry()
    h = reg.histogram("lat_ms", buckets=(1, 10, 100))
    assert h.percentile(50) is None  # empty
    for v in (0.5, 5, 5, 50, 500):
        h.observe(v)
    assert h.count == 5
    # p100 = observed max even from the overflow bucket
    assert h.percentile(100) == 500
    p50 = h.percentile(50)
    assert 1 <= p50 <= 10  # the bucket containing the median
    # memory stays O(buckets) no matter how many observations
    for _ in range(1000):
        h.observe(2.0)
    assert len(h._counts) == 4


def test_prometheus_text_parses():
    reg = obs_registry.MetricRegistry()
    reg.counter("health/rpc_retries").inc(3)
    reg.counter("labeled").inc(2, kind="a")
    reg.gauge("pp/bubble_measured").set(0.45)
    h = reg.histogram("step_ms", buckets=(1, 10))
    h.observe(0.5)
    h.observe(99)
    text = reg.to_prometheus()
    sample = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? (-?[0-9.eE+-]+|[+-]?Inf|NaN)$"
        r"|^# (HELP|TYPE|NAME) .+$"
    )
    for line in text.strip().splitlines():
        assert sample.match(line), line
    # the exposition is exactly invertible (promparse is the inverse; the
    # full round-trip contract lives in test_slo.py)
    from paddle_tpu.observability import promparse

    assert promparse.parse(text) == reg.snapshot()
    # cumulative buckets + +Inf + sum/count for histograms
    assert 'step_ms_bucket{le="+Inf"} 2' in text
    assert "step_ms_count 2" in text
    # metric names sanitized (no '/')
    assert "health_rpc_retries 3" in text
    assert 'labeled{kind="a"} 2' in text


def test_registry_snapshot_and_reset_prefix():
    reg = obs_registry.MetricRegistry()
    reg.counter("health/a").inc()
    reg.counter("other").inc()
    snap = reg.snapshot()
    assert snap["health/a"]["values"][""] == 1
    reg.reset("health/")
    assert reg.names("health/") == []
    assert reg.get("other") is not None


# ---- health shim ---------------------------------------------------------


def test_health_shim_semantics():
    assert health.snapshot() == {}
    health.incr("nan_steps_skipped")
    health.incr("rpc_retries", 4)
    assert health.get("rpc_retries") == 4
    assert health.get("never_touched") == 0  # read does not create
    assert health.snapshot() == {"nan_steps_skipped": 1, "rpc_retries": 4}
    health.reset()
    assert health.snapshot() == {}
    assert health.get("rpc_retries") == 0


def test_health_counters_ride_the_registry():
    health.incr("master_retries", 2)
    c = obs_registry.default_registry().get("health/master_retries")
    assert c is not None and c.value() == 2


# ---- stepstats -----------------------------------------------------------


def test_active_gate_off_by_default():
    assert not obs_stepstats.active()
    pt.set_flags({"telemetry_log_every": 5})
    assert obs_stepstats.active()


def test_record_step_folds_pending_stall():
    col = obs_stepstats.StepStatsCollector(
        registry=obs_registry.MetricRegistry()
    )
    col.add_feed_stall(3.0)
    col.add_feed_stall(2.0)
    st = col.record_step(20.0, loss=1.5)
    assert st.feed_stall_ms == 5.0
    assert st.step == 1 and st.loss == 1.5
    st2 = col.record_step(10.0, n_steps=4)
    assert st2.feed_stall_ms == 0.0  # consumed by the previous step
    assert st2.step == 5  # counters advance by n_steps
    assert col.registry.get("steps_total").value() == 5
    assert col.registry.get("step_ms").count == 2


def test_cache_and_nan_counters():
    col = obs_stepstats.StepStatsCollector(
        registry=obs_registry.MetricRegistry()
    )
    col.record_step(5.0, cache_hit=False)
    col.record_step(5.0, cache_hit=True, nan_trip=True)
    assert col.registry.get("compile_cache/hits").value() == 1
    assert col.registry.get("compile_cache/misses").value() == 1
    assert col.registry.get("nan_guard/trips").value() == 1


def test_bubble_estimator_two_m_slope():
    """Exact synthetic model t(m) = c + (m+pp-1)·τ: the estimator must
    recover τ and the bubble 1 - m·τ/t(m) for the smallest m."""
    col = obs_stepstats.StepStatsCollector(
        registry=obs_registry.MetricRegistry()
    )
    pp, tau, c = 4, 10.0, 5.0
    t = lambda m: c + (m + pp - 1) * tau
    assert col.bubble_estimate() is None  # no pp data
    for _ in range(3):
        col.record_step(t(4), pp=pp, n_micro=4, schedule="gpipe")
    assert col.bubble_estimate() is None  # single m group
    for _ in range(3):
        col.record_step(t(16), pp=pp, n_micro=16, schedule="gpipe")
    est = col.bubble_estimate()
    assert est["pp"] == 4 and (est["m1"], est["m2"]) == (4, 16)
    assert est["tick_ms"] == pytest.approx(tau)
    assert est["bubble"] == pytest.approx(1 - 4 * tau / t(4), abs=1e-3)
    assert est["analytic"] == pytest.approx(
        obs_stepstats.analytic_bubble(4, 4), abs=1e-4
    )
    g = col.registry.get("pp/bubble_measured")
    assert g is not None
    assert g.value() == pytest.approx(est["bubble"], abs=1e-3)


def test_analytic_bubble_values():
    assert obs_stepstats.analytic_bubble(4, 4) == pytest.approx(3 / 7)
    assert obs_stepstats.analytic_bubble(1, 8) == 0.0
    # pipeline re-exports it (docs/parallelism.md's formula home)
    from paddle_tpu.parallel import pipeline

    assert pipeline.analytic_bubble is obs_stepstats.analytic_bubble


def test_health_log_line(capfd):
    pt.set_flags({"telemetry_log_every": 2})
    col = obs_stepstats.collector()
    health.incr("rpc_retries", 3)
    col.record_step(10.0, loss=0.25)
    col.record_step(10.0)
    out = capfd.readouterr().err
    assert "[telemetry] step=2" in out
    assert "step_ms=10.00" in out
    assert "rpc_retries=+3" in out


# ---- export --------------------------------------------------------------


def test_jsonl_schema_and_snapshot_records(tmp_path):
    d = str(tmp_path / "t")
    pt.set_flags({"telemetry_dir": d, "telemetry_interval_steps": 3})
    col = obs_stepstats.collector()
    for i in range(7):
        col.record_step(12.0, loss=float(i))
    col.flush()
    recs = obs_export.read_records(os.path.join(d, "telemetry-host0.jsonl"))
    assert recs, "no records written"
    for r in recs:
        # the ISSUE's schema bar: every record has kind/step/ts(+host)
        assert r["kind"] in ("step", "snapshot")
        assert "step" in r and "ts" in r and "host" in r
    steps = [r for r in recs if r["kind"] == "step"]
    snaps = [r for r in recs if r["kind"] == "snapshot"]
    assert len(steps) == 7
    assert len(snaps) >= 2  # interval=3 over 7 steps, plus the forced flush
    assert steps[-1]["loss"] == 6.0
    assert "metrics" in snaps[-1] and "health" in snaps[-1]
    assert snaps[-1]["metrics"]["steps_total"]["values"][""] == 7
    # Prometheus scrape file exists and carries the step histogram
    prom = open(os.path.join(d, "metrics-host0.prom")).read()
    assert "step_ms_count 7" in prom


def test_jsonl_rotation(tmp_path):
    d = str(tmp_path / "t")
    exp = obs_export.TelemetryExporter(d, interval_steps=10**6, max_bytes=600)
    for i in range(40):
        exp._write({"kind": "step", "step": i, "wall_ms": 1.0})
    exp.close()
    shard = os.path.join(d, "telemetry-host0.jsonl")
    assert os.path.exists(shard) and os.path.exists(shard + ".1")
    # no torn lines in either file
    both = obs_export.read_records(shard + ".1") + obs_export.read_records(shard)
    assert [r["step"] for r in both[-5:]] == list(range(35, 40))


def test_merge_host_shards(tmp_path):
    d = str(tmp_path)
    for host, tss in ((0, (1.0, 3.0)), (1, (2.0, 4.0))):
        with open(os.path.join(d, "telemetry-host%d.jsonl" % host), "w") as f:
            for ts in tss:
                f.write(json.dumps(
                    {"kind": "step", "step": 1, "ts": ts, "host": host}) + "\n")
    out = obs_export.merge_host_shards(d)
    assert out.endswith("telemetry-merged.jsonl")
    merged = obs_export.read_records(out)
    assert [r["ts"] for r in merged] == [1.0, 2.0, 3.0, 4.0]
    assert [r["host"] for r in merged] == [0, 1, 0, 1]
    assert obs_export.merge_host_shards(str(tmp_path / "empty")) is None


def test_executor_run_records_steps(tmp_path):
    """The Executor.run hook end-to-end: train a tiny program with
    FLAGS_telemetry_dir set, then check the stream."""
    d = str(tmp_path / "t")
    pt.set_flags({"telemetry_dir": d, "telemetry_interval_steps": 4})
    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="float32")
            p = fluid.layers.fc(x, size=1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(input=p, label=y))
            fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(0)
    with scope_guard(Scope(seed=0)):
        exe.run(startup)
        for _ in range(6):
            exe.run(main,
                    feed={"x": rng.randn(8, 4).astype("float32"),
                          "y": rng.randn(8, 1).astype("float32")},
                    fetch_list=[loss.name])
    obs_stepstats.collector().flush()
    recs = obs_export.read_records(os.path.join(d, "telemetry-host0.jsonl"))
    steps = [r for r in recs if r["kind"] == "step" and r["training"]]
    assert len(steps) >= 6
    assert all(r["wall_ms"] > 0 for r in steps)
    assert any(not r["cache_hit"] for r in steps)  # first step compiles
    assert sum(r["cache_hit"] for r in steps) >= 5
    assert any(r.get("loss") is not None for r in steps)


# ---- dp2×pp4 integration + monitor (the acceptance scenario) -------------


def _train_pp(n_micro, batches, seed=3):
    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[16], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="int64")
            h = x
            for w in (48, 32, 24):
                h = fluid.layers.fc(h, size=w, act="relu")
            logits = fluid.layers.fc(h, size=4)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(logits, y))
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(Scope(seed=seed)):
        exe.run(startup)
        es = ExecutionStrategy()
        es.pipeline_schedule = "gpipe"
        es.num_microbatches = n_micro
        pe = fluid.ParallelExecutor(
            loss_name=loss.name, main_program=main,
            mesh_config=MeshConfig(dp=2, pp=4), exec_strategy=es)
        for x_b, y_b in batches:
            pe.run(fetch_list=[loss.name], feed={"x": x_b, "y": y_b})


def test_pp_run_emits_bubble_gauge_and_monitor_renders(tmp_path):
    d = str(tmp_path / "t")
    pt.set_flags({"telemetry_dir": d, "telemetry_interval_steps": 3})
    rng = np.random.RandomState(0)

    def mk(n):
        x = rng.randn(n, 16).astype("float32")
        y = (np.abs(x[:, :4]).argmax(1)).astype("int64").reshape(n, 1)
        return x, y

    _train_pp(4, [mk(64) for _ in range(4)])
    _train_pp(16, [mk(64) for _ in range(4)])
    col = obs_stepstats.collector()
    col.flush()

    # two microbatch counts observed → the two-m-slope estimator resolves
    est = col.bubble_estimate()
    assert est is not None
    assert est["pp"] == 4 and (est["m1"], est["m2"]) == (4, 16)
    assert est["analytic"] == pytest.approx(3 / 7, abs=1e-4)

    # the published gauge is the estimator's value, clamped to [0, 1] (the
    # ISSUE tolerance: gauge ≡ the two-m estimator)
    gauge = col.registry.get("pp/bubble_measured").value()
    assert gauge == pytest.approx(
        max(0.0, min(1.0, est["bubble"])), abs=1e-3)
    assert 0.0 <= gauge <= 1.0

    # step records carry the pp schedule parameters
    recs = obs_export.read_records(os.path.join(d, "telemetry-host0.jsonl"))
    pp_steps = [r for r in recs if r.get("pp")]
    assert {r["n_micro"] for r in pp_steps} == {4, 16}
    assert all(r["schedule"] == "gpipe" and r["pp"] == 4 for r in pp_steps)
    snaps = [r for r in recs if r["kind"] == "snapshot"]
    assert snaps[-1].get("bubble", {}).get("bubble") == est["bubble"]

    # tools/monitor.py renders the stream, bubble row included
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "monitor.py"),
         "--dir", d, "--once"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "bubble (measured)" in r.stdout
    # the analytic gauge tracks the RUNNING config — last run was m=16, so
    # (pp-1)/(m+pp-1) = 3/19
    assert "bubble (analytic)" in r.stdout and "0.158" in r.stdout
    assert "p95 step ms" in r.stdout


def test_monitor_summarize_unit():
    sys.path.insert(0, TOOLS)
    try:
        import monitor

        records = [
            {"kind": "step", "step": i + 1, "ts": float(i),
             "host": 0, "wall_ms": 10.0 + i, "n_steps": 1,
             "feed_stall_ms": 1.0, "loss": 0.5}
            for i in range(10)
        ]
        records.append({
            "kind": "snapshot", "step": 10, "ts": 10.0, "host": 0,
            "metrics": {
                "pp/bubble_measured": {"kind": "gauge", "values": {"": 0.46}},
                "compile_cache/hits": {"kind": "counter", "values": {"": 9}},
            },
            "health": {"rpc_retries": 2},
            "mem": {"mem_peak_bytes": 1 << 30},
        })
        s = monitor.summarize(records, window=5)
        assert s["n_steps"] == 10 and s["last_step"] == 10
        assert s["bubble"] == 0.46
        assert s["cache_hits"] == 9
        assert s["mem_peak_bytes"] == 1 << 30
        assert s["health"] == {"rpc_retries": 2}
        # window=5 → steps 6..10: walls 15+16+17+18+19 = 85 ms, stall 5 ms
        assert s["stall_pct"] == pytest.approx(100.0 * 5 / 85, rel=1e-6)
        text = monitor.render(s)
        assert "health/rpc_retries" in text and "1.0 GiB" in text
    finally:
        sys.path.pop(0)


def test_timeline_counter_tracks(tmp_path):
    """Satellite: telemetry JSONL → chrome-trace counter events, merged under
    the name=path,... multi-trainer convention."""
    p0 = tmp_path / "t0.jsonl"
    recs = [
        {"kind": "step", "step": 1, "ts": 100.0, "host": 0,
         "wall_ms": 12.0, "n_steps": 1, "feed_stall_ms": 2.0, "loss": 0.9},
        {"kind": "step", "step": 2, "ts": 100.5, "host": 0,
         "wall_ms": 10.0, "n_steps": 1},
        {"kind": "snapshot", "step": 2, "ts": 101.0, "host": 0,
         "mem": {"mem_peak_bytes": 1234},
         "bubble": {"bubble": 0.45}},
    ]
    p0.write_text("".join(json.dumps(r) + "\n" for r in recs))
    sys.path.insert(0, TOOLS)
    try:
        import timeline

        out = str(tmp_path / "trace.json")
        n = timeline.convert("", out, telemetry_path=str(p0))
        assert n > 0
        trace = json.load(open(out))
        counters = [e for e in trace["traceEvents"] if e.get("ph") == "C"]
        names = {e["name"] for e in counters}
        assert {"step_ms", "feed_stall_ms", "loss",
                "mem_peak_bytes", "pp_bubble"} <= names
        ts0 = min(e["ts"] for e in counters)
        assert ts0 == 0.0  # normalized to the stream start
        # two trainers merge under distinct pids
        out2 = str(tmp_path / "trace2.json")
        timeline.convert("", out2,
                         telemetry_path="a=%s,b=%s" % (p0, p0))
        trace2 = json.load(open(out2))
        pids = {e["pid"] for e in trace2["traceEvents"] if e.get("ph") == "C"}
        assert len(pids) == 2
    finally:
        sys.path.pop(0)


# ---- overhead ------------------------------------------------------------


def test_telemetry_off_overhead_is_negligible(tmp_path):
    """The disabled path is one flags lookup per run; assert telemetry-on
    (with export) stays within a generous bound of telemetry-off so a
    regression that adds real per-step work to the hot path fails loudly."""
    main, startup = framework.Program(), framework.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            out = fluid.layers.fc(x, size=8)
            loss = fluid.layers.mean(out)
            fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    xb = np.ones((4, 8), "float32")

    def run_n(n):
        t0 = time.perf_counter()
        for _ in range(n):
            exe.run(main, feed={"x": xb}, fetch_list=[loss.name])
        return time.perf_counter() - t0

    with scope_guard(Scope(seed=0)):
        exe.run(startup)
        run_n(5)  # warm the compile cache
        t_off = run_n(40)
        pt.set_flags({"telemetry_dir": str(tmp_path / "t"),
                      "telemetry_interval_steps": 10})
        run_n(2)
        t_on = run_n(40)
    # generous CI-noise bound
    assert t_on < t_off * 3 + 0.25, (t_off, t_on)
