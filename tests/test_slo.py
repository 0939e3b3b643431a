"""Fleet SLO engine tests (ISSUE 20): exact Prometheus exposition
round-trip, bucket-wise histogram merge (fleet percentiles bit-equal to
pooled observations), burn-rate window arithmetic on a fake clock, the
aggregator's tolerance of a replica dying mid-scrape, and the drift /
retrace / goodput sentinels (no false positives on stationary streams)."""

import json
import math
import os
import sys
import time

import numpy as np
import pytest

from paddle_tpu.observability import promparse
from paddle_tpu.observability import registry as obs_registry
from paddle_tpu.observability.aggregate import (
    FleetAggregator,
    hist_percentile,
    merge_snapshots,
)
from paddle_tpu.observability.slo import (
    SLO,
    AlertEngine,
    BurnRateRule,
    DriftSentinel,
    GoodputSentinel,
    LocalSampler,
    RetraceSentinel,
    window_delta,
)

HERE = os.path.dirname(os.path.abspath(__file__))
TOOLS = os.path.join(HERE, "..", "tools")


# ---------------------------------------------------------------- exposition
def test_exposition_roundtrip_exact():
    """parse(to_prometheus()) == snapshot(), bit for bit — labels with
    every escape-worthy character, full-precision floats, negative and
    integer values, and empty histograms all survive."""
    reg = obs_registry.MetricRegistry()
    reg.counter("fleet/requests", "routed").inc(3, kind="predict", code="200")
    reg.counter("fleet/requests").inc(1, kind="generate", code="503")
    reg.counter("plain").inc(7)
    reg.gauge("pp/bubble_measured").set(0.4500000000001)
    reg.gauge("tiny").set(-1.5e-07)
    reg.gauge("weird").set(
        2.5, path='a"b\\c', note="line1\nline2", empty=""
    )
    h = reg.histogram("step_ms", buckets=(1, 10, 100))
    for v in (0.25, 3.5, 3.5, 42.0, 4242.0):
        h.observe(v)
    reg.histogram("never_observed", buckets=(1, 2))  # min/max are None
    snap = reg.snapshot()
    assert promparse.parse(reg.to_prometheus()) == snap
    # and the round trip is stable under re-rendering
    text = obs_registry.render_prometheus(snap)
    assert promparse.parse(text) == snap


def test_exposition_non_finite_values():
    reg = obs_registry.MetricRegistry()
    reg.gauge("pos").set(float("inf"))
    reg.gauge("neg").set(float("-inf"))
    reg.gauge("nan").set(float("nan"))
    parsed = promparse.parse(reg.to_prometheus())
    assert parsed["pos"]["values"][""] == float("inf")
    assert parsed["neg"]["values"][""] == float("-inf")
    assert math.isnan(parsed["nan"]["values"][""])


def test_parse_foreign_exposition():
    """Text from a non-registry exporter (no # NAME comments, no _min/_max)
    still parses into a usable snapshot."""
    text = (
        "# TYPE http_requests_total counter\n"
        'http_requests_total{code="200"} 10\n'
        "# TYPE lat histogram\n"
        'lat_bucket{le="1"} 3\n'
        'lat_bucket{le="+Inf"} 5\n'
        "lat_sum 9.5\n"
        "lat_count 5\n"
        "some_gauge 2.5\n"
    )
    snap = promparse.parse(text)
    assert snap["http_requests_total"]["values"]['code=200'] == 10
    assert snap["lat"]["counts"] == [3, 2]
    assert snap["lat"]["sum"] == 9.5
    assert snap["some_gauge"]["kind"] == "gauge"


# --------------------------------------------------------------------- merge
def test_histogram_merge_bit_equal_to_pooled():
    """Fleet p50/p90/p99/p100 computed from the bucket-wise merge of three
    replicas' expositions are BIT-EQUAL to percentiles over one pooled
    histogram that saw every raw observation — the shared bounded grid
    plus identical interpolation arithmetic make this exact, not
    approximate."""
    rng = np.random.RandomState(0)
    regs = [obs_registry.MetricRegistry() for _ in range(3)]
    pooled = obs_registry.MetricRegistry().histogram("lat_ms")
    for i, reg in enumerate(regs):
        h = reg.histogram("lat_ms")
        for v in rng.gamma(2.0, 25.0, size=200 + 77 * i):
            h.observe(float(v))
            pooled.observe(float(v))
    merged = merge_snapshots(
        ("rep%d" % i, promparse.parse(reg.to_prometheus()))
        for i, reg in enumerate(regs)
    )["lat_ms"]
    for q in (50, 90, 99, 100):
        assert hist_percentile(merged, q) == pooled.percentile(q), q
    assert merged["count"] == pooled.count


def test_merge_counters_gauges_and_grid_mismatch():
    a, b = obs_registry.MetricRegistry(), obs_registry.MetricRegistry()
    a.counter("req").inc(3, code="200")
    b.counter("req").inc(4, code="200")
    b.counter("req").inc(1, code="500")
    a.gauge("queue_depth").set(2)
    b.gauge("queue_depth").set(5)
    a.histogram("h", buckets=(1, 2)).observe(0.5)
    b.histogram("h", buckets=(1, 2, 3)).observe(0.5)  # different grid
    mreg = obs_registry.MetricRegistry()
    mm = mreg.counter("mismatch")
    merged = merge_snapshots(
        [("a", a.snapshot()), ("b", b.snapshot())], mismatch_counter=mm
    )
    assert merged["req"]["values"]["code=200"] == 7
    assert merged["req"]["values"]["code=500"] == 1
    # gauges never sum: one per-replica-labelled series each
    assert merged["queue_depth"]["values"] == {"replica=a": 2, "replica=b": 5}
    # the mismatched grid was skipped, not silently summed
    assert merged["h"]["count"] == 1
    assert mm.value(metric="h") == 1


# ------------------------------------------------------------- window delta
def _hist_snap(total_bad, total, ts):
    reg = obs_registry.MetricRegistry()
    c = reg.counter("req")
    if total - total_bad:
        c.inc(total - total_bad, code="200")
    if total_bad:
        c.inc(total_bad, code="500")
    return (ts, reg.snapshot())


def test_window_delta_and_counter_reset():
    hist = [_hist_snap(0, 100, 10.0), _hist_snap(0, 160, 20.0),
            _hist_snap(0, 220, 30.0)]
    delta, span = window_delta(hist, 30.0, 10.0, "req")
    assert span == 10.0
    assert delta["values"]["code=200"] == 60
    # window longer than history: falls back to the oldest snapshot
    delta, span = window_delta(hist, 30.0, 1000.0, "req")
    assert delta["values"]["code=200"] == 120 and span == 20.0
    # a counter reset (restart) clamps to the current value, never negative
    hist.append(_hist_snap(0, 5, 40.0))
    delta, _ = window_delta(hist, 40.0, 10.0, "req")
    assert delta["values"]["code=200"] == 5


# ---------------------------------------------------------------- burn rate
def _engine(reg, sampler, clock, rules, slos):
    return AlertEngine(
        slos=slos, history=sampler, rules=rules, registry=reg,
        clock=lambda: clock[0], log_stderr=False, flightrec=False,
    )


def test_burn_rate_multiwindow_fake_clock():
    """SRE-workbook window arithmetic on a fake clock: a short-window
    spike alone does NOT page (long window vetoes), sustained burn fires,
    and the page resolves as soon as the short window drains even while
    the long window is still hot."""
    clock = [1000.0]
    reg = obs_registry.MetricRegistry()
    req = reg.counter("req")
    sampler = LocalSampler(reg, clock=lambda: clock[0])
    slo = SLO("avail", 0.99, counter="req", bad={"code": "5"}, min_events=1)
    eng = _engine(reg, sampler, clock,
                  [BurnRateRule("page", 60.0, 300.0, 10.0)], [slo])

    def tick(good, bad):
        if good:
            req.inc(good, code="200")
        if bad:
            req.inc(bad, code="500")
        clock[0] += 10.0
        sampler.sample()
        return eng.evaluate()

    for _ in range(40):  # 400 s of clean traffic
        assert tick(10, 0) == []
    assert not eng.firing()
    # budget 0.01 x factor 10 -> both windows must exceed ratio 0.1
    evs = tick(5, 5)  # short window hot (ratio 1/12), long still ~0.017
    assert evs == [] and not eng.firing()
    fired_at = None
    for i in range(10):
        evs = tick(5, 5)
        if any(e.state == "firing" for e in evs):
            fired_at = i
            break
    assert fired_at is not None, "sustained burn never paged"
    ev = eng.firing()[0]
    assert ev.name == "avail" and ev.severity == "page"
    assert ev.series is not None  # the offending windowed series rides along
    # recovery: the short window drains in 6 ticks and resolves the page
    # even though the long window still remembers the incident
    resolved_at = None
    for i in range(12):
        evs = tick(10, 0)
        if any(e.state == "resolved" for e in evs):
            resolved_at = i
            break
    assert resolved_at is not None and resolved_at <= 7
    assert not eng.firing()
    # the registry saw every transition
    snap = reg.snapshot()
    assert snap["slo/alerts_firing"]["values"][""] == 0
    events = snap["slo/alert_events"]["values"]
    assert events["event=fired,name=avail,severity=page"] == 1
    assert events["event=resolved,name=avail,severity=page"] == 1


def test_latency_slo_and_min_events():
    clock = [0.0]
    reg = obs_registry.MetricRegistry()
    h = reg.histogram("lat_ms", buckets=(10, 100, 1000))
    sampler = LocalSampler(reg, clock=lambda: clock[0])
    slo = SLO("lat", 0.9, histogram="lat_ms", threshold_ms=100.0,
              min_events=5)
    eng = _engine(reg, sampler, clock, [BurnRateRule("page", 20, 60, 2.0)],
                  [slo])
    sampler.sample()
    # below min_events in the window: no traffic must not page
    h.observe(5)
    clock[0] += 10
    sampler.sample()
    assert eng.evaluate() == []
    for _ in range(6):
        for _ in range(10):
            h.observe(500.0)  # > threshold: all bad
        clock[0] += 10
        sampler.sample()
        eng.evaluate()
    assert [e.name for e in eng.firing()] == ["lat"]


def test_alert_log_jsonl_and_flight_recorder_bundle(tmp_path):
    """A fired alert lands in the JSONL log and, with the flight recorder on,
    in an slo_alert bundle that carries the offending window's series."""
    from paddle_tpu import flags as _flags
    from paddle_tpu.observability import flightrec as _flightrec

    clock = [0.0]
    reg = obs_registry.MetricRegistry()
    req = reg.counter("req")
    sampler = LocalSampler(reg, clock=lambda: clock[0])
    out = str(tmp_path / "alerts.jsonl")
    fdir = str(tmp_path / "flightrec")
    old = _flags.get_flags(["flightrec_dir"])
    _flags.set_flags({"flightrec_dir": fdir})
    _flightrec.reset()
    eng = AlertEngine(
        slos=[SLO("avail", 0.9, counter="req", bad={"code": "5"})],
        history=sampler, rules=[BurnRateRule("page", 20, 40, 1.0)],
        registry=reg, clock=lambda: clock[0], out_path=out,
        log_stderr=False, flightrec=True,
    )
    try:
        for _ in range(6):
            req.inc(5, code="500")
            clock[0] += 10
            sampler.sample()
            eng.evaluate()
    finally:
        _flags.set_flags(old)
        _flightrec.reset()
    (bundle,) = [d for d in os.listdir(fdir) if "-slo_alert-" in d]
    info = json.load(open(os.path.join(fdir, bundle, "event.json")))["info"]
    assert info["name"] == "avail" and info["severity"] == "page"
    assert info["series"]
    req.inc(200, code="200")
    for _ in range(6):
        clock[0] += 10
        sampler.sample()
        eng.evaluate()
    recs = [json.loads(l) for l in open(out)]
    assert [r["event"] for r in recs] == ["fired", "resolved"]
    assert all(r["kind"] == "alert" and r["name"] == "avail" for r in recs)
    assert recs[0]["series"]  # fired record carries the windowed series
    assert recs[1]["duration_s"] > 0


# --------------------------------------------------------------- aggregator
def test_aggregator_tolerates_replica_death():
    """A target whose fetch raises mid-scrape is recorded as down and
    counted; the merge proceeds with the survivors."""
    up = obs_registry.MetricRegistry()
    up.counter("req").inc(5, code="200")
    texts = {"http://a": up.to_prometheus()}

    def fetch(url, timeout_s):
        if url not in texts:
            raise ConnectionError("replica died: %s" % url)
        return texts[url]

    local = obs_registry.MetricRegistry()
    agg = FleetAggregator(
        targets={"a": "http://a", "b": "http://b"},
        local_registry=local, fetch=fetch, clock=lambda: 100.0,
    )
    fs = agg.scrape_once()
    assert fs.merged["req"]["values"]["code=200"] == 5
    assert fs.targets["a"]["ok"] and not fs.targets["b"]["ok"]
    assert "replica died" in fs.targets["b"]["error"]
    snap = local.snapshot()
    assert snap["fleet/scrape_errors"]["values"]["replica=b"] == 1
    # the dead replica recovering is picked up on the next scrape
    texts["http://b"] = up.to_prometheus()
    fs = agg.scrape_once()
    assert fs.targets["b"]["ok"]
    assert fs.merged["req"]["values"]["code=200"] == 10


def test_aggregator_history_stats_and_listener():
    reg = obs_registry.MetricRegistry()
    reg.counter("req").inc(2, code="200")
    reg.histogram("lat_ms").observe(3.0)
    reg.gauge("depth").set(4)
    clock = [50.0]
    seen = []
    agg = FleetAggregator(targets={}, local_registry=reg,
                          clock=lambda: clock[0])
    agg.add_listener(seen.append)
    for _ in range(3):
        agg.scrape_once()
        clock[0] += 1.0
    assert len(agg.history()) == 3 and len(seen) == 3
    assert agg.history(window_s=1.5)[-1][0] == agg.latest().ts
    st = agg.stats()
    assert st["counters"]["req"]["total"] == 2
    assert st["histograms"]["lat_ms"]["count"] == 1
    assert st["gauges"]["depth"]["mean"] == 4
    assert "fleet_scrapes" in agg.metrics_text()


# ---------------------------------------------------------------- sentinels
def _lat_history(means, per_tick=20, t0=0.0, dt=1.0, jitter=None):
    """Synthesize (ts, snapshot) history for a latency histogram whose
    per-tick mean follows `means`."""
    reg = obs_registry.MetricRegistry()
    h = reg.histogram("lat_ms")
    out = []
    rng = np.random.RandomState(7)
    for i, m in enumerate(means):
        for _ in range(per_tick):
            v = m if jitter is None else m + rng.uniform(-jitter, jitter)
            h.observe(max(v, 0.01))
        out.append((t0 + i * dt, reg.snapshot()))
    return out


def test_drift_sentinel_stationary_never_fires():
    means = [10.0] * 200  # stationary (with jitter): must stay quiet
    hist = _lat_history(means, jitter=3.0)
    s = DriftSentinel("d", "lat_ms", warmup=5, rel_threshold=0.5)
    states = [s.evaluate(hist[: i + 1], hist[i][0])[0]
              for i in range(len(hist))]
    assert "firing" not in states


def test_drift_sentinel_detects_regression_with_hysteresis():
    means = [10.0] * 30 + [30.0] * 100
    hist = _lat_history(means)
    s = DriftSentinel("d", "lat_ms", warmup=5, rel_threshold=0.5)
    fired_tick = None
    state = "hold"
    for i in range(len(hist)):
        state, info, series = s.evaluate(hist[: i + 1], hist[i][0])
        if state == "firing" and fired_tick is None:
            fired_tick = i
            assert series is not None
    assert fired_tick is not None and 30 <= fired_tick <= 40
    # the slow EWMA eventually absorbs the new level as the baseline and
    # the hysteresis band (threshold/2) resolves the alert
    assert state == "ok"


def test_retrace_sentinel_arms_then_fires():
    reg = obs_registry.MetricRegistry()
    c = reg.counter("compile_cache/misses")
    s = RetraceSentinel(steady_ticks=3)
    hist = []

    def tick(misses):
        if misses:
            c.inc(misses)
        hist.append((len(hist) * 1.0, reg.snapshot()))
        return s.evaluate(hist, hist[-1][0])[0]

    tick(0)
    assert tick(2) == "hold"  # warmup compiles: never an alert
    for _ in range(4):        # quiet ticks arm the sentinel
        tick(0)
    assert tick(1) == "firing"  # post-warmup retrace: the regression
    tick(0)
    assert tick(0) == "ok"      # two quiet ticks resolve


def test_goodput_sentinel_gauges_and_floor():
    reg = obs_registry.MetricRegistry()
    c = reg.counter("goodput/items_total")
    s = GoodputSentinel("gp", "goodput/items_total", roofline_per_s=100.0,
                        unit="img", min_frac=0.5, warmup=1, registry=reg)
    hist = []

    def tick(items, dt=1.0):
        c.inc(items)
        t = (hist[-1][0] + dt) if hist else 0.0
        hist.append((t, reg.snapshot()))
        return s.evaluate(hist, t)[0]

    tick(90)
    assert tick(90) == "hold"  # warmup tick
    assert tick(90) == "ok"
    assert s.last_per_s == 90.0 and s.last_frac == 0.9
    g = reg.snapshot()["slo/goodput_vs_roofline"]["values"]
    assert g["name=gp,unit=img"] == 0.9
    assert tick(10) == "firing"  # fell under half the roofline
    assert tick(90) == "ok"


# ------------------------------------------------------------------- tools
def test_timeline_alert_track(tmp_path):
    sys.path.insert(0, TOOLS)
    import timeline as _timeline

    alerts = tmp_path / "alerts.jsonl"
    recs = [
        {"kind": "alert", "event": "fired", "name": "latency",
         "severity": "page", "ts": 100.0, "burn_short": 20.0},
        {"kind": "alert", "event": "resolved", "name": "latency",
         "severity": "page", "ts": 130.0},
        {"kind": "alert", "event": "fired", "name": "drift",
         "severity": "drift", "ts": 110.0},  # never resolves: open-ended
    ]
    alerts.write_text("".join(json.dumps(r) + "\n" for r in recs))
    out = tmp_path / "timeline.json"
    n = _timeline.convert("", str(out), alerts_path=str(alerts))
    assert n >= 2
    doc = json.loads(out.read_text())
    bars = [e for e in doc["traceEvents"]
            if e.get("cat") == "slo_alert" and e.get("ph") == "X"]
    assert len(bars) == 2
    lat = next(b for b in bars if "latency" in b["name"])
    assert lat["dur"] == pytest.approx(30.0 * 1e6)
    assert lat["args"]["resolved"] is True
    drf = next(b for b in bars if "drift" in b["name"])
    assert drf["args"]["resolved"] is False


def test_monitor_renders_fleet_section():
    sys.path.insert(0, TOOLS)
    import monitor as _monitor

    stats = {
        "targets": {"r0": {"ok": True}, "r1": {"ok": False, "error": "x"}},
        "counters": {"fleet/requests": {"total": 42, "series": 2}},
        "gauges": {"slo/goodput_vs_roofline":
                   {"n": 1, "min": 0.8, "max": 0.9, "sum": 0.85,
                    "mean": 0.85}},
        "histograms": {"fleet/request_ms":
                       {"count": 42, "sum": 100.0, "min": 1.0, "max": 9.0,
                        "p50": 2.0, "p90": 5.0, "p99": 8.5}},
        "slo": {"slos": [{"name": "latency"}], "sentinels": ["drift"],
                "events_total": 3,
                "firing": [{"name": "latency", "severity": "page",
                            "ts": 1.0, "burn_short": 15.0}]},
    }
    text = _monitor.render_fleet(stats)
    assert "1/2 targets up" in text and "down: r1" in text
    assert "fleet/request_ms" in text and "merged buckets" in text
    assert "ALERT latency" in text
    unreachable = _monitor.render_fleet({"error": "refused"})
    assert "unreachable" in unreachable


# ------------------------------------------------------------------ router
def test_router_fleet_endpoints():
    import urllib.error
    import urllib.request

    from paddle_tpu.fleet import Router

    r = Router(port=0)  # observability OFF by default: no loop, 503s
    port = r.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                "http://127.0.0.1:%d/fleet/metrics" % port, timeout=5
            )
        assert ei.value.code == 503
    finally:
        r.stop()

    r = Router(port=0, fleet_metrics=True, scrape_interval_s=0.1,
               slos=[SLO("avail", 0.99, counter="fleet/requests",
                         bad={"code": "5"})])
    port = r.start()
    try:
        deadline = time.time() + 30.0
        while time.time() < deadline:
            if r.aggregator is not None and r.aggregator.latest():
                break
            time.sleep(0.05)
        body = urllib.request.urlopen(
            "http://127.0.0.1:%d/fleet/metrics" % port, timeout=5
        ).read().decode()
        assert "fleet_scrapes" in body
        st = json.loads(urllib.request.urlopen(
            "http://127.0.0.1:%d/fleet/stats" % port, timeout=5
        ).read().decode())
        assert st["slo"]["slos"][0]["name"] == "avail"
        assert "counters" in st and "targets" in st
    finally:
        r.stop()
