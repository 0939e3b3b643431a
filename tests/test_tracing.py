"""Distributed request tracing + flight recorder (PR 19): header
round-trip, deterministic tail sampling, NULL_SPAN identity on the
disabled path, flight-recorder bundle layout + rate limiting, span trees
through batcher -> engine, and a 2-replica Router failover whose one
trace id carries the failed attempt AND the successful retry."""

import glob
import json
import os
import sys
import time
import urllib.request

import numpy as np
import pytest

from paddle_tpu import flags as _flags
from paddle_tpu.observability import flightrec as _flightrec
from paddle_tpu.observability import tracing as _tracing
from paddle_tpu.observability.tracing import (
    NULL_SPAN,
    TRACE_HEADER,
    keep_trace,
    parse_header,
)
from paddle_tpu.serving import ContinuousBatcher, ModelServer, ServingEngine

from test_serving import _save_mlp

TOOLS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "tools")


@pytest.fixture()
def trace_env(tmp_path):
    """Tracing + flight recorder on, pointed at tmp dirs; restored (and
    the process singletons rebuilt) afterwards."""
    tdir = str(tmp_path / "traces")
    fdir = str(tmp_path / "flightrec")
    old = _flags.get_flags(["trace_dir", "flightrec_dir", "trace_sample",
                            "flightrec_min_interval_s"])
    _flags.set_flags({"trace_dir": tdir, "flightrec_dir": fdir,
                      "trace_sample": 1.0, "flightrec_min_interval_s": 0.05})
    _tracing.reset()
    _flightrec.reset()
    try:
        yield tdir, fdir
    finally:
        _tracing.reset()
        _flightrec.reset()
        _flags.set_flags(old)
        _tracing.reset()
        _flightrec.reset()


def _spans(tdir):
    _tracing.reset()  # flush + close shards, rebuild lazily
    return _tracing.load_spans(tdir)


def _by_trace(spans):
    out = {}
    for s in spans:
        out.setdefault(s["trace"], []).append(s)
    return out


# ------------------------------------------------------------ units


def test_header_roundtrip_and_parse():
    parsed = parse_header("a" * 16 + "-" + "b" * 8)
    assert parsed == ("a" * 16, "b" * 8)
    for bad in (None, "", "zz", "nohyphen", "short-ids", "a-b-c"):
        assert parse_header(bad) is None


def test_null_span_identity_when_disabled():
    """With both flags unset the hot path allocates nothing: every span
    operation returns the ONE process-wide NULL_SPAN singleton and the
    flight recorder trigger is a no-op."""
    old = _flags.get_flags(["trace_dir", "flightrec_dir"])
    _flags.set_flags({"trace_dir": "", "flightrec_dir": ""})
    _tracing.reset()
    _flightrec.reset()
    try:
        t = _tracing.tracer()
        assert not t.enabled
        s = t.start_span("router.request", kind="predict")
        assert s is NULL_SPAN
        assert s.child("x") is NULL_SPAN
        assert s.tag(a=1).event("e").error(None).end() is NULL_SPAN
        assert s.header() is None
        assert t.current() is NULL_SPAN
        with t.activate(s) as active:
            assert active is NULL_SPAN
        assert _flightrec.trigger("http_5xx", code=500) is None
        assert _flightrec.recorder() is None
    finally:
        _flags.set_flags(old)
        _tracing.reset()
        _flightrec.reset()


def test_sampling_deterministic_and_forced_keeps(tmp_path):
    """keep_trace is a pure hash: every process agrees. Error and slow
    segments bypass sampling; OK segments obey it."""
    ids = [os.urandom(8).hex() for _ in range(400)]
    frac = sum(keep_trace(t, 0.5) for t in ids) / len(ids)
    assert 0.3 < frac < 0.7
    assert all(keep_trace(t, 0.5) == keep_trace(t, 0.5) for t in ids[:20])
    assert all(keep_trace(t, 1.0) for t in ids[:20])
    assert not any(keep_trace(t, 0.0) for t in ids[:20])

    tdir = str(tmp_path / "t")
    tr = _tracing.Tracer(out_dir=tdir, sample=0.0, slow_ms=10000.0,
                         enabled=True)
    tr.start_span("ok_root").end()             # sampled out at 0.0
    tr.start_span("err_root").error(RuntimeError("boom")).end()
    forced = tr.start_span("forced_root").force_keep()
    forced.end()
    tr.close()
    names = {s["name"] for s in _tracing.load_spans(tdir)}
    assert names == {"err_root", "forced_root"}


# ------------------------------------------------------- flight recorder


def test_flightrec_bundle_layout_rate_limit_and_prune(tmp_path):
    fdir = str(tmp_path / "fr")
    rec = _flightrec.FlightRecorder(fdir, max_bundles=3, min_interval_s=30.0)
    path = rec.trigger("nan_guard", step=7)
    assert path and os.path.isdir(path)
    assert sorted(os.listdir(path)) == [
        "env.json", "event.json", "metrics.json", "spans.jsonl"
    ]
    ev = json.load(open(os.path.join(path, "event.json")))
    assert ev["reason"] == "nan_guard" and ev["info"]["step"] == 7
    assert "flags" in json.load(open(os.path.join(path, "env.json")))

    assert rec.trigger("nan_guard", step=8) is None  # rate-limited
    assert rec.trigger("watchdog_stall") is not None  # other reason passes

    rec2 = _flightrec.FlightRecorder(fdir, max_bundles=3, min_interval_s=0.0)
    for i in range(5):
        assert rec2.trigger("r%d" % i)
    assert len(rec2.bundles()) <= 3  # pruned to max_bundles


# ------------------------------------------- in-process serving span tree


def test_batcher_engine_span_chain(tmp_path, trace_env):
    """submit(parent=...) threads one trace through the batcher into the
    engine: serving.request -> serving.batch -> engine.execute, with the
    lifecycle events and model_version/precision tags the drilldown needs."""
    tdir, _ = trace_env
    model_dir, _, _, xname, _ = _save_mlp(tmp_path, prefix="trc")
    eng = ServingEngine(model_dir, name="trc", batch_buckets=(1, 2, 4))
    b = ContinuousBatcher(eng, max_queue_rows=16, max_batch_delay_ms=1.0)
    try:
        root = _tracing.tracer().start_span("client.call")
        fut = b.submit({xname: np.ones((2, 6), np.float32)}, parent=root)
        fut.result(30.0)
        root.end()
    finally:
        b.close()
    traces = _by_trace(_spans(tdir))
    chain = next(
        sp for sp in traces.values()
        if {"client.call", "serving.request"} <= {s["name"] for s in sp}
    )
    by_name = {s["name"]: s for s in chain}
    assert {"client.call", "serving.request", "serving.batch",
            "engine.execute"} <= set(by_name)
    req = by_name["serving.request"]
    assert req["parent"] == by_name["client.call"]["span"]
    assert by_name["serving.batch"]["parent"] == req["span"]
    assert by_name["engine.execute"]["parent"] == by_name["serving.batch"]["span"]
    assert [e["name"] for e in req["events"]] == ["queued", "admitted"]
    assert req["tags"]["outcome"] == "ok"
    exe = by_name["engine.execute"]
    assert exe["tags"]["precision"] == "native"
    assert "model_version" in exe["tags"] and "variant" in exe["tags"]


# --------------------------------------------------- router propagation


def _post(url, doc, timeout=30.0, headers=None):
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(),
        headers=dict({"Content-Type": "application/json"}, **(headers or {})),
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read().decode()), dict(resp.headers)


def test_router_failover_one_trace_with_error_and_retry(tmp_path, trace_env):
    """2-replica Router round trip with an injected connection reset on the
    first attempt: ONE trace id spans the client header, the router's http +
    request + both attempt spans (one error, one ok), the winning replica's
    server.request and the batcher's serving.request — and rides back to the
    client in the response header."""
    from paddle_tpu.fleet import CLOSED, Router
    from paddle_tpu.resilience import faults

    tdir, fdir = trace_env
    model_dir, _, _, xname, _ = _save_mlp(tmp_path, prefix="rtr")
    servers = []
    for _i in range(2):
        s = ModelServer(port=0)
        s.add_model("m", model_dir=model_dir)
        s.start()
        servers.append(s)
    # the breaker trips on the one reset and re-closes on the next success:
    # each transition dumps a flight-recorder bundle
    router = Router(port=0, hedge=False, probe_interval_s=60.0, seed=5,
                    breaker_opts=dict(failure_threshold=1, open_for_s=0.05,
                                      success_threshold=1))
    rport = router.start()
    try:
        for i, s in enumerate(servers):
            router.register("rep%d" % i, s.url)
        router.probe_once()
        # client-side root: the header the router must adopt
        client_trace = os.urandom(8).hex()
        client_span = os.urandom(4).hex()
        faults.install("conn_reset:step=1")
        try:
            doc = {"inputs": {xname: [[0.25] * 6]}}
            code, out, headers = _post(
                "http://127.0.0.1:%d/v1/models/m:predict" % rport, doc,
                headers={TRACE_HEADER: "%s-%s" % (client_trace, client_span)},
            )
        finally:
            faults.install(None)
        assert code == 200 and "outputs" in out
        assert headers.get(TRACE_HEADER, "").startswith(client_trace + "-")
        # more traffic until the tripped breaker has re-closed
        (tripped,) = [r for r in router.replicas().values()
                      if r.breaker.stats()["opens"]]
        deadline = time.monotonic() + 10.0
        while tripped.breaker.state != CLOSED and time.monotonic() < deadline:
            time.sleep(0.06)
            _post("http://127.0.0.1:%d/v1/models/m:predict" % rport, doc)
        assert tripped.breaker.state == CLOSED
    finally:
        router.stop()
        for s in servers:
            s.stop()

    spans = [s for s in _spans(tdir) if s["trace"] == client_trace]
    assert spans, "client trace id did not propagate"
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    assert set(by_name) >= {"router.http", "router.request", "router.attempt",
                            "server.request", "serving.request"}
    assert by_name["router.http"][0]["parent"] == client_span
    attempts = by_name["router.attempt"]
    assert len(attempts) == 2  # reset attempt + failover retry
    statuses = sorted(a["status"] for a in attempts)
    assert statuses == ["error", "ok"]
    req_span = by_name["router.request"][0]
    assert all(a["parent"] == req_span["span"] for a in attempts)
    assert req_span["tags"]["attempts"] == 2
    assert any(e["name"] == "retry" for e in req_span["events"])
    # the winning attempt's replica served a server.request under it
    ok_attempt = next(a for a in attempts if a["status"] == "ok")
    assert any(s["parent"] == ok_attempt["span"]
               for s in by_name["server.request"])
    # the reset leg left an error server span on the losing replica
    assert any(s["status"] == "error" and s["tags"].get("fault") == "conn_reset"
               for s in by_name["server.request"])

    # a breaker-transition bundle's span ring shows that failover: the failed
    # attempt and the successful retry under the client's trace id
    bundles = sorted(d for d in os.listdir(fdir) if d.startswith("bundle-"))
    assert any("-breaker_transition-" in b for b in bundles), bundles
    shown = False
    for b in bundles:
        ring = _tracing.load_spans(os.path.join(fdir, b, "spans.jsonl"))
        att = [s["status"] for s in ring
               if s["trace"] == client_trace and s["name"] == "router.attempt"]
        shown = shown or sorted(att) == ["error", "ok"]
    assert shown, bundles

    # both renderers read the shards
    sys.path.insert(0, TOOLS)
    try:
        import timeline
        import trace_view
    finally:
        sys.path.remove(TOOLS)
    n_events = timeline.convert("", str(tmp_path / "timeline.json"),
                                trace_path=tdir)
    assert n_events >= len(_tracing.load_spans(tdir))
    assert trace_view.main([tdir, "--top", "5"]) == 0
    assert trace_view.main([tdir, "--trace", client_trace]) == 0


# ------------------------------------------------ parity + disabled cost


def test_tracing_off_bit_parity_and_disabled_overhead(tmp_path):
    """Tracing must be observationally free: outputs bit-equal with the
    flags on vs off, and the off path must hand back the singleton from
    every call site (no per-request garbage)."""
    model_dir, _, _, xname, _ = _save_mlp(tmp_path, prefix="par")
    feed = {xname: np.random.RandomState(3).rand(4, 6).astype(np.float32)}

    def run_once():
        eng = ServingEngine(model_dir, name="par", batch_buckets=(4,))
        b = ContinuousBatcher(eng, max_queue_rows=8, max_batch_delay_ms=1.0)
        try:
            return np.asarray(b.submit(dict(feed)).result(30.0)[0])
        finally:
            b.close()

    old = _flags.get_flags(["trace_dir", "flightrec_dir"])
    try:
        _flags.set_flags({"trace_dir": "", "flightrec_dir": ""})
        _tracing.reset()
        off = run_once()
        assert _tracing.tracer().start_span("x") is NULL_SPAN

        _flags.set_flags({"trace_dir": str(tmp_path / "tr"),
                          "flightrec_dir": ""})
        _tracing.reset()
        on = run_once()
    finally:
        _flags.set_flags(old)
        _tracing.reset()
        _flightrec.reset()
    np.testing.assert_array_equal(off, on)
