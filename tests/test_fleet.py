"""Fleet tier (paddle_tpu/fleet/): circuit-breaker state machine at zero
wall time, retry-budget arithmetic, router/replica parity (bit-equal
outputs + model_version through the proxy), staleness-gated routing against
a PR 15 model repository, failover on connection reset, breaker
open/half-open/close under a replica that errors or times out, `:generate`
through the router, hedged first-wins for slow primaries, drain-then-stop
with zero dropped requests, and SIGKILL mid-request failover + ack-gated
rejoin with REAL replica subprocesses tracing into one directory."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle_tpu.fleet import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    CircuitBreaker,
    ReplicaProcess,
    RetryBudget,
    Router,
)
from paddle_tpu.resilience import faults
from paddle_tpu.serving import ModelServer

from test_serving import _save_mlp

# ReplicaProcess children serve from whatever the environment gives them;
# these tests state the CPU
_CPU = {"JAX_PLATFORMS": "cpu"}


# --------------------------------------------------------------- breaker


def test_breaker_consecutive_failures_open_half_open_close():
    """The full ride, on an injected clock (zero wall time): closed ->
    open (streak) -> half-open after the open interval -> closed after
    success_threshold probe successes."""
    t = [0.0]
    flips = []
    b = CircuitBreaker(
        name="r0", failure_threshold=3, open_for_s=2.0, success_threshold=2,
        clock=lambda: t[0], on_transition=lambda n, old, new: flips.append(new),
    )
    assert b.state == CLOSED and b.allow()
    for _ in range(2):
        b.record_failure()
    assert b.state == CLOSED  # streak below threshold
    b.record_failure()
    assert b.state == OPEN and not b.allow() and b.opens == 1

    t[0] = 1.99
    assert not b.allow()  # open interval not yet elapsed
    t[0] = 2.0
    assert b.state == HALF_OPEN
    assert b.allow()       # claims THE probe slot
    assert not b.allow()   # half_open_probes=1: second request refused
    b.record_success()
    assert b.state == HALF_OPEN  # one success < success_threshold
    assert b.allow()
    b.record_success()
    assert b.state == CLOSED and b.allow()
    assert flips == [OPEN, HALF_OPEN, CLOSED]


def test_breaker_failed_probe_doubles_open_interval_capped():
    t = [0.0]
    b = CircuitBreaker(
        name="r1", failure_threshold=1, open_for_s=1.0, max_open_s=4.0,
        clock=lambda: t[0],
    )
    b.record_failure()            # open, interval 1.0
    for expected in (2.0, 4.0, 4.0):  # doubling, capped at max_open_s
        t[0] += b.stats()["open_interval_s"]
        assert b.state == HALF_OPEN and b.allow()
        b.record_failure()        # failed probe: reopen, doubled
        assert b.state == OPEN
        assert b.stats()["open_interval_s"] == expected
    assert b.opens == 4


def test_breaker_error_rate_trip_needs_min_requests():
    b = CircuitBreaker(
        name="r2", failure_threshold=100, error_rate_threshold=0.5,
        window=10, min_requests=6, clock=lambda: 0.0,
    )
    # alternating outcomes: 50% error rate, but below min_requests -> closed
    for _ in range(2):
        b.record_failure()
        b.record_success()
    assert b.state == CLOSED
    b.record_failure()
    b.record_success()  # 6 outcomes now, rate 0.5 >= threshold... but the
    # trip is evaluated on record_failure; the success above doesn't trip
    assert b.state == CLOSED
    b.record_failure()  # 7 outcomes, 4/7 >= 0.5 -> open
    assert b.state == OPEN


def test_retry_budget_tokens():
    budget = RetryBudget(ratio=0.5, max_tokens=2.0)
    assert budget.take() and budget.take()  # starts full
    assert not budget.take()                # empty: retries refused
    budget.on_request()                     # each request earns `ratio`
    assert not budget.take()                # 0.5 < 1 token
    budget.on_request()
    assert budget.take()
    for _ in range(100):
        budget.on_request()
    assert budget.tokens == 2.0             # capped


# ------------------------------------------------------------ http helpers


def _post(url, doc, timeout=30.0):
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read().decode())


def _start_server(model_dir, name="m", **server_kw):
    s = ModelServer(port=0, **server_kw)
    s.add_model(name, model_dir=model_dir)
    s.start()
    return s


# ------------------------------------------------------- router integration


@pytest.fixture()
def mlp_dir(tmp_path):
    model_dir, _, _, xname, _ = _save_mlp(tmp_path, prefix="flt")
    return model_dir, xname


def test_router_parity_bit_equal_and_model_version(mlp_dir):
    """A predict through the router == the same predict straight at a
    replica: outputs bit-equal (full-precision JSON round-trip) and the
    same model_version attribution."""
    model_dir, xname = mlp_dir
    servers = [_start_server(model_dir) for _ in range(2)]
    router = Router(port=0, hedge=False, probe_interval_s=60.0)
    rport = router.start()
    try:
        for i, s in enumerate(servers):
            router.register("rep%d" % i, s.url)
        router.probe_once()
        assert sorted(router.stats()["routable"]) == ["rep0", "rep1"]

        doc = {"inputs": {
            xname: np.random.RandomState(7).rand(3, 6).tolist()
        }}
        direct = [
            _post(s.url + "/v1/models/m:predict", doc)[1] for s in servers
        ]
        assert direct[0]["outputs"] == direct[1]["outputs"]  # same seed/dir
        for _ in range(4):
            code, routed = _post(
                "http://127.0.0.1:%d/v1/models/m:predict" % rport, doc
            )
            assert code == 200
            assert routed["outputs"] == direct[0]["outputs"]
            assert routed["model_version"] == direct[0]["model_version"]
    finally:
        router.stop()
        for s in servers:
            s.stop()


def test_staleness_gate_routes_only_acked_replicas(mlp_dir, tmp_path):
    """With a model repository attached, a replica is routable only once it
    has ACKED the published version — probed-ready is not enough (PR 15's
    landing proof gates rejoin after a restart)."""
    from paddle_tpu.online.publisher import ModelPublisher
    from paddle_tpu.online.staleness import write_ack

    model_dir, xname = mlp_dir
    servers = [_start_server(model_dir) for _ in range(2)]
    repo = str(tmp_path / "repo")
    pub = ModelPublisher(repo)
    eng = servers[0]._models["m"].engine
    params = {n: np.asarray(eng.scope.vars[n]).copy()
              for n in eng.param_names()}
    pub.publish(params, 3)

    router = Router(port=0, hedge=False, probe_interval_s=60.0,
                    repo=repo, repo_model="m", total_deadline_s=2.0)
    rport = router.start()
    try:
        router.register("rep0", servers[0].url)
        router.register("rep1", servers[1].url)
        router.probe_once()
        # both probed ready, neither acked version 3 -> nobody routable
        assert router.target_versions() == {"m": 3}
        assert router.stats()["routable"] == []
        doc = {"inputs": {xname: [[0.5] * 6]}}
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post("http://127.0.0.1:%d/v1/models/m:predict" % rport, doc,
                  timeout=10.0)
        assert ei.value.code == 503

        write_ack(repo, "rep0", 3, {"train_step": 3})
        router.probe_once()
        assert router.stats()["routable"] == ["rep0"]
        code, out = _post(
            "http://127.0.0.1:%d/v1/models/m:predict" % rport, doc
        )
        assert code == 200

        write_ack(repo, "rep1", 3, {"train_step": 3})
        router.probe_once()
        assert router.stats()["routable"] == ["rep0", "rep1"]
        # a manual gate past every ack empties the pool again
        router.set_target_version("m", 4)
        assert router.stats()["routable"] == []
    finally:
        router.stop()
        for s in servers:
            s.stop()


def test_conn_reset_fails_over_to_other_replica(mlp_dir):
    """A reset connection (server closes the socket without replying) is
    retried on a DIFFERENT replica within the deadline — the client never
    sees it."""
    model_dir, xname = mlp_dir
    servers = [_start_server(model_dir) for _ in range(2)]
    router = Router(port=0, hedge=False, probe_interval_s=60.0, seed=5)
    rport = router.start()
    try:
        router.register("rep0", servers[0].url)
        router.register("rep1", servers[1].url)
        router.probe_once()
        # process-global plan: the FIRST :predict POST (whichever replica
        # draws it) resets its connection; everything after is clean
        faults.install("conn_reset:step=1")
        doc = {"inputs": {xname: [[0.25] * 6]}}
        code, out = _post(
            "http://127.0.0.1:%d/v1/models/m:predict" % rport, doc
        )
        assert code == 200 and "outputs" in out
        assert router._m_retries.value(kind="predict") >= 1
        failed = [n for n, r in router.replicas().items()
                  if r.requests_failed > 0]
        assert len(failed) == 1  # exactly one replica ate the reset
    finally:
        faults.install(None)
        router.stop()
        for s in servers:
            s.stop()


@pytest.mark.parametrize("fault", ["error_500", "timeout"])
def test_breaker_opens_on_broken_replica_then_recloses(mlp_dir, fault):
    """A replica answering 500s, or answering later than the attempt's
    timeout, trips its breaker (traffic shifts to the healthy one); once it
    heals, the half-open probe re-closes the breaker and it serves again. No
    client-visible errors throughout."""
    model_dir, xname = mlp_dir
    servers = [_start_server(model_dir) for _ in range(2)]
    router = Router(
        port=0, hedge=False, probe_interval_s=60.0, seed=3,
        attempt_timeout_s=0.3 if fault == "timeout" else 5.0,
        breaker_opts=dict(failure_threshold=2, open_for_s=0.05,
                          success_threshold=1),
        retry_budget_ratio=1.0,
    )
    rport = router.start()
    try:
        router.register("rep0", servers[0].url)
        router.register("rep1", servers[1].url)
        router.probe_once()

        eng = servers[0]._models["m"].engine
        orig_run = eng.run

        def broken(feed):
            if fault == "timeout":
                time.sleep(1.0)
                return orig_run(feed)
            raise RuntimeError("injected engine brown-out")

        eng.run = broken
        doc = {"inputs": {xname: [[0.1] * 6]}}
        url = "http://127.0.0.1:%d/v1/models/m:predict" % rport
        for _ in range(12):
            code, _out = _post(url, doc)
            assert code == 200  # failover absorbs every failed attempt
        rep0 = router.replicas()["rep0"]
        assert rep0.breaker.stats()["opens"] >= 1
        assert router._m_breaker.value(replica="rep0", to="open") >= 1

        eng.run = orig_run  # heal
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            _post(url, doc)
            if rep0.breaker.state == CLOSED and rep0.requests_ok > 0:
                break
            time.sleep(0.05)
        assert rep0.breaker.state == CLOSED
        assert rep0.requests_ok > 0  # the healed replica serves again
    finally:
        router.stop()
        for s in servers:
            s.stop()


def test_generate_routes_and_replicas_agree():
    """`:generate` is proxied like `:predict`, and replicas built from the
    same seed answer a prompt with the same tokens, whichever serves it."""
    from paddle_tpu.executor import Scope
    from paddle_tpu.models.gpt_decoder import GPTDecoder

    gen_kw = dict(vocab_size=24, n_layer=2, n_head=2, d_model=16, d_inner=32,
                  max_context=16)
    servers = []
    for _ in range(2):
        s = ModelServer(port=0, request_timeout_ms=60000.0)
        s.add_generation_model("g", model=GPTDecoder(**gen_kw),
                               scope=Scope(seed=0), max_slots=2, page_size=4,
                               max_context=16)
        s.start()
        servers.append(s)
    router = Router(port=0, hedge=False, probe_interval_s=60.0, seed=7)
    rport = router.start()
    try:
        for i, s in enumerate(servers):
            router.register("rep%d" % i, s.url)
        router.probe_once()
        doc = {"prompt": [1, 2, 3], "max_new_tokens": 4, "eos_id": 999}
        direct = [_post(s.url + "/v1/models/g:generate", doc)[1]["tokens"]
                  for s in servers]
        assert direct[0] == direct[1] and len(direct[0]) == 4
        for _ in range(4):
            code, out = _post(
                "http://127.0.0.1:%d/v1/models/g:generate" % rport, doc)
            assert code == 200 and out["tokens"] == direct[0]
        assert router._m_requests.value(kind="generate", code="200") == 4
    finally:
        router.stop()
        for s in servers:
            s.stop()


def test_hedged_predict_first_wins_and_loser_unpunished(mlp_dir):
    """With a browned-out primary, the hedge fires after the hedge delay and
    the fast replica's reply wins — bit-equal to an unhedged predict — while
    the slow loser's breaker records NO failure (cancellation != failure)."""
    model_dir, xname = mlp_dir
    servers = [_start_server(model_dir) for _ in range(2)]
    router = Router(port=0, hedge=True, hedge_delay_ms=60.0,
                    hedge_after_observations=10 ** 9,  # pin the fixed delay
                    probe_interval_s=60.0, seed=1)
    rport = router.start()
    try:
        router.register("rep0", servers[0].url)
        router.register("rep1", servers[1].url)
        router.probe_once()

        doc = {"inputs": {xname: [[0.9] * 6]}}
        url = "http://127.0.0.1:%d/v1/models/m:predict" % rport
        _code, baseline = _post(url, doc)

        # slow BOTH replicas' engines is wrong — slow exactly one, then make
        # sure the router picked it first by draining the fast one's choice:
        # least-inflight with random tie-break means either may be primary,
        # so run a few rounds; every reply must be fast + correct regardless
        eng0 = servers[0]._models["m"].engine
        orig = eng0.run
        eng0.run = lambda feed: (time.sleep(0.5), orig(feed))[1]
        t0 = time.perf_counter()
        wins_before = router._m_hedges.value(event="won")
        for _ in range(6):
            code, out = _post(url, doc)
            assert code == 200
            assert out["outputs"] == baseline["outputs"]
        elapsed = time.perf_counter() - t0
        # 6 requests against a 0.5s-stalled primary in far less than 6*0.5s:
        # the hedge (60ms) won whenever the slow replica was primary
        assert elapsed < 2.5
        assert router._m_hedges.value(event="launched") >= 1
        rep0 = router.replicas()["rep0"]
        assert rep0.breaker.stats()["opens"] == 0
        assert rep0.requests_failed == 0  # cancelled losers aren't failures
        assert router._m_hedges.value(event="won") > wins_before
    finally:
        eng0.run = orig
        router.stop()
        for s in servers:
            s.stop()


def test_drain_then_stop_drops_nothing(mlp_dir):
    """drain() fences NEW traffic off a replica while in-flight requests
    finish; stopping the drained replica afterwards loses nothing — every
    concurrent client got a 200."""
    model_dir, xname = mlp_dir
    servers = [_start_server(model_dir) for _ in range(2)]
    router = Router(port=0, hedge=False, probe_interval_s=60.0, seed=2)
    rport = router.start()
    results = []
    stop = threading.Event()

    def client():
        doc = {"inputs": {xname: [[0.3] * 6]}}
        url = "http://127.0.0.1:%d/v1/models/m:predict" % rport
        while not stop.is_set():
            try:
                code, _ = _post(url, doc)
                results.append(code)
            except Exception as e:  # any client-visible failure is a bug
                results.append(repr(e))

    try:
        router.register("rep0", servers[0].url)
        router.register("rep1", servers[1].url)
        router.probe_once()
        threads = [threading.Thread(target=client) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.3)
        assert router.drain("rep0", wait_s=10.0)
        assert router.replicas()["rep0"].inflight == 0
        servers[0].stop()          # safe: fenced + drained
        time.sleep(0.3)
        stop.set()
        for t in threads:
            t.join(10.0)
        assert len(results) > 20
        assert all(code == 200 for code in results), results[:10]
        # post-drain traffic all landed on the survivor
        assert router.replicas()["rep1"].requests_ok > 0
    finally:
        stop.set()
        router.stop()
        for s in servers:
            s.stop()


# ------------------------------------------------- subprocess chaos (SIGKILL)


def test_sigkill_mid_request_failover_and_rejoin(tmp_path):
    """REAL process death: two replica subprocesses following one model
    repository, one armed to reset its FIRST connection and SIGKILL itself on
    its SECOND request (mid-request — the socket dies with no reply). Every
    client request still gets a 200 via failover, and the reset's trace id
    joins three OS processes (router, the replica that failed, the one that
    answered); the killed replica goes DOWN at the router, and a restart
    rejoins the pool only once its HotReloader has acked the published
    version."""
    from paddle_tpu import flags as _flags
    from paddle_tpu.observability import tracing as _tracing
    from paddle_tpu.online import ModelPublisher, read_latest
    from paddle_tpu.serving import ServingEngine

    model_dir, _, _, xname, _ = _save_mlp(tmp_path, prefix="kfl")
    repo = str(tmp_path / "repo")
    eng = ServingEngine(model_dir, name="m", batch_buckets=(1,))
    ModelPublisher(repo).publish(
        {n: np.asarray(eng.scope.vars[n]).copy() for n in eng.param_names()}, 1)
    target = read_latest(repo)["version"]
    tdir = str(tmp_path / "traces")
    env = dict(_CPU, FLAGS_trace_dir=tdir, FLAGS_trace_sample="1.0")
    spec = lambda name: {
        "name": name,
        "request_timeout_ms": 10000.0,
        "predict": {"model": "m", "model_dir": model_dir},
        "repo": repo,
        "poll_interval_s": 0.1,
    }
    reps = [
        ReplicaProcess(spec("kr0"), str(tmp_path), env=env,
                       faults="conn_reset:step=1,replica_kill:step=2"),
        ReplicaProcess(spec("kr1"), str(tmp_path), env=env),
    ]
    old_flags = _flags.get_flags(["trace_dir", "trace_sample"])
    _flags.set_flags({"trace_dir": tdir, "trace_sample": 1.0})
    _tracing.reset()
    router = Router(port=0, hedge=False, probe_interval_s=0.2, seed=4,
                    total_deadline_s=30.0, attempt_timeout_s=10.0,
                    down_after=2, repo=repo, repo_model="m")
    rport = router.start()
    try:
        for r in reps:
            r.start()
        for r in reps:
            r.wait_ready(timeout=180.0)
            router.register(r.name, r.url)
        router.probe_once()
        # both landed and acked the published version before they served
        assert sorted(router.stats()["routable"]) == ["kr0", "kr1"]

        doc = {"inputs": {xname: [[0.7] * 6]}}
        url = "http://127.0.0.1:%d/v1/models/m:predict" % rport

        def reset_span_on_disk():
            return any(s["name"] == "server.request"
                       and s.get("tags", {}).get("fault") == "conn_reset"
                       for s in _tracing.load_spans(tdir))

        codes, retries0, flushed = [], router._m_retries.value(kind="predict"), False
        for _ in range(10):
            codes.append(_post(url, doc, timeout=60.0)[0])
            if not flushed and router._m_retries.value(kind="predict") > retries0:
                # kr0 reset that connection; its next request kills it, so
                # its writer thread has the span on disk before we send one
                deadline = time.monotonic() + 30.0
                while not reset_span_on_disk() and time.monotonic() < deadline:
                    time.sleep(0.02)
                assert reset_span_on_disk()
                flushed = True
        assert codes == [200] * 10  # neither fault reached a client

        deadline = time.monotonic() + 30.0
        while reps[0].alive() and time.monotonic() < deadline:
            _post(url, doc, timeout=60.0)
            time.sleep(0.1)
        assert not reps[0].alive()  # the fault plan really killed it
        router.probe_once()
        router.probe_once()  # down_after=2 consecutive probe failures
        assert router.stats()["routable"] == ["kr1"]

        # restart WITHOUT the fault plan: same name, fresh process
        reps[0]._extra_env.pop(faults.ENV_VAR, None)
        reps[0].restart()
        reps[0].wait_ready(timeout=180.0)
        router.register(reps[0].name, reps[0].url)  # re-register: new port
        router.probe_once()
        assert sorted(router.stats()["routable"]) == ["kr0", "kr1"]
        # the staleness gate: routable because it acked the published version
        assert router.replicas()["kr0"].version_for_gate("m") >= target
        codes = [_post(url, doc, timeout=60.0)[0] for _ in range(4)]
        assert codes == [200] * 4
    finally:
        router.stop()
        for r in reps:
            try:
                r.kill()
            except Exception:
                pass
        _tracing.reset()  # flush the router's shard
        _flags.set_flags(old_flags)
        _tracing.reset()

    by_trace = {}
    for s in _tracing.load_spans(tdir):
        by_trace.setdefault(s["trace"], []).append(s)
    joined = [
        sp for sp in by_trace.values()
        if len({(s.get("host"), s.get("pid")) for s in sp}) >= 3
        and {"error", "ok"} <= {s["status"] for s in sp
                                if s["name"] == "router.attempt"}
    ]
    assert joined, "no failover trace spans three processes (%d traces)" % len(
        by_trace)
