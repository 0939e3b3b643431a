"""The phase spans of the scheduler loop, the engine step and Executor.run
(profiler.RecordEvent -> jax.profiler TraceAnnotation "fluid.*",
docs/observability.md): under a real CPU profiler session the spans land in
the xplane, the scheduler's five phases partition their iteration and the
engine's leaves nest in them; the phase histograms add up; gen_ctx_tokens
counts a hand-built step; both executors leave their three leaves; outputs do
not change with a session on; the repaired span tags; the public reads.
An admission's spans (fluid.engine.admit, fluid.prefix.*) nest where the work
happens and their histograms count with no session; the engine counts the
device run dry; a collection is a span and a count, and may start inside an
observe; a slow pass leaves its line."""

import gc
import glob
import os
import threading
import time

import jax
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import flags as _flags
from paddle_tpu import profiler
from paddle_tpu.executor import Scope, scope_guard
from paddle_tpu.models.gpt_decoder import GPTDecoder
from paddle_tpu.observability import registry, tracing
from paddle_tpu.serving import GenerationEngine, GenerationScheduler, GenRequest
from paddle_tpu.serving import generation

MODEL_KW = dict(
    vocab_size=24, n_layer=2, n_head=2, d_model=16, d_inner=32, max_context=32
)
NO_EOS = 999
SCHED_PHASES = ("lock", "admit", "prefill", "decode", "retire")
ENGINE_LEAVES = ("feed", "dispatch", "wait", "sample")


def make_engine(name):
    eng = GenerationEngine(
        GPTDecoder(**MODEL_KW), name=name, max_slots=3, page_size=4,
        max_context=32, cache_dir=None,
    )
    eng.warmup()
    return eng


class Session:
    """A jax.profiler session without the Python tracer, as chipbench starts
    one; `spans` are the fluid.* host events it recorded."""

    def __init__(self, log_dir):
        self.log_dir = str(log_dir)
        self.spans = []

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        for path in glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"),
                              recursive=True):
            for plane in jax.profiler.ProfileData.from_file(path).planes:
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(profiler.SPAN_PREFIX):
                            self.spans.append({
                                "name": ev.name[len(profiler.SPAN_PREFIX):],
                                "start": ev.start_ns,
                                "end": ev.start_ns + ev.duration_ns,
                                "tags": dict(ev.stats),
                            })
        return False

    def named(self, name):
        return [s for s in self.spans if s["name"] == name]


def hist_sum(model, name):
    return registry.default_registry().snapshot()[
        "serving/%s/%s" % (model, name)]["sum"]


def inside(inner, outer):
    return outer["start"] <= inner["start"] and inner["end"] <= outer["end"]


def test_scheduler_phases_partition_the_iteration(tmp_path):
    eng = make_engine("phasegen")
    sched = GenerationScheduler(eng, timeout_ms=60000.0)
    rng = np.random.default_rng(0)
    try:
        with Session(tmp_path / "prof") as prof:
            for batch in range(2):  # the scheduler idles between the two
                futs = [
                    sched.submit(rng.integers(1, 20, size=n).tolist(),
                                 max_new_tokens=6, eos_id=NO_EOS)
                    for n in (5, 9, 3)
                ]
                for f in futs:
                    assert len(f.result(60.0).tokens) == 6
                time.sleep(0.05)
    finally:
        sched.close()

    names = {s["name"] for s in prof.spans}
    assert {"sched.iter", "sched.idle"} <= names
    assert {"sched." + p for p in SCHED_PHASES} <= names
    assert {"engine." + p for p in ENGINE_LEAVES} <= names
    assert {s["tags"]["kind"] for s in prof.named("engine.dispatch")} == {
        "decode", "prefill"}

    by_iter = {}
    for s in prof.spans:
        if s["name"] == "host.gc":
            continue  # a collection belongs to no iteration
        assert "iter" in s["tags"], s
        by_iter.setdefault(s["tags"]["iter"], []).append(s)
    covered_share = []  # of each decoding pass
    leaves_seen = set()  # the decode leaves of a pass, in order
    for it, group in by_iter.items():
        wrappers = [s for s in group if s["name"] == "sched.iter"]
        if not wrappers:
            continue  # a pass cut by the session's start or end
        (wrapper,) = wrappers
        phases = sorted((s for s in group
                         if s["name"][len("sched."):] in SCHED_PHASES),
                        key=lambda s: s["start"])
        assert [s["name"] for s in phases] == [
            "sched." + p for p in SCHED_PHASES
            if any(s["name"] == "sched." + p for s in phases)]
        for a, b in zip(phases, phases[1:]):
            assert a["end"] <= b["start"]  # none overlaps the next
        assert all(inside(s, wrapper) for s in phases)
        # sched.idle lies outside the iteration
        assert not any(s["name"] == "sched.idle" and inside(s, wrapper)
                       for s in group)
        held = {s["name"]: s for s in phases}
        for leaf in (s for s in group if "kind" in s["tags"]):
            outer = held["sched." + leaf["tags"]["kind"]]
            assert inside(leaf, outer), (leaf, outer)
        for admit in (s for s in group if s["name"] == "engine.admit"):
            assert inside(admit, held["sched.admit"])
        # a pass feeds and dispatches step n+1, then waits for and samples
        # step n (the host reads a decode step one step late): the first
        # decoding pass has no step to read, the last none to dispatch
        decode_leaves = [s["name"][len("engine."):] for s in sorted(
            (s for s in group if s["tags"].get("kind") == "decode"),
            key=lambda s: s["start"])]
        assert decode_leaves in (
            [], list(ENGINE_LEAVES), list(ENGINE_LEAVES[:2]),
            list(ENGINE_LEAVES[2:])), decode_leaves
        leaves_seen.add(tuple(decode_leaves))
        if "sched.decode" in held:
            covered = sum(s["end"] - s["start"] for s in phases)
            covered_share.append(covered / (wrapper["end"] - wrapper["start"]))
    assert len(covered_share) >= 5
    assert ENGINE_LEAVES in leaves_seen  # most passes: dispatch n+1, read n
    # The phases cover the pass. A thread descheduled between two phases
    # opens a gap in that pass alone, so the claim is held on the median pass.
    assert np.median(covered_share) >= 0.9, sorted(covered_share)

    # the operator's histograms tell the same story with no session at all.
    # A histogram's interval lies inside its span's (the annotation opens
    # first and closes last), so it observes as often, never more time, and
    # less only by what a thread descheduled between the two exits adds to
    # the span alone: a tenth, and half a millisecond a pass beside it.
    snap = registry.default_registry().snapshot()

    def close_below(less, more, passes):
        return less <= more + 1.0 and more - less <= 0.1 * more + 0.5 * passes

    for name in ("iter",) + SCHED_PHASES:
        hist = snap["serving/phasegen/sched_%s_ms" % name]
        spans = prof.named("sched." + name)
        # the slack: a pass the session's start or end cut is in one only
        assert abs(hist["count"] - len(spans)) <= 2, (name, hist["count"], len(spans))
        span_ms = sum(s["end"] - s["start"] for s in spans) / 1e6
        assert close_below(hist["sum"], span_ms, len(spans)), (
            name, hist["sum"], span_ms)
    passes = snap["serving/phasegen/sched_iter_ms"]["count"]
    iter_ms = hist_sum("phasegen", "sched_iter_ms")
    assert iter_ms > 0
    phases_ms = sum(hist_sum("phasegen", "sched_%s_ms" % p) for p in SCHED_PHASES)
    # the phases lie inside the pass and fill it
    assert phases_ms <= iter_ms * (1 + 1e-6)
    assert close_below(phases_ms, iter_ms, passes), (phases_ms, iter_ms)
    assert hist_sum("phasegen", "sched_idle_ms") >= 50 * 0.5  # the sleep
    assert hist_sum("phasegen", "sched_host_stall_ms") <= iter_ms
    steps = snap["serving/phasegen/gen_step_ms"]["count"]
    for leaf in ENGINE_LEAVES:  # decode steps only
        assert snap["serving/phasegen/gen_%s_ms" % leaf]["count"] == steps


def test_engine_leaves_partition_a_decode_step_whatever_its_rows_fetch(tmp_path):
    """The ids come from the device and a sampled row fetches its logits:
    the decode step is still feed, dispatch, wait, sample in that order,
    none overlapping the next, once a step each; the first three fill the
    step's wall (gen_step_ms ends where sample starts: their histograms'
    sums against its own, which no other process's load can part, where a
    share of each sub-millisecond step's wall clock could be) and
    gen_fetch_bytes observes once a step."""
    eng = make_engine("leafgen")
    runs = [
        eng.start(GenRequest([1, 2, 3, 4, 5], max_new_tokens=12, eos_id=NO_EOS)),
        eng.start(GenRequest([7, 8, 9], max_new_tokens=12, eos_id=NO_EOS,
                             temperature=0.8, top_k=4, seed=3)),
    ]
    steps = 8
    gc.disable()  # a collection between two leaves is in the step and in none
    try:
        with Session(tmp_path / "prof") as prof:
            for step in range(steps):
                eng.iter = step + 1
                eng.decode_step(runs if step % 2 else runs[:1])
    finally:
        gc.enable()
        for r in runs:
            eng.finish(r)
    by_iter = {}
    for s in prof.spans:
        if s["tags"].get("kind") == "decode":
            by_iter.setdefault(s["tags"]["iter"], []).append(s)
    assert len(by_iter) == steps
    for group in by_iter.values():
        group.sort(key=lambda s: s["start"])
        assert [s["name"] for s in group] == ["engine." + p for p in ENGINE_LEAVES]
        for a, b in zip(group, group[1:]):
            assert a["end"] <= b["start"]
        covered = sum(s["end"] - s["start"] for s in group)
        assert 0 < covered <= group[-1]["end"] - group[0]["start"]
    snap = registry.default_registry().snapshot()
    for leaf in ENGINE_LEAVES:
        assert snap["serving/leafgen/gen_%s_ms" % leaf]["count"] == steps
    assert snap["serving/leafgen/gen_step_ms"]["count"] == steps
    fetched = snap["serving/leafgen/gen_fetch_bytes"]
    assert (fetched["count"], fetched["sum"]) == (
        steps, steps * 3 * 4 + (steps // 2) * MODEL_KW["vocab_size"] * 4)
    to_sample = sum(hist_sum("leafgen", "gen_%s_ms" % leaf)
                    for leaf in ENGINE_LEAVES[:3])
    step_ms = hist_sum("leafgen", "gen_step_ms")
    assert to_sample <= step_ms * (1 + 1e-6)
    assert step_ms - to_sample <= 0.1 * step_ms + 0.5 * steps


def test_ctx_tokens_counts_the_positions_of_a_hand_built_step():
    eng = make_engine("ctxgen")
    runs = [eng.start(GenRequest(list(range(1, n + 1)), max_new_tokens=4,
                                 eos_id=NO_EOS)) for n in (5, 9)]
    try:
        assert [r.next_pos for r in runs] == [5, 9]
        eng.decode_step(runs)  # attends 5 + 9 positions
        eng.decode_step(runs)  # 6 + 10
        eng.decode_step(runs[:1])  # 7
    finally:
        for r in runs:
            eng.finish(r)
    h = registry.default_registry().snapshot()["serving/ctxgen/gen_ctx_tokens"]
    assert (h["count"], h["sum"]) == (3, 14 + 16 + 7)
    assert h["buckets"][-1] >= 24 * 1024  # a full gpt2-large pool fits a bucket
    assert eng.stats()["positions_walked_per_step"] == 3 * 8 * 4


def test_walked_tokens_counts_the_kernel_s_trips_on_a_hand_built_step(
        monkeypatch):
    """gen_walked_tokens is the kernel's trip count in positions, over all
    three slots: a block is one 4-row page here, so the runs at 5 and 9
    take 2 and 3 trips and the idle slot, at position 0, one."""
    from paddle_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_PAGED_PAGES_PER_BLOCK", 1)
    eng = make_engine("walkgen")
    runs = [eng.start(GenRequest(list(range(1, n + 1)), max_new_tokens=4,
                                 eos_id=NO_EOS)) for n in (5, 9)]
    walked = []
    try:
        for step in (runs, runs, runs[:1]):
            eng.decode_step(step)
            # the mirror on the very positions the compiled step was fed
            fed = eng._dec_feeds["dec_positions"]
            assert fed.shape == (3, 1)
            walked.append(pk.paged_positions_walked(fed, 4, 8, 16 * 4))
    finally:
        for r in runs:
            eng.finish(r)
    # (5, 9, idle), (6, 10, idle), then 7 with the second slot's row left
    # at 10: the kernel walks every row it is given
    assert walked == [(2 + 3 + 1) * 4] * 3
    snap = registry.default_registry().snapshot()
    h = snap["serving/walkgen/gen_walked_tokens"]
    assert (h["count"], h["sum"]) == (3, sum(walked))
    assert h["buckets"] == snap["serving/walkgen/gen_ctx_tokens"]["buckets"]
    # the most a step can walk keeps its name and its value
    assert eng.stats()["positions_walked_per_step"] == 3 * 8 * 4
    assert pk.paged_positions_walked([31] * 3, 4, 8, 16 * 4) == 3 * 8 * 4


def test_variant_key_follows_the_kernels_revision_and_nothing_else(
        monkeypatch):
    """An exported serving module embeds the Mosaic kernels it was lowered
    with: the cache key has to miss when their revision changes, and only
    then."""
    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.serving import compile_cache

    def key():
        return compile_cache.variant_key(
            "fp", {"x": ((3, 1), "int32")}, ["logits"],
            state_avals={"k": ((8, 16), "float32")}, geometry={"page_size": 4},
        )

    before = key()
    assert key() == before
    monkeypatch.setattr(pk, "KERNELS_REVISION", pk.KERNELS_REVISION + "+1")
    assert key() != before
    monkeypatch.undo()
    assert key() == before


def _toy_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[8], dtype="float32")
        y = fluid.layers.fc(x, size=4)
        loss = fluid.layers.mean(y)
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    return main, startup, loss


@pytest.mark.parametrize("kind", ["executor", "parallel_executor"])
def test_executor_run_leaves_its_three_phases(tmp_path, kind):
    if kind == "parallel_executor" and len(jax.devices()) < 4:
        pytest.skip("needs four virtual CPU devices (tests/conftest.py)")
    main, startup, loss = _toy_program()
    feed = {"x": np.ones((8, 8), "float32")}
    scope = Scope()
    with scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        if kind == "executor":
            step = lambda: exe.run(main, feed=feed, fetch_list=[loss.name])
        else:
            from paddle_tpu.parallel import MeshConfig

            pe = fluid.ParallelExecutor(
                loss_name=loss.name, main_program=main, scope=scope,
                mesh_config=MeshConfig(dp=4), devices=jax.devices()[:4])
            step = lambda: pe.run(fetch_list=[loss.name], feed=feed)
        step()  # compile outside the session
        with Session(tmp_path / "prof") as prof:
            for _ in range(3):
                step()
    runs = prof.named("executor.run")
    assert len(runs) == 3
    iters = [r["tags"]["iter"] for r in runs]
    assert iters == list(range(iters[0], iters[0] + 3))  # the running count
    for r in runs:
        leaves = sorted((s for s in prof.spans
                         if s["tags"].get("iter") == r["tags"]["iter"]
                         and s["name"] != "executor.run"),
                        key=lambda s: s["start"])
        assert [s["name"] for s in leaves] == [
            "executor.prepare", "executor.call", "executor.finish"]
        assert all(inside(s, r) for s in leaves)
        for a, b in zip(leaves, leaves[1:]):
            assert a["end"] <= b["start"]


def test_outputs_are_bit_identical_with_a_session_on_and_null_span_stays(tmp_path):
    eng = make_engine("bitgen")
    prompt = [3, 7, 11, 2, 5]

    def generate():
        res = eng.generate(prompt, max_new_tokens=6, eos_id=NO_EOS)
        return res.tokens, np.array(eng.last_logits), np.array(
            eng.last_prefill_logits)

    off = generate()
    assert profiler.watch_gc() in gc.callbacks  # the collection hook is on
    with Session(tmp_path / "prof") as prof:
        on = generate()
    # the session did record the run, an admission's spans among the rest
    assert prof.named("engine.wait") and prof.named("engine.admit")
    assert prof.named("prefix.lookup") and prof.named("prefix.insert")
    assert on[0] == off[0]
    assert on[1].tobytes() == off[1].tobytes()
    assert on[2].tobytes() == off[2].tobytes()
    # no profiler session and no FLAGS_trace_dir: the request path's span is
    # the shared no-op, by identity
    assert not _flags.get_flags("trace_dir")["trace_dir"]
    assert tracing.tracer().start_span("serving.genrequest") is tracing.NULL_SPAN


def test_record_event_observes_wall_and_stall_with_no_session():
    reg = registry.MetricRegistry()
    wall, stall = reg.histogram("wall_ms"), reg.histogram("stall_ms")
    with profiler.RecordEvent("test.sleep", hist=wall, stall=stall, iter=1) as ev:
        time.sleep(0.02)  # off the CPU: nearly all of it is stall
    snap = reg.snapshot()
    assert snap["wall_ms"]["count"] == snap["stall_ms"]["count"] == 1
    assert ev.ms == snap["wall_ms"]["sum"] >= 20.0
    assert 0.5 * ev.ms <= snap["stall_ms"]["sum"] <= ev.ms
    with profiler.RecordEvent("test.spin", stall=stall) as spin:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 0.02:
            pass  # on the CPU: a smaller share of it is stall, on any host
    spin_stall = reg.snapshot()["stall_ms"]["sum"] - snap["stall_ms"]["sum"]
    assert spin_stall / spin.ms < snap["stall_ms"]["sum"] / ev.ms
    assert not profiler._events  # the reference table records only when on


def test_the_profiler_table_keeps_its_rows():
    """The phase spans add no row to start_profiler()'s table and no level
    to the rows nested in them (tools/timeline.py reads those names)."""
    main, startup, loss = _toy_program()
    feed = {"x": np.ones((8, 8), "float32")}
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        profiler.reset_profiler()
        profiler.start_profiler()
        try:
            for _ in range(2):
                exe.run(main, feed=feed, fetch_list=[loss.name])
            with profiler.RecordEvent(span="test.span_only"):
                with profiler.RecordEvent("row"):
                    pass
        finally:
            table = profiler.stop_profiler(profile_path=None)
            profiler.reset_profiler()
    assert set(table) == {"prepare/block0", "run/block0", "row"}
    assert table["prepare/block0"][0] == 1 and table["run/block0"][0] == 2


@pytest.fixture()
def trace_dir(tmp_path):
    old = _flags.get_flags(["trace_dir", "trace_sample"])
    _flags.set_flags({"trace_dir": str(tmp_path / "traces"), "trace_sample": 1.0})
    tracing.reset()
    try:
        yield str(tmp_path / "traces")
    finally:
        tracing.reset()
        _flags.set_flags(old)
        tracing.reset()


def test_request_spans_carry_host_ms_and_the_iteration(trace_dir):
    """engine.decode / engine.prefill: the mislabelled device_ms tag is
    host_ms, with the leaves' dispatch_ms and wait_ms beside it, and `iter`
    joins the request's trace to the scheduler iteration that served it."""
    eng = make_engine("taggen")
    sched = GenerationScheduler(eng, timeout_ms=60000.0)
    try:
        sched.submit([4, 5, 6, 7, 8], max_new_tokens=4, eos_id=NO_EOS).result(60.0)
    finally:
        sched.close()
    tracing.reset()
    spans = tracing.load_spans(trace_dir)
    decode = [s for s in spans if s["name"] == "engine.decode"]
    prefill = [s for s in spans if s["name"] == "engine.prefill"]
    assert decode and prefill
    for s in decode:
        assert {"host_ms", "dispatch_ms", "wait_ms", "iter"} <= set(s["tags"])
        assert s["tags"]["dispatch_ms"] + s["tags"]["wait_ms"] <= s["tags"]["host_ms"] + 1e-3
    for s in prefill:
        assert {"host_ms", "dispatch_ms", "iter"} <= set(s["tags"])
    assert not any("device_ms" in s["tags"] for s in spans)
    iters = [s["tags"]["iter"] for s in prefill + decode]
    assert iters == sorted(iters) and iters[0] >= 1


def test_generation_engine_compiled_hlo_is_every_variant_s_text():
    eng = make_engine("hlogen")
    texts = eng.compiled_hlo()
    assert set(texts) == {"decode"} | {
        "prefill:%d" % b for b in eng.prefill_buckets}
    assert all("HloModule" in t for t in texts.values())
    assert "paged_attention" in texts["decode"]  # op_name metadata rides along


def test_executor_memory_plan_is_the_last_run_s_memory_analysis():
    main, startup, loss = _toy_program()
    with scope_guard(Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        with pytest.raises(RuntimeError, match="memory_plan"):
            exe.memory_plan()
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((8, 8), "float32")},
                fetch_list=[loss.name])
        plan = exe.memory_plan()
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
        assert getattr(plan, field) >= 0
    assert plan.argument_size_in_bytes >= 8 * 8 * 4  # the feed alone


# ---- the rare, long things: an admission, the device run dry, a collection,
# a pass that stalls

def hist(name):
    snap = registry.default_registry().snapshot()[name]
    return snap["count"], snap["sum"]


def test_an_admission_leaves_its_spans_where_the_work_happens(tmp_path):
    """fluid.engine.admit holds the prefix lookup; the last chunk's
    fluid.engine.sample holds the insert, and the insert of a trie at its
    cap the eviction pass."""
    eng = make_engine("admitgen")
    eng.prefix_cache.capacity_pages = 1  # the second prompt's page finds it full
    runs = []
    try:
        with Session(tmp_path / "prof") as prof:
            for i, first in enumerate((1, 7)):
                eng.iter = i + 1
                runs.append(eng.start(GenRequest(
                    list(range(first, first + 6)), max_new_tokens=2,
                    eos_id=NO_EOS)))
    finally:
        for r in runs:
            eng.finish(r)
    admits, lookups = prof.named("engine.admit"), prof.named("prefix.lookup")
    assert len(admits) == len(lookups) == 2
    for lookup, admit in zip(lookups, admits):
        assert inside(lookup, admit)
        assert lookup["tags"]["iter"] == admit["tags"]["iter"]
    samples = [s for s in prof.named("engine.sample")
               if s["tags"]["kind"] == "prefill"]
    inserts = prof.named("prefix.insert")
    assert len(samples) == len(inserts) == 2
    for insert, sample in zip(inserts, samples):
        assert inside(insert, sample)
    (evict,) = prof.named("prefix.evict")  # the first insert found room
    assert inside(evict, inserts[1])
    assert [s["tags"]["iter"] for s in inserts] == [1, 2]


def test_the_new_histograms_count_with_no_session(monkeypatch):
    monkeypatch.setattr(generation, "SLOW_PASS_MS", 0.0)
    eng = make_engine("raregen")
    eng.prefix_cache.capacity_pages = 1
    sched = GenerationScheduler(eng, timeout_ms=60000.0)
    try:
        for first in (1, 7, 13):
            fut = sched.submit(list(range(first, first + 6)),
                               max_new_tokens=5, eos_id=NO_EOS)
            assert len(fut.result(60.0).tokens) == 5
    finally:
        sched.close()
    gc.collect()
    snap = registry.default_registry().snapshot()
    prompts = 3
    for name, least in (
            ("gen_admit_ms", prompts), ("prefix_lookup_ms", prompts),
            ("prefix_insert_ms", prompts), ("prefix_evict_ms", prompts - 1),
            ("gen_prefill_wait_ms", prompts),
            ("gen_prefill_sample_ms", prompts),
            # each prompt's last chunk is read with nothing behind it
            ("gen_device_starved_ms", prompts),
            ("gen_dispatch_found_dry", prompts),
            ("sched_slow_iter_ms", prompts)):
        h = snap["serving/raregen/" + name]
        assert h["count"] >= least and h["sum"] >= 0, (name, h)
    assert snap["serving/raregen/gen_admit_ms"]["count"] == prompts
    assert snap["serving/raregen/gen_dispatch_found_dry"]["buckets"] == [0, 1]
    found = snap["serving/raregen/gen_dispatch_found_dry"]
    assert 0 <= found["sum"] <= found["count"]
    assert (snap["serving/raregen/sched_slow_iter_ms"]["count"]
            == snap["serving/raregen/sched_iter_ms"]["count"])
    assert snap["host/gc_pause_ms"]["count"] >= 1


def test_the_engine_counts_the_device_run_dry_once_and_not_under_a_step():
    """Dispatch, read, dispatch: gen_device_starved_ms observes at the
    second dispatch, and only when the read was of the program dispatched
    last; gen_dispatch_found_dry observes once a pipelined dispatch."""
    eng = make_engine("drygen")
    starved = lambda: hist("serving/drygen/gen_device_starved_ms")[0]
    found = lambda: hist("serving/drygen/gen_dispatch_found_dry")[0]
    # the prompt's one chunk is dispatched and read: the device has nothing
    run = eng.start(GenRequest([1, 2, 3, 4, 5], max_new_tokens=8, eos_id=NO_EOS))
    try:
        assert (starved(), found()) == (0, 0)
        eng.decode_step([run], ahead=True)  # step 1 out: the dry spell ends
        assert (starved(), found()) == (1, 0)
        assert eng.steps_in_flight == 1
        # step 2 goes out before step 1 is read: a pipelined dispatch, and
        # the read that follows is not of the program dispatched last
        eng.decode_step([run], ahead=True)
        eng.decode_step([run], ahead=True)
        assert (starved(), found()) == (1, 2)
        eng.drain("idle")  # step 3 read, none behind it: dry again
        assert starved() == 1
        eng.decode_step([run], ahead=True)
        assert (starved(), found()) == (2, 2)
        eng.drain("idle")
    finally:
        eng.finish(run)
    h = registry.default_registry().snapshot()[
        "serving/drygen/gen_device_starved_ms"]
    assert h["count"] == 2 and h["sum"] > 0


def test_a_collection_is_one_span_and_one_count_and_the_hook_installs_once(
        tmp_path):
    watch = profiler.watch_gc()
    assert profiler.watch_gc() is watch
    assert gc.callbacks.count(watch) == 1
    gc.disable()  # no collection but the one asked for
    try:
        count, ms = hist("host/gc_pause_ms")
        seen = watch.count
        with Session(tmp_path / "prof") as prof:
            gc.collect()
        (span,) = prof.named("host.gc")
        assert span["tags"]["generation"] == 2
        assert watch.count == seen + 1 and watch.pending
        after = hist("host/gc_pause_ms")  # the snapshot folds what is pending
        assert after[0] == count + 1 and after[1] > ms
        assert not watch.pending
    finally:
        gc.enable()


class _CollectingLock:
    """The registry's lock, with a collection forced while it is held: what
    happens when the interpreter decides to collect inside an observe."""

    def __init__(self, lock):
        self.lock = lock

    def __enter__(self):
        self.lock.acquire()
        gc.collect()

    def __exit__(self, *exc):
        self.lock.release()


def test_a_collection_inside_an_observe_does_not_take_the_registry_s_lock():
    watch = profiler.watch_gc()
    reg = registry.default_registry()
    h = reg.histogram("test/collected_inside_observe")
    gc.disable()  # no collection but the one inside the observe
    try:
        h._lock = _CollectingLock(reg._lock)
        seen, before = watch.count, hist("host/gc_pause_ms")[0]
        t = threading.Thread(target=h.observe, args=(1.0,), daemon=True)
        t.start()
        t.join(30.0)
        assert not t.is_alive()  # the callback did not wait for the lock
        assert not reg._lock.locked()
        assert watch.count == seen + 1
        # the pause was kept aside, and a reader finds it folded
        assert hist("host/gc_pause_ms")[0] == before + 1
        assert h.count == 1
    finally:
        gc.enable()
        reg.remove("test/collected_inside_observe")


SLOW_PASS_FIELDS = (
    "model", "iter", "ms", "lock_ms", "admit_ms", "prefill_ms", "decode_ms",
    "retire_ms", "host_stall_ms", "gc_ms", "admitted", "chunks",
    "prefill_wait_ms", "prefill_sample_ms", "decode_dispatch_ms",
    "decode_wait_ms", "prefix_evict_ms", "live", "queued", "held_back")


def test_a_slow_pass_leaves_one_line_and_holds_the_next_back(
        monkeypatch, capsys):
    monkeypatch.setattr(generation, "SLOW_PASS_MS", 0.0)  # every pass is slow
    eng = make_engine("slowgen")
    sched = GenerationScheduler(eng, timeout_ms=60000.0)
    try:
        fut = sched.submit([3, 1, 4, 1, 5], max_new_tokens=6, eos_id=NO_EOS)
        assert len(fut.result(60.0).tokens) == 6
    finally:
        sched.close()
    lines = [l for l in capsys.readouterr().err.splitlines()
             if l.startswith("[fluid.slow_pass] ")]
    records = [dict(kv.split("=", 1) for kv in l.split(" ")[1:]) for l in lines]
    assert records and all(tuple(r) == SLOW_PASS_FIELDS for r in records)
    first = records[0]
    assert first["model"] == "slowgen" and first["held_back"] == "0"
    # the first pass admitted the prompt and ran its one chunk
    assert (first["iter"], first["admitted"], first["chunks"]) == ("1", "1", "1")
    assert float(first["ms"]) >= float(first["prefill_ms"]) > 0
    # the one chunk was the prompt's last: its read and its first token
    assert float(first["prefill_ms"]) >= float(first["prefill_wait_ms"]) > 0
    assert float(first["prefill_sample_ms"]) > 0
    passes, _ = hist("serving/slowgen/sched_slow_iter_ms")
    assert passes == hist("serving/slowgen/sched_iter_ms")[0] >= 6
    # one line a second: every pass is counted, and each is either a line's
    # own, one a later line says it held back, or still held
    assert len(lines) < passes
    assert passes == len(lines) + sched._slow_held_back + sum(
        int(r["held_back"]) for r in records)
