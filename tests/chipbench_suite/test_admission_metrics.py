"""PR 35's per-layer metrics: the reader trace_idle_under_span on synthetic
lists (a gap half under the span, nested spans counted once, no such span),
and every metric file the PR adds: it loads, names a reader that exists and
a layer and cells BENCHMARK.json has, and reads None, not a fault, on a run
of a program that has neither the histogram nor the span (the parent's)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import data  # noqa: E402

# device 0 is busy over [0, 100) and [300, 400): one idle gap, [100, 300)
DEVICE = [("op:mul fusion.1 fusion x", 0, 100),
          ("op:paged_attention out.3 custom-call x", 300, 100)]
TRACE = {"events": DEVICE, "busy_s": 200e-9, "window_s": 400e-9}

SERVING_CELLS = ["gpt2l_chat", "lfm2moe_chat", "xing4_docs_chat",
                 "granite4h_burst_chat"]
# metric -> (reader, the histogram or the spans it reads)
NEW_METRICS = {
    "serve.sched_prefill_share": ("histogram_window_share", "sched_prefill_ms"),
    "serve.sched_decode_share": ("histogram_window_share", "sched_decode_ms"),
    "serve.engine_admit_mean_ms": ("histogram_window_mean", "gen_admit_ms"),
    "serve.prefix_lookup_mean_ms": ("histogram_window_mean", "prefix_lookup_ms"),
    "serve.prefix_insert_mean_ms": ("histogram_window_mean", "prefix_insert_ms"),
    "serve.prefix_evict_share": ("histogram_window_share", "prefix_evict_ms"),
    "serve.prefill_chunk_host_mean_ms": ("histogram_window_mean", "gen_prefill_ms"),
    "serve.prefill_wait_mean_ms": ("histogram_window_mean", "gen_prefill_wait_ms"),
    "serve.prefill_sample_mean_ms": (
        "histogram_window_mean", "gen_prefill_sample_ms"),
    "serve.device_starved_share": (
        "histogram_window_share", "gen_device_starved_ms"),
    "serve.decode_found_dry_mean": (
        "histogram_window_mean", "gen_dispatch_found_dry"),
    "serve.gc_pause_share": ("histogram_window_share", "host/gc_pause_ms"),
    "serve.slow_pass_share": ("histogram_window_share", "sched_slow_iter_ms"),
    "serve.idle_in_prefix_share": ("trace_idle_in_spans", "fluid.prefix."),
    "serve.idle_in_gc_share": ("trace_idle_in_spans", "fluid.host.gc"),
    "serve.idle_under_prefill_share": (
        "trace_idle_under_span", "fluid.sched.prefill"),
    "serve.idle_under_decode_share": (
        "trace_idle_under_span", "fluid.sched.decode"),
}


def traced(host_spans):
    return {"trace": dict(TRACE, program_spans=host_spans)}


def under(span, run):
    return data.module("readers", "trace_idle_under_span").read(
        {"span": span}, run)


def test_a_gap_half_under_the_span_reads_50():
    run = traced([("fluid.sched.prefill", 50, 150)])  # [50, 200): half the gap
    assert under("fluid.sched.prefill", run) == pytest.approx(50.0)


def test_what_nests_inside_the_span_takes_nothing_from_it_and_counts_once():
    run = traced([
        ("fluid.sched.iter", 90, 200),       # [90, 290)
        ("fluid.sched.prefill", 120, 100),   # [120, 220), inside iter
        ("fluid.engine.wait", 150, 40),      # [150, 190), inside prefill
        ("fluid.prefix.insert", 195, 20),    # [195, 215), inside prefill
        ("fluid.sched.prefill", 210, 40),    # [210, 250): overlaps the first
        ("fluid.sched.prefill", 600, 50),    # far from any gap
    ])
    # the union of the three prefill spans inside the gap: [120, 250)
    assert under("fluid.sched.prefill", run) == pytest.approx(100.0 * 130 / 200)
    assert under("fluid.engine.wait", run) == pytest.approx(100.0 * 40 / 200)
    assert under("fluid.sched.iter", run) == pytest.approx(100.0 * 190 / 200)


def test_several_gaps_and_several_spans_are_walked_in_order():
    run = traced([("fluid.sched.decode", 80, 40),    # [80, 120): 20 of gap 1
                  ("fluid.sched.decode", 280, 300),  # [280, 580): 20 + 100
                  ("fluid.sched.decode", 590, 5)])   # inside an operation
    run["trace"]["events"] = DEVICE + [("op:mul fusion.9 fusion x", 500, 100)]
    # gaps [100, 300) and [400, 500): 300 ns of idle, 140 under the spans
    assert under("fluid.sched.decode", run) == pytest.approx(100.0 * 140 / 300)


def test_no_such_span_no_trace_or_no_idle_reads_none():
    run = traced([("fluid.sched.iter", 90, 200)])
    assert under("fluid.sched.prefill", run) is None
    assert under("fluid.sched.prefill", traced([])) is None
    assert under("fluid.sched.prefill", {}) is None  # an untraced run
    never_idle = traced([("fluid.sched.prefill", 0, 10)])
    never_idle["trace"]["events"] = DEVICE[:1]
    assert under("fluid.sched.prefill", never_idle) is None


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_new_metric_s_file_loads_and_reads_none_without_its_source(name):
    reader, source = NEW_METRICS[name]
    spec = data.named("layer_metrics", name)
    assert spec["reader"] == reader
    assert source in str(spec["args"])
    (entry,) = [m for m in data.manifest()["per_layer"] if m["name"] == name]
    assert entry["workloads"] == SERVING_CELLS
    assert entry["moves"] == spec["moves"] == "serve_ms_per_token_p50"
    assert (entry["layer"], entry["unit"], entry["source"]) == (
        spec["layer"], spec["unit"], spec["source"])
    # the layer is one the benchmark already named, letter for letter
    assert entry["layer"] in {
        m["layer"] for m in data.manifest()["per_layer"]
        if m["name"] not in NEW_METRICS}
    read = data.module("readers", reader).read
    # a run of a program older than this PR: a window and a model's name,
    # other histograms, a profile whose fluid. spans are the scheduler's
    # five phases' wrapper only
    old = dict(traced([("fluid.sched.iter", 90, 200)]),
               names={"model": "m"}, window={"seconds": 50.0},
               histograms={"serving/m/sched_admit_ms": ((0.0, 0), (5.0, 9))})
    got = read(spec["args"], old)
    # the innermost-span reader credits 0 to a name the profile lacks
    assert got is None or (reader == "trace_idle_in_spans" and got == 0.0)
    # nothing collected at all
    assert read(spec["args"], {"names": {"model": "m"}}) is None
