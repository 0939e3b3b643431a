"""The readers of the program's own phase spans and histograms, on synthetic
lists: histogram_window_share, trace_span_stat, trace_idle_in_spans (idle
gaps credited to the innermost fluid. span by overlap, the complement, a
program with no spans, a run with no profile)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import data, program_spans  # noqa: E402

# device 0 is busy over [0, 100) and [300, 400): one idle gap, [100, 300)
DEVICE = [("op:mul fusion.1 fusion x", 0, 60), ("op:mul fusion.2 fusion x", 50, 50),
          ("op:paged_attention out.3 custom-call x", 300, 100)]
TRACE = {"events": DEVICE, "busy_s": 200e-9, "window_s": 400e-9}


def traced(host_spans):
    """A run whose profile held these spans: they stand where the readers
    keep what they read from the profile."""
    return {"trace": dict(TRACE, program_spans=host_spans)}


def reader(name):
    return data.module("readers", name).read


def test_a_gap_half_covered_by_a_span_reads_50_and_the_complement_the_rest():
    read = reader("trace_idle_in_spans")
    run = traced([("fluid.sched.admit", 50, 150)])  # [50, 200): half the gap
    assert read({"spans": ["fluid.sched.admit"]}, run) == pytest.approx(50.0)
    assert read({"spans": ["fluid.sched.retire"]}, run) == 0.0
    assert read({"complement": True}, run) == pytest.approx(50.0)


def test_a_wrapper_is_credited_with_its_self_time_only():
    read = reader("trace_idle_in_spans")
    run = traced([
        ("fluid.sched.iter", 90, 200),      # [90, 290): holds 190 of the gap
        ("fluid.sched.decode", 120, 100),   # [120, 220), inside iter
        ("fluid.engine.wait", 150, 40),     # [150, 190), inside decode
        ("fluid.engine.wait", 600, 50),     # far from any gap
    ])
    share = lambda *names: read({"spans": list(names)}, run)
    assert share("fluid.engine.wait") == pytest.approx(100.0 * 40 / 200)
    assert share("fluid.sched.decode") == pytest.approx(100.0 * 60 / 200)
    assert share("fluid.sched.iter") == pytest.approx(100.0 * 90 / 200)
    assert share("fluid.sched.iter", "fluid.sched.decode") == pytest.approx(75.0)
    assert read({"complement": True}, run) == pytest.approx(100.0 * 10 / 200)
    # the shares of every name and the complement make up the whole
    assert (share("fluid.sched.iter", "fluid.sched.decode", "fluid.engine.wait")
            + read({"complement": True}, run)) == pytest.approx(100.0)


def test_spans_off_the_device_s_clock_credit_nothing():
    """The toy traced runs: a real CPU profile beside a made-up device."""
    read = reader("trace_idle_in_spans")
    run = traced([("fluid.sched.iter", 10 ** 9, 5000)])
    assert read({"spans": ["fluid.sched.iter"]}, run) == 0.0
    assert read({"complement": True}, run) == 100.0


def test_nothing_to_read_is_none_not_a_fault(tmp_path, monkeypatch, capsys):
    idle, stat = reader("trace_idle_in_spans"), reader("trace_span_stat")
    old = traced([])  # a program older than the spans: a profile, no fluid. span
    assert idle({"spans": ["fluid.sched.iter"]}, old) is None
    assert idle({"complement": True}, old) is None
    assert stat({"span": "fluid.executor.call", "q": 50}, old) is None
    assert idle({"complement": True}, {}) is None  # an untraced run
    assert stat({"span": "fluid.sched.iter"}, {}) is None
    never_idle = traced([("fluid.sched.iter", 0, 10)])
    never_idle["trace"]["events"] = DEVICE[:2]
    assert idle({"complement": True}, never_idle) is None
    # no profile under data.SCRATCH, looked up when called: None, and said so
    monkeypatch.setattr(data, "SCRATCH", str(tmp_path / "nothing_here"))
    no_profile = {"trace": dict(TRACE)}
    assert idle({"complement": True}, no_profile) is None
    assert stat({"span": "fluid.executor.call"}, no_profile) is None
    assert "no *.xplane.pb under" in capsys.readouterr().err


def test_span_stat_is_a_percentile_of_one_name_s_durations():
    read = reader("trace_span_stat")
    run = traced([
        ("fluid.executor.call", 0, 2_000_000), ("fluid.executor.call", 10, 4_000_000),
        ("fluid.executor.call", 20, 9_000_000), ("fluid.executor.prepare", 0, 500_000)])
    assert read({"span": "fluid.executor.call", "q": 50}, run) == pytest.approx(4.0)
    assert read({"span": "fluid.executor.call", "q": 100}, run) == pytest.approx(9.0)
    assert read({"span": "fluid.executor.prepare"}, run) == pytest.approx(0.5)
    assert read({"span": "fluid.executor.finish", "q": 50}, run) is None


def test_histogram_window_share_is_the_summed_ms_over_the_window_s_wall():
    read = reader("histogram_window_share")
    run = {"names": {"model": "m"}, "window": {"seconds": 50.0},
           "histograms": {"serving/m/sched_admit_ms": ((100.0, 7), (600.0, 90)),
                          "serving/m/sched_idle_ms": ((0.0, 0), (0.0, 0))}}
    args = {"histogram": "serving/{model}/sched_admit_ms"}
    assert read(args, run) == pytest.approx(100.0 * 500.0 / 50000.0)
    assert read({"histogram": "serving/{model}/sched_idle_ms"}, run) == 0.0
    assert read({"histogram": "serving/{model}/no_such_ms"}, run) is None
    assert read(args, {"names": {"model": "m"}}) is None


def test_the_profile_s_spans_are_read_from_a_real_session(tmp_path, monkeypatch):
    """load() on what jax.profiler writes: names under the prefix only, on
    the host planes, from the newest profile under data.SCRATCH/trace."""
    import time

    import jax
    from jax.profiler import TraceAnnotation

    monkeypatch.setattr(data, "SCRATCH", str(tmp_path))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace" / "cell"), profiler_options=options)
    try:
        with TraceAnnotation("fluid.sched.iter", iter=3):
            with TraceAnnotation("fluid.sched.admit", iter=3):
                time.sleep(0.002)
        with TraceAnnotation("chipbench.dispatch"):
            pass
    finally:
        jax.profiler.stop_trace()
    got = program_spans.load()
    assert sorted(n for n, _, _ in got) == ["fluid.sched.admit", "fluid.sched.iter"]
    by = {n: (s, d) for n, s, d in got}
    assert by["fluid.sched.admit"][1] >= 2_000_000
    assert by["fluid.sched.iter"][0] <= by["fluid.sched.admit"][0]
    segs = program_spans.innermost(got)
    assert [n for _, _, n in segs] == ["fluid.sched.iter", "fluid.sched.admit",
                                       "fluid.sched.iter"]
    assert program_spans.durations_ms(got, "fluid.sched.admit")[0] >= 2.0
