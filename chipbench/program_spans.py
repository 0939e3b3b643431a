"""The program's own spans, the `fluid.` ones that paddle_tpu.profiler's
RecordEvent writes into jax.profiler's trace, read from the run's own profile
(the accepted reduction keeps only the benchmark's `chipbench.` spans), and
the device's idle gaps credited to them. Plain functions over lists of
(name, start_ns, duration_ns), so a small synthetic list checks them; only
`load` touches the profiler's file format. A program that writes no such
span (one older than the spans) reads as nothing, not as a fault."""

import bisect
import glob
import os
import sys

from . import data, trace

PREFIX = "fluid."


def newest_profile():
    """The newest *.xplane.pb under the traces the runners write, or None."""
    paths = glob.glob(os.path.join(data.SCRATCH, "trace", "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def load():
    """[(name, start_ns, duration_ns)] of the `fluid.` host spans in the
    newest profile, names cut at a '#' (a TraceAnnotation's tags may ride in
    the event's name). None, and a line on stderr, when there is no
    profile."""
    path = newest_profile()
    if path is None:
        print("chipbench: no *.xplane.pb under %s: the program's spans are "
              "not read" % os.path.join(data.SCRATCH, "trace"),
              file=sys.stderr, flush=True)
        return None
    from jax.profiler import ProfileData

    return [
        (ev.name.split("#", 1)[0], ev.start_ns, ev.duration_ns)
        for plane in ProfileData.from_file(path).planes
        if not trace._DEVICE_PLANE.match(plane.name)
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith(PREFIX)
    ]


def of(run):
    """The program's spans of a traced run, read from its profile once and
    kept with the run's trace; None for an untraced run or a run that left
    no profile."""
    tr = run.get("trace")
    if not tr:
        return None
    if "program_spans" not in tr:
        tr["program_spans"] = load()
    return tr["program_spans"]


def durations_ms(host_spans, name):
    return [d / 1e6 for n, _, d in host_spans if n == name]


def innermost(host_spans):
    """Sorted, disjoint [(start, end, name)]: over each stretch between two
    span boundaries, the shortest span that holds it (the innermost one, as
    trace.idle_gaps picks), so a wrapper keeps only its self time."""
    live = sorted((s, s + d, n) for n, s, d in host_spans if d > 0)
    edges = sorted({t for s, e, _ in live for t in (s, e)})
    out, held, k = [], [], 0
    for t0, t1 in zip(edges, edges[1:]):
        while k < len(live) and live[k][0] <= t0:
            s, e, n = live[k]
            held.append((e - s, e, n))
            k += 1
        held = [h for h in held if h[1] > t0]
        if held:
            out.append((t0, t1, min(held)[2]))
    return out


def idle_gaps(device_events):
    """Device 0's idle gaps [(start, end)]: between the merged intervals of
    its operations, the definition the trace_idle reader has."""
    merged = trace.union((s, s + d) for _, s, d in device_events)
    return [(e0, s1) for (_, e0), (s1, _) in zip(merged, merged[1:]) if s1 > e0]


def credit(gaps, host_spans):
    """({span name: ns}, summed ns of the gaps): every instant of a gap
    credited to the innermost span that holds it, by overlap; what no span
    holds is credited to nobody."""
    segs = innermost(host_spans)
    starts = [s for s, _, _ in segs]
    by_name, total = {}, 0
    for g0, g1 in gaps:
        total += g1 - g0
        k = max(bisect.bisect_right(starts, g0) - 1, 0)
        while k < len(segs) and segs[k][0] < g1:
            s, e, n = segs[k]
            over = min(e, g1) - max(s, g0)
            if over > 0:
                by_name[n] = by_name.get(n, 0) + over
            k += 1
    return by_name, total
