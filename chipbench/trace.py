"""From a jax.profiler trace to numbers: device busy time as the union of the
intervals in which an operation ran, the idle gaps between them labelled by
what the host was doing (the benchmark's own TraceAnnotation spans), and the
device time of the operations a pattern names. Plain functions over lists of
(name, start_ns, duration_ns), so a small synthetic list checks them; only
reduce_dir touches the profiler's file format."""

import bisect
import collections
import glob
import os
import re
import shutil

from . import data

SPAN_PREFIX = "chipbench."
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"


class CompileCounter:
    """Counts backend compilations through JAX's own monitoring event, not
    through a counter of the program under test."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self._on = False

    def _listen(self, event, duration, **_):
        if self._on and event == self.EVENT:
            self.count += 1

    def start(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        self._on = True

    def stop(self):
        # jax.monitoring has no public way to drop one listener; the flag
        # makes the one this object registered inert
        self._on = False


def start(cell_name):
    """Start the profiler into an emptied directory of the cell's own and
    return the directory. The Python tracer is off: it hooks every Python
    call and would slow the very host code whose gaps the trace is read
    for; TraceAnnotation spans are recorded all the same."""
    import jax

    d = os.path.join(data.SCRATCH, "trace", cell_name)
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=options)
    return d


# ---------------------------------------------------------------- intervals


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_and_span(events):
    """(busy ns, span ns, merged intervals) of one device's events: busy is
    the union of the intervals, span runs from the first start to the last
    end."""
    merged = union((s, s + d) for _, s, d in events)
    if not merged:
        return 0.0, 0.0, merged
    busy = sum(e - s for s, e in merged)
    return busy, merged[-1][1] - merged[0][0], merged


def idle_gaps(merged, host_spans):
    """[[label, seconds]] over the gaps between merged device intervals, each
    gap labelled by the innermost chipbench span that holds its midpoint."""
    by_label = collections.Counter()
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        mid = (e0 + s1) / 2.0
        holding = [
            (d, n) for n, s, d in host_spans if s <= mid < s + d
        ]
        label = min(holding)[1] if holding else "outside_chipbench_spans"
        by_label[label] += (s1 - e0) / 1e9
    return [[n, s] for n, s in by_label.most_common(10)]


# -------------------------------------------------------------------- names


def hlo_op_names(hlo_text):
    """{instruction name: op_name metadata} of a compiled module's text. The
    program's lowering writes '<op type>/out=<var>' and
    'pallas_kernel=<family>.<group>' scopes into op_name."""
    out = {}
    pat = re.compile(
        r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name="([^"]*)"')
    for line in (hlo_text or "").splitlines():
        m = pat.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def category(name, op_name):
    """A readable name for a device operation: the Pallas family, else the
    framework op type that emitted it, else the HLO instruction's opcode."""
    m = re.search(r"pallas_kernel=([A-Za-z0-9_]+)\.", op_name or "")
    if m:
        return "pallas:" + m.group(1)
    segs = (op_name or "").split("/")
    for i, seg in enumerate(segs):
        if seg.startswith("out=") and i:
            return "op:" + segs[i - 1]
    return "hlo:" + re.sub(r"[.\d]+$", "", name)


def pattern_seconds(events, pattern):
    """Summed device seconds and count of the events whose text (name,
    op_name, category) matches the regular expression."""
    rx = re.compile(pattern)
    hit = [d for text, _, d in events if rx.search(text)]
    return sum(hit) / 1e9, len(hit)


# ------------------------------------------------------------------- reduce


def reduce_events(device_events, host_spans, chips):
    """device_events: {device index: [(text, start_ns, duration_ns)]};
    host_spans: [(name, start_ns, duration_ns)]. Busy and span are averaged
    over the `chips` lowest-numbered devices; patterns, top operations and
    gaps are read on device 0."""
    used = sorted(device_events)[:chips]
    if not used:
        raise RuntimeError("the trace holds no device operation")
    per = [busy_and_span(device_events[i]) for i in used]
    first = device_events[used[0]]
    top = collections.Counter()
    for text, _, d in first:
        top[text.split(" ", 1)[0]] += d / 1e9
    return {
        "busy_s": sum(p[0] for p in per) / len(per) / 1e9,
        "window_s": max(p[1] for p in per) / 1e9,
        "events": first,
        "breakdown": {
            "device_ops": [[n, s] for n, s in top.most_common(10)],
            "idle_gaps": idle_gaps(per[0][2], host_spans),
        },
    }


_OPCODE = re.compile(r"\s([a-z][a-z\-]*)\(")


def instr_and_opcode(event_name):
    """('fusion.3', 'fusion') from an 'XLA Ops' event, whose name is the
    instruction's whole text: '%fusion.3 = bf16[8,16]{1,0:T(8,128)}
    fusion(...)'. A Pallas kernel's opcode is 'custom-call' and its
    instruction is named after the variable it writes."""
    instr, _, rest = event_name.lstrip("%").partition(" ")
    m = _OPCODE.search(rest)
    return instr, m.group(1) if m else ""


def name_ops(ops, modules, op_name_maps):
    """[(text, start, duration)] for one device's operations, text being
    'category instruction opcode op_name'. `ops` is
    [((instruction, opcode), start, dur)],
    `modules` the [(module name, start, dur)] of the 'XLA Modules' line and
    `op_name_maps` one {instruction: op_name} a compiled program. Two
    programs reuse instruction names ('fusion.3'), so each traced module is
    given the map that knows most of the instructions seen inside it."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    seen = collections.defaultdict(set)
    owner = []
    for (instr, _), s, _ in ops:
        k = bisect.bisect_right(starts, s) - 1
        mod = modules[k][0] if k >= 0 and s < modules[k][1] + modules[k][2] else None
        owner.append(mod)
        seen[mod].add(instr)
    empty = {}
    best = {
        mod: max(op_name_maps, key=lambda m: len(instrs & m.keys()), default=empty)
        for mod, instrs in seen.items()
    }
    out = []
    for ((instr, opcode), s, d), mod in zip(ops, owner):
        op_name = best[mod].get(instr, "")
        out.append(("%s %s %s %s" % (
            category(instr, op_name), instr, opcode, op_name), s, d))
    return out


def reduce_dir(log_dir, hlo_texts, chips):
    """Reduce the newest profile under log_dir. `hlo_texts` are the compiled
    programs' texts (Executor.compiled_hlo(), a variant's as_text()), read
    only for their op_name metadata."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(
        os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise RuntimeError("the profiler wrote no xplane.pb under %s" % log_dir)
    maps = [hlo_op_names(t) for t in hlo_texts]
    device_events, host_spans = {}, []
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            m = _DEVICE_PLANE.match(plane.name)
            if m:
                lines = {line.name: line for line in plane.lines}
                if _OPS_LINE not in lines:
                    continue
                ops = [(instr_and_opcode(ev.name), ev.start_ns, ev.duration_ns)
                       for ev in lines[_OPS_LINE].events]
                modules = [(ev.name, ev.start_ns, ev.duration_ns)
                           for ev in getattr(lines.get(_MODULES_LINE), "events", ())]
                device_events.setdefault(int(m.group(1)), []).extend(
                    name_ops(ops, modules, maps))
            else:
                for line in plane.lines:
                    host_spans.extend(
                        (ev.name, ev.start_ns, ev.duration_ns)
                        for ev in line.events
                        if ev.name.startswith(SPAN_PREFIX)
                    )
    return reduce_events(device_events, host_spans, chips)
