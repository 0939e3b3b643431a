"""Host-side event profiler + device trace hooks.

Reference analog: platform/profiler.{h,cc} (RecordEvent RAII pairs wrapping
every op run, EnableProfiler/DisableProfiler aggregation tables, sorted
summaries) + platform/device_tracer (CUPTI kernel timeline, correlated and
exported via tools/timeline.py into chrome://tracing) + python/paddle/fluid/
profiler.py:221 (the `with profiler.profiler(...)` context manager).

TPU-first redesign: the per-op interpreter is gone — blocks run as whole XLA
modules — so host events are per *phase* (program prepare/compile, XLA
segment runs, host RPC ops, feed/fetch), and the device-side story is XLA's
own profiler (`xla_trace` wraps jax.profiler.start_trace; view in
TensorBoard/xprof), replacing CUPTI. The aggregation-table surface
(start/stop/reset, sorted_key, chrome-trace export via tools/timeline.py) is
kept API-compatible.
"""

import contextlib
import gc
import json
import os
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = [
    "RecordEvent",
    "start_profiler",
    "stop_profiler",
    "reset_profiler",
    "profiler",
    "is_profiling",
    "xla_trace",
    "device_op_profile",
    "watch_gc",
]

_state = {"on": False, "mode": "All"}
_events = []  # (name, start_s, end_s, thread_id)
_events_lock = threading.Lock()
_tls = threading.local()


def is_profiling():
    return _state["on"]


SPAN_PREFIX = "fluid."


class RecordEvent:
    """RAII event (reference platform/profiler.h:66). Nesting is recorded via
    name stacking, like the reference's pushed event pairs.

    Every event is also a span named "fluid.<span or name>" in jax.profiler's
    trace (a TraceAnnotation carrying `tags`): with a profiler session on it
    lands on the host timeline of the xplane that holds the device's
    operations, one clock for both; with none it costs under a microsecond.
    The catalog of span names is docs/observability.md's. With `name` None
    the event is a span only: it adds no row to the start_profiler() table
    and no level to the rows nested in it. `hist` (a registry
    Histogram) observes the wall ms at exit and `stall` (anything with
    observe()) the part of it the calling thread was not on a CPU, wall minus
    time.thread_time(): for a phase that makes no blocking call, the time it
    waited for the interpreter lock, a mutex or the OS. Both observe whether a
    session is on or not, like every registry metric. `ms` is the wall time
    of the last exit."""

    def __init__(self, name=None, span=None, hist=None, stall=None, **tags):
        self.name = name
        self.ms = None
        self._ann = TraceAnnotation(SPAN_PREFIX + (span or name), **tags)
        self._hist = hist
        self._stall = stall
        self._start = None
        self._pushed = False

    def __enter__(self):
        if _state["on"] and self.name is not None:
            stack = getattr(_tls, "stack", None)
            if stack is None:
                stack = _tls.stack = []
            stack.append(self.name)
            self._pushed = True
        self._ann.__enter__()
        if self._stall is not None:
            self._cpu = time.thread_time()
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        self.ms = (end - self._start) * 1e3
        if self._stall is not None:
            cpu_ms = (time.thread_time() - self._cpu) * 1e3
            self._stall.observe(max(0.0, self.ms - cpu_ms))
        self._ann.__exit__(*exc)
        if self._hist is not None:
            self._hist.observe(self.ms)
        # pop whenever we pushed — profiling may have been stopped by another
        # thread mid-event, and a leaked stack entry would prefix every event
        # of the next session
        if self._pushed:
            stack = _tls.stack
            full = "/".join(stack)
            stack.pop()
            self._pushed = False
            if _state["on"]:
                with _events_lock:
                    _events.append((full, self._start, end, threading.get_ident()))
        return False


class _GcWatch:
    """The gc.callbacks hook: a span fluid.host.gc (tag `generation`) around
    every collection of the process, whichever thread it interrupts, and its
    pause in the registry histogram host/gc_pause_ms.

    A collection can start inside Histogram.observe, which holds the
    registry's one non-reentrant lock, so the callback never touches the
    registry: it adds to plain attributes (`ms` and `count`, every pause so
    far) and to `pending`, and fold() moves the pending pauses into the
    histogram from ordinary code (a scheduler pass's end, Executor.run's
    finish, the registry's snapshot())."""

    def __init__(self, hist):
        self.ms = 0.0
        self.count = 0
        self.pending = []
        self._hist = hist
        self._event = None

    def __call__(self, phase, info):
        if phase == "start":
            self._event = RecordEvent(
                span="host.gc", generation=info["generation"]).__enter__()
        elif self._event is not None:
            event, self._event = self._event, None
            event.__exit__(None, None, None)
            self.ms += event.ms
            self.count += 1
            self.pending.append(event.ms)

    def fold(self):
        # pop, never swap the list: the callback may append at any bytecode,
        # and another thread may be folding too
        while self.pending:
            try:
                ms = self.pending.pop()
            except IndexError:
                return
            self._hist.observe(ms)


_gc_watch = None
_gc_watch_lock = threading.Lock()


def watch_gc():
    """Install the collection hook, once a process however often it is called
    (a GenerationScheduler's start and an Executor's construction do), and
    return it. Nothing of the collector's behaviour changes."""
    global _gc_watch
    if _gc_watch is None:
        from .observability import registry as _registry

        reg = _registry.default_registry()
        hist = reg.histogram(
            "host/gc_pause_ms",
            "one garbage collection of the process (span fluid.host.gc), "
            "wall ms: every thread stands still for it",
        )
        with _gc_watch_lock:
            if _gc_watch is None:
                _gc_watch = _GcWatch(hist)
                gc.callbacks.append(_gc_watch)
                reg.before_snapshot(_gc_watch.fold)
    return _gc_watch


def reset_profiler():
    with _events_lock:
        _events.clear()


def start_profiler(state="All"):
    """state in {CPU, GPU, TPU, All} — kept for API parity; host events are
    recorded regardless, device tracing is xla_trace's job."""
    _state["mode"] = state
    _state["on"] = True


def _aggregate():
    table = {}
    with _events_lock:
        snapshot = list(_events)
    for name, start, end, _tid in snapshot:
        row = table.setdefault(name, [0, 0.0, float("inf"), 0.0])
        dt = (end - start) * 1000.0
        row[0] += 1
        row[1] += dt
        row[2] = min(row[2], dt)
        row[3] = max(row[3], dt)
    return table, snapshot


_SORT_KEYS = {
    None: lambda kv: 0,
    "default": lambda kv: 0,
    "calls": lambda kv: -kv[1][0],
    "total": lambda kv: -kv[1][1],
    "max": lambda kv: -kv[1][3],
    "min": lambda kv: -kv[1][2],
    "ave": lambda kv: -(kv[1][1] / kv[1][0]),
}


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    """Print the aggregation table (reference DisableProfiler's summary) and
    dump raw events for tools/timeline.py."""
    _state["on"] = False
    table, snapshot = _aggregate()
    rows = sorted(table.items(), key=_SORT_KEYS.get(sorted_key, _SORT_KEYS[None]))
    header = "%-50s %8s %12s %12s %12s %12s" % (
        "Event", "Calls", "Total(ms)", "Min(ms)", "Max(ms)", "Ave(ms)",
    )
    lines = ["------------------------->    Profiling Report    <-------------------------", header]
    for name, (calls, total, mn, mx) in rows:
        lines.append(
            "%-50s %8d %12.4f %12.4f %12.4f %12.4f"
            % (name[:50], calls, total, mn, mx, total / calls)
        )
    print("\n".join(lines))
    if profile_path:
        with open(profile_path, "w") as f:
            json.dump(
                {
                    "events": [
                        {"name": n, "start": s, "end": e, "tid": t}
                        for n, s, e, t in snapshot
                    ]
                },
                f,
            )
    return table


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile"):
    """`with profiler.profiler('All', 'total'):` (reference profiler.py:221)."""
    start_profiler(state)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def xla_trace(log_dir):
    """Device-side trace via XLA's profiler (the CUPTI device_tracer analog):
    writes a TensorBoard/xprof trace with per-HLO timing on TPU."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def cuda_profiler(*args, **kwargs):
    """API-compat shim for reference profiler.cuda_profiler (nvprof control);
    on TPU use xla_trace instead."""
    yield


def _hlo_op_attribution(hlo_text):
    """instruction name -> (op type, output var name or None), parsed from
    the compiled HLO's op_name metadata. registry.lower_ops emits
    '.../<op type>/out=<first output>/...' nested scopes, so the first
    non-wrapper segment is the op type and the segment after it (when it is
    an 'out=' tag) names the op INSTANCE; sub-block ops attribute to their
    enclosing control-flow op."""
    import re

    mapping = {}
    for m in re.finditer(r'%([\w.\-]+) = [^\n]*op_name="([^"]+)"', hlo_text):
        path = m.group(2).split("/")
        key = None
        out = None
        for i, seg in enumerate(path):
            # skip jit/transform wrappers, arg-pytree paths like
            # "feeds['img']" / "mut_state['w_0']" (donation copies — those
            # group under their HLO opcode instead), and the
            # fusion-group wrapper the fuse_elemwise_act pass adds (its
            # member ops carry their own type segments one level deeper)
            if (
                seg.startswith("jit(")
                or seg.startswith("transpose(")
                or seg.startswith("fusion_group=")
                or "[" in seg
            ):
                continue
            # a Pallas kernel-substitution scope ("pallas_kernel=
            # <family>.<gid>", registry._lower_pallas_run) replaces its
            # member ops' HLO wholesale: attribute to a "pallas:<family>"
            # row with the group id as the instance
            if seg.startswith("pallas_kernel="):
                tag = seg[len("pallas_kernel="):]
                fam, _, gid = tag.partition(".")
                key = "pallas:" + fam
                out = gid or None
                break
            key = seg
            if i + 1 < len(path) and path[i + 1].startswith("out="):
                out = path[i + 1][len("out="):]
            break
        if key:
            mapping[m.group(1)] = (key, out)
    return mapping


def _hlo_op_map(hlo_text):
    """instruction name -> framework op type (the type-level view of
    _hlo_op_attribution, kept as device_op_profile's correlation key)."""
    return {
        instr: typ for instr, (typ, _out) in _hlo_op_attribution(hlo_text).items()
    }


def _merge_device_plane_events(planes, events, aux=None):
    """Fold one xplane's device planes into the shared `events` table
    ({instr_name: [count, total_ms, min_ms, max_ms]}). Separated from the
    file loop so synthetic plane data can drive it in tests.

    `aux` (optional dict) additionally collects XLA cost-analysis stats the
    trace carries per instruction — {instr_name: {"flops": f, "bytes": b}} —
    without changing the 4-element row shape existing callers
    (device_op_profile) depend on."""
    for plane in planes:
        if "TPU" not in plane.name and "GPU" not in plane.name:
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                name = ev.name.lstrip("%").split(" ")[0]
                dur_ms = None
                flops = nbytes = None
                for k, v in ev.stats or []:
                    if k == "device_duration_ps":
                        dur_ms = float(v) / 1e9
                    elif k == "flops":
                        flops = float(v)
                    elif k in ("bytes accessed", "bytes_accessed"):
                        nbytes = float(v)
                if dur_ms is None:
                    continue
                row = events.setdefault(name, [0, 0.0, float("inf"), 0.0])
                row[0] += 1
                row[1] += dur_ms
                row[2] = min(row[2], dur_ms)
                row[3] = max(row[3], dur_ms)
                if aux is not None and (flops is not None or nbytes is not None):
                    # cost analysis is per-instruction, not per-execution:
                    # keep the max seen, don't accumulate over repeats
                    a = aux.setdefault(name, {"flops": 0.0, "bytes": 0.0})
                    if flops is not None:
                        a["flops"] = max(a["flops"], flops)
                    if nbytes is not None:
                        a["bytes"] = max(a["bytes"], nbytes)
    return events


def device_instr_events(log_dir, aux=None):
    """Per-HLO-instruction device timings from an xla_trace log dir:
    {instr_name: [count, total_ms, min_ms, max_ms]}. The base of
    device_op_profile. Pass a dict as `aux` to also
    collect per-instruction XLA cost-analysis stats when the trace carries
    them (see _merge_device_plane_events).

    ALL xplane.pb files under the dir are merged — a trace session writes one
    per host (multi-host run) and a restarted/repeated trace leaves several;
    reading only the newest silently dropped every other host's kernels."""
    import glob as _glob

    paths = sorted(
        _glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    )
    if not paths:
        raise FileNotFoundError("no xplane.pb under %r — run xla_trace first" % log_dir)
    # module-attr access (not `from ... import`) so the name resolves at call
    # time — tests substitute it
    import jax.profiler as _jprof

    events = {}
    for path in paths:
        _merge_device_plane_events(
            _jprof.ProfileData.from_file(path).planes, events, aux=aux
        )
    return events


def device_op_profile(log_dir, hlo_text=None, print_table=True):
    """Fold an xla_trace's per-HLO device timings back onto framework op
    types (ROADMAP 10; reference analog: device_tracer.cc correlating CUPTI
    kernels to RecordEvent annotations into the same profiler table).

    `log_dir` is the directory a profiler.xla_trace wrote. With `hlo_text`
    (from Executor.compiled_hlo()) each HLO instruction is attributed to the
    framework op whose lowering emitted it; without it, instructions
    aggregate by HLO opcode. Returns {key: [count, total_ms, min_ms, max_ms]}
    in stop_profiler's table shape; prints the same report format."""
    mapping = _hlo_op_map(hlo_text) if hlo_text else {}
    table = {}
    for name, (count, total, mn, mx) in device_instr_events(log_dir).items():
        key = mapping.get(name)
        if key is None:
            # strip SSA suffix then retry, else group by HLO opcode
            key = mapping.get(name.split(".")[0])
        if key is None:
            key = "hlo:" + name.split(".")[0]
        row = table.setdefault(key, [0, 0.0, float("inf"), 0.0])
        row[0] += count
        row[1] += total
        row[2] = min(row[2], mn)
        row[3] = max(row[3], mx)
    if print_table and table:
        rows = sorted(table.items(), key=lambda kv: -kv[1][1])
        lines = [
            "------------------->    Device Profiling Report (XLA)    <-------------------",
            "%-50s %8s %12s %12s %12s %12s"
            % ("Op", "Kernels", "Total(ms)", "Min(ms)", "Max(ms)", "Ave(ms)"),
        ]
        for name, (calls, total, mn, mx) in rows:
            lines.append(
                "%-50s %8d %12.4f %12.4f %12.4f %12.4f"
                % (name[:50], calls, total, mn, mx, total / calls)
            )
        print("\n".join(lines))
    return table
