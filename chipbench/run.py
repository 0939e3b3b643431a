"""Run one cell of the benchmark once.

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process per run. It needs the chip: unless JAX's first device is a TPU
and there are as many as the cell asks for, it exits non-zero and prints no
result line. The last line of standard output is one JSON object with the
keys correct, attempted, failed, metrics, device (and breakdown with
--trace 1): with --trace 0 the metrics are the cell's end-to-end metrics,
with --trace 1 its per-layer metrics.
"""

import time

_T0 = time.perf_counter()  # process start, as near as Python lets us

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from chipbench import data  # noqa: E402


def say(msg):
    print("chipbench: " + msg, file=sys.stderr, flush=True)


def devices_or_exit(chips):
    """JAX's devices, if the first is a TPU and there are at least `chips`;
    otherwise exit 2. There is no CPU fallback: tests replace this function."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        say("needs a TPU and JAX found none: its first device is %s (%s)"
            % (dev.platform, dev.device_kind))
        raise SystemExit(2)
    if len(devices) < chips:
        say("the cell asks for %d chips, JAX sees %d" % (chips, len(devices)))
        raise SystemExit(2)
    return devices


def place_caches():
    """The program puts XLA's persistent cache at <checkout>/.jax_cache (or
    where JAX_COMPILATION_CACHE_DIR says) when it is imported; the benchmark
    only lowers the thresholds, so that small programs are kept too and a
    second run finds every program there."""
    import jax

    import paddle_tpu  # noqa: F401 — places the compile cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


def device_record(devices, chips, trace, planned_bytes):
    """The device as JAX reports it. memory_peak_bytes is the allocator's
    peak on the fullest chip, or what the compiler planned for the cell's
    program on one chip where that is larger: the allocator does not count
    XLA's temporaries (PERF.md, section 7)."""
    dev = devices[0]
    peak = int(planned_bytes or 0)
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    rec = {
        "platform": str(dev.platform), "kind": str(dev.device_kind),
        "count": len(devices), "memory_peak_bytes": peak,
    }
    if trace:
        rec["busy_s"] = trace["busy_s"]
        rec["window_s"] = trace["window_s"]
    return rec


# where each group of BENCHMARK.json keeps its metrics' files
METRIC_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def metrics(man, cell_name, group, run):
    """Every metric of the group ('end_to_end' or 'per_layer') that the cell
    reports and that its reader can read from what the run collected. A
    reader that finds nothing returns None and the metric is left out: a run
    with no reply has no latency."""
    out = {}
    for m in data.cell_metrics(man, cell_name, group):
        spec = data.named(METRIC_DIRS[group], m["name"])
        value = data.module("readers", spec["reader"]).read(spec["args"], run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    man = data.manifest()
    if args.workload not in [w["name"] for w in man["workloads"]]:
        say("no cell %r in BENCHMARK.json" % args.workload)
        return 2
    cell = data.cell(args.workload)
    chips = cell["workload"]["chips"]
    devices = devices_or_exit(chips)
    cache_dir = place_caches()
    say("%s on %s x%d, seed %d, %.0f s, trace %d, compile cache %s" % (
        args.workload, devices[0].device_kind, len(devices), args.seed,
        args.seconds, args.trace, cache_dir))

    runner = data.module("runners", cell["config"]["runner"])
    run = runner.run(
        cell, args.seed, args.seconds, bool(args.trace),
        lambda: time.perf_counter() - _T0, devices,
    )
    for p in run["problems"]:
        say("not correct: " + p)
    run["peaks"] = data.peaks(str(devices[0].device_kind))

    result = {
        "correct": bool(run["correct"]),
        "attempted": int(run["attempted"]),
        "failed": int(run["failed"]),
    }
    result["metrics"] = metrics(
        man, args.workload, "per_layer" if args.trace else "end_to_end", run)
    if args.trace:
        result["breakdown"] = run["trace"]["breakdown"]
    result["device"] = device_record(
        devices, chips, run.get("trace"), run.get("planned_bytes"))
    notes = dict(run.get("notes", {}), setup_s=run["setup_s"],
                 planned_bytes=run.get("planned_bytes"),
                 memory_stats=devices[0].memory_stats())
    say("notes " + json.dumps(notes))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
