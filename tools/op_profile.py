#!/usr/bin/env python
"""Top-k per-op device-time/FLOPs table from op_profile telemetry records.

Renders the ``op_profile`` records written by
paddle_tpu.observability.opprof (device_profile for an xplane trace,
host_profile for FLAGS_profile_ops host events) — the op-level answer to
"where did this step's time go":

    Op                 Count  Total(ms)   Mean(ms)   FLOPs  Bytes    %  Roof%

plus, with ``--rollup``, a per-category rollup ranked by roofline headroom
(busy ms above each category's roofline minimum — the attack-order signal
for kernel substitution; see docs/observability.md).

Input is either a telemetry directory (FLAGS_telemetry_dir — per-host
``telemetry-host*.jsonl`` shards; the LATEST op_profile record wins), a
single JSONL shard, or a JSON file holding one record (e.g. saved from
``device_profile(...)``).

Usage:
    python tools/op_profile.py --dir /path/to/telemetry
    python tools/op_profile.py --file record.json --top 30
    python tools/op_profile.py --dir /path/to/telemetry --json   # raw record

No dependency on paddle_tpu (pure stdlib) so it can run on a machine that
only has the telemetry files.
"""

import argparse
import glob
import json
import os
import sys

SHARD_GLOB = "telemetry-host*.jsonl*"


def _iter_json_lines(path):
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    yield json.loads(line)
                except ValueError:
                    continue  # torn tail line of a live file
    except OSError:
        return


def load_op_profiles(path):
    """All op_profile records from a telemetry dir, a JSONL shard, or a
    plain JSON file, in ts order."""
    records = []
    if os.path.isdir(path):
        for shard in sorted(glob.glob(os.path.join(path, SHARD_GLOB))):
            records.extend(_iter_json_lines(shard))
    else:
        try:
            with open(path) as f:
                text = f.read()
        except OSError as e:
            print("op_profile: cannot read %s: %s" % (path, e), file=sys.stderr)
            return []
        try:
            doc = json.loads(text)
            records = doc if isinstance(doc, list) else [doc]
        except ValueError:
            records = list(_iter_json_lines(path))
    out = [r for r in records if isinstance(r, dict) and r.get("kind") == "op_profile"]
    out.sort(key=lambda r: r.get("ts", 0.0))
    return out


def _fmt_flops(f):
    if not f:
        return "-"
    for unit in ("", "K", "M", "G", "T", "P"):
        if f < 1000 or unit == "P":
            return "%.4g%s" % (f, unit)
        f /= 1000.0


# roofline peaks for the Roof% column / headroom rollup: analytic defaults;
# a record carrying "peak_tflops"/"peak_bw_gbs" overrides them
PEAK_MM_TFLOPS = 192.0
PEAK_BW_GBS = 676.0


def _roofline_ms(row, peak_tflops, peak_bw_gbs):
    """Roofline minimum busy ms for one row — max of the compute leg
    (flops / peak matmul throughput) and the memory leg (bytes / peak HBM
    bandwidth); None when the row carries neither cost."""
    f = row.get("flops", 0) or 0
    b = row.get("bytes", 0) or 0
    if not f and not b:
        return None
    return max(f / (peak_tflops * 1e9), b / (peak_bw_gbs * 1e6))


def _row_roof_pct(r, peak_tflops, peak_bw_gbs):
    roof = _roofline_ms(r, peak_tflops, peak_bw_gbs)
    if roof is None or not r["total_ms"]:
        return "-"
    return "%.1f" % min(100.0 * roof / r["total_ms"], 100.0)


def render_table(record, top=20):
    """Same layout as paddle_tpu.observability.opprof.render_table — kept in
    sync by tests/test_opprof.py so this tool stays paddle_tpu-free. Roof%
    is achieved fraction of the per-row roofline minimum (100 = nothing
    left to win)."""
    peak_tflops = record.get("peak_tflops", PEAK_MM_TFLOPS)
    peak_bw_gbs = record.get("peak_bw_gbs", PEAK_BW_GBS)
    lines = [
        "---------------->    Op Profile (%s)    <----------------"
        % record.get("source", "?"),
        "%-44s %7s %10s %10s %8s %10s %6s %6s"
        % ("Op", "Count", "Total(ms)", "Mean(ms)", "FLOPs", "Bytes", "%",
           "Roof%"),
    ]
    for r in record.get("ops", [])[:top]:
        lines.append(
            "%-44s %7d %10.4f %10.4f %8s %10s %6.2f %6s"
            % (
                r["op"][:44],
                r["count"],
                r["total_ms"],
                r.get("mean_ms", r["total_ms"] / max(r["count"], 1)),
                _fmt_flops(r.get("flops", 0)),
                _fmt_flops(r.get("bytes", 0)),
                r.get("pct", 0.0),
                _row_roof_pct(r, peak_tflops, peak_bw_gbs),
            )
        )
    total = record.get("total_device_ms")
    if total is not None:
        tail = "total device ms: %.4f" % total
        if record.get("step_ms") is not None:
            tail += "   step ms: %.4f   coverage: %.1f%%" % (
                record["step_ms"],
                100.0 * total / record["step_ms"] if record["step_ms"] else 0.0,
            )
        lines.append(tail)
    return "\n".join(lines)


def render_rollup(record, top=10):
    """Category (op type) rollup ranked by roofline HEADROOM — the busy ms
    above each category's roofline minimum, i.e. the time a kernel
    substitution could still win back. Raw ms ranks a category that is big
    but already optimal above one that is smaller but 3x off roofline;
    headroom is the attack-order signal. Rows without cost analysis are
    assumed AT roofline (they claim no headroom)."""
    peak_tflops = record.get("peak_tflops", PEAK_MM_TFLOPS)
    peak_bw_gbs = record.get("peak_bw_gbs", PEAK_BW_GBS)
    cats = {}
    for r in record.get("ops", []):
        c = cats.setdefault(
            r.get("type") or r["op"],
            {"count": 0, "total_ms": 0.0, "roof_ms": 0.0},
        )
        c["count"] += r["count"]
        c["total_ms"] += r["total_ms"]
        roof = _roofline_ms(r, peak_tflops, peak_bw_gbs)
        c["roof_ms"] += min(
            roof if roof is not None else r["total_ms"], r["total_ms"]
        )
    lines = [
        "----------------> Category rollup (by headroom) <----------------",
        "%-28s %7s %10s %12s %12s %6s"
        % ("Category", "Count", "Total(ms)", "Roofline(ms)", "Headroom(ms)",
           "Roof%"),
    ]
    ranked = sorted(
        cats.items(), key=lambda kv: kv[1]["roof_ms"] - kv[1]["total_ms"]
    )
    for name, c in ranked[:top]:
        headroom = c["total_ms"] - c["roof_ms"]
        pct = 100.0 * c["roof_ms"] / c["total_ms"] if c["total_ms"] else 0.0
        lines.append(
            "%-28s %7d %10.4f %12.4f %12.4f %6.1f"
            % (name[:28], c["count"], c["total_ms"], c["roof_ms"], headroom,
               pct)
        )
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--dir", help="FLAGS_telemetry_dir path")
    src.add_argument(
        "--file", help="one JSONL shard or a JSON file holding a record"
    )
    ap.add_argument("--top", type=int, default=20, help="rows to print")
    ap.add_argument(
        "--json", action="store_true",
        help="dump the raw record instead of the table",
    )
    ap.add_argument(
        "--rollup", action="store_true",
        help="append the per-category headroom rollup",
    )
    args = ap.parse_args(argv)

    records = load_op_profiles(args.dir or args.file)
    if not records:
        print(
            "op_profile: no op_profile records in %s (profile a run with "
            "opprof.device_profile / host_profile and FLAGS_telemetry_dir "
            "set)" % (args.dir or args.file),
            file=sys.stderr,
        )
        return 1
    record = records[-1]
    if args.json:
        print(json.dumps(record, indent=2))
    else:
        print(render_table(record, top=args.top))
        if args.rollup:
            print(render_rollup(record, top=args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
