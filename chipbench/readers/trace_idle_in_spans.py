"""Percent of device 0's idle time, in the traced span, that fell inside the
program's spans of the names given (args: spans), each instant of a gap
credited to the innermost `fluid.` span that holds it, so a wrapper is
credited with its self time only; with "complement": true, the percent that
no `fluid.` span holds. None where the run has no trace, the device was never
idle, or the profile holds no `fluid.` span at all (a program older than the
spans)."""

from .. import program_spans


def read(args, run):
    host = program_spans.of(run)
    if not host:
        return None
    by_name, total = program_spans.credit(
        program_spans.idle_gaps(run["trace"]["events"]), host)
    if not total:
        return None
    if args.get("complement"):
        return 100.0 * (total - sum(by_name.values())) / total
    return 100.0 * sum(by_name.get(n, 0) for n in args["spans"]) / total
