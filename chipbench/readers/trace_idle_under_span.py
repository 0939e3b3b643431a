"""Percent of device 0's idle time, in the traced span, that overlaps the
union of the program's spans of one name (args: span), whatever nests inside
them: where trace_idle_in_spans credits an instant to the innermost span
alone, this one asks under which phase the device stood still (a prompt's
prefill section, the decode section), so the shares of two names that nest
do not add up. None where the run has no trace, the device was never idle,
or the profile holds no span of that name (a program older than the span)."""

from .. import program_spans


def read(args, run):
    # among spans of one name the innermost is that name: credit() then
    # sums the gaps' overlap with their union
    named = [sp for sp in program_spans.of(run) or []
             if sp[0] == args["span"] and sp[2] > 0]
    if not named:
        return None
    by_name, total = program_spans.credit(
        program_spans.idle_gaps(run["trace"]["events"]), named)
    if not total:
        return None
    return 100.0 * by_name.get(args["span"], 0) / total
