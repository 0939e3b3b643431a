"""A percentile (args: q, 50 the median) of the durations in ms of the
program's host spans of one name (args: span) in the run's profile. None
where the profile holds no such span."""

import numpy as np

from .. import program_spans


def read(args, run):
    ms = program_spans.durations_ms(program_spans.of(run) or [], args["span"])
    if not ms:
        return None
    return float(np.percentile(np.asarray(ms, np.float64), args.get("q", 50)))
