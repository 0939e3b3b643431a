"""A percentile of the samples the benchmark took under a name (args:
samples, q). Exact: numpy over every sample."""

import numpy as np


def read(args, run):
    samples = run.get("samples", {}).get(args["samples"])
    if not samples:
        return None
    return float(np.percentile(np.asarray(samples, np.float64), args["q"]))
