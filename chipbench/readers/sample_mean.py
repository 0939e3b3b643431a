"""The mean of the samples the benchmark took under a name (args: samples,
optional scale)."""

import numpy as np


def read(args, run):
    samples = run.get("samples", {}).get(args["samples"])
    if not samples:
        return None
    return float(np.mean(np.asarray(samples, np.float64))) * args.get("scale", 1.0)
