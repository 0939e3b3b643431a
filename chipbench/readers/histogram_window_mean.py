"""Mean of a registry histogram over the window: the exact running sum and
count read at the window's two ends, delta sum / delta count (args:
histogram, with {model} standing for the served model's name). The
registry's percentile() interpolates inside fixed buckets and is not read."""


def read(args, run):
    name = args["histogram"].format(**run.get("names", {}))
    ends = run.get("histograms", {}).get(name)
    if not ends:
        return None
    (s0, c0), (s1, c1) = ends
    if c1 <= c0:
        return None
    return (s1 - s0) / (c1 - c0)
