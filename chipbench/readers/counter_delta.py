"""A counter's change over the window, or the ratio of two such changes
(args: counter, optional over, optional scale)."""


def read(args, run):
    counters = run.get("counters", {})
    if args["counter"] not in counters:
        return None
    value = float(counters[args["counter"]])
    if "over" in args:
        den = counters.get(args["over"])
        if not den:
            return None
        value /= float(den)
    return value * args.get("scale", 1.0)
