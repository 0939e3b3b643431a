"""Percent of the window's wall that a registry histogram of milliseconds
summed up: 100 x (sum at the window's end - sum at its start) / the window's
milliseconds (args: histogram, with {model} standing for the served model's
name). A phase that one thread runs reads as that thread's share of the
window. None where the program has no such histogram."""


def read(args, run):
    name = args["histogram"].format(**run.get("names", {}))
    ends = run.get("histograms", {}).get(name)
    seconds = run.get("window", {}).get("seconds")
    if not ends or not seconds:
        return None
    (s0, _), (s1, _) = ends
    return 100.0 * (s1 - s0) / (seconds * 1e3)
