"""Device time of the operations a pattern names, from the traced span
(args: pattern, and one of
  of: "busy"            percent of the device's busy time;
  flops + peak          percent of the roofline: the operations the traced
                        steps need (run["window"][flops] a step) over the
                        summed kernel time times the device_kind peak)."""

from .. import trace


def read(args, run):
    tr = run.get("trace")
    if not tr:
        return None
    seconds, n = trace.pattern_seconds(tr["events"], args["pattern"])
    if not n or seconds <= 0:
        return None
    if args.get("of") == "busy":
        return 100.0 * seconds / tr["busy_s"]
    flops = run["window"][args["flops"]] * tr["steps"] / run["window"]["chips"]
    return 100.0 * flops / (seconds * run["peaks"][args["peak"]] * 1e12)
