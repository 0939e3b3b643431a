"""The expert layers' share of their HBM roofline over the traced span
(args: pattern, the device operations of the expert layers; peak, the
bandwidth in run["peaks"]): the bytes the routing required over the summed
device time of those operations times the peak bandwidth.

The bytes are counted from the routing, not from the implementation: every
distinct expert that a call's live rows picked has to be read once, its three
matrices, and every call reads its router. That is the least any
implementation of the grouped product moves (rows and picks, a few KB a
row, are left out), so the share cannot pass 100 % unless the time leaves out
part of the work. A lowering that streams all the experts of a layer whatever
the routing reads low here: that is what the number is for.

run["moe_traced"] (the runner's, read from the program's gen_experts_touched
and gen_chunk_experts_touched histograms at two instants inside the
profile): experts_touched, summed over the expert layers and over the decode
steps and prefill chunks between them; calls, the expert-layer calls those
made; and the configuration's sizes. None where the run has none (an
untraced run, a program without the histograms)."""

from .. import trace


def required_bytes(work):
    """Bytes the routing of `work` (run["moe_traced"]) requires of HBM."""
    d, f = work["hidden_size"], work["moe_intermediate_size"]
    expert = 3 * d * f * work["weight_bytes"]
    router = (d + 1) * work["num_experts"] * 4  # float32 matrix and bias
    return work["experts_touched"] * expert + work["calls"] * router


def read(args, run):
    tr, work = run.get("trace"), run.get("moe_traced")
    if not tr or not work or not work["calls"]:
        return None
    seconds, n = trace.pattern_seconds(tr["events"], args["pattern"])
    if not n or seconds <= 0:
        return None
    peak = run["peaks"][args["peak"]] * 1e9
    return 100.0 * required_bytes(work) / (seconds * peak)
