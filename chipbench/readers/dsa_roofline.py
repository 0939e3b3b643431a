"""A learned-sparse-attention kernel's share of its roofline over the traced
span (args: pattern, the device operations of the kernel; kind, "index" for
the lightning indexer's scan or "sparse" for the latent walk over the
selection; bandwidth and flops, the peaks in run["peaks"]): the time the
work the traffic required would take at the device's peaks, the larger of
its bytes over the bandwidth and its operations over the matrix unit's
rate, over the summed device time of those operations.

Both are counted from what the work requires, not from what the kernel
moves, and bytes over DISTINCT pool rows: a row two query rows need (a
document's pages under several questions, a chunk's shared prefix) is
counted once, so a kernel that reads it once for all of them cannot pass
100 %. "index": every index key up to each query row's position, on every
full layer (index_width values of index_bytes), and per (query row,
position) pair the index heads' products (index_heads x index_width x 2).
"sparse": every latent row a selection names, on every layer that attends
it (key_values values of cache_bytes: c_kv and the rotary key, not the
zero padding), and per (query row, selected position) pair, a head, one
product over the key and one over the value ((key_values + value_values) x
2). A kernel that reads a row once for each query row, pads, or walks
what no row needs, reads low here: that is what the number is for.

run["dsa_traced"] (the runner's, read from the program's gen_dsa_*
histograms at two instants inside the profile, each already summed over the
layers): index_positions, index_pairs, selected_positions, selected_pairs,
calls (decode steps and prefill chunks), and the configuration's widths.
None where the run has none (an untraced run, a program without the
histograms)."""

from .. import trace


def required_bytes(work, kind):
    """Bytes of pool the `kind` kernel's work (run["dsa_traced"]) has to
    read."""
    if kind == "index":
        return work["index_positions"] * work["index_width"] * work["index_bytes"]
    return work["selected_positions"] * work["key_values"] * work["cache_bytes"]


def required_flops(work, kind):
    """Floating-point operations of its products."""
    if kind == "index":
        return work["index_pairs"] * work["index_heads"] * work["index_width"] * 2
    return (work["selected_pairs"] * work["heads"]
            * (work["key_values"] + work["value_values"]) * 2)


def read(args, run):
    tr, work = run.get("trace"), run.get("dsa_traced")
    if not tr or not work or not work["calls"]:
        return None
    seconds, n = trace.pattern_seconds(tr["events"], args["pattern"])
    if not n or seconds <= 0:
        return None
    peaks = run["peaks"]
    least = max(
        required_bytes(work, args["kind"]) / (peaks[args["bandwidth"]] * 1e9),
        required_flops(work, args["kind"]) / (peaks[args["flops"]] * 1e12))
    return 100.0 * least / seconds
