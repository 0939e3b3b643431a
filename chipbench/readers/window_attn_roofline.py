"""Sliding-window attention's share of its roofline over the traced span
(args: pattern, the device operations of the window layers' attention;
bandwidth and flops, the peaks in run["peaks"]): the time the attention the
traffic required would take at the device's peaks, the larger of its bytes
over the bandwidth and its operations over the matrix unit's rate, over the
summed device time of those operations.

Both are counted from what attention requires, not from what the kernel
moves. Bytes: every position a decode row attends (at most the window, each
row its own) and every distinct position a prefill chunk attends (its rows
and the window behind its first) has its cached K and V rows read once a
window layer (kv_heads x d_head values each, of cache_bytes). Operations:
every (query row, position) pair attended is, a query head, one product
over the key and one over the value (d_head x 4 FLOP). A kernel that walks
blocks behind the window, or reads a page again for every row of a chunk,
reads low here: that is what the number is for; it cannot pass 100 %
unless the time leaves out part of the work.

run["window_traced"] (the runner's, read from the program's
gen_window_ctx_tokens, gen_chunk_window_tokens and gen_chunk_window_pairs
histograms at two instants inside the profile): positions and pairs, summed
over the decode steps and prefill chunks between them, a layer; and the
configuration's sizes. None where the run has none (an untraced run, a
program without the histograms)."""

from .. import trace


def required_bytes(work):
    """Bytes of cache the window layers' attention of `work` has to read."""
    return (work["positions"] * work["kv_heads"] * work["d_head"] * 2
            * work["cache_bytes"] * work["layers"])


def required_flops(work):
    """Floating-point operations of its score and context products."""
    return work["pairs"] * work["heads"] * work["d_head"] * 4 * work["layers"]


def read(args, run):
    tr, work = run.get("trace"), run.get("window_traced")
    if not tr or not work or not work["calls"]:
        return None
    seconds, n = trace.pattern_seconds(tr["events"], args["pattern"])
    if not n or seconds <= 0:
        return None
    peaks = run["peaks"]
    least = max(required_bytes(work) / (peaks[args["bandwidth"]] * 1e9),
                required_flops(work) / (peaks[args["flops"]] * 1e12))
    return 100.0 * least / seconds
