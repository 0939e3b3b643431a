"""Latent attention's share of its roofline over the traced span (args:
pattern, the device operations of the latent walk; bandwidth and flops, the
peaks in run["peaks"]): the time the attention the traffic required would
take at the device's peaks, the larger of its bytes over the bandwidth and
its operations over the matrix unit's rate, over the summed device time of
those operations.

Both are counted from what attention requires, not from what the kernel
moves. Bytes: every position a decode row or a prefill chunk attends has its
cached row read once a layer, key_values values (c_kv and the rotary key:
576 here, 1152 B; the zero columns a pooled row is padded with are the
kernel's affair, and rows of two requests that share a document's pages are
counted for each, as each reads them). Operations: every (query row,
position) pair attended is, a head, one product over the key (key_values)
and one over the value (value_values), 2 FLOP a multiply-add. A kernel that
reads a page again for every tile of a chunk's rows, or pads, or masks half
a block away, reads low here: that is what the number is for; it cannot pass
100 % unless the time leaves out part of the work.

run["latent_traced"] (the runner's, read from the program's gen_ctx_tokens,
gen_chunk_ctx_tokens and gen_chunk_ctx_pairs histograms at two instants
inside the profile): positions and pairs, summed over the decode steps and
prefill chunks between them, a layer; and the configuration's sizes. None
where the run has none (an untraced run, a program without the
histograms)."""

from .. import trace


def required_bytes(work):
    """Bytes of cache the attention of `work` (run["latent_traced"]) has to
    read."""
    return (work["positions"] * work["key_values"] * work["cache_bytes"]
            * work["layers"])


def required_flops(work):
    """Floating-point operations of its score and context products."""
    return (work["pairs"] * work["heads"]
            * (work["key_values"] + work["value_values"]) * 2 * work["layers"])


def read(args, run):
    tr, work = run.get("trace"), run.get("latent_traced")
    if not tr or not work or not work["calls"]:
        return None
    seconds, n = trace.pattern_seconds(tr["events"], args["pattern"])
    if not n or seconds <= 0:
        return None
    peaks = run["peaks"]
    least = max(required_bytes(work) / (peaks[args["bandwidth"]] * 1e9),
                required_flops(work) / (peaks[args["flops"]] * 1e12))
    return 100.0 * least / seconds
