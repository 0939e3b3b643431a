"""The state-space layers' share of their roofline over the traced span
(args: pattern, the device operations of the scan; bandwidth and flops, the
peaks in run["peaks"]): the time what the recurrence required would take at
the device's peaks, the larger of its bytes over the bandwidth and its
operations over the matrix unit's rate, over the summed device time of those
operations.

Both are counted from what the recurrence requires, not from what an
implementation moves. Bytes: every live row of a decode step has its
recurrent state read once and written once a layer (the program's
gen_ssm_state_bytes, already summed over the layers), and a prefill chunk
its one row likewise; the rows' inputs, a few KB, are left out. Operations:
a position of a sequence, decode step or chunk alike, costs a layer 5 a
state entry: the decay's multiply, the outer product's multiply and add,
the output's multiply and add. A lowering that gathers, updates and
scatters every slot's state whatever is live, or moves a row three times
over, reads low here: that is what the number is for; it cannot pass 100 %
unless the time leaves out part of the work.

run["ssm_traced"] (the runner's, read from the program's
gen_ssm_state_bytes, gen_ssm_rows and gen_chunk_ssm_tokens histograms at two
instants inside the profile): step_state_bytes and step_rows, summed over
the decode steps between them; chunks and chunk_tokens, the prefill chunks
made and the positions they scanned; and the sizes. None where the run has
none (an untraced run, a program without the histograms)."""

from .. import trace

FLOPS_PER_STATE_ENTRY = 5


def required_bytes(work):
    """Bytes of recurrent state the steps and chunks of `work`
    (run["ssm_traced"]) have to read and write."""
    return (work["step_state_bytes"]
            + work["chunks"] * 2 * work["state_bytes"])


def required_flops(work):
    """Floating-point operations of the recurrence over their positions."""
    positions = work["step_rows"] + work["chunk_tokens"]
    return (positions * work["layers"] * work["state_values"]
            * FLOPS_PER_STATE_ENTRY)


def read(args, run):
    tr, work = run.get("trace"), run.get("ssm_traced")
    if not tr or not work or not (work["steps"] or work["chunks"]):
        return None
    seconds, n = trace.pattern_seconds(tr["events"], args["pattern"])
    if not n or seconds <= 0:
        return None
    peaks = run["peaks"]
    least = max(required_bytes(work) / (peaks[args["bandwidth"]] * 1e9),
                required_flops(work) / (peaks[args["flops"]] * 1e12))
    return 100.0 * least / seconds
