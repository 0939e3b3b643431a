"""Arithmetic on the timed window: a per-step quantity times the steps
completed, over the window's seconds (args: per_step) and, with `peak`, as a
percent of chips times that device_kind peak. Tokens per second is this with
tokens_per_step; model-FLOP utilisation with flops_per_step and bf16_tflops."""


def read(args, run):
    w = run.get("window")
    if not w or not w.get("steps"):
        return None
    rate = w[args["per_step"]] * w["steps"] / w["seconds"]
    if "peak" not in args:
        return rate
    return 100.0 * rate / (w["chips"] * run["peaks"][args["peak"]] * 1e12)
