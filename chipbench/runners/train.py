"""The train runner: one fluid Program trained step by step through
fluid.Executor (or fluid.ParallelExecutor when the workload carries a mesh),
the way a training script that logs its loss drives it."""

import collections
import time

import numpy as np

from .. import data, trace as trace_mod

TRACED_STEPS = 10  # about this many steps under the profiler, after the window
# set-up: the steps the fused path and the per-op path both make from the seed
REFERENCE_STEPS = 4
# a loss near 10 in bf16 has a last bit of 6e-3 relative
LOSS_RTOL = 1e-2
# the cosine between the two paths' updates of one parameter. Adam's first
# steps move an element by about lr x sign(gradient), so bf16 rounding that
# flips the sign of a near-zero gradient element shows at once: a parameter
# whose gradient is nearly all rounding noise (the late decoder layers' q and
# k projections at initialisation) reads 0.14-0.6 with both paths sound, and
# pure noise would read 0; most read 0.99. A wrong fused kernel, pass or
# optimizer reaches every parameter behind it and gives cosines near 0; a
# wrong sign gives -1. So: the median, the share of parameters at 0.9 or
# more, and no update that points against the reference's (PERF.md, PR 24,
# has what the chip read)
COSINE_MEDIAN = 0.98
COSINE_GOOD, COSINE_GOOD_SHARE = 0.9, 0.9
COSINE_LEAST = -0.2


def _executor(cell, devices, main, loss, scope):
    """(run(feed) -> loss array, compiled_hlo()) for the cell's chips."""
    import paddle_tpu.fluid as fluid

    wl = cell["workload"]
    mesh = wl.get("mesh")
    place = fluid.TPUPlace() if devices[0].platform == "tpu" else fluid.CPUPlace()
    exe = fluid.Executor(place)
    if not mesh:
        def step(feed):
            return exe.run(main, feed=feed, fetch_list=[loss.name],
                           return_numpy=False)[0]
        return exe, step, exe.compiled_hlo
    from paddle_tpu.parallel import MeshConfig

    pe = fluid.ParallelExecutor(
        loss_name=loss.name, main_program=main, scope=scope,
        mesh_config=MeshConfig(**mesh), devices=devices[:wl["chips"]],
    )

    def step(feed):
        return pe.run(fetch_list=[loss.name], feed=feed, return_numpy=False)[0]
    return exe, step, pe.compiled_hlo


def run(cell, seed, seconds, trace, clock, devices):
    import jax

    from paddle_tpu import flags
    from paddle_tpu.executor import Scope, scope_guard
    from paddle_tpu.transpiler.bf16_transpiler import Bf16Transpiler

    config, tr = cell["config"], cell["traffic"]
    b, t = tr["batch"], tr["positions"]
    program = data.module("programs", config["program"])
    host_batches = data.module("traffic", tr["generator"]).batches(cell, seed)

    main, startup, loss = program.build(config, t)
    Bf16Transpiler().transpile(main)  # train mode: the scope keeps f32 masters
    params = sorted(p.name for p in main.global_block().all_parameters())
    prev = flags.get_flags("pass_pipeline")
    compiles = trace_mod.CompileCounter()
    out = {"attempted": 0, "failed": 0, "problems": [], "notes": {}}
    marks = out["notes"]["setup_marks_s"] = {"built": clock()}
    try:
        ring = [{n: jax.device_put(v) for n, v in hb.items()}
                for hb in host_batches]
        # the reference: the same Program lowered op by op with no pass
        # pipeline, REFERENCE_STEPS steps from the same seed in a scope of
        # its own: its losses, and where its parameters went
        flags.set_flags({"pass_pipeline": ""})
        ref = _first_steps(cell, devices, main, startup, loss, seed, ring,
                           params)
        marks["reference_run"] = clock()
        flags.set_flags({"pass_pipeline": config["pass_pipeline"]})
        scope = Scope(seed=seed % (2 ** 31 - 1))
        with scope_guard(scope):
            exe, step, compiled_hlo = _executor(cell, devices, main, loss, scope)
            exe.run(startup)  # every weight made on the device, one program
            marks["startup_run"] = clock()
            # warm-up: the one train step program compiles here, and its
            # first steps are what the reference is compared with
            kernels0 = _kernel_dispatches()
            first = [_scalar(step(ring[i % len(ring)]))
                     for i in range(REFERENCE_STEPS)]
            fused_kernels = _delta(_kernel_dispatches(), kernels0)
            _compare(out, first, {n: scope.vars[n] for n in params}, ref)
            out["notes"].update(
                first_losses=first, reference_losses=ref["losses"],
                fused_kernel_dispatches=fused_kernels,
                reference_kernel_dispatches=ref["kernels"])
            if sum(ref["kernels"].values()):
                out["problems"].append(
                    "the reference dispatched fused kernels: %s" % ref["kernels"])
            del ref

            compiles.start()
            out["setup_s"] = clock()
            losses, dispatch_s, elapsed, ready_s = _window(
                step, ring, tr["in_flight"], seconds)
            out["compiles_in_window"] = compiles.count
            if not cell["workload"].get("mesh"):
                out["planned_bytes"] = _planned_bytes(exe, out["notes"])
            if trace:
                out["trace"] = _traced_steps(step, ring, tr["in_flight"],
                                             cell, compiled_hlo())
            compiles.stop()
    finally:
        flags.set_flags(prev)
    values = np.asarray(jax.device_get(losses), np.float64).reshape(-1)
    steps = len(values)
    out["attempted"] = steps
    out["failed"] = int((~np.isfinite(values)).sum())
    if out["failed"]:
        out["problems"].append("%d losses in the window are not finite" % out["failed"])
    if out["compiles_in_window"]:
        out["problems"].append(
            "%d compilations inside the window" % out["compiles_in_window"])
    out["window"] = {
        "seconds": elapsed, "steps": steps, "chips": cell["workload"]["chips"],
        "tokens_per_step": program.tokens_per_step(b, t),
        "flops_per_step": program.flops_per_step(config, b, t),
        "attention_flops_per_step": program.attention_flops_step(config, b, t),
    }
    step_ms = np.diff(ready_s) * 1e3
    out["samples"] = {"dispatch_ms": [s * 1e3 for s in dispatch_s],
                      "step_ms": [float(v) for v in step_ms]}
    if len(step_ms):
        # where a window lost time, if it did: the host's view of each step
        # (loss ready to loss ready) and of each dispatch call
        median = float(np.median(step_ms))
        longest = np.argsort(step_ms)[::-1][:8]
        out["notes"]["step_ms"] = {
            "p50": median, "p99": float(np.percentile(step_ms, 99)),
            "over_1.5x_median": int((step_ms > 1.5 * median).sum()),
            "seconds_over_median": float(np.clip(step_ms - median, 0, None).sum() / 1e3),
            "longest": [[int(i), float(step_ms[i])] for i in sorted(longest)],
            "dispatch_ms_max": float(max(dispatch_s) * 1e3),
            "dispatch_over_50_ms": int(sum(1 for d in dispatch_s if d > 0.05))}
    out["counters"] = {"compiles_in_window": out["compiles_in_window"]}
    out["correct"] = not out["problems"]
    return out


def _scalar(x):
    return float(np.asarray(x, np.float32).reshape(-1)[0])


# the Pallas families the pass pipeline substitutes; the per-op reference
# must dispatch none of them
_FUSED_FAMILIES = ("gemm_epilogue", "gemm_dbuf", "layer_norm",
                   "layer_norm_grad", "multi_adam")


def _kernel_dispatches():
    from paddle_tpu.ops import pallas_kernels as pk

    return {k: pk.KERNEL_DISPATCHES.get(k, 0) for k in _FUSED_FAMILIES}


def _delta(after, before):
    return {k: after[k] - before[k] for k in after}


def _first_steps(cell, devices, main, startup, loss, seed, ring, params):
    """REFERENCE_STEPS steps of `main` from the seed under the pass pipeline
    that is set, in a scope of their own. Returns the losses, the parameters
    after the last step, the parameters as the startup program made them
    (copied: the step donates its inputs) and the fused-kernel dispatches."""
    import jax.numpy as jnp

    from paddle_tpu.executor import Scope, scope_guard

    scope = Scope(seed=seed % (2 ** 31 - 1))
    kernels0 = _kernel_dispatches()
    with scope_guard(scope):
        exe, step, _ = _executor(cell, devices, main, loss, scope)
        exe.run(startup)
        start = {n: jnp.array(scope.vars[n], copy=True) for n in params}
        losses = [_scalar(step(ring[i % len(ring)]))
                  for i in range(REFERENCE_STEPS)]
        return {"losses": losses, "start": start,
                "end": {n: scope.vars[n] for n in params},
                "kernels": _delta(_kernel_dispatches(), kernels0)}


def _compare(out, losses, end, ref):
    """The fused path against the per-op reference after the same steps from
    the same seed: every loss, which checks the forward pass, and for every
    parameter the cosine between the two paths' updates (end - start), which
    checks the backward pass and the optimizer that made them."""
    import jax
    import jax.numpy as jnp

    for i, (got, want) in enumerate(zip(losses, ref["losses"])):
        rel = abs(got - want) / abs(want)
        if not (np.isfinite(got) and rel <= LOSS_RTOL):
            out["problems"].append(
                "loss of step %d, %.6f, is %.2e relative from the per-op "
                "reference %.6f" % (i, got, rel, want))

    @jax.jit
    def products(start, a, b):
        rows = []
        for n in sorted(start):
            p0 = start[n].astype(jnp.float32)
            da = (a[n].astype(jnp.float32) - p0).ravel()
            db = (b[n].astype(jnp.float32) - p0).ravel()
            rows.append(jnp.stack([jnp.vdot(da, db), jnp.vdot(da, da),
                                   jnp.vdot(db, db)]))
        return jnp.stack(rows)

    prod = np.asarray(products(ref["start"], end, ref["end"]), np.float64)
    cosines = {}
    for n, (ab, aa, bb) in zip(sorted(ref["start"]), prod):
        if aa == 0.0 and bb == 0.0:
            continue  # a parameter neither path moved
        cosines[n] = float(ab / np.sqrt(aa * bb)) if aa and bb else 0.0
    if not cosines:
        out["problems"].append("no parameter moved in %d steps" % REFERENCE_STEPS)
        return
    values = np.array(sorted(cosines.values()))
    worst = sorted(cosines, key=cosines.get)[:3]
    median = float(np.median(values))
    good = float((values >= COSINE_GOOD).mean())
    out["notes"]["update_cosines"] = {
        "parameters": len(values), "median": median,
        "share_at_%.1f_or_more" % COSINE_GOOD: good,
        "under_0.5": int((values < 0.5).sum()),
        "p5": float(np.percentile(values, 5)),
        "least": [[n, cosines[n]] for n in worst],
    }
    if not median >= COSINE_MEDIAN:
        out["problems"].append(
            "the median cosine between the fused path's and the per-op "
            "reference's parameter updates is %.4f (least allowed %.2f)"
            % (median, COSINE_MEDIAN))
    if not good >= COSINE_GOOD_SHARE:
        out["problems"].append(
            "only %.1f %% of the parameters' updates have a cosine of %.1f or "
            "more with the per-op reference's (least allowed %.0f %%)"
            % (100 * good, COSINE_GOOD, 100 * COSINE_GOOD_SHARE))
    if not values[0] > COSINE_LEAST:
        out["problems"].append(
            "parameter %s: the fused path's update points against the per-op "
            "reference's (cosine %.4f)" % (worst[0], values[0]))


def _planned_bytes(exe, notes):
    """What the compiler planned for the step program on one device:
    arguments + outputs + temporaries + code, less what is aliased. The
    allocator's peak_bytes_in_use leaves XLA's temporaries out (PERF.md), so
    the device record takes the larger of the two. The program has no public
    way to this (PERF.md, Open questions): it is read from the executor's
    record of its last run the way Executor.compiled_hlo() does. Where a
    later version keeps none, the run says so aloud and memory_peak_bytes is
    the allocator's peak alone."""
    import sys

    import jax

    try:
        compiled, scope_ref, feed_avals = exe._last_run
        scope = scope_ref()
        ro = {n: scope.vars[n] for n in compiled.ro_names}
        mut = {n: scope.vars[n] for n in compiled.mut_names}
        with jax.default_device(exe._device):
            m = compiled.jitted.lower(
                feed_avals, ro, mut, scope.rng_key).compile().memory_analysis()
        return int(m.argument_size_in_bytes + m.output_size_in_bytes
                   + m.temp_size_in_bytes + m.generated_code_size_in_bytes
                   - m.alias_size_in_bytes)
    except (AttributeError, TypeError, ValueError) as e:
        notes["memory_peak_source"] = (
            "allocator's peak only: the compiler's plan could not be read "
            "(%r)" % (e,))
        print("chipbench: memory_peak_bytes is the " + notes["memory_peak_source"]
              + "; it leaves XLA's temporaries out and reads several times "
              "too low", file=sys.stderr, flush=True)
        return None


def _window(step, ring, in_flight, seconds):
    """Dispatch steps for `seconds`, at most `in_flight` unfinished; close by
    blocking on the last loss. Returns (losses, the host seconds each
    dispatch call took, elapsed seconds, the times at which the host saw a
    loss ready)."""
    import jax
    from jax.profiler import TraceAnnotation

    losses, dispatch_s, ready_s = [], [], []
    pending = collections.deque()
    i = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        if len(pending) >= in_flight:
            with TraceAnnotation("chipbench.wait_loss"):
                jax.block_until_ready(pending.popleft())
            ready_s.append(time.perf_counter() - t0)
        tc = time.perf_counter()
        with TraceAnnotation("chipbench.dispatch"):
            l = step(ring[i % len(ring)])
        dispatch_s.append(time.perf_counter() - tc)
        pending.append(l)
        losses.append(l)
        i += 1
    with TraceAnnotation("chipbench.wait_loss"):
        jax.block_until_ready(losses[-1])
    return losses, dispatch_s, time.perf_counter() - t0, ready_s


def _traced_steps(step, ring, in_flight, cell, hlo_text):
    """Profile about ten steps after the timed window, so that the profiler
    costs the window nothing, and reduce the trace."""
    import jax
    from jax.profiler import TraceAnnotation

    t0 = time.perf_counter()
    jax.block_until_ready(step(ring[0]))
    step_s = time.perf_counter() - t0
    log_dir = trace_mod.start(cell["workload"]["name"])
    try:
        with TraceAnnotation("chipbench.traced"):
            losses, _, _, _ = _window(step, ring, in_flight, (TRACED_STEPS - 0.5) * step_s)
    finally:
        jax.profiler.stop_trace()
    reduced = trace_mod.reduce_dir(log_dir, [hlo_text], cell["workload"]["chips"])
    reduced["steps"] = len(losses)
    return reduced
