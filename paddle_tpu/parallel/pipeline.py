"""Pipeline parallelism (GPipe microbatch schedule) over a mesh 'pp' axis.

The reference era (Fluid ~1.2) scaled across stages only via the pserver
graph split (transpiler/distribute_transpiler.py splits the program at
send/recv ops — reference paddle/fluid/transpiler); modern large-model
practice pipelines LAYER STAGES. TPU-native design, per the scaling-book
recipe rather than a send/recv port:

- stages are HOMOGENEOUS (a stack of identical blocks — the transformer
  case); their parameters are stacked on a leading [n_stages, ...] axis and
  sharded over the mesh's 'pp' axis, so each pp rank holds
  n_stages/pp_size consecutive stages;
- activations flow rank -> rank+1 through `lax.ppermute` (ICI
  neighbor-exchange, the NCCL-send/recv analog) on a GPipe schedule:
  microbatch m occupies rank r at tick m + r; the bubble is the classic
  (pp_size - 1) / (n_micro + pp_size - 1) fraction;
- the whole schedule is a traced loop of static length
  n_micro + pp_size - 1 inside ONE shard_map region, so XLA sees the
  compute/ppermute dependence chain and overlaps neighbor DMA with the
  next microbatch's stage compute;
- `ppermute` has a transpose rule, so `jax.grad` through the pipeline IS
  the backward pipeline (cotangents flow rank+1 -> rank via the reversed
  ring) — the GPipe schedules need no hand-written backward, and the
  optimizer update composes outside like any other jax.grad.

Two generations of schedule live here:

- the HOMOGENEOUS tier (`gpipe`/`gpipe_spmd`): stages are a stack of
  identical blocks, parameters stacked [n_stages, ...] and sharded P('pp');
- the HETEROGENEOUS tier (`pipeline_fwd_spmd`/`pipeline_1f1b_spmd`), the
  engine under ParallelExecutor's Program lowering: each pp rank holds ONE
  stage's arbitrary op subgraph (dispatched per-rank via lax.switch in the
  caller-built `stage_f`), activations cross stages through a uniform
  packed [mb, K] boundary buffer, and the 1F1B variant (PipeDream /
  Megatron flavor: Narayanan et al.) interleaves one forward with one
  backward per tick, rematerializing the stage forward at backward time so
  the stash holds only the O(pp) in-flight stage INPUTS instead of GPipe's
  O(n_micro) residual sets.

Composition: 'pp' is one axis of the SAME mesh as dp/tp/sp/ep, so a
dp2xpp4 mesh runs data-parallel pipelines (each dp slice pipelines its
own batch shard; parameter gradients psum over 'dp' at the optimizer like
every other ParallelExecutor path).
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .collectives import axis_size, shard_map

__all__ = [
    "gpipe",
    "gpipe_spmd",
    "pipeline_fwd_spmd",
    "pipeline_1f1b_spmd",
    "analytic_bubble",
]

# (pp-1)/(m+pp-1), the fill-drain bound both schedules share. Canonical home
# is observability.stepstats (no jax dependency) so the telemetry layer can
# publish the analytic gauge next to its runtime two-m-slope measurement;
# re-exported here because this module owns the schedules it describes.
from ..observability.stepstats import analytic_bubble  # noqa: E402


def _apply_stages(stage_fn, params_local, x):
    """Chain this rank's consecutive stages (leading axis of params_local)."""

    def body(carry, p):
        return stage_fn(p, carry), None

    out, _ = lax.scan(body, x, params_local)
    return out


def gpipe_spmd(stage_fn, params_local, x, n_micro, axis_name="pp"):
    """The per-shard GPipe schedule — call INSIDE an existing shard_map
    whose mesh has `axis_name`. `params_local` is this rank's
    [n_local, ...] stage stack; `x` is the (already dp-sharded) batch,
    replicated across `axis_name`. Returns the last stage's outputs,
    replicated across `axis_name`."""
    pp = axis_size(axis_name)
    r = lax.axis_index(axis_name)
    b = x.shape[0]
    if b % n_micro:
        raise ValueError("batch %d not divisible into %d microbatches"
                         % (b, n_micro))
    mb = b // n_micro
    x_micro = x.reshape((n_micro, mb) + x.shape[1:])

    ticks = n_micro + pp - 1
    recv = jnp.zeros_like(
        jax.eval_shape(lambda p, v: _apply_stages(stage_fn, p, v),
                       params_local, x_micro[0]),
    )
    perm = [(i, (i + 1) % pp) for i in range(pp)]
    outs = []
    for t in range(ticks):
        # rank 0 injects microbatch t (clamped: past the last microbatch it
        # reprocesses garbage whose outputs are never collected); other
        # ranks consume the neighbor's activation from tick t-1
        inj = x_micro[min(t, n_micro - 1)]
        inp = jnp.where(r == 0, inj.astype(recv.dtype), recv)
        out = _apply_stages(stage_fn, params_local, inp)
        recv = lax.ppermute(out, axis_name, perm)
        outs.append(out)

    # the LAST rank's outputs at ticks pp-1 .. pp-1+n_micro-1 are the
    # pipeline's results for microbatches 0..n_micro-1; replicate them to
    # every pp rank with a masked psum (its transpose routes cotangents
    # back to the last rank — the backward pipeline's entry point)
    y = jnp.stack(outs[pp - 1 : pp - 1 + n_micro])
    y = lax.psum(jnp.where(r == pp - 1, y, jnp.zeros_like(y)), axis_name)
    return y.reshape((b,) + y.shape[2:])


def gpipe(stage_fn, stacked_params, x, n_micro, mesh, axis_name="pp",
          batch_axis="dp"):
    """Run a stack of homogeneous stages as a GPipe pipeline over
    `mesh`'s `axis_name`, data-parallel over `batch_axis`.

    stage_fn(params_i, x) -> y with y.shape == x.shape (homogeneous
    stages); stacked_params: pytree with leading axis n_stages (must be
    divisible by the pp size); x: [batch, ...] global batch. Returns the
    final stage outputs [batch, ...]. Differentiable end to end.
    """
    n_stages = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    pp = mesh.shape[axis_name]
    if n_stages % pp:
        raise ValueError("%d stages not divisible over pp=%d" % (n_stages, pp))

    fn = shard_map(
        functools.partial(gpipe_spmd, stage_fn, n_micro=n_micro,
                          axis_name=axis_name),
        mesh=mesh,
        in_specs=(P(axis_name), P(batch_axis)),
        out_specs=P(batch_axis),
        check_vma=False,
    )
    params_sh = jax.device_put(
        stacked_params, NamedSharding(mesh, P(axis_name))
    )
    x_sh = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P(batch_axis)))
    return fn(params_sh, x_sh)


# ---------------------------------------------------------------------------
# Heterogeneous-stage engines (the ParallelExecutor Program lowering's core)
# ---------------------------------------------------------------------------
#
# Caller contract (both engines; call INSIDE a shard_map whose mesh binds
# `axis_name`): `stage_f(boundary_in, mb_idx) -> (boundary_out, scalars)`
# runs THIS RANK's stage (dispatch over lax.axis_index inside, e.g. via
# lax.switch) on one microbatch. boundary_in/out are the uniform packed
# activation buffers [mb, K] float32; `scalars` is a packed [n_scalars]
# float32 vector that only the LAST stage fills (loss + scalar fetches);
# mb_idx is the (traced, clamped-valid) microbatch index for feed slicing.
# Microbatch-MEAN combination: the engines average scalars over microbatches
# (exact for batch-mean losses/metrics when n_micro divides the batch).


def pipeline_fwd_spmd(stage_f, n_micro, boundary_shape, n_scalars,
                      axis_name="pp"):
    """GPipe forward schedule over heterogeneous stages: microbatch m
    occupies rank r at tick m + r; ticks = n_micro + pp - 1; the bubble is
    (pp-1)/(n_micro+pp-1). Returns the microbatch-mean scalars vector,
    replicated over `axis_name`. Backward: differentiate THROUGH this
    function (ppermute/psum transposes give the reversed-ring cotangent
    pipeline); peak liveness is the classic GPipe O(n_micro) residual set."""
    pp = axis_size(axis_name)
    r = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % pp) for i in range(pp)]
    recv = jnp.zeros(boundary_shape, jnp.float32)
    scal_sum = jnp.zeros((n_scalars,), jnp.float32)
    for t in range(n_micro + pp - 1):
        f = t - r  # microbatch this rank works on at tick t (may be invalid)
        fvalid = (f >= 0) & (f < n_micro)
        fc = jnp.clip(f, 0, n_micro - 1)
        out, scal = stage_f(recv, fc)
        scal_sum = scal_sum + jnp.where(
            fvalid & (r == pp - 1), scal, jnp.zeros_like(scal)
        )
        recv = lax.ppermute(out, axis_name, perm)
    scal_mean = scal_sum / n_micro
    return lax.psum(
        jnp.where(r == pp - 1, scal_mean, jnp.zeros_like(scal_mean)), axis_name
    )


def pipeline_1f1b_spmd(stage_f, params_local, n_micro, boundary_shape,
                       scal_cotangent, axis_name="pp"):
    """1F1B schedule (PipeDream-flush / Megatron): each tick interleaves one
    forward with one backward sub-step, so microbatch b's backward at rank r
    runs at tick b + 2(pp-1) - r — in-flight forwards per rank stay at most
    2(pp-1-r)+1 ≈ O(pp) instead of GPipe's O(n_micro). The backward is
    hand-scheduled with per-stage jax.vjp, REMATERIALIZING the stage forward
    from the stashed boundary input (activation-checkpoint flavor), so the
    stash is a [2·pp, mb, K] ring buffer of stage inputs, not full residuals.

    `stage_f(params_local, boundary_in, mb_idx) -> (boundary_out, scalars)`
    (params explicit here so vjp can differentiate w.r.t. them).
    `scal_cotangent` [n_scalars] seeds the loss cotangent at the LAST rank
    (one-hot at the loss slot, scaled 1/n_micro for the microbatch mean).

    Returns (microbatch-mean scalars replicated over axis_name,
    accumulated parameter-buffer gradient shaped like params_local).
    The math is identical to GPipe's jax.grad — same per-microbatch grads,
    summed — only the schedule (and liveness) differs.
    """
    pp = axis_size(axis_name)
    r = lax.axis_index(axis_name)
    perm_f = [(i, (i + 1) % pp) for i in range(pp)]
    perm_b = [(i, (i - 1) % pp) for i in range(pp)]
    n_scalars = scal_cotangent.shape[0]

    # stash of in-flight stage INPUTS keyed f mod W, one trash slot at W for
    # invalid-tick writes (clobbering a live slot would corrupt the replay)
    W = 2 * pp - 1
    stash = jnp.zeros((W + 1,) + tuple(boundary_shape), jnp.float32)
    gacc = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params_local
    )
    scal_sum = jnp.zeros((n_scalars,), jnp.float32)
    recv_f = jnp.zeros(boundary_shape, jnp.float32)
    recv_b = jnp.zeros(boundary_shape, jnp.float32)

    for t in range(n_micro + 2 * (pp - 1)):
        # ---- forward sub-step: microbatch f = t - r
        f = t - r
        fvalid = (f >= 0) & (f < n_micro)
        fc = jnp.clip(f, 0, n_micro - 1)
        out_f, scal = stage_f(params_local, recv_f, fc)
        scal_sum = scal_sum + jnp.where(
            fvalid & (r == pp - 1), scal, jnp.zeros_like(scal)
        )
        slot = jnp.where(fvalid, jnp.remainder(fc, W), W)
        stash = lax.dynamic_update_index_in_dim(
            stash, recv_f[None], slot, axis=0
        )
        recv_f = lax.ppermute(out_f, axis_name, perm_f)

        # ---- backward sub-step: microbatch b = t - 2(pp-1) + r
        b = t - 2 * (pp - 1) + r
        bvalid = (b >= 0) & (b < n_micro)
        bc = jnp.clip(b, 0, n_micro - 1)
        bin_b = lax.dynamic_index_in_dim(
            stash, jnp.remainder(bc, W), axis=0, keepdims=False
        )
        _, vjp = jax.vjp(
            lambda p, bi: stage_f(p, bi, bc), params_local, bin_b
        )
        is_last = r == pp - 1
        cot_out = jnp.where(is_last, jnp.zeros_like(recv_b), recv_b)
        cot_scal = jnp.where(
            is_last, scal_cotangent, jnp.zeros_like(scal_cotangent)
        )
        gp, gbi = vjp((cot_out, cot_scal))
        gacc = jax.tree_util.tree_map(
            lambda acc, g: acc + jnp.where(bvalid, g, jnp.zeros_like(g)),
            gacc, gp,
        )
        send = jnp.where(bvalid, gbi, jnp.zeros_like(gbi))
        recv_b = lax.ppermute(send, axis_name, perm_b)

    scal_mean = scal_sum / n_micro
    scal_repl = lax.psum(
        jnp.where(r == pp - 1, scal_mean, jnp.zeros_like(scal_mean)), axis_name
    )
    return scal_repl, gacc
