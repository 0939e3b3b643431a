"""Operator registry: lowering, shape inference, and gradient definitions.

Reference analog: paddle/fluid/framework/op_registry.h:196 (REGISTER_OPERATOR)
plus per-op InferShape and GradOpDescMaker (grad_op_desc_maker.h). The TPU-first
redesign collapses all three into one artifact — the JAX lowering:

- **lowering**: `lower(ctx, ins, attrs) -> outs` maps slot-name->[jax arrays] to
  slot-name->[jax arrays]. This replaces the reference's per-op CPU/CUDA kernels
  (operators/*.cc/.cu); XLA fuses across ops since the executor lowers whole
  blocks into one jitted function (executor.py).
- **shape inference**: `jax.eval_shape` over the lowering — free and always
  consistent with execution, replacing ~400 hand-written InferShape functions.
  Dynamic batch dims (-1) are substituted with a sentinel extent and mapped back.
- **gradients**: unless an op registers a custom grad, `{type}_grad` is derived
  automatically with `jax.vjp` over the forward lowering (functional transforms
  instead of hand-written *_grad kernels). append_backward (backward.py) emits
  grad ops in the program exactly like the reference's GradOpDescMaker pass.
"""

import functools
import re as _re

import jax
import jax.numpy as jnp
import numpy as np

from .. import framework

# Sentinel extent substituted for -1 (dynamic batch) dims during eval_shape.
# Any output dim equal to it is mapped back to -1. Chosen to be an implausible
# real extent; collisions would only mislabel build-time metadata, never
# execution (the executor re-traces with concrete feed shapes).
_DYN_SENTINEL = 8191

_GRAD_SUFFIX = "@GRAD"

# meta attrs attached by backward.py to generic grad ops
FWD_IN_SLOTS_ATTR = "__fwd_in_slots__"
FWD_OUT_SLOTS_ATTR = "__fwd_out_slots__"

_META_ATTRS = (
    FWD_IN_SLOTS_ATTR,
    FWD_OUT_SLOTS_ATTR,
    framework.OpRole.OP_ROLE_KEY,
    framework.OpRole.OP_ROLE_VAR_KEY,
)


class OpDef:
    def __init__(
        self,
        type,
        lower=None,
        infer_shape=None,
        grad=None,
        no_grad=False,
        stochastic=False,
        skip_exec=False,
        host_fn=None,
        abstract_eval=None,
    ):
        self.type = type
        self.lower = lower
        self.custom_infer_shape = infer_shape
        # abstract_eval: the static analyzer's transfer function
        # (analysis/dataflow.py), `fn(actx, op, ins) -> {slot: [VarFact]}`.
        # Most ops need none — the analyzer abstracts the lowering itself
        # with jax.eval_shape, the same machinery infer_shape below uses.
        # Register one only where the lowering cannot be abstracted from
        # flat tensor facts: control-flow ops recurse into their sub-blocks
        # (actx.analyze_block), tensor-array ops model (buffer, size) pairs
        # (ops/control_flow_ops.py).
        self.abstract_eval = abstract_eval
        # grad: fn(op, block, grad_name_map) -> list of op-spec dicts, or None
        # for the generic vjp-derived gradient.
        self.grad = grad
        self.no_grad = no_grad
        self.stochastic = stochastic
        self.skip_exec = skip_exec  # executor/infer ignore (feed/fetch markers)
        # host ops run OUTSIDE the jitted computation, between XLA segments
        # (RPC send/recv, listen_and_serv, checkpoint notify — the reference's
        # non-kernel OperatorBase ops, SURVEY.md §2.7). Signature:
        # host_fn(op, scope). The executor partitions the block at host ops.
        self.host_fn = host_fn

    @property
    def is_host(self):
        return self.host_fn is not None


OPS = {}


def register(type, **kwargs):
    """Decorator: @register("matmul") def lower(ctx, ins, attrs): ..."""

    def deco(fn):
        OPS[type] = OpDef(type, lower=fn, **kwargs)
        return fn

    return deco


def register_no_lower(type, **kwargs):
    OPS[type] = OpDef(type, lower=None, skip_exec=True, **kwargs)


def register_host(type, **kwargs):
    """Decorator: @register_host("send") def run(op, scope): ... Host ops are
    no-grad and contribute no shape inference."""

    def deco(fn):
        OPS[type] = OpDef(type, lower=None, no_grad=True, host_fn=fn, **kwargs)
        return fn

    return deco


def get(type):
    d = OPS.get(type)
    if d is not None:
        return d
    if type.endswith("_grad"):
        base = OPS.get(type[: -len("_grad")])
        if base is not None and base.lower is not None:
            d = OpDef(type, lower=_make_generic_grad(base), no_grad=True)
            OPS[type] = d
            return d
    raise KeyError("no op registered for type %r" % type)


def is_registered(type):
    try:
        get(type)
        return True
    except KeyError:
        return False


class LowerCtx:
    """Per-trace context handed to lowerings. Threads the PRNG key through the
    block (stochastic ops call next_rng()), carries build attrs, and exposes
    the SPMD mesh (None single-device) so mesh-aware ops (ring attention,
    sharded embedding) can pick their distributed lowering.

    zero1_axis (a mesh axis name, normally 'dp') selects the ZeRO-1 sharded
    optimizer tier: optimizer-op lowerings (core_ops._opt_f32) constrain their
    gradient to a sharded layout (GSPMD → reduce-scatter), update the 1/dp
    param+moment shard locally, and constrain ParamOut back to replicated
    (→ all-gather). Set by _CompiledBlock when the ParallelExecutor build
    strategy asks for ReduceStrategy.Reduce.

    sharding (a parallel.sharding_rules.Resolver, or None) is the
    declarative rule engine bound to this trace's mesh: optimizer lowerings
    consult it for the parameter's storage layout (FSDP/TP take precedence
    over the zero1 tier per param), fused Pallas lowerings decline when it
    shards their tile dims, and _lower_one constrains rule-matched op
    outputs. `op` is the framework Operator currently being lowered (set by
    _lower_one; lowerings only see traced values, so the op is the only
    handle back to variable NAMES)."""

    def __init__(self, key, is_test=False, mesh=None, zero1_axis=None,
                 sharding=None):
        self.key = key
        self.is_test = is_test
        self.mesh = mesh
        self.zero1_axis = zero1_axis
        self.sharding = sharding
        self.op = None

    def next_rng(self):
        self.key, sub = jax.random.split(self.key)
        return sub


def _clean_attrs(attrs):
    return {k: v for k, v in attrs.items() if k not in _META_ATTRS}


def _make_generic_grad(fwd_def):
    """Build the vjp-derived lowering for `{type}_grad`.

    The grad op's inputs follow the reference convention (grad_op_desc_maker.h
    DefaultGradOpDescMaker): forward input slots, forward output slots, and
    `<out-slot>@GRAD` cotangents. Outputs are `<in-slot>@GRAD`. Differentiable
    leaves are the floating-point forward inputs; everything else rides in the
    closure. Missing cotangents become zeros.
    """

    def lower(ctx, ins, attrs):
        in_slots = list(attrs[FWD_IN_SLOTS_ATTR])
        out_slots = list(attrs[FWD_OUT_SLOTS_ATTR])
        fwd_attrs = _clean_attrs(attrs)
        fwd_ins = {s: list(ins[s]) for s in in_slots if s in ins}

        leaves, spec = [], []
        for s in in_slots:
            for i, v in enumerate(fwd_ins.get(s, [])):
                if v is not None and jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating):
                    leaves.append(v)
                    spec.append((s, i))

        def f(*leaf_vals):
            d = {s: list(vs) for s, vs in fwd_ins.items()}
            for (s, i), v in zip(spec, leaf_vals):
                d[s][i] = v
            outs = fwd_def.lower(ctx, d, fwd_attrs)
            return tuple(tuple(outs.get(s, ())) for s in out_slots)

        primals, vjp_fn = jax.vjp(f, *leaves)

        cots = []
        for s, pvals in zip(out_slots, primals):
            gs = ins.get(s + _GRAD_SUFFIX)
            row = []
            for i, p in enumerate(pvals):
                g = gs[i] if gs is not None and i < len(gs) and gs[i] is not None else None
                row.append(
                    g.astype(p.dtype) if g is not None else jnp.zeros(p.shape, p.dtype)
                )
            cots.append(tuple(row))
        grads = vjp_fn(tuple(cots))

        out = {}
        for (s, i), g in zip(spec, grads):
            lst = out.setdefault(s + _GRAD_SUFFIX, {})
            lst[i] = g
        result = {}
        for s, d in out.items():
            n = max(d) + 1
            result[s] = [d.get(i) for i in range(n)]
        return result

    return lower


EMPTY_VAR_NAME = "@EMPTY@"  # reference core.kEmptyVarName

# named_scope only keeps a conservative charset (jax drops e.g. '@', so
# "x@GRAD" would silently become "x"); sanitize OURSELVES so the exact
# string that lands in the HLO op_name metadata is predictable and the
# parser (profiler._hlo_op_attribution) can invert it
_SCOPE_UNSAFE = _re.compile(r"[^A-Za-z0-9_.=\-]")
OUT_SCOPE_PREFIX = "out="

# passes.builtin.FuseElemwiseActPass tags matmul/conv+add[+act] chains with
# this attr; lower_ops lowers a contiguous run sharing one tag inside a
# single enclosing named_scope ("fusion_group=<id>") so XLA's fusion
# heuristics see the chain as a unit and the profiler can attribute its
# HLO to the group (profiler._hlo_op_attribution skips the wrapper segment)
FUSION_GROUP_ATTR = "__fusion_group__"
FUSION_SCOPE_PREFIX = "fusion_group="

# Kernel-substitution tier (docs/passes.md "Kernel substitution"): the
# fuse_gemm_epilogue / fuse_layer_norm passes tag op runs
# with a group id + a kernel family name; lower_ops hands a contiguous
# same-group run to the family's registered FUSED lowering (a Pallas kernel,
# ops/pallas_kernels.py) instead of lowering op by op. A fused lowering may
# DECLINE at trace time (ragged shapes, unsupported attrs, a mesh)
# by returning False — the run then falls back to per-op lowering with
# identical semantics, so tagging is always safe. Like FUSION_GROUP_ATTR
# the tags are attr-only: def-use, op order, and count are untouched.
PALLAS_GROUP_ATTR = "__pallas_group__"
PALLAS_KERNEL_ATTR = "__pallas_kernel__"
PALLAS_SCOPE_PREFIX = "pallas_kernel="

# kernel family name -> fused lowering fn(ctx, ops, env) -> bool (True when
# the run was handled and its outputs written into env)
FUSED_LOWERINGS = {}


def register_fused(family):
    """Decorator: @register_fused("gemm_epilogue")
    def lower_run(ctx, ops, env) -> bool: ..."""

    def deco(fn):
        FUSED_LOWERINGS[family] = fn
        return fn

    return deco


def gather_op_inputs(op, env):
    """Resolve an op's input slots from the lowering env (shared by
    _lower_one and the fused lowerings)."""
    ins = {}
    for slot, names in op.inputs.items():
        if names:
            ins[slot] = [
                env[n] if n != EMPTY_VAR_NAME else None for n in names
            ]
    return ins


def scatter_op_outputs(op, outs, env):
    """Bind an op's output slots back into the lowering env (shared by
    _lower_one and the fused lowerings)."""
    for slot, names in op.outputs.items():
        vals = outs.get(slot)
        if vals is None:
            continue
        for name, val in zip(names, vals):
            if val is not None and name != EMPTY_VAR_NAME:
                env[name] = val


def op_output_scope(op):
    """Scope name carrying the op's identity (its first real output var) into
    the HLO metadata, or None for ops with no named outputs. Ops themselves
    are anonymous in fluid programs — outputs are the only stable handle."""
    for name in op.output_arg_names:
        if name != EMPTY_VAR_NAME:
            return OUT_SCOPE_PREFIX + _SCOPE_UNSAFE.sub("_", name)
    return None


def _lower_one(ctx, op, env):
    """Lower a single op into env (see lower_ops)."""
    opdef = get(op.type)
    if opdef.skip_exec:
        return
    ins = gather_op_inputs(op, env)
    # named_scope tags every HLO this op emits with op_name="…/<type>/…"
    # metadata — the correlation key profiler.device_op_profile uses to
    # fold XLA's per-HLO device timings back onto framework op types
    # (the reference correlates CUPTI kernels to ops the same way,
    # platform/device_tracer.cc). A nested "out=<first output>" scope
    # distinguishes op INSTANCES (profiler._hlo_op_attribution); the
    # type-level parse skips it, so device_op_profile is unchanged.
    out_scope = op_output_scope(op)
    ctx.op = op  # name handle for sharding-aware lowerings (LowerCtx doc)
    with jax.named_scope(op.type):
        if out_scope is None:
            outs = opdef.lower(ctx, ins, op.attrs)
        else:
            with jax.named_scope(out_scope):
                outs = opdef.lower(ctx, ins, op.attrs)
    ctx.op = None
    scatter_op_outputs(op, outs, env)
    if ctx.sharding is not None:
        # rule-matched outputs (params written back, annotated activations)
        # get their declared placement pinned right where they materialize
        ctx.sharding.constrain_outputs(op, env)


def _lower_pallas_run(ctx, run, env):
    """Try the registered fused Pallas lowering for a tagged run; fall back to
    per-op lowering when the family is unknown or the lowering declines."""
    family = run[0].attrs.get(PALLAS_KERNEL_ATTR)
    fused = FUSED_LOWERINGS.get(family)
    gid = run[0].attrs.get(PALLAS_GROUP_ATTR)
    # "<family>.<gid>" so the profiler can attribute the kernel's HLO to a
    # "pallas:<family>" row with per-group instances (profiler.py)
    scope = PALLAS_SCOPE_PREFIX + _SCOPE_UNSAFE.sub(
        "_", "%s.%s" % (family, gid)
    )
    if fused is not None:
        with jax.named_scope(scope):
            if fused(ctx, run, env):
                return
    for member in run:
        _lower_one(ctx, member, env)


def lower_ops(ctx, ops, env):
    """Lower a list of ops into an env (name -> traced value), rebinding
    outputs. The single shared interpreter loop for the whole-block executor
    (executor.py) and for sub-block control-flow ops (while/cond/recurrent in
    control_flow_ops.py) — the reference's Executor::RunPreparedContext loop
    (executor.cc:389-396) respectively its nested-Executor reuse inside
    while_op.cc:36.

    Contiguous runs of ops sharing a FUSION_GROUP_ATTR value (tagged by the
    fuse_elemwise_act pass) lower inside ONE enclosing named_scope: the
    group's HLO shares an op_name prefix, so XLA's fusion heuristics and the
    profiler's attribution both see the chain as a unit.

    Contiguous runs sharing a PALLAS_GROUP_ATTR value (tagged by the
    fuse_gemm_epilogue / fuse_layer_norm passes) are handed
    to the family's fused Pallas lowering (FUSED_LOWERINGS); a decline falls
    back to per-op lowering. Pallas tags take precedence over fusion-group
    tags when an op carries both (the kernel subsumes the XLA fusion hint)."""
    i, n = 0, len(ops)
    while i < n:
        op = ops[i]
        pg = op.attrs.get(PALLAS_GROUP_ATTR)
        if pg is not None:
            j = i
            while j < n and ops[j].attrs.get(PALLAS_GROUP_ATTR) == pg:
                j += 1
            _lower_pallas_run(ctx, ops[i:j], env)
            i = j
            continue
        fg = op.attrs.get(FUSION_GROUP_ATTR)
        if fg is None:
            _lower_one(ctx, op, env)
            i += 1
            continue
        j = i
        while j < n and (
            ops[j].attrs.get(FUSION_GROUP_ATTR) == fg
            and ops[j].attrs.get(PALLAS_GROUP_ATTR) is None
        ):
            j += 1
        with jax.named_scope(
            FUSION_SCOPE_PREFIX + _SCOPE_UNSAFE.sub("_", str(fg))
        ):
            for member in ops[i:j]:
                _lower_one(ctx, member, env)
        i = j
    return env


# ---------------------------------------------------------------------------
# shape inference (reference: per-op InferShape, operator.cc:705; here derived
# from the lowering itself with jax.eval_shape)
# ---------------------------------------------------------------------------


def infer_shape(op, block):
    try:
        opdef = get(op.type)
    except KeyError:
        return  # unknown ops get shapes from custom layer code or stay None
    if opdef.custom_infer_shape is not None:
        opdef.custom_infer_shape(op, block)
        return
    if opdef.lower is None or opdef.skip_exec:
        return

    abstract_ins = {}
    for slot, names in op.inputs.items():
        vals = []
        for name in names:
            if name == EMPTY_VAR_NAME:
                vals.append(None)
                continue
            v = block._var_recursive(name)
            if v.shape is None or v.dtype is None:
                return  # cannot infer yet (e.g. fed later) — leave outputs as-is
            shape = tuple(_DYN_SENTINEL if d == -1 else d for d in v.shape)
            vals.append(jax.ShapeDtypeStruct(shape, jnp.dtype(v.dtype)))
        abstract_ins[slot] = vals

    attrs = dict(op.attrs)
    ctx = LowerCtx(jax.eval_shape(lambda: jax.random.key(0)), is_test=True)

    def run(ins):
        c = LowerCtx(jax.random.key(0), is_test=bool(attrs.get("is_test", False)))
        return opdef.lower(c, ins, attrs)

    try:
        outs = jax.eval_shape(run, abstract_ins)
    except Exception as e:  # surface shape errors at build time, like InferShape
        raise ValueError(
            "shape inference failed for op %s: %s" % (op, e)
        ) from e

    for slot, names in op.outputs.items():
        vals = outs.get(slot)
        if vals is None:
            continue
        for name, aval in zip(names, vals):
            if aval is None or name == EMPTY_VAR_NAME:
                continue
            v = block._var_recursive(name)
            v.shape = tuple(-1 if d == _DYN_SENTINEL else d for d in aval.shape)
            v.dtype = framework.convert_np_dtype(aval.dtype)


# ---------------------------------------------------------------------------
# shared helpers for lowerings
# ---------------------------------------------------------------------------


def bcast_y(x, y, axis):
    """Paddle elementwise broadcast: align y's dims to x starting at `axis`
    (reference operators/elementwise/elementwise_op_function.h). axis=-1 means
    align trailing dims (NumPy style after right-padding)."""
    if x.ndim == y.ndim:
        return y
    if axis == -1:
        axis = x.ndim - y.ndim
    # trim trailing 1s in y (paddle allows y shape (..., 1, 1))
    yshape = list(y.shape)
    while yshape and yshape[-1] == 1 and len(yshape) > 1 and axis + len(yshape) > x.ndim:
        yshape.pop()
    new_shape = [1] * x.ndim
    for i, d in enumerate(yshape):
        new_shape[axis + i] = d
    return y.reshape(new_shape)


def reduce_grad_to_shape(g, shape):
    """Sum-reduce a broadcasted gradient back to `shape` (for custom grads)."""
    if tuple(g.shape) == tuple(shape):
        return g
    extra = g.ndim - len(shape)
    if extra > 0:
        g = g.sum(axis=tuple(range(extra)))
    axes = tuple(i for i, d in enumerate(shape) if d == 1 and g.shape[i] != 1)
    if axes:
        g = g.sum(axis=axes, keepdims=True)
    return g.reshape(shape)
