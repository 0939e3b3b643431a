"""Core operator lowerings (dense math, NN, tensor manipulation, optimizers).

Reference analog: paddle/fluid/operators/*.cc/.cu (336 registered ops, §2.5 of
SURVEY.md). Each lowering is a pure JAX function over slot-keyed arrays; the
executor stitches a whole block of them into ONE jitted XLA computation, so
elementwise chains fuse into the adjacent matmuls/convs on the MXU instead of
being separate kernel launches as in the reference's per-op dispatch loop
(reference framework/executor.cc:389-396).

Gradients: nearly all ops rely on the registry's generic jax.vjp grad
(registry._make_generic_grad). Custom grads exist only where vjp-replay is
wrong (dropout must reuse its sampled Mask).

Dtype policy (TPU-first): float64→float32 and int64→int32 are canonicalized at
the framework boundary (TPUs have no fast f64/i64 path), mirroring JAX's own
default dtype canonicalization.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import framework
from .registry import (
    LowerCtx,
    bcast_y,
    register,
    register_no_lower,
)

# ---------------------------------------------------------------------------
# executor-handled markers
# ---------------------------------------------------------------------------

register_no_lower("feed")
register_no_lower("fetch")


def _dtype(attr_dtype):
    return jnp.dtype(framework.convert_np_dtype(attr_dtype))


def _rng(ctx, attrs):
    seed = int(attrs.get("seed", 0) or 0)
    if seed:
        return jax.random.key(seed)
    return ctx.next_rng()


# ---------------------------------------------------------------------------
# creation / random ops (reference: fill_constant_op.cc, uniform_random_op.cc,
# gaussian_random_op.cc, truncated_gaussian_random_op.cc)
# ---------------------------------------------------------------------------


@register("fill_constant", no_grad=True)
def _fill_constant(ctx, ins, attrs):
    shape = [int(s) for s in attrs["shape"]]
    dt = _dtype(attrs.get("dtype", "float32"))
    return {"Out": [jnp.full(shape, attrs.get("value", 0.0), dtype=dt)]}


@register("fill_constant_batch_size_like", no_grad=True)
def _fill_constant_bsl(ctx, ins, attrs):
    (ref,) = ins["Input"]
    shape = [int(s) for s in attrs["shape"]]
    in_idx = int(attrs.get("input_dim_idx", 0))
    out_idx = int(attrs.get("output_dim_idx", 0))
    shape[out_idx] = ref.shape[in_idx]
    dt = _dtype(attrs.get("dtype", "float32"))
    return {"Out": [jnp.full(shape, attrs.get("value", 0.0), dtype=dt)]}


@register("fill_zeros_like", no_grad=True)
def _fill_zeros_like(ctx, ins, attrs):
    (x,) = ins["X"]
    return {"Out": [jnp.zeros_like(x)]}


@register("uniform_random", no_grad=True, stochastic=True)
def _uniform_random(ctx, ins, attrs):
    shape = [int(s) for s in attrs["shape"]]
    dt = _dtype(attrs.get("dtype", "float32"))
    out = jax.random.uniform(
        _rng(ctx, attrs),
        shape,
        dtype=jnp.float32,
        minval=attrs.get("min", -1.0),
        maxval=attrs.get("max", 1.0),
    )
    return {"Out": [out.astype(dt)]}


@register("gaussian_random", no_grad=True, stochastic=True)
def _gaussian_random(ctx, ins, attrs):
    shape = [int(s) for s in attrs["shape"]]
    dt = _dtype(attrs.get("dtype", "float32"))
    out = attrs.get("mean", 0.0) + attrs.get("std", 1.0) * jax.random.normal(
        _rng(ctx, attrs), shape, dtype=jnp.float32
    )
    return {"Out": [out.astype(dt)]}


@register("truncated_gaussian_random", no_grad=True, stochastic=True)
def _truncated_gaussian_random(ctx, ins, attrs):
    shape = [int(s) for s in attrs["shape"]]
    dt = _dtype(attrs.get("dtype", "float32"))
    out = attrs.get("mean", 0.0) + attrs.get("std", 1.0) * jax.random.truncated_normal(
        _rng(ctx, attrs), -2.0, 2.0, shape, dtype=jnp.float32
    )
    return {"Out": [out.astype(dt)]}


@register("assign_value", no_grad=True)
def _assign_value(ctx, ins, attrs):
    dt = _dtype(attrs.get("dtype", "float32"))
    vals = np.asarray(attrs["values"]).reshape([int(s) for s in attrs["shape"]])
    return {"Out": [jnp.asarray(vals, dtype=dt)]}


@register("assign")
def _assign(ctx, ins, attrs):
    (x,) = ins["X"]
    return {"Out": [x]}


@register("cast")
def _cast(ctx, ins, attrs):
    (x,) = ins["X"]
    return {"Out": [x.astype(_dtype(attrs["out_dtype"]))]}


@register("shape", no_grad=True)
def _shape(ctx, ins, attrs):
    (x,) = ins["Input"]
    return {"Out": [jnp.asarray(x.shape, dtype=jnp.int32)]}


# ---------------------------------------------------------------------------
# dense math (reference: mul_op.cc, matmul_op.cc, operators/math/blas.h — on
# TPU these land on the MXU via XLA dot_general)
# ---------------------------------------------------------------------------


@register("mul")
def _mul(ctx, ins, attrs):
    (x,) = ins["X"]
    (y,) = ins["Y"]
    xnc = int(attrs.get("x_num_col_dims", 1))
    ync = int(attrs.get("y_num_col_dims", 1))
    x2 = x.reshape((int(np.prod(x.shape[:xnc])), -1))
    y2 = y.reshape((int(np.prod(y.shape[:ync])), -1))
    out = x2 @ y2
    out_shape = tuple(x.shape[:xnc]) + tuple(y.shape[ync:])
    return {"Out": [out.reshape(out_shape)]}


@register("matmul")
def _matmul(ctx, ins, attrs):
    (x,) = ins["X"]
    (y,) = ins["Y"]
    tx, ty = attrs.get("transpose_X", False), attrs.get("transpose_Y", False)
    alpha = attrs.get("alpha", 1.0)
    if x.ndim == 1:
        x = x[None, :]
    if y.ndim == 1:
        y = y[:, None]
    if tx:
        x = jnp.swapaxes(x, -1, -2)
    if ty:
        y = jnp.swapaxes(y, -1, -2)
    # out_dtype: the accumulator's dtype kept for the result (bf16
    # operands, float32 logits) instead of one more rounding
    out_dtype = attrs.get("out_dtype")
    out = jnp.matmul(
        x, y, preferred_element_type=_dtype(out_dtype) if out_dtype else None)
    if alpha != 1.0:
        out = out * jnp.asarray(alpha, out.dtype)
    return {"Out": [out]}


# ---------------------------------------------------------------------------
# elementwise binary with paddle axis-broadcast
# (reference: operators/elementwise/elementwise_op_function.h)
# ---------------------------------------------------------------------------


def _register_elementwise(name, fn):
    @register(name)
    def _lower(ctx, ins, attrs, _fn=fn):
        (x,) = ins["X"]
        (y,) = ins["Y"]
        y = bcast_y(x, y, int(attrs.get("axis", -1)))
        return {"Out": [_fn(x, y)]}


_register_elementwise("elementwise_add", jnp.add)
_register_elementwise("elementwise_sub", jnp.subtract)
_register_elementwise("elementwise_mul", jnp.multiply)
_register_elementwise("elementwise_div", jnp.divide)
_register_elementwise("elementwise_min", jnp.minimum)
_register_elementwise("elementwise_max", jnp.maximum)
_register_elementwise("elementwise_pow", jnp.power)
_register_elementwise("elementwise_mod", jnp.mod)
_register_elementwise("elementwise_floordiv", jnp.floor_divide)


@register("sum")
def _sum(ctx, ins, attrs):
    xs = ins["X"]
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return {"Out": [out]}


@register("scale")
def _scale(ctx, ins, attrs):
    (x,) = ins["X"]
    s = jnp.asarray(attrs.get("scale", 1.0), x.dtype)
    b = jnp.asarray(attrs.get("bias", 0.0), x.dtype)
    if attrs.get("bias_after_scale", True):
        return {"Out": [x * s + b]}
    return {"Out": [(x + b) * s]}


@register("increment")
def _increment(ctx, ins, attrs):
    (x,) = ins["X"]
    return {"Out": [x + jnp.asarray(attrs.get("step", 1.0), x.dtype)]}


@register("clip")
def _clip(ctx, ins, attrs):
    (x,) = ins["X"]
    return {"Out": [jnp.clip(x, attrs["min"], attrs["max"])]}


@register("clip_by_norm")
def _clip_by_norm(ctx, ins, attrs):
    (x,) = ins["X"]
    max_norm = attrs["max_norm"]
    norm = jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2))
    scale = jnp.where(norm > max_norm, max_norm / jnp.maximum(norm, 1e-12), 1.0)
    return {"Out": [(x.astype(jnp.float32) * scale).astype(x.dtype)]}


@register("squared_l2_norm")
def _squared_l2_norm(ctx, ins, attrs):
    (x,) = ins["X"]
    return {"Out": [jnp.sum(x.astype(jnp.float32) ** 2).reshape((1,)).astype(x.dtype)]}


# ---------------------------------------------------------------------------
# activations (reference: activation_op.cc — ~20 activations)
# ---------------------------------------------------------------------------


def _register_act(name, fn):
    @register(name)
    def _lower(ctx, ins, attrs, _fn=fn):
        (x,) = ins["X"]
        return {"Out": [_fn(x, attrs)]}


_register_act("relu", lambda x, a: jnp.maximum(x, 0))
_register_act("sigmoid", lambda x, a: jax.nn.sigmoid(x))
_register_act("logsigmoid", lambda x, a: jax.nn.log_sigmoid(x))
_register_act("tanh", lambda x, a: jnp.tanh(x))
_register_act("tanh_shrink", lambda x, a: x - jnp.tanh(x))
_register_act("sqrt", lambda x, a: jnp.sqrt(x))
_register_act("abs", lambda x, a: jnp.abs(x))
_register_act("ceil", lambda x, a: jnp.ceil(x))
_register_act("floor", lambda x, a: jnp.floor(x))
_register_act("cos", lambda x, a: jnp.cos(x))
_register_act("sin", lambda x, a: jnp.sin(x))
_register_act("round", lambda x, a: jnp.round(x))
_register_act("reciprocal", lambda x, a: 1.0 / x)
_register_act("exp", lambda x, a: jnp.exp(x))
_register_act("log", lambda x, a: jnp.log(x))
_register_act("square", lambda x, a: jnp.square(x))
_register_act("softplus", lambda x, a: jax.nn.softplus(x))
_register_act("softsign", lambda x, a: jax.nn.soft_sign(x))
_register_act("softshrink", lambda x, a: jnp.sign(x) * jnp.maximum(jnp.abs(x) - a.get("lambda", 0.5), 0))
_register_act("hard_shrink", lambda x, a: jnp.where(jnp.abs(x) > a.get("threshold", 0.5), x, 0))
_register_act("brelu", lambda x, a: jnp.clip(x, a.get("t_min", 0.0), a.get("t_max", 24.0)))
_register_act("leaky_relu", lambda x, a: jnp.where(x >= 0, x, x * a.get("alpha", 0.02)))
_register_act(
    "soft_relu",
    lambda x, a: jnp.log1p(jnp.exp(jnp.clip(x, -a.get("threshold", 40.0), a.get("threshold", 40.0)))),
)
_register_act("elu", lambda x, a: jnp.where(x >= 0, x, a.get("alpha", 1.0) * (jnp.exp(x) - 1)))
_register_act("relu6", lambda x, a: jnp.clip(x, 0, a.get("threshold", 6.0)))
_register_act("pow", lambda x, a: jnp.power(x, a.get("factor", 1.0)))
_register_act(
    "stanh",
    lambda x, a: a.get("scale_b", 1.7159) * jnp.tanh(a.get("scale_a", 0.67) * x),
)
_register_act(
    "hard_sigmoid",
    lambda x, a: jnp.clip(a.get("slope", 0.2) * x + a.get("offset", 0.5), 0.0, 1.0),
)
_register_act("swish", lambda x, a: x * jax.nn.sigmoid(a.get("beta", 1.0) * x))
_register_act("gelu", lambda x, a: jax.nn.gelu(x, approximate=False))
_register_act(
    "thresholded_relu", lambda x, a: jnp.where(x > a.get("threshold", 1.0), x, 0)
)
_register_act("rsqrt", lambda x, a: lax.rsqrt(x))
_register_act("sign", lambda x, a: jnp.sign(x))


@register("prelu")
def _prelu(ctx, ins, attrs):
    (x,) = ins["X"]
    (alpha,) = ins["Alpha"]
    mode = attrs.get("mode", "all")
    if mode == "channel":
        alpha = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    elif mode == "all":
        alpha = alpha.reshape(())
    return {"Out": [jnp.where(x >= 0, x, x * alpha)]}


# ---------------------------------------------------------------------------
# softmax / losses (reference: softmax_op.cc, softmax_with_cross_entropy_op.cc,
# cross_entropy_op.cc, mean_op.cc, huber/smooth-l1/log/hinge losses)
# ---------------------------------------------------------------------------


@register("softmax")
def _softmax(ctx, ins, attrs):
    (x,) = ins["X"]
    return {"Out": [jax.nn.softmax(x, axis=-1)]}


@register("log_softmax")
def _log_softmax(ctx, ins, attrs):
    (x,) = ins["X"]
    return {"Out": [jax.nn.log_softmax(x, axis=int(attrs.get("axis", -1)))]}


def _softmax_ce_grad_maker(op, block, grad_map):
    outputs = {}
    logits_g = grad_map.get(op.input("Logits")[0])
    if logits_g:
        outputs["Logits@GRAD"] = [logits_g]
    # soft labels are float and may carry gradient (e.g. via label_smooth)
    lbl_g = (
        grad_map.get(op.input("Label")[0])
        if op.attrs.get("soft_label", False)
        else None
    )
    if lbl_g:
        outputs["Label@GRAD"] = [lbl_g]
    if not outputs:
        return []
    inputs = {
        "Softmax": [op.output("Softmax")[0]],
        "Label": [op.input("Label")[0]],
    }
    # Loss may carry no gradient (e.g. only the Softmax output is consumed
    # downstream); the grad lowering treats a missing dloss as zeros
    loss_g = grad_map.get(op.output("Loss")[0])
    if loss_g:
        inputs["Loss@GRAD"] = [loss_g]
    # a downstream consumer of the Softmax output contributes through the
    # softmax Jacobian as well (grad_map only has the entry when it flows)
    sm_g = grad_map.get(op.output("Softmax")[0])
    if sm_g:
        inputs["Softmax@GRAD"] = [sm_g]
    return [
        {
            "type": "softmax_with_cross_entropy_grad",
            "inputs": inputs,
            "outputs": outputs,
            "attrs": {k: v for k, v in op.attrs.items()},
        }
    ]


@register("softmax_with_cross_entropy", grad=_softmax_ce_grad_maker)
def _softmax_with_ce(ctx, ins, attrs):
    """Numerically-safe CE in the INPUT dtype: under bf16 mixed precision the
    [N, V] tensors stay bf16 in HBM while the log-sum-exp accumulates in f32
    (the f32 intermediates live only inside the XLA fusion). Loss is computed
    from the log-partition z = max + lse and a gather on the raw logits —
    never from a materialized [N, V] log-softmax (for a 32k vocab the f32
    [N, V] passes were ~11 ms/step of pure HBM traffic on the bench chip,
    round-4 per-HLO audit)."""
    (logits,) = ins["Logits"]
    (label,) = ins["Label"]
    m = jnp.max(logits, axis=-1, keepdims=True)
    sh = (logits - m).astype(jnp.float32)
    lse = jnp.log(jnp.sum(jnp.exp(sh), axis=-1, keepdims=True))  # f32 [N,1]
    softmax = jnp.exp(sh - lse).astype(logits.dtype)
    z = m.astype(jnp.float32) + lse  # log partition
    if attrs.get("soft_label", False):
        # sum_j label_j * (z - logit_j), without materializing log-softmax
        s_lbl = jnp.sum(label, axis=-1, keepdims=True, dtype=jnp.float32)
        s_ll = jnp.sum(
            label.astype(jnp.float32) * logits.astype(jnp.float32),
            axis=-1,
            keepdims=True,
        )
        loss = z * s_lbl - s_ll
    else:
        lbl = label.reshape(label.shape[:-1]).astype(jnp.int32)
        picked = jnp.take_along_axis(logits, lbl[..., None], axis=-1).astype(
            jnp.float32
        )
        eps = float(attrs.get("smooth_eps", 0.0) or 0.0)
        if eps:
            # exact uniform label smoothing WITHOUT the [N, V] one-hot the
            # reference pipeline materializes (label_smooth + soft_label CE):
            # sum_j smooth_j·(−logp_j) with smooth = ε/V + (1−ε)δ_y reduces
            # to (1−ε)·(z−logit_y) + ε·(z − mean_j logit_j)
            mean_l = jnp.mean(
                logits.astype(jnp.float32), axis=-1, keepdims=True
            )
            loss = (1.0 - eps) * (z - picked) + eps * (z - mean_l)
        else:
            loss = z - picked
        ignore = int(attrs.get("ignore_index", -100))
        loss = jnp.where(lbl[..., None] == ignore, 0.0, loss)
    return {"Softmax": [softmax], "Loss": [loss.astype(logits.dtype)]}


@register("softmax_with_cross_entropy_grad", no_grad=True)
def _softmax_with_ce_grad(ctx, ins, attrs):
    """Closed-form CE backward from the SAVED Softmax (reference
    softmax_with_cross_entropy_op.h CrossEntropyGrad): dlogits =
    dloss · (softmax − target), no forward recompute. Kept in the softmax
    dtype, one-hot built by iota compare (no scatter), and wrapped in an
    optimization_barrier so XLA materializes the [N, V] gradient ONCE
    instead of recomputing it inside both the dW and dX consumer fusions
    (measured duplication cost ~8 ms/step on the bench transformer,
    round-4 audit)."""
    dloss = ins.get("Loss@GRAD", [None])[0]  # [N, 1] or absent (zeros)
    (softmax,) = ins["Softmax"]  # [N, V]
    (label,) = ins["Label"]
    dsm = ins.get("Softmax@GRAD", [None])[0]
    v = softmax.shape[-1]
    result = {}
    if attrs.get("soft_label", False):
        s_lbl = jnp.sum(label, axis=-1, keepdims=True).astype(softmax.dtype)
        d = softmax * s_lbl - label.astype(softmax.dtype)
        # dloss/dlabel_j = −logp_j, from the saved softmax
        neg_logp = -jnp.log(jnp.maximum(softmax.astype(jnp.float32), 1e-38))
        dl32 = (
            dloss.astype(jnp.float32)
            if dloss is not None
            else jnp.zeros(softmax.shape[:-1] + (1,), jnp.float32)
        )
        result["Label@GRAD"] = [(dl32 * neg_logp).astype(label.dtype)]
    else:
        lbl = label.reshape(label.shape[:-1]).astype(jnp.int32)
        onehot = (
            lax.broadcasted_iota(jnp.int32, softmax.shape, softmax.ndim - 1)
            == lbl[..., None]
        )
        eps = float(attrs.get("smooth_eps", 0.0) or 0.0)
        if eps:
            tgt = (1.0 - eps) * onehot.astype(jnp.float32) + eps / v
            d = (softmax.astype(jnp.float32) - tgt).astype(softmax.dtype)
        else:
            d = softmax - onehot.astype(softmax.dtype)
        ignore = int(attrs.get("ignore_index", -100))
        d = jnp.where((lbl != ignore)[..., None], d, 0)
    out = d * dloss.astype(d.dtype) if dloss is not None else jnp.zeros_like(softmax)
    if dsm is not None:
        # Jacobian of softmax applied to the Softmax output's own cotangent:
        # Jᵀ dS = s ⊙ (dS − ⟨dS, s⟩)
        s32 = softmax.astype(jnp.float32)
        dsm32 = dsm.astype(jnp.float32)
        inner = jnp.sum(dsm32 * s32, axis=-1, keepdims=True)
        out = out + (s32 * (dsm32 - inner)).astype(out.dtype)
    result["Logits@GRAD"] = [lax.optimization_barrier(out)]
    return result


@register("cross_entropy")
def _cross_entropy(ctx, ins, attrs):
    (x,) = ins["X"]
    (label,) = ins["Label"]
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(jnp.maximum(x, 1e-20)), axis=-1, keepdims=True)
    else:
        lbl = label.reshape(label.shape[:-1]).astype(jnp.int32)
        picked = jnp.take_along_axis(x, lbl[..., None], axis=-1)
        loss = -jnp.log(jnp.maximum(picked, 1e-20))
    return {"Y": [loss]}


@register("mean")
def _mean(ctx, ins, attrs):
    (x,) = ins["X"]
    return {"Out": [jnp.mean(x).reshape((1,))]}


@register("square_error_cost")
def _square_error_cost(ctx, ins, attrs):
    (x,) = ins["X"]
    (y,) = ins["Y"]
    return {"Out": [jnp.square(x - y)]}


@register("smooth_l1_loss")
def _smooth_l1(ctx, ins, attrs):
    (x,) = ins["X"]
    (y,) = ins["Y"]
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    diff = x - y
    if "InsideWeight" in ins:
        diff = diff * ins["InsideWeight"][0]
    ad = jnp.abs(diff)
    val = jnp.where(ad < 1.0 / s2, 0.5 * s2 * diff * diff, ad - 0.5 / s2)
    if "OutsideWeight" in ins:
        val = val * ins["OutsideWeight"][0]
    out = jnp.sum(val.reshape(val.shape[0], -1), axis=1, keepdims=True)
    return {"Out": [out], "Diff": [diff]}


@register("log_loss")
def _log_loss(ctx, ins, attrs):
    (p,) = ins["Predicted"]
    (l,) = ins["Labels"]
    eps = attrs.get("epsilon", 1e-4)
    out = -l * jnp.log(p + eps) - (1 - l) * jnp.log(1 - p + eps)
    return {"Loss": [out]}


@register("huber_loss")
def _huber_loss(ctx, ins, attrs):
    (x,) = ins["X"]
    (y,) = ins["Y"]
    delta = attrs.get("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    out = jnp.where(ar <= delta, 0.5 * r * r, delta * (ar - 0.5 * delta))
    return {"Out": [out], "Residual": [r]}


@register("hinge_loss")
def _hinge_loss(ctx, ins, attrs):
    (logits,) = ins["Logits"]
    (labels,) = ins["Labels"]
    return {"Loss": [jnp.maximum(0.0, 1.0 - (2.0 * labels - 1.0) * logits)]}


@register("sigmoid_cross_entropy_with_logits")
def _sigmoid_ce(ctx, ins, attrs):
    (x,) = ins["X"]
    (label,) = ins["Label"]
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore = attrs.get("ignore_index", -100)
    loss = jnp.where(label == ignore, 0.0, loss)
    return {"Out": [loss]}


# ---------------------------------------------------------------------------
# reductions / argmax / comparisons (reference: reduce_ops/, compare_op.cc,
# logical_op.cc, arg_max_op.cc, top_k_op.cc)
# ---------------------------------------------------------------------------


def _register_reduce(name, fn):
    @register(name)
    def _lower(ctx, ins, attrs, _fn=fn):
        (x,) = ins["X"]
        dims = attrs.get("dim", [0])
        if isinstance(dims, int):
            dims = [dims]
        keep = bool(attrs.get("keep_dim", False))
        if attrs.get("reduce_all", False):
            out = _fn(x, axis=None, keepdims=False).reshape((1,))
        else:
            axes = tuple(d % x.ndim for d in dims)
            out = _fn(x, axis=axes, keepdims=keep)
            if out.ndim == 0:
                out = out.reshape((1,))
        return {"Out": [out]}


_register_reduce("reduce_sum", jnp.sum)
_register_reduce("reduce_mean", jnp.mean)
_register_reduce("reduce_max", jnp.max)
_register_reduce("reduce_min", jnp.min)
_register_reduce("reduce_prod", jnp.prod)


def _register_compare(name, fn):
    @register(name, no_grad=True)
    def _lower(ctx, ins, attrs, _fn=fn):
        (x,) = ins["X"]
        (y,) = ins["Y"]
        y = bcast_y(x, y, int(attrs.get("axis", -1)))
        return {"Out": [_fn(x, y)]}


_register_compare("less_than", jnp.less)
_register_compare("less_equal", jnp.less_equal)
_register_compare("greater_than", jnp.greater)
_register_compare("greater_equal", jnp.greater_equal)
_register_compare("equal", jnp.equal)
_register_compare("not_equal", jnp.not_equal)


def _register_logical(name, fn, unary=False):
    @register(name, no_grad=True)
    def _lower(ctx, ins, attrs, _fn=fn, _unary=unary):
        (x,) = ins["X"]
        if _unary:
            return {"Out": [_fn(x)]}
        (y,) = ins["Y"]
        return {"Out": [_fn(x, y)]}


_register_logical("logical_and", jnp.logical_and)
_register_logical("logical_or", jnp.logical_or)
_register_logical("logical_xor", jnp.logical_xor)
_register_logical("logical_not", jnp.logical_not, unary=True)


@register("arg_max", no_grad=True)
def _arg_max(ctx, ins, attrs):
    (x,) = ins["X"]
    return {"Out": [jnp.argmax(x, axis=int(attrs.get("axis", -1))).astype(jnp.int32)]}


@register("arg_min", no_grad=True)
def _arg_min(ctx, ins, attrs):
    (x,) = ins["X"]
    return {"Out": [jnp.argmin(x, axis=int(attrs.get("axis", -1))).astype(jnp.int32)]}


@register("top_k", no_grad=True)
def _top_k(ctx, ins, attrs):
    (x,) = ins["X"]
    k = int(attrs["k"])
    vals, idx = lax.top_k(x, k)
    return {"Out": [vals], "Indices": [idx.astype(jnp.int32)]}


@register("argsort", no_grad=True)
def _argsort(ctx, ins, attrs):
    (x,) = ins["X"]
    axis = int(attrs.get("axis", -1))
    idx = jnp.argsort(x, axis=axis).astype(jnp.int32)
    out = jnp.sort(x, axis=axis)
    return {"Out": [out], "Indices": [idx]}


@register("cumsum")
def _cumsum(ctx, ins, attrs):
    (x,) = ins["X"]
    axis = int(attrs.get("axis", -1))
    out = jnp.cumsum(jnp.flip(x, axis) if attrs.get("reverse", False) else x, axis=axis)
    if attrs.get("reverse", False):
        out = jnp.flip(out, axis)
    if attrs.get("exclusive", False):
        pad = [(0, 0)] * x.ndim
        pad[axis] = (1, 0)
        out = jnp.pad(out, pad)[
            tuple(slice(0, x.shape[i]) if i == axis % x.ndim else slice(None) for i in range(x.ndim))
        ]
    return {"Out": [out]}


# ---------------------------------------------------------------------------
# metrics (reference: metrics/accuracy_op.cc, metrics/auc_op.cc)
# ---------------------------------------------------------------------------


@register("accuracy", no_grad=True)
def _accuracy(ctx, ins, attrs):
    (indices,) = ins["Indices"]
    (label,) = ins["Label"]
    correct = jnp.any(indices == label.astype(indices.dtype), axis=-1)
    num_correct = jnp.sum(correct.astype(jnp.float32))
    total = indices.shape[0]
    return {
        "Accuracy": [(num_correct / total).reshape((1,))],
        "Correct": [num_correct.astype(jnp.int32).reshape((1,))],
        "Total": [jnp.asarray([total], dtype=jnp.int32)],
    }


@register("auc", no_grad=True)
def _auc(ctx, ins, attrs):
    """Streaming AUC (reference metrics/auc_op.cc): histogram positives and
    negatives into threshold buckets, accumulate into StatPos/StatNeg, compute
    AUC by trapezoidal rule over the cumulative counts."""
    (predict,) = ins["Predict"]
    (label,) = ins["Label"]
    stat_pos, stat_neg = ins["StatPos"][0], ins["StatNeg"][0]
    n = int(attrs.get("num_thresholds", 4095))
    pos_prob = predict[:, -1]
    bucket = jnp.clip((pos_prob * n).astype(jnp.int32), 0, n)
    is_pos = (label.reshape(-1) > 0).astype(jnp.float32)
    pos_hist = jnp.zeros(n + 1, jnp.float32).at[bucket].add(is_pos)
    neg_hist = jnp.zeros(n + 1, jnp.float32).at[bucket].add(1.0 - is_pos)
    sp = stat_pos + pos_hist
    sn = stat_neg + neg_hist
    # descending threshold cumulative TP/FP
    tp = jnp.cumsum(sp[::-1])
    fp = jnp.cumsum(sn[::-1])
    tot_pos, tot_neg = tp[-1], fp[-1]
    tp0 = jnp.concatenate([jnp.zeros(1), tp[:-1]])
    fp0 = jnp.concatenate([jnp.zeros(1), fp[:-1]])
    area = jnp.sum((fp - fp0) * (tp + tp0) / 2.0)
    auc = jnp.where(tot_pos * tot_neg > 0, area / (tot_pos * tot_neg + 1e-12), 0.0)
    return {
        "AUC": [auc.reshape((1,))],
        "StatPosOut": [sp],
        "StatNegOut": [sn],
    }


def _chunk_flags(y, n_types, scheme, excluded, seqlen):
    """Per-position chunk (start, end, type) flags for a padded [b, t] int
    tag grid under one of the conlleval tagging schemes.

    Tag layout matches the reference chunk_eval_op.h: label =
    chunk_type * num_tag_types + tag_type, with tag ids B=0,I=1 (IOB) /
    I=0,E=1 (IOE) / B=0,I=1,E=2,S=3 (IOBES) / the single tag 0 (plain, every
    tagged position its own chunk); any label outside [0, n_types*num_tag)
    is the O tag. A chunk starts where the tag says so OR the type changes
    OR the previous position is O/sequence-start (conlleval's boundary
    rules), and symmetrically for ends.
    """
    ntag = {"plain": 1, "IOB": 2, "IOE": 2, "IOBES": 4}[scheme]
    typ = y // ntag
    tag = y % ntag
    valid = (y >= 0) & (y < n_types * ntag)
    for ex in excluded:
        valid = valid & (typ != int(ex))
    t = y.shape[1]
    if seqlen is not None:
        valid = valid & (
            jnp.arange(t)[None, :] < seqlen.reshape(-1, 1).astype(jnp.int32)
        )
    pad_col = jnp.zeros((y.shape[0], 1), y.dtype)
    pad_f = jnp.zeros((y.shape[0], 1), bool)
    p_valid = jnp.concatenate([pad_f, valid[:, :-1]], 1)
    p_typ = jnp.concatenate([pad_col, typ[:, :-1]], 1)
    p_tag = jnp.concatenate([pad_col, tag[:, :-1]], 1)
    n_valid = jnp.concatenate([valid[:, 1:], pad_f], 1)
    n_typ = jnp.concatenate([typ[:, 1:], pad_col], 1)
    n_tag = jnp.concatenate([tag[:, 1:], pad_col], 1)
    boundary_in = ~p_valid | (p_typ != typ)
    boundary_out = ~n_valid | (n_typ != typ)
    if scheme == "plain":
        start = valid
        end = valid
    elif scheme == "IOB":
        start = valid & ((tag == 0) | boundary_in)
        end = valid & (boundary_out | (n_tag == 0))
    elif scheme == "IOE":
        start = valid & (boundary_in | (p_tag == 1))
        end = valid & ((tag == 1) | boundary_out)
    else:  # IOBES
        start = valid & ((tag == 0) | (tag == 3) | boundary_in | (p_tag >= 2))
        end = valid & ((tag >= 2) | boundary_out | (n_tag == 0) | (n_tag == 3))
    return start, end, typ


def _chunk_endpos(end):
    """For each position, the index of the NEXT chunk end at-or-after it
    (reverse running minimum over end positions) — a chunk starting at i
    spans [i, endpos[i]]."""
    t = end.shape[1]
    cand = jnp.where(end, jnp.arange(t)[None, :], t)
    return jnp.flip(lax.cummin(jnp.flip(cand, 1), axis=1), 1)


@register("chunk_eval", no_grad=True)
def _chunk_eval(ctx, ins, attrs):
    """Chunk-level precision/recall/F1 (reference chunk_eval_op.cc — the
    conlleval metric for NER-style taggers). Sequence layout follows this
    repo's padded-dense convention (sequence_ops.py): Inference/Label are
    [b, t] (or [b, t, 1]) tag grids with an optional SeqLength [b] mask.
    A predicted chunk is correct when a label chunk with the same span AND
    type exists; counting is fully vectorized (start/end boundary flags +
    span-end matching) rather than the reference's per-sequence scan."""
    (inference,) = ins["Inference"]
    (label,) = ins["Label"]
    seqlen = (ins.get("SeqLength") or [None])[0]
    scheme = str(attrs.get("chunk_scheme", "IOB"))
    if scheme not in ("plain", "IOB", "IOE", "IOBES"):
        raise ValueError("chunk_eval: unknown chunk_scheme %r" % scheme)
    n_types = int(attrs["num_chunk_types"])
    excluded = tuple(attrs.get("excluded_chunk_types", ()) or ())
    inf = inference.reshape(inference.shape[0], -1).astype(jnp.int32)
    lab = label.reshape(label.shape[0], -1).astype(jnp.int32)
    i_start, i_end, i_typ = _chunk_flags(inf, n_types, scheme, excluded, seqlen)
    l_start, l_end, l_typ = _chunk_flags(lab, n_types, scheme, excluded, seqlen)
    n_inf = jnp.sum(i_start)
    n_lab = jnp.sum(l_start)
    n_cor = jnp.sum(
        i_start
        & l_start
        & (i_typ == l_typ)
        & (_chunk_endpos(i_end) == _chunk_endpos(l_end))
    )
    fi, fl, fc = (x.astype(jnp.float32) for x in (n_inf, n_lab, n_cor))
    precision = jnp.where(fi > 0, fc / jnp.maximum(fi, 1.0), 0.0)
    recall = jnp.where(fl > 0, fc / jnp.maximum(fl, 1.0), 0.0)
    f1 = jnp.where(
        precision + recall > 0,
        2.0 * precision * recall / jnp.maximum(precision + recall, 1e-38),
        0.0,
    )
    i64 = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    return {
        "Precision": [precision.reshape((1,))],
        "Recall": [recall.reshape((1,))],
        "F1-Score": [f1.reshape((1,))],
        "NumInferChunks": [n_inf.astype(i64).reshape((1,))],
        "NumLabelChunks": [n_lab.astype(i64).reshape((1,))],
        "NumCorrectChunks": [n_cor.astype(i64).reshape((1,))],
    }


@register("positive_negative_pair", no_grad=True)
def _positive_negative_pair(ctx, ins, attrs):
    """Pairwise ranking metric (reference positive_negative_pair_op.cc, the
    mq2007/LETOR evaluation): over every within-query item pair with
    differing labels, a pair is positive when the higher-labeled item also
    scores higher, negative when it scores lower, neutral on score ties.
    O(N^2) masked pairwise comparison — N is a batch, not a corpus."""
    (score,) = ins["Score"]
    (label,) = ins["Label"]
    (qid,) = ins["QueryID"]
    col = int(attrs.get("column", -1))
    s = score.reshape(score.shape[0], -1)[:, col].astype(jnp.float32)
    l = label.reshape(-1).astype(jnp.float32)
    q = qid.reshape(-1)
    n = s.shape[0]
    # each unordered pair once: strict upper triangle of the same-query mask
    pair = (
        (q[:, None] == q[None, :])
        & (jnp.arange(n)[:, None] < jnp.arange(n)[None, :])
        & (l[:, None] != l[None, :])
    ).astype(jnp.float32)
    if ins.get("Weight"):
        w = ins["Weight"][0].reshape(-1).astype(jnp.float32)
        pair = pair * 0.5 * (w[:, None] + w[None, :])
    # orient the score difference so positive means ranked like the labels
    d = (s[:, None] - s[None, :]) * jnp.sign(l[:, None] - l[None, :])
    pos = jnp.sum(pair * (d > 0))
    neg = jnp.sum(pair * (d < 0))
    neu = jnp.sum(pair * (d == 0))
    for slot, v in (
        ("AccumulatePositivePair", pos),
        ("AccumulateNegativePair", neg),
        ("AccumulateNeutralPair", neu),
    ):
        if ins.get(slot):
            v = v + ins[slot][0].reshape(())
        if slot == "AccumulatePositivePair":
            pos = v
        elif slot == "AccumulateNegativePair":
            neg = v
        else:
            neu = v
    return {
        "PositivePair": [pos.reshape((1,))],
        "NegativePair": [neg.reshape((1,))],
        "NeutralPair": [neu.reshape((1,))],
    }


# ---------------------------------------------------------------------------
# tensor manipulation (reference: reshape_op.cc, transpose_op.cc, concat_op.cc,
# split_op.cc, stack_op.cc, squeeze/unsqueeze, flatten, slice, gather, scatter,
# pad, expand, one_hot, lod_reset)
# ---------------------------------------------------------------------------


def _reshape_shape(x, shape_attr):
    shape = list(int(s) for s in shape_attr)
    # paddle semantics: 0 means copy input dim at that position
    for i, s in enumerate(shape):
        if s == 0:
            shape[i] = x.shape[i]
    return shape


@register("reshape")
def _reshape(ctx, ins, attrs):
    (x,) = ins["X"]
    return {"Out": [x.reshape(_reshape_shape(x, attrs["shape"]))]}


@register("reshape2")
def _reshape2(ctx, ins, attrs):
    (x,) = ins["X"]
    out = x.reshape(_reshape_shape(x, attrs["shape"]))
    xshape = jnp.zeros((0,) + x.shape, dtype=x.dtype)
    return {"Out": [out], "XShape": [xshape]}


@register("transpose")
def _transpose(ctx, ins, attrs):
    (x,) = ins["X"]
    return {"Out": [jnp.transpose(x, attrs["axis"])]}


@register("transpose2")
def _transpose2(ctx, ins, attrs):
    (x,) = ins["X"]
    out = jnp.transpose(x, attrs["axis"])
    return {"Out": [out], "XShape": [jnp.zeros((0,) + x.shape, dtype=x.dtype)]}


@register("concat")
def _concat(ctx, ins, attrs):
    xs = ins["X"]
    return {"Out": [jnp.concatenate(xs, axis=int(attrs.get("axis", 0)))]}


@register("split")
def _split(ctx, ins, attrs):
    (x,) = ins["X"]
    axis = int(attrs.get("axis", 0))
    sections = attrs.get("sections", [])
    num = int(attrs.get("num", 0))
    if sections:
        idx = np.cumsum(sections[:-1]).tolist()
        outs = jnp.split(x, idx, axis=axis)
    else:
        outs = jnp.split(x, num, axis=axis)
    return {"Out": list(outs)}


@register("stack")
def _stack(ctx, ins, attrs):
    xs = ins["X"]
    return {"Y": [jnp.stack(xs, axis=int(attrs.get("axis", 0)))]}


@register("unstack")
def _unstack(ctx, ins, attrs):
    (x,) = ins["X"]
    axis = int(attrs.get("axis", 0))
    n = x.shape[axis]
    outs = [jnp.squeeze(s, axis=axis) for s in jnp.split(x, n, axis=axis)]
    return {"Y": outs}


def _squeeze_axes(x, axes):
    if axes:
        return tuple(a % x.ndim for a in axes if x.shape[a % x.ndim] == 1)
    return tuple(i for i, d in enumerate(x.shape) if d == 1)


@register("squeeze")
def _squeeze(ctx, ins, attrs):
    (x,) = ins["X"]
    return {"Out": [jnp.squeeze(x, axis=_squeeze_axes(x, attrs.get("axes", [])))]}


@register("squeeze2")
def _squeeze2(ctx, ins, attrs):
    (x,) = ins["X"]
    out = jnp.squeeze(x, axis=_squeeze_axes(x, attrs.get("axes", [])))
    return {"Out": [out], "XShape": [jnp.zeros((0,) + x.shape, dtype=x.dtype)]}


@register("unsqueeze")
def _unsqueeze(ctx, ins, attrs):
    (x,) = ins["X"]
    out = x
    for a in sorted(attrs["axes"]):
        out = jnp.expand_dims(out, a)
    return {"Out": [out]}


@register("unsqueeze2")
def _unsqueeze2(ctx, ins, attrs):
    (x,) = ins["X"]
    out = x
    for a in sorted(attrs["axes"]):
        out = jnp.expand_dims(out, a)
    return {"Out": [out], "XShape": [jnp.zeros((0,) + x.shape, dtype=x.dtype)]}


@register("flatten")
def _flatten(ctx, ins, attrs):
    (x,) = ins["X"]
    axis = int(attrs.get("axis", 1))
    lead = int(np.prod(x.shape[:axis])) if axis > 0 else 1
    return {"Out": [x.reshape((lead, -1))]}


@register("flatten2")
def _flatten2(ctx, ins, attrs):
    out = _flatten(ctx, ins, attrs)["Out"]
    (x,) = ins["X"]
    return {"Out": out, "XShape": [jnp.zeros((0,) + x.shape, dtype=x.dtype)]}


@register("slice")
def _slice(ctx, ins, attrs):
    (x,) = ins["Input"]
    axes = attrs["axes"]
    starts = attrs["starts"]
    ends = attrs["ends"]
    idx = [slice(None)] * x.ndim
    for a, s, e in zip(axes, starts, ends):
        dim = x.shape[a]
        s = max(s + dim, 0) if s < 0 else min(s, dim)
        e = max(e + dim, 0) if e < 0 else min(e, dim)
        idx[a] = slice(s, e)
    return {"Out": [x[tuple(idx)]]}


@register("gather")
def _gather(ctx, ins, attrs):
    (x,) = ins["X"]
    (idx,) = ins["Index"]
    return {"Out": [jnp.take(x, idx.reshape(-1).astype(jnp.int32), axis=0)]}


@register("scatter")
def _scatter(ctx, ins, attrs):
    (x,) = ins["X"]
    (ids,) = ins["Ids"]
    (updates,) = ins["Updates"]
    ids = ids.reshape(-1).astype(jnp.int32)
    if attrs.get("overwrite", True):
        out = x.at[ids].set(updates)
    else:
        out = x.at[ids].add(updates)
    return {"Out": [out]}


@register("pad")
def _pad(ctx, ins, attrs):
    (x,) = ins["X"]
    p = attrs["paddings"]
    pairs = [(p[2 * i], p[2 * i + 1]) for i in range(x.ndim)]
    return {
        "Out": [jnp.pad(x, pairs, constant_values=attrs.get("pad_value", 0.0))]
    }


@register("pad2d")
def _pad2d(ctx, ins, attrs):
    (x,) = ins["X"]
    p = attrs["paddings"]  # [top, bottom, left, right]
    mode = attrs.get("mode", "constant")
    pairs = [(0, 0), (0, 0), (p[0], p[1]), (p[2], p[3])]
    if mode == "constant":
        out = jnp.pad(x, pairs, constant_values=attrs.get("pad_value", 0.0))
    elif mode == "reflect":
        out = jnp.pad(x, pairs, mode="reflect")
    else:
        out = jnp.pad(x, pairs, mode="edge")
    return {"Out": [out]}


@register("expand")
def _expand(ctx, ins, attrs):
    (x,) = ins["X"]
    times = attrs["expand_times"]
    return {"Out": [jnp.tile(x, times)]}


@register("one_hot", no_grad=True)
def _one_hot(ctx, ins, attrs):
    (x,) = ins["X"]
    depth = int(attrs["depth"])
    flat = x.reshape(x.shape[:-1]) if x.shape[-1] == 1 else x
    return {"Out": [jax.nn.one_hot(flat.astype(jnp.int32), depth, dtype=jnp.float32)]}


@register("hash", no_grad=True)
def _hash(ctx, ins, attrs):
    """Feature hashing of integer id rows (reference hash_op.cc, the
    "hash trick" front-end of sparse models: ids → num_hash hashed buckets
    in [0, mod_by), each feeding a lookup_table). The reference runs xxHash
    over each row's raw int64 bytes per seed; this is the same XXH32 round
    structure (the <16-byte tail path: per-4-byte-lane mix + avalanche,
    primes 2654435761/2246822519/3266489917/668265263/374761393) in wrapped
    uint32 jnp arithmetic — bit-exact XXH32 for the typical [N, 1] int64 id
    column, lane-chained for wider rows. Each logical id always hashes as 8
    bytes (hi lane 0 under i64→i32 canonicalization) so bucket assignment
    is independent of the executor's dtype policy."""
    (x,) = ins["X"]
    num_hash = int(attrs.get("num_hash", 1))
    mod_by = int(attrs.get("mod_by", 1))
    p1, p2, p3, p4, p5 = (
        jnp.uint32(2654435761),
        jnp.uint32(2246822519),
        jnp.uint32(3266489917),
        jnp.uint32(668265263),
        jnp.uint32(374761393),
    )

    def rotl(v, r):
        return (v << jnp.uint32(r)) | (v >> jnp.uint32(32 - r))

    ids = x.reshape(x.shape[0], -1)
    lanes = []
    for c in range(ids.shape[1]):
        col = ids[:, c]
        lo = col.astype(jnp.uint32)  # wraps mod 2^32 == the low 4 bytes
        hi = (
            (col >> 32).astype(jnp.uint32)
            if np.dtype(col.dtype).itemsize == 8
            else jnp.zeros(col.shape, jnp.uint32)
        )
        lanes += [lo, hi]
    nbytes = jnp.uint32(8 * ids.shape[1])
    outs = []
    for seed in range(num_hash):
        h = jnp.full(ids.shape[:1], jnp.uint32(seed), jnp.uint32) + p5 + nbytes
        for w in lanes:
            h = rotl(h + w * p3, 17) * p4
        h = (h ^ (h >> jnp.uint32(15))) * p2
        h = (h ^ (h >> jnp.uint32(13))) * p3
        h = h ^ (h >> jnp.uint32(16))
        outs.append((h % jnp.uint32(mod_by)).astype(x.dtype))
    return {"Out": [jnp.stack(outs, axis=1).reshape(x.shape[0], num_hash, 1)]}


@register("lookup_table")
def _lookup_table(ctx, ins, attrs):
    (w,) = ins["W"]
    (ids,) = ins["Ids"]
    padding_idx = int(attrs.get("padding_idx", -1))
    flat = ids.reshape(-1).astype(jnp.int32)
    out = jnp.take(w, flat, axis=0)
    # negative ids are padding/masked slots (AsyncExecutor's bucketed batches,
    # split_ids' shard masks): zero rows, zero grad — jnp.take alone would
    # clip them to row 0 and silently contribute it
    out = jnp.where((flat < 0)[:, None], 0.0, out)
    if padding_idx != -1:
        pad = padding_idx if padding_idx >= 0 else padding_idx + w.shape[0]
        out = jnp.where((flat == pad)[:, None], 0.0, out)
    out_shape = tuple(ids.shape[:-1]) + (w.shape[1],)
    if ids.shape[-1] != 1:
        out_shape = tuple(ids.shape) + (w.shape[1],)
    return {"Out": [out.reshape(out_shape)]}


@register("lookup_table_grad", no_grad=True)
def _lookup_table_grad(ctx, ins, attrs):
    """Explicit grad: scatter-add of the cotangent rows ACCUMULATED IN F32,
    cast once to the cotangent's dtype at the end. The f32 accumulator is
    what makes repeated ids safe under bf16 training: adding 1-ulp increments
    into a bf16 row plateaus once the row outgrows the increment's precision
    (the sum of ones stalls at 256 — the r05 advisor's swamping repro, covered
    by tests/test_ops_roundout.py), while one final rounding step loses at
    most 1 ulp. The result still lands in the cotangent's dtype, so the
    bf16-wire saving vs the generic vjp (which scatters in the f32 master
    table's dtype AND hands the f32 grad downstream — 2x 262 MB/step of HBM
    traffic on the MFU-bench transformer, r05 audit) is kept for every
    consumer; XLA fuses the trailing cast into the scatter's output write.
    W is consulted for its SHAPE only, so the transpiler's W@BF16 cast (if
    any) dead-codes away."""
    (w,) = ins["W"]
    (ids,) = ins["Ids"]
    (dout,) = ins["Out@GRAD"]
    padding_idx = int(attrs.get("padding_idx", -1))
    flat = ids.reshape(-1).astype(jnp.int32)
    d2 = dout.reshape(-1, w.shape[1])
    mask = flat >= 0
    if padding_idx != -1:
        pad = padding_idx if padding_idx >= 0 else padding_idx + w.shape[0]
        mask = mask & (flat != pad)
    dw = (
        jnp.zeros(w.shape, jnp.float32)
        .at[jnp.where(mask, flat, 0)]
        .add(jnp.where(mask[:, None], d2, 0).astype(jnp.float32))
        .astype(d2.dtype)
    )
    return {"W@GRAD": [dw]}


@register("embedding")
def _embedding(ctx, ins, attrs):
    return _lookup_table(ctx, ins, attrs)


@register("reverse")
def _reverse(ctx, ins, attrs):
    (x,) = ins["X"]
    axes = attrs["axis"]
    if isinstance(axes, int):
        axes = [axes]
    out = x
    for a in axes:
        out = jnp.flip(out, axis=a)
    return {"Out": [out]}


@register("label_smooth")
def _label_smooth(ctx, ins, attrs):
    (x,) = ins["X"]
    eps = attrs.get("epsilon", 0.1)
    k = x.shape[-1]
    if "PriorDist" in ins:
        prior = ins["PriorDist"][0].reshape(-1)
        out = (1 - eps) * x + eps * prior
    else:
        out = (1 - eps) * x + eps / k
    return {"Out": [out]}


@register("norm")
def _norm(ctx, ins, attrs):
    (x,) = ins["X"]
    axis = int(attrs.get("axis", 1))
    eps = attrs.get("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    return {"Out": [x / norm], "Norm": [norm]}


def _interp_shape(x, attrs):
    return int(attrs["out_h"]), int(attrs["out_w"])


@register("bilinear_interp")
def _bilinear_interp(ctx, ins, attrs):
    (x,) = ins["X"]
    oh, ow = _interp_shape(x, attrs)
    out = jax.image.resize(x, (x.shape[0], x.shape[1], oh, ow), method="bilinear")
    return {"Out": [out]}


@register("nearest_interp")
def _nearest_interp(ctx, ins, attrs):
    (x,) = ins["X"]
    oh, ow = _interp_shape(x, attrs)
    out = jax.image.resize(x, (x.shape[0], x.shape[1], oh, ow), method="nearest")
    return {"Out": [out]}


@register("lod_reset")
def _lod_reset(ctx, ins, attrs):
    (x,) = ins["X"]
    return {"Out": [x]}


@register("where", no_grad=False)
def _where(ctx, ins, attrs):
    (cond,) = ins["Condition"]
    (x,) = ins["X"]
    (y,) = ins["Y"]
    return {"Out": [jnp.where(cond, x, y)]}


# ---------------------------------------------------------------------------
# convolution / pooling / normalization (reference: conv_op.cc +
# conv_cudnn_op.cu.cc, pool_op.cc, batch_norm_op.cc, layer_norm_op.cc — these
# are the MXU workhorses; lowered to XLA conv_general_dilated / reduce_window)
# ---------------------------------------------------------------------------


@register("conv2d")
def _conv2d(ctx, ins, attrs):
    (x,) = ins["Input"]
    (w,) = ins["Filter"]
    strides = [int(s) for s in attrs.get("strides", [1, 1])]
    paddings = [int(p) for p in attrs.get("paddings", [0, 0])]
    dilations = [int(d) for d in attrs.get("dilations", [1, 1])]
    groups = int(attrs.get("groups", 1) or 1)
    out = lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=[(paddings[0], paddings[0]), (paddings[1], paddings[1])],
        rhs_dilation=dilations,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups,
    )
    return {"Output": [out]}


@register("depthwise_conv2d")
def _depthwise_conv2d(ctx, ins, attrs):
    return _conv2d(ctx, ins, attrs)


# conv2d_transpose is registered in nn_extra_ops.py beside the other
# _conv_nd(transpose=True) family members (conv3d_transpose,
# depthwise_conv2d_transpose)


@register("pool2d")
def _pool2d(ctx, ins, attrs):
    (x,) = ins["X"]
    ptype = attrs.get("pooling_type", "max")
    ksize = [int(k) for k in attrs.get("ksize", [2, 2])]
    strides = [int(s) for s in attrs.get("strides", ksize)]
    paddings = [int(p) for p in attrs.get("paddings", [0, 0])]
    if attrs.get("global_pooling", False) or attrs.get("adaptive", False) and list(
        attrs.get("ksize")
    ) == [1, 1]:
        ksize = [x.shape[2], x.shape[3]]
        strides = ksize
        paddings = [0, 0]
    window = (1, 1, ksize[0], ksize[1])
    strd = (1, 1, strides[0], strides[1])
    pads = ((0, 0), (0, 0), (paddings[0], paddings[0]), (paddings[1], paddings[1]))
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        out = lax.reduce_window(x, init, lax.max, window, strd, pads)
    else:
        s = lax.reduce_window(x, 0.0, lax.add, window, strd, pads)
        if attrs.get("exclusive", True) and (paddings[0] or paddings[1]):
            ones = jnp.ones_like(x)
            cnt = lax.reduce_window(ones, 0.0, lax.add, window, strd, pads)
            out = s / cnt
        else:
            out = s / (ksize[0] * ksize[1])
    return {"Out": [out]}


@register("batch_norm")
def _batch_norm(ctx, ins, attrs):
    (x,) = ins["X"]
    (scale,) = ins["Scale"]
    (bias,) = ins["Bias"]
    (mean,) = ins["Mean"]
    (var,) = ins["Variance"]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = bool(attrs.get("is_test", False)) or bool(
        attrs.get("use_global_stats", False)
    )
    layout = attrs.get("data_layout", "NCHW")
    axes = (
        tuple(i for i in range(x.ndim) if i != 1)
        if layout == "NCHW"
        else tuple(range(x.ndim - 1))
    )
    cshape = [1] * x.ndim
    c_axis = 1 if layout == "NCHW" else x.ndim - 1
    cshape[c_axis] = x.shape[c_axis]

    if is_test:
        use_mean, use_var = mean, var
        saved_mean, saved_var = mean, var
        mean_out, var_out = mean, var
    else:
        xf = x.astype(jnp.float32)
        bmean = jnp.mean(xf, axis=axes)
        bvar = jnp.mean(jnp.square(xf), axis=axes) - jnp.square(bmean)
        use_mean, use_var = bmean, bvar
        saved_mean = bmean
        saved_var = 1.0 / jnp.sqrt(bvar + eps)  # reference saves inv-std
        mean_out = mean * momentum + bmean * (1 - momentum)
        var_out = var * momentum + bvar * (1 - momentum)

    inv = lax.rsqrt(use_var.reshape(cshape) + eps)
    y = (x - use_mean.reshape(cshape)) * inv * scale.reshape(cshape) + bias.reshape(
        cshape
    )
    return {
        "Y": [y.astype(x.dtype)],
        "MeanOut": [mean_out],
        "VarianceOut": [var_out],
        "SavedMean": [saved_mean],
        "SavedVariance": [saved_var],
    }


@register("layer_norm")
def _layer_norm(ctx, ins, attrs):
    (x,) = ins["X"]
    eps = attrs.get("epsilon", 1e-5)
    bna = int(attrs.get("begin_norm_axis", 1))
    lead = int(np.prod(x.shape[:bna]))
    x2 = x.reshape((lead, -1)).astype(jnp.float32)
    mean = jnp.mean(x2, axis=1)
    var = jnp.var(x2, axis=1)
    y = (x2 - mean[:, None]) * lax.rsqrt(var[:, None] + eps)
    if "Scale" in ins:
        y = y * ins["Scale"][0].reshape(-1)[None, :]
    if "Bias" in ins:
        y = y + ins["Bias"][0].reshape(-1)[None, :]
    return {
        "Y": [y.reshape(x.shape).astype(x.dtype)],
        "Mean": [mean],
        "Variance": [var],
    }


@register("lrn")
def _lrn(ctx, ins, attrs):
    (x,) = ins["X"]
    n = int(attrs.get("n", 5))
    k = attrs.get("k", 1.0)
    alpha = attrs.get("alpha", 1e-4)
    beta = attrs.get("beta", 0.75)
    sq = jnp.square(x)
    half = n // 2
    pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = sum(pad[:, i : i + x.shape[1]] for i in range(n))
    mid = k + alpha * acc
    return {"Out": [x / jnp.power(mid, beta)], "MidOut": [mid]}


# ---------------------------------------------------------------------------
# dropout — custom grad: must reuse the forward-sampled mask, so the generic
# vjp-replay grad does not apply (reference dropout_op.cc keeps Mask for grad)
# ---------------------------------------------------------------------------


def _dropout_grad_maker(op, block, grad_map):
    return [
        {
            "type": "dropout_grad",
            "inputs": {
                "Out@GRAD": [grad_map[op.output("Out")[0]]],
                "Mask": [op.output("Mask")[0]],
            },
            "outputs": {"X@GRAD": [grad_map[op.input("X")[0]]]},
            "attrs": {k: v for k, v in op.attrs.items()},
        }
    ]


@register("dropout", stochastic=True, grad=_dropout_grad_maker)
def _dropout(ctx, ins, attrs):
    (x,) = ins["X"]
    p = attrs.get("dropout_prob", 0.5)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if attrs.get("is_test", False):
        mask = jnp.ones_like(x)
        out = x * (1.0 - p) if impl == "downgrade_in_infer" else x
        return {"Out": [out], "Mask": [mask]}
    keep = jax.random.bernoulli(_rng(ctx, attrs), 1.0 - p, x.shape)
    if impl == "upscale_in_train":
        mask = keep.astype(x.dtype) / (1.0 - p)
    else:
        mask = keep.astype(x.dtype)
    return {"Out": [x * mask], "Mask": [mask]}


@register("dropout_grad", no_grad=True)
def _dropout_grad(ctx, ins, attrs):
    (dout,) = ins["Out@GRAD"]
    (mask,) = ins["Mask"]
    return {"X@GRAD": [dout * mask]}


# ---------------------------------------------------------------------------
# optimizer ops (reference: operators/optimizers/*.cc — sgd, momentum, adam,
# adagrad, rmsprop, adadelta, adamax, decayed_adagrad, ftrl, lars_momentum).
# Each consumes Param (+state) and emits ParamOut (+state outs) under the SAME
# variable names; the executor's env-update model gives in-place semantics and
# the jit donates param buffers.
# ---------------------------------------------------------------------------


def _p(ins, slot):
    return ins[slot][0]


# optimizer-state input slots per op type — the moment/accumulator tensors the
# ZeRO-1 tier (ReduceStrategy.Reduce) stores sharded 1/dp per rank. Scalar
# state (Beta*Pow, LearningRate) is NOT listed: shape [1] cannot shard and its
# update must stay replicated for numerics identical to the all-reduce path.
# Consumed by executor._CompiledBlock to build the sharded in/out_shardings.
ZERO1_STATE_SLOTS = {
    "momentum": ("Velocity",),
    "lars_momentum": ("Velocity",),
    "adam": ("Moment1", "Moment2"),
    "adagrad": ("Moment",),
    "decayed_adagrad": ("Moment",),
    "rmsprop": ("MeanSquare", "Moment", "MeanGrad"),
    "adadelta": ("AvgSquaredGrad", "AvgSquaredUpdate"),
    "adamax": ("Moment", "InfNorm"),
    "ftrl": ("SquaredAccumulator", "LinearAccumulator"),
}


def _opt_f32(fn):
    """Optimizer-lowering dtype fidelity: compute the update in f32 (bf16
    grads upcast; master states already f32 under the train-mode
    Bf16Transpiler), then cast every `<Slot>Out` back to its `<Slot>` input's
    dtype. Without the output casts, f32 promotion (the f32 LearningRate)
    silently retypes the written-back state, which both changes training
    numerics and — because the state dtype is part of the compile-cache
    key — forces a full recompile on the next step."""

    @functools.wraps(fn)
    def wrapped(ctx, ins, attrs):
        from ..parallel import sharding_rules as _sr

        # storage-layout constraints (parallel/sharding_rules): rule-sharded
        # params (FSDP/TP) pin param+grad+moments to the declared spec; else
        # the ZeRO-1 tier reduce-scatters the grad and slices param+moments
        # to this rank's 1/dp shard. Either way BEFORE the f32 upcast (the
        # wire carries the grad's native dtype; the upcast then touches only
        # the local shard).
        raw_ins = ins
        ins = _sr.opt_constrain_ins(ctx, ins)
        orig_dt = {}
        ins32 = {}
        for slot, vals in ins.items():
            up = []
            for a in vals:
                if a is not None and jnp.issubdtype(
                    jnp.asarray(a).dtype, jnp.floating
                ):
                    orig_dt.setdefault(slot, jnp.asarray(a).dtype)
                    up.append(jnp.asarray(a).astype(jnp.float32))
                else:
                    up.append(a)
            ins32[slot] = up
        res = fn(ctx, ins32, attrs)
        out = {}
        for slot, vals in res.items():
            base = slot[:-3] if slot.endswith("Out") else slot
            dt = orig_dt.get(base, orig_dt.get("Param"))
            down = []
            for v in vals:
                if (
                    dt is not None
                    and hasattr(v, "dtype")
                    and jnp.issubdtype(v.dtype, jnp.floating)
                    and v.dtype != dt
                ):
                    down.append(v.astype(dt))
                else:
                    down.append(v)
            out[slot] = down
        # rule-sharded: outputs stay in the storage spec (params live
        # sharded, all-gather-on-use). ZeRO-1: ParamOut all-gathers back to
        # every rank; moments stay sharded (stored 1/dp via the executor's
        # state shardings).
        return _sr.opt_constrain_outs(ctx, out, raw_ins)

    return wrapped


@register("sgd", no_grad=True)
@_opt_f32
def _sgd(ctx, ins, attrs):
    p, g, lr = _p(ins, "Param"), _p(ins, "Grad"), _p(ins, "LearningRate")
    return {"ParamOut": [p - lr.reshape(()).astype(p.dtype) * g]}


@register("momentum", no_grad=True)
@_opt_f32
def _momentum(ctx, ins, attrs):
    p, g, v, lr = (
        _p(ins, "Param"),
        _p(ins, "Grad"),
        _p(ins, "Velocity"),
        _p(ins, "LearningRate"),
    )
    mu = attrs["mu"]
    lr = lr.reshape(()).astype(p.dtype)
    v_out = mu * v + g
    if attrs.get("use_nesterov", False):
        p_out = p - (g + mu * v_out) * lr
    else:
        p_out = p - lr * v_out
    return {"ParamOut": [p_out], "VelocityOut": [v_out]}


@register("lars_momentum", no_grad=True)
@_opt_f32
def _lars_momentum(ctx, ins, attrs):
    p, g, v, lr = (
        _p(ins, "Param"),
        _p(ins, "Grad"),
        _p(ins, "Velocity"),
        _p(ins, "LearningRate"),
    )
    mu = attrs["mu"]
    lars_coeff = attrs.get("lars_coeff", 0.001)
    lars_wd = attrs.get("lars_weight_decay", 0.0005)
    lr = lr.reshape(()).astype(jnp.float32)
    pn = jnp.sqrt(jnp.sum(jnp.square(p.astype(jnp.float32))))
    gn = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
    local_lr = jnp.where(
        (pn > 0) & (gn > 0), lr * lars_coeff * pn / (gn + lars_wd * pn), lr
    )
    v_out = mu * v + local_lr * (g + lars_wd * p)
    return {"ParamOut": [p - v_out], "VelocityOut": [v_out]}


@register("adam", no_grad=True)
@_opt_f32
def _adam(ctx, ins, attrs):
    p, g, lr = _p(ins, "Param"), _p(ins, "Grad"), _p(ins, "LearningRate")
    m1, m2 = _p(ins, "Moment1"), _p(ins, "Moment2")
    b1p, b2p = _p(ins, "Beta1Pow"), _p(ins, "Beta2Pow")
    b1, b2, eps = attrs.get("beta1", 0.9), attrs.get("beta2", 0.999), attrs.get(
        "epsilon", 1e-8
    )
    lr = lr.reshape(()).astype(jnp.float32)
    m1o = b1 * m1 + (1 - b1) * g
    m2o = b2 * m2 + (1 - b2) * jnp.square(g)
    lr_t = lr * jnp.sqrt(1 - b2p.reshape(())) / (1 - b1p.reshape(()))
    p_out = p - lr_t * m1o / (jnp.sqrt(m2o) + eps)
    return {"ParamOut": [p_out], "Moment1Out": [m1o], "Moment2Out": [m2o]}


@register("adagrad", no_grad=True)
@_opt_f32
def _adagrad(ctx, ins, attrs):
    p, g, lr, mom = (
        _p(ins, "Param"),
        _p(ins, "Grad"),
        _p(ins, "LearningRate"),
        _p(ins, "Moment"),
    )
    eps = attrs.get("epsilon", 1e-6)
    mom_out = mom + jnp.square(g)
    p_out = p - lr.reshape(()) * g / (jnp.sqrt(mom_out) + eps)
    return {"ParamOut": [p_out], "MomentOut": [mom_out]}


@register("decayed_adagrad", no_grad=True)
@_opt_f32
def _decayed_adagrad(ctx, ins, attrs):
    p, g, lr, mom = (
        _p(ins, "Param"),
        _p(ins, "Grad"),
        _p(ins, "LearningRate"),
        _p(ins, "Moment"),
    )
    decay = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    mom_out = decay * mom + (1 - decay) * jnp.square(g)
    p_out = p - lr.reshape(()) * g / (jnp.sqrt(mom_out) + eps)
    return {"ParamOut": [p_out], "MomentOut": [mom_out]}


@register("rmsprop", no_grad=True)
@_opt_f32
def _rmsprop(ctx, ins, attrs):
    p, g, lr = _p(ins, "Param"), _p(ins, "Grad"), _p(ins, "LearningRate")
    ms, mom = _p(ins, "MeanSquare"), _p(ins, "Moment")
    eps, decay, momentum = (
        attrs.get("epsilon", 1e-10),
        attrs.get("decay", 0.9),
        attrs.get("momentum", 0.0),
    )
    lr = lr.reshape(())
    if attrs.get("centered", False):
        mg = _p(ins, "MeanGrad")
        ms_out = decay * ms + (1 - decay) * jnp.square(g)
        mg_out = decay * mg + (1 - decay) * g
        mom_out = momentum * mom + lr * g / jnp.sqrt(
            ms_out - jnp.square(mg_out) + eps
        )
        return {
            "ParamOut": [p - mom_out],
            "MeanSquareOut": [ms_out],
            "MomentOut": [mom_out],
            "MeanGradOut": [mg_out],
        }
    ms_out = decay * ms + (1 - decay) * jnp.square(g)
    mom_out = momentum * mom + lr * g / jnp.sqrt(ms_out + eps)
    return {"ParamOut": [p - mom_out], "MeanSquareOut": [ms_out], "MomentOut": [mom_out]}


@register("adadelta", no_grad=True)
@_opt_f32
def _adadelta(ctx, ins, attrs):
    p, g = _p(ins, "Param"), _p(ins, "Grad")
    avg_sq_g, avg_sq_u = _p(ins, "AvgSquaredGrad"), _p(ins, "AvgSquaredUpdate")
    rho, eps = attrs.get("rho", 0.95), attrs.get("epsilon", 1e-6)
    asg = rho * avg_sq_g + (1 - rho) * jnp.square(g)
    update = -jnp.sqrt((avg_sq_u + eps) / (asg + eps)) * g
    asu = rho * avg_sq_u + (1 - rho) * jnp.square(update)
    return {
        "ParamOut": [p + update],
        "AvgSquaredGradOut": [asg],
        "AvgSquaredUpdateOut": [asu],
    }


@register("adamax", no_grad=True)
@_opt_f32
def _adamax(ctx, ins, attrs):
    p, g, lr = _p(ins, "Param"), _p(ins, "Grad"), _p(ins, "LearningRate")
    mom, inf_norm, b1p = _p(ins, "Moment"), _p(ins, "InfNorm"), _p(ins, "Beta1Pow")
    b1, b2, eps = attrs.get("beta1", 0.9), attrs.get("beta2", 0.999), attrs.get(
        "epsilon", 1e-8
    )
    mom_out = b1 * mom + (1 - b1) * g
    inf_out = jnp.maximum(b2 * inf_norm, jnp.abs(g))
    lr_t = lr.reshape(()) / (1 - b1p.reshape(()))
    p_out = p - lr_t * mom_out / (inf_out + eps)
    return {"ParamOut": [p_out], "MomentOut": [mom_out], "InfNormOut": [inf_out]}


@register("ftrl", no_grad=True)
@_opt_f32
def _ftrl(ctx, ins, attrs):
    p, g, lr = _p(ins, "Param"), _p(ins, "Grad"), _p(ins, "LearningRate")
    sq_acc, lin_acc = _p(ins, "SquaredAccumulator"), _p(ins, "LinearAccumulator")
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    lr_power = attrs.get("lr_power", -0.5)
    lr = lr.reshape(())
    new_acc = sq_acc + jnp.square(g)
    if lr_power == -0.5:
        sigma = (jnp.sqrt(new_acc) - jnp.sqrt(sq_acc)) / lr
    else:
        sigma = (jnp.power(new_acc, -lr_power) - jnp.power(sq_acc, -lr_power)) / lr
    lin_out = lin_acc + g - sigma * p
    if lr_power == -0.5:
        x_den = l2 + jnp.sqrt(new_acc) / lr
    else:
        x_den = l2 + jnp.power(new_acc, -lr_power) / lr
    pre = jnp.clip(lin_out, -l1, l1) - lin_out
    p_out = pre / x_den
    return {
        "ParamOut": [p_out],
        "SquaredAccumOut": [new_acc],
        "LinearAccumOut": [lin_out],
    }
