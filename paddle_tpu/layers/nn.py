"""Neural-network layers (reference python/paddle/fluid/layers/nn.py — 144
public layers; this module covers the dense/conv/norm/embedding core, with
sequence and detection families in their own modules)."""

import numpy as np

from .. import framework
from ..framework import Variable
from ..initializer import Constant, Normal, Xavier
from ..layer_helper import LayerHelper

__all__ = [
    "flash_attention",
    "fc",
    "embedding",
    "hash",
    "chunk_eval",
    "dropout",
    "conv2d",
    "conv2d_transpose",
    "pool2d",
    "batch_norm",
    "layer_norm",
    "softmax",
    "softmax_with_cross_entropy",
    "cross_entropy",
    "square_error_cost",
    "smooth_l1",
    "log_loss",
    "sigmoid_cross_entropy_with_logits",
    "matmul",
    "mul",
    "topk",
    "reshape",
    "transpose",
    "split",
    "reduce_sum",
    "reduce_mean",
    "reduce_max",
    "reduce_min",
    "reduce_prod",
    "mean",
    "elementwise_add",
    "elementwise_sub",
    "elementwise_mul",
    "elementwise_div",
    "elementwise_max",
    "elementwise_min",
    "elementwise_pow",
    "one_hot",
    "lrn",
    "pad",
    "pad2d",
    "label_smooth",
    "flatten",
    "squeeze",
    "unsqueeze",
    "stack",
    "unstack",
    "expand",
    "gather",
    "scatter",
    "slice",
    "shape",
    "clip",
    "clip_by_norm",
    "prelu",
    "leaky_relu",
    "relu",
    "log",
    "l2_normalize",
    "image_resize",
    "resize_bilinear",
    "resize_nearest",
    "autoincreased_step_counter",
    "ring_attention",
    "distributed_embedding",
    "beam_search",
    "beam_search_decode",
    "kv_cache_write",
    "paged_attention",
    "rms_norm",
    "rotary_embedding",
    "hyper_connection",
    "hyper_connection_post",
    "mla_attention",
    "dsa_index",
    "dsa_topk",
    "mla_sparse_attention",
    "dsa_count",
    "swiglu",
    "short_conv",
    "ssm_scan",
    "moe_router",
    "moe_experts",
]


def fc(
    input,
    size,
    num_flatten_dims=1,
    param_attr=None,
    bias_attr=None,
    act=None,
    is_test=False,
    name=None,
):
    """Fully-connected layer (reference layers/nn.py fc: one mul op per input
    + sum + bias + activation, composed from `mul`)."""
    helper = LayerHelper("fc", **locals())
    dtype = helper.input_dtype()
    all_inputs = helper.multiple_input()
    if num_flatten_dims == 1 and len(all_inputs) > 1:
        # mixed ragged/dense inputs would produce rank-mismatched mul results
        out_ranks = {
            (len(v.shape) if getattr(v, "_len_name", None) else 2)
            for v in all_inputs
        }
        if len(out_ranks) > 1:
            raise ValueError(
                "fc with mixed ragged and non-ragged inputs is ambiguous; "
                "pass an explicit num_flatten_dims"
            )
    mul_results = []
    for input_var, param_attr in helper.iter_inputs_and_params():
        input_shape = input_var.shape
        nfd = num_flatten_dims
        # ragged input: reference LoD tensors are (T_total, d) so fc's default
        # num_flatten_dims=1 means "per timestep"; our padded (b, t, d) needs
        # the feature dim alone flattened for the same semantics
        if getattr(input_var, "_len_name", None) and num_flatten_dims == 1:
            nfd = len(input_shape) - 1
        param_shape = [
            int(np.prod(input_shape[nfd:])),
            size,
        ]
        w = helper.create_parameter(
            attr=param_attr, shape=param_shape, dtype=dtype, is_bias=False
        )
        tmp = helper.create_variable_for_type_inference(dtype)
        helper.append_op(
            type="mul",
            inputs={"X": [input_var.name], "Y": [w.name]},
            outputs={"Out": [tmp.name]},
            attrs={"x_num_col_dims": nfd, "y_num_col_dims": 1},
        )
        if getattr(input_var, "_len_name", None):
            tmp._len_name = input_var._len_name
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(dtype)
        helper.append_op(
            type="sum",
            inputs={"X": [v.name for v in mul_results]},
            outputs={"Out": [pre_bias.name]},
        )
    pre_act = helper.append_bias_op(pre_bias, dim_start=nfd)
    out = helper.append_activation(pre_act)
    from .sequence import _propagate

    return _propagate(out, mul_results[0])


def embedding(
    input,
    size,
    is_sparse=False,
    is_distributed=False,
    padding_idx=None,
    param_attr=None,
    dtype="float32",
):
    """Embedding lookup (reference layers/nn.py embedding → lookup_table op).
    `is_sparse=True` routes the gradient through the SelectedRows analog
    (paddle_tpu/embedding/): a (rows, values) pair whose size scales with
    ids-per-batch, consumed by per-row sgd/adagrad/adam updates — use it for
    big tables touched sparsely. Dense gradients (the default) stay a single
    fused scatter-add. `is_distributed=True` row-shards the table over the
    mesh 'ep' axis via the EmbeddingEngine."""
    if is_distributed:
        return distributed_embedding(
            input,
            size,
            param_attr=param_attr,
            dtype=dtype,
            is_sparse=is_sparse,
            padding_idx=padding_idx,
        )
    helper = LayerHelper("embedding", **locals())
    w = helper.create_parameter(
        attr=helper.param_attr, shape=size, dtype=dtype, is_bias=False
    )
    tmp = helper.create_variable_for_type_inference(dtype)
    padding_idx = (
        -1
        if padding_idx is None
        else padding_idx
        if padding_idx >= 0
        else (size[0] + padding_idx)
    )
    helper.append_op(
        type="lookup_table",
        inputs={"W": [w.name], "Ids": [input.name]},
        outputs={"Out": [tmp.name]},
        attrs={
            "is_sparse": is_sparse,
            "is_distributed": is_distributed,
            "padding_idx": padding_idx,
        },
    )
    if getattr(input, "_len_name", None):
        tmp._len_name = input._len_name
    return tmp


def hash(input, hash_size, num_hash=1, name=None):
    """Feature-hash integer ids into [0, hash_size) buckets (reference
    layers/nn.py hash → hash op): Out is [N, num_hash, 1], one bucket id per
    hash seed, ready to feed `embedding`/lookup_table. See ops/core_ops.py
    _hash for the XXH32 scheme."""
    helper = LayerHelper("hash", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="hash",
        inputs={"X": [input.name]},
        outputs={"Out": [out.name]},
        attrs={"num_hash": num_hash, "mod_by": hash_size},
    )
    out.stop_gradient = True
    return out


def chunk_eval(
    input,
    label,
    chunk_scheme,
    num_chunk_types,
    excluded_chunk_types=None,
    seq_length=None,
):
    """Chunk-level precision/recall/F1 for sequence tagging (reference
    layers/nn.py chunk_eval → chunk_eval op, the conlleval metric).

    input/label are padded-dense [b, t] tag grids (this repo's sequence
    convention), with `seq_length` [b] masking padding. Returns the 6-tuple
    (precision, recall, f1, num_infer_chunks, num_label_chunks,
    num_correct_chunks); fetch the three counts per batch and feed them to
    fluid.metrics.ChunkEvaluator.update for streaming aggregation — the
    counting itself runs in-framework, inside the compiled program."""
    helper = LayerHelper("chunk_eval")
    precision = helper.create_variable_for_type_inference(dtype="float32")
    recall = helper.create_variable_for_type_inference(dtype="float32")
    f1_score = helper.create_variable_for_type_inference(dtype="float32")
    num_infer = helper.create_variable_for_type_inference(dtype="int64")
    num_label = helper.create_variable_for_type_inference(dtype="int64")
    num_correct = helper.create_variable_for_type_inference(dtype="int64")
    inputs = {"Inference": [input.name], "Label": [label.name]}
    if seq_length is not None:
        inputs["SeqLength"] = [seq_length.name]
    helper.append_op(
        type="chunk_eval",
        inputs=inputs,
        outputs={
            "Precision": [precision.name],
            "Recall": [recall.name],
            "F1-Score": [f1_score.name],
            "NumInferChunks": [num_infer.name],
            "NumLabelChunks": [num_label.name],
            "NumCorrectChunks": [num_correct.name],
        },
        attrs={
            "chunk_scheme": chunk_scheme,
            "num_chunk_types": num_chunk_types,
            "excluded_chunk_types": list(excluded_chunk_types or []),
        },
    )
    for v in (precision, recall, f1_score, num_infer, num_label, num_correct):
        v.stop_gradient = True
    return precision, recall, f1_score, num_infer, num_label, num_correct


def dropout(
    x,
    dropout_prob,
    is_test=False,
    seed=None,
    name=None,
    dropout_implementation="downgrade_in_infer",
):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    mask = helper.create_variable_for_type_inference(dtype=x.dtype, stop_gradient=True)
    helper.append_op(
        type="dropout",
        inputs={"X": [x.name]},
        outputs={"Out": [out.name], "Mask": [mask.name]},
        attrs={
            "dropout_prob": dropout_prob,
            "is_test": is_test,
            "seed": seed if seed is not None else 0,
            "dropout_implementation": dropout_implementation,
        },
    )
    return out


def _pair(v):
    return list(v) if isinstance(v, (list, tuple)) else [v, v]


def conv2d(
    input,
    num_filters,
    filter_size,
    stride=1,
    padding=0,
    dilation=1,
    groups=None,
    param_attr=None,
    bias_attr=None,
    use_cudnn=True,
    act=None,
    name=None,
):
    """2-D convolution, NCHW / OIHW (reference layers/nn.py conv2d → conv2d op
    → cuDNN; here XLA conv_general_dilated targeting the MXU)."""
    helper = LayerHelper("conv2d", **locals())
    dtype = helper.input_dtype()
    num_channels = input.shape[1]
    groups = groups or 1
    filter_size = _pair(filter_size)
    stride = _pair(stride)
    padding = _pair(padding)
    dilation = _pair(dilation)
    filter_shape = [num_filters, num_channels // groups] + filter_size

    def _std(shape):
        fan_in = (num_channels // groups) * shape[2] * shape[3]
        return (2.0 / fan_in) ** 0.5

    w = helper.create_parameter(
        attr=helper.param_attr,
        shape=filter_shape,
        dtype=dtype,
        default_initializer=Normal(0.0, _std(filter_shape)),
    )
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="conv2d",
        inputs={"Input": [input.name], "Filter": [w.name]},
        outputs={"Output": [pre_bias.name]},
        attrs={
            "strides": stride,
            "paddings": padding,
            "dilations": dilation,
            "groups": groups,
            "use_cudnn": use_cudnn,
        },
    )
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def conv2d_transpose(
    input,
    num_filters,
    output_size=None,
    filter_size=None,
    padding=0,
    stride=1,
    dilation=1,
    groups=None,
    param_attr=None,
    bias_attr=None,
    use_cudnn=True,
    act=None,
    name=None,
):
    helper = LayerHelper("conv2d_transpose", **locals())
    dtype = helper.input_dtype()
    num_channels = input.shape[1]
    groups = groups or 1
    stride = _pair(stride)
    padding = _pair(padding)
    dilation = _pair(dilation)
    if filter_size is None:
        if output_size is None:
            raise ValueError("filter_size or output_size required")
        output_size = _pair(output_size)
        h_in, w_in = input.shape[2], input.shape[3]
        filter_size = [
            (output_size[0] - (h_in - 1) * stride[0] + 2 * padding[0] - 1) // dilation[0]
            + 1,
            (output_size[1] - (w_in - 1) * stride[1] + 2 * padding[1] - 1) // dilation[1]
            + 1,
        ]
    else:
        filter_size = _pair(filter_size)
    filter_shape = [num_channels, num_filters // groups] + filter_size
    w = helper.create_parameter(attr=helper.param_attr, shape=filter_shape, dtype=dtype)
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="conv2d_transpose",
        inputs={"Input": [input.name], "Filter": [w.name]},
        outputs={"Output": [pre_bias.name]},
        attrs={
            "strides": stride,
            "paddings": padding,
            "dilations": dilation,
            "groups": groups,
        },
    )
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def pool2d(
    input,
    pool_size=-1,
    pool_type="max",
    pool_stride=1,
    pool_padding=0,
    global_pooling=False,
    use_cudnn=True,
    ceil_mode=False,
    name=None,
    exclusive=True,
):
    helper = LayerHelper("pool2d", **locals())
    out = helper.create_variable_for_type_inference(helper.input_dtype())
    helper.append_op(
        type="pool2d",
        inputs={"X": [input.name]},
        outputs={"Out": [out.name]},
        attrs={
            "pooling_type": pool_type,
            "ksize": _pair(pool_size),
            "global_pooling": global_pooling,
            "strides": _pair(pool_stride),
            "paddings": _pair(pool_padding),
            "ceil_mode": ceil_mode,
            "exclusive": exclusive,
        },
    )
    return out


def batch_norm(
    input,
    act=None,
    is_test=False,
    momentum=0.9,
    epsilon=1e-5,
    param_attr=None,
    bias_attr=None,
    data_layout="NCHW",
    in_place=False,
    name=None,
    moving_mean_name=None,
    moving_variance_name=None,
    do_model_average_for_mean_and_var=False,
    use_global_stats=False,
):
    """Batch normalization (reference layers/nn.py batch_norm → batch_norm op).
    Running mean/variance are persistable non-trainable params updated by the
    op itself (MeanOut/VarianceOut alias the same variables)."""
    helper = LayerHelper("batch_norm", **locals())
    dtype = helper.input_dtype()
    channels = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    param_shape = [channels]

    scale = helper.create_parameter(
        attr=helper.param_attr,
        shape=param_shape,
        dtype=dtype,
        default_initializer=Constant(1.0),
    )
    bias = helper.create_parameter(
        attr=helper.bias_attr, shape=param_shape, dtype=dtype, is_bias=True
    )
    from ..param_attr import ParamAttr

    mean = helper.create_parameter(
        attr=ParamAttr(
            name=moving_mean_name, initializer=Constant(0.0), trainable=False
        ),
        shape=param_shape,
        dtype=dtype,
    )
    variance = helper.create_parameter(
        attr=ParamAttr(
            name=moving_variance_name, initializer=Constant(1.0), trainable=False
        ),
        shape=param_shape,
        dtype=dtype,
    )
    mean.stop_gradient = True
    variance.stop_gradient = True

    saved_mean = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    saved_variance = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    out = input if in_place else helper.create_variable_for_type_inference(dtype)

    helper.append_op(
        type="batch_norm",
        inputs={
            "X": [input.name],
            "Scale": [scale.name],
            "Bias": [bias.name],
            "Mean": [mean.name],
            "Variance": [variance.name],
        },
        outputs={
            "Y": [out.name],
            "MeanOut": [mean.name],
            "VarianceOut": [variance.name],
            "SavedMean": [saved_mean.name],
            "SavedVariance": [saved_variance.name],
        },
        attrs={
            "momentum": momentum,
            "epsilon": epsilon,
            "is_test": is_test,
            "data_layout": data_layout,
            "use_global_stats": use_global_stats,
        },
    )
    return helper.append_activation(out)


def layer_norm(
    input,
    scale=True,
    shift=True,
    begin_norm_axis=1,
    epsilon=1e-5,
    param_attr=None,
    bias_attr=None,
    act=None,
    name=None,
):
    helper = LayerHelper("layer_norm", **locals())
    dtype = helper.input_dtype()
    param_shape = [int(np.prod(input.shape[begin_norm_axis:]))]
    inputs = {"X": [input.name]}
    if scale:
        s = helper.create_parameter(
            attr=helper.param_attr,
            shape=param_shape,
            dtype=dtype,
            default_initializer=Constant(1.0),
        )
        inputs["Scale"] = [s.name]
    if shift:
        b = helper.create_parameter(
            attr=helper.bias_attr, shape=param_shape, dtype=dtype, is_bias=True
        )
        inputs["Bias"] = [b.name]
    mean_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    var_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="layer_norm",
        inputs=inputs,
        outputs={"Y": [out.name], "Mean": [mean_out.name], "Variance": [var_out.name]},
        attrs={"epsilon": epsilon, "begin_norm_axis": begin_norm_axis},
    )
    return helper.append_activation(out)


def softmax(input, use_cudnn=True, name=None):
    helper = LayerHelper("softmax", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="softmax", inputs={"X": [input.name]}, outputs={"Out": [out.name]}
    )
    return out


def softmax_with_cross_entropy(
    logits,
    label,
    soft_label=False,
    ignore_index=-100,
    numeric_stable_mode=True,
    return_softmax=False,
    smooth_eps=0.0,
):
    """smooth_eps (TPU-native extension, hard labels only): uniform label
    smoothing fused into the CE — mathematically identical to
    label_smooth(one_hot(label, V), ε) + soft_label CE, but never
    materializes the [N, V] one-hot (which dominates loss-path HBM traffic
    and memory at LM vocab sizes)."""
    if smooth_eps and soft_label:
        raise ValueError("smooth_eps applies to hard labels only")
    helper = LayerHelper("softmax_with_cross_entropy")
    softmax_out = helper.create_variable_for_type_inference(logits.dtype)
    loss = helper.create_variable_for_type_inference(logits.dtype)
    helper.append_op(
        type="softmax_with_cross_entropy",
        inputs={"Logits": [logits.name], "Label": [label.name]},
        outputs={"Softmax": [softmax_out.name], "Loss": [loss.name]},
        attrs={
            "soft_label": soft_label,
            "ignore_index": ignore_index,
            "numeric_stable_mode": numeric_stable_mode,
            "smooth_eps": float(smooth_eps),
        },
    )
    if return_softmax:
        return loss, softmax_out
    return loss


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="cross_entropy",
        inputs={"X": [input.name], "Label": [label.name]},
        outputs={"Y": [out.name]},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index},
    )
    return out


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="square_error_cost",
        inputs={"X": [input.name], "Y": [label.name]},
        outputs={"Out": [out.name]},
    )
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1_loss")
    diff = helper.create_variable_for_type_inference(x.dtype)
    loss = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x.name], "Y": [y.name]}
    if inside_weight is not None:
        inputs["InsideWeight"] = [inside_weight.name]
    if outside_weight is not None:
        inputs["OutsideWeight"] = [outside_weight.name]
    helper.append_op(
        type="smooth_l1_loss",
        inputs=inputs,
        outputs={"Diff": [diff.name], "Out": [loss.name]},
        attrs={"sigma": sigma if sigma is not None else 1.0},
    )
    return loss


def log_loss(input, label, epsilon=1e-4, name=None):
    helper = LayerHelper("log_loss", name=name)
    loss = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="log_loss",
        inputs={"Predicted": [input.name], "Labels": [label.name]},
        outputs={"Loss": [loss.name]},
        attrs={"epsilon": epsilon},
    )
    return loss


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100, name=None):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="sigmoid_cross_entropy_with_logits",
        inputs={"X": [x.name], "Label": [label.name]},
        outputs={"Out": [out.name]},
        attrs={"ignore_index": ignore_index},
    )
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="matmul",
        inputs={"X": [x.name], "Y": [y.name]},
        outputs={"Out": [out.name]},
        attrs={
            "transpose_X": transpose_x,
            "transpose_Y": transpose_y,
            "alpha": float(alpha),
        },
    )
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="mul",
        inputs={"X": [x.name], "Y": [y.name]},
        outputs={"Out": [out.name]},
        attrs={"x_num_col_dims": x_num_col_dims, "y_num_col_dims": y_num_col_dims},
    )
    return out


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    values = helper.create_variable_for_type_inference(input.dtype)
    indices = helper.create_variable_for_type_inference("int32")
    helper.append_op(
        type="top_k",
        inputs={"X": [input.name]},
        outputs={"Out": [values.name], "Indices": [indices.name]},
        attrs={"k": int(k)},
    )
    values.stop_gradient = True
    indices.stop_gradient = True
    return values, indices


def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper("reshape2", name=name, act=act)
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op(
        type="reshape2",
        inputs={"X": [x.name]},
        outputs={"Out": [out.name], "XShape": [xshape.name]},
        attrs={"shape": [int(s) for s in shape]},
    )
    return helper.append_activation(out)


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose2", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op(
        type="transpose2",
        inputs={"X": [x.name]},
        outputs={"Out": [out.name], "XShape": [xshape.name]},
        attrs={"axis": list(perm)},
    )
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", name=name)
    dim = dim if dim >= 0 else dim + len(input.shape)
    if isinstance(num_or_sections, int):
        num = num_or_sections
        sections = []
    else:
        num = 0
        sections = [int(s) for s in num_or_sections]
    outs = [
        helper.create_variable_for_type_inference(input.dtype)
        for _ in range(num or len(sections))
    ]
    helper.append_op(
        type="split",
        inputs={"X": [input.name]},
        outputs={"Out": [o.name for o in outs]},
        attrs={"num": num, "sections": sections, "axis": dim},
    )
    return outs


def _reduce(op_type, input, dim, keep_dim, name):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    if dim is not None and not isinstance(dim, (list, tuple)):
        dim = [dim]
    helper.append_op(
        type=op_type,
        inputs={"X": [input.name]},
        outputs={"Out": [out.name]},
        attrs={
            "dim": dim if dim is not None else [0],
            "keep_dim": keep_dim,
            "reduce_all": dim is None,
        },
    )
    return out


def reduce_sum(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_sum", input, dim, keep_dim, name)


def reduce_mean(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_mean", input, dim, keep_dim, name)


def reduce_max(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_max", input, dim, keep_dim, name)


def reduce_min(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_min", input, dim, keep_dim, name)


def reduce_prod(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_prod", input, dim, keep_dim, name)


def mean(x, name=None):
    helper = LayerHelper("mean", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="mean", inputs={"X": [x.name]}, outputs={"Out": [out.name]})
    return out


def _elementwise(op_type, x, y, axis, act, name):
    helper = LayerHelper(op_type, name=name, act=act)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type=op_type,
        inputs={"X": [x.name], "Y": [y.name]},
        outputs={"Out": [out.name]},
        attrs={"axis": axis},
    )
    return helper.append_activation(out)


def elementwise_add(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_add", x, y, axis, act, name)


def elementwise_sub(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_sub", x, y, axis, act, name)


def elementwise_mul(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_mul", x, y, axis, act, name)


def elementwise_div(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_div", x, y, axis, act, name)


def elementwise_max(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_max", x, y, axis, act, name)


def elementwise_min(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_min", x, y, axis, act, name)


def elementwise_pow(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_pow", x, y, axis, act, name)


def one_hot(input, depth):
    helper = LayerHelper("one_hot")
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        type="one_hot",
        inputs={"X": [input.name]},
        outputs={"Out": [out.name]},
        attrs={"depth": depth},
    )
    return out


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper("lrn", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    mid = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op(
        type="lrn",
        inputs={"X": [input.name]},
        outputs={"Out": [out.name], "MidOut": [mid.name]},
        attrs={"n": n, "k": k, "alpha": alpha, "beta": beta},
    )
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper("pad", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="pad",
        inputs={"X": [x.name]},
        outputs={"Out": [out.name]},
        attrs={"paddings": list(paddings), "pad_value": float(pad_value)},
    )
    return out


def pad2d(
    input, paddings=[0, 0, 0, 0], mode="constant", pad_value=0.0, data_format="NCHW", name=None
):
    helper = LayerHelper("pad2d", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="pad2d",
        inputs={"X": [input.name]},
        outputs={"Out": [out.name]},
        attrs={
            "paddings": list(paddings),
            "mode": mode,
            "pad_value": float(pad_value),
            "data_format": data_format,
        },
    )
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32", name=None):
    helper = LayerHelper("label_smooth", name=name)
    out = helper.create_variable_for_type_inference(dtype)
    inputs = {"X": [label.name]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist.name]
    helper.append_op(
        type="label_smooth",
        inputs=inputs,
        outputs={"Out": [out.name]},
        attrs={"epsilon": float(epsilon)},
    )
    return out


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten2", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op(
        type="flatten2",
        inputs={"X": [x.name]},
        outputs={"Out": [out.name], "XShape": [xshape.name]},
        attrs={"axis": axis},
    )
    return out


def squeeze(input, axes, name=None):
    helper = LayerHelper("squeeze2", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    xshape = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op(
        type="squeeze2",
        inputs={"X": [input.name]},
        outputs={"Out": [out.name], "XShape": [xshape.name]},
        attrs={"axes": list(axes)},
    )
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze2", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    xshape = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op(
        type="unsqueeze2",
        inputs={"X": [input.name]},
        outputs={"Out": [out.name], "XShape": [xshape.name]},
        attrs={"axes": list(axes)},
    )
    return out


def stack(x, axis=0):
    helper = LayerHelper("stack")
    if isinstance(x, Variable):
        x = [x]
    out = helper.create_variable_for_type_inference(x[0].dtype)
    helper.append_op(
        type="stack",
        inputs={"X": [v.name for v in x]},
        outputs={"Y": [out.name]},
        attrs={"axis": axis},
    )
    return out


def unstack(x, axis=0, num=None):
    helper = LayerHelper("unstack")
    if num is None:
        num = x.shape[axis]
    outs = [helper.create_variable_for_type_inference(x.dtype) for _ in range(num)]
    helper.append_op(
        type="unstack",
        inputs={"X": [x.name]},
        outputs={"Y": [o.name for o in outs]},
        attrs={"axis": axis, "num": num},
    )
    return outs


def expand(x, expand_times, name=None):
    helper = LayerHelper("expand", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="expand",
        inputs={"X": [x.name]},
        outputs={"Out": [out.name]},
        attrs={"expand_times": list(expand_times)},
    )
    return out


def gather(input, index):
    helper = LayerHelper("gather")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="gather",
        inputs={"X": [input.name], "Index": [index.name]},
        outputs={"Out": [out.name]},
    )
    return out


def scatter(input, index, updates, name=None, overwrite=True):
    helper = LayerHelper("scatter", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="scatter",
        inputs={"X": [input.name], "Ids": [index.name], "Updates": [updates.name]},
        outputs={"Out": [out.name]},
        attrs={"overwrite": overwrite},
    )
    return out


def slice(input, axes, starts, ends):
    helper = LayerHelper("slice")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="slice",
        inputs={"Input": [input.name]},
        outputs={"Out": [out.name]},
        attrs={"axes": list(axes), "starts": list(starts), "ends": list(ends)},
    )
    return out


def shape(input):
    helper = LayerHelper("shape")
    out = helper.create_variable_for_type_inference("int32")
    helper.append_op(
        type="shape", inputs={"Input": [input.name]}, outputs={"Out": [out.name]}
    )
    return out


def clip(x, min, max, name=None):
    helper = LayerHelper("clip", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="clip",
        inputs={"X": [x.name]},
        outputs={"Out": [out.name]},
        attrs={"min": float(min), "max": float(max)},
    )
    return out


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper("clip_by_norm", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="clip_by_norm",
        inputs={"X": [x.name]},
        outputs={"Out": [out.name]},
        attrs={"max_norm": float(max_norm)},
    )
    return out


def prelu(x, mode, param_attr=None, name=None):
    helper = LayerHelper("prelu", name=name)
    alpha_shape = [1]
    if mode == "channel":
        alpha_shape = [x.shape[1]]
    elif mode == "element":
        alpha_shape = list(x.shape[1:])
    alpha = helper.create_parameter(
        attr=helper.param_attr,
        shape=alpha_shape,
        dtype=x.dtype,
        default_initializer=Constant(0.25),
    )
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="prelu",
        inputs={"X": [x.name], "Alpha": [alpha.name]},
        outputs={"Out": [out.name]},
        attrs={"mode": mode},
    )
    return out


def leaky_relu(x, alpha=0.02, name=None):
    helper = LayerHelper("leaky_relu", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="leaky_relu",
        inputs={"X": [x.name]},
        outputs={"Out": [out.name]},
        attrs={"alpha": float(alpha)},
    )
    return out


def relu(x, name=None):
    helper = LayerHelper("relu", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="relu", inputs={"X": [x.name]}, outputs={"Out": [out.name]})
    return out


def log(x, name=None):
    helper = LayerHelper("log", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="log", inputs={"X": [x.name]}, outputs={"Out": [out.name]})
    return out


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    norm = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op(
        type="norm",
        inputs={"X": [x.name]},
        outputs={"Out": [out.name], "Norm": [norm.name]},
        attrs={"axis": 1 if axis is None else axis, "epsilon": epsilon},
    )
    return out


def image_resize(input, out_shape=None, scale=None, name=None, resample="BILINEAR", actual_shape=None, align_corners=True, align_mode=1):
    helper = LayerHelper("image_resize", name=name)
    if out_shape is None:
        out_shape = [int(input.shape[2] * scale), int(input.shape[3] * scale)]
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="bilinear_interp" if resample == "BILINEAR" else "nearest_interp",
        inputs={"X": [input.name]},
        outputs={"Out": [out.name]},
        attrs={
            "out_h": int(out_shape[0]),
            "out_w": int(out_shape[1]),
            "align_corners": align_corners,
        },
    )
    return out


def resize_bilinear(input, out_shape=None, scale=None, name=None, actual_shape=None, align_corners=True, align_mode=1):
    return image_resize(input, out_shape, scale, name, "BILINEAR", actual_shape, align_corners, align_mode)


def resize_nearest(input, out_shape=None, scale=None, name=None, actual_shape=None, align_corners=True):
    return image_resize(input, out_shape, scale, name, "NEAREST", actual_shape, align_corners)


def ring_attention(q, k, v, causal=False, axis_name="sp", name=None):
    """Exact attention with sequence sharded over the mesh's `axis_name`
    (context parallelism — new TPU-native capability; see
    parallel/ring_attention.py). q/k/v: (b, heads, t, d)."""
    helper = LayerHelper("ring_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    helper.append_op(
        type="ring_attention",
        inputs={"Q": [q.name], "K": [k.name], "V": [v.name]},
        outputs={"Out": [out.name]},
        attrs={"causal": causal, "axis_name": axis_name},
    )
    return out


def distributed_embedding(
    input,
    size,
    param_attr=None,
    dtype="float32",
    axis_name="ep",
    is_sparse=True,
    padding_idx=None,
    name=None,
):
    """Row-sharded embedding (the reference's distributed lookup table,
    SURVEY.md §2.7.5) on the EmbeddingEngine (paddle_tpu/embedding/): the
    table param shards over `axis_name`, the forward is a local gather + one
    psum, and with `is_sparse` (default) the backward emits a SelectedRows
    pair consumed by per-row optimizer updates with row-sharded moments —
    wire/HBM cost O(ids-per-batch) instead of O(table rows)."""
    from ..embedding import EmbeddingEngine

    engine = EmbeddingEngine(
        name=name,
        num_rows=size[0],
        dim=size[1],
        dtype=dtype,
        axis_name=axis_name,
        padding_idx=padding_idx,
        is_sparse=is_sparse,
        param_attr=param_attr,
    )
    return engine.lookup(input)


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    """Global step counter (reference layers/nn.py autoincreased_step_counter):
    persistable int var incremented once per executor run; used by LR
    schedulers."""
    helper = LayerHelper("global_step_counter")
    counter_name = counter_name or "@STEP_COUNTER@"
    counter = helper.create_or_get_global_variable(
        name=counter_name, dtype="int32", shape=[1], persistable=True
    )
    if not getattr(counter, "_step_counter_initialized", False):
        helper.set_variable_initializer(
            counter, Constant(value=float(begin - 1))
        )
        helper.main_program.global_block()._prepend_op(
            type="increment",
            inputs={"X": [counter.name]},
            outputs={"Out": [counter.name]},
            attrs={"step": float(step)},
        )
        counter._step_counter_initialized = True
        counter.stop_gradient = True
    return counter


def beam_search(
    pre_ids,
    pre_scores,
    ids,
    scores,
    beam_size,
    end_id,
    level=0,
    name=None,
    return_parent_idx=False,
):
    """One beam-search expansion step (reference layers/nn.py beam_search →
    beam_search_op.cc). Dense [batch*beam] layout: instead of the reference's
    LoD-encoded parentage this also produces a flat parent_idx tensor —
    gather decoder state with it each step (selected_ids._parent_idx holds
    the Variable when return_parent_idx is False).

    First step: initialize pre_scores as [0, -inf, ..., -inf] per source so
    identical initial beams don't crowd the beam (see decode_ops.py)."""
    helper = LayerHelper("beam_search", **locals())
    selected_ids = helper.create_variable_for_type_inference("int64")
    selected_scores = helper.create_variable_for_type_inference("float32")
    parent_idx = helper.create_variable_for_type_inference("int32")
    helper.append_op(
        type="beam_search",
        inputs={
            "pre_ids": [pre_ids.name],
            "pre_scores": [pre_scores.name],
            "ids": [ids.name],
            "scores": [scores.name],
        },
        outputs={
            "selected_ids": [selected_ids.name],
            "selected_scores": [selected_scores.name],
            "parent_idx": [parent_idx.name],
        },
        attrs={"beam_size": beam_size, "end_id": end_id, "level": level},
    )
    selected_ids.stop_gradient = True
    selected_scores.stop_gradient = True
    parent_idx.stop_gradient = True
    selected_ids._parent_idx = parent_idx
    if return_parent_idx:
        return selected_ids, selected_scores, parent_idx
    return selected_ids, selected_scores


def beam_search_decode(ids, scores, beam_size, end_id, name=None, parents=None):
    """Backtrack per-step beam selections into full hypotheses (reference
    layers/nn.py beam_search_decode → beam_search_decode_op.cc). `ids` and
    `scores` are tensor arrays written once per step; pass the parents array
    (of beam_search parent_idx writes) to follow beam reordering. Returns
    (sentence_ids [B, beam, T] best-first, sentence_scores [B, beam]); the
    ids Variable carries per-hypothesis lengths in ._hyp_len."""
    helper = LayerHelper("beam_search_decode", **locals())
    sentence_ids = helper.create_variable_for_type_inference("int64")
    sentence_scores = helper.create_variable_for_type_inference("float32")
    hyp_len = helper.create_variable_for_type_inference("int32")
    inputs = {"Ids": [ids.name], "Scores": [scores.name]}
    if parents is not None:
        inputs["Parents"] = [parents.name]
    helper.append_op(
        type="beam_search_decode",
        inputs=inputs,
        outputs={
            "SentenceIds": [sentence_ids.name],
            "SentenceScores": [sentence_scores.name],
            "SentenceLength": [hyp_len.name],
        },
        attrs={"beam_size": beam_size, "end_id": end_id},
    )
    sentence_ids.stop_gradient = True
    sentence_scores.stop_gradient = True
    hyp_len.stop_gradient = True
    sentence_ids._hyp_len = hyp_len
    return sentence_ids, sentence_scores


def flash_attention(q, k, v, causal=False, sm_scale=None, n_head=None,
                    name=None):
    """Fused blockwise attention — emits the Pallas flash-attention op
    (ops/pallas_kernels.py), the hand-tuned-kernel tier analog of the
    reference's math/jit_kernel fused primitives. With n_head, over
    [b, t, h·d] tensors, the heads side by side on the last axis as a
    projection leaves them (no head split before and no merge after);
    without, over (b, h, t, d). Returns the context in q's layout."""
    from ..ops.pallas_kernels import flash_path_taken

    helper = LayerHelper("flash_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    attrs = {"causal": bool(causal)}
    if sm_scale is not None:
        attrs["sm_scale"] = float(sm_scale)
    if n_head is not None:
        attrs["n_head"] = int(n_head)
    outputs = {"Out": [out.name]}
    # declare the logsumexp residual exactly when the static shapes make the
    # lowering take the Pallas path (flash_path_taken is that decision's
    # mirror), so flash_attention_grad consumes the saved residual instead
    # of re-running the forward inside jax.vjp (see _flash_attention_op)
    rank, t_axis = (3, 1) if n_head is not None else (4, 2)
    tq = q.shape[t_axis] if q.shape is not None and len(q.shape) == rank else -1
    tk = k.shape[t_axis] if k.shape is not None and len(k.shape) == rank else -1
    if flash_path_taken(tq, tk, causal=bool(causal)):
        lse = helper.create_variable_for_type_inference("float32")
        lse.stop_gradient = True
        outputs["Lse"] = [lse.name]
    helper.append_op(
        type="flash_attention",
        inputs={"Q": [q.name], "K": [k.name], "V": [v.name]},
        outputs=outputs,
        attrs=attrs,
    )
    return out


def kv_cache_write(pool, rows, block_table, pos, page_size, scales=None,
                   name=None):
    """Scatter per-token K or V rows into a paged cache pool in place.

    ``pool`` is a persistable ``[n_pages * page_size, feat]`` tensor; each
    row of ``rows`` lands at ``block_table[pos // page_size] * page_size +
    pos % page_size``. The op's output IS the pool variable (the in-place
    idiom), so the serving lowering classifies the pool as written state
    and can donate its buffer across decode steps.

    ``scales`` (a persistable ``[n_pages * page_size]`` f32 tensor) turns
    on the int8 storage mode: rows quantize symmetrically per row on the
    scatter and the scale pool becomes a second in-place output, donated
    alongside the level pool."""
    helper = LayerHelper("kv_cache_write", name=name)
    inputs = {
        "Pool": [pool.name],
        "Rows": [rows.name],
        "BlockTable": [block_table.name],
        "Pos": [pos.name],
    }
    outputs = {"Out": [pool.name]}
    if scales is not None:
        inputs["Scales"] = [scales.name]
        outputs["OutScales"] = [scales.name]
    helper.append_op(
        type="kv_cache_write",
        inputs=inputs,
        outputs=outputs,
        attrs={"page_size": int(page_size)},
    )
    return pool


def paged_attention(q, k_pool, v_pool, block_table, pos, n_head, page_size,
                    sm_scale=None, k_scales=None, v_scales=None, name=None,
                    n_kv_head=None, window=None):
    """One-query-per-slot attention over a paged KV pool.

    ``q`` is ``[slots, n_head * d_head]`` (one decode token per slot),
    ``block_table`` ``[slots, pages_per_slot]`` int32, ``pos`` the query
    token's position; each slot attends to context positions 0..pos through
    its block table. Unused table entries point at the scratch page and are
    masked by the position bound. ``k_scales``/``v_scales`` (both or
    neither) read int8 pools: per-row f32 scales dequantize the gathered
    levels inline (see ops/generation_ops.py int8 pool mode). ``n_kv_head``
    (grouped-query attention): the pools' rows are ``n_kv_head * d_head``
    wide and query head ``i`` reads KV head ``i // (n_head // n_kv_head)``.
    ``window`` (a sliding-window layer): the query at ``pos`` attends only
    ``pos - window < s <= pos``, through the op ``window_attention`` (the
    same lowering; table entries behind the window may be the scratch
    page)."""
    helper = LayerHelper("window_attention" if window else "paged_attention",
                         name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    attrs = {"n_head": int(n_head), "page_size": int(page_size)}
    if window:
        attrs["window"] = int(window)
    if n_kv_head is not None and int(n_kv_head) != int(n_head):
        attrs["n_kv_head"] = int(n_kv_head)
    if sm_scale is not None:
        attrs["sm_scale"] = float(sm_scale)
    inputs = {
        "Q": [q.name],
        "KPool": [k_pool.name],
        "VPool": [v_pool.name],
        "BlockTable": [block_table.name],
        "Pos": [pos.name],
    }
    if k_scales is not None:
        inputs["KScales"] = [k_scales.name]
        inputs["VScales"] = [v_scales.name]
    helper.append_op(
        type="window_attention" if window else "paged_attention",
        inputs=inputs,
        outputs={"Out": [out.name]},
        attrs=attrs,
    )
    return out


def rms_norm(x, epsilon=1e-5, groups=1, param_attr=None, name=None):
    """Root-mean-square norm over the last axis with a learned scale, in
    float32 whatever ``x``'s dtype. ``groups`` > 1 norms each of that many
    equal slices of the last axis on its own with one ``[d / groups]``
    scale: the per-head q/k norm over ``[rows, n_head * d_head]``."""
    from ..initializer import Constant

    helper = LayerHelper("rms_norm", name=name)
    width = int(x.shape[-1]) // int(groups)
    scale = helper.create_parameter(
        attr=param_attr, shape=[width], dtype="float32",
        default_initializer=Constant(1.0),
    )
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="rms_norm",
        inputs={"X": [x.name], "Scale": [scale.name]},
        outputs={"Out": [out.name]},
        attrs={"epsilon": float(epsilon), "groups": int(groups)},
    )
    return out


def rotary_embedding(x, pos, n_head, theta=10000.0, rotary_dim=None,
                     form="half", yarn=None, mscale=1.0, name=None):
    """Rotary position embedding of ``[rows, n_head * d_head]`` at the rows'
    positions ``pos``: of the whole head, or of its last ``rotary_dim``
    features; ``form`` "half" (pairs ``(i, i + d/2)``, rotate_half) or
    "interleaved" (pairs ``(2i, 2i + 1)``); ``yarn`` ``[factor, original
    positions, beta_fast, beta_slow]`` blends the frequencies as YaRN does
    (ops/decoder_ops.py ``yarn_inv_freq``); cos and sin times ``mscale``."""
    helper = LayerHelper("rotary_embedding", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    attrs = {"n_head": int(n_head), "theta": float(theta)}
    if rotary_dim:
        attrs["rotary_dim"] = int(rotary_dim)
    if form != "half":
        attrs["form"] = str(form)
    if yarn:
        attrs["yarn"] = [float(v) for v in yarn]
    if float(mscale) != 1.0:
        attrs["mscale"] = float(mscale)
    helper.append_op(
        type="rotary_embedding",
        inputs={"X": [x.name], "Pos": [pos.name]},
        outputs={"Out": [out.name]},
        attrs=attrs,
    )
    return out


def hyper_connection(x, streams, iters, eps, clamp, norm_eps=1e-6,
                     w_attr=None, alpha_attr=None, bias_attr=None, name=None):
    """The read side of a hyper-connection around one sub-layer
    (ops/decoder_ops.py ``hyper_connection``): ``x`` is the residual stream
    ``[rows, streams * d]``; returns ``(u [rows, d], maps)``, what the
    sub-layer reads and the float32 maps ``hyper_connection_post`` writes
    its result back with. Its parameters are float32: ``W [streams * d,
    2 * streams + streams^2]``, ``Alpha [3]``, ``Bias [2 * streams +
    streams^2]``."""
    helper = LayerHelper("hyper_connection", name=name)
    n = int(streams)
    cols = 2 * n + n * n
    w = helper.create_parameter(
        attr=w_attr, shape=[int(x.shape[-1]), cols], dtype="float32")
    alpha = helper.create_parameter(attr=alpha_attr, shape=[3], dtype="float32")
    bias = helper.create_parameter(
        attr=bias_attr, shape=[cols], dtype="float32", is_bias=True)
    out = helper.create_variable_for_type_inference(x.dtype)
    maps = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        type="hyper_connection",
        inputs={"X": [x.name], "W": [w.name], "Alpha": [alpha.name],
                "Bias": [bias.name]},
        outputs={"Out": [out.name], "Maps": [maps.name]},
        attrs={"streams": n, "iters": int(iters), "eps": float(eps),
               "clamp_min": float(clamp[0]), "clamp_max": float(clamp[1]),
               "norm_eps": float(norm_eps)},
    )
    return out, maps


def hyper_connection_post(x, y, maps, streams, name=None):
    """The write side: the stream ``x`` mixed by the maps' ``H_res`` plus
    ``y`` spread by their ``H_post``; ``[rows, streams * d]``."""
    helper = LayerHelper("hyper_connection_post", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="hyper_connection_post",
        inputs={"X": [x.name], "Y": [y.name], "Maps": [maps.name]},
        outputs={"Out": [out.name]},
        attrs={"streams": int(streams)},
    )
    return out


def mla_attention(q, pool, block_table, pos, page_size, d_value, sm_scale,
                  name=None):
    """Latent attention, absorbed form, over one paged pool whose row is
    every head's key and, its first ``d_value`` columns, their value
    (ops/generation_ops.py ``mla_attention``). ``q`` is ``[rows, n_head *
    width]`` with ``width`` the pool's; returns ``[rows, n_head *
    d_value]``."""
    helper = LayerHelper("mla_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    helper.append_op(
        type="mla_attention",
        inputs={"Q": [q.name], "Pool": [pool.name],
                "BlockTable": [block_table.name], "Pos": [pos.name]},
        outputs={"Out": [out.name]},
        attrs={"page_size": int(page_size), "d_value": int(d_value),
               "sm_scale": float(sm_scale)},
    )
    return out


def dsa_index(q, w, pool, block_table, pos, page_size, name=None):
    """The lightning indexer's scores of every cached position a row's block
    table holds (ops/generation_ops.py ``dsa_index``): ``q`` ``[rows, heads *
    width]`` against a paged pool of index keys ``[pool_rows, width]``,
    ``w`` ``[rows, heads]`` float32; returns ``[rows, pages * page_size]``
    float32, -inf past each row's position."""
    helper = LayerHelper("dsa_index", name=name)
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        type="dsa_index",
        inputs={"Q": [q.name], "W": [w.name], "Pool": [pool.name],
                "BlockTable": [block_table.name], "Pos": [pos.name]},
        outputs={"Out": [out.name]},
        attrs={"page_size": int(page_size)},
    )
    return out


def dsa_topk(scores, k, name=None):
    """The positions of each row's ``k`` largest scores, ascending, -1
    behind a row that has fewer (ops/generation_ops.py ``dsa_topk``):
    ``([rows, k] int32, the same selection as a [rows, positions] bool
    mask)``."""
    helper = LayerHelper("dsa_topk", name=name)
    out = helper.create_variable_for_type_inference("int32")
    mask = helper.create_variable_for_type_inference("bool")
    helper.append_op(
        type="dsa_topk", inputs={"X": [scores.name]},
        outputs={"Out": [out.name], "Mask": [mask.name]}, attrs={"k": int(k)},
    )
    return out, mask


def mla_sparse_attention(q, pool, block_table, selected, page_size, d_value,
                         sm_scale, live=None, name=None):
    """mla_attention over the positions ``selected`` ``[rows, k]`` alone
    (ops/generation_ops.py ``mla_sparse_attention``); returns ``[rows, n_head
    * d_value]``. ``live`` ``[rows]`` int32 (1: a real row), with per-row
    block tables: the other rows walk nothing and give 0."""
    helper = LayerHelper("mla_sparse_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    inputs = {"Q": [q.name], "Pool": [pool.name],
              "BlockTable": [block_table.name], "Selected": [selected.name]}
    if live is not None:
        inputs["Live"] = [live.name]
    helper.append_op(
        type="mla_sparse_attention",
        inputs=inputs,
        outputs={"Out": [out.name]},
        attrs={"page_size": int(page_size), "d_value": int(d_value),
               "sm_scale": float(sm_scale)},
    )
    return out


def dsa_count(mask, block_table, pos, page_size, name=None):
    """int32 ``[1]``: the distinct pool rows the selection ``mask``
    (``dsa_topk``'s) names through ``block_table`` (ops/generation_ops.py
    ``dsa_count``)."""
    helper = LayerHelper("dsa_count", name=name)
    out = helper.create_variable_for_type_inference("int32")
    helper.append_op(
        type="dsa_count",
        inputs={"Mask": [mask.name], "BlockTable": [block_table.name],
                "Pos": [pos.name]},
        outputs={"Out": [out.name]}, attrs={"page_size": int(page_size)},
    )
    return out


def swiglu(gate, up, name=None):
    """``silu(gate) * up``, the gated feed-forward's activation."""
    helper = LayerHelper("swiglu", name=name)
    out = helper.create_variable_for_type_inference(gate.dtype)
    helper.append_op(
        type="swiglu",
        inputs={"X": [gate.name], "Y": [up.name]},
        outputs={"Out": [out.name]},
    )
    return out


def short_conv(x, taps, param_attr=None, state=None, rows=None, start=None,
               live=None, mode="chunk", seq_len=0, form="gated",
               bias_attr=None, name=None):
    """A depthwise causal convolution of ``taps`` taps over the sequence.
    ``form`` "gated": between its projections, ``x`` is ``[rows, 3 * d]`` =
    ``[B, C, z]`` and the result ``C * conv(B * z)``; "silu": ``x`` is
    ``[rows, d]`` and the result ``silu(conv(x) + bias)`` (the bias with
    ``bias_attr``). ``state`` is the persistable ``[state_rows, (taps - 1)
    * d]`` pool holding each sequence's last ``taps - 1`` rows of what is
    convolved, indexed by the row table ``rows`` (row 0 is scratch); the op
    writes it in place. ``mode`` "chunk": consecutive positions of one
    sequence from ``start``, ``live`` marks the rows that are not padding;
    "step": one position of a sequence a row. Without a state the rows are
    whole sequences of ``seq_len`` positions."""
    helper = LayerHelper("short_conv", name=name)
    gated = form == "gated"
    d = int(x.shape[-1]) // (3 if gated else 1)
    kernel = helper.create_parameter(
        attr=param_attr, shape=[d, int(taps)], dtype="float32")
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x.name], "Kernel": [kernel.name]}
    outputs = {"Out": [out.name]}
    attrs = {"mode": mode, "seq_len": int(seq_len)}
    if not gated:
        attrs["form"] = str(form)
        if bias_attr is not None:
            bias = helper.create_parameter(
                attr=bias_attr, shape=[d], dtype="float32", is_bias=True)
            inputs["Bias"] = [bias.name]
    _seq_state_slots(inputs, outputs, state, rows, start, live, mode)
    helper.append_op(
        type="short_conv", inputs=inputs, outputs=outputs, attrs=attrs)
    return out


def _seq_state_slots(inputs, outputs, state, rows, start, live, mode):
    """The slots of an op that keeps a per-sequence state row in a pool it
    writes in place (short_conv, ssm_scan)."""
    if state is None:
        return
    inputs["State"] = [state.name]
    inputs["Rows"] = [rows.name]
    outputs["StateOut"] = [state.name]
    if mode == "chunk":
        inputs["Start"] = [start.name]
        inputs["Live"] = [live.name]


def ssm_scan(x, dt, z, heads, d_head, d_state, groups=1, chunk=256,
             epsilon=1e-5, dt_bias_attr=None, a_log_attr=None, d_attr=None,
             norm_attr=None, state=None, rows=None, start=None, live=None,
             mode="chunk", seq_len=0, name=None):
    """The Mamba-2 state-space operator between its convolution and its
    output projection (ops/decoder_ops.py ``ssm_scan``): ``x`` is the
    convolved ``[x | B | C]`` (``[rows, heads * d_head + 2 * groups *
    d_state]``), ``dt`` the raw step sizes ``[rows, heads]``, ``z`` the gate
    ``[rows, heads * d_head]``; returns the gated, RMS-normed output, shaped
    like ``z``. Its parameters (``dt_bias``, ``A_log``, ``D`` a head, the
    norm's scale) are float32, as is ``state``, the persistable
    ``[state_rows, d_state * heads * d_head]`` pool of the sequences'
    recurrent states, handled as ``short_conv``'s: ``rows``, ``start``,
    ``live``, ``mode`` and ``seq_len`` mean what they mean there."""
    helper = LayerHelper("ssm_scan", name=name)
    f32 = lambda attr, shape: helper.create_parameter(
        attr=attr, shape=shape, dtype="float32")
    heads = int(heads)
    inputs = {
        "X": [x.name], "Dt": [dt.name], "Z": [z.name],
        "DtBias": [f32(dt_bias_attr, [heads]).name],
        "ALog": [f32(a_log_attr, [heads]).name],
        "D": [f32(d_attr, [heads]).name],
        "NormScale": [f32(norm_attr, [heads * int(d_head)]).name],
    }
    out = helper.create_variable_for_type_inference(z.dtype)
    outputs = {"Out": [out.name]}
    _seq_state_slots(inputs, outputs, state, rows, start, live, mode)
    helper.append_op(
        type="ssm_scan", inputs=inputs, outputs=outputs,
        attrs={"mode": mode, "seq_len": int(seq_len), "heads": heads,
               "d_head": int(d_head), "d_state": int(d_state),
               "groups": int(groups), "chunk": int(chunk),
               "epsilon": float(epsilon)},
    )
    return out


def moe_router(x, num_experts, k, live=None, use_bias=True, norm_topk=True,
               scale=1.0, param_attr=None, bias_attr=None, name=None):
    """Sigmoid top-k router in float32: returns ``(picks [rows, k] int32,
    weights [rows, k] float32)``. The bias is added to the scores for the
    choice only. Rows with ``live`` == 0 get the pick ``num_experts`` (no
    expert) and weight 0."""
    helper = LayerHelper("moe_router", name=name)
    w = helper.create_parameter(
        attr=param_attr, shape=[int(x.shape[-1]), int(num_experts)],
        dtype="float32")
    inputs = {"X": [x.name], "W": [w.name]}
    if use_bias:
        b = helper.create_parameter(
            attr=bias_attr, shape=[int(num_experts)], dtype="float32",
            is_bias=True)
        inputs["Bias"] = [b.name]
    if live is not None:
        inputs["Live"] = [live.name]
    picks = helper.create_variable_for_type_inference("int32")
    weights = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        type="moe_router", inputs=inputs,
        outputs={"Picks": [picks.name], "Weights": [weights.name]},
        attrs={"k": int(k), "norm_topk": bool(norm_topk),
               "scale": float(scale)},
    )
    return picks, weights


def moe_experts(x, picks, weights, num_experts, width, experts_held=None,
                w1_attr=None, w3_attr=None, w2_attr=None, name=None):
    """The held experts' part of the routed sum of gated feed-forwards
    (``width`` wide): rows grouped by expert and one ragged product a
    matrix. ``experts_held`` lists the global ids of the experts whose
    weights this op owns (default: all ``num_experts``)."""
    helper = LayerHelper("moe_experts", name=name)
    held = [int(e) for e in (experts_held or [])]
    n_held = len(held) or int(num_experts)
    d = int(x.shape[-1])
    dtype = x.dtype
    w1 = helper.create_parameter(attr=w1_attr, shape=[n_held, d, int(width)], dtype=dtype)
    w3 = helper.create_parameter(attr=w3_attr, shape=[n_held, d, int(width)], dtype=dtype)
    w2 = helper.create_parameter(attr=w2_attr, shape=[n_held, int(width), d], dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="moe_experts",
        inputs={"X": [x.name], "Picks": [picks.name],
                "Weights": [weights.name], "W1": [w1.name], "W3": [w3.name],
                "W2": [w2.name]},
        outputs={"Out": [out.name]},
        attrs={"num_experts": int(num_experts), "experts_held": held},
    )
    return out
