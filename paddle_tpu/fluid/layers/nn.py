"""Neural-network layer functions (reference: python/paddle/fluid/layers/nn.py,
~17.9k LoC / ~200 functions; each builds ops through LayerHelper.append_op).
"""

from __future__ import annotations

import numpy as np

from .. import core
from ..framework import Variable
from ..layer_helper import LayerHelper
from ..initializer import Constant, Normal, Xavier
from ..param_attr import ParamAttr

__all__ = [
    "hsigmoid",
    "nce",
    "cos_sim",
    "flash_attention",
    "flash_decode_paged_attention",
    "kv_cache_write_paged",
    "kv_cache_gather_paged",
    "kv_cache_block_copy",
    "scale",
    "sequence_pool",
    "sequence_first_step",
    "sequence_last_step",
    "sequence_softmax",
    "sequence_reshape",
    "sequence_concat",
    "sequence_expand",
    "sequence_expand_as",
    "sequence_pad",
    "sequence_unpad",
    "sequence_slice",
    "sequence_reverse",
    "sequence_mask",
    "sequence_enumerate",
    "sequence_scatter",
    "sequence_conv",
    "row_conv",
    "im2sequence",
    "linear_chain_crf",
    "crf_decoding",
    "fc",
    "embedding",
    "conv2d",
    "conv2d_transpose",
    "pool2d",
    "adaptive_pool2d",
    "batch_norm",
    "layer_norm",
    "group_norm",
    "instance_norm",
    "dropout",
    "softmax",
    "log_softmax",
    "relu6",
    "leaky_relu",
    "elu",
    "swish",
    "hard_sigmoid",
    "hard_swish",
    "brelu",
    "soft_relu",
    "prelu",
    "pow",
    "stanh",
    "l2_normalize",
    "matmul",
    "mul",
    "elementwise_add",
    "elementwise_sub",
    "elementwise_mul",
    "elementwise_div",
    "elementwise_max",
    "elementwise_min",
    "elementwise_pow",
    "elementwise_mod",
    "elementwise_floordiv",
    "clip",
    "clip_by_norm",
    "mean",
    "reduce_sum",
    "reduce_mean",
    "reduce_max",
    "reduce_min",
    "reduce_prod",
    "reduce_all",
    "reduce_any",
    "reshape",
    "squeeze",
    "unsqueeze",
    "flatten",
    "transpose",
    "split",
    "stack",
    "unstack",
    "expand",
    "slice",
    "gather",
    "scatter",
    "shape",
    "one_hot",
    "topk",
    "lrn",
    "pad",
    "pad2d",
    "image_resize",
    "resize_bilinear",
    "resize_nearest",
    "label_smooth",
    "maxout",
    "relu",
    "uniform_random_batch_size_like",
    "gaussian_random",
    "sampling_id",
    "autoincreased_step_counter",
    "unfold",
    "where",
    "sign",
    "grid_sampler",
    "logical_and",
    "logical_or",
    "logical_not",
    "logical_xor",
]


def fc(
    input,
    size,
    num_flatten_dims=1,
    param_attr=None,
    bias_attr=None,
    act=None,
    name=None,
):
    """Fully connected (reference: layers/nn.py:233 fc — mul + elementwise_add
    + activation; multiple inputs are summed)."""
    helper = LayerHelper("fc", **locals())
    dtype = helper.input_dtype()
    mul_results = []
    for input_var, param_attr_ in helper.iter_inputs_and_params():
        input_shape = input_var.shape
        param_shape = [
            int(np.prod(input_shape[num_flatten_dims:])),
            size,
        ]
        w = helper.create_parameter(
            attr=param_attr_, shape=param_shape, dtype=dtype
        )
        tmp = helper.create_variable_for_type_inference(dtype)
        helper.append_op(
            type="mul",
            inputs={"X": [input_var], "Y": [w]},
            outputs={"Out": [tmp]},
            attrs={"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1},
        )
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(dtype)
        helper.append_op(
            type="sum", inputs={"X": mul_results}, outputs={"Out": [pre_bias]}
        )
    pre_act = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims)
    return helper.append_activation(pre_act)


def embedding(
    input,
    size,
    is_sparse=False,
    is_distributed=False,
    padding_idx=None,
    param_attr=None,
    dtype="float32",
):
    """reference: layers/nn.py embedding -> lookup_table op. On TPU the
    gather is dense XLA; is_sparse only affects the gradient representation
    (dense scatter-add here — SelectedRows is host-side only)."""
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
        inputs={"Ids": [input], "W": [w]},
        outputs={"Out": [tmp]},
        attrs={
            "is_sparse": is_sparse,
            "is_distributed": is_distributed,
            "padding_idx": padding_idx,
        },
    )
    return tmp


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
    data_format="NCHW",
):
    """reference: layers/nn.py conv2d. use_cudnn is accepted and ignored —
    XLA owns conv algorithm selection on TPU."""
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
    op_type = (
        "depthwise_conv2d"
        if groups == num_channels and num_filters % num_channels == 0
        else "conv2d"
    )
    helper.append_op(
        type=op_type,
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [pre_bias]},
        attrs={
            "strides": stride,
            "paddings": padding,
            "dilations": dilation,
            "groups": groups,
            "use_cudnn": use_cudnn,
            "data_format": data_format,
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
            raise ValueError("filter_size or output_size must be set")
        output_size = _pair(output_size)
        h = input.shape[2]
        filter_size = [
            output_size[0] - (h - 1) * stride[0] + 2 * padding[0],
            output_size[1] - (input.shape[3] - 1) * stride[1] + 2 * padding[1],
        ]
    else:
        filter_size = _pair(filter_size)
    filter_shape = [num_channels, num_filters // groups] + filter_size
    w = helper.create_parameter(
        attr=helper.param_attr, shape=filter_shape, dtype=dtype
    )
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="conv2d_transpose",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [pre_bias]},
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
    dtype = helper.input_dtype()
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="pool2d",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={
            "pooling_type": pool_type,
            "ksize": _pair(pool_size),
            "global_pooling": global_pooling,
            "strides": _pair(pool_stride),
            "paddings": _pair(pool_padding),
            "ceil_mode": ceil_mode,
            "use_cudnn": use_cudnn,
            "exclusive": exclusive,
        },
    )
    return out


def adaptive_pool2d(input, pool_size, pool_type="max", require_index=False, name=None):
    helper = LayerHelper("adaptive_pool2d", **locals())
    out = helper.create_variable_for_type_inference(helper.input_dtype())
    helper.append_op(
        type="pool2d",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={
            "pooling_type": pool_type,
            "ksize": _pair(pool_size),
            "adaptive": True,
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
    """reference: layers/nn.py batch_norm. Mean/Variance are persistable vars
    the op rewrites in place (MeanOut/VarianceOut alias them)."""
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
    mean = helper.create_parameter(
        attr=ParamAttr(
            name=moving_mean_name, initializer=Constant(0.0), trainable=False
        ),
        shape=param_shape,
        dtype=dtype,
    )
    mean.stop_gradient = True
    variance = helper.create_parameter(
        attr=ParamAttr(
            name=moving_variance_name, initializer=Constant(1.0), trainable=False
        ),
        shape=param_shape,
        dtype=dtype,
    )
    variance.stop_gradient = True

    saved_mean = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    saved_variance = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    batch_norm_out = (
        input if in_place else helper.create_variable_for_type_inference(dtype)
    )
    helper.append_op(
        type="batch_norm",
        inputs={
            "X": [input],
            "Scale": [scale],
            "Bias": [bias],
            "Mean": [mean],
            "Variance": [variance],
        },
        outputs={
            "Y": [batch_norm_out],
            "MeanOut": [mean],
            "VarianceOut": [variance],
            "SavedMean": [saved_mean],
            "SavedVariance": [saved_variance],
        },
        attrs={
            "momentum": momentum,
            "epsilon": epsilon,
            "is_test": is_test,
            "data_layout": data_layout,
            "use_global_stats": use_global_stats,
        },
    )
    return helper.append_activation(batch_norm_out)


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
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(
            attr=helper.param_attr,
            shape=param_shape,
            dtype=dtype,
            default_initializer=Constant(1.0),
        )
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(
            attr=helper.bias_attr, shape=param_shape, dtype=dtype, is_bias=True
        )
        inputs["Bias"] = [b]
    mean_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    variance_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="layer_norm",
        inputs=inputs,
        outputs={"Y": [out], "Mean": [mean_out], "Variance": [variance_out]},
        attrs={"epsilon": epsilon, "begin_norm_axis": begin_norm_axis},
    )
    return helper.append_activation(out)


def group_norm(
    input, groups, epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
    data_layout="NCHW", name=None
):
    helper = LayerHelper("group_norm", **locals())
    dtype = helper.input_dtype()
    channels = input.shape[1]
    inputs = {"X": [input]}
    if param_attr is not False:
        s = helper.create_parameter(
            attr=helper.param_attr,
            shape=[channels],
            dtype=dtype,
            default_initializer=Constant(1.0),
        )
        inputs["Scale"] = [s]
    if bias_attr is not False:
        b = helper.create_parameter(
            attr=helper.bias_attr, shape=[channels], dtype=dtype, is_bias=True
        )
        inputs["Bias"] = [b]
    mean_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    variance_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="group_norm",
        inputs=inputs,
        outputs={"Y": [out], "Mean": [mean_out], "Variance": [variance_out]},
        attrs={"epsilon": epsilon, "groups": groups},
    )
    return helper.append_activation(out)


def instance_norm(input, epsilon=1e-5, param_attr=None, bias_attr=None, name=None):
    helper = LayerHelper("instance_norm", **locals())
    dtype = helper.input_dtype()
    channels = input.shape[1]
    scale = helper.create_parameter(
        attr=helper.param_attr,
        shape=[channels],
        dtype=dtype,
        default_initializer=Constant(1.0),
    )
    bias = helper.create_parameter(
        attr=helper.bias_attr, shape=[channels], dtype=dtype, is_bias=True
    )
    saved_mean = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    saved_var = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="instance_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias]},
        outputs={
            "Y": [out],
            "SavedMean": [saved_mean],
            "SavedVariance": [saved_var],
        },
        attrs={"epsilon": epsilon},
    )
    return out


def dropout(
    x,
    dropout_prob,
    is_test=False,
    seed=None,
    name=None,
    dropout_implementation="downgrade_in_infer",
):
    helper = LayerHelper("dropout", **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    mask = helper.create_variable_for_type_inference(
        dtype=x.dtype, stop_gradient=True
    )
    helper.append_op(
        type="dropout",
        inputs={"X": [x]},
        outputs={"Out": [out], "Mask": [mask]},
        attrs={
            "dropout_prob": dropout_prob,
            "is_test": is_test,
            "fix_seed": seed is not None,
            "seed": seed if seed is not None else 0,
            "dropout_implementation": dropout_implementation,
        },
    )
    return out


def softmax(input, use_cudnn=False, name=None, axis=-1):
    helper = LayerHelper("softmax", **locals())
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="softmax",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"axis": axis, "use_cudnn": use_cudnn},
    )
    return out


def log_softmax(input, axis=-1, name=None):
    helper = LayerHelper("log_softmax", **locals())
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="log_softmax",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"axis": axis},
    )
    return out


def _unary_attr_layer(op_type, x, attrs, name=None):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type=op_type, inputs={"X": [x]}, outputs={"Out": [out]}, attrs=attrs
    )
    return out


def relu(x, name=None):
    return _unary_attr_layer("relu", x, {}, name)


def relu6(x, threshold=6.0, name=None):
    return _unary_attr_layer("relu6", x, {"threshold": threshold}, name)


def leaky_relu(x, alpha=0.02, name=None):
    return _unary_attr_layer("leaky_relu", x, {"alpha": alpha}, name)


def elu(x, alpha=1.0, name=None):
    return _unary_attr_layer("elu", x, {"alpha": alpha}, name)


def swish(x, beta=1.0, name=None):
    return _unary_attr_layer("swish", x, {"beta": beta}, name)


def hard_sigmoid(x, slope=0.2, offset=0.5, name=None):
    return _unary_attr_layer(
        "hard_sigmoid", x, {"slope": slope, "offset": offset}, name
    )


def hard_swish(x, threshold=6.0, scale=6.0, offset=3.0, name=None):
    return _unary_attr_layer(
        "hard_swish",
        x,
        {"threshold": threshold, "scale": scale, "offset": offset},
        name,
    )


def brelu(x, t_min=0.0, t_max=24.0, name=None):
    return _unary_attr_layer("brelu", x, {"t_min": t_min, "t_max": t_max}, name)


def soft_relu(x, threshold=40.0, name=None):
    return _unary_attr_layer("soft_relu", x, {"threshold": threshold}, name)


def pow(x, factor=1.0, name=None):
    return _unary_attr_layer("pow", x, {"factor": factor}, name)


def stanh(x, scale_a=0.67, scale_b=1.7159, name=None):
    return _unary_attr_layer(
        "stanh", x, {"scale_a": scale_a, "scale_b": scale_b}, name
    )


def prelu(x, mode, param_attr=None, name=None):
    helper = LayerHelper("prelu", **locals())
    alpha_shape = [1]
    if mode == "channel":
        alpha_shape = [1, x.shape[1], 1, 1]
    elif mode == "element":
        alpha_shape = list(x.shape[1:])
    alpha = helper.create_parameter(
        attr=helper.param_attr,
        shape=alpha_shape,
        dtype="float32",
        is_bias=False,
        default_initializer=Constant(0.25),
    )
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="prelu",
        inputs={"X": [x], "Alpha": [alpha]},
        outputs={"Out": [out]},
        attrs={"mode": mode},
    )
    return out


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    norm = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="l2_normalize",
        inputs={"X": [x]},
        outputs={"Out": [out], "Norm": [norm]},
        attrs={"axis": axis, "epsilon": epsilon},
    )
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="matmul",
        inputs={"X": [x], "Y": [y]},
        outputs={"Out": [out]},
        attrs={
            "transpose_X": transpose_x,
            "transpose_Y": transpose_y,
            "alpha": float(alpha),
        },
    )
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="mul",
        inputs={"X": [x], "Y": [y]},
        outputs={"Out": [out]},
        attrs={
            "x_num_col_dims": x_num_col_dims,
            "y_num_col_dims": y_num_col_dims,
        },
    )
    return out


def _elementwise(op_type, x, y, axis=-1, act=None, name=None):
    helper = LayerHelper(op_type, name=name, act=act)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type=op_type,
        inputs={"X": [x], "Y": [y]},
        outputs={"Out": [out]},
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


def elementwise_mod(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_mod", x, y, axis, act, name)


def elementwise_floordiv(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_floordiv", x, y, axis, act, name)


def clip(x, min, max, name=None):
    helper = LayerHelper("clip", **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="clip",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"min": float(min), "max": float(max)},
    )
    return out


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper("clip_by_norm", **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="clip_by_norm",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"max_norm": float(max_norm)},
    )
    return out


def mean(x, name=None):
    helper = LayerHelper("mean", **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(type="mean", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def _reduce(op_type, input, dim=None, keep_dim=False, name=None):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    if dim is not None and not isinstance(dim, (list, tuple)):
        dim = [dim]
    helper.append_op(
        type=op_type,
        inputs={"X": [input]},
        outputs={"Out": [out]},
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


def reduce_all(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_all", input, dim, keep_dim, name)


def reduce_any(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_any", input, dim, keep_dim, name)


def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper("reshape2", **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    x_shape = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="reshape2",
        inputs={"X": [x]},
        outputs={"Out": [out], "XShape": [x_shape]},
        attrs={"shape": list(shape)},
    )
    return helper.append_activation(out)


def squeeze(input, axes, name=None):
    helper = LayerHelper("squeeze2", **locals())
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    x_shape = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="squeeze2",
        inputs={"X": [input]},
        outputs={"Out": [out], "XShape": [x_shape]},
        attrs={"axes": axes},
    )
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze2", **locals())
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    x_shape = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="unsqueeze2",
        inputs={"X": [input]},
        outputs={"Out": [out], "XShape": [x_shape]},
        attrs={"axes": axes},
    )
    return out


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten2", **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    x_shape = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="flatten2",
        inputs={"X": [x]},
        outputs={"Out": [out], "XShape": [x_shape]},
        attrs={"axis": axis},
    )
    return out


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose2", **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    x_shape = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="transpose2",
        inputs={"X": [x]},
        outputs={"Out": [out], "XShape": [x_shape]},
        attrs={"axis": list(perm)},
    )
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", **locals())
    dim = dim if dim >= 0 else dim + len(input.shape)
    if isinstance(num_or_sections, int):
        num = num_or_sections
        sections = []
    else:
        num = 0
        sections = list(num_or_sections)
    outs = [
        helper.create_variable_for_type_inference(dtype=input.dtype)
        for _ in range(num or len(sections))
    ]
    helper.append_op(
        type="split",
        inputs={"X": [input]},
        outputs={"Out": outs},
        attrs={"num": num, "sections": sections, "axis": dim},
    )
    return outs


def stack(x, axis=0):
    helper = LayerHelper("stack")
    if isinstance(x, Variable):
        x = [x]
    out = helper.create_variable_for_type_inference(dtype=x[0].dtype)
    helper.append_op(
        type="stack", inputs={"X": x}, outputs={"Y": [out]}, attrs={"axis": axis}
    )
    return out


def unstack(x, axis=0, num=None):
    helper = LayerHelper("unstack")
    if num is None:
        num = x.shape[axis]
    outs = [
        helper.create_variable_for_type_inference(dtype=x.dtype)
        for _ in range(num)
    ]
    helper.append_op(
        type="unstack",
        inputs={"X": [x]},
        outputs={"Y": outs},
        attrs={"axis": axis, "num": num},
    )
    return outs


def expand(x, expand_times, name=None):
    helper = LayerHelper("expand", **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="expand",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"expand_times": list(expand_times)},
    )
    return out


def slice(input, axes, starts, ends):
    helper = LayerHelper("slice")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="slice",
        inputs={"Input": [input]},
        outputs={"Out": [out]},
        attrs={"axes": list(axes), "starts": list(starts), "ends": list(ends)},
    )
    return out


def gather(input, index, overwrite=True):
    helper = LayerHelper("gather")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="gather",
        inputs={"X": [input], "Index": [index]},
        outputs={"Out": [out]},
    )
    return out


def scatter(input, index, updates, name=None, overwrite=True):
    helper = LayerHelper("scatter", **locals())
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="scatter",
        inputs={"X": [input], "Ids": [index], "Updates": [updates]},
        outputs={"Out": [out]},
        attrs={"overwrite": overwrite},
    )
    return out


def shape(input):
    helper = LayerHelper("shape")
    out = helper.create_variable_for_type_inference(dtype=core.VarDesc.VarType.INT32)
    helper.append_op(
        type="shape", inputs={"Input": [input]}, outputs={"Out": [out]}
    )
    return out


def one_hot(input, depth, allow_out_of_range=False):
    helper = LayerHelper("one_hot")
    out = helper.create_variable_for_type_inference(dtype=core.VarDesc.VarType.FP32)
    helper.append_op(
        type="one_hot",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"depth": depth, "allow_out_of_range": allow_out_of_range},
    )
    return out


def topk(input, k, name=None):
    helper = LayerHelper("top_k", **locals())
    values = helper.create_variable_for_type_inference(dtype=input.dtype)
    indices = helper.create_variable_for_type_inference(
        dtype=core.VarDesc.VarType.INT64
    )
    helper.append_op(
        type="top_k",
        inputs={"X": [input]},
        outputs={"Out": [values], "Indices": [indices]},
        attrs={"k": k if isinstance(k, int) else 1},
    )
    values.stop_gradient = True
    indices.stop_gradient = True
    return values, indices


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper("lrn", **locals())
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    mid = helper.create_variable_for_type_inference(
        dtype=input.dtype, stop_gradient=True
    )
    helper.append_op(
        type="lrn",
        inputs={"X": [input]},
        outputs={"Out": [out], "MidOut": [mid]},
        attrs={"n": n, "k": k, "alpha": alpha, "beta": beta},
    )
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper("pad", **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="pad",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"paddings": list(paddings), "pad_value": float(pad_value)},
    )
    return out


def pad2d(
    input,
    paddings=[0, 0, 0, 0],
    mode="constant",
    pad_value=0.0,
    data_format="NCHW",
    name=None,
):
    helper = LayerHelper("pad2d", **locals())
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="pad2d",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={
            "paddings": list(paddings),
            "mode": mode,
            "pad_value": float(pad_value),
            "data_format": data_format,
        },
    )
    return out


def image_resize(
    input, out_shape=None, scale=None, name=None, resample="BILINEAR",
    actual_shape=None, align_corners=True, align_mode=1,
):
    op_type = "bilinear_interp" if resample == "BILINEAR" else "nearest_interp"
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    attrs = {
        "out_h": out_shape[0] if out_shape else 0,
        "out_w": out_shape[1] if out_shape else 0,
        "scale": scale or 0.0,
        "align_corners": align_corners,
        "align_mode": align_mode,
    }
    helper.append_op(
        type=op_type, inputs={"X": [input]}, outputs={"Out": [out]}, attrs=attrs
    )
    return out


def resize_bilinear(input, out_shape=None, scale=None, name=None, **kwargs):
    return image_resize(input, out_shape, scale, name, "BILINEAR", **kwargs)


def resize_nearest(input, out_shape=None, scale=None, name=None, **kwargs):
    return image_resize(input, out_shape, scale, name, "NEAREST", **kwargs)


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32", name=None):
    helper = LayerHelper("label_smooth", **locals())
    out = helper.create_variable_for_type_inference(dtype)
    inputs = {"X": [label]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist]
    helper.append_op(
        type="label_smooth",
        inputs=inputs,
        outputs={"Out": [out]},
        attrs={"epsilon": float(epsilon)},
    )
    return out


def maxout(x, groups, name=None):
    helper = LayerHelper("maxout", **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="maxout",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"groups": groups},
    )
    return out


def uniform_random_batch_size_like(
    input, shape, dtype="float32", input_dim_idx=0, output_dim_idx=0,
    min=-1.0, max=1.0, seed=0,
):
    helper = LayerHelper("uniform_random_batch_size_like", **locals())
    out = helper.create_variable_for_type_inference(core.np_to_dtype(np.dtype(dtype)))
    helper.append_op(
        type="uniform_random_batch_size_like",
        inputs={"Input": [input]},
        outputs={"Out": [out]},
        attrs={
            "shape": list(shape),
            "input_dim_idx": input_dim_idx,
            "output_dim_idx": output_dim_idx,
            "min": min,
            "max": max,
            "seed": seed,
            "dtype": core.np_to_dtype(np.dtype(dtype)),
        },
    )
    return out


def gaussian_random(shape, mean=0.0, std=1.0, seed=0, dtype="float32"):
    helper = LayerHelper("gaussian_random")
    out = helper.create_variable_for_type_inference(core.np_to_dtype(np.dtype(dtype)))
    helper.append_op(
        type="gaussian_random",
        outputs={"Out": [out]},
        attrs={
            "shape": list(shape),
            "mean": mean,
            "std": std,
            "seed": seed,
            "dtype": core.np_to_dtype(np.dtype(dtype)),
        },
    )
    return out


def sampling_id(x, min=0.0, max=1.0, seed=0, dtype="float32"):
    helper = LayerHelper("sampling_id")
    out = helper.create_variable_for_type_inference(core.VarDesc.VarType.INT64)
    helper.append_op(
        type="sampling_id",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"min": min, "max": max, "seed": seed},
    )
    return out


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    """reference: layers/nn.py autoincreased_step_counter — a persistable
    int64 counter incremented once per run; drives lr schedules."""
    helper = LayerHelper("global_step_counter")
    counter_name = counter_name or "@STEP_COUNTER@"
    counter = helper.create_or_get_global_variable(
        name=counter_name,
        dtype=core.VarDesc.VarType.INT64,
        shape=[1],
        persistable=True,
    )
    if not getattr(counter, "_step_init_done", False):
        from ..initializer import Constant

        helper.set_variable_initializer(
            counter, Constant(value=float(begin - 1))
        )
        helper.main_program.current_block()._prepend_op(
            type="increment",
            inputs={"X": [counter]},
            outputs={"Out": [counter]},
            attrs={"step": float(step)},
        )
        counter._step_init_done = True
        counter.stop_gradient = True
    return counter


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    helper = LayerHelper("unfold", **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="unfold",
        inputs={"X": [x]},
        outputs={"Y": [out]},
        attrs={
            "kernel_sizes": _pair(kernel_sizes),
            "strides": _pair(strides),
            "paddings": list(
                paddings if isinstance(paddings, (list, tuple)) else [paddings] * 4
            ),
            "dilations": _pair(dilations),
        },
    )
    return out


def where(condition, x, y):
    helper = LayerHelper("where")
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="where",
        inputs={"Condition": [condition], "X": [x], "Y": [y]},
        outputs={"Out": [out]},
    )
    return out


def sign(x):
    helper = LayerHelper("sign")
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(type="sign", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def grid_sampler(x, grid, name=None):
    helper = LayerHelper("grid_sampler", **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="grid_sampler",
        inputs={"X": [x], "Grid": [grid]},
        outputs={"Output": [out]},
    )
    return out


def _logical(op_type, x, y=None, out=None, name=None):
    helper = LayerHelper(op_type, name=name)
    if out is None:
        out = helper.create_variable_for_type_inference(
            dtype=core.VarDesc.VarType.BOOL
        )
    inputs = {"X": [x]}
    if y is not None:
        inputs["Y"] = [y]
    helper.append_op(type=op_type, inputs=inputs, outputs={"Out": [out]})
    return out


def logical_and(x, y, out=None, name=None):
    return _logical("logical_and", x, y, out, name)


def logical_or(x, y, out=None, name=None):
    return _logical("logical_or", x, y, out, name)


def logical_xor(x, y, out=None, name=None):
    return _logical("logical_xor", x, y, out, name)


def logical_not(x, out=None, name=None):
    return _logical("logical_not", x, None, out, name)


def _pair(v):
    if isinstance(v, (list, tuple)):
        return [int(x) for x in v]
    return [int(v), int(v)]


# ---------------------------------------------------------------------------
# sequence layers (reference: layers/nn.py sequence_* family and
# layers/sequence_lod.py in later versions) — thin builders over the
# padded+lengths sequence ops (ops/sequence_ops.py)
# ---------------------------------------------------------------------------
def _seq_one_in(op_type, x, attrs=None, out_slot="Out", extra_inputs=None,
                extra_outputs=None, dtype=None):
    helper = LayerHelper(op_type)
    out = helper.create_variable_for_type_inference(
        dtype=dtype or x.dtype
    )
    inputs = {"X": [x]}
    if extra_inputs:
        inputs.update(extra_inputs)
    outputs = {out_slot: [out]}
    if extra_outputs:
        outputs.update(extra_outputs)
    helper.append_op(
        type=op_type, inputs=inputs, outputs=outputs, attrs=attrs or {}
    )
    return out


def flash_attention(q, k, v, key_bias=None, bias=None, causal=False,
                    scale=0.0, dropout_rate=0.0, is_test=False,
                    interpret=False, name=None):
    """Fused online-softmax attention over [N, heads, S, d_head] tensors
    (Pallas kernel on TPU — forward and backward, no [S, S] tensor ever
    reaches HBM; jnp reference elsewhere; reference analog: the
    fused_multihead_matmul CUDA op). ``key_bias``: optional [N, S]
    additive key mask; ``bias``: optional general additive bias
    broadcastable to [N, heads, S, S] (relative-position / ALiBi);
    ``scale`` 0 means 1/sqrt(d_head). ``dropout_rate``: in-kernel
    attention-probability dropout (seeded per step from the executor's
    key stream; disabled when ``is_test``)."""
    helper = LayerHelper("flash_attention", **locals())
    out = helper.create_variable_for_type_inference(dtype=q.dtype)
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if key_bias is not None:
        inputs["KeyBias"] = [key_bias]
    if bias is not None:
        inputs["Bias"] = [bias]
    helper.append_op(
        type="flash_attention",
        inputs=inputs,
        outputs={"Out": [out]},
        attrs={"causal": causal, "scale": float(scale),
               "dropout_rate": float(dropout_rate), "is_test": bool(is_test),
               "interpret": bool(interpret)},
    )
    return out


def flash_decode_paged_attention(q, k, v, tables, key_bias=None,
                                 scale=0.0, interpret=False, lengths=None,
                                 name=None):
    """Decode-mode single-query fused attention THROUGH a block table:
    ``q`` [N, heads, 1, d_head] against the shared paged pool ``k``/``v``
    [blocks, 1, block, heads*d_head] (a token's keys are one row, its
    heads side by side in ``q``'s order), with ``tables`` [N, max_blocks]
    int32 mapping each slot's logical blocks to physical pool blocks.
    ``key_bias`` [N, max_blocks*block] masks positions at/beyond each
    slot's live length (and any sink-block garbage). ``lengths`` [N]
    int, live keys a slot: table entries past ceil(length / block) are
    neither fetched nor computed (without it every entry is live).
    Tables and lengths are runtime data (scalar-prefetched on TPU) — one
    compiled program serves every table layout and length mix.
    Forward-only; ``scale`` 0 means 1/sqrt(d_head)."""
    helper = LayerHelper("flash_decode_paged_attention", **locals())
    out = helper.create_variable_for_type_inference(dtype=q.dtype)
    inputs = {"Q": [q], "K": [k], "V": [v], "Tables": [tables]}
    if key_bias is not None:
        inputs["KeyBias"] = [key_bias]
    if lengths is not None:
        inputs["Lengths"] = [lengths]
    helper.append_op(
        type="flash_decode_paged_attention",
        inputs=inputs,
        outputs={"Out": [out]},
        attrs={"scale": float(scale), "interpret": bool(interpret)},
    )
    return out


def kv_cache_write_paged(cache, new, tables, pos, name=None):
    """Block-table KV write: lands each slot's token window into ONE
    shared [blocks, r0, block, r1] pool through its fed
    [slots, max_blocks] int32 block table. What a token's ``[r0, r1]``
    row holds is the model's to say (``models/cache_kinds.py``: all the
    heads' keys side by side, ``[1, hidden]``, for ``models/gpt.py``; one
    latent row for ``models/deepseek.py``). ``new`` [slots, r0, T, r1];
    ``pos`` [slots] logical start positions — token j of slot
    s goes to pool block ``tables[s, (pos[s]+j)//block]`` at offset
    ``(pos[s]+j)%block``. Tables and positions are runtime DATA; one
    compiled program serves every table layout at 0 recompiles.
    Returns ``cache`` (output aliases input; donation-friendly).
    Inference-only (no gradient)."""
    helper = LayerHelper("kv_cache_write_paged", **locals())
    helper.append_op(
        type="kv_cache_write_paged",
        inputs={"Cache": [cache], "New": [new], "Tables": [tables],
                "Pos": [pos]},
        outputs={"Out": [cache]},
    )
    return cache


def kv_cache_gather_paged(cache, tables, name=None):
    """Materialize each slot's logical [r0, max_blocks*block, r1]
    cache row by gathering the [blocks, r0, block, r1] pool's blocks
    through its fed block table — the read half of the paged
    step/window programs. Out
    [slots, r0, max_blocks*block, r1]; positions past a slot's
    live length carry whatever the mapped blocks hold and MUST be
    masked by the caller's additive key bias. Inference-only."""
    helper = LayerHelper("kv_cache_gather_paged", **locals())
    out = helper.create_variable_for_type_inference(dtype=cache.dtype)
    helper.append_op(
        type="kv_cache_gather_paged",
        inputs={"Cache": [cache], "Tables": [tables]},
        outputs={"Out": [out]},
    )
    return out


def kv_cache_block_copy(cache, src, dst, name=None):
    """Pool-internal whole-block copy ``cache[dst[i]] = cache[src[i]]``
    — the copy-on-write primitive: duplicate a shared block's contents
    into a fresh block before its new owner writes the partial tail.
    ``src``/``dst`` are fed int32 vectors (runtime data; only their
    count is shape — pad with src==dst identity pairs to reuse one
    compiled count). Reads happen before writes (functional gather →
    scatter), so overlapping pairs see pre-copy values. Returns
    ``cache`` (output aliases input). Inference-only."""
    helper = LayerHelper("kv_cache_block_copy", **locals())
    helper.append_op(
        type="kv_cache_block_copy",
        inputs={"Cache": [cache], "Src": [src], "Dst": [dst]},
        outputs={"Out": [cache]},
    )
    return cache


def cos_sim(X, Y):
    """Row-wise cosine similarity (reference: layers/nn.py cos_sim over
    cos_sim_op.cc); Y may have batch 1 and broadcast against X."""
    helper = LayerHelper("cos_sim", **locals())
    out = helper.create_variable_for_type_inference(dtype=X.dtype)
    xnorm = helper.create_variable_for_type_inference(dtype=X.dtype)
    ynorm = helper.create_variable_for_type_inference(dtype=X.dtype)
    helper.append_op(
        type="cos_sim",
        inputs={"X": [X], "Y": [Y]},
        outputs={"Out": [out], "XNorm": [xnorm], "YNorm": [ynorm]},
    )
    return out


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None,
          name=None):
    """reference: layers/nn.py scale."""
    helper = LayerHelper("scale", **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="scale",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"scale": float(scale), "bias": float(bias),
               "bias_after_scale": bias_after_scale},
    )
    return helper.append_activation(out) if act else out


def sequence_pool(input, pool_type, is_test=False, pad_value=0.0):
    """reference: layers/nn.py sequence_pool."""
    helper = LayerHelper("sequence_pool")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    max_index = helper.create_variable_for_type_inference(dtype="int32")
    helper.append_op(
        type="sequence_pool",
        inputs={"X": [input]},
        outputs={"Out": [out], "MaxIndex": [max_index]},
        attrs={"pooltype": pool_type.upper(), "is_test": is_test,
               "pad_value": pad_value},
    )
    return out


def sequence_first_step(input):
    return sequence_pool(input, "first")


def sequence_last_step(input):
    return sequence_pool(input, "last")


def sequence_softmax(input, use_cudnn=False, name=None):
    return _seq_one_in("sequence_softmax", input)


def sequence_reshape(input, new_dim):
    return _seq_one_in("sequence_reshape", input, {"new_dim": new_dim})


def sequence_concat(input, name=None):
    helper = LayerHelper("sequence_concat")
    out = helper.create_variable_for_type_inference(dtype=input[0].dtype)
    helper.append_op(
        type="sequence_concat", inputs={"X": input}, outputs={"Out": [out]}
    )
    return out


def sequence_expand(x, y, ref_level=-1, name=None):
    helper = LayerHelper("sequence_expand")
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="sequence_expand",
        inputs={"X": [x], "Y": [y]},
        outputs={"Out": [out]},
        attrs={"ref_level": ref_level},
    )
    return out


def sequence_expand_as(x, y, name=None):
    helper = LayerHelper("sequence_expand_as")
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="sequence_expand_as",
        inputs={"X": [x], "Y": [y]},
        outputs={"Out": [out]},
    )
    return out


def sequence_pad(x, pad_value, maxlen=None, name=None):
    helper = LayerHelper("sequence_pad")
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    length = helper.create_variable_for_type_inference(dtype="int64")
    helper.append_op(
        type="sequence_pad",
        inputs={"X": [x], "PadValue": [pad_value]},
        outputs={"Out": [out], "Length": [length]},
        attrs={"padded_length": maxlen if maxlen is not None else -1},
    )
    return out, length


def sequence_unpad(x, length, name=None):
    helper = LayerHelper("sequence_unpad")
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op(
        type="sequence_unpad",
        inputs={"X": [x], "Length": [length]},
        outputs={"Out": [out]},
    )
    return out


def sequence_slice(input, offset, length, name=None):
    helper = LayerHelper("sequence_slice")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="sequence_slice",
        inputs={"X": [input], "Offset": [offset], "Length": [length]},
        outputs={"Out": [out]},
    )
    return out


def sequence_reverse(x, name=None):
    return _seq_one_in("sequence_reverse", x, out_slot="Y")


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    helper = LayerHelper("sequence_mask")
    out = helper.create_variable_for_type_inference(dtype=dtype)
    helper.append_op(
        type="sequence_mask",
        inputs={"X": [x]},
        outputs={"Y": [out]},
        attrs={
            "maxlen": maxlen if maxlen is not None else -1,
            "out_dtype": core.np_to_dtype(dtype),
        },
    )
    return out


def sequence_enumerate(input, win_size, pad_value=0, name=None):
    return _seq_one_in(
        "sequence_enumerate", input,
        {"win_size": win_size, "pad_value": pad_value},
    )


def sequence_scatter(input, index, updates, name=None):
    helper = LayerHelper("sequence_scatter")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type="sequence_scatter",
        inputs={"X": [input], "Ids": [index], "Updates": [updates]},
        outputs={"Out": [out]},
    )
    return out


def sequence_conv(input, num_filters, filter_size=3, filter_stride=1,
                  padding=None, bias_attr=None, param_attr=None, act=None,
                  name=None):
    """reference: layers/nn.py sequence_conv."""
    helper = LayerHelper("sequence_conv", **locals())
    dtype = helper.input_dtype()
    filter_shape = [filter_size * input.shape[-1], num_filters]
    filter_param = helper.create_parameter(
        attr=helper.param_attr, shape=filter_shape, dtype=dtype
    )
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="sequence_conv",
        inputs={"X": [input], "Filter": [filter_param]},
        outputs={"Out": [pre_bias]},
        attrs={
            "contextStride": filter_stride,
            "contextStart": -int(filter_size // 2),
            "contextLength": filter_size,
        },
    )
    pre_act = helper.append_bias_op(pre_bias, dim_start=2)
    return helper.append_activation(pre_act)


def row_conv(input, future_context_size, param_attr=None, act=None):
    helper = LayerHelper("row_conv", **locals())
    dtype = helper.input_dtype()
    filter_shape = [future_context_size + 1, input.shape[-1]]
    filter_param = helper.create_parameter(
        attr=helper.param_attr, shape=filter_shape, dtype=dtype
    )
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="row_conv",
        inputs={"X": [input], "Filter": [filter_param]},
        outputs={"Out": [out]},
    )
    return helper.append_activation(out)


def im2sequence(input, filter_size=1, stride=1, padding=0, input_image_size=None,
                out_stride=1, name=None):
    helper = LayerHelper("im2sequence")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    fs = filter_size if isinstance(filter_size, (list, tuple)) else [
        filter_size, filter_size
    ]
    st = stride if isinstance(stride, (list, tuple)) else [stride, stride]
    pd = padding if isinstance(padding, (list, tuple)) else [
        padding, padding, padding, padding
    ]
    helper.append_op(
        type="im2sequence",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"kernels": fs, "strides": st, "paddings": pd},
    )
    return out


def linear_chain_crf(input, label, param_attr=None, length=None):
    """reference: layers/nn.py linear_chain_crf."""
    helper = LayerHelper("linear_chain_crf", **locals())
    size = input.shape[-1]
    transition = helper.create_parameter(
        attr=helper.param_attr, shape=[size + 2, size], dtype=input.dtype
    )
    alpha = helper.create_variable_for_type_inference(dtype=input.dtype)
    emission_exps = helper.create_variable_for_type_inference(
        dtype=input.dtype
    )
    transition_exps = helper.create_variable_for_type_inference(
        dtype=input.dtype
    )
    log_likelihood = helper.create_variable_for_type_inference(
        dtype=input.dtype
    )
    inputs = {"Emission": [input], "Transition": [transition],
              "Label": [label]}
    if length is not None:
        inputs["Length"] = [length]
    helper.append_op(
        type="linear_chain_crf",
        inputs=inputs,
        outputs={
            "Alpha": [alpha],
            "EmissionExps": [emission_exps],
            "TransitionExps": [transition_exps],
            "LogLikelihood": [log_likelihood],
        },
    )
    return log_likelihood


def crf_decoding(input, param_attr, label=None, length=None):
    helper = LayerHelper("crf_decoding")
    # look up the transition parameter trained by linear_chain_crf
    tname = getattr(param_attr, "name", None) or str(param_attr)
    transition = helper.main_program.global_block()._find_var_recursive(
        tname
    )
    if transition is None:
        raise ValueError(
            "crf_decoding: transition parameter %r not found — pass the "
            "ParamAttr (with its name) used by linear_chain_crf" % tname
        )
    viterbi_path = helper.create_variable_for_type_inference(dtype="int64")
    inputs = {"Emission": [input], "Transition": [transition]}
    if label is not None:
        inputs["Label"] = [label]
    helper.append_op(
        type="crf_decoding",
        inputs=inputs,
        outputs={"ViterbiPath": [viterbi_path]},
    )
    return viterbi_path


def hsigmoid(
    input,
    label,
    num_classes,
    param_attr=None,
    bias_attr=None,
    name=None,
    path_table=None,
    path_code=None,
    is_custom=False,
    is_sparse=False,
):
    """Hierarchical sigmoid loss (reference: layers/nn.py hsigmoid over
    hierarchical_sigmoid_op.cc). Default = complete binary tree over
    num_classes; custom trees pass path_table/path_code."""
    helper = LayerHelper("hsigmoid", **locals())
    dtype = helper.input_dtype()
    num_nodes = num_classes - 1 if not is_custom else num_classes
    w = helper.create_parameter(
        attr=param_attr, shape=[max(num_nodes, 1), input.shape[-1]], dtype=dtype
    )
    inputs = {"X": [input], "Label": [label], "W": [w]}
    if bias_attr is not False:
        b = helper.create_parameter(
            attr=bias_attr, shape=[max(num_nodes, 1), 1], dtype=dtype,
            is_bias=True,
        )
        inputs["Bias"] = [b]
    if path_table is not None:
        inputs["PathTable"] = [path_table]
    if path_code is not None:
        inputs["PathCode"] = [path_code]
    out = helper.create_variable_for_type_inference(dtype)
    pre_out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="hierarchical_sigmoid",
        inputs=inputs,
        outputs={"Out": [out], "PreOut": [pre_out]},
        attrs={"num_classes": num_classes, "is_sparse": is_sparse},
    )
    return out


def nce(
    input,
    label,
    num_total_classes,
    sample_weight=None,
    param_attr=None,
    bias_attr=None,
    num_neg_samples=None,
    name=None,
    sampler="uniform",
    custom_dist=None,
    seed=0,
    is_sparse=False,
):
    """Noise-contrastive estimation loss (reference: layers/nn.py nce over
    nce_op.cc)."""
    helper = LayerHelper("nce", **locals())
    dtype = helper.input_dtype()
    dim = input.shape[-1]
    w = helper.create_parameter(
        attr=param_attr, shape=[num_total_classes, dim], dtype=dtype
    )
    inputs = {"Input": [input], "Label": [label], "Weight": [w]}
    if sample_weight is not None:
        inputs["SampleWeight"] = [sample_weight]
    if bias_attr is not False:
        b = helper.create_parameter(
            attr=bias_attr, shape=[num_total_classes, 1], dtype=dtype,
            is_bias=True,
        )
        inputs["Bias"] = [b]
    if custom_dist is not None:
        block = helper.main_program.current_block()
        probs = block.create_var(
            name=helper.name + "_custom_dist", dtype=dtype,
            shape=[num_total_classes], persistable=True,
        )
        from .tensor import assign

        assign(np.asarray(custom_dist, dtype=np.float32), output=probs)
        inputs["CustomDistProbs"] = [probs]
    cost = helper.create_variable_for_type_inference(dtype)
    sample_logits = helper.create_variable_for_type_inference(dtype)
    sample_labels = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="nce",
        inputs=inputs,
        outputs={
            "Cost": [cost],
            "SampleLogits": [sample_logits],
            "SampleLabels": [sample_labels],
        },
        attrs={
            "num_total_classes": num_total_classes,
            "num_neg_samples": num_neg_samples or 10,
            "seed": seed,
            "sampler": {"uniform": 0, "log_uniform": 1, "custom_dist": 2}[sampler],
            "is_sparse": is_sparse,
        },
    )
    return cost
