"""Neural network layers (the `fluid.layers.*` DSL).

Parity surface: python/paddle/fluid/layers/nn.py (~15k LoC, ~300 functions)
in the reference. Each function appends ops via LayerHelper; semantics match
the reference's op defs while lowering happens through the JAX emitters.
"""
from __future__ import annotations

import zlib
from typing import List, Optional, Sequence, Union

import numpy as np

from .. import framework
from ..dtypes import convert_dtype
from ..framework import Variable
from ..initializer import (ConstantInitializer, NormalInitializer,
                           NumpyArrayInitializer, UniformInitializer,
                           XavierInitializer)
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr


def fc(
    input,
    size,
    num_flatten_dims=1,
    param_attr=None,
    bias_attr=None,
    act=None,
    name=None,
):
    """Fully-connected layer (reference layers/nn.py fc). Multiple inputs sum."""
    helper = LayerHelper(
        "fc", input=input, param_attr=param_attr, bias_attr=bias_attr, act=act, name=name
    )
    inputs = helper.multiple_input()
    dtype = helper.input_dtype()
    mul_results = []
    param_attrs = helper.param_attr
    if not isinstance(param_attrs, list):
        param_attrs = [param_attrs] * len(inputs)
    for inp, pattr in zip(inputs, param_attrs):
        in_dims = inp.shape
        flat = int(np.prod([abs(d) for d in in_dims[num_flatten_dims:]]))
        w = helper.create_parameter(pattr, shape=[flat, size], dtype=dtype)
        tmp = helper.create_variable_for_type_inference(dtype)
        helper.append_op(
            type="mul",
            inputs={"X": [inp], "Y": [w]},
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
    """reference layers/nn.py embedding (lookup_table_v2). is_sparse is a
    no-op on TPU: the vjp grad is a fused scatter-add in XLA."""
    helper = LayerHelper("embedding", param_attr=param_attr)
    w = helper.create_parameter(helper.param_attr, shape=list(size), dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype)
    padding_idx = (
        -1
        if padding_idx is None
        else padding_idx
        if padding_idx >= 0
        else size[0] + padding_idx
    )
    helper.append_op(
        type="lookup_table_v2",
        inputs={"W": [w], "Ids": [input]},
        outputs={"Out": [out]},
        attrs={"padding_idx": padding_idx, "is_sparse": is_sparse},
    )
    return out


def conv2d(
    input,
    num_filters,
    filter_size,
    stride=1,
    padding=0,
    dilation=1,
    groups=1,
    param_attr=None,
    bias_attr=None,
    use_cudnn=True,
    act=None,
    name=None,
    data_format="NCHW",
):
    helper = LayerHelper(
        "conv2d", param_attr=param_attr, bias_attr=bias_attr, act=act, name=name
    )
    dtype = input.dtype
    num_channels = input.shape[1] if data_format == "NCHW" else input.shape[-1]
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    if isinstance(stride, int):
        stride = [stride, stride]
    if isinstance(padding, int):
        padding = [padding, padding]
    if isinstance(dilation, int):
        dilation = [dilation, dilation]
    filter_shape = [num_filters, num_channels // groups] + list(filter_size)
    std = (2.0 / (filter_size[0] * filter_size[1] * num_channels)) ** 0.5
    w = helper.create_parameter(
        helper.param_attr,
        shape=filter_shape,
        dtype=dtype,
        default_initializer=NormalInitializer(0.0, std),
    )
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        type="conv2d",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [pre_bias]},
        attrs={
            "strides": stride,
            "paddings": padding,
            "dilations": dilation,
            "groups": groups,
            "data_format": data_format,
        },
    )
    if data_format == "NCHW":
        pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    else:
        pre_act = helper.append_bias_op(pre_bias, dim_start=3)
    return helper.append_activation(pre_act)


def conv2d_transpose(
    input,
    num_filters,
    output_size=None,
    filter_size=None,
    padding=0,
    stride=1,
    dilation=1,
    groups=1,
    param_attr=None,
    bias_attr=None,
    use_cudnn=True,
    act=None,
    name=None,
):
    helper = LayerHelper(
        "conv2d_transpose", param_attr=param_attr, bias_attr=bias_attr, act=act, name=name
    )
    dtype = input.dtype
    num_channels = input.shape[1]
    if isinstance(stride, int):
        stride = [stride, stride]
    if isinstance(padding, int):
        padding = [padding, padding]
    if isinstance(dilation, int):
        dilation = [dilation, dilation]
    if filter_size is None:
        if output_size is None:
            raise ValueError("either filter_size or output_size must be set")
        if isinstance(output_size, int):
            output_size = [output_size, output_size]
        h_in, w_in = input.shape[2], input.shape[3]
        filter_size = [
            (output_size[0] - (h_in - 1) * stride[0] + 2 * padding[0] - 1) // dilation[0] + 1,
            (output_size[1] - (w_in - 1) * stride[1] + 2 * padding[1] - 1) // dilation[1] + 1,
        ]
    elif isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    filter_shape = [num_channels, num_filters // groups] + list(filter_size)
    w = helper.create_parameter(helper.param_attr, shape=filter_shape, dtype=dtype)
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
    exclusive=True,
    name=None,
    data_format="NCHW",
):
    helper = LayerHelper("pool2d", name=name)
    if isinstance(pool_size, int):
        pool_size = [pool_size, pool_size]
    if isinstance(pool_stride, int):
        pool_stride = [pool_stride, pool_stride]
    if isinstance(pool_padding, int):
        pool_padding = [pool_padding, pool_padding]
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="pool2d",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={
            "pooling_type": pool_type,
            "ksize": pool_size,
            "strides": pool_stride,
            "paddings": pool_padding,
            "global_pooling": global_pooling,
            "ceil_mode": ceil_mode,
            "exclusive": exclusive,
            "data_format": data_format,
        },
    )
    return out


def adaptive_pool2d(input, pool_size, pool_type="max", require_index=False, name=None):
    helper = LayerHelper("adaptive_pool2d", name=name)
    if isinstance(pool_size, int):
        pool_size = [pool_size, pool_size]
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="pool2d",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"pooling_type": pool_type, "ksize": pool_size, "adaptive": True},
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
    do_model_average_for_mean_and_var=True,
    use_global_stats=False,
):
    helper = LayerHelper(
        "batch_norm", param_attr=param_attr, bias_attr=bias_attr, act=act, name=name
    )
    dtype = input.dtype
    channels = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    scale = helper.create_parameter(
        helper.param_attr,
        shape=[channels],
        dtype=dtype,
        default_initializer=ConstantInitializer(1.0),
    )
    bias = helper.create_parameter(
        helper.bias_attr, shape=[channels], dtype=dtype, is_bias=True
    )
    mean = helper.create_parameter(
        ParamAttr(name=moving_mean_name, initializer=ConstantInitializer(0.0), trainable=False),
        shape=[channels],
        dtype=dtype,
    )
    mean.stop_gradient = True
    variance = helper.create_parameter(
        ParamAttr(name=moving_variance_name, initializer=ConstantInitializer(1.0), trainable=False),
        shape=[channels],
        dtype=dtype,
    )
    variance.stop_gradient = True
    saved_mean = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    saved_var = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    out = helper.create_variable_for_type_inference(dtype)
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
            "Y": [out],
            "MeanOut": [mean],
            "VarianceOut": [variance],
            "SavedMean": [saved_mean],
            "SavedVariance": [saved_var],
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
    helper = LayerHelper(
        "layer_norm", param_attr=param_attr, bias_attr=bias_attr, act=act, name=name
    )
    dtype = input.dtype
    norm_shape = list(input.shape[begin_norm_axis:])
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(
            helper.param_attr,
            shape=norm_shape,
            dtype=dtype,
            default_initializer=ConstantInitializer(1.0),
        )
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(
            helper.bias_attr, shape=norm_shape, dtype=dtype, is_bias=True
        )
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(dtype)
    mean = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    var = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op(
        type="layer_norm",
        inputs=inputs,
        outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
        attrs={"epsilon": epsilon, "begin_norm_axis": begin_norm_axis},
    )
    return helper.append_activation(out)


def group_norm(
    input, groups, epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
    data_layout="NCHW", name=None
):
    helper = LayerHelper(
        "group_norm", param_attr=param_attr, bias_attr=bias_attr, act=act, name=name
    )
    dtype = input.dtype
    channels = input.shape[1]
    inputs = {"X": [input]}
    if helper.param_attr is not False:
        s = helper.create_parameter(
            helper.param_attr, shape=[channels], dtype=dtype,
            default_initializer=ConstantInitializer(1.0),
        )
        inputs["Scale"] = [s]
    if helper.bias_attr is not False:
        b = helper.create_parameter(
            helper.bias_attr, shape=[channels], dtype=dtype, is_bias=True
        )
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(dtype)
    mean = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    var = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op(
        type="group_norm",
        inputs=inputs,
        outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
        attrs={"groups": groups, "epsilon": epsilon},
    )
    return helper.append_activation(out)


def instance_norm(input, epsilon=1e-5, param_attr=None, bias_attr=None, name=None):
    helper = LayerHelper("instance_norm", param_attr=param_attr, bias_attr=bias_attr, name=name)
    dtype = input.dtype
    channels = input.shape[1]
    inputs = {"X": [input]}
    if helper.param_attr is not False:
        s = helper.create_parameter(
            helper.param_attr, shape=[channels], dtype=dtype,
            default_initializer=ConstantInitializer(1.0),
        )
        inputs["Scale"] = [s]
    if helper.bias_attr is not False:
        b = helper.create_parameter(helper.bias_attr, shape=[channels], dtype=dtype, is_bias=True)
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(dtype)
    sm = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    sv = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op(
        type="instance_norm",
        inputs=inputs,
        outputs={"Y": [out], "SavedMean": [sm], "SavedVariance": [sv]},
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
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    mask = helper.create_variable_for_type_inference("uint8", stop_gradient=True)
    helper.append_op(
        type="dropout",
        inputs={"X": [x]},
        outputs={"Out": [out], "Mask": [mask]},
        attrs={
            "dropout_prob": dropout_prob,
            "is_test": is_test,
            "dropout_implementation": dropout_implementation,
        },
    )
    return out


def softmax(input, use_cudnn=False, name=None, axis=-1):
    helper = LayerHelper("softmax", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="softmax",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"axis": axis},
    )
    return out


def log_softmax(input, axis=-1, name=None):
    helper = LayerHelper("log_softmax", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="log_softmax",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"axis": axis},
    )
    return out


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="cross_entropy",
        inputs={"X": [input], "Label": [label]},
        outputs={"Y": [out]},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index},
    )
    return out


def softmax_with_cross_entropy(
    logits,
    label,
    soft_label=False,
    ignore_index=-100,
    numeric_stable_mode=True,
    return_softmax=False,
    axis=-1,
):
    helper = LayerHelper("softmax_with_cross_entropy")
    softmax_out = helper.create_variable_for_type_inference(logits.dtype)
    loss = helper.create_variable_for_type_inference(logits.dtype)
    helper.append_op(
        type="softmax_with_cross_entropy",
        inputs={"Logits": [logits], "Label": [label]},
        outputs={"Softmax": [softmax_out], "Loss": [loss]},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index, "axis": axis},
    )
    if return_softmax:
        return loss, softmax_out
    return loss


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100, name=None, normalize=False):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="sigmoid_cross_entropy_with_logits",
        inputs={"X": [x], "Label": [label]},
        outputs={"Out": [out]},
        attrs={"ignore_index": ignore_index, "normalize": normalize},
    )
    return out


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="square_error_cost",
        inputs={"X": [input], "Y": [label]},
        outputs={"Out": [out]},
    )
    return out


def one_hot(input, depth, allow_out_of_range=False):
    helper = LayerHelper("one_hot")
    out = helper.create_variable_for_type_inference("float32")
    op_type = "one_hot" if (input.shape and input.shape[-1] == 1) else "one_hot_v2"
    helper.append_op(
        type=op_type,
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"depth": depth},
    )
    out.stop_gradient = True
    return out


def mean(x, name=None):
    helper = LayerHelper("mean", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="mean", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def accuracy(input, label, k=1, correct=None, total=None):
    helper = LayerHelper("accuracy")
    topk_out = helper.create_variable_for_type_inference(input.dtype)
    topk_indices = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="top_k",
        inputs={"X": [input]},
        outputs={"Out": [topk_out], "Indices": [topk_indices]},
        attrs={"k": k},
    )
    acc_out = helper.create_variable_for_type_inference("float32")
    correct = correct or helper.create_variable_for_type_inference("int32")
    total = total or helper.create_variable_for_type_inference("int32")
    helper.append_op(
        type="accuracy",
        inputs={"Out": [topk_out], "Indices": [topk_indices], "Label": [label]},
        outputs={"Accuracy": [acc_out], "Correct": [correct], "Total": [total]},
    )
    acc_out.stop_gradient = True
    return acc_out


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    values = helper.create_variable_for_type_inference(input.dtype)
    indices = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        type="top_k",
        inputs={"X": [input]},
        outputs={"Out": [values], "Indices": [indices]},
        attrs={"k": k},
    )
    return values, indices


# ---------------------------------------------------------------------------
# elementwise / matmul / reduce wrappers
# ---------------------------------------------------------------------------


def _elementwise(op_type):
    def layer(x, y, axis=-1, act=None, name=None):
        helper = LayerHelper(op_type, act=act, name=name)
        out = helper.create_variable_for_type_inference(x.dtype)
        helper.append_op(
            type=op_type,
            inputs={"X": [x], "Y": [y]},
            outputs={"Out": [out]},
            attrs={"axis": axis},
        )
        return helper.append_activation(out)

    layer.__name__ = op_type
    return layer


elementwise_add = _elementwise("elementwise_add")
elementwise_sub = _elementwise("elementwise_sub")
elementwise_mul = _elementwise("elementwise_mul")
elementwise_div = _elementwise("elementwise_div")
elementwise_min = _elementwise("elementwise_min")
elementwise_max = _elementwise("elementwise_max")
elementwise_pow = _elementwise("elementwise_pow")
elementwise_mod = _elementwise("elementwise_mod")
elementwise_floordiv = _elementwise("elementwise_floordiv")


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
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
    helper = LayerHelper("mul", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="mul",
        inputs={"X": [x], "Y": [y]},
        outputs={"Out": [out]},
        attrs={"x_num_col_dims": x_num_col_dims, "y_num_col_dims": y_num_col_dims},
    )
    return out


def _reduce(op_type):
    def layer(input, dim=None, keep_dim=False, name=None):
        helper = LayerHelper(op_type, name=name)
        out = helper.create_variable_for_type_inference(input.dtype)
        if dim is None:
            attrs = {"reduce_all": True, "keep_dim": keep_dim}
        else:
            if isinstance(dim, int):
                dim = [dim]
            attrs = {"dim": list(dim), "keep_dim": keep_dim}
        helper.append_op(
            type=op_type, inputs={"X": [input]}, outputs={"Out": [out]}, attrs=attrs
        )
        return out

    layer.__name__ = op_type
    return layer


reduce_sum = _reduce("reduce_sum")
reduce_mean = _reduce("reduce_mean")
reduce_max = _reduce("reduce_max")
reduce_min = _reduce("reduce_min")
reduce_prod = _reduce("reduce_prod")
reduce_all = _reduce("reduce_all")
reduce_any = _reduce("reduce_any")


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper("scale", act=act, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="scale",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={
            "scale": float(scale),
            "bias": float(bias),
            "bias_after_scale": bias_after_scale,
        },
    )
    return helper.append_activation(out)


def clip(x, min, max, name=None):
    helper = LayerHelper("clip", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="clip",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"min": float(min), "max": float(max)},
    )
    return out


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper("clip_by_norm", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="clip_by_norm",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"max_norm": float(max_norm)},
    )
    return out


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    nrm = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="norm",
        inputs={"X": [x]},
        outputs={"Out": [out], "Norm": [nrm]},
        attrs={"axis": axis, "epsilon": epsilon},
    )
    return out


# ---------------------------------------------------------------------------
# shape manipulation wrappers
# ---------------------------------------------------------------------------


def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper("reshape2", act=act, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op(
        type="reshape2",
        inputs={"X": [x]},
        outputs={"Out": [out], "XShape": [xshape]},
        attrs={"shape": [int(s) for s in shape]},
    )
    return helper.append_activation(out)


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose2", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op(
        type="transpose2",
        inputs={"X": [x]},
        outputs={"Out": [out], "XShape": [xshape]},
        attrs={"axis": list(perm)},
    )
    return out


def squeeze(input, axes, name=None):
    helper = LayerHelper("squeeze2", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    xshape = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op(
        type="squeeze2",
        inputs={"X": [input]},
        outputs={"Out": [out], "XShape": [xshape]},
        attrs={"axes": list(axes)},
    )
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze2", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    xshape = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op(
        type="unsqueeze2",
        inputs={"X": [input]},
        outputs={"Out": [out], "XShape": [xshape]},
        attrs={"axes": list(axes)},
    )
    return out


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten2", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op(
        type="flatten2",
        inputs={"X": [x]},
        outputs={"Out": [out], "XShape": [xshape]},
        attrs={"axis": axis},
    )
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", name=name)
    axis = dim if dim >= 0 else dim + len(input.shape)
    if isinstance(num_or_sections, int):
        num = num_or_sections
        sections = []
        n_out = num
    else:
        num = 0
        sections = list(num_or_sections)
        n_out = len(sections)
    outs = [helper.create_variable_for_type_inference(input.dtype) for _ in range(n_out)]
    helper.append_op(
        type="split",
        inputs={"X": [input]},
        outputs={"Out": outs},
        attrs={"axis": axis, "num": num, "sections": sections},
    )
    return outs


def stack(x, axis=0, name=None):
    helper = LayerHelper("stack", name=name)
    out = helper.create_variable_for_type_inference(x[0].dtype)
    helper.append_op(
        type="stack",
        inputs={"X": x},
        outputs={"Y": [out]},
        attrs={"axis": axis},
    )
    return out


def unstack(x, axis=0, num=None, name=None):
    helper = LayerHelper("unstack", name=name)
    if num is None:
        num = x.shape[axis]
    outs = [helper.create_variable_for_type_inference(x.dtype) for _ in range(num)]
    helper.append_op(
        type="unstack",
        inputs={"X": [x]},
        outputs={"Y": outs},
        attrs={"axis": axis, "num": num},
    )
    return outs


def expand(x, expand_times, name=None):
    helper = LayerHelper("expand", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="expand",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"expand_times": list(expand_times)},
    )
    return out


def expand_as(x, target_tensor, name=None):
    helper = LayerHelper("expand_as", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="expand_as",
        inputs={"X": [x], "target_tensor": [target_tensor]},
        outputs={"Out": [out]},
    )
    return out


def gather(input, index, overwrite=True):
    helper = LayerHelper("gather")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="gather",
        inputs={"X": [input], "Index": [index]},
        outputs={"Out": [out]},
    )
    return out


def gather_nd(input, index, name=None):
    helper = LayerHelper("gather_nd", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="gather_nd",
        inputs={"X": [input], "Index": [index]},
        outputs={"Out": [out]},
    )
    return out


def scatter(input, index, updates, name=None, overwrite=True):
    helper = LayerHelper("scatter", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="scatter",
        inputs={"X": [input], "Ids": [index], "Updates": [updates]},
        outputs={"Out": [out]},
        attrs={"overwrite": overwrite},
    )
    return out


def scatter_nd_add(ref, index, updates, name=None):
    helper = LayerHelper("scatter_nd_add", name=name)
    out = helper.create_variable_for_type_inference(ref.dtype)
    helper.append_op(
        type="scatter_nd_add",
        inputs={"X": [ref], "Index": [index], "Updates": [updates]},
        outputs={"Out": [out]},
    )
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper("pad", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="pad",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"paddings": list(paddings), "pad_value": float(pad_value)},
    )
    return out


def pad2d(
    input, paddings=[0, 0, 0, 0], mode="constant", pad_value=0.0,
    data_format="NCHW", name=None
):
    helper = LayerHelper("pad2d", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
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


def shape(input):
    helper = LayerHelper("shape")
    out = helper.create_variable_for_type_inference("int32", stop_gradient=True)
    helper.append_op(type="shape", inputs={"Input": [input]}, outputs={"Out": [out]})
    return out


def slice(input, axes, starts, ends):
    helper = LayerHelper("slice")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="slice",
        inputs={"Input": [input]},
        outputs={"Out": [out]},
        attrs={"axes": list(axes), "starts": list(starts), "ends": list(ends)},
    )
    return out


def strided_slice(input, axes, starts, ends, strides):
    helper = LayerHelper("strided_slice")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="strided_slice",
        inputs={"Input": [input]},
        outputs={"Out": [out]},
        attrs={
            "axes": list(axes),
            "starts": list(starts),
            "ends": list(ends),
            "strides": list(strides),
        },
    )
    return out


def where(condition, x=None, y=None):
    """paddle.where / fluid.layers.where — ternary select."""
    helper = LayerHelper("where")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="where",
        inputs={"Condition": [condition], "X": [x], "Y": [y]},
        outputs={"Out": [out]},
    )
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32", name=None):
    helper = LayerHelper("label_smooth", name=name)
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


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1_loss")
    out = helper.create_variable_for_type_inference(x.dtype)
    diff = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x], "Y": [y]}
    if inside_weight is not None:
        inputs["InsideWeight"] = [inside_weight]
    if outside_weight is not None:
        inputs["OutsideWeight"] = [outside_weight]
    helper.append_op(
        type="smooth_l1_loss",
        inputs=inputs,
        outputs={"Out": [out], "Diff": [diff]},
        attrs={"sigma": sigma or 1.0},
    )
    return out


def huber_loss(input, label, delta):
    helper = LayerHelper("huber_loss")
    out = helper.create_variable_for_type_inference(input.dtype)
    residual = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="huber_loss",
        inputs={"X": [input], "Y": [label]},
        outputs={"Out": [out], "Residual": [residual]},
        attrs={"delta": float(delta)},
    )
    return out


def kldiv_loss(x, target, reduction="mean", name=None):
    helper = LayerHelper("kldiv_loss", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="kldiv_loss",
        inputs={"X": [x], "Target": [target]},
        outputs={"Loss": [out]},
        attrs={"reduction": reduction},
    )
    return out


def log_loss(input, label, epsilon=1e-4, name=None):
    helper = LayerHelper("log_loss", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="log_loss",
        inputs={"Predicted": [input], "Labels": [label]},
        outputs={"Loss": [out]},
        attrs={"epsilon": float(epsilon)},
    )
    return out


def cumsum(x, axis=None, exclusive=None, reverse=None):
    helper = LayerHelper("cumsum")
    out = helper.create_variable_for_type_inference(x.dtype)
    attrs = {}
    if axis is not None:
        attrs["axis"] = axis
    if exclusive is not None:
        attrs["exclusive"] = exclusive
    if reverse is not None:
        attrs["reverse"] = reverse
    helper.append_op(
        type="cumsum", inputs={"X": [x]}, outputs={"Out": [out]}, attrs=attrs
    )
    return out


def prelu(x, mode, param_attr=None, name=None):
    helper = LayerHelper("prelu", param_attr=param_attr, name=name)
    if mode == "all":
        alpha_shape = [1]
    elif mode == "channel":
        alpha_shape = [x.shape[1]]
    else:
        alpha_shape = list(x.shape[1:])
    alpha = helper.create_parameter(
        helper.param_attr,
        shape=alpha_shape,
        dtype=x.dtype,
        default_initializer=ConstantInitializer(0.25),
    )
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        type="prelu",
        inputs={"X": [x], "Alpha": [alpha]},
        outputs={"Out": [out]},
        attrs={"mode": mode},
    )
    return out


_rng_salt_counter = [0]


def fused_multihead_attention(
    q, k, v, attn_bias=None, num_heads=1, dropout_prob=0.0, is_test=False,
    causal=False, softmax_scale=None, name=None
):
    """Fused scaled-dot-product attention over head-interleaved [B,S,H]
    tensors (TPU: Pallas flash attention; see ops/attention.py). The
    reference gets this via graph fusion passes (multihead_matmul_fuse_pass);
    here it is a first-class op. causal=True masks future positions
    inside the kernel (block-level skipping of upper-triangular work).

    The scores are scaled by 1/sqrt(q's head width) unless `softmax_scale`
    gives the factor. `v` may have heads of another width than `q` and `k`
    ([B, S, num_heads * dv]); the result then has v's width. Either takes
    the latent form of the op (`ops/attention.py:latent_attention`), which
    has no bias and no dropout."""
    helper = LayerHelper("fused_multihead_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    _rng_salt_counter[0] += 1
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if attn_bias is not None:
        inputs["BiasQK"] = [attn_bias]
    attrs = {
        "num_heads": num_heads,
        "dropout_prob": dropout_prob,
        "is_test": is_test,
        "causal": bool(causal),
        "rng_salt": _rng_salt_counter[0],
    }
    if softmax_scale is not None:
        attrs["softmax_scale"] = float(softmax_scale)
    helper.append_op(
        type="fused_multihead_attention",
        inputs=inputs,
        outputs={"Out": [out]},
        attrs=attrs,
    )
    return out


def moe_ffn(
    input,
    num_experts,
    expert_hidden,
    top_k=2,
    capacity_factor=1.25,
    act="gelu",
    param_attr=None,
    name=None,
):
    """Mixture-of-Experts FFN (ops/moe_ops.py): top-k router + capacity-
    bounded dispatch + per-expert 2-layer FFN, all dense einsums so GSPMD
    can shard the expert dim over an "ep" mesh axis
    (DistributedStrategy.expert_parallel). New TPU-era capability — the
    reference (2020) predates MoE.

    input: [B, S, H]. Returns (out [B, S, H], aux_loss [] scalar); add
    `aux_weight * aux_loss` to the training loss to keep experts balanced.
    """
    helper = LayerHelper("moe_ffn", input=input, param_attr=param_attr, name=name)
    dtype = helper.input_dtype()
    h = input.shape[-1]
    e, f = num_experts, expert_hidden

    def _param(suffix, shape, is_bias=False):
        attr = ParamAttr._to_attr(param_attr)
        # biases stay zero-init (LayerHelper default) regardless of the
        # caller's weight initializer, matching the dense-FFN fc path
        init = attr.initializer if (attr and not is_bias) else None
        attr = ParamAttr(name=f"{name or helper.name}_{suffix}", initializer=init)
        return helper.create_parameter(attr, shape=shape, dtype=dtype, is_bias=is_bias)

    gate_w = _param("gate.w_0", [h, e])
    w1 = _param("expert.w1", [e, h, f])
    b1 = _param("expert.b1", [e, f], is_bias=True)
    w2 = _param("expert.w2", [e, f, h])
    b2 = _param("expert.b2", [e, h], is_bias=True)

    out = helper.create_variable_for_type_inference(dtype)
    aux = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        type="moe_ffn",
        inputs={
            "X": [input], "GateW": [gate_w],
            "W1": [w1], "B1": [b1], "W2": [w2], "B2": [b2],
        },
        outputs={"Out": [out], "AuxLoss": [aux]},
        attrs={
            "top_k": int(top_k),
            "capacity_factor": float(capacity_factor),
            "activation": act,
        },
    )
    return out, aux


def _named_param(helper, param_attr, name, suffix, shape, dtype,
                 default_initializer=None, trainable=True):
    """A parameter `<name>.<suffix>` that takes the caller's initializer
    (one `param_attr` serves all the matrices of a block)."""
    attr = ParamAttr._to_attr(param_attr)
    attr = ParamAttr(name=f"{name}.{suffix}",
                     initializer=attr.initializer if attr else None,
                     trainable=trainable)
    return helper.create_parameter(
        attr, shape=shape, dtype=dtype,
        default_initializer=default_initializer)


def rms_norm(input, epsilon=1e-5, group_size=None, param_attr=None, name=None):
    """Root-mean-square norm with a learned weight and no bias
    (ops/decoder_ops.py):

        y = x / sqrt(mean(x^2) + epsilon) * w

    over the last axis, or with `group_size` over every consecutive group
    of that many columns by itself under one shared `w` of that length:
    the per-head norm of queries and keys on a head-interleaved
    [B, S, heads * head_dim] tensor (`group_size=head_dim`). Statistics
    in float32 whatever the input dtype."""
    helper = LayerHelper("rms_norm", param_attr=param_attr, name=name)
    width = int(group_size or input.shape[-1])
    scale = helper.create_parameter(
        helper.param_attr, shape=[width], dtype="float32",
        default_initializer=ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="rms_norm", inputs={"X": [input], "Scale": [scale]},
        outputs={"Y": [out]}, attrs={"epsilon": float(epsilon)})
    return out


def rope(input, head_dim, theta=10000.0, inv_freq=None, name=None):
    """Rotary position embedding on a head-interleaved [B, S, heads *
    head_dim] tensor (ops/decoder_ops.py), rotate-half pairing: inside
    every head, columns i and i + head_dim/2 are one pair,

        y_i        = x_i cos(t f_i) - x_{i+d/2} sin(t f_i)
        y_{i+d/2}  = x_{i+d/2} cos(t f_i) + x_i sin(t f_i),  f_i = theta^(-2i/d)

    with t the index on axis 1. `inv_freq`, head_dim/2 numbers, replaces
    the f_i where the model brings its own table (YaRN's blend, computed
    once on the host). No parameter; rotation in float32."""
    helper = LayerHelper("rope", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    attrs = {"head_dim": int(head_dim), "theta": float(theta)}
    if inv_freq is not None:
        attrs["inv_freq"] = [float(f) for f in inv_freq]
    helper.append_op(
        type="rope", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs=attrs)
    return out


def short_conv(input, kernel_size=3, param_attr=None, name=None):
    """Gated short convolution, the operator of the `conv` layers of the
    LFM2 family (ops/decoder_ops.py). For x [B, S, H]:

        [Bg, Cg, u] = split3(x W_in)                    W_in  [H, 3H]
        c_t = sum_j w_j * (Bg * u)_{t-(L-1)+j}          w [L, H], depthwise,
                                                        causal, zeros before t = 0
        y   = (Cg * c) W_out                            W_out [H, H]

    No bias and no activation anywhere. Parameters `<name>.in_proj`,
    `<name>.conv` and `<name>.out_proj`."""
    helper = LayerHelper("short_conv", param_attr=param_attr, name=name)
    name = name or helper.name
    h = input.shape[-1]
    w_in = _named_param(helper, param_attr, name, "in_proj", [h, 3 * h],
                        "float32")
    taps = _named_param(helper, param_attr, name, "conv",
                        [int(kernel_size), h], "float32")
    w_out = _named_param(helper, param_attr, name, "out_proj", [h, h],
                         "float32")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        type="short_conv",
        inputs={"X": [input], "InW": [w_in], "Filter": [taps],
                "OutW": [w_out]},
        outputs={"Out": [out]})
    return out


def swiglu_ffn(input, size, remat=False, param_attr=None, name=None):
    """Dense SwiGLU feed-forward (ops/decoder_ops.py):

        y = W2 (silu(W1 x) * W3 x)        W1, W3 [H, size], W2 [size, H]

    without biases. `remat=True` keeps only x for the backward pass and
    computes the [.., size] intermediates again there. Parameters
    `<name>.w1`, `<name>.w3`, `<name>.w2`."""
    return _swiglu("swiglu_ffn", input, size, remat, param_attr, name)


def _expert_form(activation):
    """True where the experts have the third matrix W3 (SwiGLU), False for
    the two-matrix squared-ReLU form."""
    if activation not in ("swiglu", "relu2"):
        raise ValueError(
            f"activation {activation!r}: experts are 'swiglu' or 'relu2'")
    return activation == "swiglu"


def _swiglu(op_type, input, size, remat, param_attr, name,
            activation="swiglu"):
    helper = LayerHelper(op_type, param_attr=param_attr, name=name)
    name = name or helper.name
    h = input.shape[-1]
    inputs = {"X": [input], "W1": [_named_param(
        helper, param_attr, name, "w1", [h, size], "float32")]}
    if _expert_form(activation):
        inputs["W3"] = [_named_param(helper, param_attr, name, "w3",
                                     [h, size], "float32")]
    inputs["W2"] = [_named_param(helper, param_attr, name, "w2", [size, h],
                                 "float32")]
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type=op_type, inputs=inputs, outputs={"Out": [out]},
                     attrs={"remat": bool(remat)})
    return out


def shared_expert(input, size, remat=False, param_attr=None, name=None,
                  activation="swiglu"):
    """The expert every token passes, beside the routed ones of
    `moe_swiglu`: a SwiGLU feed-forward of the experts' width, as
    `swiglu_ffn` (or, with `activation="relu2"`, the two-matrix W2
    relu(W1 x)^2 without `<name>.w3`), lowered under the part scope
    `shared_expert`. In a deployment that splits the routed experts over
    chips every chip computes it alike, so it is counted once when shares
    are added up."""
    return _swiglu("shared_expert", input, size, remat, param_attr, name,
                   activation)


def mamba2(input, num_heads, head_dim, n_groups, state_size, conv_kernel=4,
           chunk_size=128, epsilon=1e-5, dt_min=0.001, dt_max=0.1,
           dt_floor=1e-4, param_attr=None, out_attr=None, name=None):
    """The Mamba-2 mixer (ops/ssm_ops.py; Dao and Gu, arXiv:2405.21060),
    the whole sublayer, for x [B, S, C] with d_in = num_heads * head_dim:

        [z, xBC, dt] = x W_in           d_in, d_in + 2 G N and H columns
        xBC = silu(conv(xBC) + b_conv)  causal, depthwise, conv_kernel taps
        [x, B, C] = xBC                 H heads of P; G groups of N
        dt_h = softplus(dt_h + dt_bias_h),  A_h = -exp(A_log_h)
        S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T      S_0 = 0, [P, N]
        y_t = S_t C_t + D_h x_t         head h reads group h // (H / G)
        out = RMSNorm_{d_in / G}(y * silu(z)) W_out

    The recurrence runs in chunks of `chunk_size` positions as matrix
    products (`ssd_scan`), decays and the carried state in float32.
    Returns (out [B, S, C], min_decay [H] float32: each head's smallest
    exp(dt A) over the step's tokens, fetchable like any variable).

    Parameters `<name>.in_proj`, `.conv1d.weight` [taps, d_in + 2 G N]
    and `.conv1d.bias` (uniform in +-1/sqrt(taps), what the release's
    convolution starts at), `.dt_bias` (the inverse softplus of a dt drawn
    log-uniform in [dt_min, dt_max] and floored at dt_floor), `.A_log`
    (log of A drawn uniform in [1, 16], the release's), `.D` (1),
    `.norm.weight` (1) and `.out_proj` (`out_attr`'s initializer where
    given, else `param_attr`'s). The two drawn per-head vectors follow the program's
    `random_seed` and the layer's name."""
    helper = LayerHelper("mamba2", param_attr=param_attr, name=name)
    name = name or helper.name
    c = input.shape[-1]
    h, p, g, n = (int(num_heads), int(head_dim), int(n_groups),
                  int(state_size))
    if h % g:
        raise ValueError(f"mamba2: {h} heads in {g} groups")
    d_in, conv_dim = h * p, h * p + 2 * g * n
    rng = np.random.default_rng(
        [int(helper.main_program.random_seed or 0),
         zlib.crc32(name.encode())])
    dt = np.maximum(np.exp(rng.uniform(np.log(dt_min), np.log(dt_max), h)),
                    dt_floor)
    bound = 1.0 / np.sqrt(conv_kernel)

    def vector(suffix, shape, init):
        return helper.create_parameter(
            ParamAttr(name=f"{name}.{suffix}"), shape=shape, dtype="float32",
            default_initializer=init)

    inputs = {
        "X": [input],
        "InW": [_named_param(helper, param_attr, name, "in_proj",
                             [c, d_in + conv_dim + h], "float32")],
        "ConvW": [vector("conv1d.weight", [int(conv_kernel), conv_dim],
                         UniformInitializer(-bound, bound))],
        "ConvB": [vector("conv1d.bias", [conv_dim],
                         UniformInitializer(-bound, bound))],
        # softplus^-1(dt) = dt + log(1 - exp(-dt))
        "DtBias": [vector("dt_bias", [h], NumpyArrayInitializer(
            dt + np.log(-np.expm1(-dt))))],
        "ALog": [vector("A_log", [h], NumpyArrayInitializer(
            np.log(rng.uniform(1.0, 16.0, h))))],
        "D": [vector("D", [h], ConstantInitializer(1.0))],
        "NormW": [vector("norm.weight", [d_in], ConstantInitializer(1.0))],
        "OutW": [_named_param(helper, out_attr or param_attr, name,
                              "out_proj", [d_in, c], "float32")],
    }
    out = helper.create_variable_for_type_inference(input.dtype)
    min_decay = helper.create_variable_for_type_inference(
        "float32", stop_gradient=True)
    helper.append_op(
        type="mamba2", inputs=inputs,
        outputs={"Out": [out], "MinDecay": [min_decay]},
        attrs={"num_heads": h, "head_dim": p, "n_groups": g, "state_size": n,
               "chunk_size": int(chunk_size), "epsilon": float(epsilon)})
    return out, min_decay


def mla(input, num_heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
        qk_rope_head_dim, v_head_dim, softmax_scale, epsilon=1e-6,
        theta=10000.0, inv_freq=None, param_attr=None, name=None):
    """Causal multi-head latent attention (ops/latent_ops.py), the whole
    sublayer, for x [B, S, C], as DeepSeek-V2/V3 publish it:

        c_q = RMSNorm(x W_qa)                       [q_lora_rank]
        [q_nope_h, q_rope_h] = c_q W_qb             a head: 128 + 64
        [c_kv, k_rope] = x W_kva                    [kv_lora_rank], [64]
        [k_nope_h, v_h] = RMSNorm(c_kv) W_kvb       a head: 128 + 128
        q_h = [q_nope_h, R(q_rope_h)],  k_h = [k_nope_h, R(k_rope)]
        out = concat_h(softmax(q_h . k_h * softmax_scale, causal) v_h) W_o

    R is the rotate-half rotary embedding at theta^(-2i/d) or at the
    caller's `inv_freq` table; the one k_rope serves every head.
    `num_heads` is the number of heads held here: W_qb and W_kvb have
    their columns, W_o their rows, and the result is their part of the sum
    over heads. Parameters `<name>.q_a_proj`, `.q_a_layernorm`, `.q_b_proj`,
    `.kv_a_proj`, `.kv_a_layernorm`, `.kv_b_proj`, `.o_proj`."""
    helper = LayerHelper("mla", param_attr=param_attr, name=name)
    name = name or helper.name
    c = input.shape[-1]
    nh, nope, rot, dv = (int(num_heads), int(qk_nope_head_dim),
                         int(qk_rope_head_dim), int(v_head_dim))

    def matrix(suffix, shape):
        return _named_param(helper, param_attr, name, suffix, shape, "float32")

    def norm(suffix, width):
        return helper.create_parameter(
            ParamAttr(name=f"{name}.{suffix}"), shape=[width],
            dtype="float32", default_initializer=ConstantInitializer(1.0))

    inputs = {
        "X": [input],
        "QA": [matrix("q_a_proj", [c, int(q_lora_rank)])],
        "QANorm": [norm("q_a_layernorm", int(q_lora_rank))],
        "QB": [matrix("q_b_proj", [int(q_lora_rank), nh * (nope + rot)])],
        "KVA": [matrix("kv_a_proj", [c, int(kv_lora_rank) + rot])],
        "KVANorm": [norm("kv_a_layernorm", int(kv_lora_rank))],
        "KVB": [matrix("kv_b_proj", [int(kv_lora_rank), nh * (nope + dv)])],
        "O": [matrix("o_proj", [nh * dv, c])],
    }
    attrs = {"num_heads": nh, "qk_nope_head_dim": nope,
             "qk_rope_head_dim": rot, "v_head_dim": dv,
             "softmax_scale": float(softmax_scale),
             "epsilon": float(epsilon), "theta": float(theta)}
    if inv_freq is not None:
        attrs["inv_freq"] = [float(f) for f in inv_freq]
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type="mla", inputs=inputs, outputs={"Out": [out]},
                     attrs=attrs)
    return out


def mhc_map(streams_in, streams, epsilon=1e-6, sinkhorn_iters=20,
            clamp_min=-30.0, clamp_max=30.0, alpha_init=0.01,
            param_attr=None, bias_attr=None, name=None):
    """The three mappings of one sublayer under manifold-constrained
    hyper-connections (ops/latent_ops.py), from the n = `streams` residual
    streams X [B, S, n*C] (stream j the columns j*C .. (j+1)*C), a token
    at a time and in float32:

        xbar   = vec(X) / sqrt(mean(vec(X)^2) + epsilon)
        H_pre  = sigmoid(a_pre xbar phi_pre + b_pre)            [n]
        H_post = 2 sigmoid(a_post xbar phi_post + b_post)       [n]
        H_res  = Sinkhorn(exp(clamp(a_res mat(xbar phi_res) + b_res)))  [n, n]

    Sinkhorn: `sinkhorn_iters` rounds of rows over their sums, then columns
    over theirs. Returns (H_pre [B, S, n], H_post [B, S, n], H_res [B, S,
    n*n] row-major, gap [n]: the worst distance of row i's or column i's
    sum from 1 over the tokens, fetchable to see whether the rounds
    sufficed). Parameters `<name>.phi` [n*C, 2n + n*n], `<name>.b`
    [2n + n*n] (both in the order pre, post, res) and `<name>.alpha` [3]."""
    helper = LayerHelper("mhc_map", param_attr=param_attr, name=name)
    name = name or helper.name
    n = int(streams)
    width = 2 * n + n * n
    phi = _named_param(helper, param_attr, name, "phi",
                       [streams_in.shape[-1], width], "float32")
    bias = _named_param(helper, bias_attr, name, "b", [width], "float32",
                        default_initializer=ConstantInitializer(0.0))
    alpha = helper.create_parameter(
        ParamAttr(name=f"{name}.alpha"), shape=[3], dtype="float32",
        default_initializer=ConstantInitializer(float(alpha_init)))
    outs = {k: helper.create_variable_for_type_inference("float32")
            for k in ("HPre", "HPost", "HRes")}
    gap = helper.create_variable_for_type_inference("float32",
                                                    stop_gradient=True)
    helper.append_op(
        type="mhc_map",
        inputs={"X": [streams_in], "Phi": [phi], "Bias": [bias],
                "Alpha": [alpha]},
        outputs={**{k: [v] for k, v in outs.items()}, "SinkhornGap": [gap]},
        attrs={"streams": n, "epsilon": float(epsilon),
               "sinkhorn_iters": int(sinkhorn_iters),
               "clamp_min": float(clamp_min), "clamp_max": float(clamp_max)})
    return outs["HPre"], outs["HPost"], outs["HRes"], gap


def mhc_pre(streams_in, h_pre=None, streams=None, name=None):
    """u = H_pre X: a sublayer's input [B, S, C] out of the n residual
    streams [B, S, n*C]. Without `h_pre`, the plain sum of the `streams`
    streams (the model's readout)."""
    helper = LayerHelper("mhc_pre", name=name)
    out = helper.create_variable_for_type_inference(streams_in.dtype)
    inputs = {"X": [streams_in]}
    if h_pre is not None:
        inputs["HPre"] = [h_pre]
        streams = h_pre.shape[-1]
    helper.append_op(type="mhc_pre", inputs=inputs, outputs={"Out": [out]},
                     attrs={"streams": int(streams)})
    return out


def mhc_post(streams_in, y, h_res, h_post, name=None):
    """X' = H_res X + H_post^T y: the streams after a sublayer whose output
    is y [B, S, C]."""
    helper = LayerHelper("mhc_post", name=name)
    out = helper.create_variable_for_type_inference(streams_in.dtype)
    helper.append_op(
        type="mhc_post",
        inputs={"X": [streams_in], "Y": [y], "HRes": [h_res],
                "HPost": [h_post]},
        outputs={"Out": [out]})
    return out


def moe_swiglu(
    input,
    num_experts,
    expert_hidden,
    experts_held=None,
    first_expert=0,
    top_k=4,
    norm_topk_prob=True,
    routed_scaling_factor=1.0,
    remat=False,
    bias_update_rate=0.0,
    param_attr=None,
    bias_attr=None,
    name=None,
    activation="swiglu",
):
    """Dropless, bias-routed mixture of SwiGLU experts that is told which
    experts it holds (ops/moe_ops.py). For a token z, over a router of
    `num_experts` outputs:

        s = sigmoid(W_g z)                         float32
        I = top-k of (s + b)                       b: a buffer, not trained
        g_i = s_i / (sum_{j in I} s_j + 1e-6) * routed_scaling_factor
                                                   (without norm_topk_prob: g_i = s_i)
        y = sum_{i in I, i held here} g_i W2_i (silu(W1_i z) * W3_i z)

    With `activation="relu2"` an expert is two matrices and not three,
    W2_i relu(W1_i z)^2, and there is no `<name>.w3`; everything around
    the experts is the same code.

    This layer holds experts `first_expert .. first_expert + experts_held
    - 1` (all of them by default): its weights are [experts_held, ...], the
    router keeps its published width, and the result is the held experts'
    part of the layer's output; the shares of all holders add up to the
    whole layer. No capacity and no dropped token at any imbalance: the
    (token, pick) pairs on held experts are sorted by expert and each
    projection is one grouped product over the rows present.

    `bias_update_rate` u > 0 makes the layer keep its experts balanced as a
    trainer of such models does, without an auxiliary loss: after each
    step's picks, b_e += u * sign(mean load - load_e) for every expert of
    the router, written back into the buffer for the next step (0: the
    buffer is left alone, as at inference).

    input: [B, S, H]. Returns (out [B, S, H], tokens_per_expert
    [experts_held] int32: the rows each held expert received this step,
    fetchable like any variable). Parameters `<name>.gate`,
    `<name>.expert_bias` (persistable, no gradient), `<name>.w1`,
    `<name>.w3`, `<name>.w2`."""
    helper = LayerHelper("moe_swiglu", param_attr=param_attr, name=name)
    name = name or helper.name
    h = input.shape[-1]
    held = int(num_experts if experts_held is None else experts_held)
    f = int(expert_hidden)
    gate_w = _named_param(helper, param_attr, name, "gate",
                          [h, int(num_experts)], "float32")
    bias = _named_param(helper, bias_attr, name, "expert_bias",
                        [int(num_experts)], "float32",
                        default_initializer=ConstantInitializer(0.0),
                        trainable=False)
    bias.stop_gradient = True
    inputs = {"X": [input], "GateW": [gate_w], "ExpertBias": [bias],
              "W1": [_named_param(helper, param_attr, name, "w1",
                                  [held, h, f], "float32")]}
    if _expert_form(activation):
        inputs["W3"] = [_named_param(helper, param_attr, name, "w3",
                                     [held, h, f], "float32")]
    inputs["W2"] = [_named_param(helper, param_attr, name, "w2",
                                 [held, f, h], "float32")]
    out = helper.create_variable_for_type_inference(input.dtype)
    counts = helper.create_variable_for_type_inference("int32",
                                                       stop_gradient=True)
    helper.append_op(
        type="moe_swiglu",
        inputs=inputs,
        # the buffer is read by the selection and written by the balancing
        # rule: one persistable variable, updated in place every step
        outputs={"Out": [out], "TokensPerExpert": [counts],
                 "ExpertBiasOut": [bias]},
        attrs={
            "top_k": int(top_k),
            "bias_update_rate": float(bias_update_rate),
            "norm_topk_prob": bool(norm_topk_prob),
            "routed_scaling_factor": float(routed_scaling_factor),
            "first_expert": int(first_expert),
            "remat": bool(remat),
        },
    )
    return out, counts


def unique_name_layer():  # pragma: no cover - placeholder parity stub
    raise NotImplementedError


def cos_sim(X, Y, name=None):
    """Row-wise cosine similarity (reference layers cos_sim)."""
    helper = LayerHelper("cos_sim", name=name)
    out = helper.create_variable_for_type_inference(X.dtype)
    xn = helper.create_variable_for_type_inference(X.dtype)
    yn = helper.create_variable_for_type_inference(X.dtype)
    helper.append_op(
        type="cos_sim", inputs={"X": [X], "Y": [Y]},
        outputs={"Out": [out], "XNorm": [xn], "YNorm": [yn]},
    )
    return out
