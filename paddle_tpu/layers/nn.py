"""Graph-building NN layers (reference python/paddle/fluid/layers/nn.py).

Each function appends IR ops to the current program and returns the output
Variable(s); in dygraph mode the same calls trace eagerly.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from ..framework.core import Variable, in_dygraph_mode
from ..framework.initializer import ConstantInitializer, NormalInitializer
from ..framework.layer_helper import LayerHelper

__all__ = [
    "fc", "fc_valid_rows", "swiglu_valid_rows", "conv2d", "conv2d_transpose",
    "pool2d", "batch_norm", "layer_norm",
    "group_norm", "instance_norm", "embedding", "dropout", "relu", "softmax",
    "log_softmax", "sigmoid", "tanh", "gelu", "leaky_relu", "relu6", "elu",
    "swish", "hard_sigmoid", "hard_swish", "prelu", "matmul", "bmm", "mul",
    "one_hot", "topk", "flatten", "l2_normalize", "label_smooth", "maxout",
    "soft_relu", "log_loss", "clip", "clip_by_norm", "mean", "pad",
    "adaptive_pool2d", "flash_attention", "flash_attention_qkv",
    "rms_norm", "rope",
    "cached_attention", "chunk_attention", "kv_pool_write",
    "kv_pool_gather",
    "paged_decode_attention", "latent_prefill_attention",
    "latent_decode_attention", "latent_chunk_attention", "block_begin",
    "block_unmask",
    "short_conv", "short_conv_tail", "slot_state_write", "short_conv_step",
    "gated_delta_chunk", "gated_delta_step", "ssd_chunk", "ssd_step",
    "linear_chain_crf", "crf_decoding", "warpctc",
    "nce", "hsigmoid", "conv3d", "pool3d", "lrn", "row_conv",
    "shuffle_channel", "temporal_shift", "multiplex",
    "silu", "mish",
    "exp", "log", "sqrt", "square", "reciprocal", "softplus",
    "softsign", "sin", "cos", "erf", "ceil", "floor", "round", "abs",
    "resize_bilinear", "resize_nearest", "pixel_shuffle",
    "cos_sim", "pad2d", "expand_as", "crop_tensor", "crop",
    "pad_constant_like", "image_resize", "space_to_depth", "norm",
    "dist", "py_func", "moe_ffn", "moe_routed_ffn",
]


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, name=None, out_dtype=None):
    """Fully-connected layer (reference layers/nn.py:295 `fc`): flattens
    input to 2-D at num_flatten_dims, matmuls against a [in, size] weight.
    ``out_dtype`` "float32" on two-byte rows: the product's float32 sum
    itself comes back, not rounded to the rows' dtype (op ``mul``)."""
    helper = LayerHelper("fc", name=name)
    inputs = input if isinstance(input, (list, tuple)) else [input]
    mul_results = []
    for x in inputs:
        in_features = int(np.prod(x.shape[num_flatten_dims:]))
        w = helper.create_parameter(param_attr, [in_features, size], x.dtype)
        out = helper.create_variable_for_type_inference(out_dtype or x.dtype)
        attrs = {"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1}
        # (an attr only where asked for: the other programs' text stays)
        if out_dtype:
            attrs["out_dtype"] = out_dtype
        helper.append_op("mul", inputs={"X": [x], "Y": [w]},
                         outputs={"Out": [out]}, attrs=attrs)
        mul_results.append(out)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(inputs[0].dtype)
        helper.append_op("sum", inputs={"X": mul_results},
                         outputs={"Out": [pre_bias]})
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [size], pre_bias.dtype,
                                    is_bias=True)
        pre_act = helper.create_variable_for_type_inference(pre_bias.dtype)
        helper.append_op("elementwise_add",
                         inputs={"X": [pre_bias], "Y": [b]},
                         outputs={"Out": [pre_act]},
                         attrs={"axis": num_flatten_dims})
    else:
        pre_act = pre_bias
    return helper.append_activation(pre_act, act)


def fc_valid_rows(input, size, valid_rows, param_attr=None, name=None,
                  segment=None):
    """``fc(input, size, num_flatten_dims=2, bias_attr=False)`` on rows
    ``input`` [1, S, K] of which only the first ``valid_rows[0]`` hold
    anything (a prompt padded to its rung): the product runs over the
    segments of rows that hold one of them and the rows behind are zero
    (op ``mul_valid_rows``), ``segment`` rows at a time (None:
    ``ops/math_ops.py`` ``VALID_ROW_SEGMENT``).  The weight is ``fc``'s,
    under its name."""
    from ..ops.math_ops import VALID_ROW_SEGMENT

    helper = LayerHelper("fc", name=name)
    w = helper.create_parameter(param_attr, [int(input.shape[2]), size],
                                input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("mul_valid_rows",
                     inputs={"X": [input], "Y": [w],
                             "ValidRows": [valid_rows]},
                     outputs={"Out": [out]},
                     attrs={"segment": int(segment or VALID_ROW_SEGMENT)})
    return out


def swiglu_valid_rows(input, width, size, valid_rows, gate_up_attr=None,
                      down_attr=None, limit=None, name=None, segment=None):
    """``fc(silu(gate) * up, size)`` with ``gate | up = fc(input, 2 *
    width)`` (no biases; with ``limit`` L the gate held under L and the up
    to [-L, L]) on rows ``input`` [1, S, K] of which only the first
    ``valid_rows[0]`` hold anything: both products run a segment of rows
    at a time over the segments that hold one of them, the rows behind are
    zero and no [S, 2 * width] is held (op ``swiglu_valid_rows``;
    ``segment``: :func:`fc_valid_rows`'s).  The weights are the two
    ``fc``s', under their names."""
    from ..ops.math_ops import VALID_ROW_SEGMENT

    helper = LayerHelper("fc", name=name)
    gate_up = helper.create_parameter(
        gate_up_attr, [int(input.shape[2]), 2 * width], input.dtype)
    down = helper.create_parameter(down_attr, [width, size], input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    attrs = {"segment": int(segment or VALID_ROW_SEGMENT)}
    if limit is not None:
        attrs["limit"] = float(limit)
    helper.append_op("swiglu_valid_rows",
                     inputs={"X": [input], "GateUp": [gate_up],
                             "Down": [down], "ValidRows": [valid_rows]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None, data_format="NCHW"):
    """reference layers/nn.py conv2d; filter layout OIHW."""
    helper = LayerHelper("conv2d", name=name)
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    if isinstance(stride, int):
        stride = [stride, stride]
    if isinstance(dilation, int):
        dilation = [dilation, dilation]
    if isinstance(padding, int):
        padding = [padding, padding]
    c_in = input.shape[1] if data_format == "NCHW" else input.shape[-1]
    w_shape = [num_filters, c_in // groups] + list(filter_size)
    fan_in = (c_in // groups) * filter_size[0] * filter_size[1]
    std = (2.0 / fan_in) ** 0.5
    w = helper.create_parameter(
        param_attr, w_shape, input.dtype,
        default_initializer=NormalInitializer(0.0, std))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("conv2d",
                     inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [out]},
                     attrs={"strides": stride, "paddings": padding,
                            "dilations": dilation, "groups": groups,
                            "data_format": data_format})
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [num_filters], input.dtype,
                                    is_bias=True)
        pre_act = helper.create_variable_for_type_inference(input.dtype)
        helper.append_op("elementwise_add", inputs={"X": [out], "Y": [b]},
                         outputs={"Out": [pre_act]},
                         attrs={"axis": 1 if data_format == "NCHW" else 3})
    else:
        pre_act = out
    return helper.append_activation(pre_act, act)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None, data_format="NCHW"):
    helper = LayerHelper("conv2d_transpose", name=name)
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    if isinstance(stride, int):
        stride = [stride, stride]
    if isinstance(dilation, int):
        dilation = [dilation, dilation]
    if isinstance(padding, int):
        padding = [padding, padding]
    c_in = input.shape[1] if data_format == "NCHW" else input.shape[-1]
    w_shape = [c_in, num_filters // groups] + list(filter_size)
    w = helper.create_parameter(param_attr, w_shape, input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    attrs = {"strides": stride, "paddings": padding, "dilations": dilation,
             "groups": groups, "data_format": data_format}
    if output_size:
        attrs["output_size"] = list(output_size) \
            if isinstance(output_size, (list, tuple)) else [output_size] * 2
    helper.append_op("conv2d_transpose",
                     inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [out]}, attrs=attrs)
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [num_filters], input.dtype,
                                    is_bias=True)
        pre = helper.create_variable_for_type_inference(input.dtype)
        helper.append_op("elementwise_add", inputs={"X": [out], "Y": [b]},
                         outputs={"Out": [pre]},
                         attrs={"axis": 1 if data_format == "NCHW" else 3})
    else:
        pre = out
    return helper.append_activation(pre, act)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, name=None, exclusive=True,
           data_format="NCHW"):
    helper = LayerHelper("pool2d", name=name)
    if isinstance(pool_size, int):
        pool_size = [pool_size, pool_size]
    if isinstance(pool_stride, int):
        pool_stride = [pool_stride, pool_stride]
    if isinstance(pool_padding, int):
        pool_padding = [pool_padding, pool_padding]
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("pool2d", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"pooling_type": pool_type, "ksize": pool_size,
                            "strides": pool_stride,
                            "paddings": pool_padding,
                            "global_pooling": global_pooling,
                            "ceil_mode": ceil_mode, "exclusive": exclusive,
                            "data_format": data_format})
    return out


def adaptive_pool2d(input, pool_size, pool_type="max", name=None):
    helper = LayerHelper("adaptive_pool2d", name=name)
    if isinstance(pool_size, int):
        pool_size = [pool_size, pool_size]
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("pool2d", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"pooling_type": pool_type, "ksize": pool_size,
                            "adaptive": True})
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               name=None, moving_mean_name=None, moving_variance_name=None,
               do_model_average_for_mean_and_var=True,
               use_global_stats=False):
    """reference layers/nn.py batch_norm; running stats are persistable
    state vars threaded through the compiled step."""
    helper = LayerHelper("batch_norm", name=name)
    c = (input.shape[1] if data_layout == "NCHW" else input.shape[-1])
    dtype = "float32"
    scale = helper.create_parameter(
        param_attr, [c], dtype,
        default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(bias_attr, [c], dtype, is_bias=True)
    from ..framework.core import default_main_program, unique_name
    gb = helper.main_program.global_block()
    mean_name = moving_mean_name or unique_name(f"{helper.name}.mean")
    var_name = moving_variance_name or unique_name(f"{helper.name}.var")
    mean = gb.create_var(name=mean_name, shape=[c], dtype=dtype,
                         persistable=True, stop_gradient=True)
    variance = gb.create_var(name=var_name, shape=[c], dtype=dtype,
                             persistable=True, stop_gradient=True)
    ConstantInitializer(0.0)(mean, helper.startup_program.global_block())
    ConstantInitializer(1.0)(variance, helper.startup_program.global_block())
    saved_mean = helper.create_variable_for_type_inference(dtype)
    saved_var = helper.create_variable_for_type_inference(dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "batch_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias],
                "Mean": [mean], "Variance": [variance]},
        outputs={"Y": [out], "MeanOut": [mean], "VarianceOut": [variance],
                 "SavedMean": [saved_mean], "SavedVariance": [saved_var]},
        attrs={"momentum": momentum, "epsilon": epsilon,
               "is_test": is_test, "data_layout": data_layout,
               "use_global_stats": use_global_stats})
    return helper.append_activation(out, act)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    helper = LayerHelper("layer_norm", name=name)
    n = int(np.prod(input.shape[begin_norm_axis:]))
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(
            param_attr, [n], "float32",
            default_initializer=ConstantInitializer(1.0))
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(bias_attr, [n], "float32", is_bias=True)
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(input.dtype)
    mean = helper.create_variable_for_type_inference("float32")
    var = helper.create_variable_for_type_inference("float32")
    helper.append_op("layer_norm", inputs=inputs,
                     outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
                     attrs={"epsilon": epsilon,
                            "begin_norm_axis": begin_norm_axis})
    return helper.append_activation(out, act)


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, data_layout="NCHW", name=None):
    helper = LayerHelper("group_norm", name=name)
    c = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    inputs = {"X": [input]}
    if param_attr is not False:
        s = helper.create_parameter(
            param_attr, [c], "float32",
            default_initializer=ConstantInitializer(1.0))
        inputs["Scale"] = [s]
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [c], "float32", is_bias=True)
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(input.dtype)
    mean = helper.create_variable_for_type_inference("float32")
    var = helper.create_variable_for_type_inference("float32")
    helper.append_op("group_norm", inputs=inputs,
                     outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
                     attrs={"groups": groups, "epsilon": epsilon,
                            "data_layout": data_layout})
    return helper.append_activation(out, act)


def instance_norm(input, epsilon=1e-5, param_attr=None, bias_attr=None,
                  name=None):
    helper = LayerHelper("instance_norm", name=name)
    c = input.shape[1]
    inputs = {"X": [input]}
    if param_attr is not False:
        s = helper.create_parameter(
            param_attr, [c], "float32",
            default_initializer=ConstantInitializer(1.0))
        inputs["Scale"] = [s]
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [c], "float32", is_bias=True)
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(input.dtype)
    sm = helper.create_variable_for_type_inference("float32")
    sv = helper.create_variable_for_type_inference("float32")
    helper.append_op("instance_norm", inputs=inputs,
                     outputs={"Y": [out], "SavedMean": [sm],
                              "SavedVariance": [sv]},
                     attrs={"epsilon": epsilon})
    return out


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32",
              name=None):
    """reference layers/nn.py embedding -> lookup_table_v2."""
    helper = LayerHelper("embedding", name=name)
    w = helper.create_parameter(param_attr, list(size), dtype)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("lookup_table_v2",
                     inputs={"W": [w], "Ids": [input]},
                     outputs={"Out": [out]},
                     attrs={"padding_idx": -1 if padding_idx is None
                            else padding_idx,
                            "is_sparse": is_sparse,
                            "is_distributed": is_distributed})
    return out


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    # no Mask output: nothing consumes it (grads are vjp-derived with
    # deterministic per-op RNG replay, not Mask-replay like the
    # reference dropout_grad)
    helper.append_op("dropout", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"dropout_prob": dropout_prob, "is_test": is_test,
                            "seed": seed or 0,
                            "dropout_implementation": dropout_implementation})
    return out


def _unary(op_type):
    def f(x, name=None, **attrs):
        helper = LayerHelper(op_type, name=name)
        out = helper.create_variable_for_type_inference(x.dtype)
        helper.append_op(op_type, inputs={"X": [x]},
                         outputs={"Out": [out]}, attrs=attrs)
        return out
    f.__name__ = op_type
    return f


relu = _unary("relu")
sigmoid = _unary("sigmoid")
tanh = _unary("tanh")
gelu = _unary("gelu")
relu6 = _unary("relu6")
elu = _unary("elu")
swish = _unary("swish")
hard_sigmoid = _unary("hard_sigmoid")
hard_swish = _unary("hard_swish")
exp = _unary("exp")
log = _unary("log")
sqrt = _unary("sqrt")
square = _unary("square")
abs = _unary("abs")
ceil = _unary("ceil")
floor = _unary("floor")
round = _unary("round")
reciprocal = _unary("reciprocal")
softplus = _unary("softplus")
softsign = _unary("softsign")
sin = _unary("sin")
cos = _unary("cos")
erf = _unary("erf")


def soft_relu(x, threshold=40.0, name=None):
    helper = LayerHelper("soft_relu", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("softplus", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def leaky_relu(x, alpha=0.02, name=None):
    helper = LayerHelper("leaky_relu", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("leaky_relu", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"alpha": alpha})
    return out


def prelu(x, mode="all", param_attr=None, name=None):
    helper = LayerHelper("prelu", name=name)
    if mode == "all":
        alpha_shape = [1]
    elif mode == "channel":
        alpha_shape = [x.shape[1]]
    else:
        alpha_shape = list(x.shape[1:])
    alpha = helper.create_parameter(
        param_attr, alpha_shape, x.dtype,
        default_initializer=ConstantInitializer(0.25))
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("prelu", inputs={"X": [x], "Alpha": [alpha]},
                     outputs={"Out": [out]}, attrs={"mode": mode})
    return out


def softmax(input, axis=-1, name=None, use_cudnn=False):
    helper = LayerHelper("softmax", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("softmax", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs={"axis": axis})
    return out


def log_softmax(input, axis=-1, name=None):
    helper = LayerHelper("log_softmax", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("log_softmax", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs={"axis": axis})
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None,
           out_dtype=None):
    """``out_dtype``: :func:`fc`'s."""
    helper = LayerHelper("matmul", name=name)
    out = helper.create_variable_for_type_inference(out_dtype or x.dtype)
    attrs = {"transpose_X": transpose_x, "transpose_Y": transpose_y,
             "alpha": alpha}
    if out_dtype:
        attrs["out_dtype"] = out_dtype
    helper.append_op("matmul", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def bmm(x, y, name=None):
    helper = LayerHelper("bmm", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("bmm", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]})
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("mul", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]},
                     attrs={"x_num_col_dims": x_num_col_dims,
                            "y_num_col_dims": y_num_col_dims})
    return out


def one_hot(input, depth, allow_out_of_range=False):
    helper = LayerHelper("one_hot")
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op("one_hot_v2", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs={"depth": depth})
    return out


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    values = helper.create_variable_for_type_inference(input.dtype)
    indices = helper.create_variable_for_type_inference("int64")
    helper.append_op("top_k", inputs={"X": [input]},
                     outputs={"Out": [values], "Indices": [indices]},
                     attrs={"k": k})
    return values, indices


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("flatten2", inputs={"X": [x]},
                     outputs={"Out": [out], "XShape": [xshape]},
                     attrs={"axis": axis})
    return out


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    norm = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("norm", inputs={"X": [x]},
                     outputs={"Out": [out], "Norm": [norm]},
                     attrs={"axis": 1 if axis is None else axis,
                            "epsilon": epsilon})
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    helper = LayerHelper("label_smooth", name=name)
    inputs = {"X": [label]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist]
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("label_smooth", inputs=inputs,
                     outputs={"Out": [out]}, attrs={"epsilon": epsilon})
    return out


def maxout(x, groups, name=None, axis=1):
    helper = LayerHelper("maxout", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("maxout", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"groups": groups, "axis": axis})
    return out


def log_loss(input, label, epsilon=1e-4, name=None):
    helper = LayerHelper("log_loss", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("bce_loss", inputs={"X": [input], "Label": [label]},
                     outputs={"Out": [out]})
    return out


def clip(x, min, max, name=None):
    helper = LayerHelper("clip", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("clip", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"min": min, "max": max})
    return out


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper("clip_by_norm", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("clip_by_norm", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"max_norm": max_norm})
    return out


def mean(x, name=None):
    helper = LayerHelper("mean", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("mean", inputs={"X": [x]}, outputs={"Out": [out]})
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper("pad", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("pad", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"paddings": paddings, "pad_value": pad_value})
    return out


def flash_attention(q, k, v, bias=None, causal=False, scale=None,
                    seq_parallel_mode="ring", impl="auto", layout="bhsd",
                    dropout_prob=0.0, is_test=False, name=None,
                    window=None, mask_block=None, precision=None,
                    softmax_float32=False):
    """Fused multi-head attention; q/k/v: [B, H, S, D] (layout "bhsd")
    or [B, S, H, D] (layout "bshd", impl="xla" only).

    impl="auto": pallas TPU kernel, or ring/Ulysses attention when the
    sequence is sharded over the `sp` mesh axis (ops/attention_ops.py).
    impl="xla": einsum formulation (XLA-fused softmax chain; supports
    in-op probability dropout and the transpose-free bshd layout —
    fastest at short/moderate S on v5e).
    bias: optional additive score bias [B, S] (or [B,1,1,S]) — the padding
    mask, 0 = attend / -1e4 = pad.
    window: with ``causal``, query i attends keys j with
    ``i - window < j <= i`` (the window counts the token itself); None
    leaves the op exactly as it was.
    mask_block: with ``causal``, the block-causal mask of block
    diffusion: query i attends keys j with ``j // mask_block <= i //
    mask_block`` (causal across blocks, a block sees itself whole).
    precision: None leaves the two products (scores, and probabilities
    times values) at the backend's default, under which a TPU rounds
    float32 operands to bfloat16; "highest" feeds them whole, whatever
    the mask (forward only, no padding bias).
    softmax_float32: with two-byte q, k, v under impl="xla", the scores
    are the first product's float32 sum, the softmax runs on them in
    float32 and the probabilities are rounded where they enter the second
    product (what the kernels and the blockwise formulation do whatever
    they are given; without it that formulation's scores and softmax are
    the operands' dtype, as a mixed-precision training step has them).
    """
    helper = LayerHelper("flash_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    attrs = {"causal": causal, "seq_parallel_mode": seq_parallel_mode,
             "impl": impl, "layout": layout,
             "dropout_prob": float(dropout_prob), "is_test": is_test}
    if scale is not None:
        attrs["scale"] = float(scale)
    if window is not None:
        attrs["window"] = int(window)
    if mask_block is not None:
        attrs["mask_block"] = int(mask_block)
    if precision is not None:
        attrs["precision"] = str(precision)
    if softmax_float32:
        attrs["softmax_float32"] = True
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if bias is not None:
        inputs["Bias"] = [bias]
    outputs = {"Out": [out]}
    if impl != "xla":
        # the kernels' softmax statistic (log-sum-exp rows, float32):
        # written where the op lowers to them, read by its grad op
        outputs["SoftmaxLse"] = [
            helper.create_variable_for_type_inference("float32")]
    helper.append_op("flash_attention", inputs=inputs, outputs=outputs,
                     attrs=attrs)
    return out


def flash_attention_qkv(qkv, num_heads, bias=None, causal=False,
                        scale=None, name=None):
    """Transpose-free fused attention on a packed QKV projection.

    qkv: [B, S, 3H] (the fused projection output, heads contiguous per
    tensor), returns [B, S, H].  Lowers to the packed pallas kernels on
    TPU (ops/attention_ops.py flash_attention_qkv) — no
    [B,S,3H] <-> [B,h,S,d] layout traffic.  bias: optional [B, S]
    additive score rows (padding mask).
    """
    helper = LayerHelper("flash_attention_qkv", name=name)
    out = helper.create_variable_for_type_inference(qkv.dtype)
    attrs = {"num_heads": int(num_heads), "causal": causal}
    if scale is not None:
        attrs["scale"] = float(scale)
    inputs = {"QKV": [qkv]}
    if bias is not None:
        inputs["Bias"] = [bias]
    # SoftmaxLse: as in flash_attention
    lse = helper.create_variable_for_type_inference("float32")
    helper.append_op("flash_attention_qkv", inputs=inputs,
                     outputs={"Out": [out], "SoftmaxLse": [lse]},
                     attrs=attrs)
    return out


silu = _unary("silu")
mish = _unary("mish")


def rms_norm(x, epsilon=1e-6, param_attr=None, name=None, group_size=None):
    """RMSNorm over the last dim (LLM configs; no fluid-era analog).
    ``group_size``: the mean of squares is taken over each run of that
    many consecutive channels apart (it divides the last dim); the learned
    weight is one a channel either way."""
    helper = LayerHelper("rms_norm", name=name)
    scale = helper.create_parameter(
        param_attr, [x.shape[-1]], "float32",
        default_initializer=ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference(x.dtype)
    attrs = {"epsilon": epsilon}
    # (an attr only where asked for: the other programs' text stays)
    if group_size and int(group_size) != int(x.shape[-1]):
        if int(x.shape[-1]) % int(group_size):
            raise ValueError(f"rms_norm: groups of {group_size} channels do "
                             f"not divide {x.shape[-1]}")
        attrs["group_size"] = int(group_size)
    helper.append_op("rms_norm", inputs={"X": [x], "Scale": [scale]},
                     outputs={"Y": [out]}, attrs=attrs)
    return out


def rope(x, base=10000.0, position_offset=0, offset=None, name=None,
         interleave=False, yarn=None):
    """Rotary position embedding; x: [B, H, S, D].

    ``offset``: optional [B] int Variable of per-row dynamic position
    offsets (cached decode: row b's S positions start at ``offset[b]``);
    the static ``position_offset`` attr applies when it is absent.
    ``interleave``: rotate the pairs ``(2i, 2i + 1)``, not ``(i, i + D /
    2)``.  ``yarn``: ``{"factor", "original_max", "beta_fast",
    "beta_slow"}``, YaRN's frequency table (``ops/rope_ops.py``
    ``yarn_inv_freq``)."""
    helper = LayerHelper("rope", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x]}
    if offset is not None:
        inputs["Offset"] = [offset]
    attrs = {"base": base, "position_offset": position_offset}
    # (an attr only where asked for: the other programs' text stays)
    if interleave:
        attrs["interleave"] = True
    if yarn:
        attrs["yarn"] = [float(yarn[k]) for k in (
            "factor", "original_max", "beta_fast", "beta_slow")]
    helper.append_op("rope", inputs=inputs, outputs={"Out": [out]},
                     attrs=attrs)
    return out


def kv_pool_write(pool, new, positions, block_table, lengths,
                  name=None, per_head=False, whole_pages=False):
    """Paged-cache write, in place: ``pool`` [P, Hkv, pt, D] gets row
    (b, t) of ``new`` [B, Hkv, T, D] at logical position
    ``positions[b] + t`` of slot b, routed through ``block_table``
    [B, NP] to a physical page; rows with ``t >= lengths[b]`` go to
    the reserved trash page 0.  The op's output is the pool variable
    itself, so the executor classifies the pool as mutated persistable
    state → donated buffer (HBM reused, no copy).  ``per_head`` scatters
    each (row, head) under its own index, as the one-row step always
    does: the form for the few rows a slot of a decode grid writes (a
    prefill chunk's many rows keep the [Hkv, D] window).  ``whole_pages``
    is the whole-prompt prefill's form: one slot (B = 1), ``positions`` a
    page boundary (the caller's word), T a whole number of pages; the
    rows go in page by page, the pool's other bytes as the row forms
    leave them, and on a TPU the pool is not re-laid for it.  Returns the
    pool Variable (now carrying the updated value in the lowered graph)."""
    helper = LayerHelper("kv_pool_write", name=name)
    # an attr only where asked for: the other programs' text stays the same
    attrs = {}
    if per_head:
        attrs["per_head"] = True
    if whole_pages:
        attrs["whole_pages"] = True
    helper.append_op("kv_pool_write",
                     inputs={"Pool": [pool], "New": [new],
                             "Positions": [positions],
                             "BlockTable": [block_table],
                             "Lengths": [lengths]},
                     outputs={"Out": [pool]},
                     attrs=attrs)
    return pool


def kv_pool_gather(pool, block_table, name=None, head_dim=None):
    """Gather a slot's pages back into the logical cache layout:
    ``pool`` [P, Hkv, pt, D] through ``block_table`` [B, NP] ->
    [B, Hkv, NP*pt, D] (column j = logical position j, exactly what
    :func:`cached_attention` contracts over).  ``head_dim``: the heads'
    own width where the pool packs two a row (ops/decode_ops.py
    ``pool_shape``); the view comes back unpacked."""
    helper = LayerHelper("kv_pool_gather", name=name)
    out = helper.create_variable_for_type_inference(pool.dtype)
    attrs = {}
    if head_dim is not None and int(head_dim) != int(pool.shape[-1]):
        attrs["head_dim"] = int(head_dim)
    helper.append_op("kv_pool_gather",
                     inputs={"Pool": [pool],
                             "BlockTable": [block_table]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def cached_attention(q, cache_k, cache_v, positions, scale=None,
                     name=None, window=None):
    """Decode-step attention over a KV cache: ``q`` [B, H, T, D]
    attends ``cache_k``/``cache_v`` [B, Hkv, S_max, D] with per-row
    validity ``j <= positions[b] + t`` (``positions`` [B] = pre-step
    sequence length).  GQA caches expand repeat-interleave style inside
    the op.  ``window`` adds the lower bound ``j > positions[b] + t -
    window``.  Returns [B, H, T, D]."""
    helper = LayerHelper("cached_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    attrs = {}
    if scale is not None:
        attrs["scale"] = float(scale)
    if window is not None:
        attrs["window"] = int(window)
    helper.append_op("cached_attention",
                     inputs={"Q": [q], "K": [cache_k], "V": [cache_v],
                             "Positions": [positions]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def chunk_attention(q, cache_k, cache_v, positions, scale=None, name=None,
                    window=None):
    """A prefill chunk's attention: ``q`` [B, H, C, D] at positions
    ``positions[b] + t`` over the gathered views ``cache_k`` / ``cache_v``
    [B, Hkv, S, D] (:func:`kv_pool_gather`; the chunk's own rows already
    written), :func:`cached_attention`'s rule and ``window``.  A TPU
    backend runs a Pallas kernel that never forms the ``[H, C, S]``
    scores nor repeats K / V to the query heads; any other runs
    :func:`cached_attention`'s formulation, bit for bit."""
    helper = LayerHelper("chunk_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    attrs = {}
    if scale is not None:
        attrs["scale"] = float(scale)
    if window is not None:
        attrs["window"] = int(window)
    helper.append_op("chunk_attention",
                     inputs={"Q": [q], "K": [cache_k], "V": [cache_v],
                             "Positions": [positions]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def paged_decode_attention(q, pool_k, pool_v, block_table, positions,
                           scale=None, name=None, window=None):
    """The paged decode step's attention: ``q`` [B, H, 1, D] (one new
    token per slot) attends pools ``pool_k``/``pool_v`` [P, Hkv, pt, D]
    through ``block_table`` [B, NP] at columns ``j <= positions[b]``.
    ``q`` [B, H, T, D] is a block of T rows at ``positions[b]`` whose
    rows all attend ``j <= positions[b] + T - 1`` (no window then).
    A TPU backend reads the live pages in place (Pallas kernel); any
    other runs :func:`kv_pool_gather` + :func:`cached_attention`'s
    formulation, bit for bit.  ``window`` bounds the columns below too
    (``j > positions[b] - window``): pages left of the window are never
    read, and their block-table entries may point at the trash page.
    Returns [B, H, 1, D]."""
    helper = LayerHelper("paged_decode_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    attrs = {}
    if scale is not None:
        attrs["scale"] = float(scale)
    if window is not None:
        attrs["window"] = int(window)
    helper.append_op("paged_decode_attention",
                     inputs={"Q": [q], "PoolK": [pool_k],
                             "PoolV": [pool_v],
                             "BlockTable": [block_table],
                             "Positions": [positions]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def latent_prefill_attention(q, k, v, scale, impl="auto", name=None):
    """A latent (MLA) layer's prefill attention, the expanded form: ``q``
    and ``k`` [B, H, S, nope + rope], ``v`` [B, H, S, v_dim], causal, keys
    wider than values (ops/latent_attention_ops.py).  Returns [B, H, S,
    v_dim]."""
    helper = LayerHelper("latent_prefill_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    attrs = {"scale": float(scale)}
    if impl != "auto":
        attrs["impl"] = impl
    helper.append_op("latent_prefill_attention",
                     inputs={"Q": [q], "K": [k], "V": [v]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def latent_decode_attention(q_nope, q_rope, w_kvb, pool, block_table,
                            positions, scale, value_dim, name=None):
    """A latent (MLA) layer's decode-step attention, the absorbed form:
    ``q_nope`` [B, H, 1, nope] and ``q_rope`` [B, H, 1, rope] over the
    latent pool [P, 1, pt, ROW] (rows ``[c_kv | k_r | 0]``) through
    ``block_table`` at columns ``j <= positions[b]``, with the layer's
    up-projection ``w_kvb`` [C, H * (nope + value_dim)] read as ``W_UK``
    and ``W_UV`` (ops/latent_attention_ops.py).  Returns [B, H, 1,
    value_dim]."""
    helper = LayerHelper("latent_decode_attention", name=name)
    out = helper.create_variable_for_type_inference(q_nope.dtype)
    helper.append_op("latent_decode_attention",
                     inputs={"QNope": [q_nope], "QRope": [q_rope],
                             "Wkvb": [w_kvb], "Pool": [pool],
                             "BlockTable": [block_table],
                             "Positions": [positions]},
                     outputs={"Out": [out]},
                     attrs={"scale": float(scale),
                            "value_dim": int(value_dim)})
    return out


def latent_chunk_attention(q_nope, q_rope, w_kvb, pool, block_table,
                           positions, lengths, scale, value_dim, name=None):
    """A latent (MLA) layer's prefill-chunk attention: ``q_nope`` [1, H, C,
    nope] and ``q_rope`` [1, H, C, rope], the chunk's rows at
    ``positions[0] + t``, over the slot's latent pages (the chunk's own
    rows already written; ``lengths`` [1] of them real) through
    ``block_table`` [1, NP], in the expanded arithmetic with ``w_kvb``
    expanding each cached row (ops/latent_attention_ops.py).  Returns
    [1, H, C, value_dim]."""
    helper = LayerHelper("latent_chunk_attention", name=name)
    out = helper.create_variable_for_type_inference(q_nope.dtype)
    helper.append_op("latent_chunk_attention",
                     inputs={"QNope": [q_nope], "QRope": [q_rope],
                             "Wkvb": [w_kvb], "Pool": [pool],
                             "BlockTable": [block_table],
                             "Positions": [positions],
                             "Lengths": [lengths]},
                     outputs={"Out": [out]},
                     attrs={"scale": float(scale),
                            "value_dim": int(value_dim)})
    return out


def block_begin(tokens, masked, fresh, mask_id, name=None):
    """Block diffusion (ops/decode_ops.py ``block_begin``): ``tokens``
    and ``masked`` [S, B] as the last pass left them, except where
    ``fresh`` [S] is set: there a new block, ``mask_id`` everywhere and
    every position undecided.  Returns ``(tokens, masked)``."""
    helper = LayerHelper("block_begin", name=name)
    t_out = helper.create_variable_for_type_inference(tokens.dtype)
    m_out = helper.create_variable_for_type_inference(masked.dtype)
    helper.append_op("block_begin",
                     inputs={"Tokens": [tokens], "Masked": [masked],
                             "Fresh": [fresh]},
                     outputs={"TokensOut": [t_out], "MaskedOut": [m_out]},
                     attrs={"mask_id": int(mask_id)})
    return t_out, m_out


def block_unmask(logits, tokens, masked, quota, name=None):
    """Block diffusion's unmasking (ops/decode_ops.py ``block_unmask``):
    of each slot's undecided positions (``masked`` [S, B] = 1) the
    ``quota`` [S] whose ``argmax(logits)`` is most confident take that
    token.  ``logits`` [S, B, V].  Returns ``(tokens, masked)``."""
    helper = LayerHelper("block_unmask", name=name)
    t_out = helper.create_variable_for_type_inference(tokens.dtype)
    m_out = helper.create_variable_for_type_inference(masked.dtype)
    helper.append_op("block_unmask",
                     inputs={"Logits": [logits], "Tokens": [tokens],
                             "Masked": [masked], "Quota": [quota]},
                     outputs={"TokensOut": [t_out], "MaskedOut": [m_out]})
    return t_out, m_out


def _short_conv_params(helper, hidden, kernel, param_attr, bias_attr, dtype):
    """The depthwise kernel [H, L] (Glorot over one channel's fan: L taps
    in, L out) and, where asked for, the bias [H]."""
    from ..framework.initializer import XavierInitializer

    inputs = {"W": [helper.create_parameter(
        param_attr, [hidden, int(kernel)], dtype,
        default_initializer=XavierInitializer(fan_in=int(kernel),
                                              fan_out=int(kernel)))]}
    if bias_attr:
        inputs["Bias"] = [helper.create_parameter(bias_attr, [hidden], dtype,
                                                  is_bias=True)]
    return inputs


def short_conv(x, kernel, param_attr=None, bias_attr=None, name=None):
    """Causal depthwise convolution of ``kernel`` taps over ``x``
    [B, S, H] with zero history (ops/decode_ops.py ``short_conv``): the
    whole-sequence form of a gated short-convolution mixer."""
    helper = LayerHelper("short_conv", name=name)
    inputs = _short_conv_params(helper, int(x.shape[-1]), kernel,
                                param_attr, bias_attr, x.dtype)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("short_conv", inputs=dict(inputs, X=[x]),
                     outputs={"Out": [out]})
    return out


def short_conv_tail(x, lengths, rows, name=None):
    """The ``rows`` rows of ``x`` [B, S, H] before position
    ``lengths[b]``, oldest first, zero where there are fewer: what a
    right-padded prompt leaves for the decode step's convolution."""
    helper = LayerHelper("short_conv_tail", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("short_conv_tail",
                     inputs={"X": [x], "Lengths": [lengths]},
                     outputs={"Out": [out]}, attrs={"rows": int(rows)})
    return out


def slot_state_write(state, rows, slot, name=None):
    """Per-slot state that is not pages, in place: ``state`` [slots + 1,
    R, H] gets ``rows`` [1, R, H] as the whole of row ``slot[0]`` (row
    ``slots`` is the trash row).  Returns the state Variable, donated
    like a page pool."""
    helper = LayerHelper("slot_state_write", name=name)
    helper.append_op("slot_state_write",
                     inputs={"State": [state], "Rows": [rows],
                             "Slot": [slot]},
                     outputs={"StateOut": [state]})
    return state


def short_conv_step(x, state, live, kernel, param_attr=None, bias_attr=None,
                    name=None):
    """One decode step of :func:`short_conv`: ``x`` [slots, 1, H] over
    ``state`` [slots + 1, kernel - 1, H]; the state moves on by one row
    for rows with ``live`` set, in place.  Returns the output
    [slots, 1, H]."""
    helper = LayerHelper("short_conv_step", name=name)
    inputs = _short_conv_params(helper, int(x.shape[-1]), kernel,
                                param_attr, bias_attr, x.dtype)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("short_conv_step",
                     inputs=dict(inputs, X=[x], State=[state], Live=[live]),
                     outputs={"Out": [out], "StateOut": [state]})
    return out


def gated_delta_chunk(q, k, v, g, beta, state0=None, valid=None, name=None):
    """The gated delta rule over a whole sequence (ops/gated_delta_ops.py
    ``gated_delta_chunk``): ``q``, ``k`` [B, T, H, Dk], ``v`` [B, T, H, Dv],
    log decay ``g`` and ``beta`` [B, T, H], optionally from ``state0``
    [B, H, Dk, Dv] and with ``valid`` [B] real rows.  Returns ``(out
    [B, T, H, Dv], state [B, H, Dk, Dv])``, the state after the last real
    token."""
    helper = LayerHelper("gated_delta_chunk", name=name)
    out = helper.create_variable_for_type_inference(v.dtype)
    state = helper.create_variable_for_type_inference(v.dtype)
    inputs = {"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta]}
    if state0 is not None:
        inputs["State0"] = [state0]
    if valid is not None:
        inputs["Valid"] = [valid]
    helper.append_op("gated_delta_chunk", inputs=inputs,
                     outputs={"Out": [out], "StateOut": [state]})
    return out, state


def gated_delta_step(q, k, v, g, beta, state, live, name=None):
    """One decode step of :func:`gated_delta_chunk`: one row a slot
    (``q``, ``k`` [slots, 1, H, Dk], ``v`` [slots, 1, H, Dv], ``g``,
    ``beta`` [slots, 1, H]) over ``state`` [slots + 1, H, Dk, Dv], which
    moves on in place for rows with ``live`` set.  Returns the output
    [slots, 1, H, Dv]."""
    helper = LayerHelper("gated_delta_step", name=name)
    out = helper.create_variable_for_type_inference(v.dtype)
    helper.append_op("gated_delta_step",
                     inputs={"Q": [q], "K": [k], "V": [v], "G": [g],
                             "Beta": [beta], "State": [state],
                             "Live": [live]},
                     outputs={"Out": [out], "StateOut": [state]})
    return out


def ssd_chunk(x, dt, a, bm, cm, d, state0=None, valid=None, name=None):
    """The state-space duality recurrence over a whole sequence
    (ops/ssd_ops.py ``ssd_chunk``): ``x`` [B, T, H, P], ``dt`` [B, T, H]
    (positive), ``a`` and ``d`` [H] (``a`` negative), ``bm``, ``cm``
    [B, T, N] (or [B, T, G, N]: G groups, head ``h`` reads group ``h //
    (H / G)``'s), optionally from ``state0`` [B, N, H * P] and with
    ``valid`` [B] real rows.  Returns ``(out [B, T, H, P], state [B, N, H *
    P])``,
    the state after the last real token."""
    helper = LayerHelper("ssd_chunk", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    state = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x], "Dt": [dt], "A": [a], "Bm": [bm], "Cm": [cm],
              "D": [d]}
    if state0 is not None:
        inputs["State0"] = [state0]
    if valid is not None:
        inputs["Valid"] = [valid]
    helper.append_op("ssd_chunk", inputs=inputs,
                     outputs={"Out": [out], "StateOut": [state]})
    return out, state


def ssd_step(x, dt, a, bm, cm, d, state, live, name=None):
    """One decode step of :func:`ssd_chunk`: one row a slot (``x``
    [slots, 1, H, P], ``dt`` [slots, 1, H], ``bm``, ``cm`` [slots, 1, N]
    or [slots, 1, G, N]) over ``state`` [slots + 1, N, H * P], which moves
    on in place for rows
    with ``live`` set.  Returns the output [slots, 1, H, P]."""
    helper = LayerHelper("ssd_step", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("ssd_step",
                     inputs={"X": [x], "Dt": [dt], "A": [a], "Bm": [bm],
                             "Cm": [cm], "D": [d], "State": [state],
                             "Live": [live]},
                     outputs={"Out": [out], "StateOut": [state]})
    return out


def resize_bilinear(input, out_shape=None, scale=None, name=None,
                    align_corners=True):
    """reference layers/nn.py resize_bilinear -> bilinear_interp op."""
    if out_shape is None and scale is None:
        raise ValueError("one of out_shape / scale is required")
    helper = LayerHelper("resize_bilinear", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    attrs = {"align_corners": align_corners}
    if out_shape is not None:
        attrs["out_h"], attrs["out_w"] = int(out_shape[0]), int(out_shape[1])
    if scale is not None:
        attrs["scale"] = float(scale)
    helper.append_op("bilinear_interp", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def resize_nearest(input, out_shape=None, scale=None, name=None,
                   align_corners=True):
    """reference layers/nn.py resize_nearest -> nearest_interp op."""
    if out_shape is None and scale is None:
        raise ValueError("one of out_shape / scale is required")
    helper = LayerHelper("resize_nearest", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    attrs = {"align_corners": align_corners}
    if out_shape is not None:
        attrs["out_h"], attrs["out_w"] = int(out_shape[0]), int(out_shape[1])
    if scale is not None:
        attrs["scale"] = float(scale)
    helper.append_op("nearest_interp", inputs={"X": [input]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def pixel_shuffle(x, upscale_factor, name=None):
    helper = LayerHelper("pixel_shuffle", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("pixel_shuffle", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"upscale_factor": int(upscale_factor)})
    return out


def cos_sim(X, Y, name=None):
    """Cosine similarity along the last dim (reference layers/nn.py
    cos_sim -> cos_sim_op): composition over existing ops."""
    from .math_op_patch import binary
    from .tensor import _reduce_sum_dim

    def _dotl(a, b):
        return _reduce_sum_dim(binary(a, b, "elementwise_mul"),
                               len(a.shape) - 1)

    num = _dotl(X, Y)
    den = sqrt(binary(_dotl(X, X), _dotl(Y, Y), "elementwise_mul"))
    return binary(num, den, "elementwise_div")


def pad2d(input, paddings=(0, 0, 0, 0), mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    """reference layers/nn.py pad2d: [top, bottom, left, right] on the
    spatial dims of NCHW."""
    if data_format != "NCHW":
        raise ValueError("pad2d: NHWC not supported; transpose first")
    helper = LayerHelper("pad2d", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("pad2d", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"paddings": list(paddings), "mode": mode,
                            "pad_value": float(pad_value)})
    return out


def expand_as(x, target_tensor, name=None):
    helper = LayerHelper("expand_as", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("expand_as_v2",
                     inputs={"X": [x], "Y": [target_tensor]},
                     outputs={"Out": [out]},
                     attrs={"target_shape": [int(d) for d in
                                             target_tensor.shape]})
    return out


def crop_tensor(x, shape=None, offsets=None, name=None):
    """Static crop (reference crop_tensor with list args); a shape entry
    of -1 crops to the end of that dim."""
    from .tensor import slice as _slice
    if shape is None:
        raise ValueError("crop_tensor: shape is required")
    offsets = offsets or [0] * len(shape)
    axes = list(range(len(shape)))
    starts = [int(o) for o in offsets]
    ends = []
    for d, (o, s) in enumerate(zip(offsets, shape)):
        if int(s) == -1:
            ends.append(int(x.shape[d]))
        else:
            ends.append(int(o) + int(s))
    return _slice(x, axes=axes, starts=starts, ends=ends)


crop = crop_tensor


def pad_constant_like(x, y, pad_value=0.0, name=None):
    """Pad y up to x's shape (reference pad_constant_like_op)."""
    pads = []
    for dx, dy in zip(x.shape, y.shape):
        pads += [0, int(dx) - int(dy)]
    return pad(y, pads, pad_value=pad_value, name=name)


def image_resize(input, out_shape=None, scale=None, resample="BILINEAR",
                 align_corners=True, name=None):
    """reference layers/nn.py image_resize dispatcher."""
    if resample.upper() == "BILINEAR":
        return resize_bilinear(input, out_shape, scale, name,
                               align_corners)
    if resample.upper() == "NEAREST":
        return resize_nearest(input, out_shape, scale, name,
                              align_corners)
    raise ValueError(f"unsupported resample {resample!r}")


def space_to_depth(x, blocksize, name=None):
    """reference space_to_depth_op: NCHW [B,C,H,W] ->
    [B, C*b*b, H/b, W/b] with the darknet-reorg element order
    (space_to_depth_op.h:39 index mapping — NOT the TF ordering)."""
    helper = LayerHelper("space_to_depth", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("space_to_depth", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"blocksize": int(blocksize)})
    return out


def norm(x, p=2, axis=-1, keepdim=False, name=None):
    helper = LayerHelper("p_norm", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("p_norm", inputs={"X": [x]}, outputs={"Out": [out]},
                     attrs={"porder": float(p), "axis": int(axis),
                            "keepdim": bool(keepdim), "epsilon": 1e-12})
    return out


def dist(x, y, p=2, name=None):
    """p-norm of (x - y) over all elements (reference paddle.dist)."""
    from .math_op_patch import binary
    from .tensor import reshape as _reshape
    d = binary(x, y, "elementwise_sub")
    n = 1
    for s in d.shape:
        n *= int(s) if s > 0 else 1
    flat = _reshape(d, [-1])
    return norm(flat, p=p, axis=0)


def linear_chain_crf(input, label, length, param_attr=None, name=None):
    """Linear-chain CRF NLL (reference layers.linear_chain_crf /
    operators/linear_chain_crf_op.h). input: emissions [B, T, N]; label
    [B, T] int64; length [B] int64. Creates the [N+2, N] transition
    parameter (row 0 start, row 1 stop, rows 2.. pairwise). Returns the
    per-sequence negative log-likelihood [B, 1]."""
    helper = LayerHelper("linear_chain_crf", name=name)
    n = int(input.shape[-1])
    transition = helper.create_parameter(param_attr, [n + 2, n],
                                         input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("linear_chain_crf",
                     inputs={"Emission": [input], "Transition": [transition],
                             "Label": [label], "Length": [length]},
                     outputs={"LogLikelihood": [out]})
    return out


def crf_decoding(input, length, param_attr=None, transition=None,
                 name=None):
    """Viterbi decode (reference layers.crf_decoding). Pass the training
    CRF's transition parameter (or a param_attr naming it) to share
    weights. Returns the best path [B, T] int64 (0 past length)."""
    helper = LayerHelper("crf_decoding", name=name)
    if transition is None:
        n = int(input.shape[-1])
        transition = helper.create_parameter(param_attr, [n + 2, n],
                                             input.dtype)
    out = helper.create_variable_for_type_inference("int64")
    helper.append_op("crf_decoding",
                     inputs={"Emission": [input],
                             "Transition": [transition],
                             "Length": [length]},
                     outputs={"ViterbiPath": [out]})
    return out


def warpctc(input, label, input_length, label_length, blank=0, name=None):
    """CTC loss (reference layers.warpctc, padded mode). input: logits
    [B, T, C]; label [B, L] (no blanks); lengths [B]. Returns [B, 1]."""
    helper = LayerHelper("warpctc", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("warpctc",
                     inputs={"Logits": [input], "Label": [label],
                             "LogitsLength": [input_length],
                             "LabelLength": [label_length]},
                     outputs={"Loss": [out]},
                     attrs={"blank": int(blank)})
    return out


def nce(input, label, num_total_classes, num_neg_samples=10, sampler=0,
        param_attr=None, bias_attr=None, name=None):
    """NCE loss (reference layers.nce / operators/nce_op.h). input
    [B, D]; label [B, num_true] int64. sampler: 0 uniform, 1
    log-uniform. Creates Weight [num_total_classes, D] and Bias.
    Returns per-sample cost [B, 1]."""
    helper = LayerHelper("nce", name=name)
    d = int(input.shape[-1])
    w = helper.create_parameter(param_attr, [num_total_classes, d],
                                input.dtype)
    inputs = {"Input": [input], "Weight": [w], "Label": [label]}
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [num_total_classes],
                                    input.dtype, is_bias=True)
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("nce", inputs=inputs, outputs={"Cost": [out]},
                     attrs={"num_neg_samples": int(num_neg_samples),
                            "num_total_classes": int(num_total_classes),
                            "sampler": int(sampler)})
    return out


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None,
             path_table=None, path_code=None, name=None):
    """Hierarchical sigmoid loss (reference layers.hsigmoid /
    operators/hierarchical_sigmoid_op.cc). input [B, D]; label [B] or
    [B,1]. Default complete binary tree; custom Huffman trees via
    path_table/path_code [B, P]. Returns [B, 1]."""
    helper = LayerHelper("hierarchical_sigmoid", name=name)
    d = int(input.shape[-1])
    w = helper.create_parameter(param_attr, [num_classes - 1, d],
                                input.dtype)
    inputs = {"X": [input], "W": [w], "Label": [label]}
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [num_classes - 1],
                                    input.dtype, is_bias=True)
        inputs["Bias"] = [b]
    if path_table is not None:
        inputs["PathTable"] = [path_table]
        inputs["PathCode"] = [path_code]
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("hierarchical_sigmoid", inputs=inputs,
                     outputs={"Out": [out]},
                     attrs={"num_classes": int(num_classes)})
    return out


def conv3d(input, num_filters, filter_size, stride=1, padding=0,
           dilation=1, groups=1, param_attr=None, bias_attr=None,
           act=None, name=None):
    """reference layers.conv3d (NCDHW, OIDHW filters)."""
    helper = LayerHelper("conv3d", name=name)
    trip = (lambda v: list(v) if isinstance(v, (list, tuple))
            else [v] * 3)
    fs = trip(filter_size)
    c_in = input.shape[1]
    fan_in = (c_in // groups) * fs[0] * fs[1] * fs[2]
    w = helper.create_parameter(
        param_attr, [num_filters, c_in // groups] + fs, input.dtype,
        default_initializer=NormalInitializer(0.0, (2.0 / fan_in) ** 0.5))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("conv3d",
                     inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [out]},
                     attrs={"strides": trip(stride),
                            "paddings": trip(padding),
                            "dilations": trip(dilation),
                            "groups": groups})
    if bias_attr is not False:
        b = helper.create_parameter(bias_attr, [num_filters], input.dtype,
                                    is_bias=True)
        pre = helper.create_variable_for_type_inference(input.dtype)
        helper.append_op("elementwise_add", inputs={"X": [out], "Y": [b]},
                         outputs={"Out": [pre]}, attrs={"axis": 1})
    else:
        pre = out
    return helper.append_activation(pre, act)


def pool3d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, name=None):
    """reference layers.pool3d (NCDHW)."""
    helper = LayerHelper("pool3d", name=name)
    trip = (lambda v: list(v) if isinstance(v, (list, tuple))
            else [v] * 3)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("pool3d", inputs={"X": [input]},
                     outputs={"Out": [out]},
                     attrs={"pooling_type": pool_type,
                            "ksize": trip(pool_size),
                            "strides": trip(pool_stride),
                            "paddings": trip(pool_padding),
                            "global_pooling": global_pooling})
    return out


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    """reference layers.lrn."""
    helper = LayerHelper("lrn", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    mid = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("lrn", inputs={"X": [input]},
                     outputs={"Out": [out], "MidOut": [mid]},
                     attrs={"n": n, "k": k, "alpha": alpha, "beta": beta})
    return out


def row_conv(input, future_context_size, param_attr=None, name=None):
    """reference layers.row_conv (padded [B, T, D] convention)."""
    helper = LayerHelper("row_conv", name=name)
    d = int(input.shape[-1])
    w = helper.create_parameter(param_attr,
                                [future_context_size + 1, d], input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("row_conv",
                     inputs={"X": [input], "Filter": [w]},
                     outputs={"Out": [out]})
    return out


def shuffle_channel(x, group, name=None):
    helper = LayerHelper("shuffle_channel", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("shuffle_channel", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"group": group})
    return out


def temporal_shift(x, seg_num, shift_ratio=0.25, name=None):
    helper = LayerHelper("temporal_shift", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("temporal_shift", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"seg_num": seg_num,
                            "shift_ratio": shift_ratio})
    return out


def multiplex(inputs, index, name=None):
    helper = LayerHelper("multiplex", name=name)
    out = helper.create_variable_for_type_inference(inputs[0].dtype)
    helper.append_op("multiplex",
                     inputs={"X": list(inputs), "Ids": [index]},
                     outputs={"Out": [out]})
    return out


def py_func(func, x, out, backward_func=None,
            skip_vars_in_backward_input=None):
    """Run a Python callable as a graph op (reference layers/nn.py
    py_func over py_func_op.cc:44). `out` must be pre-created Variables
    with shapes/dtypes (create_variable / create_parameter), exactly
    like the reference. backward_func(x..., out..., dout...) -> dx...
    enables gradients."""
    from ..ops.io_ops import register_py_func
    helper = LayerHelper("py_func")
    xs = [x] if isinstance(x, Variable) else list(x)
    outs = [out] if isinstance(out, Variable) else list(out)
    fid = register_py_func(func)
    bid = register_py_func(backward_func) if backward_func else -1
    helper.append_op(
        type="py_func",
        inputs={"X": [v.name for v in xs]},
        outputs={"Out": [v.name for v in outs]},
        attrs={"forward_callable_id": fid,
               "backward_callable_id": bid})
    return out


def moe_routed_ffn(x, router_x, num_experts, top_k, d_ff,
                   activation="relu", valid=None, name=None,
                   keep_router_logits=False, score="softmax",
                   expert_bias=False, norm_topk=True, route_scale=1.0,
                   held=None, limit=None, n_group=1, topk_group=1,
                   gated=True, zero_experts=0, scope=None):
    """Dropless top-k mixture of gated experts without bias
    (ops/moe_ops.py ``moe_routed_ffn``): each token of ``x`` [B, S, H]
    goes to the ``top_k`` experts its row of ``router_x`` [B, S, H]
    scores highest (float32 logits, softmax over the selected), through
    ``act(x W_gate) * (x W_up)`` then ``W_down``; no capacity, nothing
    dropped.  ``valid`` [B] int: real rows per batch row; the rows
    behind them go through no expert and their output is 0.
    ``activation``: "relu" or "silu".
    ``score`` "sigmoid" scores each expert on its own: the ``top_k``
    largest of ``sigmoid(logits)`` (plus, with ``expert_bias``, the
    float32 parameter ``.expert_bias`` [E], which moves the choice and
    never the weights), weighted by their unbiased sigmoids, with
    ``norm_topk`` divided by their sum plus 1e-6, times ``route_scale``
    (``parallel/moe.py`` ``route_top_k``).
    ``held`` ``(first, count)``: this chip holds experts ``first ..
    first + count - 1`` of the ``num_experts`` the router scores (its
    share of an expert-parallel group): the expert matrices have
    ``count`` leading rows and ``out`` is those experts' part of the sum.
    ``limit`` L: ``act(min(gate, L)) * clip(up, -L, L)``.
    ``n_group`` > 1 with ``topk_group``: group-limited selection over
    softmax scores (``route_top_k``); ``expert_count`` then comes back as
    the pair ``(expert_count, group_rows [n_group] int32)``, the valid rows
    that kept each group.
    ``gated`` False: experts of two matrices, ``relu(x W_up)^2 W_down``
    (``activation`` "relu2", the squared ReLU, and no other) and no gate
    matrix; the first stack is then ``.up.w`` [E, H, d_ff].  ``router_x``
    may be wider than ``x`` (a router over the full row beside experts
    that work in a latent one): ``.router.w`` is [its width, E].
    ``zero_experts`` Z: the LAST Z of the ``num_experts`` router outputs are
    identity ("zero-computation") experts: no weights, a pick adds its
    routing weight times ``x`` (``parallel/moe.py`` ``moe_routed_tokens``);
    the real experts are the first ``num_experts - Z``, ``held`` lies among
    them, and with ``expert_bias`` a softmax router chooses by ``softmax +
    bias`` and weighs by the unbiased softmax.  ``scope``: a named scope
    around the layer's operations, in the compiled module's metadata.
    ``name`` prefixes the parameters ``.router.w`` [H, E], ``.gate_up.w``
    [E, H, 2 d_ff] and ``.down.w`` [E, d_ff, H].  Returns ``(out,
    expert_count [E] int32, router_logits or None)``."""
    from ..framework.initializer import XavierInitializer

    helper = LayerHelper("moe_routed_ffn", name=name)
    h, e, i = int(x.shape[-1]), int(num_experts), int(d_ff)
    zero = int(zero_experts)
    if not 0 <= zero < e or (zero and int(n_group) > 1):
        raise ValueError(f"moe_routed_ffn: {zero} identity experts among "
                         f"{e} router outputs in {n_group} group(s)")
    here = e - zero
    if held is not None:
        first, here = int(held[0]), int(held[1])
        if not 0 <= first < first + here <= e - zero:
            raise ValueError(f"moe_routed_ffn holds experts {first} .. "
                             f"{first + here - 1} of {e - zero}")
    p = (lambda s: f"{name}.{s}") if name else (lambda s: None)
    router_w = helper.create_parameter(
        p("router.w"), [int(router_x.shape[-1]), e], x.dtype)
    # per-expert matrices: Glorot over one expert's fan, not the stack's
    cols = i * (2 if gated else 1)
    gate_up = helper.create_parameter(
        p("gate_up.w" if gated else "up.w"), [here, h, cols], x.dtype,
        default_initializer=XavierInitializer(fan_in=h, fan_out=cols))
    down = helper.create_parameter(
        p("down.w"), [here, i, h], x.dtype,
        default_initializer=XavierInitializer(fan_in=i, fan_out=h))
    out = helper.create_variable_for_type_inference(x.dtype)
    counts = helper.create_variable_for_type_inference("int32")
    inputs = {"X": [x], "RouterX": [router_x], "RouterW": [router_w],
              "GateUpW": [gate_up], "DownW": [down]}
    if valid is not None:
        inputs["Valid"] = [valid]
    attrs = {"top_k": int(top_k), "activation": activation}
    if score != "softmax" or not norm_topk or route_scale != 1.0 \
            or int(n_group) > 1:
        attrs.update(score=score, norm_topk=bool(norm_topk),
                     route_scale=float(route_scale))
    if held is not None:
        attrs["held_first"] = first
    if limit is not None:
        attrs["limit"] = float(limit)
    if zero:
        attrs["zero_experts"] = zero
    if scope:
        attrs["scope"] = str(scope)
    if expert_bias:
        inputs["ExpertBias"] = [helper.create_parameter(
            p("expert_bias"), [e], "float32", is_bias=True)]
    outputs = {"Out": [out], "ExpertCount": [counts]}
    if int(n_group) > 1:
        if e % int(n_group) or not 1 <= int(topk_group) <= int(n_group):
            raise ValueError(f"moe_routed_ffn keeps {topk_group} of "
                             f"{n_group} groups of {e} experts")
        attrs.update(n_group=int(n_group), topk_group=int(topk_group))
        group_rows = helper.create_variable_for_type_inference("int32")
        outputs["GroupRows"] = [group_rows]
        counts = (counts, group_rows)
    logits = None
    if keep_router_logits:
        logits = helper.create_variable_for_type_inference("float32")
        outputs["RouterLogits"] = [logits]
    helper.append_op("moe_routed_ffn", inputs=inputs, outputs=outputs,
                     attrs=attrs)
    return out, counts, logits


def moe_ffn(x, num_experts, d_ff, capacity_factor=1.25,
            activation="gelu", name=None, param_attr=None):
    """Switch-style top-1 gated mixture-of-experts FFN (new capability —
    SURVEY §2.6 EP row; ops/moe_ops.py). Returns (out, aux_loss); add
    aux_loss (scaled ~1e-2) to the training loss for balanced routing.
    Parameter names carry the 'moe' tag so parallel.moe.moe_rules shards
    the expert dims over the `ep` mesh axis."""
    helper = LayerHelper("moe_ffn", name=name)
    h = int(x.shape[-1])
    e, i = int(num_experts), int(d_ff)
    # names inherit the "moe_ffn" helper prefix, which moe_rules keys on
    gate_w = helper.create_parameter(param_attr, [h, e], x.dtype)
    w1 = helper.create_parameter(param_attr, [e, h, i], x.dtype)
    b1 = helper.create_parameter(param_attr, [e, i], x.dtype, is_bias=True)
    w2 = helper.create_parameter(param_attr, [e, i, h], x.dtype)
    b2 = helper.create_parameter(param_attr, [e, h], x.dtype, is_bias=True)
    out = helper.create_variable_for_type_inference(x.dtype)
    aux = helper.create_variable_for_type_inference("float32")
    counts = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        "moe_ffn",
        inputs={"X": [x], "GateW": [gate_w], "W1": [w1], "B1": [b1],
                "W2": [w2], "B2": [b2]},
        outputs={"Out": [out], "AuxLoss": [aux],
                 "ExpertCount": [counts]},
        attrs={"capacity_factor": float(capacity_factor),
               "activation": activation})
    return out, aux
