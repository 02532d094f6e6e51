"""Neural-network layer functions (reference python/paddle/fluid/layers/nn.py,
175 functions in __all__).  Each builds ops into the default main program via
LayerHelper; nothing touches a device until the executor lowers the block.
"""

from __future__ import annotations

import numpy as np

from .. import framework
from ..framework import Variable
from ..layer_helper import LayerHelper
from ..initializer import Constant, Normal, Xavier
from ..param_attr import ParamAttr

__all__ = [
    "fc", "embedding", "flash_attention", "moe_ffn",
    "paged_attention", "kv_cache_write", "kv_cache_write_pages",
    "ragged_attention", "paged_attention_quant", "kv_cache_write_quant",
    "kv_cache_write_pages_quant",
    "weight_matmul", "headwise_matmul", "rms_norm", "swiglu",
    "rope_interleaved", "dsa_indexer_scores", "dsa_topk_select",
    "sparse_mla_attention", "moe_ffn_held", "rope_half", "sigmoid_gate",
    "paged_mla_attention", "mla_chunk_attention", "bicubic_resize_table",
    "rope_2d_interleaved", "vit_attention", "select_embedding_rows",
    "short_conv", "gdn_inputs", "gated_delta_rule", "gated_rms_norm",
    "conv2d", "conv3d", "conv2d_transpose", "pool2d",
    "batch_norm", "layer_norm", "group_norm", "instance_norm", "dropout",
    "softmax", "log_softmax", "cross_entropy", "softmax_with_cross_entropy",
    "sigmoid_cross_entropy_with_logits", "square_error_cost", "accuracy",
    "matmul", "mul", "scale", "relu", "leaky_relu", "prelu", "elu", "relu6",
    "gelu", "swish", "hard_sigmoid", "hard_swish", "elementwise_add",
    "elementwise_sub", "elementwise_mul", "elementwise_div", "elementwise_max",
    "elementwise_min", "elementwise_pow", "elementwise_mod",
    "elementwise_floordiv", "clip", "clip_by_norm", "l2_normalize",
    "reduce_sum", "reduce_mean", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_all", "reduce_any", "topk", "one_hot", "reshape", "transpose",
    "flatten", "squeeze", "unsqueeze", "concat", "split", "stack", "unstack",
    "expand", "expand_as", "slice", "strided_slice", "gather", "gather_nd",
    "scatter", "pad", "pad2d", "label_smooth", "mean", "pow", "lrn",
    "image_resize", "resize_bilinear", "resize_nearest", "dice_loss",
    "log_loss", "huber_loss", "smooth_l1", "cos_sim", "dropout",
    "cumsum", "argmax", "argmin", "argsort", "where", "index_select",
    "shape", "logical_and", "logical_or", "logical_not", "logical_xor",
    "equal", "not_equal", "less_than", "less_equal", "greater_than",
    "greater_equal", "cast", "brelu", "soft_relu", "uniform_random",
    "floor", "ceil", "round", "cos", "sin", "rsqrt", "reciprocal", "sign",
    "gaussian_random", "sampling_id", "unfold", "group_norm", "sigmoid",
    "tanh", "exp", "log", "sqrt", "square", "abs", "sequence_conv",
    "sequence_pool", "sequence_softmax", "sequence_expand", "sequence_reverse",
    "sequence_first_step", "sequence_last_step", "sequence_mask",
    "sequence_unpad", "sequence_concat", "sequence_expand_as",
    "sequence_slice", "sequence_enumerate",
    "kldiv_loss", "margin_rank_loss", "rank_loss", "hinge_loss", "bpr_loss",
    "maxout", "selu", "pixel_shuffle", "shuffle_channel", "affine_channel",
    "grid_sampler", "crop", "im2sequence", "chunk_eval",
    "softmax_mask_fuse_upper_triangle", "adaptive_pool2d",
]


def _single_out_layer(helper, op_type, inputs, attrs=None, dtype=None, out=None):
    if out is None:
        out = helper.create_variable_for_type_inference(
            dtype=dtype or next(iter(inputs.values()))[0].dtype)
    helper.append_op(op_type, inputs=inputs, outputs={_OUT_SLOT.get(op_type, "Out"): [out]},
                     attrs=attrs or {})
    return out


_OUT_SLOT = {"cross_entropy": "Y", "stack": "Y", "mul": "Out",
             "kldiv_loss": "Loss", "hinge_loss": "Loss", "bpr_loss": "Y",
             "grid_sampler": "Output"}


# ---------------------------------------------------------------------------
# core layers
# ---------------------------------------------------------------------------


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, name=None):
    """Fully-connected (reference layers/nn.py fc): mul + elementwise_add +
    activation.  Lowers to one MXU matmul fused with bias/act by XLA."""
    helper = LayerHelper("fc", input=input, size=size, bias_attr=bias_attr,
                         act=act, name=name)
    inputs = input if isinstance(input, (list, tuple)) else [input]
    param_attrs = ParamAttr._to_attr(param_attr)
    if not isinstance(param_attrs, list):
        param_attrs = [param_attrs] * len(inputs)
    mul_results = []
    for inp, pa in zip(inputs, param_attrs):
        in_shape = inp.shape
        w_shape = [int(np.prod(in_shape[num_flatten_dims:])), size]
        w = helper.create_parameter(pa, shape=w_shape, dtype=inp.dtype)
        out = helper.create_variable_for_type_inference(dtype=inp.dtype)
        helper.append_op("mul", inputs={"X": [inp], "Y": [w]}, outputs={"Out": [out]},
                         attrs={"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1})
        mul_results.append(out)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(dtype=inputs[0].dtype)
        helper.append_op("sum", inputs={"X": mul_results}, outputs={"Out": [pre_bias]})
    pre_act = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims)
    return helper.append_activation(pre_act)


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32"):
    """reference layers/nn.py embedding → lookup_table op.  is_sparse is
    accepted for parity; on TPU the dense scatter-add gradient is already the
    fast path (no SelectedRows needed)."""
    helper = LayerHelper("embedding", **locals())
    w = helper.create_parameter(param_attr, shape=list(size), dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype=dtype)
    pad = -1 if padding_idx is None else (
        padding_idx if padding_idx >= 0 else size[0] + padding_idx)
    helper.append_op("lookup_table", inputs={"W": [w], "Ids": [input]},
                     outputs={"Out": [out]},
                     attrs={"padding_idx": pad, "is_sparse": is_sparse,
                            "is_distributed": is_distributed})
    return out


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None, data_format="NCHW"):
    helper = LayerHelper("conv2d", input=input, size=num_filters,
                         bias_attr=bias_attr, act=act, name=name)
    chans = input.shape[1]
    # reference parity (layers/nn.py conv2d): a fully-grouped conv emits the
    # dedicated depthwise_conv2d op when cuDNN is declined — era MobileNet
    # code passes use_cudnn=False on its depthwise layers to get this.  Both
    # op types reach the same grouped-conv XLA lowering here; the switch
    # keeps built programs interoperable with reference-exported ones.
    op_type = ("depthwise_conv2d"
               if chans == groups and num_filters % max(chans, 1) == 0
               and not use_cudnn else "conv2d")
    fs = filter_size if isinstance(filter_size, (list, tuple)) else [filter_size] * 2
    stride = stride if isinstance(stride, (list, tuple)) else [stride] * 2
    padding = padding if isinstance(padding, (list, tuple)) else [padding] * 2
    dilation = dilation if isinstance(dilation, (list, tuple)) else [dilation] * 2
    w_shape = [num_filters, chans // groups] + list(fs)
    fan_in = (chans // groups) * fs[0] * fs[1]
    default_init = Normal(0.0, float((2.0 / fan_in) ** 0.5))
    w = helper.create_parameter(param_attr, shape=w_shape, dtype=input.dtype,
                                default_initializer=default_init)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(op_type, inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [out]},
                     attrs={"strides": list(stride), "paddings": list(padding),
                            "dilations": list(dilation), "groups": groups,
                            "data_format": data_format})
    pre_act = _conv_bias(helper, out, bias_attr, num_filters, input.dtype)
    return helper.append_activation(pre_act)


def _conv_bias(helper, conv_out, bias_attr, num_filters, dtype):
    if bias_attr is False:
        return conv_out
    b = helper.create_parameter(ParamAttr._to_attr(bias_attr), shape=[num_filters],
                                dtype=dtype, is_bias=True)
    if b is None:
        return conv_out
    out = helper.create_variable_for_type_inference(dtype=dtype)
    helper.append_op("elementwise_add", inputs={"X": [conv_out], "Y": [b]},
                     outputs={"Out": [out]}, attrs={"axis": 1})
    return out


def conv3d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, act=None, name=None, **kw):
    helper = LayerHelper("conv3d", input=input, size=num_filters,
                         bias_attr=bias_attr, act=act, name=name)
    chans = input.shape[1]
    fs = filter_size if isinstance(filter_size, (list, tuple)) else [filter_size] * 3
    stride = stride if isinstance(stride, (list, tuple)) else [stride] * 3
    padding = padding if isinstance(padding, (list, tuple)) else [padding] * 3
    dilation = dilation if isinstance(dilation, (list, tuple)) else [dilation] * 3
    w = helper.create_parameter(param_attr, shape=[num_filters, chans // groups] + list(fs),
                                dtype=input.dtype)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("conv3d", inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [out]},
                     attrs={"strides": list(stride), "paddings": list(padding),
                            "dilations": list(dilation), "groups": groups})
    pre = _conv_bias(helper, out, bias_attr, num_filters, input.dtype)
    return helper.append_activation(pre)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     stride=1, padding=0, dilation=1, groups=1, param_attr=None,
                     bias_attr=None, act=None, name=None, **kw):
    helper = LayerHelper("conv2d_transpose", input=input, size=num_filters,
                         bias_attr=bias_attr, act=act, name=name)
    chans = input.shape[1]
    fs = filter_size if isinstance(filter_size, (list, tuple)) else [filter_size] * 2
    stride = stride if isinstance(stride, (list, tuple)) else [stride] * 2
    padding = padding if isinstance(padding, (list, tuple)) else [padding] * 2
    dilation = dilation if isinstance(dilation, (list, tuple)) else [dilation] * 2
    w = helper.create_parameter(param_attr, shape=[chans, num_filters // groups] + list(fs),
                                dtype=input.dtype)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("conv2d_transpose", inputs={"Input": [input], "Filter": [w]},
                     outputs={"Output": [out]},
                     attrs={"strides": list(stride), "paddings": list(padding),
                            "dilations": list(dilation), "groups": groups})
    pre = _conv_bias(helper, out, bias_attr, num_filters, input.dtype)
    return helper.append_activation(pre)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1, pool_padding=0,
           global_pooling=False, use_cudnn=True, ceil_mode=False, name=None,
           exclusive=True, adaptive=False, data_format="NCHW"):
    helper = LayerHelper("pool2d", name=name)
    ps = pool_size if isinstance(pool_size, (list, tuple)) else [pool_size] * 2
    st = pool_stride if isinstance(pool_stride, (list, tuple)) else [pool_stride] * 2
    pd = pool_padding if isinstance(pool_padding, (list, tuple)) else [pool_padding] * 2
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("pool2d", inputs={"X": [input]}, outputs={"Out": [out]},
                     attrs={"pooling_type": pool_type, "ksize": list(ps),
                            "strides": list(st), "paddings": list(pd),
                            "global_pooling": global_pooling, "ceil_mode": ceil_mode,
                            "exclusive": exclusive, "adaptive": adaptive})
    return out


def adaptive_pool2d(input, pool_size, pool_type="max", name=None):
    return pool2d(input, pool_size=pool_size, pool_type=pool_type, adaptive=True,
                  name=name)


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               in_place=False, name=None, moving_mean_name=None,
               moving_variance_name=None, do_model_average_for_mean_and_var=False,
               use_global_stats=False):
    helper = LayerHelper("batch_norm", act=act, name=name)
    dtype = input.dtype
    c = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    scale = helper.create_parameter(param_attr, shape=[c], dtype=dtype,
                                    default_initializer=Constant(1.0))
    bias = helper.create_parameter(bias_attr, shape=[c], dtype=dtype, is_bias=True)
    mean = helper.create_or_get_global_variable(
        moving_mean_name or f"{helper.name}.mean", shape=[c], dtype=dtype,
        persistable=True, stop_gradient=True)
    var = helper.create_or_get_global_variable(
        moving_variance_name or f"{helper.name}.var", shape=[c], dtype=dtype,
        persistable=True, stop_gradient=True)
    helper.set_variable_initializer(mean, Constant(0.0))
    helper.set_variable_initializer(var, Constant(1.0))
    saved_mean = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    saved_var = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "batch_norm",
        inputs={"X": [input], "Scale": [scale], "Bias": [bias],
                "Mean": [mean], "Variance": [var]},
        outputs={"Y": [out], "MeanOut": [mean], "VarianceOut": [var],
                 "SavedMean": [saved_mean], "SavedVariance": [saved_var]},
        attrs={"momentum": momentum, "epsilon": epsilon, "is_test": is_test,
               "data_layout": data_layout, "use_global_stats": use_global_stats})
    return helper.append_activation(out)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1, epsilon=1e-5,
               param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("layer_norm", act=act, name=name)
    dtype = input.dtype
    norm_shape = [int(np.prod(input.shape[begin_norm_axis:]))]
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(param_attr, shape=norm_shape, dtype=dtype,
                                    default_initializer=Constant(1.0))
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(bias_attr, shape=norm_shape, dtype=dtype, is_bias=True)
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(dtype)
    mean = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    var = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op("layer_norm", inputs=inputs,
                     outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
                     attrs={"epsilon": epsilon, "begin_norm_axis": begin_norm_axis})
    return helper.append_activation(out)


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, data_layout="NCHW", name=None):
    helper = LayerHelper("group_norm", act=act, name=name)
    c = input.shape[1]
    inputs = {"X": [input]}
    if param_attr is not False:
        inputs["Scale"] = [helper.create_parameter(param_attr, shape=[c], dtype=input.dtype,
                                                   default_initializer=Constant(1.0))]
    if bias_attr is not False:
        inputs["Bias"] = [helper.create_parameter(bias_attr, shape=[c], dtype=input.dtype,
                                                  is_bias=True)]
    out = helper.create_variable_for_type_inference(input.dtype)
    mean = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    var = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op("group_norm", inputs=inputs,
                     outputs={"Y": [out], "Mean": [mean], "Variance": [var]},
                     attrs={"epsilon": epsilon, "groups": groups})
    return helper.append_activation(out)


def instance_norm(input, epsilon=1e-5, param_attr=None, bias_attr=None, name=None):
    helper = LayerHelper("instance_norm", name=name)
    c = input.shape[1]
    scale = helper.create_parameter(param_attr, shape=[c], dtype=input.dtype,
                                    default_initializer=Constant(1.0))
    bias = helper.create_parameter(bias_attr, shape=[c], dtype=input.dtype, is_bias=True)
    out = helper.create_variable_for_type_inference(input.dtype)
    sm = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    sv = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op("instance_norm",
                     inputs={"X": [input], "Scale": [scale], "Bias": [bias]},
                     outputs={"Y": [out], "SavedMean": [sm], "SavedVariance": [sv]},
                     attrs={"epsilon": epsilon})
    return out


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    mask = helper.create_variable_for_type_inference("uint8",
                                                     stop_gradient=True)
    helper.append_op("dropout", inputs={"X": [x]},
                     outputs={"Out": [out], "Mask": [mask]},
                     attrs={"dropout_prob": dropout_prob, "is_test": is_test,
                            "seed": seed if seed is not None else 0,
                            "dropout_implementation": dropout_implementation})
    return out


# ---------------------------------------------------------------------------
# losses / classification
# ---------------------------------------------------------------------------


def softmax(input, use_cudnn=False, name=None, axis=-1):
    helper = LayerHelper("softmax", name=name)
    return _single_out_layer(helper, "softmax", {"X": [input]}, {"axis": axis})


def log_softmax(input, axis=-1, name=None):
    helper = LayerHelper("log_softmax", name=name)
    return _single_out_layer(helper, "log_softmax", {"X": [input]}, {"axis": axis})


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("cross_entropy", inputs={"X": [input], "Label": [label]},
                     outputs={"Y": [out]},
                     attrs={"soft_label": soft_label, "ignore_index": ignore_index})
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False, ignore_index=-100,
                               numeric_stable_mode=True, return_softmax=False, axis=-1):
    helper = LayerHelper("softmax_with_cross_entropy")
    sm = helper.create_variable_for_type_inference(logits.dtype)
    loss = helper.create_variable_for_type_inference(logits.dtype)
    helper.append_op("softmax_with_cross_entropy",
                     inputs={"Logits": [logits], "Label": [label]},
                     outputs={"Softmax": [sm], "Loss": [loss]},
                     attrs={"soft_label": soft_label, "ignore_index": ignore_index,
                            "axis": axis})
    if return_softmax:
        return loss, sm
    return loss


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100, name=None,
                                      normalize=False):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    return _single_out_layer(helper, "sigmoid_cross_entropy_with_logits",
                             {"X": [x], "Label": [label]},
                             {"ignore_index": ignore_index, "normalize": normalize})


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost")
    return _single_out_layer(helper, "square_error_cost", {"X": [input], "Y": [label]})


def accuracy(input, label, k=1, correct=None, total=None):
    helper = LayerHelper("accuracy")
    topk_out = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    topk_idx = helper.create_variable_for_type_inference("int64", stop_gradient=True)
    helper.append_op("top_k", inputs={"X": [input]},
                     outputs={"Out": [topk_out], "Indices": [topk_idx]}, attrs={"k": k})
    acc = helper.create_variable_for_type_inference("float32", stop_gradient=True)
    correct = correct or helper.create_variable_for_type_inference("int32", stop_gradient=True)
    total = total or helper.create_variable_for_type_inference("int32", stop_gradient=True)
    helper.append_op("accuracy",
                     inputs={"Out": [topk_out], "Indices": [topk_idx], "Label": [label]},
                     outputs={"Accuracy": [acc], "Correct": [correct], "Total": [total]})
    return acc


def dice_loss(input, label, epsilon=1e-5):
    label = cast(label, input.dtype)
    reduce_dims = list(range(1, len(input.shape)))
    inse = reduce_sum(elementwise_mul(input, label), dim=reduce_dims)
    dice_denominator = reduce_sum(input, dim=reduce_dims) + reduce_sum(label, dim=reduce_dims)
    dice_score = 1 - inse * 2.0 / (dice_denominator + epsilon)
    return reduce_mean(dice_score)


def log_loss(input, label, epsilon=1e-4, name=None):
    helper = LayerHelper("log_loss", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("log_loss", inputs={"Predicted": [input], "Labels": [label]},
                     outputs={"Loss": [out]}, attrs={"epsilon": epsilon})
    return out


def huber_loss(input, label, delta):
    helper = LayerHelper("huber_loss")
    out = helper.create_variable_for_type_inference(input.dtype)
    resid = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op("huber_loss", inputs={"X": [input], "Y": [label]},
                     outputs={"Out": [out], "Residual": [resid]}, attrs={"delta": delta})
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1_loss")
    out = helper.create_variable_for_type_inference(x.dtype)
    diff = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    ins = {"X": [x], "Y": [y]}
    if inside_weight is not None:
        ins["InsideWeight"] = [inside_weight]
    if outside_weight is not None:
        ins["OutsideWeight"] = [outside_weight]
    helper.append_op("smooth_l1_loss", inputs=ins,
                     outputs={"Out": [out], "Diff": [diff]},
                     attrs={"sigma": sigma or 1.0})
    return out


def cos_sim(X, Y):
    xn = l2_normalize(X, axis=-1)
    yn = l2_normalize(Y, axis=-1)
    helper = LayerHelper("cos_sim")
    return _single_out_layer(helper, "dot", {"X": [xn], "Y": [yn]})


def mean(x, name=None):
    helper = LayerHelper("mean", name=name)
    return _single_out_layer(helper, "mean", {"X": [x]})


# ---------------------------------------------------------------------------
# math wrappers
# ---------------------------------------------------------------------------


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", name=name)
    return _single_out_layer(helper, "matmul", {"X": [x], "Y": [y]},
                             {"transpose_X": transpose_x, "transpose_Y": transpose_y,
                              "alpha": float(alpha)})


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", name=name)
    return _single_out_layer(helper, "mul", {"X": [x], "Y": [y]},
                             {"x_num_col_dims": x_num_col_dims,
                              "y_num_col_dims": y_num_col_dims})


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper("scale", act=act, name=name)
    out = _single_out_layer(helper, "scale", {"X": [x]},
                            {"scale": float(scale), "bias": float(bias),
                             "bias_after_scale": bias_after_scale})
    return helper.append_activation(out)


def _elementwise(op_type, x, y, axis=-1, act=None, name=None):
    helper = LayerHelper(op_type, act=act, name=name)
    out = _single_out_layer(helper, op_type, {"X": [x], "Y": [y]}, {"axis": axis})
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


def _elementwise_binary_var(x, y, op_type):
    """Operator-overload path (reference math_op_patch.py)."""
    from . import tensor as _t

    if isinstance(x, (int, float)):
        if op_type == "elementwise_add":
            return scale(y, 1.0, float(x))
        if op_type == "elementwise_mul":
            return scale(y, float(x))
        if op_type == "elementwise_sub":
            return scale(y, -1.0, float(x))
        x = _t.fill_constant(shape=[1], dtype=y.dtype, value=float(x))
    if isinstance(y, (int, float)):
        if op_type == "elementwise_add":
            return scale(x, 1.0, float(y))
        if op_type == "elementwise_mul":
            return scale(x, float(y))
        if op_type == "elementwise_sub":
            return scale(x, 1.0, -float(y))
        if op_type == "elementwise_div":
            return scale(x, 1.0 / float(y))
        y = _t.fill_constant(shape=[1], dtype=x.dtype, value=float(y))
    return _elementwise(op_type, x, y)


def _cmp_layer(op_type, x, y, name=None, out=None):
    helper = LayerHelper(op_type, name=name)
    return _single_out_layer(helper, op_type, {"X": [x], "Y": [y]},
                             dtype="bool", out=out)


def equal(x, y, cond=None):
    return _cmp_layer("equal", x, y, out=cond)


def not_equal(x, y, cond=None):
    return _cmp_layer("not_equal", x, y, out=cond)


def less_than(x, y, cond=None, force_cpu=None):
    return _cmp_layer("less_than", x, y, out=cond)


def less_equal(x, y, cond=None):
    return _cmp_layer("less_equal", x, y, out=cond)


def greater_than(x, y, cond=None):
    return _cmp_layer("greater_than", x, y, out=cond)


def greater_equal(x, y, cond=None):
    return _cmp_layer("greater_equal", x, y, out=cond)


def logical_and(x, y, out=None, name=None):
    return _cmp_layer("logical_and", x, y, out=out)


def logical_or(x, y, out=None, name=None):
    return _cmp_layer("logical_or", x, y, out=out)


def logical_xor(x, y, out=None, name=None):
    return _cmp_layer("logical_xor", x, y, out=out)


def logical_not(x, out=None, name=None):
    helper = LayerHelper("logical_not")
    return _single_out_layer(helper, "logical_not", {"X": [x]}, dtype="bool",
                             out=out)


# activations ---------------------------------------------------------------


def _act_layer(op_type, x, attrs=None, name=None):
    helper = LayerHelper(op_type, name=name)
    return _single_out_layer(helper, op_type, {"X": [x]}, attrs or {})


def relu(x, name=None):
    return _act_layer("relu", x, name=name)


def sigmoid(x, name=None):
    return _act_layer("sigmoid", x, name=name)


def tanh(x, name=None):
    return _act_layer("tanh", x, name=name)


def exp(x, name=None):
    return _act_layer("exp", x, name=name)


def log(x, name=None):
    return _act_layer("log", x, name=name)


def sqrt(x, name=None):
    return _act_layer("sqrt", x, name=name)


def square(x, name=None):
    return _act_layer("square", x, name=name)


def abs(x, name=None):
    return _act_layer("abs", x, name=name)


def leaky_relu(x, alpha=0.02, name=None):
    return _act_layer("leaky_relu", x, {"alpha": alpha}, name)


def elu(x, alpha=1.0, name=None):
    return _act_layer("elu", x, {"alpha": alpha}, name)


def relu6(x, threshold=6.0, name=None):
    return _act_layer("relu6", x, {"threshold": threshold}, name)


def gelu(x, approximate=False):
    return _act_layer("gelu", x, {"approximate": approximate})


def swish(x, beta=1.0, name=None):
    return _act_layer("swish", x, {"beta": beta}, name)


def hard_sigmoid(x, slope=0.2, offset=0.5, name=None):
    return _act_layer("hard_sigmoid", x, {"slope": slope, "offset": offset}, name)


def hard_swish(x, threshold=6.0, scale=6.0, offset=3.0, name=None):
    return _act_layer("hard_swish", x,
                      {"threshold": threshold, "scale": scale, "offset": offset}, name)


def brelu(x, t_min=0.0, t_max=24.0, name=None):
    return _act_layer("brelu", x, {"t_min": t_min, "t_max": t_max}, name)


def soft_relu(x, threshold=40.0, name=None):
    return _act_layer("softplus", x, name=name)


def pow(x, factor=1.0, name=None):
    return _act_layer("pow", x, {"factor": factor}, name)


def floor(x, name=None):
    return _act_layer("floor", x, name=name)


def ceil(x, name=None):
    return _act_layer("ceil", x, name=name)


def round(x, name=None):
    return _act_layer("round", x, name=name)


def cos(x, name=None):
    return _act_layer("cos", x, name=name)


def sin(x, name=None):
    return _act_layer("sin", x, name=name)


def rsqrt(x, name=None):
    return _act_layer("rsqrt", x, name=name)


def reciprocal(x, name=None):
    return _act_layer("reciprocal", x, name=name)


def sign(x, name=None):
    return _act_layer("sign", x, name=name)


def prelu(x, mode="all", param_attr=None, name=None):
    helper = LayerHelper("prelu", name=name)
    alpha_shape = [1] if mode == "all" else (
        [x.shape[1]] if mode == "channel" else list(x.shape[1:]))
    alpha = helper.create_parameter(param_attr, shape=alpha_shape, dtype=x.dtype,
                                    default_initializer=Constant(0.25))
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("prelu", inputs={"X": [x], "Alpha": [alpha]},
                     outputs={"Out": [out]}, attrs={"mode": mode})
    return out


# reductions ----------------------------------------------------------------


def _reduce_layer(op_type, input, dim=None, keep_dim=False, name=None):
    helper = LayerHelper(op_type, name=name)
    if dim is None:
        attrs = {"dim": [0], "keep_dim": keep_dim, "reduce_all": True}
    else:
        d = dim if isinstance(dim, (list, tuple)) else [dim]
        attrs = {"dim": list(d), "keep_dim": keep_dim, "reduce_all": False}
    return _single_out_layer(helper, op_type, {"X": [input]}, attrs)


def reduce_sum(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_sum", input, dim, keep_dim, name)


def reduce_mean(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_mean", input, dim, keep_dim, name)


def reduce_max(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_max", input, dim, keep_dim, name)


def reduce_min(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_min", input, dim, keep_dim, name)


def reduce_prod(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_prod", input, dim, keep_dim, name)


def reduce_all(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_all", input, dim, keep_dim, name)


def reduce_any(input, dim=None, keep_dim=False, name=None):
    return _reduce_layer("reduce_any", input, dim, keep_dim, name)


def clip(x, min, max, name=None):
    return _act_layer("clip", x, {"min": float(min), "max": float(max)}, name)


def clip_by_norm(x, max_norm, name=None):
    return _act_layer("clip_by_norm", x, {"max_norm": float(max_norm)}, name)


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    norm = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op("l2_normalize", inputs={"X": [x]},
                     outputs={"Out": [out], "Norm": [norm]},
                     attrs={"axis": axis, "epsilon": epsilon})
    return out


def cumsum(x, axis=-1, exclusive=False, reverse=False):
    return _act_layer("cumsum", x, {"axis": axis, "exclusive": exclusive,
                                    "reverse": reverse})


# shape ops -----------------------------------------------------------------


def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper("reshape2", act=act, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op("reshape2", inputs={"X": [x]},
                     outputs={"Out": [out], "XShape": [xshape]},
                     attrs={"shape": list(shape)})
    return helper.append_activation(out)


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose2", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op("transpose2", inputs={"X": [x]},
                     outputs={"Out": [out], "XShape": [xshape]},
                     attrs={"axis": list(perm)})
    return out


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten2", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    xshape = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op("flatten2", inputs={"X": [x]},
                     outputs={"Out": [out], "XShape": [xshape]}, attrs={"axis": axis})
    return out


def squeeze(input, axes, name=None):
    helper = LayerHelper("squeeze2", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    xshape = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op("squeeze2", inputs={"X": [input]},
                     outputs={"Out": [out], "XShape": [xshape]}, attrs={"axes": list(axes)})
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze2", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    xshape = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op("unsqueeze2", inputs={"X": [input]},
                     outputs={"Out": [out], "XShape": [xshape]}, attrs={"axes": list(axes)})
    return out


def concat(input, axis=0, name=None):
    helper = LayerHelper("concat", name=name)
    return _single_out_layer(helper, "concat", {"X": list(input)}, {"axis": axis})


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", name=name)
    ndim = len(input.shape)
    axis = dim % ndim
    if isinstance(num_or_sections, int):
        n = num_or_sections
        attrs = {"num": n, "sections": [], "axis": axis}
    else:
        n = len(num_or_sections)
        attrs = {"num": 0, "sections": list(num_or_sections), "axis": axis}
    outs = [helper.create_variable_for_type_inference(input.dtype) for _ in range(n)]
    helper.append_op("split", inputs={"X": [input]}, outputs={"Out": outs}, attrs=attrs)
    return outs


def stack(x, axis=0):
    helper = LayerHelper("stack")
    return _single_out_layer(helper, "stack", {"X": list(x)}, {"axis": axis})


def unstack(x, axis=0, num=None):
    helper = LayerHelper("unstack")
    n = num if num is not None else x.shape[axis]
    outs = [helper.create_variable_for_type_inference(x.dtype) for _ in range(n)]
    helper.append_op("unstack", inputs={"X": [x]}, outputs={"Y": outs},
                     attrs={"axis": axis, "num": n})
    return outs


def expand(x, expand_times, name=None):
    return _act_layer("expand", x, {"expand_times": list(expand_times)}, name)


def expand_as(x, target_tensor, name=None):
    helper = LayerHelper("expand_as", name=name)
    return _single_out_layer(helper, "expand_as",
                             {"X": [x], "target_tensor": [target_tensor]})


def slice(input, axes, starts, ends):
    helper = LayerHelper("slice")
    return _single_out_layer(helper, "slice", {"Input": [input]},
                             {"axes": list(axes), "starts": list(starts),
                              "ends": list(ends), "decrease_axis": []})


def strided_slice(input, axes, starts, ends, strides):
    helper = LayerHelper("strided_slice")
    return _single_out_layer(helper, "strided_slice", {"Input": [input]},
                             {"axes": list(axes), "starts": list(starts),
                              "ends": list(ends), "strides": list(strides)})


def gather(input, index, overwrite=True):
    helper = LayerHelper("gather")
    return _single_out_layer(helper, "gather", {"X": [input], "Index": [index]})


def gather_nd(input, index, name=None):
    helper = LayerHelper("gather_nd", name=name)
    return _single_out_layer(helper, "gather_nd", {"X": [input], "Index": [index]})


def scatter(input, index, updates, name=None, overwrite=True):
    helper = LayerHelper("scatter", name=name)
    return _single_out_layer(helper, "scatter",
                             {"X": [input], "Ids": [index], "Updates": [updates]},
                             {"overwrite": overwrite})


def pad(x, paddings, pad_value=0.0, name=None):
    return _act_layer("pad", x, {"paddings": list(paddings), "pad_value": pad_value}, name)


def pad2d(input, paddings=[0, 0, 0, 0], mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    return _act_layer("pad2d", input, {"paddings": list(paddings), "mode": mode,
                                       "pad_value": pad_value}, name)


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32", name=None):
    helper = LayerHelper("label_smooth", name=name)
    ins = {"X": [label]}
    if prior_dist is not None:
        ins["PriorDist"] = [prior_dist]
    return _single_out_layer(helper, "label_smooth", ins, {"epsilon": float(epsilon)})


def one_hot(input, depth, allow_out_of_range=False):
    helper = LayerHelper("one_hot")
    return _single_out_layer(helper, "one_hot", {"X": [input]},
                             {"depth": depth}, dtype="float32")


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    vals = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    idx = helper.create_variable_for_type_inference("int64", stop_gradient=True)
    helper.append_op("top_k", inputs={"X": [input]},
                     outputs={"Out": [vals], "Indices": [idx]}, attrs={"k": k})
    return vals, idx


def argmax(x, axis=0, name=None):
    helper = LayerHelper("arg_max", name=name)
    return _single_out_layer(helper, "arg_max", {"X": [x]}, {"axis": axis}, dtype="int64")


def argmin(x, axis=0, name=None):
    helper = LayerHelper("arg_min", name=name)
    return _single_out_layer(helper, "arg_min", {"X": [x]}, {"axis": axis}, dtype="int64")


def argsort(input, axis=-1, descending=False, name=None):
    helper = LayerHelper("argsort", name=name)
    out = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    idx = helper.create_variable_for_type_inference("int64", stop_gradient=True)
    helper.append_op("argsort", inputs={"X": [input]},
                     outputs={"Out": [out], "Indices": [idx]},
                     attrs={"axis": axis, "descending": descending})
    return out, idx


def where(condition):
    helper = LayerHelper("where_index")
    return _single_out_layer(helper, "where_index", {"Condition": [condition]},
                             dtype="int64")


def index_select(input, index, dim=0):
    helper = LayerHelper("index_select")
    return _single_out_layer(helper, "index_select", {"X": [input], "Index": [index]},
                             {"dim": dim})


def shape(input):
    helper = LayerHelper("shape")
    return _single_out_layer(helper, "shape", {"Input": [input]}, dtype="int32")


def cast(x, dtype):
    from ..framework import convert_np_dtype_to_dtype_

    helper = LayerHelper("cast")
    dt = convert_np_dtype_to_dtype_(dtype)
    return _single_out_layer(helper, "cast", {"X": [x]}, {"out_dtype": dt}, dtype=dt)


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper("lrn", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    mid = helper.create_variable_for_type_inference(input.dtype, stop_gradient=True)
    helper.append_op("lrn", inputs={"X": [input]},
                     outputs={"Out": [out], "MidOut": [mid]},
                     attrs={"n": n, "k": k, "alpha": alpha, "beta": beta})
    return out


def image_resize(input, out_shape=None, scale=None, name=None,
                 resample="BILINEAR", align_corners=True, align_mode=1):
    op = "bilinear_interp" if resample.upper() == "BILINEAR" else "nearest_interp"
    if out_shape is None:
        out_shape = [int(input.shape[2] * scale), int(input.shape[3] * scale)]
    helper = LayerHelper(op, name=name)
    # align attrs MUST reach the op: the reference's default is
    # align_corners=True and the kernels branch on it (r5 review: they
    # were silently dropped here)
    return _single_out_layer(helper, op, {"X": [input]},
                             {"out_h": out_shape[0], "out_w": out_shape[1],
                              "align_corners": bool(align_corners),
                              "align_mode": int(align_mode)})


def resize_bilinear(input, out_shape=None, scale=None, name=None,
                    align_corners=True, align_mode=1, **kw):
    return image_resize(input, out_shape, scale, name, "BILINEAR",
                        align_corners=align_corners, align_mode=align_mode)


def resize_nearest(input, out_shape=None, scale=None, name=None,
                   align_corners=True, **kw):
    return image_resize(input, out_shape, scale, name, "NEAREST",
                        align_corners=align_corners)


def uniform_random(shape, dtype="float32", min=-1.0, max=1.0, seed=0):
    helper = LayerHelper("uniform_random")
    out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op("uniform_random", outputs={"Out": [out]},
                     attrs={"shape": list(shape), "dtype": dtype,
                            "min": float(min), "max": float(max), "seed": seed})
    return out


def gaussian_random(shape, mean=0.0, std=1.0, seed=0, dtype="float32"):
    helper = LayerHelper("gaussian_random")
    out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op("gaussian_random", outputs={"Out": [out]},
                     attrs={"shape": list(shape), "dtype": dtype,
                            "mean": float(mean), "std": float(std), "seed": seed})
    return out


def sampling_id(x, min=0.0, max=1.0, seed=0, dtype="float32"):
    # sample an id from each row's multinomial distribution
    helper = LayerHelper("sampling_id")
    cum = cumsum(x, axis=-1)
    r = uniform_random([x.shape[0], 1], dtype=x.dtype, min=0.0, max=1.0, seed=seed)
    ge = cast(greater_equal(cum, r), "int64")
    return argmax(ge, axis=-1)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """im2col (reference unfold_op.cc): [N, C, H, W] → [N, C*kh*kw, L]."""
    def _pair(v):
        return list(v) if isinstance(v, (list, tuple)) else [v, v]

    helper = LayerHelper("unfold", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op("unfold", inputs={"X": [x]}, outputs={"Y": [out]},
                     attrs={"kernel_sizes": _pair(kernel_sizes),
                            "strides": _pair(strides),
                            "paddings": _pair(paddings),
                            "dilations": _pair(dilations)})
    return out


def group_norm_(*a, **k):
    return group_norm(*a, **k)


# ---------------------------------------------------------------------------
# sequence layers (reference layers/nn.py sequence_* → operators/sequence_ops/).
# TPU-native representation: padded dense [B, T, D] + optional lengths [B]
# instead of LoD offsets (see paddle_tpu/ops/sequence_ops.py).
# ---------------------------------------------------------------------------


def sequence_conv(input, num_filters, filter_size=3, filter_stride=1,
                  padding=True, bias_attr=None, param_attr=None, act=None,
                  name=None, length=None):
    if filter_stride != 1:
        raise ValueError(
            "sequence_conv supports contextStride == 1 only (same "
            "restriction as the reference sequence_conv_op.cc)")
    helper = LayerHelper("sequence_conv", act=act, name=name, size=num_filters,
                         bias_attr=bias_attr)
    d = input.shape[-1]
    w = helper.create_parameter(param_attr, shape=[filter_size * d, num_filters],
                                dtype=input.dtype)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    inputs = {"X": [input], "Filter": [w]}
    if length is not None:
        inputs["Length"] = [length]
    helper.append_op("sequence_conv", inputs=inputs, outputs={"Out": [out]},
                     attrs={"contextLength": filter_size,
                            "contextStart": -((filter_size - 1) // 2),
                            "contextStride": filter_stride})
    out = helper.append_bias_op(out, dim_start=2)
    return helper.append_activation(out)


def sequence_pool(input, pool_type="average", is_test=False, length=None):
    helper = LayerHelper("sequence_pool")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    max_index = helper.create_variable_for_type_inference("int32", stop_gradient=True)
    inputs = {"X": [input]}
    if length is not None:
        inputs["Length"] = [length]
    helper.append_op("sequence_pool", inputs=inputs,
                     outputs={"Out": [out], "MaxIndex": [max_index]},
                     attrs={"pooltype": pool_type.upper()})
    return out


def sequence_softmax(input, use_cudnn=False, name=None, length=None):
    helper = LayerHelper("sequence_softmax", name=name)
    inputs = {"X": [input]}
    if length is not None:
        inputs["Length"] = [length]
    return _single_out_layer(helper, "sequence_softmax", inputs)


def sequence_expand(x, y, ref_level=-1, name=None):
    helper = LayerHelper("sequence_expand", name=name)
    return _single_out_layer(helper, "sequence_expand", {"X": [x], "Y": [y]})


def sequence_reverse(x, name=None, length=None):
    helper = LayerHelper("sequence_reverse", name=name)
    inputs = {"X": [x]}
    if length is not None:
        inputs["Length"] = [length]
    return _single_out_layer(helper, "sequence_reverse", inputs)


def sequence_first_step(input, length=None):
    return sequence_pool(input, pool_type="first", length=length)


def sequence_last_step(input, length=None):
    return sequence_pool(input, pool_type="last", length=length)


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    if maxlen is None:
        # reference semantics (sequence_mask_op.cc): maxlen=None means max(x),
        # a data-dependent extent XLA's static shapes cannot express
        raise ValueError(
            "sequence_mask requires an explicit maxlen on TPU: the reference's "
            "maxlen=None (max of the lengths) is a data-dependent shape")
    helper = LayerHelper("sequence_mask", name=name)
    out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op("sequence_mask", inputs={"X": [x]}, outputs={"Y": [out]},
                     attrs={"maxlen": int(maxlen), "out_dtype": dtype})
    return out


def sequence_unpad(x, length, name=None):
    """Zero the padding tail (dense analog of reference sequence_unpad)."""
    helper = LayerHelper("sequence_unpad", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op("sequence_unpad", inputs={"X": [x], "Length": [length]},
                     outputs={"Out": [out]}, attrs={})
    return out


def sequence_concat(input, lengths=None, name=None):
    """Row-wise concat of valid prefixes (reference sequence_concat);
    lengths: optional list matching `input`.  Returns (out, out_lengths)
    when lengths given, else out."""
    helper = LayerHelper("sequence_concat", name=name)
    out = helper.create_variable_for_type_inference(dtype=input[0].dtype)
    out_len = helper.create_variable_for_type_inference("int32",
                                                        stop_gradient=True)
    inputs = {"X": list(input)}
    if lengths is not None:
        inputs["Length"] = list(lengths)
    helper.append_op("sequence_concat", inputs=inputs,
                     outputs={"Out": [out], "OutLength": [out_len]}, attrs={})
    return (out, out_len) if lengths is not None else out


def sequence_expand_as(x, y, name=None):
    helper = LayerHelper("sequence_expand_as", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op("sequence_expand_as", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]}, attrs={})
    return out


def sequence_slice(input, offset, length, name=None):
    """Per-row time window, left-aligned and zero-padded (reference
    sequence_slice)."""
    helper = LayerHelper("sequence_slice", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("sequence_slice",
                     inputs={"X": [input], "Offset": [offset],
                             "Length": [length]},
                     outputs={"Out": [out]}, attrs={})
    return out


def sequence_enumerate(input, win_size, pad_value=0, length=None, name=None):
    """Sliding id windows [B, T] → [B, T, win] (reference
    sequence_enumerate)."""
    helper = LayerHelper("sequence_enumerate", name=name)
    out = helper.create_variable_for_type_inference("int64",
                                                    stop_gradient=True)
    inputs = {"X": [input]}
    if length is not None:
        inputs["Length"] = [length]
    helper.append_op("sequence_enumerate", inputs=inputs,
                     outputs={"Out": [out]},
                     attrs={"win_size": win_size, "pad_value": pad_value})
    return out


def kldiv_loss(x, target, reduction="mean", name=None):
    """KL divergence with x = log-probs (reference nn.py kldiv_loss)."""
    helper = LayerHelper("kldiv_loss", name=name)
    return _single_out_layer(helper, "kldiv_loss",
                             {"X": [x], "Target": [target]},
                             {"reduction": reduction})


def margin_rank_loss(label, left, right, margin=0.1, name=None):
    helper = LayerHelper("margin_rank_loss", name=name)
    out = helper.create_variable_for_type_inference(dtype=left.dtype)
    act = helper.create_variable_for_type_inference(dtype=left.dtype,
                                                    stop_gradient=True)
    helper.append_op("margin_rank_loss",
                     inputs={"X1": [left], "X2": [right], "Label": [label]},
                     outputs={"Out": [out], "Activated": [act]},
                     attrs={"margin": float(margin)})
    return out


def rank_loss(label, left, right, name=None):
    helper = LayerHelper("rank_loss", name=name)
    return _single_out_layer(helper, "rank_loss",
                             {"Left": [left], "Right": [right],
                              "Label": [label]})


def hinge_loss(input, label, name=None):
    helper = LayerHelper("hinge_loss", name=name)
    return _single_out_layer(helper, "hinge_loss",
                             {"Logits": [input], "Labels": [label]})


def bpr_loss(input, label, name=None):
    helper = LayerHelper("bpr_loss", name=name)
    return _single_out_layer(helper, "bpr_loss",
                             {"X": [input], "Label": [label]})


def maxout(x, groups, name=None):
    helper = LayerHelper("maxout", name=name)
    return _single_out_layer(helper, "maxout", {"X": [x]},
                             {"groups": groups})


def selu(x, scale=None, alpha=None, name=None):
    helper = LayerHelper("selu", name=name)
    attrs = {}
    if scale is not None:
        attrs["scale"] = float(scale)
    if alpha is not None:
        attrs["alpha"] = float(alpha)
    return _single_out_layer(helper, "selu", {"X": [x]}, attrs)


def pixel_shuffle(x, upscale_factor, name=None):
    helper = LayerHelper("pixel_shuffle", name=name)
    return _single_out_layer(helper, "pixel_shuffle", {"X": [x]},
                             {"upscale_factor": upscale_factor})


def shuffle_channel(x, group, name=None):
    helper = LayerHelper("shuffle_channel", name=name)
    return _single_out_layer(helper, "shuffle_channel", {"X": [x]},
                             {"group": group})


def affine_channel(x, scale=None, bias=None, data_layout="NCHW", name=None):
    """Per-channel affine; None scale/bias act as identity."""
    helper = LayerHelper("affine_channel", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    inputs = {"X": [x]}
    if scale is not None:
        inputs["Scale"] = [scale]
    if bias is not None:
        inputs["Bias"] = [bias]
    helper.append_op("affine_channel", inputs=inputs,
                     outputs={"Out": [out]},
                     attrs={"data_layout": data_layout})
    return out


def grid_sampler(x, grid, name=None):
    helper = LayerHelper("grid_sampler", name=name)
    return _single_out_layer(helper, "grid_sampler",
                             {"X": [x], "Grid": [grid]})


def crop(x, shape, offsets=None, name=None):
    """Static-shape crop (reference nn.py crop); offsets may be a tensor
    (dynamic_slice) or a list attr."""
    helper = LayerHelper("crop", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    inputs = {"X": [x]}
    attrs = {"shape": list(shape)}
    if isinstance(offsets, Variable):
        inputs["Offsets"] = [offsets]
    elif offsets is not None:
        attrs["offsets"] = list(offsets)
    helper.append_op("crop", inputs=inputs, outputs={"Out": [out]},
                     attrs=attrs)
    return out


def im2sequence(input, filter_size=1, stride=1, padding=0, name=None):
    """Image → patch sequence [B, T, C*kh*kw] (dense analog of reference
    nn.py im2sequence)."""
    helper = LayerHelper("im2sequence", name=name)
    fs = filter_size if isinstance(filter_size, (list, tuple)) else [filter_size] * 2
    st = stride if isinstance(stride, (list, tuple)) else [stride] * 2
    pd = padding if isinstance(padding, (list, tuple)) else [padding] * 2
    return _single_out_layer(helper, "im2sequence", {"X": [input]},
                             {"kernels": list(fs), "strides": list(st),
                              "paddings": list(pd)})


def chunk_eval(input, label, chunk_scheme, num_chunk_types, length=None,
               name=None):
    """Chunking F1 (reference nn.py chunk_eval → chunk_eval op, IOB
    scheme).  Returns (precision, recall, f1, n_infer, n_label, n_correct)."""
    helper = LayerHelper("chunk_eval", name=name)
    outs = {s: helper.create_variable_for_type_inference(
        dtype="float32" if i < 3 else "int32", stop_gradient=True)
        for i, s in enumerate(["Precision", "Recall", "F1-Score",
                               "NumInferChunks", "NumLabelChunks",
                               "NumCorrectChunks"])}
    inputs = {"Inference": [input], "Label": [label]}
    if length is not None:
        inputs["Length"] = [length]
    helper.append_op("chunk_eval", inputs=inputs,
                     outputs={k: [v] for k, v in outs.items()},
                     attrs={"chunk_scheme": chunk_scheme,
                            "num_chunk_types": num_chunk_types})
    o = outs
    return (o["Precision"], o["Recall"], o["F1-Score"],
            o["NumInferChunks"], o["NumLabelChunks"], o["NumCorrectChunks"])


def softmax_mask_fuse_upper_triangle(x, name=None):
    """Causal softmax: softmax(x) with the upper triangle (future positions)
    masked to -inf, fused (reference fused/fused_softmax_mask_upper_triangle
    family).  x: [..., S, S] attention scores."""
    helper = LayerHelper("softmax_mask_fuse_upper_triangle", name=name)
    return _single_out_layer(helper, "softmax_mask_fuse_upper_triangle",
                             {"X": [x]})


def flash_attention(q, k, v, attn_bias=None, causal=False, sm_scale=None,
                    sequence_parallel=False, name=None):
    """Memory-efficient attention over [B, n_heads, S, d] (Pallas kernel on
    TPU; see paddle_tpu/kernels/flash_attention.py).  attn_bias: additive
    [B, 1, 1, S] key bias (padding mask).  sequence_parallel: under a mesh
    with an 'sp' axis, lower to ring attention (K/V rotate via ppermute)."""
    helper = LayerHelper("flash_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if attn_bias is not None:
        inputs["Bias"] = [attn_bias]
    attrs = {"causal": causal}
    if sequence_parallel:
        attrs["sequence_parallel"] = True
    if sm_scale is not None:
        attrs["sm_scale"] = float(sm_scale)
    helper.append_op("flash_attention", inputs=inputs, outputs={"Out": [out]},
                     attrs=attrs)
    return out


def moe_ffn(x, num_experts, d_ff, top_k=2, act="gelu", param_attr=None,
            name=None):
    """Mixture-of-experts feed-forward over [B, S, D] (ops/nn_ops.py
    moe_ffn — dense dispatch, expert dim shardable over the 'ep' mesh
    axis).  No reference analog; expert-parallel building block."""
    helper = LayerHelper("moe_ffn", name=name)
    d = x.shape[-1]
    pname = name or helper.name
    init = (param_attr.initializer
            if param_attr is not None and param_attr.initializer else
            Normal(0.0, 0.02))
    gate = helper.create_parameter(
        ParamAttr(name=pname + "_moe_gate.w_0", initializer=init),
        shape=[d, num_experts])
    w1 = helper.create_parameter(
        ParamAttr(name=pname + "_moe_w1.w_0", initializer=init),
        shape=[num_experts, d, d_ff])
    b1 = helper.create_parameter(
        ParamAttr(name=pname + "_moe_w1.b_0", initializer=Constant(0.0)),
        shape=[num_experts, d_ff], is_bias=True)
    w2 = helper.create_parameter(
        ParamAttr(name=pname + "_moe_w2.w_0", initializer=init),
        shape=[num_experts, d_ff, d])
    b2 = helper.create_parameter(
        ParamAttr(name=pname + "_moe_w2.b_0", initializer=Constant(0.0)),
        shape=[num_experts, d], is_bias=True)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("moe_ffn",
                     inputs={"X": [x], "GateW": [gate], "W1": [w1],
                             "B1": [b1], "W2": [w2], "B2": [b2]},
                     outputs={"Out": [out]},
                     attrs={"top_k": int(top_k), "act": act})
    return out


def paged_attention(q, k_pages, v_pages, page_table, q_start,
                    sm_scale=None, force=None, name=None, window=None,
                    sinks=None):
    """Attention of q [B, n_heads, T, d] against pool K/V
    [num_pages, page_size, n_kv_heads*d] (n_kv_heads = n_heads, or a
    divisor of it: grouped-query heads) read THROUGH a per-sequence page
    table (decode serving lane, docs/SERVING.md "Decode lane";
    kernels/paged_attention.py — Pallas on TPU, lax gather reference on
    CPU).  Query i of row b attends global key positions
    j <= q_start[b] + i and, with ``window``, j > q_start[b] + i -
    window.  The V pool may hold its heads at another width d_v (the
    output is [B, n_heads, T, d_v]); ``sinks`` [n_heads] float32 joins
    each head's softmax as one more column, which carries no value."""
    helper = LayerHelper("paged_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    attrs = {}
    if window is not None:
        attrs["window"] = int(window)
    if sm_scale is not None:
        attrs["sm_scale"] = float(sm_scale)
    if force is not None:
        attrs["force"] = force
    inputs = {"Q": [q], "KPages": [k_pages], "VPages": [v_pages],
              "PageTable": [page_table], "QStart": [q_start]}
    if sinks is not None:
        inputs["Sinks"] = [sinks]
    helper.append_op("paged_attention", inputs=inputs,
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def kv_cache_write(pages, new, page_idx, offset, name=None):
    """Scatter one decode step's K or V rows (new [B, n, d], flattened
    to the pool's [B, n*d] rows) into the KV pool
    [num_pages, page_size, n*d] at per-slot (page_idx[b], offset[b])
    coordinates; returns
    the updated pool var (aliasing `pages` — XLA buffer donation, the
    pool is never doubled).  Payload dtype must match the pool dtype
    (trace-time error otherwise — the mixed-precision guard)."""
    helper = LayerHelper("kv_cache_write", name=name)
    # PagesOut IS Pages (the optimizer-op ParamOut convention): the pool
    # var is persistable, so the executor writes the update back to the
    # scope and donates the old buffer
    helper.append_op("kv_cache_write",
                     inputs={"Pages": [pages], "New": [new],
                             "PageIdx": [page_idx], "Offset": [offset]},
                     outputs={"PagesOut": [pages]})
    return pages


def kv_cache_write_pages(pages, new, page_idx, name=None):
    """Scatter a prefill chunk's K or V (new [C, n, d], C a multiple of
    the pool page size) into whole pool pages page_idx [C/page_size];
    returns the updated pool var (aliasing `pages`).  Same dtype guard
    as kv_cache_write."""
    helper = LayerHelper("kv_cache_write_pages", name=name)
    helper.append_op("kv_cache_write_pages",
                     inputs={"Pages": [pages], "New": [new],
                             "PageIdx": [page_idx]},
                     outputs={"PagesOut": [pages]})
    return pages


def ragged_attention(q, k, v, lengths, causal=False, sm_scale=None,
                     force=None, name=None):
    """Variable-length attention over [B, n_heads, S, d] driven by a
    per-row length vector (kernels/primitives/ragged.py; docs/SERVING.md
    "Ragged serving"): row b attends key positions j < lengths[b] (and
    j <= i when causal) — padded positions are never scored, so one
    fixed S serves every mixed-length batch.  Inference-only."""
    helper = LayerHelper("ragged_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    attrs = {"causal": causal}
    if sm_scale is not None:
        attrs["sm_scale"] = float(sm_scale)
    if force is not None:
        attrs["force"] = force
    helper.append_op("ragged_attention",
                     inputs={"Q": [q], "K": [k], "V": [v],
                             "Lengths": [lengths]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def paged_attention_quant(q, k_hi, k_lo, k_scale, v_hi, v_lo, v_scale,
                          page_table, q_start, sm_scale=None, force=None,
                          name=None):
    """paged_attention over the dual-int8 pool (hi/lo int8
    [num_pages, page_size, n*d] + one fp32 scale a head_dim vector
    [num_pages, page_size, n]; docs/KERNELS.md "int8 KV") — dequant
    happens inside the kernel, fp32 K/V never materializes outside
    VMEM."""
    helper = LayerHelper("paged_attention_quant", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    attrs = {}
    if sm_scale is not None:
        attrs["sm_scale"] = float(sm_scale)
    if force is not None:
        attrs["force"] = force
    helper.append_op("paged_attention_quant",
                     inputs={"Q": [q], "KHi": [k_hi], "KLo": [k_lo],
                             "KScale": [k_scale], "VHi": [v_hi],
                             "VLo": [v_lo], "VScale": [v_scale],
                             "PageTable": [page_table],
                             "QStart": [q_start]},
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def kv_cache_write_quant(hi, lo, scale, new, page_idx, offset, name=None):
    """kv_cache_write for the int8 pool: quantize one decode step's K or
    V rows (new [B, n, d]) at append and scatter hi/lo/scale at per-slot
    (page_idx[b], offset[b]) coordinates; returns the updated pool vars
    (aliasing, the ParamOut convention)."""
    helper = LayerHelper("kv_cache_write_quant", name=name)
    helper.append_op("kv_cache_write_quant",
                     inputs={"Hi": [hi], "Lo": [lo], "Scale": [scale],
                             "New": [new], "PageIdx": [page_idx],
                             "Offset": [offset]},
                     outputs={"HiOut": [hi], "LoOut": [lo],
                              "ScaleOut": [scale]})
    return hi, lo, scale


def kv_cache_write_pages_quant(hi, lo, scale, new, page_idx, name=None):
    """kv_cache_write_pages for the int8 pool: quantize a prefill
    chunk's K or V (new [C, n, d]) at append and scatter whole pages of
    hi/lo/scale; returns the updated pool vars (aliasing)."""
    helper = LayerHelper("kv_cache_write_pages_quant", name=name)
    helper.append_op("kv_cache_write_pages_quant",
                     inputs={"Hi": [hi], "Lo": [lo], "Scale": [scale],
                             "New": [new], "PageIdx": [page_idx]},
                     outputs={"HiOut": [hi], "LoOut": [lo],
                              "ScaleOut": [scale]})
    return hi, lo, scale


# ---------------------------------------------------------------------------
# latent attention, learned sparse attention, held experts (ops/mla_ops.py;
# models/glm.py).  Inference-only; results are float32.
# ---------------------------------------------------------------------------


def _out_f32(helper, op, inputs, attrs=None):
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op(op, inputs=inputs, outputs={"Out": [out]},
                     attrs=attrs or {})
    return out


def weight_matmul(x, size, param_attr=None, dtype="float32", name=None,
                  head_dim=None):
    """x [.., K] @ W [K, size], W stored in ``dtype`` and the product
    taken in it with float32 accumulation (no bias).  ``head_dim``: the
    width of the heads the caller reshapes the product into, if it does
    (the op pins the product's layout where they are no whole lane
    tiles: ops/mla_ops.py ``_pin_product``)."""
    helper = LayerHelper("weight_matmul", name=name)
    w = helper.create_parameter(param_attr, shape=[x.shape[-1], size],
                                dtype=dtype,
                                default_initializer=Normal(0.0, 0.02))
    return _out_f32(helper, "weight_matmul", {"X": [x], "W": [w]},
                    {"head_dim": int(head_dim)} if head_dim else None)


def headwise_matmul(x, size, param_attr=None, dtype="float32", name=None):
    """x [B, T, H, a] times one [a, size] matrix a head, W [H, a, size]."""
    helper = LayerHelper("headwise_matmul", name=name)
    w = helper.create_parameter(
        param_attr, shape=[x.shape[-2], x.shape[-1], size], dtype=dtype,
        default_initializer=Normal(0.0, 0.02))
    return _out_f32(helper, "headwise_matmul", {"X": [x], "W": [w]})


def rms_norm(x, epsilon=1e-5, param_attr=None, dtype="float32", name=None,
             gain_offset=0.0):
    """x rsqrt(mean x^2 + eps) (``gain_offset`` + scale) over the last
    dimension; ``gain_offset`` 1 is a zero-centred gain (``1 + w``, the
    stored ``w`` starting at 0)."""
    helper = LayerHelper("rms_norm", name=name)
    scale = helper.create_parameter(
        param_attr, shape=[x.shape[-1]], dtype=dtype,
        default_initializer=Constant(1.0 - float(gain_offset)))
    attrs = {"epsilon": float(epsilon)}
    if gain_offset:
        attrs["gain_offset"] = float(gain_offset)
    return _out_f32(helper, "rms_norm", {"X": [x], "Scale": [scale]}, attrs)


def swiglu(gate, up, name=None):
    """silu(gate) * up."""
    return _out_f32(LayerHelper("swiglu", name=name), "swiglu",
                    {"Gate": [gate], "Up": [up]})


def rope_interleaved(x, pos, theta, rotary_dim, name=None):
    """Rotary embedding on the first ``rotary_dim`` entries of x's last
    dimension, pairs interleaved; x [B, T, d] or [B, T, H, d], pos [B, T]."""
    return _out_f32(LayerHelper("rope_interleaved", name=name),
                    "rope_interleaved", {"X": [x], "Pos": [pos]},
                    {"theta": float(theta), "rotary_dim": int(rotary_dim)})


def rope_half(x, pos, theta, rotary_dim=None, name=None):
    """Rotary embedding in the ``rotate_half`` form over the whole last
    dimension of x [B, T, H, d], or over its first ``rotary_dim``
    entries alone; pos [B, T] (ops/gqa_ops.py)."""
    attrs = {"theta": float(theta)}
    if rotary_dim is not None:
        attrs["rotary_dim"] = int(rotary_dim)
    return _out_f32(LayerHelper("rope_half", name=name), "rope_half",
                    {"X": [x], "Pos": [pos]}, attrs)


def sigmoid_gate(x, gate, name=None):
    """x * sigmoid(gate), elementwise."""
    return _out_f32(LayerHelper("sigmoid_gate", name=name), "sigmoid_gate",
                    {"X": [x], "Gate": [gate]})


def dsa_indexer_scores(q, w, index_pages, page_table, q_start, force=None,
                       name=None):
    """Indexer scores sum_j w_j relu(q_j . k) of q [B, T, Hi, Di] against
    the paged indexer cache [num_pages, page_size, Di] → [B, T, Lp]
    float32 (kernels/primitives/dsa.py)."""
    attrs = {} if force is None else {"force": force}
    return _out_f32(LayerHelper("dsa_indexer_scores", name=name),
                    "dsa_indexer_scores",
                    {"Q": [q], "W": [w], "IndexPages": [index_pages],
                     "PageTable": [page_table], "QStart": [q_start]}, attrs)


def dsa_topk_select(scores, k, force=None, name=None):
    """Additive mask of the k largest scores a query (exact)."""
    attrs = {"k": int(k)}
    if force is not None:
        attrs["force"] = force
    return _out_f32(LayerHelper("dsa_topk_select", name=name),
                    "dsa_topk_select", {"Scores": [scores]}, attrs)


def sparse_mla_attention(q_latent, q_rope, latent_pages, page_table,
                         selected, q_start, sm_scale, force=None, name=None):
    """Latent-space attention over the selected rows of the paged latent
    cache [num_pages, page_size, C + R] → [B, T, H, C] float32."""
    attrs = {"sm_scale": float(sm_scale)}
    if force is not None:
        attrs["force"] = force
    return _out_f32(LayerHelper("sparse_mla_attention", name=name),
                    "sparse_mla_attention",
                    {"QLatent": [q_latent], "QRope": [q_rope],
                     "LatentPages": [latent_pages],
                     "PageTable": [page_table], "Selected": [selected],
                     "QStart": [q_start]}, attrs)


def paged_mla_attention(q_latent, q_rope, latent_pages, page_table, q_start,
                        sm_scale, force=None, name=None):
    """Latent-space attention over every visible row of the paged latent
    cache [num_pages, page_size, >= C + R] -> [B, T, H, C] float32 (the
    decode step's form; kernels/primitives/mla.py)."""
    attrs = {"sm_scale": float(sm_scale)}
    if force is not None:
        attrs["force"] = force
    return _out_f32(LayerHelper("paged_mla_attention", name=name),
                    "paged_mla_attention",
                    {"QLatent": [q_latent], "QRope": [q_rope],
                     "LatentPages": [latent_pages],
                     "PageTable": [page_table], "QStart": [q_start]}, attrs)


def mla_chunk_attention(q_nope, q_rope, latent_pages, page_table, q_start,
                        kv_lora_rank, v_head_dim, sm_scale, k_attr=None,
                        v_attr=None, dtype="float32", force=None, name=None):
    """Head-space attention of q [B, T, H, nope | R] over every visible
    row of the paged latent cache, the rows up-projected inside the
    kernel by the two halves of the KV up-projection, ``k_attr``
    [H, nope, C] and ``v_attr`` [H, C, v] (the parameters
    ``headwise_matmul`` holds in the decode step) -> [B, T, H, v]
    float32 (the prefill chunk's form; kernels/primitives/mla.py)."""
    helper = LayerHelper("mla_chunk_attention", name=name)
    heads, nope = q_nope.shape[-2], q_nope.shape[-1]
    init = Normal(0.0, 0.02)
    w_uk = helper.create_parameter(
        k_attr, shape=[heads, nope, int(kv_lora_rank)], dtype=dtype,
        default_initializer=init)
    w_uv = helper.create_parameter(
        v_attr, shape=[heads, int(kv_lora_rank), int(v_head_dim)],
        dtype=dtype, default_initializer=init)
    attrs = {"sm_scale": float(sm_scale)}
    if force is not None:
        attrs["force"] = force
    return _out_f32(helper, "mla_chunk_attention",
                    {"QNope": [q_nope], "QRope": [q_rope],
                     "LatentPages": [latent_pages],
                     "PageTable": [page_table], "QStart": [q_start],
                     "WUk": [w_uk], "WUv": [w_uv]}, attrs)


def bicubic_resize_table(table, out_h, out_w, name=None):
    """A learned [h0, w0, D] position table resized (bicubic, a = -0.75,
    half-pixel centres) to [out_h * out_w, D] float32."""
    return _out_f32(LayerHelper("bicubic_resize_table", name=name),
                    "bicubic_resize_table", {"X": [table]},
                    {"out_h": int(out_h), "out_w": int(out_w)})


def rope_2d_interleaved(x, grid_h, grid_w, theta, name=None):
    """Rotary embedding of x [N, H, d] by each patch's (row, column) on a
    (grid_h, grid_w) grid in row-major order (ops/vision_ops.py)."""
    return _out_f32(LayerHelper("rope_2d_interleaved", name=name),
                    "rope_2d_interleaved", {"X": [x]},
                    {"grid_h": int(grid_h), "grid_w": int(grid_w),
                     "theta": float(theta)})


def vit_attention(q, k, v, sm_scale, dtype="float32", force=None, name=None):
    """Bidirectional attention inside one image: q, k, v [N, H, d] ->
    [N, H, d] float32, operands rounded to ``dtype``
    (kernels/primitives/vit.py)."""
    attrs = {"sm_scale": float(sm_scale), "dtype": dtype}
    if force is not None:
        attrs["force"] = force
    return _out_f32(LayerHelper("vit_attention", name=name), "vit_attention",
                    {"Q": [q], "K": [k], "V": [v]}, attrs)


def select_embedding_rows(emb, rows, idx, name=None):
    """A chunk's input rows: ``emb`` [B, T, D], or, where ``idx`` [B, T]
    is >= 0, that row of the staged image rows ``rows`` [R, 1, D]."""
    return _out_f32(LayerHelper("select_embedding_rows", name=name),
                    "select_embedding_rows",
                    {"Emb": [emb], "Rows": [rows], "Idx": [idx]})


# ---------------------------------------------------------------------------
# a linear-attention (gated delta rule) layer over per-sequence state
# (ops/gdn_ops.py; models/olmo_hybrid.py).  Inference-only; float32.
# ---------------------------------------------------------------------------


def short_conv(x, kernel, tail, block, q_start=None, last_idx=None,
               param_attr=None, name=None):
    """Depthwise causal convolution of ``kernel`` taps over time, then
    SiLU, of x [B, T, ch]; its last ``kernel`` - 1 pre-activation inputs
    are carried in the state var ``tail`` [blocks, (kernel - 1) * ch],
    updated in place.  With ``q_start`` and ``last_idx`` ([1] each) x is
    one sequence's chunk and ``block`` [1] its block (read as zeros where
    ``q_start`` is 0, written up to position ``last_idx``); without them
    x is one token a slot and ``block`` [B] each slot's block."""
    helper = LayerHelper("short_conv", name=name)
    w = helper.create_parameter(
        param_attr, shape=[int(kernel), x.shape[-1]], dtype="float32",
        default_initializer=Normal(0.0, 0.3))
    out = helper.create_variable_for_type_inference("float32")
    inputs = {"X": [x], "W": [w], "Tail": [tail], "Block": [block]}
    op = "short_conv_step"
    if q_start is not None:
        op = "short_conv_chunk"
        inputs.update({"QStart": [q_start], "LastIdx": [last_idx]})
    helper.append_op(op, inputs=inputs,
                     outputs={"Out": [out], "TailOut": [tail]})
    return out


def gdn_inputs(qkv, a, b, heads, key_dim, value_dim, beta_scale=1.0,
               epsilon=1e-6, row_valid=None, a_log_attr=None,
               dt_bias_attr=None, key_heads=None, name=None):
    """The gated delta rule's operands from the convolved projections
    qkv [B, T, 2 H d_k + H d_v] and the gate projections a, b [B, T, H]:
    (q [B, T, H, d_k] L2-normalised and scaled by d_k^-1/2, k
    L2-normalised, v [B, T, H, d_v], g = -exp(A_log) softplus(a +
    dt_bias), beta = ``beta_scale`` sigmoid(b)).  With a [B, T, H d_k]
    the decay is one number a key channel: dt_bias is [H d_k] (A_log
    stays [H]) and g [B, T, H, d_k].  ``row_valid`` [T]: rows marked 0
    get beta = 0 and g = 0.  ``key_heads`` (H_k, a divisor of H; H where
    None): q and k have H_k heads, qkv is [B, T, 2 H_k d_k + H d_v], and
    value head h reads key head h // (H / H_k) in ``gated_delta_rule``."""
    helper = LayerHelper("gdn_inputs", name=name)
    hk = int(heads if key_heads is None else key_heads)
    if int(heads) % hk or int(qkv.shape[-1]) != (
            2 * hk * int(key_dim) + int(heads) * int(value_dim)):
        raise ValueError(
            f"gdn_inputs: {heads} value heads on {hk} key heads of "
            f"{key_dim} / {value_dim} want whole groups and qkv "
            f"{2 * hk * int(key_dim) + int(heads) * int(value_dim)} wide, "
            f"got {qkv.shape[-1]}")
    if int(a.shape[-1]) not in (int(heads), int(heads) * int(key_dim)):
        raise ValueError(
            f"gdn_inputs: the gate projection is {a.shape[-1]} wide, wanted "
            f"{heads} (a decay a head) or {heads * key_dim} (a decay a key "
            f"channel)")
    a_log = helper.create_parameter(a_log_attr, shape=[int(heads)],
                                    dtype="float32",
                                    default_initializer=Constant(0.0))
    dt_bias = helper.create_parameter(dt_bias_attr,
                                      shape=[int(a.shape[-1])],
                                      dtype="float32",
                                      default_initializer=Constant(0.0))
    outs = [helper.create_variable_for_type_inference("float32")
            for _ in range(5)]
    inputs = {"QKV": [qkv], "A": [a], "B": [b], "ALog": [a_log],
              "DtBias": [dt_bias]}
    if row_valid is not None:
        inputs["RowValid"] = [row_valid]
    attrs = {"heads": int(heads), "key_dim": int(key_dim),
             "value_dim": int(value_dim), "beta_scale": float(beta_scale),
             "epsilon": float(epsilon)}
    if hk != int(heads):
        attrs["key_heads"] = hk
    helper.append_op(
        "gdn_inputs", inputs=inputs,
        outputs=dict(zip(("Q", "K", "V", "G", "Beta"),
                         ([o] for o in outs))), attrs=attrs)
    return outs


def gated_delta_rule(q, k, v, g, beta, state, block, q_start=None,
                     force=None, name=None):
    """The gated delta rule over the per-sequence state var ``state``
    [blocks, d_k, H * d_v] (kernels/primitives/gdn.py; kda.py where g
    is [B, T, H, d_k], a decay a key channel), updated in place
    -> [B, T, H, d_v] float32; q and k may have fewer heads than v (whole
    groups of value heads a key head).  With ``q_start`` [1] the operands
    are one sequence's chunk [1, C, H, .] and ``block`` [1] its block (read as
    zeros where ``q_start`` is 0); without it they are one token a slot
    [B, 1, H, .] and ``block`` [B] each slot's block."""
    helper = LayerHelper("gated_delta_rule", name=name)
    out = helper.create_variable_for_type_inference("float32")
    inputs = {"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta],
              "State": [state], "Block": [block]}
    op = "gated_delta_step"
    if q_start is not None:
        op = "gated_delta_chunk"
        inputs["QStart"] = [q_start]
    helper.append_op(op, inputs=inputs,
                     outputs={"Out": [out], "StateOut": [state]},
                     attrs={} if force is None else {"force": force})
    return out


def gated_rms_norm(x, gate, epsilon=1e-6, param_attr=None,
                   activation="silu", name=None):
    """RMSNorm over each head's entries of x [B, T, H, d] (one gain [d])
    times ``activation``(gate [B, T, H * d]), ``silu`` or ``sigmoid``
    -> [B, T, H * d]."""
    if activation not in ("silu", "sigmoid"):
        raise ValueError(f"gated_rms_norm: activation {activation!r}, "
                         f"wanted 'silu' or 'sigmoid'")
    helper = LayerHelper("gated_rms_norm", name=name)
    scale = helper.create_parameter(param_attr, shape=[x.shape[-1]],
                                    dtype="float32",
                                    default_initializer=Constant(1.0))
    return _out_f32(helper, "gated_rms_norm",
                    {"X": [x], "Gate": [gate], "Scale": [scale]},
                    {"epsilon": float(epsilon), "activation": activation})


def moe_ffn_held(x, num_experts, held_experts, d_ff, top_k, first_expert=0,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 row_valid=None, stats=None, dtype="float32", force=None,
                 name=None, score_func="sigmoid"):
    """One chip's share of an expert-parallel SwiGLU expert layer over
    x [B, T, D] (ops/mla_ops.py moe_ffn_held): the router scores all
    ``num_experts`` (``score_func`` "sigmoid": sigmoid, selection bias,
    ``top_k`` picks, normalised, scaled; "softmax": a softmax over all of
    them in float32, its ``top_k`` largest, normalised, no bias
    parameter); this chip holds experts ``first_expert ..
    first_expert + held_experts`` and adds up the picks that land on
    them.  ``stats`` [held_experts + 2] int32 persistable, added to in
    place: the picks each held expert got and the picks that went
    elsewhere, over the rows ``row_valid`` marks (> 0), and the held
    experts this call touched."""
    if score_func not in ("sigmoid", "softmax"):
        raise ValueError(f"moe_ffn_held: score_func {score_func!r}, wanted "
                         f"'sigmoid' or 'softmax'")
    helper = LayerHelper("moe_ffn_held", name=name)
    d = x.shape[-1]
    pname = name or helper.name
    init = Normal(0.0, 0.02)

    def param(suffix, shape, dt=dtype, initializer=init):
        return helper.create_parameter(
            ParamAttr(name=f"{pname}_{suffix}", initializer=initializer),
            shape=shape, dtype=dt)

    inputs = {"X": [x], "RouterW": [param("router.w_0", [d, num_experts])]}
    if score_func == "sigmoid":
        inputs["RouterBias"] = [param("router.b_0", [num_experts],
                                      initializer=Constant(0.0))]
    inputs.update({
        "WGate": [param("experts_gate.w_0", [held_experts, d, d_ff])],
        "WUp": [param("experts_up.w_0", [held_experts, d, d_ff])],
        "WDown": [param("experts_down.w_0", [held_experts, d_ff, d])],
    })
    out = helper.create_variable_for_type_inference("float32")
    outputs = {"Out": [out]}
    if row_valid is not None:
        inputs["RowValid"] = [row_valid]
    if stats is not None:
        inputs["Stats"] = [stats]
        outputs["StatsOut"] = [stats]
    attrs = {"top_k": int(top_k), "first_expert": int(first_expert),
             "routed_scaling_factor": float(routed_scaling_factor),
             "norm_topk_prob": bool(norm_topk_prob)}
    if score_func != "sigmoid":
        attrs["score_func"] = score_func
    if force is not None:
        attrs["force"] = force
    helper.append_op("moe_ffn_held", inputs=inputs, outputs=outputs,
                     attrs=attrs)
    return out
