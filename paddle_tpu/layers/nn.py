"""NN layers (reference python/paddle/fluid/layers/nn.py -- 177 functions).

Each function builds ops into the default main program via LayerHelper,
mirroring the reference's graph-construction API; execution is deferred to
the XLA-compiling Executor.
"""
from __future__ import annotations

import numpy as np

from ..core.program import Variable
from ..core.types import as_datatype
from ..initializer import ConstantInitializer, NormalInitializer, \
    XavierInitializer
from ..layer_helper import LayerHelper

__all__ = [
    "fc", "embedding", "conv2d", "conv3d", "conv2d_transpose", "pool2d",
    "adaptive_pool2d", "batch_norm", "layer_norm", "group_norm",
    "instance_norm", "dropout", "softmax", "log_softmax",
    "cross_entropy", "softmax_with_cross_entropy",
    "sigmoid_cross_entropy_with_logits", "square_error_cost",
    "huber_loss", "log_loss", "smooth_l1", "hinge_loss",
    "margin_rank_loss", "bpr_loss", "kldiv_loss",
    "mean", "reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
    "reduce_prod", "reduce_all", "reduce_any",
    "matmul", "mul", "dot", "elementwise_add", "elementwise_sub",
    "elementwise_mul", "elementwise_div", "elementwise_max",
    "elementwise_min", "elementwise_pow", "elementwise_mod",
    "elementwise_floordiv",
    "reshape", "squeeze", "unsqueeze", "transpose", "flatten", "concat",
    "split", "stack", "unstack", "expand", "expand_as", "slice",
    "strided_slice", "gather", "gather_nd", "scatter", "pad", "pad2d",
    "crop", "one_hot", "topk", "argsort", "argmax", "argmin", "where",
    "scale", "cast", "clip", "clip_by_norm", "l2_normalize",
    "lrn", "relu", "leaky_relu", "prelu", "maxout", "swish",
    "hard_swish", "hard_sigmoid", "elu", "relu6", "pow", "soft_relu",
    "brelu", "label_smooth", "cos_sim", "dice_loss", "npair_loss",
    "image_resize", "resize_bilinear", "resize_nearest", "grid_sampler",
    "affine_grid", "affine_channel", "shuffle_channel", "pixel_shuffle",
    "roi_pool", "roi_align", "psroi_pool", "row_conv",
    "increment", "zeros_like", "ones_like", "shape", "reverse",
    "uniform_random_batch_size_like", "gaussian_random",
    "sampling_id", "sums", "sum", "lstm", "dynamic_lstm", "dynamic_gru",
    "gru_unit", "lstm_unit", "beam_search", "beam_search_decode",
    "sequence_conv", "sequence_pool", "sequence_softmax",
    "sequence_expand", "sequence_concat", "sequence_first_step",
    "sequence_last_step", "sequence_reshape", "sequence_pad",
    "sequence_unpad", "sequence_reverse", "sequence_slice",
    "sequence_enumerate", "sequence_expand_as", "sequence_scatter",
    "edit_distance", "ctc_greedy_decoder", "warpctc", "nce",
    "hsigmoid", "sampled_softmax_with_cross_entropy", "im2sequence",
    "multiplex", "smooth_l1_loss", "spectral_norm", "temporal_shift",
    "pixel_unshuffle", "unfold", "deformable_conv",
]


def _single_out(helper, op_type, inputs, attrs=None, dtype=None,
                out_slot="Out"):
    out = helper.create_variable_for_type_inference(
        dtype or helper.input_dtype() if helper.kwargs.get("input")
        is not None else dtype)
    helper.append_op(op_type, inputs, {out_slot: out}, attrs or {})
    return out


# ---------------------------------------------------------------------------
# dense / conv / norm
# ---------------------------------------------------------------------------
def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, is_test=False, name=None):
    """Fully-connected (reference layers/nn.py fc): out = act(X W + b).

    Multiple inputs are summed after their own matmuls, like the reference.
    """
    helper = LayerHelper("fc", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    inputs = input if isinstance(input, (list, tuple)) else [input]
    param_attrs = helper.param_attr
    if not isinstance(param_attrs, (list, tuple)):
        param_attrs = [param_attrs] * len(inputs)
    mul_results = []
    for x, pattr in zip(inputs, param_attrs):
        in_features = int(np.prod(x.shape[num_flatten_dims:]))
        w = helper.create_parameter(pattr, [in_features, size], x.dtype)
        tmp = helper.create_variable_for_type_inference(x.dtype)
        helper.append_op("mul", {"X": x, "Y": w}, {"Out": tmp},
                         {"x_num_col_dims": num_flatten_dims,
                          "y_num_col_dims": 1})
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(
            inputs[0].dtype)
        helper.append_op("sum", {"X": mul_results}, {"Out": pre_bias}, {})
    pre_act = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims)
    return helper.append_activation(pre_act)


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32",
              name=None):
    """reference layers/nn.py embedding -> lookup_table op."""
    helper = LayerHelper("embedding", param_attr=param_attr, name=name)
    w = helper.create_parameter(helper.param_attr, size, dtype)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "lookup_table", {"Ids": input, "W": w}, {"Out": out},
        {"is_sparse": is_sparse, "is_distributed": is_distributed,
         "padding_idx": -1 if padding_idx is None else padding_idx})
    return out


def conv2d(input, num_filters, filter_size, stride=1, padding=0,
           dilation=1, groups=1, param_attr=None, bias_attr=None,
           use_cudnn=True, act=None, name=None):
    helper = LayerHelper("conv2d", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    num_channels = input.shape[1]
    fs = filter_size if isinstance(filter_size, (list, tuple)) \
        else [filter_size, filter_size]
    filter_shape = [num_filters, num_channels // groups, fs[0], fs[1]]
    std = (2.0 / (fs[0] * fs[1] * num_channels)) ** 0.5
    w = helper.create_parameter(
        helper.param_attr, filter_shape, input.dtype,
        default_initializer=NormalInitializer(0.0, std))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "conv2d", {"Input": input, "Filter": w}, {"Output": out},
        {"strides": _pair(stride), "paddings": _pair(padding),
         "dilations": _pair(dilation), "groups": groups})
    out = _conv_bias(helper, out)
    return helper.append_activation(out)


def conv3d(input, num_filters, filter_size, stride=1, padding=0,
           dilation=1, groups=1, param_attr=None, bias_attr=None,
           act=None, name=None):
    helper = LayerHelper("conv3d", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    c = input.shape[1]
    fs = filter_size if isinstance(filter_size, (list, tuple)) \
        else [filter_size] * 3
    w = helper.create_parameter(
        helper.param_attr, [num_filters, c // groups] + list(fs),
        input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "conv3d", {"Input": input, "Filter": w}, {"Output": out},
        {"strides": _triple(stride), "paddings": _triple(padding),
         "dilations": _triple(dilation), "groups": groups})
    out = _conv_bias(helper, out)
    return helper.append_activation(out)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     stride=1, padding=0, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("conv2d_transpose", input=input,
                         param_attr=param_attr, bias_attr=bias_attr,
                         act=act, name=name)
    c = input.shape[1]
    fs = filter_size if isinstance(filter_size, (list, tuple)) \
        else [filter_size, filter_size]
    w = helper.create_parameter(
        helper.param_attr, [c, num_filters // groups, fs[0], fs[1]],
        input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "conv2d_transpose", {"Input": input, "Filter": w},
        {"Output": out},
        {"strides": _pair(stride), "paddings": _pair(padding),
         "dilations": _pair(dilation), "groups": groups})
    out = _conv_bias(helper, out)
    return helper.append_activation(out)


def _conv_bias(helper, out):
    bias_attr = helper.bias_attr
    if bias_attr is False:
        return out
    b = helper.create_parameter(bias_attr, [out.shape[1]], out.dtype,
                                is_bias=True)
    if b is None:
        return out
    new = helper.create_variable_for_type_inference(out.dtype)
    helper.append_op("elementwise_add", {"X": out, "Y": b}, {"Out": new},
                     {"axis": 1})
    return new


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, exclusive=True, name=None):
    helper = LayerHelper("pool2d", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "pool2d", {"X": input}, {"Out": out},
        {"pooling_type": pool_type, "ksize": _pair(pool_size),
         "strides": _pair(pool_stride), "paddings": _pair(pool_padding),
         "global_pooling": global_pooling, "ceil_mode": ceil_mode,
         "exclusive": exclusive})
    return out


def adaptive_pool2d(input, pool_size, pool_type="max", name=None):
    helper = LayerHelper("adaptive_pool2d", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("adaptive_pool2d", {"X": input}, {"Out": out},
                     {"pooling_size": _pair(pool_size),
                      "pooling_type": pool_type})
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               in_place=False, name=None, moving_mean_name=None,
               moving_variance_name=None, do_model_average_for_mean_and_var=
               False, use_global_stats=False):
    """reference layers/nn.py batch_norm; running stats are persistable
    state threaded through the executor (MeanOut/VarianceOut)."""
    helper = LayerHelper("batch_norm", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    c = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    dtype = input.dtype
    scale = helper.create_parameter(
        helper.param_attr, [c], dtype,
        default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(helper.bias_attr, [c], dtype,
                                   is_bias=True)
    mean = helper.create_global_variable(
        [c], dtype, persistable=True,
        name=moving_mean_name, stop_gradient=True)
    helper.set_variable_initializer(mean, ConstantInitializer(0.0))
    variance = helper.create_global_variable(
        [c], dtype, persistable=True,
        name=moving_variance_name, stop_gradient=True)
    helper.set_variable_initializer(variance, ConstantInitializer(1.0))
    saved_mean = helper.create_variable_for_type_inference(
        dtype, stop_gradient=True)
    saved_var = helper.create_variable_for_type_inference(
        dtype, stop_gradient=True)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "batch_norm",
        {"X": input, "Scale": scale, "Bias": bias, "Mean": mean,
         "Variance": variance},
        {"Y": out, "MeanOut": mean, "VarianceOut": variance,
         "SavedMean": saved_mean, "SavedVariance": saved_var},
        {"momentum": momentum, "epsilon": epsilon, "is_test": is_test,
         "data_layout": data_layout,
         "use_global_stats": use_global_stats})
    return helper.append_activation(out)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    helper = LayerHelper("layer_norm", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    dim = int(np.prod(input.shape[begin_norm_axis:]))
    inputs = {"X": input}
    if scale:
        s = helper.create_parameter(
            helper.param_attr, [dim], dtype,
            default_initializer=ConstantInitializer(1.0))
        inputs["Scale"] = s
    if shift:
        b = helper.create_parameter(helper.bias_attr, [dim], dtype,
                                    is_bias=True)
        if b is not None:
            inputs["Bias"] = b
    out = helper.create_variable_for_type_inference(dtype)
    mean = helper.create_variable_for_type_inference(dtype, True)
    var = helper.create_variable_for_type_inference(dtype, True)
    helper.append_op("layer_norm", inputs,
                     {"Y": out, "Mean": mean, "Variance": var},
                     {"epsilon": epsilon,
                      "begin_norm_axis": begin_norm_axis})
    return helper.append_activation(out)


def group_norm(input, groups, epsilon=1e-5, param_attr=None,
               bias_attr=None, act=None, data_layout="NCHW", name=None):
    helper = LayerHelper("group_norm", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    c = input.shape[1]
    inputs = {"X": input}
    if param_attr is not False:
        inputs["Scale"] = helper.create_parameter(
            helper.param_attr, [c], input.dtype,
            default_initializer=ConstantInitializer(1.0))
    if bias_attr is not False:
        inputs["Bias"] = helper.create_parameter(
            helper.bias_attr, [c], input.dtype, is_bias=True)
    out = helper.create_variable_for_type_inference(input.dtype)
    mean = helper.create_variable_for_type_inference(input.dtype, True)
    var = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op("group_norm", inputs,
                     {"Y": out, "Mean": mean, "Variance": var},
                     {"groups": groups, "epsilon": epsilon})
    return helper.append_activation(out)


def instance_norm(input, epsilon=1e-5, param_attr=None, bias_attr=None,
                  name=None):
    helper = LayerHelper("instance_norm", input=input,
                         param_attr=param_attr, bias_attr=bias_attr,
                         name=name)
    c = input.shape[1]
    inputs = {"X": input}
    if param_attr is not False:
        inputs["Scale"] = helper.create_parameter(
            helper.param_attr, [c], input.dtype,
            default_initializer=ConstantInitializer(1.0))
    if bias_attr is not False:
        inputs["Bias"] = helper.create_parameter(
            helper.bias_attr, [c], input.dtype, is_bias=True)
    out = helper.create_variable_for_type_inference(input.dtype)
    sm = helper.create_variable_for_type_inference(input.dtype, True)
    sv = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op("instance_norm", inputs,
                     {"Y": out, "SavedMean": sm, "SavedVariance": sv},
                     {"epsilon": epsilon})
    return out


def spectral_norm(weight, dim=0, power_iters=1, eps=1e-12, name=None):
    helper = LayerHelper("spectral_norm", input=weight, name=name)
    out = helper.create_variable_for_type_inference(weight.dtype)
    h = weight.shape[dim]
    import functools
    w = int(np.prod(weight.shape)) // h
    u = helper.create_parameter(None, [h], weight.dtype,
                                default_initializer=NormalInitializer())
    v = helper.create_parameter(None, [w], weight.dtype,
                                default_initializer=NormalInitializer())
    helper.append_op("spectral_norm",
                     {"Weight": weight, "U": u, "V": v}, {"Out": out},
                     {"dim": dim, "power_iters": power_iters, "eps": eps})
    return out


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    mask = helper.create_variable_for_type_inference(x.dtype, True)
    helper.append_op("dropout", {"X": x}, {"Out": out, "Mask": mask},
                     {"dropout_prob": dropout_prob, "is_test": is_test,
                      "seed": seed or 0,
                      "dropout_implementation": dropout_implementation})
    return out


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------
def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, axis=-1,
                               label_smooth_eps=0.0):
    helper = LayerHelper("softmax_with_cross_entropy", input=logits)
    loss = helper.create_variable_for_type_inference(logits.dtype)
    sm = helper.create_variable_for_type_inference(logits.dtype)
    helper.append_op("softmax_with_cross_entropy",
                     {"Logits": logits, "Label": label},
                     {"Loss": loss, "Softmax": sm},
                     {"soft_label": soft_label,
                      "ignore_index": ignore_index,
                      "label_smooth_eps": label_smooth_eps})
    if return_softmax:
        return loss, sm
    return loss


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("cross_entropy", {"X": input, "Label": label},
                     {"Y": out} if False else {"Out": out},
                     {"soft_label": soft_label,
                      "ignore_index": ignore_index})
    return out


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100,
                                      name=None, normalize=False):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("sigmoid_cross_entropy_with_logits",
                     {"X": x, "Label": label}, {"Out": out},
                     {"ignore_index": ignore_index,
                      "normalize": normalize})
    return out


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("square_error_cost", {"X": input, "Y": label},
                     {"Out": out}, {})
    return out


def huber_loss(input, label, delta):
    helper = LayerHelper("huber_loss", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    res = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op("huber_loss", {"X": input, "Y": label},
                     {"Out": out, "Residual": res}, {"delta": delta})
    return out


def log_loss(input, label, epsilon=1e-4, name=None):
    helper = LayerHelper("log_loss", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("log_loss", {"Predicted": input, "Labels": label},
                     {"Loss": out}, {"epsilon": epsilon})
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1_loss", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    diff = helper.create_variable_for_type_inference(x.dtype, True)
    ins = {"X": x, "Y": y}
    if inside_weight is not None:
        ins["InsideWeight"] = inside_weight
    if outside_weight is not None:
        ins["OutsideWeight"] = outside_weight
    helper.append_op("smooth_l1_loss", ins,
                     {"Out": out, "Diff": diff},
                     {"sigma": sigma or 1.0})
    return out


smooth_l1_loss = smooth_l1


def hinge_loss(input, label):
    helper = LayerHelper("hinge_loss", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("hinge_loss", {"Logits": input, "Labels": label},
                     {"Loss": out}, {})
    return out


def margin_rank_loss(label, left, right, margin=0.1, name=None):
    helper = LayerHelper("margin_rank_loss", input=left)
    out = helper.create_variable_for_type_inference(left.dtype)
    act = helper.create_variable_for_type_inference(left.dtype, True)
    helper.append_op("margin_rank_loss",
                     {"Label": label, "X1": left, "X2": right},
                     {"Out": out, "Activated": act}, {"margin": margin})
    return out


def bpr_loss(input, label, name=None):
    helper = LayerHelper("bpr_loss", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("bpr_loss", {"X": input, "Label": label},
                     {"Out": out}, {})
    return out


def kldiv_loss(x, target, reduction="mean", name=None):
    helper = LayerHelper("kldiv_loss", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("kldiv_loss", {"X": x, "Target": target},
                     {"Loss": out}, {"reduction": reduction})
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    helper = LayerHelper("label_smooth", input=label)
    out = helper.create_variable_for_type_inference(dtype)
    ins = {"X": label}
    if prior_dist is not None:
        ins["PriorDist"] = prior_dist
    helper.append_op("label_smooth", ins, {"Out": out},
                     {"epsilon": epsilon})
    return out


def cos_sim(X, Y):
    helper = LayerHelper("cos_sim", input=X)
    out = helper.create_variable_for_type_inference(X.dtype)
    xn = helper.create_variable_for_type_inference(X.dtype, True)
    yn = helper.create_variable_for_type_inference(X.dtype, True)
    helper.append_op("cos_sim", {"X": X, "Y": Y},
                     {"Out": out, "XNorm": xn, "YNorm": yn}, {})
    return out


def dice_loss(input, label, epsilon=1e-5):
    helper = LayerHelper("dice_loss", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("dice_loss", {"X": input, "Label": label},
                     {"Out": out}, {"epsilon": epsilon})
    return out


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    helper = LayerHelper("npair_loss", input=anchor)
    out = helper.create_variable_for_type_inference(anchor.dtype)
    helper.append_op("npair_loss",
                     {"Anchor": anchor, "Positive": positive,
                      "Labels": labels},
                     {"Out": out}, {"l2_reg": l2_reg})
    return out


# ---------------------------------------------------------------------------
# generated elementwise / unary / reduce wrappers
# ---------------------------------------------------------------------------
def _make_elementwise(op_type):
    def layer(x, y, axis=-1, act=None, name=None):
        helper = LayerHelper(op_type, input=x, act=act, name=name)
        out = helper.create_variable_for_type_inference(x.dtype)
        helper.append_op(op_type, {"X": x, "Y": y}, {"Out": out},
                         {"axis": axis})
        return helper.append_activation(out)

    layer.__name__ = op_type
    return layer


elementwise_add = _make_elementwise("elementwise_add")
elementwise_sub = _make_elementwise("elementwise_sub")
elementwise_mul = _make_elementwise("elementwise_mul")
elementwise_div = _make_elementwise("elementwise_div")
elementwise_max = _make_elementwise("elementwise_max")
elementwise_min = _make_elementwise("elementwise_min")
elementwise_pow = _make_elementwise("elementwise_pow")
elementwise_mod = _make_elementwise("elementwise_mod")
elementwise_floordiv = _make_elementwise("elementwise_floordiv")


def _make_reduce(op_type):
    def layer(input, dim=None, keep_dim=False, name=None):
        helper = LayerHelper(op_type, input=input, name=name)
        out = helper.create_variable_for_type_inference(input.dtype)
        if dim is None:
            attrs = {"dim": [0], "reduce_all": True, "keep_dim": keep_dim}
        else:
            if not isinstance(dim, (list, tuple)):
                dim = [dim]
            attrs = {"dim": list(dim), "reduce_all": False,
                     "keep_dim": keep_dim}
        helper.append_op(op_type, {"X": input}, {"Out": out}, attrs)
        return out

    layer.__name__ = op_type
    return layer


reduce_sum = _make_reduce("reduce_sum")
reduce_mean = _make_reduce("reduce_mean")
reduce_max = _make_reduce("reduce_max")
reduce_min = _make_reduce("reduce_min")
reduce_prod = _make_reduce("reduce_prod")
reduce_all = _make_reduce("reduce_all")
reduce_any = _make_reduce("reduce_any")


def mean(x, name=None):
    helper = LayerHelper("mean", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("mean", {"X": x}, {"Out": out}, {})
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0,
           name=None):
    helper = LayerHelper("matmul", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("matmul", {"X": x, "Y": y}, {"Out": out},
                     {"transpose_X": transpose_x,
                      "transpose_Y": transpose_y, "alpha": alpha})
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("mul", {"X": x, "Y": y}, {"Out": out},
                     {"x_num_col_dims": x_num_col_dims,
                      "y_num_col_dims": y_num_col_dims})
    return out


def dot(x, y, name=None):
    helper = LayerHelper("dot", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("dot", {"X": x, "Y": y}, {"Out": out}, {})
    return out


# ---------------------------------------------------------------------------
# shape manipulation wrappers
# ---------------------------------------------------------------------------
def reshape(x, shape, actual_shape=None, act=None, inplace=False,
            name=None):
    helper = LayerHelper("reshape2", input=x, act=act, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("reshape2", {"X": x}, {"Out": out},
                     {"shape": list(shape)})
    return helper.append_activation(out)


def squeeze(input, axes, name=None):
    helper = LayerHelper("squeeze2", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("squeeze2", {"X": input}, {"Out": out},
                     {"axes": list(axes)})
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze2", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("unsqueeze2", {"X": input}, {"Out": out},
                     {"axes": list(axes)})
    return out


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose2", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("transpose2", {"X": x}, {"Out": out},
                     {"axis": list(perm)})
    return out


def flatten(x, axis=1, name=None):
    helper = LayerHelper("flatten2", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("flatten2", {"X": x}, {"Out": out}, {"axis": axis})
    return out


def concat(input, axis=0, name=None):
    helper = LayerHelper("concat", input=input[0], name=name)
    out = helper.create_variable_for_type_inference(input[0].dtype)
    helper.append_op("concat", {"X": input}, {"Out": out}, {"axis": axis})
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", input=input, name=name)
    axis = dim if dim >= 0 else dim + len(input.shape)
    if isinstance(num_or_sections, int):
        n = num_or_sections
        attrs = {"num": n, "sections": [], "axis": axis}
    else:
        n = len(num_or_sections)
        attrs = {"num": 0, "sections": list(num_or_sections),
                 "axis": axis}
    outs = [helper.create_variable_for_type_inference(input.dtype)
            for _ in range(n)]
    helper.append_op("split", {"X": input}, {"Out": outs}, attrs)
    return outs


def stack(x, axis=0):
    if not isinstance(x, (list, tuple)):
        x = [x]
    helper = LayerHelper("stack", input=x[0])
    out = helper.create_variable_for_type_inference(x[0].dtype)
    helper.append_op("stack", {"X": x}, {"Y": out} if False else
                     {"Out": out}, {"axis": axis})
    return out


def unstack(x, axis=0, num=None):
    helper = LayerHelper("unstack", input=x)
    n = num or x.shape[axis]
    outs = [helper.create_variable_for_type_inference(x.dtype)
            for _ in range(n)]
    helper.append_op("unstack", {"X": x}, {"Y": outs}, {"axis": axis})
    return outs


def expand(x, expand_times, name=None):
    helper = LayerHelper("expand", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("expand", {"X": x}, {"Out": out},
                     {"expand_times": list(expand_times)})
    return out


def expand_as(x, target_tensor, name=None):
    helper = LayerHelper("expand_as", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("expand_as",
                     {"X": x, "target_tensor": target_tensor},
                     {"Out": out}, {})
    return out


def slice(input, axes, starts, ends):
    helper = LayerHelper("slice", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("slice", {"Input": input}, {"Out": out},
                     {"axes": list(axes), "starts": list(starts),
                      "ends": list(ends)})
    return out


def strided_slice(input, axes, starts, ends, strides):
    helper = LayerHelper("strided_slice", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("strided_slice", {"Input": input}, {"Out": out},
                     {"axes": list(axes), "starts": list(starts),
                      "ends": list(ends), "strides": list(strides)})
    return out


def gather(input, index, overwrite=True):
    helper = LayerHelper("gather", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("gather", {"X": input, "Index": index},
                     {"Out": out}, {})
    return out


def gather_nd(input, index, name=None):
    helper = LayerHelper("gather_nd", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("gather_nd", {"X": input, "Index": index},
                     {"Out": out}, {})
    return out


def scatter(input, index, updates, name=None, overwrite=True):
    helper = LayerHelper("scatter", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("scatter",
                     {"X": input, "Ids": index, "Updates": updates},
                     {"Out": out}, {"overwrite": overwrite})
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper("pad", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("pad", {"X": x}, {"Out": out},
                     {"paddings": list(paddings), "pad_value": pad_value})
    return out


def pad2d(input, paddings=[0, 0, 0, 0], mode="constant", pad_value=0.0,
          data_format="NCHW", name=None):
    helper = LayerHelper("pad2d", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("pad2d", {"X": input}, {"Out": out},
                     {"paddings": list(paddings), "mode": mode,
                      "pad_value": pad_value, "data_format": data_format})
    return out


def crop(x, shape=None, offsets=None, name=None):
    helper = LayerHelper("crop", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("crop", {"X": x}, {"Out": out},
                     {"shape": list(shape), "offsets": list(offsets or
                      [0] * len(shape))})
    return out


def one_hot(input, depth):
    helper = LayerHelper("one_hot", input=input)
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op("one_hot", {"X": input}, {"Out": out},
                     {"depth": depth})
    return out


def topk(input, k, name=None):
    helper = LayerHelper("top_k", input=input, name=name)
    values = helper.create_variable_for_type_inference(input.dtype)
    indices = helper.create_variable_for_type_inference("int64", True)
    helper.append_op("top_k", {"X": input},
                     {"Out": values, "Indices": indices}, {"k": k})
    return values, indices


def argsort(input, axis=-1, name=None):
    helper = LayerHelper("argsort", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    ids = helper.create_variable_for_type_inference("int64", True)
    helper.append_op("argsort", {"X": input},
                     {"Out": out, "Indices": ids}, {"axis": axis})
    return out, ids


def argmax(x, axis=0):
    helper = LayerHelper("arg_max", input=x)
    out = helper.create_variable_for_type_inference("int64", True)
    helper.append_op("arg_max", {"X": x}, {"Out": out}, {"axis": axis})
    return out


def argmin(x, axis=0):
    helper = LayerHelper("arg_min", input=x)
    out = helper.create_variable_for_type_inference("int64", True)
    helper.append_op("arg_min", {"X": x}, {"Out": out}, {"axis": axis})
    return out


def where(condition, x=None, y=None):
    helper = LayerHelper("where", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("where", {"Condition": condition, "X": x, "Y": y},
                     {"Out": out}, {})
    return out


def multiplex(inputs, index):
    helper = LayerHelper("multiplex", input=inputs[0])
    out = helper.create_variable_for_type_inference(inputs[0].dtype)
    helper.append_op("multiplex", {"X": inputs, "Ids": index},
                     {"Out": out}, {})
    return out


# ---------------------------------------------------------------------------
# scalar / unary wrappers
# ---------------------------------------------------------------------------
def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None,
          name=None):
    helper = LayerHelper("scale", input=x, act=act, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("scale", {"X": x}, {"Out": out},
                     {"scale": scale, "bias": bias,
                      "bias_after_scale": bias_after_scale})
    return helper.append_activation(out)


def cast(x, dtype):
    helper = LayerHelper("cast", input=x)
    dtype = as_datatype(dtype)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("cast", {"X": x}, {"Out": out},
                     {"out_dtype": dtype.value})
    return out


def clip(x, min, max, name=None):
    helper = LayerHelper("clip", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("clip", {"X": x}, {"Out": out},
                     {"min": min, "max": max})
    return out


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper("clip_by_norm", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("clip_by_norm", {"X": x}, {"Out": out},
                     {"max_norm": max_norm})
    return out


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    norm = helper.create_variable_for_type_inference(x.dtype, True)
    helper.append_op("l2_normalize", {"X": x},
                     {"Out": out, "Norm": norm},
                     {"axis": axis, "epsilon": epsilon})
    return out


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper("lrn", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    mid = helper.create_variable_for_type_inference(input.dtype, True)
    helper.append_op("lrn", {"X": input}, {"Out": out, "MidOut": mid},
                     {"n": n, "k": k, "alpha": alpha, "beta": beta})
    return out


def relu(x, name=None):
    helper = LayerHelper("relu", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("relu", {"X": x}, {"Out": out}, {})
    return out


def leaky_relu(x, alpha=0.02, name=None):
    helper = LayerHelper("leaky_relu", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("leaky_relu", {"X": x}, {"Out": out},
                     {"alpha": alpha})
    return out


def prelu(x, mode, param_attr=None, name=None):
    helper = LayerHelper("prelu", input=x, param_attr=param_attr,
                         name=name)
    if mode == "all":
        alpha_shape = [1]
    elif mode == "channel":
        alpha_shape = [1, x.shape[1], 1, 1]
    else:
        alpha_shape = [1] + list(x.shape[1:])
    alpha = helper.create_parameter(
        helper.param_attr, alpha_shape, x.dtype,
        default_initializer=ConstantInitializer(0.25))
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("prelu", {"X": x, "Alpha": alpha}, {"Out": out},
                     {"mode": mode})
    return out


def maxout(x, groups, name=None):
    helper = LayerHelper("maxout", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("maxout", {"X": x}, {"Out": out},
                     {"groups": groups})
    return out


def swish(x, beta=1.0, name=None):
    helper = LayerHelper("swish", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("swish", {"X": x}, {"Out": out}, {"beta": beta})
    return out


def hard_swish(x, threshold=6.0, scale=6.0, offset=3.0, name=None):
    helper = LayerHelper("hard_swish", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("hard_swish", {"X": x}, {"Out": out},
                     {"threshold": threshold, "scale": scale,
                      "offset": offset})
    return out


def hard_sigmoid(x, slope=0.2, offset=0.5, name=None):
    helper = LayerHelper("hard_sigmoid", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("hard_sigmoid", {"X": x}, {"Out": out},
                     {"slope": slope, "offset": offset})
    return out


def elu(x, alpha=1.0, name=None):
    helper = LayerHelper("elu", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("elu", {"X": x}, {"Out": out}, {"alpha": alpha})
    return out


def relu6(x, threshold=6.0, name=None):
    helper = LayerHelper("relu6", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("relu6", {"X": x}, {"Out": out},
                     {"threshold": threshold})
    return out


def pow(x, factor=1.0, name=None):
    helper = LayerHelper("pow", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("pow", {"X": x}, {"Out": out}, {"factor": factor})
    return out


def soft_relu(x, threshold=40.0, name=None):
    helper = LayerHelper("soft_relu", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("soft_relu", {"X": x}, {"Out": out},
                     {"threshold": threshold})
    return out


def brelu(x, t_min=0.0, t_max=24.0, name=None):
    helper = LayerHelper("brelu", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("brelu", {"X": x}, {"Out": out},
                     {"t_min": t_min, "t_max": t_max})
    return out


def softmax(input, use_cudnn=False, name=None, axis=-1):
    helper = LayerHelper("softmax", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("softmax", {"X": input}, {"Out": out},
                     {"axis": axis})
    return out


def log_softmax(input, axis=-1, name=None):
    helper = LayerHelper("log_softmax", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("log_softmax", {"X": input}, {"Out": out},
                     {"axis": axis})
    return out


def increment(x, value=1.0, in_place=True):
    helper = LayerHelper("increment", input=x)
    # integer counters: a python-float step (the fluid-parity 1.0
    # default) would promote the value to float under JAX weak typing
    # and break lax.while_loop carry dtypes (analysis checker PTA020)
    # -- coerce integral steps to int so counters stay counters
    dt = getattr(x, "dtype", None)
    dt = getattr(dt, "value", dt)
    if isinstance(value, float) and isinstance(dt, str) \
            and dt.startswith(("int", "uint")) and value.is_integer():
        value = int(value)
    if in_place:
        out = x
    else:
        out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("increment", {"X": x}, {"Out": out},
                     {"step": value})
    return out


def zeros_like(x, out=None):
    helper = LayerHelper("fill_zeros_like", input=x)
    out = out or helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("fill_zeros_like", {"X": x}, {"Out": out}, {})
    return out


def ones_like(x, out=None):
    helper = LayerHelper("fill_any_like", input=x)
    out = out or helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("fill_any_like", {"X": x}, {"Out": out},
                     {"value": 1.0})
    return out


def shape(input):
    helper = LayerHelper("shape", input=input)
    out = helper.create_variable_for_type_inference("int32", True)
    helper.append_op("shape", {"Input": input}, {"Out": out}, {})
    return out


def reverse(x, axis):
    helper = LayerHelper("reverse", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    if isinstance(axis, int):
        axis = [axis]
    helper.append_op("reverse", {"X": x}, {"Out": out},
                     {"axis": list(axis)})
    return out


def uniform_random_batch_size_like(input, shape, dtype="float32",
                                   input_dim_idx=0, output_dim_idx=0,
                                   min=-1.0, max=1.0, seed=0):
    helper = LayerHelper("uniform_random_batch_size_like", input=input)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("uniform_random_batch_size_like", {"Input": input},
                     {"Out": out},
                     {"shape": list(shape), "input_dim_idx": input_dim_idx,
                      "output_dim_idx": output_dim_idx, "min": min,
                      "max": max, "seed": seed})
    return out


def gaussian_random(shape, mean=0.0, std=1.0, seed=0, dtype="float32"):
    helper = LayerHelper("gaussian_random")
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("gaussian_random", {}, {"Out": out},
                     {"shape": list(shape), "mean": mean, "std": std,
                      "seed": seed, "dtype": as_datatype(dtype).value})
    return out


def sampling_id(x, min=0.0, max=1.0, seed=0, dtype="float32"):
    helper = LayerHelper("sampling_id", input=x)
    out = helper.create_variable_for_type_inference("int64", True)
    helper.append_op("sampling_id", {"X": x}, {"Out": out},
                     {"min": min, "max": max, "seed": seed})
    return out


def sums(input, out=None):
    helper = LayerHelper("sum", input=input[0])
    out = out or helper.create_variable_for_type_inference(
        input[0].dtype)
    helper.append_op("sum", {"X": input}, {"Out": out}, {})
    return out


sum = sums


# ---------------------------------------------------------------------------
# vision ops -- thin wrappers; kernels in ops/vision_ops.py
# ---------------------------------------------------------------------------
def image_resize(input, out_shape=None, scale=None, name=None,
                 resample="BILINEAR", align_corners=True, align_mode=1):
    helper = LayerHelper("interpolate", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    if out_shape is None:
        h, w = input.shape[2], input.shape[3]
        out_shape = [int(h * scale), int(w * scale)]
    helper.append_op("interpolate", {"X": input}, {"Out": out},
                     {"out_h": out_shape[0], "out_w": out_shape[1],
                      "interp_method": resample.lower(),
                      "align_corners": align_corners,
                      "align_mode": align_mode})
    return out


def resize_bilinear(input, out_shape=None, scale=None, name=None,
                    align_corners=True, align_mode=1):
    return image_resize(input, out_shape, scale, name, "BILINEAR",
                        align_corners, align_mode)


def resize_nearest(input, out_shape=None, scale=None, name=None,
                   align_corners=True):
    return image_resize(input, out_shape, scale, name, "NEAREST",
                        align_corners)


def grid_sampler(x, grid, name=None):
    helper = LayerHelper("grid_sampler", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("grid_sampler", {"X": x, "Grid": grid},
                     {"Output": out}, {})
    return out


def affine_grid(theta, out_shape, name=None):
    helper = LayerHelper("affine_grid", input=theta, name=name)
    out = helper.create_variable_for_type_inference(theta.dtype)
    attrs = {}
    if isinstance(out_shape, (list, tuple)):
        attrs["output_shape"] = [int(v) for v in out_shape]
        helper.append_op("affine_grid", {"Theta": theta},
                         {"Output": out}, attrs)
    else:
        helper.append_op("affine_grid",
                         {"Theta": theta, "OutputShape": out_shape},
                         {"Output": out}, attrs)
    return out


def affine_channel(x, scale=None, bias=None, data_layout="NCHW",
                   name=None):
    helper = LayerHelper("affine_channel", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("affine_channel",
                     {"X": x, "Scale": scale, "Bias": bias},
                     {"Out": out}, {"data_layout": data_layout})
    return out


def shuffle_channel(x, group, name=None):
    helper = LayerHelper("shuffle_channel", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("shuffle_channel", {"X": x}, {"Out": out},
                     {"group": group})
    return out


def pixel_shuffle(x, upscale_factor):
    helper = LayerHelper("pixel_shuffle", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("pixel_shuffle", {"X": x}, {"Out": out},
                     {"upscale_factor": upscale_factor})
    return out


def pixel_unshuffle(x, downscale_factor):
    helper = LayerHelper("pixel_unshuffle", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("pixel_unshuffle", {"X": x}, {"Out": out},
                     {"downscale_factor": downscale_factor})
    return out


def roi_pool(input, rois, pooled_height=1, pooled_width=1,
             spatial_scale=1.0):
    helper = LayerHelper("roi_pool", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    argmax_ = helper.create_variable_for_type_inference("int32", True)
    helper.append_op("roi_pool", {"X": input, "ROIs": rois},
                     {"Out": out, "Argmax": argmax_},
                     {"pooled_height": pooled_height,
                      "pooled_width": pooled_width,
                      "spatial_scale": spatial_scale})
    return out


def roi_align(input, rois, pooled_height=1, pooled_width=1,
              spatial_scale=1.0, sampling_ratio=-1, name=None):
    helper = LayerHelper("roi_align", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("roi_align", {"X": input, "ROIs": rois},
                     {"Out": out},
                     {"pooled_height": pooled_height,
                      "pooled_width": pooled_width,
                      "spatial_scale": spatial_scale,
                      "sampling_ratio": sampling_ratio})
    return out


def psroi_pool(input, rois, output_channels, spatial_scale, pooled_height,
               pooled_width, name=None):
    helper = LayerHelper("psroi_pool", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("psroi_pool", {"X": input, "ROIs": rois},
                     {"Out": out},
                     {"output_channels": output_channels,
                      "spatial_scale": spatial_scale,
                      "pooled_height": pooled_height,
                      "pooled_width": pooled_width})
    return out


def row_conv(input, future_context_size, param_attr=None, act=None):
    helper = LayerHelper("row_conv", input=input, param_attr=param_attr,
                         act=act)
    w = helper.create_parameter(
        helper.param_attr, [future_context_size + 1, input.shape[-1]],
        input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("row_conv", {"X": input, "Filter": w},
                     {"Out": out}, {})
    return helper.append_activation(out)


def temporal_shift(x, seg_num, shift_ratio=0.25, name=None):
    helper = LayerHelper("temporal_shift", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("temporal_shift", {"X": x}, {"Out": out},
                     {"seg_num": seg_num, "shift_ratio": shift_ratio})
    return out


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1,
           name=None):
    helper = LayerHelper("unfold", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("unfold", {"X": x}, {"Y": out},
                     {"kernel_sizes": _pair(kernel_sizes),
                      "strides": _pair(strides),
                      "paddings": _pair(paddings),
                      "dilations": _pair(dilations)})
    return out


def deformable_conv(input, offset, mask=None, num_filters=None,
                    filter_size=None, stride=1, padding=0, dilation=1,
                    groups=1, deformable_groups=1, im2col_step=None,
                    param_attr=None, bias_attr=None, modulated=None,
                    act=None, name=None):
    """Deformable conv v1 (mask=None) / v2 (modulated, with mask).
    Beyond-reference capability (no op in this reference tree; API
    modeled on later fluid surfaces). `offset` is
    [B, 2*deformable_groups*kh*kw, Ho, Wo] with (dy, dx) per tap;
    `mask` is [B, deformable_groups*kh*kw, Ho, Wo]. `modulated`
    defaults to inferring v1/v2 from mask presence; passing it
    explicitly must agree with the mask (silently dropping a mask or
    degrading v2 to v1 would be wrong numbers, not an error).
    im2col_step is accepted for API parity and ignored (the TPU
    lowering samples all taps in one gather — see ops/nn_ops.py
    deformable_conv)."""
    if modulated is None:
        modulated = mask is not None
    if modulated and mask is None:
        raise ValueError("deformable_conv: modulated=True (v2) needs "
                         "a mask input")
    if not modulated and mask is not None:
        raise ValueError("deformable_conv: a mask was given but "
                         "modulated=False would silently ignore it; "
                         "pass modulated=True or drop the mask")
    helper = LayerHelper("deformable_conv", input=input,
                         param_attr=param_attr, bias_attr=bias_attr,
                         act=act, name=name)
    num_channels = input.shape[1]
    fs = filter_size if isinstance(filter_size, (list, tuple)) \
        else [filter_size, filter_size]
    filter_shape = [num_filters, num_channels // groups, fs[0], fs[1]]
    std = (2.0 / (fs[0] * fs[1] * num_channels)) ** 0.5
    w = helper.create_parameter(
        helper.param_attr, filter_shape, input.dtype,
        default_initializer=NormalInitializer(0.0, std))
    out = helper.create_variable_for_type_inference(input.dtype)
    ins = {"Input": input, "Offset": offset, "Filter": w}
    if mask is not None:
        ins["Mask"] = mask
    helper.append_op(
        "deformable_conv", ins, {"Output": out},
        {"strides": _pair(stride), "paddings": _pair(padding),
         "dilations": _pair(dilation), "groups": groups,
         "deformable_groups": deformable_groups})
    out = _conv_bias(helper, out)
    return helper.append_activation(out)


def switch_moe(input, num_experts, d_inner, top_k=1,
               capacity_factor=2.0, param_attr=None, name=None,
               return_drop_frac=False):
    """Switch/GShard mixture-of-experts FFN (beyond-reference; routing
    math + expert-parallel dataflow in parallel/moe.py, lowered by the
    `switch_moe` op). This is the CAPACITY routing, which DROPS tokens:
    every expert takes at most `capacity_factor * top_k * tokens /
    num_experts` of them and the rest get no expert (their output is
    zero). `moe_dropless` below is the routing that drops none.
    Returns (out, aux_loss): add
    ``aux_loss * coeff`` (Switch uses coeff=0.01) onto the training
    loss or routing collapses onto one expert.
    With ``return_drop_frac=True`` returns (out, aux_loss, drop_frac)
    where drop_frac [1] is the fraction of tokens that received NO
    expert slot this step — fetch it to monitor silent over-capacity
    drops (it costs nothing when unfetched; XLA dead-codes it).

    input: [..., D]; experts are [D, d_inner] -> [d_inner, D] relu
    MLPs. Under `with expert_parallel(mesh):` the op runs all_to_all
    expert-parallel over the 'ep' mesh axis."""
    helper = LayerHelper("switch_moe", input=input,
                         param_attr=param_attr, name=name)
    d = input.shape[-1]
    prefix = name or helper.name
    std = (2.0 / d) ** 0.5

    def _attr(suffix):
        from ..param_attr import ParamAttr
        import copy as _copy

        a = ParamAttr._to_attr(param_attr)
        a = ParamAttr() if a is None else _copy.copy(a)
        a.name = f"{prefix}_{suffix}" if a.name is None \
            else f"{a.name}_{suffix}"
        return a

    wg = helper.create_parameter(
        _attr("gate_w"), [d, num_experts], input.dtype,
        default_initializer=NormalInitializer(0.0, 0.02))
    w1 = helper.create_parameter(
        _attr("expert_w1"), [num_experts, d, d_inner], input.dtype,
        default_initializer=NormalInitializer(0.0, std))
    w2 = helper.create_parameter(
        _attr("expert_w2"), [num_experts, d_inner, d], input.dtype,
        default_initializer=NormalInitializer(0.0, std))
    out = helper.create_variable_for_type_inference(input.dtype)
    aux = helper.create_variable_for_type_inference("float32")
    drop = helper.create_variable_for_type_inference("float32")
    drop.stop_gradient = True
    helper.append_op(
        "switch_moe",
        {"X": input, "GateW": wg, "W1": w1, "W2": w2},
        {"Out": out, "AuxLoss": aux, "DropFrac": drop},
        {"top_k": int(top_k), "capacity_factor": float(capacity_factor)})
    if return_drop_frac:
        return out, aux, drop
    return out, aux


def im2sequence(input, filter_size=1, stride=1, padding=0, name=None):
    helper = LayerHelper("im2sequence", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("im2sequence", {"X": input}, {"Out": out},
                     {"kernels": _pair(filter_size),
                      "strides": _pair(stride),
                      "paddings": _pair(padding) + _pair(padding)})
    return out


# --- sequence/RNN/decoding layers live in rnn.py & sequence.py; imported
# lazily at the bottom to avoid circular imports -------------------------
from .sequence import (  # noqa: E402,F401
    sequence_conv, sequence_pool, sequence_softmax, sequence_expand,
    sequence_concat, sequence_first_step, sequence_last_step,
    sequence_reshape, sequence_pad, sequence_unpad, sequence_reverse,
    sequence_slice, sequence_enumerate, sequence_expand_as,
    sequence_scatter)
from .rnn import (  # noqa: E402,F401
    lstm, dynamic_lstm, dynamic_gru, gru_unit, lstm_unit, beam_search,
    beam_search_decode, edit_distance, ctc_greedy_decoder, warpctc, nce,
    hsigmoid, sampled_softmax_with_cross_entropy, linear_chain_crf,
    linear_chain_crf_raw, crf_decoding, crf_decoding_raw)


def _pair(v):
    if isinstance(v, (list, tuple)):
        return list(v)
    return [v, v]


def _triple(v):
    if isinstance(v, (list, tuple)):
        return list(v)
    return [v, v, v]


def attention_block(x, n_heads, causal=False, scale=None,
                    param_attr_qkv=None, param_attr_out=None,
                    name=None):
    """Whole-layer fused self-attention sub-layer (no dropout, no
    projection biases, residual outside): ONE op replacing the
    qkv-fc/split/reshape/attention/reshape/out-fc sequence so the
    pallas kernel (ops/pallas/attention_block.py) can keep every
    intermediate in VMEM. Route multi_head_attention through it with
    PADDLE_TPU_FUSE_ATTN_BLOCK=1 (A/B knob; PERF.md)."""
    from ..param_attr import ParamAttr

    helper = LayerHelper("attention_block", input=x,
                         param_attr=param_attr_qkv, name=name)
    d = int(x.shape[-1])
    if d % n_heads:
        raise ValueError(
            f"attention_block: d_model {d} not divisible by "
            f"n_heads {n_heads}")
    w_qkv = helper.create_parameter(
        ParamAttr._to_attr(param_attr_qkv), [d, 3 * d], x.dtype)
    w_o = helper.create_parameter(
        ParamAttr._to_attr(param_attr_out), [d, d], x.dtype)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        "attention_block", {"X": x, "WQKV": w_qkv, "WO": w_o},
        {"Out": out},
        {"n_heads": int(n_heads),
         "scale": float(scale if scale is not None
                        else (d // n_heads) ** -0.5),
         "causal": bool(causal)})
    return out


__all__.append("attention_block")


def ffn_block(x, d_inner, param_attr_fc1=None, bias_attr_fc1=None,
              param_attr_fc2=None, bias_attr_fc2=None, name=None):
    """Whole-layer fused position-wise MLP (relu between two fcs, no
    dropout): ONE op replacing the mul/add/relu/mul/add sequence so
    the pallas kernel (ops/pallas/ffn_block.py) keeps the [T, d_inner]
    hidden in VMEM. Routed from models/transformer._ffn by
    PADDLE_TPU_FUSE_ATTN_BLOCK=1."""
    from ..param_attr import ParamAttr

    helper = LayerHelper("ffn_block", input=x,
                         param_attr=param_attr_fc1, name=name)
    d = int(x.shape[-1])
    w1 = helper.create_parameter(
        ParamAttr._to_attr(param_attr_fc1), [d, d_inner], x.dtype)
    b1 = helper.create_parameter(
        ParamAttr._to_attr(bias_attr_fc1), [d_inner], x.dtype,
        is_bias=True)
    w2 = helper.create_parameter(
        ParamAttr._to_attr(param_attr_fc2), [d_inner, d], x.dtype)
    b2 = helper.create_parameter(
        ParamAttr._to_attr(bias_attr_fc2), [d], x.dtype, is_bias=True)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        "ffn_block",
        {"X": x, "W1": w1, "B1": b1, "W2": w2, "B2": b2},
        {"Out": out}, {})
    return out


__all__.append("ffn_block")


def attention(q, k, v, causal=False, scale=None, dropout_rate=0.0,
              is_test=False, layout="bhtd", name=None):
    """Fused scaled-dot-product attention -- the framework's
    flash-attention entry point (Pallas kernel on TPU). layout='bthd'
    takes [B,T,H,D] straight from the head-split reshape, skipping the
    physical head transpose (see ops/nn_ops.py attention)."""
    helper = LayerHelper("attention", input=q, name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    helper.append_op("attention", {"Q": q, "K": k, "V": v},
                     {"Out": out},
                     {"causal": causal, "scale": scale,
                      "dropout_rate": dropout_rate,
                      "is_test": is_test, "layout": layout})
    return out


__all__.append("attention")
__all__.append("switch_moe")
__all__.extend(["linear_chain_crf", "linear_chain_crf_raw",
                "crf_decoding", "crf_decoding_raw"])


def stanh(x, scale_a=0.67, scale_b=1.7159, name=None):
    """reference layers/nn.py stanh -> activation_op.cc STanh."""
    helper = LayerHelper("stanh", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("stanh", {"X": x}, {"Out": out},
                     {"scale_a": scale_a, "scale_b": scale_b})
    return out


def adaptive_pool3d(input, pool_size, pool_type="max", name=None):
    """reference layers/nn.py adaptive_pool3d (NCDHW)."""
    helper = LayerHelper("adaptive_pool3d", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    size = pool_size if isinstance(pool_size, (list, tuple)) else \
        [pool_size] * 3
    helper.append_op("adaptive_pool3d", {"X": input}, {"Out": out},
                     {"pooling_size": list(size),
                      "pooling_type": pool_type})
    return out


def gaussian_random_batch_size_like(input, shape, input_dim_idx=0,
                                    output_dim_idx=0, mean=0.0,
                                    std=1.0, seed=0, dtype="float32"):
    """reference layers/nn.py gaussian_random_batch_size_like."""
    helper = LayerHelper("gaussian_random_batch_size_like",
                         input=input)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("gaussian_random_batch_size_like",
                     {"Input": input}, {"Out": out},
                     {"shape": list(shape),
                      "input_dim_idx": input_dim_idx,
                      "output_dim_idx": output_dim_idx, "mean": mean,
                      "std": std, "seed": seed})
    return out


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    """reference layers/nn.py autoincreased_step_counter: a persistable
    int64 counter bumped once per executor run (the global-step var the
    LR schedules build on)."""
    helper = LayerHelper("step_counter")
    name = counter_name or "@STEP_COUNTER@"
    block = helper.main_program.global_block
    counter = block.create_var(name=name, shape=(1,), dtype="int64",
                               persistable=True, stop_gradient=True)
    sblock = helper.startup_program.global_block
    svar = sblock.create_var(name=name, shape=(1,), dtype="int64",
                             persistable=True)
    if not any(name in op.output_arg_names for op in sblock.ops):
        from ..initializer import ConstantInitializer

        ConstantInitializer(float(begin - step))(svar, sblock)
    cur = helper.main_program.current_block()
    if not any(name in op.output_arg_names and op.type == "increment"
               for op in cur.ops):
        # int step: a python float would promote the int64 counter to
        # float32 under JAX type rules on the first x + attr
        cur.append_op("increment", {"X": counter}, {"Out": counter},
                      {"step": int(step)})
    return counter


def image_resize_short(input, out_short_len, resample="BILINEAR"):
    """reference layers/nn.py image_resize_short: scale so the SHORT
    edge becomes out_short_len, keeping aspect ratio (static shapes:
    computed at build time from the declared H/W)."""
    h, w = int(input.shape[2]), int(input.shape[3])
    short = min(h, w)
    ratio = float(out_short_len) / float(short)
    out_shape = [int(round(h * ratio)), int(round(w * ratio))]
    return image_resize(input, out_shape=out_shape, resample=resample)


def lod_reset(x, y=None, target_lod=None):
    """reference layers/nn.py lod_reset -> lod_reset_op.cc. Under the
    padded+@SEQ_LEN design the data is unchanged; the new lengths come
    from y's companion (or target_lod converted by the caller)."""
    helper = LayerHelper("lod_reset", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    ins = {"X": x}
    if y is not None:
        ins["Y"] = y
    helper.append_op("lod_reset", ins, {"Out": out},
                     {"target_lod": list(target_lod or [])})
    from .sequence import SEQ_LEN_SUFFIX

    block = out.block
    src = (y.name if y is not None else x.name) + SEQ_LEN_SUFFIX
    if block.has_var(src):
        dst = out.name + SEQ_LEN_SUFFIX
        helper.append_op("assign", {"X": src}, {"Out": dst}, {})
        block.create_var(name=dst, shape=(-1,), dtype="int32",
                         stop_gradient=True)
    return out


def mean_iou(input, label, num_classes):
    """reference layers/nn.py mean_iou -> mean_iou_op.cc."""
    helper = LayerHelper("mean_iou", input=input)
    miou = helper.create_variable_for_type_inference("float32", True)
    wrong = helper.create_variable_for_type_inference("float32", True)
    correct = helper.create_variable_for_type_inference("float32",
                                                        True)
    helper.append_op("mean_iou",
                     {"Predictions": input, "Labels": label},
                     {"OutMeanIou": miou, "OutWrong": wrong,
                      "OutCorrect": correct},
                     {"num_classes": num_classes})
    return miou, wrong, correct


def similarity_focus(input, axis, indexes, name=None):
    """reference layers/nn.py similarity_focus ->
    similarity_focus_op.cc."""
    helper = LayerHelper("similarity_focus", input=input, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("similarity_focus", {"X": input}, {"Out": out},
                     {"axis": axis, "indexes": list(indexes)})
    return out


def merge_selected_rows(x, name=None):
    """reference layers/nn.py merge_selected_rows: sum duplicate rows
    of a SelectedRows pair (rows var + values var, the sparse-grad
    representation — x is the values var, x@ROWS its companion)."""
    helper = LayerHelper("merge_selected_rows", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    rows_out = helper.create_variable_for_type_inference("int64", True)
    helper.append_op("merge_selected_rows",
                     {"Rows": x.name + "@ROWS", "Values": x},
                     {"OutRows": rows_out, "Out": out}, {})
    return out


def get_tensor_from_selected_rows(x, height=None, name=None):
    """reference layers/nn.py get_tensor_from_selected_rows: scatter a
    SelectedRows (values var + @ROWS companion) into a dense tensor."""
    helper = LayerHelper("get_tensor_from_selected_rows", input=x,
                         name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("get_tensor_from_selected_rows",
                     {"Rows": x.name + "@ROWS", "Values": x},
                     {"Out": out},
                     {"height": height or int(x.shape[0])})
    return out


def tree_conv(nodes_vector, edge_set, output_size, num_filters=1,
              max_depth=2, act="tanh", param_attr=None, bias_attr=None,
              name=None):
    """reference layers/nn.py tree_conv -> tree_conv_op.cc (TBCNN)."""
    helper = LayerHelper("tree_conv", input=nodes_vector,
                         param_attr=param_attr, bias_attr=bias_attr,
                         act=act, name=name)
    dtype = nodes_vector.dtype
    feature_size = int(nodes_vector.shape[-1])
    w = helper.create_parameter(
        helper.param_attr, [feature_size, 3, output_size, num_filters],
        dtype)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("tree_conv",
                     {"NodesVector": nodes_vector,
                      "EdgeSet": edge_set, "Filter": w},
                     {"Out": out}, {"max_depth": max_depth})
    if helper.bias_attr is not False:
        pre_act = helper.append_bias_op(out, dim_start=3)
    else:
        pre_act = out
    return helper.append_activation(pre_act)


__all__.extend([
    "stanh", "adaptive_pool3d", "gaussian_random_batch_size_like",
    "autoincreased_step_counter", "image_resize_short", "lod_reset",
    "mean_iou", "similarity_focus", "merge_selected_rows",
    "get_tensor_from_selected_rows", "tree_conv"])


# ---------------------------------------------------------------------------
# decoder-only language-model layers (ops/lm_ops.py; no reference
# counterpart: Fluid 1.x predates them)
# ---------------------------------------------------------------------------
def rms_norm(input, epsilon=1e-5, param_attr=None, name=None):
    """y = x / sqrt(mean(x^2) + eps) * scale over the last axis, scale
    a [D] parameter initialised to one."""
    helper = LayerHelper("rms_norm", input=input, param_attr=param_attr,
                         name=name)
    scale = helper.create_parameter(
        helper.param_attr, [input.shape[-1]], input.dtype,
        default_initializer=ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("rms_norm", {"X": input, "Scale": scale},
                     {"Y": out}, {"epsilon": float(epsilon)})
    return out


def rotary_embedding(x, theta=10000.0, name=None):
    """Rotary positions 0..T-1 on x [B, T, H, D]."""
    return _single_out(LayerHelper("rotary_embedding", input=x, name=name),
                       "rotary_embedding", {"X": x},
                       {"theta": float(theta)})


def swiglu(x, name=None):
    """silu(a) * b for x = [a, b] side by side on the last axis."""
    return _single_out(LayerHelper("swiglu", input=x, name=name),
                       "swiglu", {"X": x})


def short_conv(x, taps=3, param_attr=None, name=None):
    """The inside of a gated short convolution: x [B, T, 3D] holds
    [b, c, z]; returns c * causal_depthwise_conv(b * z) [B, T, D] with
    a [D, taps] filter parameter."""
    helper = LayerHelper("short_conv", input=x, param_attr=param_attr,
                         name=name)
    d = x.shape[-1] // 3
    w = helper.create_parameter(
        helper.param_attr, [d, taps], x.dtype,
        default_initializer=NormalInitializer(0.0, taps ** -0.5))
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("short_conv", {"X": x, "Filter": w}, {"Out": out},
                     {})
    return out


def moe_dropless(input, num_experts, d_inner, top_k, experts_held=None,
                 norm_topk=True, scaling=1.0, name=None,
                 scope="moe", activation="swiglu", expert_input=None):
    """Routed expert layer that drops NO token (parallel/moe.py
    `moe_dropless`; `switch_moe` above is the capacity routing, which
    drops what does not fit). A sigmoid router over all `num_experts`
    picks `top_k` a token by score plus a per-expert bias (a buffer,
    not trained: `<name>_bias`); `experts_held` = (first, count) says
    which contiguous experts this layer holds (default: all), and the
    output is their part of the result. Experts are gated feed-forward
    blocks W2(silu(W1 x) * W3 x) of width `d_inner`; parameters
    `<name>_gate.w` [D, E], `<name>_w13` [held, D, 2*d_inner] (W1 and
    W3 side by side), `<name>_w2` [held, d_inner, D].
    `activation` "relu2": experts that are not gated, W2 relu(W1 x)^2,
    with `<name>_w13` [held, D, d_inner] the one up matrix.
    `expert_input`: what the experts read where it is not what the
    router reads (a latent projection of `input`, [N, D_e]); the
    experts' matrices and the output are then D_e wide.
    Returns (out, chosen [N, top_k] int32, load [held] int32: pairs
    each held expert received, pairs_here [1] int32); the last three
    cost nothing unless fetched."""
    from ..param_attr import ParamAttr

    helper = LayerHelper("moe_dropless", input=input, name=name)
    d = input.shape[-1]
    prefix = name or helper.name
    first, held = experts_held or (0, num_experts)
    if not (0 <= first and first + held <= num_experts):
        raise ValueError(f"experts_held {experts_held} is not a range "
                         f"of {num_experts} experts")
    wg = helper.create_parameter(
        ParamAttr(name=f"{prefix}_gate.w"), [d, num_experts],
        input.dtype, default_initializer=NormalInitializer(0.0, 0.02))
    bias = helper.create_parameter(
        ParamAttr(name=f"{prefix}_bias", trainable=False),
        [num_experts], input.dtype,
        default_initializer=ConstantInitializer(0.0))
    if activation not in ("swiglu", "relu2"):
        raise ValueError(f"activation {activation!r}: swiglu or relu2")
    d_e = d if expert_input is None else expert_input.shape[-1]
    up = d_inner * (2 if activation == "swiglu" else 1)
    w13 = helper.create_parameter(
        ParamAttr(name=f"{prefix}_w13"), [held, d_e, up],
        input.dtype, default_initializer=NormalInitializer(0.0, d_e ** -0.5))
    w2 = helper.create_parameter(
        ParamAttr(name=f"{prefix}_w2"), [held, d_inner, d_e], input.dtype,
        default_initializer=NormalInitializer(0.0, d_inner ** -0.5))
    out = helper.create_variable_for_type_inference(input.dtype)
    extras = {}
    for slot, tag in (("Chosen", "chosen"), ("Load", "load"),
                      ("PairsHere", "pairs_here")):
        var = helper.create_variable(name=f"{prefix}_{tag}",
                                     dtype="int32", persistable=False)
        var.stop_gradient = True
        extras[slot] = var
    inputs = {"X": input, "GateW": wg, "ExpertBias": bias, "W13": w13,
              "W2": w2}
    attrs = {"first_held": int(first), "top_k": int(top_k),
             "norm_topk": bool(norm_topk), "scaling": float(scaling),
             "scope": scope}
    if expert_input is not None:
        inputs["ExpertX"] = expert_input
    if activation != "swiglu":
        attrs["activation"] = activation
    helper.append_op("moe_dropless", inputs, {"Out": out, **extras},
                     attrs)
    return out, extras["Chosen"], extras["Load"], extras["PairsHere"]


__all__.extend(["rms_norm", "rotary_embedding", "swiglu", "short_conv",
                "moe_dropless"])


# ---------------------------------------------------------------------------
# latent attention (MLA) and the sparse-attention indexer (ops/lm_ops.py)
# ---------------------------------------------------------------------------
def _matrix(helper, name, shape, dtype):
    from ..param_attr import ParamAttr

    return helper.create_parameter(
        ParamAttr(name=name), list(shape), dtype,
        default_initializer=NormalInitializer(0.0, shape[-2] ** -0.5))


def _ones(helper, name, size, dtype, value=1.0):
    from ..param_attr import ParamAttr

    return helper.create_parameter(
        ParamAttr(name=name), [size], dtype,
        default_initializer=ConstantInitializer(value))


def mla_project(x, pos, n_heads, q_lora_rank, kv_lora_rank,
                qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                rope_theta=10000.0, epsilon=1e-5, row_width=0, name=None):
    """Latent attention's projections of rows x [N, D] at positions
    pos [N]: returns (q_lat [N, H, W], the queries with the key
    up-projection absorbed; c_q [N, rq]; latent [N, W], the cache
    rows; kv_b, the up-projection parameter `mla_output` takes); W is
    `row_width` (default rkv + dr; zeros past rkv + dr).
    Parameters `<name>_q_a.w`, `_q_a_norm.w`, `_q_b.w`, `_kv_a.w`,
    `_kv_a_norm.w`, `_kv_b.w`."""
    helper = LayerHelper("mla_project", input=x, name=name)
    p = name or helper.name
    d, dt = x.shape[-1], x.dtype
    h, rq, rkv = n_heads, q_lora_rank, kv_lora_rank
    dn, dr, dv = qk_nope_head_dim, qk_rope_head_dim, v_head_dim
    qa = _matrix(helper, f"{p}_q_a.w", (d, rq), dt)
    qan = _ones(helper, f"{p}_q_a_norm.w", rq, dt)
    qb = _matrix(helper, f"{p}_q_b.w", (rq, h * (dn + dr)), dt)
    kva = _matrix(helper, f"{p}_kv_a.w", (d, rkv + dr), dt)
    kvan = _ones(helper, f"{p}_kv_a_norm.w", rkv, dt)
    kvb = _matrix(helper, f"{p}_kv_b.w", (rkv, h * (dn + dv)), dt)
    outs = {s: helper.create_variable_for_type_inference(dt, True)
            for s in ("QLat", "CQ", "Latent")}
    helper.append_op(
        "mla_project",
        {"X": x, "Pos": pos, "QA": qa, "QANorm": qan, "QB": qb,
         "KVA": kva, "KVANorm": kvan, "KVB": kvb}, outs,
        {"n_heads": int(h), "qk_nope_head_dim": int(dn),
         "qk_rope_head_dim": int(dr), "theta": float(rope_theta),
         "epsilon": float(epsilon), "row_width": int(row_width)})
    return outs["QLat"], outs["CQ"], outs["Latent"], kvb


def mla_output(ctx, kv_b, qk_nope_head_dim, name=None):
    """ctx [N, H, rkv] (attention over latent rows) through the value
    up-projection in kv_b -> [N, H*dv]."""
    helper = LayerHelper("mla_output", input=ctx, name=name)
    out = helper.create_variable_for_type_inference(kv_b.dtype, True)
    helper.append_op("mla_output", {"Ctx": ctx, "KVB": kv_b},
                     {"Out": out},
                     {"qk_nope_head_dim": int(qk_nope_head_dim)})
    return out


def dsa_indexer_project(x, c_q, pos, n_heads, head_dim, rope_dim,
                        rope_theta=10000.0, name=None):
    """The sparse-attention indexer's projections of rows x [N, D]
    (c_q [N, rq] from mla_project): (q_i [N, hi, di], k_i [N, di] the
    indexer's cache rows, w [N, hi] float32). Parameters
    `<name>_idx_q.w`, `_idx_k.w`, `_idx_k_norm.w`, `_idx_k_norm.b`,
    `_idx_w.w`."""
    helper = LayerHelper("dsa_indexer_project", input=x, name=name)
    p = name or helper.name
    d, dt, rq = x.shape[-1], x.dtype, c_q.shape[-1]
    iq = _matrix(helper, f"{p}_idx_q.w", (rq, n_heads * head_dim), dt)
    ik = _matrix(helper, f"{p}_idx_k.w", (d, head_dim), dt)
    ikw = _ones(helper, f"{p}_idx_k_norm.w", head_dim, dt)
    ikb = _ones(helper, f"{p}_idx_k_norm.b", head_dim, dt, 0.0)
    iw = _matrix(helper, f"{p}_idx_w.w", (d, n_heads), dt)
    qi = helper.create_variable_for_type_inference(dt, True)
    ki = helper.create_variable_for_type_inference(dt, True)
    w = helper.create_variable_for_type_inference("float32", True)
    helper.append_op(
        "dsa_indexer_project",
        {"X": x, "CQ": c_q, "Pos": pos, "IQ": iq, "IK": ik,
         "IKNormW": ikw, "IKNormB": ikb, "IW": iw},
        {"QI": qi, "KI": ki, "W": w},
        {"n_heads": int(n_heads), "rope_dim": int(rope_dim),
         "theta": float(rope_theta)})
    return qi, ki, w


def lm_head(x, vocab, param_attr, name=None):
    """float32 logits of rows x [N, D] through an untied head [D, V]
    kept in x's dtype."""
    helper = LayerHelper("lm_head", input=x, name=name)
    w = _matrix(helper, param_attr, (x.shape[-1], vocab), x.dtype)
    out = helper.create_variable_for_type_inference("float32", True)
    helper.append_op("lm_head", {"X": x, "W": w}, {"Out": out}, {})
    return out


def moe_tick_stats(chosen, active, first_held, n_held, name=None):
    """(pairs [1], hit [1], load [n_held]) that the rows with active 1
    sent to the held experts (chosen [N, k] from moe_dropless)."""
    helper = LayerHelper("moe_tick_stats", input=chosen, name=name)
    outs = {s: helper.create_variable_for_type_inference("int64", True)
            for s in ("Pairs", "Hit", "Load")}
    helper.append_op("moe_tick_stats",
                     {"Chosen": chosen, "Active": active}, outs,
                     {"first_held": int(first_held),
                      "n_held": int(n_held)})
    return outs["Pairs"], outs["Hit"], outs["Load"]


__all__.extend(["mla_project", "mla_output", "dsa_indexer_project",
                "lm_head", "moe_tick_stats"])


# ---------------------------------------------------------------------------
# state-space (Mamba-2) token mixing with per-lane state (ops/ssm_ops.py)
# and the activation of experts that are not gated
# ---------------------------------------------------------------------------
def relu2(x, name=None):
    """relu(x)^2, the activation of a feed-forward that is not gated
    (`mlp_hidden_act` "relu2"); float32 inside, x's dtype out."""
    helper = LayerHelper("relu2", input=x, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("relu2", {"X": x}, {"Out": out}, {})
    return out


def _chunk_or_tick(chunk, gate, pos):
    """The rows' place: a chunk of one lane ({"lane", "len", "pos"},
    each [1]; `gate` and `pos` are then not read) or one row a lane
    (gate [R], pos [R])."""
    if chunk is not None:
        return {"Lane": chunk["lane"], "Len": chunk["len"],
                "Pos": chunk["pos"]}
    return {"Gate": gate, "Pos": pos}


def causal_conv_tail(x, tail, kernel, name, chunk=None, gate=None,
                     pos=None, bias=True):
    """silu(causal depthwise convolution of x [N, W] with `<name>.w`
    [W, kernel] + `<name>.b` (none where `bias` is False)), the
    `kernel - 1` inputs before row 0 read from and left in `tail` [R,
    kernel-1, W] (per-lane state, in place). `chunk`: the rows are one
    lane's consecutive positions; else row r is lane r's next input
    (ops/ssm_ops.py)."""
    helper = LayerHelper("causal_conv_tail", input=x, name=name)
    width = x.shape[-1]
    w = _matrix(helper, f"{name}.w", (width, kernel), x.dtype)
    inputs = {"X": x, "Tail": tail, "Filter": w}
    if bias:
        inputs["Bias"] = _ones(helper, f"{name}.b", width, x.dtype, 0.0)
    out = helper.create_variable_for_type_inference(x.dtype, True)
    helper.append_op(
        "causal_conv_tail", {**inputs, **_chunk_or_tick(chunk, gate, pos)},
        {"Out": out, "TailOut": tail}, {})
    return out


def mamba2_scan(xbc, dt, state, n_groups, name, block=128, chunk=None,
                gate=None, pos=None):
    """y [N, H*P] float32 of the Mamba-2 recurrence on rows xbc [N, H*P
    + 2*G*N] (after the convolution) and dt [N, H], from and into the
    per-lane `state` [R, H, P, N] float32 (in place). `chunk`: one
    lane's consecutive positions, by the chunked form in blocks of
    `block` (op mamba2_chunk_scan); else one step of every lane (op
    mamba2_step). Parameters `<name>_dt_bias`, `<name>_A_log`,
    `<name>_D` [H] float32."""
    helper = LayerHelper("mamba2_scan", input=xbc, name=name)
    heads = state.shape[1]
    dt_bias, a_log, d_skip = (
        _ones(helper, f"{name}_{leaf}", heads, "float32", value)
        for leaf, value in (("dt_bias", 0.0), ("A_log", 0.0), ("D", 1.0)))
    y = helper.create_variable_for_type_inference("float32", True)
    attrs = {"n_groups": int(n_groups)}
    if chunk is not None:
        attrs["block"] = int(block)
    helper.append_op(
        "mamba2_step" if chunk is None else "mamba2_chunk_scan",
        {"XBC": xbc, "Dt": dt, "DtBias": dt_bias, "ALog": a_log,
         "D": d_skip, "State": state, **_chunk_or_tick(chunk, gate, pos)},
        {"Y": y, "StateOut": state}, attrs)
    return y


def gated_group_rms_norm(x, z, groups, epsilon=1e-5, param_attr=None,
                         name=None, norm_before_gate=False):
    """group_rms_norm(x * silu(z)) * w in `groups` groups of the last
    axis; x [N, D] float32, z [N, D]; z's dtype out.
    `norm_before_gate`: group_rms_norm(x) * w * silu(z), with one w [D
    / groups] that every group shares (Gated DeltaNet's order)."""
    helper = LayerHelper("gated_group_rms_norm", input=z, name=name)
    attrs = {"groups": int(groups), "epsilon": float(epsilon)}
    width = x.shape[-1]
    if norm_before_gate:
        attrs["norm_before_gate"] = True
        width //= groups
    scale = _ones(helper, param_attr, width, z.dtype)
    out = helper.create_variable_for_type_inference(z.dtype, True)
    helper.append_op("gated_group_rms_norm",
                     {"X": x, "Z": z, "Scale": scale}, {"Out": out}, attrs)
    return out


# ---------------------------------------------------------------------------
# gated delta-rule token mixing with per-lane state (ops/delta_ops.py)
# ---------------------------------------------------------------------------
def gated_delta(q, k, v, beta, a, state, name, block=64,
                allow_neg_eigval=False, chunk=None, gate=None, pos=None):
    """o [N, H*Dv] float32 of the gated delta rule on rows q, k [N,
    H*Dk], v [N, H*Dv] (after the convolution), beta and a [N, H]
    (before sigmoid and softplus), from and into the per-lane `state`
    [R, H, Dk, Dv] float32 (in place). `chunk`: one lane's consecutive
    positions, by the chunked form in blocks of `block` (op
    gated_delta_chunk); else one step of every lane (op
    gated_delta_step). Parameters `<name>_dt_bias`, `<name>_A_log` [H]
    float32."""
    helper = LayerHelper("gated_delta", input=q, name=name)
    heads = state.shape[1]
    dt_bias, a_log = (_ones(helper, f"{name}_{leaf}", heads, "float32",
                            0.0) for leaf in ("dt_bias", "A_log"))
    y = helper.create_variable_for_type_inference("float32", True)
    attrs = {"allow_neg_eigval": bool(allow_neg_eigval)}
    if chunk is not None:
        attrs["block"] = int(block)
    helper.append_op(
        "gated_delta_step" if chunk is None else "gated_delta_chunk",
        {"Q": q, "K": k, "V": v, "Beta": beta, "A": a, "DtBias": dt_bias,
         "ALog": a_log, "State": state, **_chunk_or_tick(chunk, gate, pos)},
        {"Y": y, "StateOut": state}, attrs)
    return y


def rope_at(x, pos, n_heads, theta, name=None):
    """Rotary positions (rotate-half layout) on the `n_heads` heads of
    rows x [N, H*D] at positions pos [N] (ops/lm_ops.py)."""
    return _single_out(LayerHelper("rope_at", input=x, name=name),
                       "rope_at", {"X": x, "Pos": pos},
                       {"n_heads": int(n_heads), "theta": float(theta)})


__all__.extend(["relu2", "causal_conv_tail", "mamba2_scan",
                "gated_group_rms_norm", "gated_delta", "rope_at"])
