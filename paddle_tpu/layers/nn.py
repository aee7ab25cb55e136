"""Neural-net layers DSL.

Capability parity with reference python/paddle/fluid/layers/nn.py (fc :117,
embedding :229, dynamic_lstm :293, dynamic_gru :597, conv2d :1365,
pool2d :1838, batch_norm :2000, layer_norm :2151, dropout, softmax,
softmax_with_cross_entropy :4195, reshape :4382, topk, ...). Layers append
IR ops; the executor compiles the whole block into one XLA computation.
"""

from __future__ import annotations

import numpy as np

from ..core import ir
from ..core import registry as _registry
from ..core.ir import seqlen_var_name
from ..layer_helper import LayerHelper
from .. import initializer as init


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, is_test=False, name=None):
    """Fully-connected layer (reference nn.py:117)."""
    helper = LayerHelper("fc", **locals())
    dtype = input[0].dtype if isinstance(input, (list, tuple)) else input.dtype
    inputs = input if isinstance(input, (list, tuple)) else [input]
    param_attrs = param_attr if isinstance(param_attr, (list, tuple)) \
        else [param_attr] * len(inputs)
    mul_results = []
    for inp, pattr in zip(inputs, param_attrs):
        in_features = 1
        for d in inp.shape[num_flatten_dims:]:
            in_features *= d
        w = helper.create_parameter(pattr, [in_features, size], dtype)
        tmp = helper.create_variable_for_type_inference(dtype)
        helper.append_op("mul", inputs={"X": [inp.name], "Y": [w.name]},
                         outputs={"Out": [tmp.name]},
                         attrs={"x_num_col_dims": num_flatten_dims,
                                "y_num_col_dims": 1})
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(dtype)
        helper.append_op("sum", inputs={"X": [m.name for m in mul_results]},
                         outputs={"Out": [pre_bias.name]})
    pre_act = _append_bias(helper, pre_bias, dim_start=num_flatten_dims)
    pre_act.lod_level = inputs[0].lod_level
    return helper.append_activation(pre_act)


def _append_bias(helper, input_var, dim_start=1):
    battr = helper.bias_attr
    if battr is False:
        return input_var
    size = input_var.shape[-1] if input_var.shape else 1
    b = helper.create_parameter(battr, [size], input_var.dtype, is_bias=True)
    out = helper.create_variable_for_type_inference(dtype=input_var.dtype)
    helper.append_op("elementwise_add",
                     inputs={"X": [input_var.name], "Y": [b.name]},
                     outputs={"Out": [out.name]}, attrs={"axis": -1})
    out.lod_level = input_var.lod_level
    return out


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32"):
    """Embedding lookup (reference nn.py:229). is_sparse maps to the same
    dense-table gather on TPU (sparse grads become scatter-adds in XLA)."""
    helper = LayerHelper("embedding", **locals())
    w = helper.create_parameter(param_attr, size, dtype)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("lookup_table",
                     inputs={"W": [w.name], "Ids": [input.name]},
                     outputs={"Out": [out.name]},
                     attrs={"padding_idx": -1 if padding_idx is None else padding_idx,
                            "is_sparse": is_sparse,
                            "is_distributed": is_distributed})
    out.lod_level = input.lod_level
    return out


def dynamic_lstm(input, size, h_0=None, c_0=None, param_attr=None,
                 bias_attr=None, use_peepholes=True, is_reverse=False,
                 gate_activation="sigmoid", cell_activation="tanh",
                 candidate_activation="tanh", dtype="float32", name=None):
    """LSTM over a variable-length batch (reference nn.py:293, including
    its use_peepholes=True default). `input` is the x-projection
    [B, T, 4*size] (apply `fc` first, as in the reference). With peepholes
    the bias packs [4H gate biases | W_ic | W_if | W_oc] (lstm_op.cc)."""
    helper = LayerHelper("lstm", **locals())
    hidden_size = size // 4
    bias_cols = 7 * hidden_size if use_peepholes else 4 * hidden_size
    weight = helper.create_parameter(param_attr, [hidden_size, 4 * hidden_size], dtype)
    bias = helper.create_parameter(helper.bias_attr, [1, bias_cols], dtype,
                                   is_bias=True) if bias_attr is not False else None
    hidden = helper.create_variable_for_type_inference(dtype)
    cell = helper.create_variable_for_type_inference(dtype)
    inputs = {"Input": [input.name], "Weight": [weight.name]}
    if bias is not None:
        inputs["Bias"] = [bias.name]
    if h_0 is not None:
        inputs["H0"] = [h_0.name]
    if c_0 is not None:
        inputs["C0"] = [c_0.name]
    seq = helper.ensure_seqlen_var(input)
    if seq is not None:
        inputs["SeqLen"] = [seq.name]
    helper.append_op("lstm", inputs=inputs,
                     outputs={"Hidden": [hidden.name], "Cell": [cell.name]},
                     attrs={"use_peepholes": use_peepholes, "is_reverse": is_reverse,
                            "gate_activation": gate_activation,
                            "cell_activation": cell_activation,
                            "candidate_activation": candidate_activation})
    hidden.lod_level = cell.lod_level = input.lod_level
    return hidden, cell


def dynamic_gru(input, size, param_attr=None, bias_attr=None, is_reverse=False,
                gate_activation="sigmoid", candidate_activation="tanh",
                h_0=None, name=None):
    """GRU over a variable-length batch (reference nn.py:597). `input` is the
    x-projection [B, T, 3*size]."""
    helper = LayerHelper("gru", **locals())
    dtype = input.dtype
    weight = helper.create_parameter(param_attr, [size, 3 * size], dtype)
    bias = helper.create_parameter(helper.bias_attr, [1, 3 * size], dtype,
                                   is_bias=True) if bias_attr is not False else None
    hidden = helper.create_variable_for_type_inference(dtype)
    inputs = {"Input": [input.name], "Weight": [weight.name]}
    if bias is not None:
        inputs["Bias"] = [bias.name]
    if h_0 is not None:
        inputs["H0"] = [h_0.name]
    seq = helper.ensure_seqlen_var(input)
    if seq is not None:
        inputs["SeqLen"] = [seq.name]
    helper.append_op("gru", inputs=inputs, outputs={"Hidden": [hidden.name]},
                     attrs={"is_reverse": is_reverse,
                            "gate_activation": gate_activation,
                            "activation": candidate_activation})
    hidden.lod_level = input.lod_level
    return hidden


def gru_unit(input, hidden, size, param_attr=None, bias_attr=None,
             activation="tanh", gate_activation="sigmoid"):
    helper = LayerHelper("gru_unit", **locals())
    dtype = input.dtype
    hidden_size = size // 3
    weight = helper.create_parameter(param_attr, [hidden_size, 3 * hidden_size], dtype)
    bias = helper.create_parameter(helper.bias_attr, [1, 3 * hidden_size], dtype,
                                   is_bias=True) if bias_attr is not False else None
    out_hidden = helper.create_variable_for_type_inference(dtype)
    reset_h = helper.create_variable_for_type_inference(dtype)
    gate = helper.create_variable_for_type_inference(dtype)
    inputs = {"Input": [input.name], "HiddenPrev": [hidden.name],
              "Weight": [weight.name]}
    if bias is not None:
        inputs["Bias"] = [bias.name]
    helper.append_op("gru_unit", inputs=inputs,
                     outputs={"Hidden": [out_hidden.name],
                              "ResetHiddenPrev": [reset_h.name],
                              "Gate": [gate.name]},
                     attrs={"activation": activation,
                            "gate_activation": gate_activation})
    return out_hidden, reset_h, gate


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None, data_format="NCHW"):
    """2-D convolution, NCHW or NHWC (reference nn.py:1365). `use_cudnn` is
    accepted for API parity and ignored — XLA owns kernel selection on TPU.
    On TPU prefer data_format="NHWC": it matches the native conv layout and
    avoids relayout transposes. Filters are stored OIHW either way."""
    helper = LayerHelper("conv2d", **locals())
    dtype = input.dtype
    c_axis = 1 if data_format == "NCHW" else len(input.shape) - 1
    num_channels = input.shape[c_axis]
    fsize = filter_size if isinstance(filter_size, (list, tuple)) \
        else [filter_size, filter_size]
    filter_shape = [num_filters, num_channels // groups] + list(fsize)
    std = (2.0 / (fsize[0] * fsize[1] * num_channels)) ** 0.5
    w = helper.create_parameter(param_attr, filter_shape, dtype,
                                default_initializer=init.NormalInitializer(0.0, std))
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op("conv2d",
                     inputs={"Input": [input.name], "Filter": [w.name]},
                     outputs={"Output": [pre_bias.name]},
                     attrs={"strides": _pair(stride), "paddings": _pair(padding),
                            "dilations": _pair(dilation), "groups": groups,
                            "data_format": data_format})
    pre_act = _append_bias_channel(helper, pre_bias, axis=c_axis)
    return helper.append_activation(pre_act)


def _pair(v):
    return list(v) if isinstance(v, (list, tuple)) else [v, v]


def _append_bias_channel(helper, input_var, axis=1):
    battr = helper.bias_attr
    if battr is False:
        return input_var
    size = input_var.shape[axis] if len(input_var.shape) > axis else 1
    b = helper.create_parameter(battr, [size], input_var.dtype, is_bias=True)
    out = helper.create_variable_for_type_inference(dtype=input_var.dtype)
    helper.append_op("elementwise_add",
                     inputs={"X": [input_var.name], "Y": [b.name]},
                     outputs={"Out": [out.name]}, attrs={"axis": axis})
    return out


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, param_attr=None,
                     bias_attr=None, act=None, name=None, use_cudnn=True):
    helper = LayerHelper("conv2d_transpose", **locals())
    dtype = input.dtype
    num_channels = input.shape[1]
    if filter_size is None:
        # derive from output_size (reference nn.py:2377-2390)
        if output_size is None:
            raise ValueError("filter_size or output_size must be set")
        osz = [output_size] * 2 if isinstance(output_size, int) \
            else list(output_size)
        st, pd, dl = _pair(stride), _pair(padding), _pair(dilation)
        filter_size = [(osz[i] - (input.shape[2 + i] - 1) * st[i]
                        + 2 * pd[i] - 1) // dl[i] + 1 for i in range(2)]
    fsize = filter_size if isinstance(filter_size, (list, tuple)) \
        else [filter_size, filter_size]
    filter_shape = [num_channels, num_filters] + list(fsize)
    w = helper.create_parameter(param_attr, filter_shape, dtype)
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op("conv2d_transpose",
                     inputs={"Input": [input.name], "Filter": [w.name]},
                     outputs={"Output": [pre_bias.name]},
                     attrs={"strides": _pair(stride), "paddings": _pair(padding),
                            "dilations": _pair(dilation)})
    pre_act = _append_bias_channel(helper, pre_bias)
    return helper.append_activation(pre_act)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1, pool_padding=0,
           global_pooling=False, use_cudnn=True, ceil_mode=False,
           exclusive=True, name=None, data_format="NCHW", adaptive=False):
    helper = LayerHelper("pool2d", **locals())
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("pool2d", inputs={"X": [input.name]},
                     outputs={"Out": [out.name]},
                     attrs={"pooling_type": pool_type, "ksize": _pair(pool_size),
                            "strides": _pair(pool_stride),
                            "paddings": _pair(pool_padding),
                            "global_pooling": global_pooling,
                            "exclusive": exclusive, "adaptive": adaptive,
                            "data_format": data_format})
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               in_place=False, name=None, moving_mean_name=None,
               moving_variance_name=None, do_model_average_for_mean_and_var=False):
    """Batch normalization (reference nn.py:2000)."""
    helper = LayerHelper("batch_norm", **locals())
    dtype = input.dtype
    c_axis = 1 if data_layout == "NCHW" else len(input.shape) - 1
    channels = input.shape[c_axis]
    scale = helper.create_parameter(param_attr, [channels], dtype,
                                    default_initializer=init.ConstantInitializer(1.0))
    bias = helper.create_parameter(helper.bias_attr, [channels], dtype, is_bias=True)
    mean = helper.create_parameter(
        moving_mean_name, [channels], dtype,
        default_initializer=init.ConstantInitializer(0.0), stop_gradient=True)
    variance = helper.create_parameter(
        moving_variance_name, [channels], dtype,
        default_initializer=init.ConstantInitializer(1.0), stop_gradient=True)
    mean.trainable = False
    variance.trainable = False
    y = helper.create_variable_for_type_inference(dtype)
    saved_mean = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    saved_var = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op("batch_norm",
                     inputs={"X": [input.name], "Scale": [scale.name],
                             "Bias": [bias.name], "Mean": [mean.name],
                             "Variance": [variance.name]},
                     outputs={"Y": [y.name], "MeanOut": [mean.name],
                              "VarianceOut": [variance.name],
                              "SavedMean": [saved_mean.name],
                              "SavedVariance": [saved_var.name]},
                     attrs={"momentum": momentum, "epsilon": epsilon,
                            "is_test": is_test, "data_layout": data_layout})
    return helper.append_activation(y)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1, epsilon=1e-5,
               param_attr=None, bias_attr=None, act=None, name=None):
    helper = LayerHelper("layer_norm", **locals())
    dtype = input.dtype
    norm_shape = [1]
    for d in input.shape[begin_norm_axis:]:
        norm_shape[0] *= d
    inputs = {"X": [input.name]}
    if scale:
        s = helper.create_parameter(param_attr, norm_shape, dtype,
                                    default_initializer=init.ConstantInitializer(1.0))
        inputs["Scale"] = [s.name]
    if shift:
        b = helper.create_parameter(helper.bias_attr, norm_shape, dtype, is_bias=True)
        inputs["Bias"] = [b.name]
    y = helper.create_variable_for_type_inference(dtype)
    mean = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    var = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op("layer_norm", inputs=inputs,
                     outputs={"Y": [y.name], "Mean": [mean.name],
                              "Variance": [var.name]},
                     attrs={"epsilon": epsilon, "begin_norm_axis": begin_norm_axis})
    y.lod_level = input.lod_level
    return helper.append_activation(y)


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper("dropout", **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    mask = helper.create_variable_for_type_inference(dtype=x.dtype, stop_gradient=True)
    helper.append_op("dropout", inputs={"X": [x.name]},
                     outputs={"Out": [out.name], "Mask": [mask.name]},
                     attrs={"dropout_prob": dropout_prob, "is_test": is_test,
                            "dropout_implementation": dropout_implementation})
    out.lod_level = x.lod_level
    return out


def softmax(input, axis=-1, use_cudnn=True, name=None):
    helper = LayerHelper("softmax", **locals())
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("softmax", inputs={"X": [input.name]},
                     outputs={"Out": [out.name]}, attrs={"axis": axis})
    out.lod_level = input.lod_level
    return out


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("cross_entropy",
                     inputs={"X": [input.name], "Label": [label.name]},
                     outputs={"Y": [out.name]},
                     attrs={"soft_label": soft_label, "ignore_index": ignore_index})
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, return_softmax=False):
    helper = LayerHelper("softmax_with_cross_entropy")
    softmax_out = helper.create_variable_for_type_inference(dtype=logits.dtype)
    loss = helper.create_variable_for_type_inference(dtype=logits.dtype)
    # hidden log-sum-exp output ([rows, 1] f32 — tiny): the grad rule
    # rebuilds softmax as exp(logits - lse) from it, pure elementwise, so
    # the backward re-runs no [rows, V] reductions and no [rows, V]
    # probabilities tensor crosses the fwd/bwd boundary
    lse_out = helper.create_variable_for_type_inference(dtype="float32")
    helper.append_op("softmax_with_cross_entropy",
                     inputs={"Logits": [logits.name], "Label": [label.name]},
                     outputs={"Softmax": [softmax_out.name], "Loss": [loss.name],
                              "LSE": [lse_out.name]},
                     attrs={"soft_label": soft_label, "ignore_index": ignore_index})
    lse_out.stop_gradient = True
    if return_softmax:
        return loss, softmax_out
    return loss


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100, name=None):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op("sigmoid_cross_entropy_with_logits",
                     inputs={"X": [x.name], "Label": [label.name]},
                     outputs={"Out": [out.name]},
                     attrs={"ignore_index": ignore_index})
    return out


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("square_error_cost",
                     inputs={"X": [input.name], "Y": [label.name]},
                     outputs={"Out": [out.name]})
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1_loss")
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    diff = helper.create_variable_for_type_inference(dtype=x.dtype)
    inputs = {"X": [x.name], "Y": [y.name]}
    if inside_weight is not None:
        inputs["InsideWeight"] = [inside_weight.name]
    if outside_weight is not None:
        inputs["OutsideWeight"] = [outside_weight.name]
    helper.append_op("smooth_l1_loss", inputs=inputs,
                     outputs={"Out": [out.name], "Diff": [diff.name]},
                     attrs={"sigma": sigma or 1.0})
    return out


def mean(x, name=None):
    helper = LayerHelper("mean", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op("mean", inputs={"X": [x.name]}, outputs={"Out": [out.name]})
    return out


def _reduce_layer(op_type):
    def layer(input, dim=None, keep_dim=False, name=None):
        helper = LayerHelper(op_type, name=name)
        out = helper.create_variable_for_type_inference(dtype=input.dtype)
        if dim is None:
            attrs = {"reduce_all": True, "keep_dim": keep_dim}
        else:
            dims = dim if isinstance(dim, (list, tuple)) else [dim]
            attrs = {"dim": list(dims), "keep_dim": keep_dim, "reduce_all": False}
        helper.append_op(op_type, inputs={"X": [input.name]},
                         outputs={"Out": [out.name]}, attrs=attrs)
        return out

    layer.__name__ = op_type
    return layer


reduce_sum = _reduce_layer("reduce_sum")
reduce_mean = _reduce_layer("reduce_mean")
reduce_max = _reduce_layer("reduce_max")
reduce_min = _reduce_layer("reduce_min")
reduce_prod = _reduce_layer("reduce_prod")


def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper("reshape", **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op("reshape", inputs={"X": [x.name]},
                     outputs={"Out": [out.name]}, attrs={"shape": list(shape)})
    return helper.append_activation(out)


def squeeze(input, axes=None, name=None):
    helper = LayerHelper("squeeze", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("squeeze", inputs={"X": [input.name]},
                     outputs={"Out": [out.name]}, attrs={"axes": axes or []})
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("unsqueeze", inputs={"X": [input.name]},
                     outputs={"Out": [out.name]}, attrs={"axes": list(axes)})
    return out


def transpose(x, perm, name=None):
    helper = LayerHelper("transpose", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op("transpose", inputs={"X": [x.name]},
                     outputs={"Out": [out.name]}, attrs={"axis": list(perm)})
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op("matmul", inputs={"X": [x.name], "Y": [y.name]},
                     outputs={"Out": [out.name]},
                     attrs={"transpose_X": transpose_x, "transpose_Y": transpose_y,
                            "alpha": float(alpha)})
    return out


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    values = helper.create_variable_for_type_inference(dtype=input.dtype)
    indices = helper.create_variable_for_type_inference(dtype="int64",
                                                        stop_gradient=True)
    helper.append_op("top_k", inputs={"X": [input.name]},
                     outputs={"Out": [values.name], "Indices": [indices.name]},
                     attrs={"k": k})
    return values, indices


def one_hot(input, depth):
    helper = LayerHelper("one_hot")
    out = helper.create_variable_for_type_inference(dtype="float32")
    helper.append_op("one_hot", inputs={"X": [input.name]},
                     outputs={"Out": [out.name]}, attrs={"depth": depth})
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", name=name)
    if isinstance(num_or_sections, int):
        num = num_or_sections
        sections = []
    else:
        num = 0
        sections = list(num_or_sections)
    n_outs = num if num else len(sections)
    outs = [helper.create_variable_for_type_inference(dtype=input.dtype)
            for _ in range(n_outs)]
    helper.append_op("split", inputs={"X": [input.name]},
                     outputs={"Out": [o.name for o in outs]},
                     attrs={"num": num, "sections": sections, "axis": dim})
    return outs


def slice(input, axes, starts, ends):
    helper = LayerHelper("slice")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("slice", inputs={"Input": [input.name]},
                     outputs={"Out": [out.name]},
                     attrs={"axes": list(axes), "starts": list(starts),
                            "ends": list(ends)})
    return out


def gather(input, index):
    helper = LayerHelper("gather")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("gather", inputs={"X": [input.name], "Index": [index.name]},
                     outputs={"Out": [out.name]})
    return out


def scatter(input, index, updates, overwrite=True, name=None):
    helper = LayerHelper("scatter", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("scatter",
                     inputs={"X": [input.name], "Ids": [index.name],
                             "Updates": [updates.name]},
                     outputs={"Out": [out.name]}, attrs={"overwrite": overwrite})
    return out


def expand(x, expand_times, name=None):
    helper = LayerHelper("expand", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op("expand", inputs={"X": [x.name]},
                     outputs={"Out": [out.name]},
                     attrs={"expand_times": list(expand_times)})
    return out


def stack(x, axis=0):
    helper = LayerHelper("stack")
    out = helper.create_variable_for_type_inference(dtype=x[0].dtype)
    helper.append_op("stack", inputs={"X": [v.name for v in x]},
                     outputs={"Y": [out.name]}, attrs={"axis": axis})
    return out


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper("pad", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op("pad", inputs={"X": [x.name]}, outputs={"Out": [out.name]},
                     attrs={"paddings": list(paddings), "pad_value": pad_value})
    return out


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    norm = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op("l2_normalize", inputs={"X": [x.name]},
                     outputs={"Out": [out.name], "Norm": [norm.name]},
                     attrs={"axis": axis, "epsilon": epsilon})
    return out


def clip(x, min, max, name=None):
    helper = LayerHelper("clip", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op("clip", inputs={"X": [x.name]}, outputs={"Out": [out.name]},
                     attrs={"min": float(min), "max": float(max)})
    return out


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper("clip_by_norm", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op("clip_by_norm", inputs={"X": [x.name]},
                     outputs={"Out": [out.name]}, attrs={"max_norm": float(max_norm)})
    return out


def relu(x, name=None):
    from . import ops as _ops
    return _ops.relu(x, name=name)


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper("scale", **locals())
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op("scale", inputs={"X": [x.name]}, outputs={"Out": [out.name]},
                     attrs={"scale": float(scale), "bias": float(bias),
                            "bias_after_scale": bias_after_scale})
    out.lod_level = x.lod_level
    return helper.append_activation(out)


def prelu(x, mode="all", param_attr=None, name=None):
    helper = LayerHelper("prelu", **locals())
    if mode == "all":
        alpha_shape = [1]
    elif mode == "channel":
        alpha_shape = [x.shape[1]]
    else:
        alpha_shape = [int(d) if d > 0 else 1 for d in x.shape[1:]]
    alpha = helper.create_parameter(param_attr, alpha_shape, x.dtype,
                                    default_initializer=init.ConstantInitializer(0.25))
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op("prelu", inputs={"X": [x.name], "Alpha": [alpha.name]},
                     outputs={"Out": [out.name]}, attrs={"mode": mode})
    return out


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper("lrn", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    mid = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("lrn", inputs={"X": [input.name]},
                     outputs={"Out": [out.name], "MidOut": [mid.name]},
                     attrs={"n": n, "k": k, "alpha": alpha, "beta": beta})
    return out


# -- sequence layers (LoD analogs) ------------------------------------------

# layers whose op rules implement the innermost-level (nested LoD)
# adapter — everything else still refuses level-2 input at build time
# rather than failing cryptically inside jit tracing
_NESTED_CAPABLE = {"sequence_pool", "sequence_softmax", "sequence_conv",
                   "sequence_reshape", "sequence_erase", "sequence_slice",
                   "sequence_expand", "sequence_concat"}


def _seq_inputs(helper, x, extra=None):
    # sequence ops act on the INNERMOST LoD level (reference
    # lod_tensor.h:110): for nested (level-2) inputs the wired companion
    # is the [B, S] inner lengths; the op rules flatten (doc, sentence)
    # rows, run the level-1 semantics, and restore the nesting
    if (getattr(x, "lod_level", 0) >= 2
            and helper.layer_type not in _NESTED_CAPABLE):
        raise NotImplementedError(
            f"{helper.layer_type}: nested (level-2) LoD input is supported "
            f"by {sorted(_NESTED_CAPABLE)}; pool the inner level first")
    inputs = {"X": [x.name]}
    level = max(getattr(x, "lod_level", 0) - 1, 0)
    seq = helper.ensure_seqlen_var(x, level=level)
    if seq is not None:
        inputs["SeqLen"] = [seq.name]
    if extra:
        inputs.update(extra)
    return inputs


def _alias_seqlen(helper, src, dst):
    """Length-preserving sequence ops (sequence_conv, row_conv, ...) carry
    their input's @SEQLEN onto the output with an explicit assign — the
    runtime propagation in lowering.py only walks propagate_seqlen=True ops,
    and a downstream sequence op would otherwise read an unmaterialized
    companion. All LoD levels are aliased (outer doc counts AND inner
    sentence lengths for nested inputs)."""
    dst.lod_level = max(dst.lod_level, src.lod_level)
    for level in range(dst.lod_level):
        seq_src = helper.ensure_seqlen_var(src, level=level)
        if seq_src is None:
            continue
        seq_dst = helper.ensure_seqlen_var(dst, level=level)
        helper.append_op("assign", inputs={"X": [seq_src.name]},
                         outputs={"Out": [seq_dst.name]})


def sequence_pool(input, pool_type, is_test=False):
    helper = LayerHelper("sequence_pool")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    if input.lod_level >= 2:
        # nested LoD: pool the INNERMOST level (reference semantics); the
        # result keeps the remaining outer level, whose lengths alias the
        # input's outer companion
        inner = helper.ensure_seqlen_var(input, level=1)
        helper.append_op("sequence_pool",
                         inputs={"X": [input.name],
                                 "SeqLen": [inner.name]},
                         outputs={"Out": [out.name]},
                         attrs={"pooltype": pool_type.upper()})
        out.lod_level = input.lod_level - 1
        outer_src = helper.ensure_seqlen_var(input, level=0)
        outer_dst = helper.ensure_seqlen_var(out, level=0)
        helper.append_op("assign", inputs={"X": [outer_src.name]},
                         outputs={"Out": [outer_dst.name]})
        return out
    helper.append_op("sequence_pool", inputs=_seq_inputs(helper, input),
                     outputs={"Out": [out.name]},
                     attrs={"pooltype": pool_type.upper()})
    return out


def sequence_first_step(input):
    return sequence_pool(input, "first")


def sequence_last_step(input):
    return sequence_pool(input, "last")


def cos_sim(X, Y):
    """Row-wise cosine similarity (reference nn.py cos_sim)."""
    helper = LayerHelper("cos_sim")
    out = helper.create_variable_for_type_inference(dtype=X.dtype)
    xn = helper.create_variable_for_type_inference(dtype=X.dtype)
    yn = helper.create_variable_for_type_inference(dtype=X.dtype)
    helper.append_op("cos_sim", inputs={"X": [X.name], "Y": [Y.name]},
                     outputs={"Out": [out.name], "XNorm": [xn.name],
                              "YNorm": [yn.name]})
    return out


def sequence_softmax(input, use_cudnn=False, name=None):
    helper = LayerHelper("sequence_softmax", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("sequence_softmax", inputs=_seq_inputs(helper, input),
                     outputs={"Out": [out.name]})
    out.lod_level = input.lod_level
    _alias_seqlen(helper, input, out)
    return out


def sequence_concat(input, name=None):
    """Concatenate sequences row-wise along the time axis (reference
    sequence_concat_op.cc): row b of the output is
    concat_i(x_i[b, :len_i[b]]), left-aligned, with length sum_i len_i.
    Inputs without a lengths companion contribute their full rows.
    Nested (level-2) inputs concatenate the innermost level per
    (doc, sentence) row; the outer counts ride through from the first
    input."""
    helper = LayerHelper("sequence_concat", name=name)
    xs = list(input) if isinstance(input, (list, tuple)) else [input]
    levels = {getattr(x, "lod_level", 0) for x in xs}
    if len(levels) > 1:
        # refuse at build time (the module contract above _NESTED_CAPABLE):
        # the nested op rule flattens every input as [B, S, ...], so a
        # mixed-level list would die cryptically inside jit tracing
        raise ValueError(
            f"sequence_concat: inputs must share one LoD level, got "
            f"{sorted(levels)} (reference sequence_concat_op.cc requires "
            f"matching LoD structure)")
    out = helper.create_variable_for_type_inference(dtype=xs[0].dtype)
    out.lod_level = max(levels)
    inputs = {"X": [x.name for x in xs]}
    seq_names, wired = [], False
    for x in xs:
        level = max(getattr(x, "lod_level", 0) - 1, 0)
        s = helper.ensure_seqlen_var(x, level=level)
        if s is None:
            seq_names.append(_registry.EMPTY_VAR)   # full-length rows
        else:
            seq_names.append(s.name)
            wired = True
    outputs = {"Out": [out.name]}
    if wired and out.lod_level:
        inputs["SeqLen"] = seq_names
        seq_out = helper.ensure_seqlen_var(out, level=out.lod_level - 1)
        outputs["OutLen"] = [seq_out.name]
        for lvl in range(out.lod_level - 1):      # nested: outer doc counts
            src = helper.ensure_seqlen_var(xs[0], level=lvl)
            if src is not None:
                dst = helper.ensure_seqlen_var(out, level=lvl)
                helper.append_op("assign", inputs={"X": [src.name]},
                                 outputs={"Out": [dst.name]})
    helper.append_op("sequence_concat", inputs=inputs, outputs=outputs)
    return out


def sequence_expand(x, y, ref_level=-1, name=None):
    helper = LayerHelper("sequence_expand", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op("sequence_expand",
                     inputs={"X": [x.name], "Y": [y.name]},
                     outputs={"Out": [out.name]}, attrs={"ref_level": ref_level})
    out.lod_level = y.lod_level
    # the output inherits Y's time axis, so its lengths are Y's
    _alias_seqlen(helper, y, out)
    return out


def sequence_conv(input, num_filters, filter_size=3, filter_stride=1,
                  padding=None, bias_attr=None, param_attr=None, act=None):
    helper = LayerHelper("sequence_conv", **locals())
    dtype = input.dtype
    d = input.shape[-1]
    w = helper.create_parameter(param_attr, [filter_size * d, num_filters], dtype)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("sequence_conv",
                     inputs=_seq_inputs(helper, input, {"Filter": [w.name]}),
                     outputs={"Out": [out.name]},
                     attrs={"contextLength": filter_size,
                            "contextStart": -(filter_size // 2),
                            "contextStride": filter_stride})
    out.lod_level = input.lod_level
    pre_act = _append_bias(helper, out)
    final = helper.append_activation(pre_act)
    # alias onto the FINAL var: downstream sequence ops read its companion,
    # and pruning keeps the alias only if its output is the one they read
    _alias_seqlen(helper, input, final)
    return final


def sequence_reshape(input, new_dim):
    helper = LayerHelper("sequence_reshape")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    out.lod_level = input.lod_level
    outputs = {"Out": [out.name]}
    if input.lod_level > 0:
        # lengths scale by D/new_dim — emitted by the op itself (OutLen)
        # onto the INNERMOST companion; outer doc counts ride through
        seq_out = helper.ensure_seqlen_var(out, level=input.lod_level - 1)
        outputs["OutLen"] = [seq_out.name]
    helper.append_op("sequence_reshape", inputs=_seq_inputs(helper, input),
                     outputs=outputs, attrs={"new_dim": new_dim})
    for level in range(input.lod_level - 1):
        src = helper.ensure_seqlen_var(input, level=level)
        if src is not None:
            dst = helper.ensure_seqlen_var(out, level=level)
            helper.append_op("assign", inputs={"X": [src.name]},
                             outputs={"Out": [dst.name]})
    return out


def row_conv(input, future_context_size, param_attr=None, act=None):
    helper = LayerHelper("row_conv", **locals())
    d = input.shape[-1]
    w = helper.create_parameter(param_attr, [future_context_size + 1, d],
                                input.dtype)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("row_conv",
                     inputs=_seq_inputs(helper, input, {"Filter": [w.name]}),
                     outputs={"Out": [out.name]})
    out.lod_level = input.lod_level
    final = helper.append_activation(out)
    _alias_seqlen(helper, input, final)
    return final


def im2sequence(input, filter_size=1, stride=1, padding=0, name=None):
    helper = LayerHelper("im2sequence", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    p = _pair(padding)
    helper.append_op("im2sequence", inputs={"X": [input.name]},
                     outputs={"Out": [out.name]},
                     attrs={"kernels": _pair(filter_size), "strides": _pair(stride),
                            "paddings": p + p})
    return out


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    helper = LayerHelper("sequence_mask", name=name)
    out = helper.create_variable_for_type_inference(dtype=dtype)
    helper.append_op("sequence_mask", inputs={"X": [x.name]},
                     outputs={"Y": [out.name]},
                     attrs={"maxlen": maxlen if maxlen else -1, "out_dtype": dtype})
    return out


# ---------------------------------------------------------------------------
# breadth layers completing the reference nn.py surface (3-D, image, misc)
# ---------------------------------------------------------------------------

def conv3d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=None, param_attr=None, bias_attr=None, act=None, name=None):
    """reference nn.py conv3d."""
    helper = LayerHelper("conv3d", **locals())
    k = filter_size if isinstance(filter_size, (list, tuple)) \
        else [filter_size] * 3
    in_c = input.shape[1]
    g = groups or 1
    w = helper.create_parameter(param_attr,
                                [num_filters, in_c // g] + list(k),
                                input.dtype,
                                default_initializer=init.MSRAInitializer())
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    inputs = {"Input": [input.name], "Filter": [w.name]}
    if bias_attr is not False:
        b = helper.create_parameter(helper.bias_attr, [num_filters],
                                    input.dtype, is_bias=True)
        inputs["Bias"] = [b.name]
    helper.append_op("conv3d", inputs=inputs,
                     outputs={"Output": [out.name]},
                     attrs={"strides": list(_triple3(stride)),
                            "paddings": list(_triple3(padding)),
                            "dilations": list(_triple3(dilation)),
                            "groups": g})
    return helper.append_activation(out)


def _triple3(v):
    return tuple(v) if isinstance(v, (list, tuple)) else (v,) * 3


def conv3d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, param_attr=None,
                     bias_attr=None, act=None, name=None):
    """reference nn.py conv3d_transpose."""
    helper = LayerHelper("conv3d_transpose", **locals())
    stride3 = _triple3(stride)
    pad3 = _triple3(padding)
    dil3 = _triple3(dilation)
    if filter_size is None:
        # reference conv2d_transpose:2377 derives the kernel from the
        # requested output: k = (out - (in-1)*s + 2p - 1)/d + 1
        if output_size is None:
            raise ValueError("filter_size or output_size must be set")
        osz = [output_size] * 3 if isinstance(output_size, int) \
            else list(output_size)
        k = [(osz[i] - (input.shape[2 + i] - 1) * stride3[i]
              + 2 * pad3[i] - 1) // dil3[i] + 1 for i in range(3)]
    else:
        k = filter_size if isinstance(filter_size, (list, tuple)) \
            else [filter_size] * 3
    in_c = input.shape[1]
    w = helper.create_parameter(param_attr, [in_c, num_filters] + list(k),
                                input.dtype,
                                default_initializer=init.XavierInitializer())
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    inputs = {"Input": [input.name], "Filter": [w.name]}
    if bias_attr is not False:
        b = helper.create_parameter(helper.bias_attr, [num_filters],
                                    input.dtype, is_bias=True)
        inputs["Bias"] = [b.name]
    helper.append_op("conv3d_transpose", inputs=inputs,
                     outputs={"Output": [out.name]},
                     attrs={"strides": list(_triple3(stride)),
                            "paddings": list(_triple3(padding)),
                            "dilations": list(_triple3(dilation))})
    return helper.append_activation(out)


def pool3d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, name=None):
    helper = LayerHelper("pool3d", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("pool3d", inputs={"X": [input.name]},
                     outputs={"Out": [out.name]},
                     attrs={"pooling_type": pool_type,
                            "ksize": list(_triple3(pool_size)),
                            "strides": list(_triple3(pool_stride)),
                            "paddings": list(_triple3(pool_padding)),
                            "global_pooling": global_pooling})
    return out


def image_resize(input, out_shape=None, scale=None, name=None,
                 resample="BILINEAR"):
    """reference nn.py image_resize (BILINEAR/NEAREST)."""
    helper = LayerHelper("image_resize", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    attrs = {"interp_method": resample.lower()}
    if out_shape is not None:
        attrs["out_h"], attrs["out_w"] = int(out_shape[0]), int(out_shape[1])
    if scale is not None:
        attrs["scale"] = float(scale)
    helper.append_op("bilinear_interp", inputs={"X": [input.name]},
                     outputs={"Out": [out.name]}, attrs=attrs)
    return out


def resize_bilinear(input, out_shape=None, scale=None, name=None):
    return image_resize(input, out_shape, scale, name, resample="BILINEAR")


def image_resize_short(input, out_short_len, resample="BILINEAR"):
    """Resize so the SHORT side equals out_short_len (reference
    nn.py image_resize_short), preserving aspect ratio."""
    h, w = input.shape[2], input.shape[3]
    short, is_h = (h, True) if h < w else (w, False)
    ratio = out_short_len / float(short)
    out_shape = ([out_short_len, int(round(w * ratio))] if is_h
                 else [int(round(h * ratio)), out_short_len])
    return image_resize(input, out_shape=out_shape, resample=resample)


def crop(x, shape=None, offsets=None, name=None):
    helper = LayerHelper("crop", name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    inputs = {"X": [x.name]}
    attrs = {}
    if isinstance(shape, ir.Variable):
        inputs["Y"] = [shape.name]
    else:
        attrs["shape"] = list(shape)
    if offsets is not None:
        attrs["offsets"] = list(offsets)
    helper.append_op("crop", inputs=inputs, outputs={"Out": [out.name]},
                     attrs=attrs)
    return out


def random_crop(x, shape, seed=None):
    helper = LayerHelper("random_crop")
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    helper.append_op("random_crop", inputs={"X": [x.name]},
                     outputs={"Out": [out.name]},
                     attrs={"shape": list(shape)})
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    helper = LayerHelper("label_smooth", name=name)
    out = helper.create_variable_for_type_inference(dtype=dtype)
    inputs = {"X": [label.name]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist.name]
    helper.append_op("label_smooth", inputs=inputs,
                     outputs={"Out": [out.name]},
                     attrs={"epsilon": float(epsilon)})
    return out


def multiplex(inputs, index):
    helper = LayerHelper("multiplex")
    out = helper.create_variable_for_type_inference(dtype=inputs[0].dtype)
    helper.append_op("multiplex",
                     inputs={"X": [v.name for v in inputs],
                             "Ids": [index.name]},
                     outputs={"Out": [out.name]})
    return out


def rank_loss(label, left, right, name=None):
    helper = LayerHelper("rank_loss", name=name)
    out = helper.create_variable_for_type_inference(dtype=left.dtype)
    helper.append_op("rank_loss",
                     inputs={"Label": [label.name], "Left": [left.name],
                             "Right": [right.name]},
                     outputs={"Out": [out.name]})
    return out


def dice_loss(input, label, epsilon=1e-5):
    """reference nn.py dice_loss — composed from elementwise layers the
    same way the reference composes it (math_op_patch overloads)."""
    from . import ops as _ops
    from .tensor import cast
    label_f = cast(label, input.dtype)
    # per-sample dice averaged over the batch (reference nn.py:4843-4851
    # reduces over dims 1.. then reduce_mean) — a global pool would let
    # large masks dominate small ones
    dims = list(range(1, len(input.shape)))
    inter = reduce_sum(_ops.elementwise_mul(input, label_f), dim=dims)
    union = reduce_sum(input, dim=dims) + reduce_sum(label_f, dim=dims)
    dice = scale(inter, scale=2.0) / (union + epsilon)
    return reduce_mean(scale(dice, scale=-1.0, bias=1.0))


def mean_iou(input, label, num_classes):
    helper = LayerHelper("mean_iou")
    miou = helper.create_variable_for_type_inference(dtype="float32")
    wrong = helper.create_variable_for_type_inference(dtype="int32")
    correct = helper.create_variable_for_type_inference(dtype="int32")
    helper.append_op("mean_iou",
                     inputs={"Predictions": [input.name],
                             "Labels": [label.name]},
                     outputs={"OutMeanIou": [miou.name],
                              "OutWrong": [wrong.name],
                              "OutCorrect": [correct.name]},
                     attrs={"num_classes": int(num_classes)})
    return miou, wrong, correct


def roi_pool(input, rois, pooled_height=1, pooled_width=1, spatial_scale=1.0):
    helper = LayerHelper("roi_pool")
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op("roi_pool",
                     inputs={"X": [input.name], "ROIs": [rois.name]},
                     outputs={"Out": [out.name]},
                     attrs={"pooled_height": int(pooled_height),
                            "pooled_width": int(pooled_width),
                            "spatial_scale": float(spatial_scale)})
    return out


def ctc_greedy_decoder(input, blank, name=None):
    """reference nn.py ctc_greedy_decoder. Returns padded ids [B, T]; the
    decoded lengths ride the @SEQLEN companion (reference emits LoD)."""
    helper = LayerHelper("ctc_greedy_decoder", name=name)
    out = helper.create_variable_for_type_inference(dtype="int32")
    lens = helper.create_variable_for_type_inference(dtype="int32")
    inputs = _seq_inputs(helper, input)
    helper.append_op("ctc_greedy_decoder", inputs=inputs,
                     outputs={"Out": [out.name], "OutLen": [lens.name]},
                     attrs={"blank": int(blank)})
    out.lod_level = 1
    blk = helper.main_program.current_block()
    comp = blk.create_var(name=seqlen_var_name(out.name), shape=[-1],
                          dtype="int32")
    helper.append_op("assign", inputs={"X": [lens.name]},
                     outputs={"Out": [comp.name]})
    return out, lens


def lod_reset(x, y=None, target_lod=None):
    helper = LayerHelper("lod_reset")
    out = helper.create_variable_for_type_inference(dtype=x.dtype)
    inputs = {"X": [x.name]}
    attrs = {}
    if y is not None:
        inputs["Y"] = [y.name]
    elif target_lod is not None:
        attrs["target_lod"] = list(target_lod)
    else:
        raise ValueError("lod_reset needs y or target_lod")
    helper.append_op("lod_reset", inputs=inputs,
                     outputs={"Out": [out.name]}, attrs=attrs)
    out.lod_level = max(1, x.lod_level)
    return out


def chunk_eval(input, label, chunk_scheme, num_chunk_types,
               excluded_chunk_types=None):
    """reference nn.py chunk_eval -> (precision, recall, f1, #infer,
    #label, #correct)."""
    helper = LayerHelper("chunk_eval")
    names = ["Precision", "Recall", "F1-Score", "NumInferChunks",
             "NumLabelChunks", "NumCorrectChunks"]
    dtypes = ["float32", "float32", "float32", "int32", "int32", "int32"]
    outs = {s: [helper.create_variable_for_type_inference(dtype=d).name]
            for s, d in zip(names, dtypes)}
    inputs = _seq_inputs(helper, input, {"Label": [label.name]})
    helper.append_op("chunk_eval", inputs=inputs, outputs=outs,
                     attrs={"num_chunk_types": int(num_chunk_types),
                            "chunk_scheme": chunk_scheme,
                            "excluded_chunk_types":
                                list(excluded_chunk_types or [])})
    blk = helper.main_program.current_block()
    return tuple(blk.var(outs[s][0]) for s in names)


def lstm_unit(x_t, hidden_t_prev, cell_t_prev, forget_bias=0.0,
              param_attr=None, bias_attr=None, name=None):
    """Single LSTM step (reference nn.py lstm_unit:2819): fc on
    [x_t, h_prev] then the lstm_unit op."""
    helper = LayerHelper("lstm_unit", **locals())
    size = cell_t_prev.shape[-1]
    from .tensor import concat
    cat = concat([x_t, hidden_t_prev], axis=1)
    fc_out = fc(input=cat, size=4 * size, param_attr=param_attr,
                bias_attr=bias_attr if bias_attr is not None else None)
    h = helper.create_variable_for_type_inference(dtype=x_t.dtype)
    c = helper.create_variable_for_type_inference(dtype=x_t.dtype)
    helper.append_op("lstm_unit",
                     inputs={"X": [fc_out.name], "C_prev": [cell_t_prev.name]},
                     outputs={"H": [h.name], "C": [c.name]},
                     attrs={"forget_bias": float(forget_bias)})
    return h, c


def dynamic_lstmp(input, size, proj_size, param_attr=None, bias_attr=None,
                  use_peepholes=True, is_reverse=False,
                  gate_activation="sigmoid", cell_activation="tanh",
                  candidate_activation="tanh", proj_activation="tanh",
                  dtype="float32", name=None):
    """LSTM with recurrent projection (reference nn.py dynamic_lstmp,
    including its use_peepholes=True default).
    `input`: [B, T, 4*hidden] x-projections, as for dynamic_lstm."""
    helper = LayerHelper("lstmp", **locals())
    hidden_size = size // 4
    bias_cols = 7 * hidden_size if use_peepholes else 4 * hidden_size
    weight = helper.create_parameter(param_attr,
                                     [proj_size, 4 * hidden_size], dtype)
    proj_weight = helper.create_parameter(param_attr,
                                          [hidden_size, proj_size], dtype)
    bias = helper.create_parameter(helper.bias_attr, [1, bias_cols],
                                   dtype, is_bias=True) \
        if bias_attr is not False else None
    proj = helper.create_variable_for_type_inference(dtype)
    cell = helper.create_variable_for_type_inference(dtype)
    inputs = {"Input": [input.name], "Weight": [weight.name],
              "ProjWeight": [proj_weight.name]}
    if bias is not None:
        inputs["Bias"] = [bias.name]
    seq = helper.ensure_seqlen_var(input)
    if seq is not None:
        inputs["SeqLen"] = [seq.name]
    helper.append_op("lstmp", inputs=inputs,
                     outputs={"Projection": [proj.name], "Cell": [cell.name]},
                     attrs={"use_peepholes": use_peepholes,
                            "is_reverse": is_reverse,
                            "gate_activation": gate_activation,
                            "cell_activation": cell_activation,
                            "candidate_activation": candidate_activation,
                            "proj_activation": proj_activation})
    proj.lod_level = cell.lod_level = input.lod_level
    return proj, cell


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    """Persistable int64 counter bumped once per executor run (reference
    nn.py autoincreased_step_counter, used by learning-rate schedulers)."""
    helper = LayerHelper("global_step_counter")
    name = counter_name or "@STEP_COUNTER@"
    blk = helper.main_program.global_block()
    if name in blk.vars:
        # idempotent (reference guards with is_new_var): a second caller
        # shares the counter instead of double-stepping it
        return blk.vars[name]
    counter = helper.create_global_variable(
        name=name, shape=[1], dtype="int64", persistable=True)
    helper.set_variable_initializer(counter,
                                    init.ConstantInitializer(begin - step))
    helper.append_op("increment", inputs={"X": [counter.name]},
                     outputs={"Out": [counter.name]},
                     attrs={"step": float(step)})
    counter.stop_gradient = True
    return counter


def beam_search(pre_ids, pre_scores, probs, beam_size, end_id, name=None,
                finished=None):
    """Static-shape beam expansion (reference nn.py beam_search:2657; the
    reference works on LoD beams, this build on dense [B, beam] state —
    same selection semantics, TPU-static shapes). `probs` are log-probs
    [B, beam, V]; returns (selected_ids, parents, new_scores, new_finished).
    See models/machine_translation.py for the full decode loop."""
    helper = LayerHelper("beam_search", name=name)
    if finished is None:
        raise ValueError("pass the running `finished` [B, beam] bool var")
    outs = {k: [helper.create_variable_for_type_inference(dtype=d).name]
            for k, d in (("Ids", "int32"), ("Parents", "int32"),
                         ("AccScoresOut", probs.dtype),
                         ("FinishedOut", "bool"))}
    helper.append_op("beam_search_step",
                     inputs={"LogProbs": [probs.name],
                             "AccScores": [pre_scores.name],
                             "Finished": [finished.name]},
                     outputs=outs,
                     attrs={"beam_size": int(beam_size),
                            "end_id": int(end_id)})
    blk = helper.main_program.current_block()
    return tuple(blk.var(outs[k][0])
                 for k in ("Ids", "Parents", "AccScoresOut", "FinishedOut"))


def beam_search_decode(ids_hist, parents_hist, final_scores, beam_size=None,
                       end_id=None, name=None):
    """Backtrack stacked beam selections into ranked sequences (reference
    nn.py beam_search_decode / beam_search_decode_op.cc). ids_hist /
    parents_hist: [B, T, beam]; returns (sentence_ids [B, beam, T],
    sentence_scores [B, beam]) best-first."""
    helper = LayerHelper("beam_search_decode", name=name)
    ids = helper.create_variable_for_type_inference(dtype="int32")
    scores = helper.create_variable_for_type_inference(
        dtype=final_scores.dtype)
    helper.append_op("beam_backtrack",
                     inputs={"Ids": [ids_hist.name],
                             "Parents": [parents_hist.name],
                             "AccScores": [final_scores.name]},
                     outputs={"SentenceIds": [ids.name],
                              "SentenceScores": [scores.name]})
    blk = helper.main_program.current_block()
    return ids, scores


def sequence_slice(input, offset, length, name=None):
    """Per-sequence sub-slices (reference sequence_slice_op.cc): row b of
    the output is input[b, offset_b : offset_b + length_b], left-aligned
    in the padded layout; the slice lengths ride the @SEQLEN companion.
    Runtime lengths clamp to the padded bound (an XLA program cannot
    raise on traced values; the reference host-asserts instead)."""
    helper = LayerHelper("sequence_slice", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    lens = helper.create_variable_for_type_inference(dtype="int32")
    helper.append_op("sequence_slice",
                     inputs={"X": [input.name], "Offset": [offset.name],
                             "Length": [length.name]},
                     outputs={"Out": [out.name], "OutLen": [lens.name]},
                     attrs={"nested": input.lod_level >= 2})
    out.lod_level = max(input.lod_level, 1)
    blk = helper.main_program.current_block()
    inner = out.lod_level - 1
    comp = blk.create_var(name=seqlen_var_name(out.name, inner),
                          shape=[-1] * (inner + 1), dtype="int32")
    helper.append_op("assign", inputs={"X": [lens.name]},
                     outputs={"Out": [comp.name]})
    for level in range(inner):      # outer doc counts ride through
        src = helper.ensure_seqlen_var(input, level=level)
        if src is not None:
            dst = helper.ensure_seqlen_var(out, level=level)
            helper.append_op("assign", inputs={"X": [src.name]},
                             outputs={"Out": [dst.name]})
    return out


def sequence_erase(input, tokens, name=None):
    """Remove `tokens` from each sequence and compact left (reference
    sequence_erase_op.cc; used by edit_distance preprocessing). The
    shrunken lengths ride the @SEQLEN companion."""
    helper = LayerHelper("sequence_erase", name=name)
    out = helper.create_variable_for_type_inference(dtype=input.dtype)
    lens = helper.create_variable_for_type_inference(dtype="int32")
    inputs = _seq_inputs(helper, input)
    helper.append_op("sequence_erase", inputs=inputs,
                     outputs={"Out": [out.name], "OutLen": [lens.name]},
                     attrs={"tokens": [int(t) for t in tokens]})
    out.lod_level = max(input.lod_level, 1)
    blk = helper.main_program.current_block()
    inner = out.lod_level - 1
    comp = blk.create_var(name=seqlen_var_name(out.name, inner),
                          shape=[-1] * (inner + 1), dtype="int32")
    helper.append_op("assign", inputs={"X": [lens.name]},
                     outputs={"Out": [comp.name]})
    for level in range(inner):      # outer doc counts ride through
        src = helper.ensure_seqlen_var(input, level=level)
        if src is not None:
            dst = helper.ensure_seqlen_var(out, level=level)
            helper.append_op("assign", inputs={"X": [src.name]},
                             outputs={"Out": [dst.name]})
    return out


# ---------------------------------------------------------------------------
# decoder-LM blocks (no reference analog): RMSNorm, rotary embedding, the
# silu-gated product, flash attention as a layer, and sparse experts
# ---------------------------------------------------------------------------

def rms_norm(input, epsilon=1e-5, param_attr=None, zero_centered=False,
             name=None):
    """`x * rsqrt(mean(x^2) + epsilon) * w` over the last axis, with a
    learned weight of that width (initialised to 1); statistics in float32.
    `zero_centered`: `* (1 + w)` with w initialised to 0."""
    helper = LayerHelper("rms_norm", **locals())
    scale = helper.create_parameter(
        param_attr, [input.shape[-1]], "float32",
        default_initializer=init.ConstantInitializer(
            0.0 if zero_centered else 1.0))
    out = helper.create_variable_for_type_inference(input.dtype)
    attrs = {"epsilon": epsilon}
    if zero_centered:
        attrs["zero_centered"] = True
    helper.append_op("rms_norm",
                     inputs={"X": [input.name], "Scale": [scale.name]},
                     outputs={"Y": [out.name]}, attrs=attrs)
    return out


def gated_rms_norm(input, gate, epsilon=1e-6, param_attr=None, name=None,
                   gate_first=False, group_size=None, activation="silu"):
    """`x * rsqrt(mean(x^2) + epsilon) * w * silu(gate)` over the last axis,
    `gate` of `input`'s shape, a learned weight of that width (initialised
    to 1): the output norm of a gated-delta-rule layer, over a head.
    `activation="sigmoid"`: `* sigmoid(gate)` in silu's place (a Kimi Delta
    Attention layer's output gate; the norm first only), same shapes, dtypes
    and precisions: `[..., tokens, heads, dim]`, the sigmoid float32.

    `gate_first` (a Mamba-2 layer's output norm, `norm_before_gate` false):
    `u = x * silu(gate)` first, then `u * rsqrt(mean(u^2) + epsilon) * w`,
    the mean over each run of `group_size` elements of the last axis
    (default: all of it) and w as wide as that whole axis, every lane its
    own. Either way `input` and `gate` `[..., tokens, width]` in any float
    dtype (bf16 under AMP), the statistics, the gate's silu and the products
    float32, the normed value rounded once to `input`'s dtype before the
    last product, the result in `input`'s dtype and shape, w float32."""
    helper = LayerHelper("gated_rms_norm", **locals())
    scale = helper.create_parameter(
        param_attr, [input.shape[-1]], "float32",
        default_initializer=init.ConstantInitializer(1.0))
    attrs = {"epsilon": epsilon}
    if activation != "silu":
        if activation != "sigmoid" or gate_first:
            raise ValueError(f"the gate's activation is silu, or sigmoid "
                             f"with the norm first, got {activation!r} with "
                             f"gate_first={gate_first}")
        attrs["activation"] = activation
    x, z = input, gate
    if gate_first:
        attrs["gate_first"] = True
        width = input.shape[-1]
        group = int(group_size or width)
        if width % group:
            raise ValueError(f"groups of {group} do not divide {width}")
        x, z = (reshape(t, shape=[0] * (len(input.shape) - 1)
                        + [width // group, group]) for t in (input, gate))
    elif group_size is not None:
        raise ValueError("group_size goes with gate_first")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("gated_rms_norm",
                     inputs={"X": [x.name], "Gate": [z.name],
                             "Scale": [scale.name]},
                     outputs={"Y": [out.name]}, attrs=attrs)
    if gate_first:
        out = reshape(out, shape=[0] * (len(input.shape) - 1) + [width])
    return out


def rotary_embedding(input, theta=10000.0, rotary_dim=None,
                     interleaved=False, scaling=None, name=None):
    """Rotary position embedding (rotate-half convention) on
    `[batch, heads, seq, head_dim]`, positions 0..seq-1; with `rotary_dim`
    on the first `rotary_dim` dims of a head only, the others pass through.
    `interleaved`: the pairs are `(x[2i], x[2i + 1])`; the rotated dims come
    out laid `[evens | odds]` (DeepSeek-V3's `rope_interleave`). `scaling`:
    a YaRN block (`factor`, `original_max_position_embeddings`, and where
    they differ from 32, 1 and `0.1 ln(factor) + 1`: `beta_fast`,
    `beta_slow`, `attention_factor`) gives other frequencies than
    `theta^(-2i/R)` and tables times `attention_factor`
    (`ops/decoder_block.py::rotary_frequencies`), at every length; float32
    tables either way."""
    from ..ops.decoder_block import rotary_frequencies
    helper = LayerHelper("rotary_embedding", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    attrs = {"theta": float(theta)}
    if rotary_dim is not None:
        attrs["rotary_dim"] = int(rotary_dim)
    if interleaved:
        attrs["interleaved"] = True
    if scaling is not None:
        rotary_frequencies(int(rotary_dim or input.shape[-1]), float(theta),
                           scaling)                # refuses at build time
        attrs["scaling"] = {k: float(v) for k, v in dict(scaling).items()}
    helper.append_op("rotary_embedding", inputs={"X": [input.name]},
                     outputs={"Out": [out.name]}, attrs=attrs)
    return out


def causal_conv1d(input, kernel_size, param_attr=None, name=None,
                  bias_attr=None, activation="silu"):
    """Depthwise causal convolution over time on `[batch, seq, channels]`,
    then `activation`: output t reads inputs t - kernel_size + 1 .. t of its
    own channel. `activation` is "silu" (what every scan's and delta rule's
    convolution takes) or None: the taps' sum as it is (LFM2's short
    convolution, whose gates stand outside it). The weight is `[channels,
    kernel_size]`, float32; `bias_attr` gives the op a bias `[channels]`
    (float32, starts at 0) that is added before the activation, none without
    it. `input` in any float dtype (bf16 under AMP), the taps' sum, the bias
    and the silu float32, the result in `input`'s dtype and shape."""
    if activation not in ("silu", None):
        raise ValueError(f"activation is \"silu\" or None, got "
                         f"{activation!r}")
    helper = LayerHelper("causal_conv1d", **locals())
    w = helper.create_parameter(param_attr, [input.shape[-1], kernel_size],
                                "float32")
    inputs = {"X": [input.name], "W": [w.name]}
    if bias_attr is not None and bias_attr is not False:
        bias = helper.create_parameter(
            bias_attr, [input.shape[-1]], "float32",
            default_initializer=init.ConstantInitializer(0.0))
        inputs["Bias"] = [bias.name]
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("causal_conv1d", inputs=inputs,
                     outputs={"Out": [out.name]},
                     attrs={"activation": activation or ""})
    return out


def gated_delta_rule(q, k, v, a, b, a_log_attr=None, dt_bias_attr=None,
                     chunk=64, name=None, beta_scale=1.0):
    """Linear attention by the gated delta rule (`ops/linear_attention.py`)
    on q, k `[batch, seq, key_heads, key_dim]` and v `[batch, seq,
    value_heads, value_dim]`; `a`, `b` `[batch, seq, value_heads]` make a
    head's log-decay `g = -exp(A_log) * softplus(a + dt_bias)` and write
    strength `beta_scale * sigmoid(b)` in float32, with the learned `A_log`
    and `dt_bias` `[value_heads]` (`a_log_attr`, `dt_bias_attr`). `beta_scale`
    2 lets beta reach 2 (`allow_neg_eigval`: with beta > 1 a token's
    transition `exp(g) (I - beta k k^T)` has the negative eigenvalue `exp(g)
    (1 - beta)`); the gates op carries it as an attribute, none at 1. q and
    k are l2-normalised over a head inside the op; seq must be a multiple of
    `chunk`. Returns `[batch, seq, value_heads, value_dim]`.

    The op has a second output, `States`: float32 `[seq / chunk, batch,
    value_heads, key_dim, value_dim]`, the state each chunk started from, as
    the forward kernel saves it. `gated_delta_rule_grad` reads it back and
    runs the backward kernel alone. Where the forward op wrote none (head
    dims that do not fill a vreg, a CPU backend: the XLA form) the grad op
    traces the rule again under `jax.vjp`."""
    helper = LayerHelper("gated_delta_rule", name=name)
    heads = v.shape[2]
    a_log = helper.create_parameter(a_log_attr, [heads], "float32")
    dt_bias = helper.create_parameter(
        dt_bias_attr, [heads], "float32",
        default_initializer=init.ConstantInitializer(1.0))
    new = helper.create_variable_for_type_inference
    g, beta = new("float32"), new("float32")
    helper.append_op("delta_rule_gates",
                     inputs={"A": [a.name], "B": [b.name],
                             "ALog": [a_log.name], "DtBias": [dt_bias.name]},
                     outputs={"G": [g.name], "Beta": [beta.name]},
                     attrs=None if beta_scale == 1
                     else {"beta_scale": float(beta_scale)})
    out = new(v.dtype)
    states = new("float32", stop_gradient=True)
    helper.append_op("gated_delta_rule",
                     inputs={"Q": [q.name], "K": [k.name], "V": [v.name],
                             "G": [g.name], "Beta": [beta.name]},
                     outputs={"Out": [out.name], "States": [states.name]},
                     attrs={"chunk": int(chunk)})
    return out


def kda_delta_rule(q, k, v, f, b, a_log_attr=None, dt_bias_attr=None,
                   lower_bound=-5.0, chunk=64, name=None):
    """Kimi Delta Attention's rule (`ops/linear_attention.py`): the delta
    rule under a decay per KEY CHANNEL, on q, k `[batch, seq, heads,
    key_dim]` and v `[batch, seq, heads, value_dim]` (as many value heads as
    key heads). `f` `[batch, seq, heads * key_dim]` and `b` `[batch, seq,
    heads]` make a channel's log-decay `g = lower_bound * sigmoid(exp(A_log_h)
    * (f + dt_bias))` in (`lower_bound`, 0) and the write strength
    `sigmoid(b)`, float32 (`kda_gates`, AMP_F32_OPS), with the learned
    `A_log` `[heads]` and `dt_bias` `[heads * key_dim]` (`a_log_attr`,
    `dt_bias_attr`; float32). Per head a float32 state `[key_dim, value_dim]`
    from 0: `S <- Diag(exp(g_t)) S;  d = beta_t (v_t - S^T k_t);  S <- S +
    k_t d^T;  o_t = S^T q_t`, computed in chunks of `chunk` tokens (seq a
    multiple of it) whose tiles are made in blocks of 16 rows, each relative
    to a row of its own, so that no exponent above `-8 lower_bound` = 40 is
    formed (`lower_bound` is what makes that a bound). q and k are
    l2-normalised over a head inside the op, q then scaled by
    `key_dim^-0.5`. q, k, v in any float dtype (bf16 under AMP); g, beta,
    their running sums, every `exp`, the l2-norms, the solve and the state
    float32. Returns `[batch, seq, heads, value_dim]` in v's dtype. The grad
    op is registered (`kda_delta_rule_grad`) and returns g's gradient per
    channel.

    The op has a second output, `States`: float32 `[seq / chunk, batch,
    heads, key_dim, value_dim]`, the state each chunk started from, as the
    forward kernel `kda_fwd` saves it. `kda_delta_rule_grad` reads it back
    and runs `kda_bwd` alone. Where the forward op wrote none (head dims that
    do not fill a vreg, a CPU backend: the XLA form) the grad op traces the
    rule again under `jax.vjp`."""
    helper = LayerHelper("kda_delta_rule", name=name)
    heads, key_dim = q.shape[2], q.shape[3]
    a_log = helper.create_parameter(a_log_attr, [heads], "float32")
    dt_bias = helper.create_parameter(
        dt_bias_attr, [heads * key_dim], "float32",
        default_initializer=init.ConstantInitializer(0.0))
    new = helper.create_variable_for_type_inference
    g, beta = new("float32"), new("float32")
    helper.append_op("kda_gates",
                     inputs={"F": [f.name], "B": [b.name],
                             "ALog": [a_log.name], "DtBias": [dt_bias.name]},
                     outputs={"G": [g.name], "Beta": [beta.name]},
                     attrs={"lower_bound": float(lower_bound)})
    out = new(v.dtype)
    states = new("float32", stop_gradient=True)
    helper.append_op("kda_delta_rule",
                     inputs={"Q": [q.name], "K": [k.name], "V": [v.name],
                             "G": [g.name], "Beta": [beta.name]},
                     outputs={"Out": [out.name], "States": [states.name]},
                     attrs={"chunk": int(chunk)})
    return out


def ssd_scan(x, b, c, dt_raw, a_log_attr=None, dt_bias_attr=None, d_attr=None,
             chunk=128, name=None):
    """The selective scan of a Mamba-2 layer (`ops/state_space.py`) on x
    `[batch, seq, heads, head_dim]` and b, c `[batch, seq, groups, state]`
    (a group's b and c serve `heads / groups` heads); `dt_raw` `[batch, seq,
    heads]` makes a head's step size `dt = softplus(dt_raw + dt_bias)` and
    log-decay `a = -exp(A_log) * dt` in float32 (`ssd_gates`, AMP_F32_OPS),
    with the learned `A_log`, `dt_bias` and the skip `D` `[heads]`
    (`a_log_attr`, `dt_bias_attr`, `d_attr`; float32, `D` starts at 1). Per
    head a float32 state `[head_dim, state]` from 0: `S_t = exp(a_t) S_{t-1}
    + dt_t x_t b_t^T`, `y_t = S_t c_t + D x_t`, computed in chunks of
    `chunk` tokens; seq must be a multiple of it. x, b, c in any float
    dtype (bf16 under AMP: the products take bf16 operands into float32
    sums on the chip); dt, a, their running sums, the decays and the state
    float32. Returns `[batch, seq, heads, head_dim]` in x's dtype.

    The op has a second output, `States`: float32 `[seq / 128, batch,
    heads, head_dim, state]`, the state each of the kernels' steps of 128
    tokens started from (whatever whole number of them `chunk` is), as the
    forward kernel `ssd_fwd` saves it. `ssd_scan_grad` reads it back and
    runs `ssd_bwd` alone. Where the forward op wrote none (head dims that do
    not fill a vreg, a CPU backend: the XLA form) the grad op traces the
    scan again under `jax.vjp`."""
    helper = LayerHelper("ssd_scan", name=name)
    heads = x.shape[2]
    a_log = helper.create_parameter(a_log_attr, [heads], "float32")
    dt_bias = helper.create_parameter(
        dt_bias_attr, [heads], "float32",
        default_initializer=init.ConstantInitializer(0.0))
    skip = helper.create_parameter(
        d_attr, [heads], "float32",
        default_initializer=init.ConstantInitializer(1.0))
    new = helper.create_variable_for_type_inference
    dt, a = new("float32"), new("float32")
    helper.append_op("ssd_gates",
                     inputs={"DtRaw": [dt_raw.name],
                             "DtBias": [dt_bias.name], "ALog": [a_log.name]},
                     outputs={"Dt": [dt.name], "A": [a.name]})
    out = new(x.dtype)
    states = new("float32", stop_gradient=True)
    helper.append_op("ssd_scan",
                     inputs={"X": [x.name], "Dt": [dt.name], "A": [a.name],
                             "B": [b.name], "C": [c.name], "D": [skip.name]},
                     outputs={"Out": [out.name], "States": [states.name]},
                     attrs={"chunk": int(chunk)})
    return out


def selective_scan(x, dt_raw, b, c, state, a_log_attr=None, dt_bias_attr=None,
                   d_attr=None, chunk=128, name=None):
    """The selective scan of a Mamba-1 layer (`ops/selective_scan.py`) on x
    and `dt_raw` `[batch, seq, channels]` and b, c `[batch, seq, state]`:
    `dt = softplus(dt_raw + dt_bias)`, `A = -exp(A_log)` with the learned
    `A_log` `[channels, state]`, `dt_bias` and the skip `D` `[channels]`
    (`a_log_attr`, `dt_bias_attr`, `d_attr`; float32; by default `A_log` =
    log(1..state) a channel, `dt_bias` 0, `D` 1). Per channel c and state n a
    float32 state from 0: `S_t = exp(dt_t[c] A[c, n]) S_{t-1} + dt_t[c]
    b_t[n] x_t[c]`, `y_t[c] = sum_n S_t c_t[n] + D[c] x_t[c]`. The op is on
    AMP_F32_OPS: bf16 operands are widened before its rule and the result is
    float32 `[batch, seq, channels]`. `chunk`: the tokens whose states the
    plain form holds at once (the kernels' chunk is 128).

    The op has a second output, `States`: float32 `[seq / 128, batch, state,
    channels]`, the state each chunk of 128 tokens started from, as the
    forward kernel `sscan_fwd` saves it. `selective_scan_grad` reads it back
    and runs `sscan_bwd` alone. Where the forward op wrote none (channels off
    the lane tile, a CPU backend: the plain form) the grad op traces the scan
    again under `jax.vjp`."""
    helper = LayerHelper("selective_scan", name=name)
    channels = x.shape[-1]
    a_log = helper.create_parameter(
        a_log_attr, [channels, state], "float32",
        default_initializer=init.NumpyArrayInitializer(np.tile(
            np.log(np.arange(1, state + 1, dtype="float32")),
            (channels, 1))))
    dt_bias = helper.create_parameter(
        dt_bias_attr, [channels], "float32",
        default_initializer=init.ConstantInitializer(0.0))
    skip = helper.create_parameter(
        d_attr, [channels], "float32",
        default_initializer=init.ConstantInitializer(1.0))
    out = helper.create_variable_for_type_inference("float32")
    states = helper.create_variable_for_type_inference("float32",
                                                       stop_gradient=True)
    helper.append_op("selective_scan",
                     inputs={"X": [x.name], "DtRaw": [dt_raw.name],
                             "DtBias": [dt_bias.name], "ALog": [a_log.name],
                             "B": [b.name], "C": [c.name], "D": [skip.name]},
                     outputs={"Out": [out.name], "States": [states.name]},
                     attrs={"chunk": int(chunk)})
    return out


def relu2(x, name=None, group_sizes=None):
    """`relu(x)^2` (`mlp_hidden_act: relu2`), float32 inside, x's dtype out.
    With `group_sizes` (the `GroupSizes` of an expert layer's share, whose
    rows x is) over the rows those groups use only."""
    helper = LayerHelper("relu2", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    used = {} if group_sizes is None else {"GroupSizes": [group_sizes.name]}
    helper.append_op("relu2", inputs={"X": [x.name], **used},
                     outputs={"Out": [out.name]})
    return out


def swiglu(gate, up, name=None, group_sizes=None):
    """`silu(gate) * up`. With `group_sizes` (the `GroupSizes` of an expert
    layer's share, whose rows `gate` and `up` are) over the rows those
    groups use only."""
    helper = LayerHelper("swiglu", name=name)
    out = helper.create_variable_for_type_inference(gate.dtype)
    used = {} if group_sizes is None else {"GroupSizes": [group_sizes.name]}
    helper.append_op("swiglu",
                     inputs={"Gate": [gate.name], "Up": [up.name], **used},
                     outputs={"Out": [out.name]})
    return out


def exit_gate(input, param_attr=None, bias_attr=None, name=None):
    """A looped LM's exit gate on `[..., width]`: the logit of
    `Linear(width, 1)` with bias, `[..., 1]`, float32 whatever dtype flows in
    (its sigmoid is the probability of leaving after this pass)."""
    helper = LayerHelper("exit_gate", **locals())
    w = helper.create_parameter(param_attr, [input.shape[-1], 1], "float32")
    b = helper.create_parameter(bias_attr, [1], "float32", is_bias=True)
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op("exit_gate",
                     inputs={"X": [input.name], "W": [w.name],
                             "Bias": [b.name]},
                     outputs={"Out": [out.name]})
    return out


def fused_attention(q, k, v, causal=False, sm_scale=None, dropout_rate=0.0,
                    is_test=False, window=None, layout="BHTD", name=None,
                    kept=None, topk=None, heads_total=None):
    """Softmax attention through the flash kernels
    (`ops/pallas_attention.py`): O(seq) memory, dropout on the attention
    weights inside the kernel. `layout` says how the operands lie, and the
    result lies the same way: "BHTD", `[batch, heads, seq, head_dim]`, or
    "BTHD", `[batch, seq, heads, head_dim]`, which is what a projection's
    `[batch, seq, heads * head_dim]` output is under a free reshape. The
    kernels read "BTHD" operands as they are, a head being a range of lanes
    picked by their block specs, several heads a grid step; no transpose
    stands on either side of the op, forward or backward. It needs `v`'s
    head width to be `q`'s. Under "BHTD" `v` may have a head width of its
    own (latent attention: q, k at 192, v
    at 128); the result has `v`'s.

    `window=W` (with `causal=True` only): key j is visible to query i iff
    `0 <= i - j < W`, sliding-window attention. The kernels then cover the
    score tiles that meet that band and no others, where a causal call
    covers the triangle's (45 tiles of 512 x 512 a head at 8192 tokens and
    W = 1024, against 136), and fetch no block for a tile they skip. Any
    `W >= 1` runs, aligned to a tile or not; `W >= seq` is plain causal.
    Not under sequence parallelism (ring attention) and not in the paged
    kernels: both raise.

    `kept` (with `causal=True`, "BHTD", no window, no dropout): an int8
    `[batch, seq, seq]` variable, the keys each query keeps of those below
    the diagonal, one set for all heads (`layers.dsa_select`); the softmax is
    over the kept keys alone. It carries no gradient. The flash kernels read
    its tiles beside the score tiles, forward and backward, under names of
    their own (`dsa_flash_fwd`, `dsa_flash_dq_flash_dkv`); every causal tile
    is computed. `topk`: how many keys a row keeps at most, for the op's
    count of kept pairs on the compile event (`dsa_keys_kept`).

    `heads_total`: where the operands hold one chip's share of a layer's
    heads, how many the layer has (`models/olmo_hybrid.py`); the op carries
    it for the compile event's census and computes nothing from it.

    The op has a second output, `Lse`: the forward kernel's log-sum-exp of
    every score row, float32 `[batch * heads, 1, seq]` in either layout,
    the kernels' own. `fused_attention_grad` reads `Out` and `Lse` back and
    runs the backward kernel alone. Where the forward op wrote no `Lse`
    (under sequence parallelism, on the CPU reference path, in a program
    built without the slot) the grad op traces the forward again under
    `jax.vjp`."""
    from ..ops.pallas_attention import LAYOUTS, _check_window
    if layout not in LAYOUTS:
        raise ValueError(f"fused_attention: layout {layout!r} is none of "
                         f"{LAYOUTS}")
    helper = LayerHelper("fused_attention", name=name)
    out = helper.create_variable_for_type_inference(q.dtype)
    lse = helper.create_variable_for_type_inference("float32",
                                                    stop_gradient=True)
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    attrs = {"causal": causal, "sm_scale": sm_scale,
             "dropout_rate": dropout_rate, "is_test": is_test}
    if layout != "BHTD":        # a head-major op is the op it was
        attrs["layout"] = layout
    if window is not None:
        _check_window(window, causal)       # refuses at build time
        attrs["window"] = int(window)
    inputs = {"Q": [q.name], "K": [k.name], "V": [v.name]}
    if kept is not None:
        if not causal or window is not None or layout != "BHTD" \
                or dropout_rate:
            raise ValueError(
                "fused_attention takes a kept set on a causal \"BHTD\" call "
                "without a window or dropout")
        inputs["Kept"] = [kept.name]
        if topk is not None:
            attrs["topk"] = int(topk)
    if heads_total is not None:
        attrs["heads_total"] = int(heads_total)
    helper.append_op("fused_attention", inputs=inputs,
                     outputs={"Out": [out.name], "Lse": [lse.name]},
                     attrs=attrs)
    return out


def dsa_index_scores(q, k, w, scale, tile=512, name=None):
    """The index scores of a learned key selection (DeepSeek-Sparse-Attention;
    `ops/sparse_attention.py`): `q` `[batch, index_heads, seq, index_dim]`,
    `k` `[batch, 1, seq, index_dim]`, `w` `[batch, seq, index_heads]` give
    `scale * sum_j w[t, j] * relu(q[j, t] . k[s])` for `s <= t`, float32
    `[batch, seq, seq]`, minus infinity above the diagonal, computed in tiles
    of `tile` x `tile`. No gradient passes it."""
    helper = LayerHelper("dsa_index_scores", name=name)
    out = helper.create_variable_for_type_inference("float32",
                                                    stop_gradient=True)
    helper.append_op("dsa_index_scores",
                     inputs={"Q": [q.name], "K": [k.name], "W": [w.name]},
                     outputs={"Scores": [out.name]},
                     attrs={"scale": float(scale), "tile": int(tile)})
    return out


def dsa_select(scores, topk, name=None):
    """The kept set of index scores `[batch, seq, seq]`: int8, row t holds 1
    at its `min(t + 1, topk)` keys of largest score below the diagonal (of
    equal scores the lower index) and 0 elsewhere; what
    `fused_attention(kept=...)` reads. No gradient passes it."""
    helper = LayerHelper("dsa_select", name=name)
    out = helper.create_variable_for_type_inference("int8",
                                                    stop_gradient=True)
    helper.append_op("dsa_select", inputs={"Scores": [scores.name]},
                     outputs={"Kept": [out.name]},
                     attrs={"topk": int(topk)})
    return out


def moe_router(input, num_experts, k, param_attr=None, norm_topk_prob=False,
               name=None, score_func="softmax", bias_attr=None,
               bias_update_rate=None, norm_eps=None, scaling_factor=None,
               n_group=None, topk_group=None):
    """Top-k router over `input` [tokens, width]: float32 logits and scores
    over all `num_experts` (`score_func`: "softmax" over the experts, or each
    expert's "sigmoid"), the k largest scores used as they are, or with
    `norm_topk_prob` divided by their sum (over all k, whichever chip holds
    the chosen experts; plus `norm_eps` where given), then times
    `scaling_factor` where given. Returns a dict: `weight` and `index`
    [tokens, k], `tokens_per_expert` [num_experts] (int32 counts of the
    assignments), `probs` [tokens, num_experts] (the scores) and `logsumexp`
    [tokens] (what the load-balancing loss and the z-loss are built from).

    `bias_attr` gives the router a selection bias `b` [num_experts] (the
    `e_score_correction_bias` of DeepSeek-V3's `noaux_tc` routing; dict key
    `bias`): the experts are chosen by `score + b`, the weights are the
    scores without it. `b` is a float32 persistable variable that starts at 0
    and is not trained: no gradient, no optimizer state, float32 under AMP,
    saved and loaded with the weights. With `bias_update_rate` gamma the step
    itself rewrites it from this step's counts c, after the choice and
    outside the gradient: `b <- b + gamma * sign(mean(c) - c)`, an expert with
    more than the mean load becomes less likely to be chosen. The router
    reads a copy taken before the update, so the backward pass, which reads
    the scope's values, differentiates the choice the forward pass made.
    The step's counts stay behind in `<bias name>.load` (int32, persistable,
    dict key `load`): fetch it, or read it from the scope after the step.

    `n_group`, `topk_group` (DeepSeek-V3's group-limited choice): the experts
    are `n_group` groups of `num_experts / n_group` consecutive ones; a
    group's score is the sum of its two largest `score + b` (float32, [tokens,
    n_group]), the `topk_group` best groups stay, and the k experts are the
    largest `score + b` among THEIR experts alone; weights, counts and the
    bias's rewrite as above. `n_group` 1 or None: one group, the program and
    the lowering it had."""
    from ..param_attr import ParamAttr
    from . import ops as _ops
    from . import tensor as _tensor
    helper = LayerHelper("moe_router", **locals())
    w = helper.create_parameter(param_attr, [input.shape[-1], num_experts],
                                "float32")
    new = helper.create_variable_for_type_inference
    outs = {"TopKWeight": new("float32"),
            "TopKIndex": new("int32", stop_gradient=True),
            "TokensPerExpert": new("int32", stop_gradient=True),
            "Probs": new("float32"), "LogSumExp": new("float32")}
    attrs = {"k": int(k)}
    if norm_topk_prob:
        attrs["norm_topk_prob"] = True
    if score_func != "softmax":
        attrs["score_func"] = str(score_func)
    if norm_eps is not None:
        attrs["norm_eps"] = float(norm_eps)
    if scaling_factor is not None:
        attrs["scaling_factor"] = float(scaling_factor)
    groups = int(n_group or 1)
    if groups > 1:
        kept, size = int(topk_group or groups), num_experts // groups
        if num_experts % groups or not 1 <= kept <= groups or size < 2 \
                or kept * size < int(k):
            raise ValueError(
                f"{num_experts} experts in {groups} groups of which "
                f"{kept} stay: the groups are equal, of two experts or "
                f"more, and those that stay hold at least k = {k}")
        attrs["n_group"], attrs["topk_group"] = groups, kept
    inputs = {"X": [input.name], "W": [w.name]}
    bias = None
    if bias_attr is not None:
        attr = ParamAttr._to_attr(bias_attr)
        attr.trainable = False
        bias = helper.create_parameter(
            attr, [num_experts], "float32", stop_gradient=True,
            default_initializer=init.ConstantInitializer(0.0))
        chosen_by = _tensor.assign(bias)
        chosen_by.stop_gradient = True
        inputs["Bias"] = [chosen_by.name]
    helper.append_op("moe_router", inputs=inputs,
                     outputs={s: [v.name] for s, v in outs.items()},
                     attrs=attrs)
    routing = {"weight": outs["TopKWeight"], "index": outs["TopKIndex"],
               "tokens_per_expert": outs["TokensPerExpert"],
               "probs": outs["Probs"], "logsumexp": outs["LogSumExp"]}
    if bias is not None:
        routing["bias"] = bias
        if bias_update_rate:
            counts = _tensor.cast(outs["TokensPerExpert"], "float32")
            over = _ops.sign(_ops.elementwise_sub(counts, reduce_mean(counts)))
            moved = _tensor.sums([chosen_by,
                                  scale(over, scale=-float(bias_update_rate))])
            _tensor.assign(moved, output=bias)
            load = helper.create_global_variable(
                name=bias.name + ".load", shape=[num_experts], dtype="int32",
                persistable=True)
            helper.set_variable_initializer(load,
                                            init.ConstantInitializer(0))
            load.stop_gradient = True
            routing["load"] = _tensor.assign(outs["TokensPerExpert"],
                                             output=load)
    return routing


def moe_experts(input, routing, num_experts, expert_size, param_attr=None,
                name=None, first_expert=None, experts_held=None, gated=True,
                activation="silu", down_attr=None):
    """Dropless gated-silu experts on `input` [tokens, width] under
    `routing` (what `moe_router` returned): every assignment is computed,
    `down_e(silu(gate_e(x)) * up_e(x))` summed over a token's experts with
    its router weights; the rows are laid out by expert in groups of whole
    row tiles (`ops/moe.py`), so the step's time does not follow the
    routing where every expert is held. The weights are stacked over experts,
    `<name>.gate.w` / `<name>.up.w` [experts, width, expert_size] and
    `<name>.down.w` [experts, expert_size, width]; `param_attr` gives their
    initializer, `down_attr` another one for `<name>.down.w` alone where the
    way back into the residual stream starts smaller.

    All `num_experts` experts are held unless `experts_held` is given: then
    this is one chip's share of an expert-parallel layer. The router chose
    among `num_experts`; the weights are `[experts_held, ...]`, those of
    experts `first_expert .. first_expert + experts_held - 1`; the result is
    the part those experts give, and assignments to the others add nothing
    here, forward or backward. Still dropless for the held experts: the rows
    of the layout are the worst case `tokens x k + experts_held x 128`,
    which every routing fits (`ops/moe.py::_dispatch_share`). What is done
    with them follows the routing: the grouped kernels visit the tiles the
    held groups use, and dispatch, combine and their grads move those rows
    only, a chunk at a time, so a share's time grows with the assignments
    that fall on it (at one chip's even share ~6% of the rows are used; with
    every assignment on a held expert the movements cost 1.3 times what
    static gathers over all the rows would). What stands between them
    follows the held rows too: the gate's and the up projection's products
    are ONE `grouped_matmul` op under a share (`W: [<name>.gate.w,
    <name>.up.w]` -> two `Out`s; the parameters are the same two), so the
    rows' gradient is one variable that the op's grad sums over the used
    rows (two ops would have `append_backward` insert a `sum` over all of
    them), and `swiglu` takes `GroupSizes` and visits the used rows only,
    as its grad does.

    `gated=False, activation="relu2"`: experts of two matrices,
    `down_e(relu(up_e(x))^2)`: no `<name>.gate.w`; one `grouped_matmul` for
    `up`, `relu2` over the rows (with `GroupSizes` under a share: the used
    rows only), one for `down`; layout, movements and sums as above. The
    stacks' shapes are as documented whatever the widths; which way the
    grouped kernels are handed one follows its widths
    (`ops/moe.py::_held_lane_major`). Either
    way `input` `[tokens, width]` in any float dtype (bf16 under AMP, where
    the grouped products take bf16 operands into float32 sums and the
    activation is float32 inside); the weights float32; the result in
    `input`'s dtype and shape."""
    from ..ops.moe import ROW_TILE
    from ..param_attr import ParamAttr
    helper = LayerHelper("moe_experts", **locals())
    prefix = name or helper.name
    base = ParamAttr._to_attr(param_attr)
    width = input.shape[-1]
    dtype = input.dtype
    new = helper.create_variable_for_type_inference

    def weight(which, shape, attr=base):
        return helper.create_parameter(
            ParamAttr(name=f"{prefix}.{which}.w",
                      initializer=attr.initializer), shape, "float32")

    if (gated, activation) not in ((True, "silu"), (False, "relu2")):
        raise ValueError(f"no expert layer with gated={gated} and "
                         f"activation {activation!r}: gated silu or ungated "
                         f"relu2")
    stacked = num_experts if experts_held is None else experts_held
    if gated:
        w_gate = weight("gate", [stacked, width, expert_size])
    w_up = weight("up", [stacked, width, expert_size])
    w_down = weight("down", [stacked, expert_size, width],
                    base if down_attr is None else ParamAttr._to_attr(down_attr))
    x_sorted = new(dtype)
    slot = new("int32", stop_gradient=True)
    source = new("int32", stop_gradient=True)
    sizes = new("int32", stop_gradient=True)
    share = {}
    if experts_held is not None:
        first = int(first_expert or 0)
        if not 0 <= first <= num_experts - experts_held:
            raise ValueError(f"experts {first} .. + {experts_held} are not "
                             f"among {num_experts}")
        share = {"first_expert": first, "experts_held": int(experts_held)}
    helper.append_op("moe_dispatch",
                     inputs={"X": [input.name],
                             "TopKIndex": [routing["index"].name],
                             "TokensPerExpert":
                                 [routing["tokens_per_expert"].name]},
                     outputs={"XSorted": [x_sorted.name],
                              "Slot": [slot.name], "Source": [source.name],
                              "GroupSizes": [sizes.name]},
                     attrs={"row_tile": ROW_TILE, **share})

    def grouped(x, *ws):
        outs = [new(dtype) for _ in ws]
        helper.append_op(
            "grouped_matmul",
            inputs={"X": [x.name], "W": [w.name for w in ws],
                    "GroupSizes": [sizes.name]},
            outputs={"Out": [out.name for out in outs]})
        return outs

    if not gated:
        hidden = relu2(*grouped(x_sorted, w_up),
                       group_sizes=sizes if share else None)
    elif share:
        # one op for the two projections of `x_sorted`: its gradient is one
        # variable, summed over the used rows by the op's grad, and the
        # silu product follows the held groups too
        hidden = swiglu(*grouped(x_sorted, w_gate, w_up), group_sizes=sizes)
    else:
        hidden = swiglu(*grouped(x_sorted, w_gate), *grouped(x_sorted, w_up))
    y_sorted, = grouped(hidden, w_down)
    out = new(dtype)
    # under a share the movements follow the held groups, as the kernels do
    used = {"GroupSizes": [sizes.name]} if share else {}
    helper.append_op("moe_combine",
                     inputs={"Y": [y_sorted.name],
                             "TopKWeight": [routing["weight"].name],
                             "Slot": [slot.name], "Source": [source.name],
                             **used},
                     outputs={"Out": [out.name]}, attrs=dict(share))
    return out
