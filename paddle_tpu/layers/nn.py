"""Neural-net layers (reference python/paddle/fluid/layers/nn.py:36, ~190
layers). Each builder appends op descs + infers static output shapes; the real
computation is the registered jax lowering (paddle_tpu/ops/*)."""
import math

import numpy as np

from ..layer_helper import LayerHelper
from ..framework import Variable
from ..initializer import (Constant, Normal, NumpyArrayInitializer,
                           RowsInitializer, Xavier)
from ..param_attr import ParamAttr
from ..core.types import convert_np_dtype_to_dtype_

__all__ = [
    'fc', 'embedding', 'dropout', 'softmax', 'cross_entropy',
    'square_error_cost', 'softmax_with_cross_entropy',
    'sigmoid_cross_entropy_with_logits', 'conv2d', 'conv3d',
    'conv2d_transpose', 'pool2d', 'pool3d', 'batch_norm', 'layer_norm',
    'fused_layer_norm_residual', 'fused_ffn_tail', 'rms_norm',
    'rotary_embedding', 'moe_ffn', 'mla_decode_attention',
    'mla_prefix_attention', 'short_conv_decode', 'short_conv_prefill',
    'ssm_decode', 'ssm_prefill', 'ssd_decode', 'ssd_prefill',
    'gdn_decode', 'gdn_prefill',
    'group_norm', 'data_norm', 'l2_normalize', 'matmul', 'mul', 'topk',
    'reshape', 'squeeze', 'unsqueeze', 'flatten', 'transpose', 'split',
    'reduce_sum', 'reduce_mean', 'reduce_max', 'reduce_min', 'reduce_prod',
    'mean', 'elementwise_add', 'elementwise_sub', 'elementwise_mul',
    'elementwise_div', 'elementwise_max', 'elementwise_min',
    'elementwise_pow', 'clip', 'clip_by_norm', 'one_hot', 'lrn', 'pad',
    'pad2d', 'pad_constant_like', 'label_smooth', 'stack', 'unstack',
    'expand', 'gather', 'scatter', 'slice', 'shape', 'crop', 'relu',
    'log', 'prelu', 'brelu', 'leaky_relu', 'soft_relu', 'sigmoid',
    'log_loss', 'huber_loss', 'smooth_l1', 'bpr_loss', 'rank_loss',
    'margin_rank_loss', 'hinge_loss', 'image_resize', 'resize_bilinear',
    'resize_nearest', 'nce', 'hsigmoid', 'im2sequence', 'multiplex',
    'maxout', 'space_to_depth', 'affine_channel', 'shuffle_channel',
    'bilinear_tensor_product', 'add_position_encoding', 'autoincreased_step_counter',
    'increment', 'cos_sim', 'scale', 'sum', 'elementwise_mod',
    'elementwise_floordiv', 'uniform_random_batch_size_like',
    'gaussian_random', 'sampling_id', 'gaussian_random_batch_size_like',
    'sums_', 'logical_and', 'logical_or', 'logical_xor', 'logical_not',
    'where', 'sign', 'gather_nd', 'random_crop', 'mean_iou', 'hash',
    'grid_sampler', 'affine_grid', 'roi_pool', 'roi_align', 'psroi_pool',
    'py_func', 'unpool', 'spp', 'adaptive_pool2d', 'adaptive_pool3d',
    'dice_loss', 'image_resize_short', 'lstm', 'lstm_unit',
    'conv3d_transpose', 'similarity_focus', 'tree_conv',
    'merge_selected_rows', 'get_tensor_from_selected_rows',
    'switch_moe', 'flash_attention',
    'teacher_student_sigmoid_loss', 'selu', 'swish',
    'sharding_constraint', 'linear_chain_crf', 'crf_decoding', 'warpctc',
    'ctc_greedy_decoder', 'edit_distance',
]


def _prod(xs):
    out = 1
    for x in xs:
        out *= x
    return out


def _simple(helper, op_type, x, out_shape=None, out_dtype=None, inputs=None,
            outputs_extra=None, attrs=None, out_slot='Out'):
    out = helper.create_variable_for_type_inference(
        dtype=out_dtype or x.dtype,
        shape=out_shape if out_shape is not None else x.shape)
    outputs = {out_slot: [out]}
    if outputs_extra:
        outputs.update(outputs_extra)
    helper.append_op(type=op_type, inputs=inputs or {'X': [x]},
                     outputs=outputs, attrs=attrs or {})
    return out


# ---------------------------------------------------------------------------
# Dense / embedding
# ---------------------------------------------------------------------------

def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       act=None, is_test=False, name=None):
    """Fully-connected (reference layers/nn.py fc; lowered as `mul` +
    `elementwise_add` — XLA fuses bias+act into the MXU matmul epilogue)."""
    helper = LayerHelper("fc", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = helper.input_dtype()
    inputs = helper.multiple_input()
    param_attrs = helper.multiple_param_attr(len(inputs))
    mul_results = []
    for inp, p_attr in zip(inputs, param_attrs):
        input_shape = inp.shape
        in_features = _prod(input_shape[num_flatten_dims:])
        w = helper.create_parameter(attr=p_attr,
                                    shape=[in_features, size], dtype=dtype)
        out_shape = tuple(input_shape[:num_flatten_dims]) + (size,)
        tmp = helper.create_variable_for_type_inference(dtype,
                                                        shape=out_shape)
        helper.append_op(
            type='mul', inputs={'X': [inp], 'Y': [w]},
            outputs={'Out': [tmp]},
            attrs={'x_num_col_dims': num_flatten_dims, 'y_num_col_dims': 1})
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(
            dtype, shape=mul_results[0].shape)
        helper.append_op(type='sum', inputs={'X': mul_results},
                         outputs={'Out': [pre_bias]})
    pre_act = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims)
    return helper.append_activation(pre_act)


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype='float32'):
    """Embedding lookup (reference lookup_table_op). With is_sparse=True the
    gradient is a SelectedRows (rows, values) pair — the dense [vocab, dim]
    cotangent is never materialized (see core/lowering.py backward handling)
    and sgd/momentum/adam/adagrad apply it with row-wise scatter updates,
    matching the reference's SelectedRows kernels."""
    helper = LayerHelper('embedding', param_attr=param_attr)
    w = helper.create_parameter(attr=helper.param_attr, shape=size,
                                dtype=dtype, is_bias=False)
    ish = input.shape
    out_shape = (ish[:-1] if ish and ish[-1] == 1 else ish) + (size[1],)
    tmp = helper.create_variable_for_type_inference(dtype, shape=out_shape)
    padding_idx = -1 if padding_idx is None else (
        padding_idx if padding_idx >= 0 else size[0] + padding_idx)
    helper.append_op(
        type='lookup_table', inputs={'Ids': [input], 'W': [w]},
        outputs={'Out': [tmp]},
        attrs={'is_sparse': is_sparse, 'is_distributed': is_distributed,
               'padding_idx': padding_idx})
    return tmp


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper('cross_entropy')
    out_shape = tuple(input.shape[:-1]) + (1,)
    return _simple(helper, 'cross_entropy', input, out_shape=out_shape,
                   inputs={'X': [input], 'Label': [label]},
                   attrs={'soft_label': soft_label,
                          'ignore_index': ignore_index}, out_slot='Y')


def square_error_cost(input, label):
    helper = LayerHelper('square_error_cost')
    minus_out = _simple(helper, 'elementwise_sub', input,
                        inputs={'X': [input], 'Y': [label]})
    return _simple(helper, 'square', minus_out)


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=False,
                               return_softmax=False):
    helper = LayerHelper('softmax_with_cross_entropy')
    softmax = helper.create_variable_for_type_inference(
        dtype=logits.dtype, shape=logits.shape)
    loss = helper.create_variable_for_type_inference(
        dtype=logits.dtype, shape=tuple(logits.shape[:-1]) + (1,))
    helper.append_op(
        type='softmax_with_cross_entropy',
        inputs={'Logits': [logits], 'Label': [label]},
        outputs={'Softmax': [softmax], 'Loss': [loss]},
        attrs={'soft_label': soft_label, 'ignore_index': ignore_index,
               'numeric_stable_mode': numeric_stable_mode})
    if return_softmax:
        return loss, softmax
    return loss


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100, name=None):
    helper = LayerHelper('sigmoid_cross_entropy_with_logits', name=name)
    return _simple(helper, 'sigmoid_cross_entropy_with_logits', x,
                   inputs={'X': [x], 'Label': [label]},
                   attrs={'ignore_index': ignore_index})


def log_loss(input, label, epsilon=1e-4, name=None):
    helper = LayerHelper('log_loss', name=name)
    return _simple(helper, 'log_loss', input,
                   inputs={'Predicted': [input], 'Labels': [label]},
                   attrs={'epsilon': epsilon}, out_slot='Loss')


def huber_loss(input, label, delta):
    helper = LayerHelper('huber_loss')
    residual = helper.create_variable_for_type_inference(
        dtype=input.dtype, shape=input.shape)
    out = helper.create_variable_for_type_inference(
        dtype=input.dtype, shape=input.shape)
    helper.append_op(type='huber_loss',
                     inputs={'X': [input], 'Y': [label]},
                     outputs={'Out': [out], 'Residual': [residual]},
                     attrs={'delta': delta})
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper('smooth_l1_loss')
    diff = helper.create_variable_for_type_inference(dtype=x.dtype,
                                                     shape=x.shape)
    loss = helper.create_variable_for_type_inference(
        dtype=x.dtype, shape=(x.shape[0], 1))
    inputs = {'X': [x], 'Y': [y]}
    if inside_weight is not None:
        inputs['InsideWeight'] = [inside_weight]
    if outside_weight is not None:
        inputs['OutsideWeight'] = [outside_weight]
    helper.append_op(type='smooth_l1_loss', inputs=inputs,
                     outputs={'Diff': [diff], 'Out': [loss]},
                     attrs={'sigma': sigma if sigma is not None else 1.0})
    return loss


def bpr_loss(input, label, name=None):
    helper = LayerHelper('bpr_loss', name=name)
    return _simple(helper, 'bpr_loss', input,
                   out_shape=(input.shape[0], 1),
                   inputs={'X': [input], 'Label': [label]}, out_slot='Y')


def rank_loss(label, left, right, name=None):
    helper = LayerHelper('rank_loss', name=name)
    return _simple(helper, 'rank_loss', left,
                   inputs={'Label': [label], 'Left': [left],
                           'Right': [right]})


def margin_rank_loss(label, left, right, margin=0.1, name=None):
    helper = LayerHelper('margin_rank_loss', name=name)
    act = helper.create_variable_for_type_inference(dtype=left.dtype,
                                                    shape=left.shape)
    out = helper.create_variable_for_type_inference(dtype=left.dtype,
                                                    shape=left.shape)
    helper.append_op(type='margin_rank_loss',
                     inputs={'Label': [label], 'X1': [left], 'X2': [right]},
                     outputs={'Out': [out], 'Activated': [act]},
                     attrs={'margin': margin})
    return out


def hinge_loss(input, label, name=None):
    helper = LayerHelper('hinge_loss', name=name)
    return _simple(helper, 'hinge_loss', input,
                   inputs={'Logits': [input], 'Labels': [label]},
                   out_slot='Loss')


def teacher_student_sigmoid_loss(input, label, soft_max_up_bound=15.0,
                                 soft_max_lower_bound=-15.0):
    helper = LayerHelper('teacher_student_sigmoid_loss')
    return _simple(helper, 'teacher_student_sigmoid_loss', input,
                   inputs={'X': [input], 'Label': [label]},
                   attrs={'soft_max_up_bound': soft_max_up_bound,
                          'soft_max_lower_bound': soft_max_lower_bound},
                   out_slot='Y')


# ---------------------------------------------------------------------------
# Convolution / pooling / norm
# ---------------------------------------------------------------------------

def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return list(v)
    return [v] * n


def _conv_out(i, k, p, s, d=1):
    if i is None or i < 0:
        return -1
    return (i + 2 * p - (d * (k - 1) + 1)) // s + 1


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=None, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None):
    helper = LayerHelper('conv2d', param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    n, c = input.shape[0], input.shape[1]
    groups = groups or 1
    fsize = _pair(filter_size)
    stride = _pair(stride)
    padding = _pair(padding)
    dilation = _pair(dilation)
    filter_shape = [num_filters, c // groups] + fsize
    fan_in = (c // groups) * fsize[0] * fsize[1]
    std = (2.0 / fan_in) ** 0.5
    w = helper.create_parameter(
        attr=helper.param_attr, shape=filter_shape, dtype=dtype,
        default_initializer=Normal(0.0, std))
    oh = _conv_out(input.shape[2], fsize[0], padding[0], stride[0],
                   dilation[0])
    ow = _conv_out(input.shape[3], fsize[1], padding[1], stride[1],
                   dilation[1])
    pre_bias = helper.create_variable_for_type_inference(
        dtype, shape=(n, num_filters, oh, ow))
    helper.append_op(
        type='conv2d', inputs={'Input': [input], 'Filter': [w]},
        outputs={'Output': [pre_bias]},
        attrs={'strides': stride, 'paddings': padding, 'dilations': dilation,
               'groups': groups, 'use_cudnn': use_cudnn})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def conv3d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=None, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None):
    helper = LayerHelper('conv3d', param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    n, c = input.shape[0], input.shape[1]
    groups = groups or 1
    fsize = _pair(filter_size, 3)
    stride = _pair(stride, 3)
    padding = _pair(padding, 3)
    dilation = _pair(dilation, 3)
    w = helper.create_parameter(
        attr=helper.param_attr,
        shape=[num_filters, c // groups] + fsize, dtype=dtype)
    osp = [_conv_out(input.shape[2 + i], fsize[i], padding[i], stride[i],
                     dilation[i]) for i in range(3)]
    pre_bias = helper.create_variable_for_type_inference(
        dtype, shape=tuple([n, num_filters] + osp))
    helper.append_op(
        type='conv3d', inputs={'Input': [input], 'Filter': [w]},
        outputs={'Output': [pre_bias]},
        attrs={'strides': stride, 'paddings': padding,
               'dilations': dilation, 'groups': groups})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=None,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None):
    helper = LayerHelper('conv2d_transpose', param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    n, c, h, w_in = input.shape
    groups = groups or 1
    stride = _pair(stride)
    padding = _pair(padding)
    dilation = _pair(dilation)
    if filter_size is None:
        if output_size is None:
            raise ValueError("output_size or filter_size required")
        output_size = _pair(output_size)
        filter_size = [
            (output_size[0] - (h - 1) * stride[0] + 2 * padding[0] - 1)
            // dilation[0] + 1,
            (output_size[1] - (w_in - 1) * stride[1] + 2 * padding[1] - 1)
            // dilation[1] + 1]
    else:
        filter_size = _pair(filter_size)
    wvar = helper.create_parameter(
        attr=helper.param_attr,
        shape=[c, num_filters // groups] + filter_size, dtype=dtype)
    oh = (h - 1) * stride[0] - 2 * padding[0] + \
        dilation[0] * (filter_size[0] - 1) + 1
    ow = (w_in - 1) * stride[1] - 2 * padding[1] + \
        dilation[1] * (filter_size[1] - 1) + 1
    pre_bias = helper.create_variable_for_type_inference(
        dtype, shape=(n, num_filters, oh, ow))
    helper.append_op(
        type='conv2d_transpose',
        inputs={'Input': [input], 'Filter': [wvar]},
        outputs={'Output': [pre_bias]},
        attrs={'strides': stride, 'paddings': padding,
               'dilations': dilation, 'groups': groups})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def pool2d(input, pool_size=-1, pool_type='max', pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, name=None, exclusive=True):
    helper = LayerHelper('pool2d', name=name)
    ksize = _pair(pool_size)
    stride = _pair(pool_stride)
    padding = _pair(pool_padding)
    n, c, h, w = input.shape
    if global_pooling:
        oh = ow = 1
    else:
        def _po(i, k, p, s):
            if i is None or i < 0:
                return -1
            if ceil_mode:
                return -(-(i + 2 * p - k) // s) + 1
            return (i + 2 * p - k) // s + 1
        oh = _po(h, ksize[0], padding[0], stride[0])
        ow = _po(w, ksize[1], padding[1], stride[1])
    out = helper.create_variable_for_type_inference(
        input.dtype, shape=(n, c, oh, ow))
    helper.append_op(
        type='pool2d', inputs={'X': [input]}, outputs={'Out': [out]},
        attrs={'pooling_type': pool_type, 'ksize': ksize,
               'global_pooling': global_pooling, 'strides': stride,
               'paddings': padding, 'ceil_mode': ceil_mode,
               'exclusive': exclusive})
    return out


def pool3d(input, pool_size=-1, pool_type='max', pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, name=None, exclusive=True):
    helper = LayerHelper('pool3d', name=name)
    ksize = _pair(pool_size, 3)
    stride = _pair(pool_stride, 3)
    padding = _pair(pool_padding, 3)
    sp = input.shape[2:]
    if global_pooling:
        osp = [1, 1, 1]
    else:
        osp = [(-(-(i + 2 * p - k) // s) if ceil_mode else
                (i + 2 * p - k) // s) + 1
               for i, k, p, s in zip(sp, ksize, padding, stride)]
    out = helper.create_variable_for_type_inference(
        input.dtype, shape=tuple(list(input.shape[:2]) + osp))
    helper.append_op(
        type='pool3d', inputs={'X': [input]}, outputs={'Out': [out]},
        attrs={'pooling_type': pool_type, 'ksize': ksize,
               'global_pooling': global_pooling, 'strides': stride,
               'paddings': padding, 'ceil_mode': ceil_mode,
               'exclusive': exclusive})
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout='NCHW',
               in_place=False, name=None, moving_mean_name=None,
               moving_variance_name=None, do_model_average_for_mean_and_var=False,
               use_global_stats=False):
    helper = LayerHelper('batch_norm', param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    c = input.shape[1] if data_layout == 'NCHW' else input.shape[-1]
    scale = helper.create_parameter(attr=helper.param_attr, shape=[c],
                                    dtype=dtype,
                                    default_initializer=Constant(1.0))
    bias = helper.create_parameter(attr=helper.bias_attr or ParamAttr(),
                                   shape=[c], dtype=dtype, is_bias=True)
    mean = helper.create_or_get_global_variable(
        name=moving_mean_name or helper.name + '.mean',
        dtype=dtype, shape=(c,))
    helper.set_variable_initializer(mean, Constant(0.0))
    variance = helper.create_or_get_global_variable(
        name=moving_variance_name or helper.name + '.variance',
        dtype=dtype, shape=(c,))
    helper.set_variable_initializer(variance, Constant(1.0))
    saved_mean = helper.create_variable_for_type_inference(dtype, shape=(c,))
    saved_var = helper.create_variable_for_type_inference(dtype, shape=(c,))
    out = helper.create_variable_for_type_inference(dtype, shape=input.shape)
    helper.append_op(
        type='batch_norm',
        inputs={'X': [input], 'Scale': [scale], 'Bias': [bias],
                'Mean': [mean], 'Variance': [variance]},
        outputs={'Y': [out], 'MeanOut': [mean], 'VarianceOut': [variance],
                 'SavedMean': [saved_mean], 'SavedVariance': [saved_var]},
        attrs={'momentum': momentum, 'epsilon': epsilon, 'is_test': is_test,
               'data_layout': data_layout,
               'use_global_stats': use_global_stats})
    return helper.append_activation(out)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    helper = LayerHelper('layer_norm', param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    norm_shape = [_prod(input.shape[begin_norm_axis:])]
    inputs = {'X': [input]}
    if scale:
        s = helper.create_parameter(attr=helper.param_attr,
                                    shape=norm_shape, dtype=dtype,
                                    default_initializer=Constant(1.0))
        inputs['Scale'] = [s]
    if shift:
        b = helper.create_parameter(attr=helper.bias_attr or ParamAttr(),
                                    shape=norm_shape, dtype=dtype,
                                    is_bias=True)
        inputs['Bias'] = [b]
    mean = helper.create_variable_for_type_inference(
        dtype, shape=(_prod(input.shape[:begin_norm_axis]),))
    variance = helper.create_variable_for_type_inference(
        dtype, shape=(_prod(input.shape[:begin_norm_axis]),))
    out = helper.create_variable_for_type_inference(dtype, shape=input.shape)
    helper.append_op(type='layer_norm', inputs=inputs,
                     outputs={'Y': [out], 'Mean': [mean],
                              'Variance': [variance]},
                     attrs={'epsilon': epsilon,
                            'begin_norm_axis': begin_norm_axis})
    return helper.append_activation(out)


def fused_layer_norm_residual(input, residual, begin_norm_axis=1,
                              epsilon=1e-5, param_attr=None,
                              bias_attr=None, name=None):
    """Fused residual-add + LayerNorm pair (kernel-tier unit,
    ops/nn_ops.py fused_ln_residual): returns ``(normed, summed)`` where
    ``summed = input + residual`` and ``normed = LN(summed)*scale+bias``.
    PADDLE_FUSED_TIER selects the lowering; tier 'off' reproduces
    elementwise_add + layer_norm bitwise, so wiring this pair into a
    model never changes legacy numerics."""
    helper = LayerHelper('fused_ln_residual', param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    dtype = input.dtype
    norm_shape = [_prod(input.shape[begin_norm_axis:])]
    s = helper.create_parameter(attr=helper.param_attr, shape=norm_shape,
                                dtype=dtype,
                                default_initializer=Constant(1.0))
    b = helper.create_parameter(attr=helper.bias_attr or ParamAttr(),
                                shape=norm_shape, dtype=dtype,
                                is_bias=True)
    out = helper.create_variable_for_type_inference(dtype,
                                                    shape=input.shape)
    summed = helper.create_variable_for_type_inference(dtype,
                                                       shape=input.shape)
    helper.append_op(type='fused_ln_residual',
                     inputs={'X': [input], 'Residual': [residual],
                             'Scale': [s], 'Bias': [b]},
                     outputs={'Y': [out], 'ResidualOut': [summed]},
                     attrs={'epsilon': epsilon,
                            'begin_norm_axis': begin_norm_axis})
    return out, summed


def fused_ffn_tail(input, inner_size, size, num_flatten_dims=1,
                   dropout_prob=0.0, is_test=False, seed=None,
                   inner_param_attr=None, inner_bias_attr=None,
                   param_attr=None, bias_attr=None, name=None):
    """Fused transformer FFN sublayer (kernel-tier unit,
    ops/ffn_ops.py fused_ffn_tail):

        out = dropout(gelu(input @ W1 + b1) @ W2 + b2)

    One op in place of the ``fc(act='gelu') -> fc -> dropout`` chain
    (the dropout with ``upscale_in_train`` semantics — keep-mask scaled
    at train time, identity at inference). PADDLE_FUSED_TIER selects
    the lowering; tier 'off' reproduces that
    six-op composition bitwise, so wiring this into a model never
    changes legacy numerics (the training-mode dropout key comes from
    the program's counted RNG stream — see ops/ffn_ops.py on mask
    replay vs. program structure). Parameters are created exactly as
    the two ``fc`` calls would (same shapes, initializers and creation
    order), so trained scopes serve either wiring unchanged."""
    helper = LayerHelper('fused_ffn_tail', name=name)
    dtype = input.dtype
    d_in = _prod(input.shape[num_flatten_dims:])
    w1 = helper.create_parameter(attr=inner_param_attr or ParamAttr(),
                                 shape=[d_in, inner_size], dtype=dtype)
    b1 = helper.create_parameter(attr=inner_bias_attr or ParamAttr(),
                                 shape=[inner_size], dtype=dtype,
                                 is_bias=True)
    w2 = helper.create_parameter(attr=param_attr or ParamAttr(),
                                 shape=[inner_size, size], dtype=dtype)
    b2 = helper.create_parameter(attr=bias_attr or ParamAttr(),
                                 shape=[size], dtype=dtype, is_bias=True)
    out_shape = tuple(input.shape[:num_flatten_dims]) + (size,)
    out = helper.create_variable_for_type_inference(dtype,
                                                    shape=out_shape)
    helper.append_op(
        type='fused_ffn_tail',
        inputs={'X': [input], 'W1': [w1], 'B1': [b1],
                'W2': [w2], 'B2': [b2]},
        outputs={'Out': [out]},
        attrs={'x_num_col_dims': num_flatten_dims,
               'dropout_prob': dropout_prob, 'is_test': is_test,
               'seed': seed if seed is not None else 0,
               'dropout_implementation': 'upscale_in_train'})
    return out


def rms_norm(input, begin_norm_axis=1, epsilon=1e-5, param_attr=None,
             name=None, zero_centred=False):
    """RMSNorm over the trailing dimensions from ``begin_norm_axis``:
    ``x * rsqrt(mean(x^2) + epsilon) * w``, computed in float32
    (ops/moe_ops.py). The weight starts at 1. ``zero_centred``: ``x *
    rsqrt(..) * (1 + w)``, the weight starts at 0 (Gemma's and
    Qwen3-Next's norm)."""
    helper = LayerHelper('rms_norm', param_attr=param_attr, name=name)
    dtype = input.dtype
    w = helper.create_parameter(
        attr=helper.param_attr,
        shape=[_prod(input.shape[begin_norm_axis:])], dtype=dtype,
        default_initializer=Constant(0.0 if zero_centred else 1.0))
    out = helper.create_variable_for_type_inference(dtype,
                                                    shape=input.shape)
    attrs = {'epsilon': epsilon, 'begin_norm_axis': begin_norm_axis}
    if zero_centred:
        # absent where unset: a program without it is what it was
        attrs['zero_centred'] = True
    helper.append_op(type='rms_norm',
                     inputs={'X': [input], 'Scale': [w]},
                     outputs={'Out': [out]}, attrs=attrs)
    return out


def rotary_embedding(input, positions, theta=10000.0, interleave=False,
                     name=None, factor=None, original_max_position=None,
                     beta_fast=32.0, beta_slow=1.0, attention_factor=None,
                     rotary_dim=None):
    """Rotary position embedding of ``input [..., H, dh]`` by the int64
    ``positions`` (one per leading row of ``input``), ``rotate_half``
    convention, or with ``interleave`` the pairs ``(2i, 2i + 1)``;
    ``inv_freq = theta^(-2i/dh)`` (ops/moe_ops.py). With ``factor`` YaRN's
    table, made when the program is traced (`moe_ops.yarn_inv_freq`): the
    frequencies that turn fewer than ``beta_slow`` times in
    ``original_max_position`` positions divided by ``factor``, those that
    turn more than ``beta_fast`` times kept, a ramp between; cos and sin
    times ``attention_factor`` (None: ``0.1 ln(factor) + 1``). With
    ``rotary_dim`` the FIRST ``rotary_dim`` numbers of each head alone are
    rotated (as a head of that size: ``inv_freq = theta^(-2i/rotary_dim)``)
    and the rest pass through (``partial_rotary_factor``)."""
    helper = LayerHelper('rotary_embedding', name=name)
    out = helper.create_variable_for_type_inference(input.dtype,
                                                    shape=input.shape)
    attrs = {'theta': float(theta)}
    if interleave:
        attrs['interleave'] = True
    if rotary_dim is not None and int(rotary_dim) != input.shape[-1]:
        attrs['rotary_dim'] = int(rotary_dim)
    if factor is not None:
        # absent where unset: a program without YaRN is what it was
        attrs.update(
            yarn_factor=float(factor),
            yarn_original_max_position=int(original_max_position),
            yarn_beta_fast=float(beta_fast), yarn_beta_slow=float(beta_slow),
            yarn_attention_factor=float(
                0.1 * math.log(factor) + 1.0 if attention_factor is None
                else attention_factor))
    helper.append_op(type='rotary_embedding',
                     inputs={'X': [input], 'Positions': [positions]},
                     outputs={'Out': [out]}, attrs=attrs)
    return out


def moe_ffn(input, n_experts, expert_width, top_k, norm_topk_prob=False,
            length=None, valid=None, router_param_attr=None,
            gate_param_attr=None, up_param_attr=None, down_param_attr=None,
            score='softmax', select_bias_attr=None, routed_scale=1.0,
            experts_held=None, router_eps=None, form='gated', name=None):
    """Dropless top-k mixture-of-experts FFN over the rows of ``input
    [N, d]`` (ops/moe_ops.py): a float32 softmax router over all
    ``n_experts``, the ``top_k`` largest (renormalised only with
    ``norm_topk_prob``), SiLU-gated experts of width ``expert_width``
    through a grouped matmul. Returns ``(out [N, d], topk_idx [N, top_k]
    int32, expert_load [n_experts] int32)``; ``length`` (real rows of a
    padded bucket) and ``valid`` (zero = an idle row) only leave rows out
    of ``expert_load``.

    ``score='sigmoid'``: sigmoid scores, chosen by score +
    ``select_bias_attr``'s parameter, weighted by the score and
    ``routed_scale``; with ``norm_topk_prob`` the chosen scores are
    divided by their sum + ``router_eps`` (None: the op's 1e-20).
    ``experts_held = (first, count)``: the layer
    holds that share of the ``n_experts`` the router scores, ``out`` is
    the part of the sum these experts give and ``expert_load`` is
    ``[count + 1]``, the last entry the assignments that went
    elsewhere. ``form='relu2'``: an expert is ``relu(x W_up)^2 W_down``,
    two matrices and no gate (``gate_param_attr`` names nothing)."""
    helper = LayerHelper('moe_ffn', name=name)
    dtype = input.dtype
    n, d = input.shape[0], input.shape[-1]
    init = Normal(0.0, 0.02)

    def param(attr, shape):
        return helper.create_parameter(attr=attr or ParamAttr(),
                                       shape=shape, dtype=dtype,
                                       default_initializer=init)
    first, held = experts_held or (0, n_experts)
    if not 0 <= first <= first + held <= n_experts or not held:
        raise ValueError('moe_ffn: experts_held=%r is no share of %d '
                         'experts' % (experts_held, n_experts))
    share = held < n_experts
    router = param(router_param_attr, [d, n_experts])
    gate = param(gate_param_attr, [held, d, expert_width]) \
        if form == 'gated' else None
    up = param(up_param_attr, [held, d, expert_width])
    down = param(down_param_attr, [held, expert_width, d])
    out = helper.create_variable_for_type_inference(dtype,
                                                    shape=input.shape)
    idx = helper.create_variable_for_type_inference('int32',
                                                    shape=(n, top_k))
    load = helper.create_variable_for_type_inference(
        'int32', shape=(held + 1 if share else held,))
    inputs = {'X': [input], 'RouterW': [router]}
    if gate is not None:
        inputs['GateW'] = [gate]
    inputs.update({'UpW': [up], 'DownW': [down]})
    if length is not None:
        inputs['Length'] = [length]
    if valid is not None:
        inputs['Valid'] = [valid]
    attrs = {'top_k': int(top_k), 'norm_topk_prob': bool(norm_topk_prob)}
    if score != 'softmax':
        attrs.update(score=score, routed_scale=float(routed_scale))
        inputs['SelectBias'] = [helper.create_parameter(
            attr=select_bias_attr or ParamAttr(), shape=[n_experts],
            dtype=dtype, default_initializer=Normal(0.0, 0.01))]
    if share:
        attrs['first_expert'] = int(first)
    if router_eps is not None:
        attrs['router_eps'] = float(router_eps)
    helper.append_op(type='moe_ffn', inputs=inputs,
                     outputs={'Out': [out], 'TopkIdx': [idx],
                              'ExpertLoad': [load]}, attrs=attrs)
    return out, idx, load


def _mla_attention(op_type, table_slot, q, cache, positions, tables, layer,
                   scale, kv_rank, rope_dim, v_dim, up_k_attr, up_v_attr):
    helper = LayerHelper(op_type)
    n_head, nope = q.shape[-2], q.shape[-1] - rope_dim
    init = Normal(0.0, 0.02)
    up_k = helper.create_parameter(attr=up_k_attr, dtype=q.dtype,
                                   shape=[n_head, nope, kv_rank],
                                   default_initializer=init)
    up_v = helper.create_parameter(attr=up_v_attr, dtype=q.dtype,
                                   shape=[n_head, kv_rank, v_dim],
                                   default_initializer=init)
    out = helper.create_variable_for_type_inference(
        q.dtype, shape=tuple(q.shape[:-1]) + (v_dim,))
    helper.append_op(
        type=op_type,
        inputs={'Q': [q], 'Cache': [cache], 'UpK': [up_k], 'UpV': [up_v],
                'Positions': [positions], table_slot: [tables]},
        outputs={'Out': [out]},
        attrs={'layer': int(layer), 'scale': float(scale)})
    return out


def mla_decode_attention(q, cache, positions, block_tables, layer, scale,
                         kv_rank, rope_dim, v_dim, up_k_attr=None,
                         up_v_attr=None):
    """Absorbed latent attention of every slot's one query against the
    latent rows its block table names (ops/mla_ops.py). ``q [S, H, nope +
    rope_dim]``, ``cache`` the latent pool; the parameters are the two
    halves of the latent up-projection, ``W_uk [H, nope, kv_rank]`` and
    ``W_uv [H, kv_rank, v_dim]``. Returns ``[S, H, v_dim]``."""
    return _mla_attention('mla_decode_attention_paged', 'BlockTables', q,
                          cache, positions, block_tables, layer, scale,
                          kv_rank, rope_dim, v_dim, up_k_attr, up_v_attr)


def mla_prefix_attention(q, cache, positions, block_table, layer, scale,
                         kv_rank, rope_dim, v_dim, up_k_attr=None,
                         up_v_attr=None):
    """Expanded causal latent attention of one prompt suffix ``q [1, T, H,
    nope + rope_dim]`` against the slot's cached rows (ops/mla_ops.py),
    with `mla_decode_attention`'s parameters. Returns ``[1, T, H,
    v_dim]``."""
    return _mla_attention('mla_prefix_attention', 'BlockTable', q, cache,
                          positions, block_table, layer, scale, kv_rank,
                          rope_dim, v_dim, up_k_attr, up_v_attr)


def _short_conv(op_type, table_slot, g, cache, positions, tables, layer,
                block_size, kernel, param_attr, length=None):
    helper = LayerHelper(op_type)
    w = helper.create_parameter(attr=param_attr or ParamAttr(),
                                shape=[g.shape[-1], int(kernel)],
                                dtype=g.dtype,
                                default_initializer=Normal(0.0, 0.3))
    out = helper.create_variable_for_type_inference(g.dtype, shape=g.shape)
    inputs = {'X': [g], 'Weight': [w], 'Cache': [cache],
              'Positions': [positions], table_slot: [tables]}
    if length is not None:
        inputs['Length'] = [length]
    helper.append_op(type=op_type, inputs=inputs,
                     outputs={'Out': [out], 'CacheOut': [cache]},
                     attrs={'layer': int(layer),
                            'block_size': int(block_size)})
    return out


def short_conv_decode(g, cache, positions, block_tables, layer, block_size,
                      kernel=3, param_attr=None):
    """The causal depthwise convolution (``kernel`` taps, weight ``[d,
    kernel]``, no bias) of every slot's one new row ``g [S, d]`` behind the
    ``kernel - 1`` rows its block table's pool entry holds, and that
    entry's update (ops/short_conv_ops.py). ``cache`` is the tails' pool,
    read and written in place. Returns ``[S, d]``."""
    return _short_conv('short_conv_decode_paged', 'BlockTables', g, cache,
                       positions, block_tables, layer, block_size, kernel,
                       param_attr)


def short_conv_prefill(g, cache, positions, block_table, length, layer,
                       block_size, kernel=3, param_attr=None):
    """`short_conv_decode` for one prompt suffix ``g [1, T, d]`` that
    starts at ``positions[0]``: resumes behind the entry of the block
    before it and writes the entry of every block its ``length`` real rows
    touch (ops/short_conv_ops.py). Returns ``[1, T, d]``."""
    return _short_conv('short_conv_prefill_paged', 'BlockTable', g, cache,
                       positions, block_table, layer, block_size, kernel,
                       param_attr, length=length)


def _ssm(op_type, u, z, state, tail, rows, layer, prefix, n_state, kernel,
         dt_rank, epsilon, positions=None, length=None):
    """A state-space mixer's op (ops/ssm_ops.py) with the layer's inner
    parameters under ``prefix``. Their defaults are Mamba's own for the
    recurrence (state-spaces/mamba `Mamba.__init__`): ``A_log`` = log(1 ..
    n_state) a channel, laid out ``[n_state, d_inner]`` as the state is;
    ``D`` = 1; the step's bias the inverse softplus of 0.01, the middle of
    the published [1e-3, 1e-1] — with a zero bias ``delta`` is 0.69 and the
    state forgets within a few positions."""
    helper = LayerHelper(op_type)
    di = int(u.shape[-1])

    def param(name, shape, init):
        return helper.create_parameter(
            attr=ParamAttr(name='%s.%s' % (prefix, name)), shape=shape,
            dtype=u.dtype, default_initializer=init)
    a_log = np.log(np.arange(1, n_state + 1, dtype='float32'))
    weights = {
        'ConvW': param('conv.w', [di, int(kernel)], Normal(0.0, 0.3)),
        'ConvB': param('conv.b', [di], Normal(0.0, 0.1)),
        'XProj': param('x.w', [di, dt_rank + 2 * n_state],
                       Normal(0.0, 0.02)),
        'DtNorm': param('dt_norm.w', [dt_rank], Constant(1.0)),
        'BNorm': param('b_norm.w', [n_state], Constant(1.0)),
        'CNorm': param('c_norm.w', [n_state], Constant(1.0)),
        'DtProj': param('dt.w', [dt_rank, di], Normal(0.0, 0.02)),
        'DtBias': param('dt.b', [di],
                        Constant(float(np.log(np.expm1(0.01))))),
        'ALog': param('A_log', [n_state, di], RowsInitializer(a_log)),
        'D': param('D', [di], Constant(1.0))}
    out = helper.create_variable_for_type_inference(u.dtype, shape=u.shape)
    inputs = {'X': [u], 'Z': [z], 'State': [state], 'Tail': [tail],
              'Rows': [rows]}
    inputs.update({k: [v] for k, v in weights.items()})
    if positions is not None:
        inputs.update({'Positions': [positions], 'Length': [length]})
    helper.append_op(type=op_type, inputs=inputs,
                     outputs={'Out': [out], 'StateOut': [state],
                              'TailOut': [tail]},
                     attrs={'layer': int(layer), 'epsilon': float(epsilon)})
    return out


def ssm_decode(u, z, state, tail, rows, layer, prefix, n_state, kernel,
               dt_rank, epsilon=1e-6):
    """One step of a state-space layer for every slot's one row: ``u`` and
    ``z`` ``[S, d_inner]`` (the two halves of the mixer's input
    projection), ``state`` / ``tail`` the two pools, read and written in
    place at the rows ``rows [S, 1]`` names (0: none), ``layer`` the
    layer's ordinal in them (ops/ssm_ops.py). Returns ``y * silu(z) [S,
    d_inner]``, the output projection's input."""
    return _ssm('ssm_decode', u, z, state, tail, rows, layer, prefix,
                n_state, kernel, dt_rank, epsilon)


def ssm_prefill(u, z, state, tail, rows, positions, length, layer, prefix,
                n_state, kernel, dt_rank, epsilon=1e-6):
    """`ssm_decode` for one prompt suffix ``u``, ``z`` ``[1, T, d_inner]``
    that starts at ``positions[0]``: from zeros there, else from the row
    as the chunk before left it, over the ``length`` real rows
    (ops/ssm_ops.py). Returns ``[1, T, d_inner]``."""
    return _ssm('ssm_prefill', u, z, state, tail, rows, layer, prefix,
                n_state, kernel, dt_rank, epsilon, positions=positions,
                length=length)


def _ssd(op_type, xbc, z, dt, state, tail, rows, layer, prefix, groups,
         kernel, epsilon, chunk=None, positions=None, length=None):
    """A Mamba-2 mixer's op (ops/ssd_ops.py) with the layer's inner
    parameters under ``prefix``. Their defaults are Mamba-2's own for the
    recurrence (state-spaces/mamba `Mamba2.__init__`): ``A_log`` the log of
    1 .. 16 evenly over the heads (published: a uniform draw there), ``D``
    = 1, the step's bias the inverse softplus of 0.01, the middle of the
    published [1e-3, 1e-1]."""
    helper = LayerHelper(op_type)
    di, heads = int(z.shape[-1]), int(dt.shape[-1])

    def param(name, shape, init):
        return helper.create_parameter(
            attr=ParamAttr(name='%s.%s' % (prefix, name)), shape=shape,
            dtype=z.dtype, default_initializer=init)
    weights = {
        'ConvW': param('conv.w', [int(xbc.shape[-1]), int(kernel)],
                       Normal(0.0, 0.3)),
        'ConvB': param('conv.b', [int(xbc.shape[-1])], Normal(0.0, 0.1)),
        'DtBias': param('dt.b', [heads],
                        Constant(float(np.log(np.expm1(0.01))))),
        'ALog': param('A_log', [heads], NumpyArrayInitializer(
            np.log(np.linspace(1.0, 16.0, heads, dtype='float32')))),
        'D': param('D', [heads], Constant(1.0)),
        'NormW': param('norm.w', [di], Constant(1.0))}
    out = helper.create_variable_for_type_inference(z.dtype, shape=z.shape)
    inputs = {'X': [xbc], 'Z': [z], 'Dt': [dt], 'State': [state],
              'Tail': [tail], 'Rows': [rows]}
    inputs.update({k: [v] for k, v in weights.items()})
    attrs = {'layer': int(layer), 'epsilon': float(epsilon),
             'groups': int(groups)}
    if positions is not None:
        inputs.update({'Positions': [positions], 'Length': [length]})
        attrs['chunk'] = int(chunk)
    helper.append_op(type=op_type, inputs=inputs,
                     outputs={'Out': [out], 'StateOut': [state],
                              'TailOut': [tail]}, attrs=attrs)
    return out


def ssd_decode(xbc, z, dt, state, tail, rows, layer, prefix, groups, kernel,
               epsilon=1e-5):
    """One step of a Mamba-2 layer for every slot's one row: ``xbc [S,
    d_inner + 2 G N]``, ``z [S, d_inner]`` and ``dt [S, H]`` (the three
    parts of the mixer's input projection), ``state`` / ``tail`` the two
    pools, read and written in place at the rows ``rows [S, 1]`` names (0:
    none), ``layer`` the layer's ordinal in them (ops/ssd_ops.py). Returns
    the gated, group-normed ``[S, d_inner]``, the output projection's
    input."""
    return _ssd('ssd_decode', xbc, z, dt, state, tail, rows, layer, prefix,
                groups, kernel, epsilon)


def ssd_prefill(xbc, z, dt, state, tail, rows, positions, length, layer,
                prefix, groups, kernel, chunk, epsilon=1e-5):
    """`ssd_decode` for one prompt suffix (``[1, T, ...]``) that starts at
    ``positions[0]``: from zeros there, else from the row as the chunk
    before left it, over the ``length`` real rows, in blocks of ``chunk``
    rows (ops/ssd_ops.py). Returns ``[1, T, d_inner]``."""
    return _ssd('ssd_prefill', xbc, z, dt, state, tail, rows, layer, prefix,
                groups, kernel, epsilon, chunk=chunk, positions=positions,
                length=length)


def _gdn(op_type, qkv, z, b, a, state, tail, rows, layer, prefix, key_heads,
         kernel, epsilon, chunk=None, positions=None, length=None,
         allow_neg_eigval=False):
    """A Gated DeltaNet mixer's op (ops/gdn_ops.py) with the layer's inner
    parameters under ``prefix``. Their defaults are the family's own (HF
    `Qwen3NextGatedDeltaNet.__init__`): ``A_log`` the log of 1 .. 16 evenly
    over the value heads (published: a uniform draw in (0, 16)), the step's
    bias 1, the output norm's one weight ``[dv]`` 1, the taps without a
    bias. ``allow_neg_eigval``: the write strength is ``2 sigmoid(b)``, in
    (0, 2) (the attribute is absent where unset)."""
    helper = LayerHelper(op_type)
    heads = int(b.shape[-1])

    def param(name, shape, init):
        return helper.create_parameter(
            attr=ParamAttr(name='%s.%s' % (prefix, name)), shape=shape,
            dtype=z.dtype, default_initializer=init)
    weights = {
        'ConvW': param('conv.w', [int(qkv.shape[-1]), int(kernel)],
                       Normal(0.0, 0.3)),
        'ALog': param('A_log', [heads], NumpyArrayInitializer(
            np.log(np.linspace(1.0, 16.0, heads, dtype='float32')))),
        'DtBias': param('dt.b', [heads], Constant(1.0)),
        'NormW': param('norm.w', [int(z.shape[-1]) // heads],
                       Constant(1.0))}
    out = helper.create_variable_for_type_inference(z.dtype, shape=z.shape)
    inputs = {'X': [qkv], 'Z': [z], 'B': [b], 'A': [a], 'State': [state],
              'Tail': [tail], 'Rows': [rows]}
    inputs.update({k: [v] for k, v in weights.items()})
    attrs = {'layer': int(layer), 'epsilon': float(epsilon),
             'key_heads': int(key_heads)}
    if positions is not None:
        inputs.update({'Positions': [positions], 'Length': [length]})
        attrs['chunk'] = int(chunk)
    if allow_neg_eigval:
        attrs['allow_neg_eigval'] = True
    helper.append_op(type=op_type, inputs=inputs,
                     outputs={'Out': [out], 'StateOut': [state],
                              'TailOut': [tail]}, attrs=attrs)
    return out


def gdn_decode(qkv, z, b, a, state, tail, rows, layer, prefix, key_heads,
               kernel, epsilon=1e-6, allow_neg_eigval=False):
    """One step of a Gated DeltaNet layer for every slot's one row: ``qkv
    [S, 2 Hk dk + Hv dv]`` and ``z [S, Hv dv]`` (the parts of the mixer's
    wide input projection), ``b`` / ``a`` ``[S, Hv]`` (its narrow one: the
    write strength and the decay's input), ``state`` / ``tail`` the two
    pools, read and written in place at the rows ``rows [S, 1]`` names (0:
    none), ``layer`` the layer's ordinal in them (ops/gdn_ops.py). Returns
    the normed, gated ``[S, Hv dv]``, the output projection's input."""
    return _gdn('gdn_decode', qkv, z, b, a, state, tail, rows, layer, prefix,
                key_heads, kernel, epsilon,
                allow_neg_eigval=allow_neg_eigval)


def gdn_prefill(qkv, z, b, a, state, tail, rows, positions, length, layer,
                prefix, key_heads, kernel, chunk, epsilon=1e-6,
                allow_neg_eigval=False):
    """`gdn_decode` for one prompt suffix (``[1, T, ...]``) that starts at
    ``positions[0]``: from zeros there, else from the row as the chunk
    before left it, over the ``length`` real rows, in blocks of ``chunk``
    rows (the chunked delta rule, ops/gdn_ops.py). Returns ``[1, T, Hv
    dv]``."""
    return _gdn('gdn_prefill', qkv, z, b, a, state, tail, rows, layer,
                prefix, key_heads, kernel, epsilon, chunk=chunk,
                positions=positions, length=length,
                allow_neg_eigval=allow_neg_eigval)


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, data_layout='NCHW', name=None):
    helper = LayerHelper('group_norm', param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    c = input.shape[1]
    inputs = {'X': [input]}
    if helper.param_attr is not False:
        s = helper.create_parameter(attr=helper.param_attr, shape=[c],
                                    dtype=dtype,
                                    default_initializer=Constant(1.0))
        inputs['Scale'] = [s]
    if helper.bias_attr is not False:
        b = helper.create_parameter(attr=helper.bias_attr or ParamAttr(),
                                    shape=[c], dtype=dtype, is_bias=True)
        inputs['Bias'] = [b]
    mean = helper.create_variable_for_type_inference(
        dtype, shape=(input.shape[0], groups))
    var = helper.create_variable_for_type_inference(
        dtype, shape=(input.shape[0], groups))
    out = helper.create_variable_for_type_inference(dtype, shape=input.shape)
    helper.append_op(type='group_norm', inputs=inputs,
                     outputs={'Y': [out], 'Mean': [mean], 'Variance': [var]},
                     attrs={'epsilon': epsilon, 'groups': groups})
    return helper.append_activation(out)


def data_norm(input, act=None, epsilon=1e-5, param_attr=None,
              data_layout='NCHW', in_place=False, name=None,
              moving_mean_name=None, moving_variance_name=None,
              do_model_average_for_mean_and_var=False):
    helper = LayerHelper('data_norm', name=name)
    dtype = input.dtype
    c = input.shape[1]
    batch_size = helper.create_parameter(
        attr=ParamAttr(name=helper.name + '.batch_size'), shape=[c],
        dtype=dtype, default_initializer=Constant(1e4))
    batch_sum = helper.create_parameter(
        attr=ParamAttr(name=helper.name + '.batch_sum'), shape=[c],
        dtype=dtype, default_initializer=Constant(0.0))
    batch_square = helper.create_parameter(
        attr=ParamAttr(name=helper.name + '.batch_square_sum'), shape=[c],
        dtype=dtype, default_initializer=Constant(1e4))
    means = helper.create_variable_for_type_inference(dtype, shape=(c,))
    scales = helper.create_variable_for_type_inference(dtype, shape=(c,))
    out = helper.create_variable_for_type_inference(dtype, shape=input.shape)
    helper.append_op(
        type='data_norm',
        inputs={'X': [input], 'BatchSize': [batch_size],
                'BatchSum': [batch_sum], 'BatchSquareSum': [batch_square]},
        outputs={'Y': [out], 'Means': [means], 'Scales': [scales]},
        attrs={'epsilon': epsilon})
    return helper.append_activation(out)


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper('l2_normalize', name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype,
                                                    shape=x.shape)
    norm = helper.create_variable_for_type_inference(dtype=x.dtype,
                                                     shape=x.shape)
    helper.append_op(type='norm', inputs={'X': [x]},
                     outputs={'Out': [out], 'Norm': [norm]},
                     attrs={'axis': axis, 'epsilon': epsilon})
    return out


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper('lrn', name=name)
    mid = helper.create_variable_for_type_inference(dtype=input.dtype,
                                                    shape=input.shape)
    out = helper.create_variable_for_type_inference(dtype=input.dtype,
                                                    shape=input.shape)
    helper.append_op(type='lrn', inputs={'X': [input]},
                     outputs={'Out': [out], 'MidOut': [mid]},
                     attrs={'n': n, 'k': k, 'alpha': alpha, 'beta': beta})
    return out


# ---------------------------------------------------------------------------
# Shape / math wrappers
# ---------------------------------------------------------------------------

def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper('matmul', name=name)
    xs = list(x.shape)
    ys = list(y.shape)
    if transpose_x and len(xs) >= 2:
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if transpose_y and len(ys) >= 2:
        ys[-1], ys[-2] = ys[-2], ys[-1]
    if len(xs) >= 2 and len(ys) >= 2:
        out_shape = xs[:-1] + [ys[-1]]
    else:
        out_shape = [1]
    out = helper.create_variable_for_type_inference(dtype=x.dtype,
                                                    shape=out_shape)
    helper.append_op(type='matmul', inputs={'X': [x], 'Y': [y]},
                     outputs={'Out': [out]},
                     attrs={'transpose_X': transpose_x,
                            'transpose_Y': transpose_y, 'alpha': alpha})
    return out


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper('mul', name=name)
    out_shape = tuple(x.shape[:x_num_col_dims]) + tuple(
        y.shape[y_num_col_dims:])
    out = helper.create_variable_for_type_inference(dtype=x.dtype,
                                                    shape=out_shape)
    helper.append_op(type='mul', inputs={'X': [x], 'Y': [y]},
                     outputs={'Out': [out]},
                     attrs={'x_num_col_dims': x_num_col_dims,
                            'y_num_col_dims': y_num_col_dims})
    return out


def topk(input, k, name=None):
    helper = LayerHelper('top_k', name=name)
    shape = tuple(input.shape[:-1]) + (k,)
    values = helper.create_variable_for_type_inference(dtype=input.dtype,
                                                       shape=shape)
    indices = helper.create_variable_for_type_inference(dtype='int64',
                                                        shape=shape)
    helper.append_op(type='top_k', inputs={'X': [input]},
                     outputs={'Out': [values], 'Indices': [indices]},
                     attrs={'k': k})
    values.stop_gradient = True
    indices.stop_gradient = True
    return values, indices


def _infer_reshape_shape(x, shape):
    shape = list(shape)
    for i, s in enumerate(shape):
        if s == 0:
            shape[i] = x.shape[i]
    if -1 in shape and all(d is not None and d >= 0 for d in x.shape):
        known = _prod([s for s in shape if s != -1])
        shape[shape.index(-1)] = _prod(x.shape) // max(known, 1)
    return shape


def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper('reshape2', name=name)
    # unknown input shape (shape=None vars): a fully-literal target IS
    # the out shape; targets with 0/-1 stay unshaped and bind at lowering
    if x.shape is not None:
        out_shape = _infer_reshape_shape(x, shape)
    elif all(isinstance(d, int) and d > 0 for d in shape):
        out_shape = tuple(shape)
    else:
        out_shape = None
    out = helper.create_variable_for_type_inference(dtype=x.dtype,
                                                    shape=out_shape)
    xshape = helper.create_variable_for_type_inference(
        dtype=x.dtype,
        shape=((0,) + tuple(x.shape)) if x.shape is not None else None)
    helper.append_op(type='reshape2', inputs={'X': [x]},
                     outputs={'Out': [out], 'XShape': [xshape]},
                     attrs={'shape': list(shape)})
    return helper.append_activation(out) if act else out


def squeeze(input, axes, name=None):
    helper = LayerHelper('squeeze2', name=name)
    shape = [s for i, s in enumerate(input.shape)
             if not (i in axes and s == 1)]
    out = helper.create_variable_for_type_inference(dtype=input.dtype,
                                                    shape=shape)
    xshape = helper.create_variable_for_type_inference(
        dtype=input.dtype, shape=(0,) + tuple(input.shape))
    helper.append_op(type='squeeze2', inputs={'X': [input]},
                     outputs={'Out': [out], 'XShape': [xshape]},
                     attrs={'axes': list(axes)})
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper('unsqueeze2', name=name)
    shape = list(input.shape)
    for a in sorted(axes):
        shape.insert(a, 1)
    out = helper.create_variable_for_type_inference(dtype=input.dtype,
                                                    shape=shape)
    xshape = helper.create_variable_for_type_inference(
        dtype=input.dtype, shape=(0,) + tuple(input.shape))
    helper.append_op(type='unsqueeze2', inputs={'X': [input]},
                     outputs={'Out': [out], 'XShape': [xshape]},
                     attrs={'axes': list(axes)})
    return out


def flatten(x, axis=1, name=None):
    helper = LayerHelper('flatten2', name=name)
    lead = _prod(x.shape[:axis]) if axis > 0 else 1
    tail = _prod(x.shape[axis:])
    out = helper.create_variable_for_type_inference(
        dtype=x.dtype, shape=(lead, tail))
    xshape = helper.create_variable_for_type_inference(
        dtype=x.dtype, shape=(0,) + tuple(x.shape))
    helper.append_op(type='flatten2', inputs={'X': [x]},
                     outputs={'Out': [out], 'XShape': [xshape]},
                     attrs={'axis': axis})
    return out


def transpose(x, perm, name=None):
    helper = LayerHelper('transpose2', name=name)
    shape = [x.shape[p] for p in perm]
    out = helper.create_variable_for_type_inference(dtype=x.dtype,
                                                    shape=shape)
    xshape = helper.create_variable_for_type_inference(
        dtype=x.dtype, shape=(0,) + tuple(x.shape))
    helper.append_op(type='transpose2', inputs={'X': [x]},
                     outputs={'Out': [out], 'XShape': [xshape]},
                     attrs={'axis': list(perm)})
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper('split', name=name)
    axis = dim % len(input.shape)
    if isinstance(num_or_sections, int):
        num = num_or_sections
        sections = []
        sizes = [input.shape[axis] // num] * num
    else:
        sections = list(num_or_sections)
        num = 0
        sizes = sections
    outs = []
    for s in sizes:
        shape = list(input.shape)
        shape[axis] = s
        outs.append(helper.create_variable_for_type_inference(
            dtype=input.dtype, shape=shape))
    helper.append_op(type='split', inputs={'X': [input]},
                     outputs={'Out': outs},
                     attrs={'axis': axis, 'num': num, 'sections': sections})
    return outs


def _reduce(op_type, input, dim, keep_dim, name):
    helper = LayerHelper(op_type, name=name)
    if dim is None:
        reduce_all = True
        dims = [0]
        shape = [1]
    else:
        reduce_all = False
        dims = dim if isinstance(dim, (list, tuple)) else [dim]
        dims = [d % len(input.shape) for d in dims]
        if keep_dim:
            shape = [1 if i in dims else s
                     for i, s in enumerate(input.shape)]
        else:
            shape = [s for i, s in enumerate(input.shape) if i not in dims]
            shape = shape or [1]
    out = helper.create_variable_for_type_inference(dtype=input.dtype,
                                                    shape=shape)
    helper.append_op(type=op_type, inputs={'X': [input]},
                     outputs={'Out': [out]},
                     attrs={'dim': dims, 'keep_dim': keep_dim,
                            'reduce_all': reduce_all})
    return out


def reduce_sum(input, dim=None, keep_dim=False, name=None):
    return _reduce('reduce_sum', input, dim, keep_dim, name)


def reduce_mean(input, dim=None, keep_dim=False, name=None):
    return _reduce('reduce_mean', input, dim, keep_dim, name)


def reduce_max(input, dim=None, keep_dim=False, name=None):
    return _reduce('reduce_max', input, dim, keep_dim, name)


def reduce_min(input, dim=None, keep_dim=False, name=None):
    return _reduce('reduce_min', input, dim, keep_dim, name)


def reduce_prod(input, dim=None, keep_dim=False, name=None):
    return _reduce('reduce_prod', input, dim, keep_dim, name)


def mean(x, name=None):
    helper = LayerHelper('mean', name=name)
    return _simple(helper, 'mean', x, out_shape=(1,))


def sum(x):
    helper = LayerHelper('sum')
    xs = x if isinstance(x, (list, tuple)) else [x]
    out = helper.create_variable_for_type_inference(dtype=xs[0].dtype,
                                                    shape=xs[0].shape)
    helper.append_op(type='sum', inputs={'X': xs}, outputs={'Out': [out]})
    return out


sums_ = sum


def _elementwise(op_type, x, y, axis=-1, act=None, name=None):
    helper = LayerHelper(op_type, name=name, act=act)
    if x.shape is None or y.shape is None:
        # the unknown side may be the LARGER broadcast operand: any static
        # shape stamped here could be wrong, so stay unshaped
        shape = None
    else:
        shape = x.shape if len(x.shape) >= len(y.shape) else y.shape
    out = helper.create_variable_for_type_inference(dtype=x.dtype,
                                                    shape=shape)
    helper.append_op(type=op_type, inputs={'X': [x], 'Y': [y]},
                     outputs={'Out': [out]}, attrs={'axis': axis})
    return helper.append_activation(out)


def elementwise_add(x, y, axis=-1, act=None, name=None):
    return _elementwise('elementwise_add', x, y, axis, act, name)


def elementwise_sub(x, y, axis=-1, act=None, name=None):
    return _elementwise('elementwise_sub', x, y, axis, act, name)


def elementwise_mul(x, y, axis=-1, act=None, name=None):
    return _elementwise('elementwise_mul', x, y, axis, act, name)


def elementwise_div(x, y, axis=-1, act=None, name=None):
    return _elementwise('elementwise_div', x, y, axis, act, name)


def elementwise_max(x, y, axis=-1, act=None, name=None):
    return _elementwise('elementwise_max', x, y, axis, act, name)


def elementwise_min(x, y, axis=-1, act=None, name=None):
    return _elementwise('elementwise_min', x, y, axis, act, name)


def elementwise_pow(x, y, axis=-1, act=None, name=None):
    return _elementwise('elementwise_pow', x, y, axis, act, name)


def elementwise_mod(x, y, axis=-1, act=None, name=None):
    return _elementwise('elementwise_mod', x, y, axis, act, name)


def elementwise_floordiv(x, y, axis=-1, act=None, name=None):
    return _elementwise('elementwise_floordiv', x, y, axis, act, name)


def _logical(op_type, x, y, out, name):
    helper = LayerHelper(op_type, name=name)
    if out is None:
        out = helper.create_variable_for_type_inference(dtype='bool',
                                                        shape=x.shape)
    inputs = {'X': [x]} if y is None else {'X': [x], 'Y': [y]}
    helper.append_op(type=op_type, inputs=inputs, outputs={'Out': [out]})
    return out


def logical_and(x, y, out=None, name=None):
    return _logical('logical_and', x, y, out, name)


def logical_or(x, y, out=None, name=None):
    return _logical('logical_or', x, y, out, name)


def logical_xor(x, y, out=None, name=None):
    return _logical('logical_xor', x, y, out, name)


def logical_not(x, out=None, name=None):
    return _logical('logical_not', x, None, out, name)


def clip(x, min, max, name=None):
    helper = LayerHelper('clip', name=name)
    return _simple(helper, 'clip', x, attrs={'min': min, 'max': max})


def clip_by_norm(x, max_norm, name=None):
    helper = LayerHelper('clip_by_norm', name=name)
    return _simple(helper, 'clip_by_norm', x, attrs={'max_norm': max_norm})


def one_hot(input, depth):
    helper = LayerHelper('one_hot')
    shape = (tuple(input.shape[:-1]) if input.shape[-1] == 1
             else tuple(input.shape)) + (depth,)
    return _simple(helper, 'one_hot', input, out_shape=shape,
                   out_dtype='float32', attrs={'depth': depth})


def pad(x, paddings, pad_value=0.0, name=None):
    helper = LayerHelper('pad', name=name)
    shape = [s + paddings[2 * i] + paddings[2 * i + 1]
             for i, s in enumerate(x.shape)]
    return _simple(helper, 'pad', x, out_shape=shape,
                   attrs={'paddings': list(paddings),
                          'pad_value': pad_value})


def pad2d(input, paddings=[0, 0, 0, 0], mode='constant', pad_value=0.0,
          data_format="NCHW", name=None):
    helper = LayerHelper('pad2d', name=name)
    n, c, h, w = input.shape
    shape = (n, c, h + paddings[0] + paddings[1],
             w + paddings[2] + paddings[3])
    return _simple(helper, 'pad2d', input, out_shape=shape,
                   attrs={'paddings': list(paddings), 'mode': mode,
                          'pad_value': pad_value,
                          'data_format': data_format})


def pad_constant_like(x, y, pad_value=0.0, name=None):
    helper = LayerHelper('pad_constant_like', name=name)
    return _simple(helper, 'pad_constant_like', y, out_shape=x.shape,
                   inputs={'X': [x], 'Y': [y]},
                   attrs={'pad_value': pad_value})


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype='float32',
                 name=None):
    helper = LayerHelper('label_smooth', name=name)
    inputs = {'X': [label]}
    if prior_dist is not None:
        inputs['PriorDist'] = [prior_dist]
    return _simple(helper, 'label_smooth', label, inputs=inputs,
                   attrs={'epsilon': float(epsilon)})


def stack(x, axis=0):
    helper = LayerHelper('stack')
    xs = x if isinstance(x, (list, tuple)) else [x]
    shape = list(xs[0].shape)
    shape.insert(axis % (len(shape) + 1), len(xs))
    out = helper.create_variable_for_type_inference(dtype=xs[0].dtype,
                                                    shape=shape)
    helper.append_op(type='stack', inputs={'X': xs}, outputs={'Y': [out]},
                     attrs={'axis': axis})
    return out


def unstack(x, axis=0, num=None):
    helper = LayerHelper('unstack')
    if num is None:
        num = x.shape[axis]
    shape = [s for i, s in enumerate(x.shape) if i != axis % len(x.shape)]
    outs = [helper.create_variable_for_type_inference(dtype=x.dtype,
                                                      shape=shape)
            for _ in range(num)]
    helper.append_op(type='unstack', inputs={'X': [x]}, outputs={'Y': outs},
                     attrs={'axis': axis, 'num': num})
    return outs


def expand(x, expand_times, name=None):
    helper = LayerHelper('expand', name=name)
    shape = [(s * t if s is not None and s >= 0 else -1)
             for s, t in zip(x.shape, expand_times)]
    return _simple(helper, 'expand', x, out_shape=shape,
                   attrs={'expand_times': list(expand_times)})


def gather(input, index):
    helper = LayerHelper('gather')
    shape = (index.shape[0],) + tuple(input.shape[1:])
    return _simple(helper, 'gather', input, out_shape=shape,
                   inputs={'X': [input], 'Index': [index]})


def gather_nd(input, index, name=None):
    helper = LayerHelper('gather_nd', name=name)
    shape = tuple(index.shape[:-1]) + tuple(input.shape[index.shape[-1]:])
    return _simple(helper, 'gather_nd', input, out_shape=shape,
                   inputs={'X': [input], 'Index': [index]})


def scatter(input, index, updates, name=None, overwrite=True):
    helper = LayerHelper('scatter', name=name)
    return _simple(helper, 'scatter', input,
                   inputs={'X': [input], 'Ids': [index],
                           'Updates': [updates]},
                   attrs={'overwrite': overwrite})


def slice(input, axes, starts, ends):
    helper = LayerHelper('slice')
    shape = list(input.shape)
    for a, s, e in zip(axes, starts, ends):
        dim = input.shape[a]
        if dim is None or dim < 0:
            shape[a] = -1
            continue
        s2 = max(s + dim, 0) if s < 0 else min(s, dim)
        e2 = max(e + dim, 0) if e < 0 else min(e, dim)
        shape[a] = max(e2 - s2, 0)
    return _simple(helper, 'slice', input, out_shape=shape,
                   inputs={'Input': [input]},
                   attrs={'axes': list(axes), 'starts': list(starts),
                          'ends': list(ends)})


def crop(x, shape=None, offsets=None, name=None):
    helper = LayerHelper('crop', name=name)
    if isinstance(shape, Variable):
        shape = shape.shape
    offsets = offsets or [0] * len(x.shape)
    return _simple(helper, 'crop', x, out_shape=shape,
                   inputs={'X': [x]},
                   attrs={'shape': list(shape), 'offsets': list(offsets)})


def shape(input):
    helper = LayerHelper('shape')
    out = helper.create_variable_for_type_inference(
        'int32', shape=(len(input.shape),))
    helper.append_op(type='shape', inputs={'Input': [input]},
                     outputs={'Out': [out]})
    return out


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None,
          name=None):
    helper = LayerHelper('scale', name=name, act=act)
    out = _simple(helper, 'scale', x,
                  attrs={'scale': float(scale), 'bias': float(bias),
                         'bias_after_scale': bias_after_scale})
    return helper.append_activation(out)


def increment(x, value=1.0, in_place=True):
    helper = LayerHelper('increment')
    if in_place:
        out = x
    else:
        out = helper.create_variable_for_type_inference(dtype=x.dtype,
                                                        shape=x.shape)
    helper.append_op(type='increment', inputs={'X': [x]},
                     outputs={'Out': [out]}, attrs={'step': float(value)})
    return out


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    helper = LayerHelper('global_step_counter')
    counter_name = counter_name or '@STEP_COUNTER@'
    gb = helper.main_program.global_block()
    is_new_var = not gb.has_var(counter_name)
    counter = helper.create_or_get_global_variable(
        name=counter_name, dtype='int64', shape=(1,))
    if is_new_var:
        # only the creator appends the increment — a shared counter must
        # advance once per step (reference nn.py:5902 is_new_var guard)
        helper.set_variable_initializer(
            counter, initializer=__import__(
                'paddle_tpu.initializer', fromlist=['Constant']
            ).Constant(begin - 1))
        helper.append_op(type='increment', inputs={'X': [counter]},
                         outputs={'Out': [counter]},
                         attrs={'step': float(step)})
    counter.stop_gradient = True
    return counter


# ---------------------------------------------------------------------------
# Activations needing extra inputs / misc
# ---------------------------------------------------------------------------

def relu(x, name=None):
    helper = LayerHelper('relu', name=name)
    return _simple(helper, 'relu', x)


def sigmoid(x, name=None):
    helper = LayerHelper('sigmoid', name=name)
    return _simple(helper, 'sigmoid', x)


def log(x, name=None):
    helper = LayerHelper('log', name=name)
    return _simple(helper, 'log', x)


def prelu(x, mode, param_attr=None, name=None):
    helper = LayerHelper('prelu', param_attr=param_attr, name=name)
    if mode == 'all':
        alpha_shape = [1]
    elif mode == 'channel':
        alpha_shape = [1, x.shape[1], 1, 1]
    else:
        alpha_shape = [1] + list(x.shape[1:])
    alpha = helper.create_parameter(
        attr=helper.param_attr, shape=alpha_shape, dtype='float32',
        is_bias=False, default_initializer=Constant(0.25))
    out = helper.create_variable_for_type_inference(dtype=x.dtype,
                                                    shape=x.shape)
    helper.append_op(type='prelu', inputs={'X': [x], 'Alpha': [alpha]},
                     outputs={'Out': [out]}, attrs={'mode': mode})
    return out


def brelu(x, t_min=0.0, t_max=24.0, name=None):
    helper = LayerHelper('brelu', name=name)
    return _simple(helper, 'brelu', x,
                   attrs={'t_min': t_min, 't_max': t_max})


def leaky_relu(x, alpha=0.02, name=None):
    helper = LayerHelper('leaky_relu', name=name)
    return _simple(helper, 'leaky_relu', x, attrs={'alpha': alpha})


def soft_relu(x, threshold=40.0, name=None):
    helper = LayerHelper('soft_relu', name=name)
    return _simple(helper, 'soft_relu', x, attrs={'threshold': threshold})


def selu(x, scale=None, alpha=None, name=None):
    helper = LayerHelper('selu', name=name)
    attrs = {}
    if scale is not None:
        attrs['scale'] = scale
    if alpha is not None:
        attrs['alpha'] = alpha
    return _simple(helper, 'selu', x, attrs=attrs)


def swish(x, beta=1.0, name=None):
    helper = LayerHelper('swish', name=name)
    return _simple(helper, 'swish', x, attrs={'beta': beta})


def softmax(input, use_cudnn=True, name=None):
    helper = LayerHelper('softmax', name=name)
    return _simple(helper, 'softmax', input)


def dropout(x, dropout_prob, is_test=False, seed=None, name=None,
            dropout_implementation="downgrade_in_infer"):
    helper = LayerHelper('dropout', name=name)
    out = helper.create_variable_for_type_inference(dtype=x.dtype,
                                                    shape=x.shape)
    mask = helper.create_variable_for_type_inference(
        dtype=x.dtype, shape=x.shape, stop_gradient=True)
    helper.append_op(
        type='dropout', inputs={'X': [x]},
        outputs={'Out': [out], 'Mask': [mask]},
        attrs={'dropout_prob': dropout_prob, 'is_test': is_test,
               'seed': seed if seed is not None else 0,
               'dropout_implementation': dropout_implementation})
    return out


def cos_sim(X, Y):
    helper = LayerHelper('cos_sim')
    out = helper.create_variable_for_type_inference(
        dtype=X.dtype, shape=(X.shape[0], 1))
    xnorm = helper.create_variable_for_type_inference(
        dtype=X.dtype, shape=(X.shape[0], 1))
    ynorm = helper.create_variable_for_type_inference(
        dtype=X.dtype, shape=(X.shape[0], 1))
    helper.append_op(type='cos_sim', inputs={'X': [X], 'Y': [Y]},
                     outputs={'Out': [out], 'XNorm': [xnorm],
                              'YNorm': [ynorm]})
    return out


def sign(x):
    helper = LayerHelper('sign')
    return _simple(helper, 'sign', x)


def where(condition, x, y):
    helper = LayerHelper('where')
    return _simple(helper, 'where', x,
                   inputs={'Condition': [condition], 'X': [x], 'Y': [y]})


def multiplex(inputs, index):
    helper = LayerHelper('multiplex')
    out = helper.create_variable_for_type_inference(
        dtype=inputs[0].dtype, shape=inputs[0].shape)
    helper.append_op(type='multiplex',
                     inputs={'X': inputs, 'Ids': [index]},
                     outputs={'Out': [out]})
    return out


def maxout(x, groups, name=None):
    helper = LayerHelper('maxout', name=name)
    n, c, h, w = x.shape
    return _simple(helper, 'maxout', x, out_shape=(n, c // groups, h, w),
                   attrs={'groups': groups})


def space_to_depth(x, blocksize, name=None):
    helper = LayerHelper('space_to_depth', name=name)
    n, c, h, w = x.shape
    return _simple(helper, 'space_to_depth', x,
                   out_shape=(n, c * blocksize * blocksize,
                              h // blocksize, w // blocksize),
                   attrs={'blocksize': blocksize})


def affine_channel(x, scale=None, bias=None, data_layout='NCHW', name=None):
    helper = LayerHelper('affine_channel', name=name)
    return _simple(helper, 'affine_channel', x,
                   inputs={'X': [x], 'Scale': [scale], 'Bias': [bias]},
                   attrs={'data_layout': data_layout})


def shuffle_channel(x, group, name=None):
    helper = LayerHelper('shuffle_channel', name=name)
    return _simple(helper, 'shuffle_channel', x, attrs={'group': group})


def bilinear_tensor_product(x, y, size, act=None, name=None,
                            param_attr=None, bias_attr=None):
    helper = LayerHelper('bilinear_tensor_product', name=name,
                         param_attr=param_attr, bias_attr=bias_attr, act=act)
    dtype = x.dtype
    w = helper.create_parameter(
        attr=helper.param_attr, shape=[size, x.shape[1], y.shape[1]],
        dtype=dtype)
    out = helper.create_variable_for_type_inference(
        dtype=dtype, shape=(x.shape[0], size))
    inputs = {'X': [x], 'Y': [y], 'Weight': [w]}
    if helper.bias_attr:
        bias = helper.create_parameter(
            attr=helper.bias_attr, shape=[1, size], dtype=dtype,
            is_bias=True)
        inputs['Bias'] = [bias]
    helper.append_op(type='bilinear_tensor_product', inputs=inputs,
                     outputs={'Out': [out]})
    return helper.append_activation(out)


def add_position_encoding(input, alpha, beta, name=None):
    helper = LayerHelper('add_position_encoding', name=name)
    return _simple(helper, 'add_position_encoding', input,
                   attrs={'alpha': alpha, 'beta': beta})


def image_resize(input, out_shape=None, scale=None, name=None,
                 resample='BILINEAR', actual_shape=None, align_corners=True,
                 align_mode=1):
    op_type = 'bilinear_interp' if resample == 'BILINEAR' else \
        'nearest_interp'
    helper = LayerHelper(op_type, name=name)
    n, c, h, w = input.shape
    if out_shape is not None:
        oh, ow = out_shape
    else:
        oh, ow = int(h * scale), int(w * scale)
    return _simple(helper, op_type, input, out_shape=(n, c, oh, ow),
                   inputs={'X': [input]},
                   attrs={'out_h': oh, 'out_w': ow,
                          'align_corners': align_corners,
                          'align_mode': align_mode})


def resize_bilinear(input, out_shape=None, scale=None, name=None,
                    actual_shape=None, align_corners=True, align_mode=1):
    return image_resize(input, out_shape, scale, name, 'BILINEAR',
                        actual_shape, align_corners, align_mode)


def resize_nearest(input, out_shape=None, scale=None, name=None,
                   actual_shape=None, align_corners=True):
    return image_resize(input, out_shape, scale, name, 'NEAREST',
                        actual_shape, align_corners)


def nce(input, label, num_total_classes, sample_weight=None,
        param_attr=None, bias_attr=None, num_neg_samples=None, name=None,
        sampler="uniform", custom_dist=None, seed=0, is_sparse=False):
    helper = LayerHelper('nce', param_attr=param_attr, bias_attr=bias_attr,
                         name=name)
    dim = input.shape[1]
    num_neg = num_neg_samples or 10
    w = helper.create_parameter(attr=helper.param_attr,
                                shape=[num_total_classes, dim],
                                dtype=input.dtype)
    inputs = {'Input': [input], 'Label': [label], 'Weight': [w]}
    if helper.bias_attr is not False:
        b = helper.create_parameter(attr=helper.bias_attr,
                                    shape=[num_total_classes, 1],
                                    dtype=input.dtype, is_bias=True)
        inputs['Bias'] = [b]
    cost = helper.create_variable_for_type_inference(
        dtype=input.dtype, shape=(input.shape[0], 1))
    sample_logits = helper.create_variable_for_type_inference(
        dtype=input.dtype, shape=(input.shape[0], num_neg + 1))
    sample_labels = helper.create_variable_for_type_inference(
        dtype='int64', shape=(input.shape[0], num_neg + 1))
    helper.append_op(
        type='nce', inputs=inputs,
        outputs={'Cost': [cost], 'SampleLogits': [sample_logits],
                 'SampleLabels': [sample_labels]},
        attrs={'num_total_classes': num_total_classes,
               'num_neg_samples': num_neg, 'seed': seed,
               'sampler': sampler})
    return cost


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None,
             name=None, path_table=None, path_code=None, is_custom=False,
             is_sparse=False):
    helper = LayerHelper('hierarchical_sigmoid', param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    dim = input.shape[1]
    w = helper.create_parameter(attr=helper.param_attr,
                                shape=[num_classes - 1, dim],
                                dtype=input.dtype)
    inputs = {'X': [input], 'Label': [label], 'W': [w]}
    if helper.bias_attr is not False:
        b = helper.create_parameter(attr=helper.bias_attr,
                                    shape=[1, num_classes - 1],
                                    dtype=input.dtype, is_bias=True)
        inputs['Bias'] = [b]
    import math
    code_len = int(math.ceil(math.log(num_classes, 2)))
    out = helper.create_variable_for_type_inference(
        dtype=input.dtype, shape=(input.shape[0], 1))
    pre_out = helper.create_variable_for_type_inference(
        dtype=input.dtype, shape=(input.shape[0], code_len))
    helper.append_op(type='hierarchical_sigmoid', inputs=inputs,
                     outputs={'Out': [out], 'PreOut': [pre_out]},
                     attrs={'num_classes': num_classes})
    return out


def im2sequence(input, filter_size=1, stride=1, padding=0, input_image_size=None,
                out_stride=1, name=None):
    helper = LayerHelper('im2sequence', name=name)
    fsize = _pair(filter_size)
    stride_ = _pair(stride)
    pads = padding if isinstance(padding, (list, tuple)) else [padding] * 4
    if len(pads) == 2:
        pads = [pads[0], pads[1], pads[0], pads[1]]
    n, c, h, w = input.shape
    oh = (h + pads[0] + pads[2] - fsize[0]) // stride_[0] + 1
    ow = (w + pads[1] + pads[3] - fsize[1]) // stride_[1] + 1
    out = helper.create_variable_for_type_inference(
        dtype=input.dtype, shape=(n * oh * ow, c * fsize[0] * fsize[1]))
    helper.append_op(type='im2sequence', inputs={'X': [input]},
                     outputs={'Out': [out]},
                     attrs={'kernels': fsize, 'strides': stride_,
                            'paddings': pads})
    return out


def uniform_random_batch_size_like(input, shape, dtype='float32',
                                   input_dim_idx=0, output_dim_idx=0,
                                   min=-1.0, max=1.0, seed=0):
    helper = LayerHelper('uniform_random_batch_size_like')
    out_shape = list(shape)
    out_shape[output_dim_idx] = input.shape[input_dim_idx]
    out = helper.create_variable_for_type_inference(dtype, shape=out_shape)
    helper.append_op(type='uniform_random_batch_size_like',
                     inputs={'Input': [input]}, outputs={'Out': [out]},
                     attrs={'shape': list(shape), 'dtype': out.dtype,
                            'min': min, 'max': max, 'seed': seed,
                            'input_dim_idx': input_dim_idx,
                            'output_dim_idx': output_dim_idx})
    return out


def gaussian_random(shape, mean=0.0, std=1.0, seed=0, dtype='float32'):
    helper = LayerHelper('gaussian_random')
    out = helper.create_variable_for_type_inference(dtype, shape=shape)
    helper.append_op(type='gaussian_random', outputs={'Out': [out]},
                     attrs={'shape': list(shape), 'mean': mean, 'std': std,
                            'seed': seed, 'dtype': out.dtype})
    return out


def gaussian_random_batch_size_like(input, shape, input_dim_idx=0,
                                    output_dim_idx=0, mean=0.0, std=1.0,
                                    seed=0, dtype='float32'):
    helper = LayerHelper('gaussian_random')
    out_shape = list(shape)
    out_shape[output_dim_idx] = input.shape[input_dim_idx]
    out = helper.create_variable_for_type_inference(dtype, shape=out_shape)
    helper.append_op(type='gaussian_random', outputs={'Out': [out]},
                     attrs={'shape': out_shape, 'mean': mean, 'std': std,
                            'seed': seed, 'dtype': out.dtype})
    return out


def sampling_id(x, min=0.0, max=1.0, seed=0, dtype='float32'):
    helper = LayerHelper('sampling_id')
    out = helper.create_variable_for_type_inference('int64',
                                                    shape=(x.shape[0],))
    helper.append_op(type='sampling_id', inputs={'X': [x]},
                     outputs={'Out': [out]}, attrs={'seed': seed})
    return out


def random_crop(x, shape, seed=None):
    helper = LayerHelper('random_crop')
    out_shape = list(x.shape[:len(x.shape) - len(shape)]) + list(shape)
    out = helper.create_variable_for_type_inference(dtype=x.dtype,
                                                    shape=out_shape)
    helper.append_op(type='random_crop', inputs={'X': [x]},
                     outputs={'Out': [out]},
                     attrs={'shape': list(shape),
                            'seed': seed if seed is not None else 0})
    return out


def mean_iou(input, label, num_classes):
    helper = LayerHelper('mean_iou')
    miou = helper.create_variable_for_type_inference('float32', shape=(1,))
    wrong = helper.create_variable_for_type_inference('int32',
                                                      shape=(num_classes,))
    correct = helper.create_variable_for_type_inference('int32',
                                                        shape=(num_classes,))
    helper.append_op(type='mean_iou',
                     inputs={'Predictions': [input], 'Labels': [label]},
                     outputs={'OutMeanIou': [miou], 'OutWrong': [wrong],
                              'OutCorrect': [correct]},
                     attrs={'num_classes': num_classes})
    return miou, wrong, correct


def hash(input, hash_size, num_hash=1, name=None):
    helper = LayerHelper('hash', name=name)
    out = helper.create_variable_for_type_inference(
        'int64', shape=(input.shape[0], num_hash, 1))
    helper.append_op(type='hash', inputs={'X': [input]},
                     outputs={'Out': [out]},
                     attrs={'num_hash': num_hash, 'mod_by': hash_size})
    return out


def sharding_constraint(x, spec, name=None):
    """Pin x's sharding to a PartitionSpec-like tuple, e.g.
    ('data', None, 'model'). TPU-native activation-sharding primitive used
    for sequence/tensor parallelism (see parallel/api.py)."""
    helper = LayerHelper('sharding_constraint', name=name)
    return _simple(helper, 'sharding_constraint', x,
                   attrs={'spec': list(spec)})


def grid_sampler(x, grid, name=None):
    """Bilinear sampling of x at grid coords in [-1, 1] (reference
    operators/grid_sampler_op.cc)."""
    helper = LayerHelper('grid_sampler', name=name)
    # output spatial dims follow the grid, not the input
    gshape = grid.shape or (None, -1, -1, 2)
    oshape = None
    if x.shape:
        oshape = (x.shape[0], x.shape[1], gshape[1], gshape[2])
    out = helper.create_variable_for_type_inference(x.dtype, shape=oshape)
    helper.append_op(type='grid_sampler', inputs={'X': [x], 'Grid': [grid]},
                     outputs={'Output': [out]})
    return out


def affine_grid(theta, out_shape=None, name=None):
    """Affine sampling grid from Theta [N,2,3] (reference
    operators/affine_grid_op.cc). out_shape: list/tuple NCHW or a Variable
    fed with it (bound statically)."""
    helper = LayerHelper('affine_grid', name=name)
    from .. import framework as _fw
    inputs = {'Theta': [theta]}
    attrs = {}
    if isinstance(out_shape, _fw.Variable):
        inputs['OutputShape'] = [out_shape]
    else:
        attrs['output_shape'] = [int(v) for v in out_shape]
    h = attrs.get('output_shape', [0, 0, -1, -1])[2]
    w = attrs.get('output_shape', [0, 0, -1, -1])[3]
    out = helper.create_variable_for_type_inference(
        theta.dtype, shape=(theta.shape[0], h, w, 2))
    helper.append_op(type='affine_grid', inputs=inputs,
                     outputs={'Output': [out]}, attrs=attrs)
    return out


def roi_pool(input, rois, pooled_height=1, pooled_width=1,
             spatial_scale=1.0):
    """Max RoI pooling (reference operators/roi_pool_op.cc)."""
    helper = LayerHelper('roi_pool')
    c = input.shape[1] if input.shape else -1
    out = helper.create_variable_for_type_inference(
        input.dtype, shape=(-1, c, pooled_height, pooled_width))
    argmax = helper.create_variable_for_type_inference(
        'int64', shape=(-1, c, pooled_height, pooled_width))
    helper.append_op(type='roi_pool',
                     inputs={'X': [input], 'ROIs': [rois]},
                     outputs={'Out': [out], 'Argmax': [argmax]},
                     attrs={'pooled_height': pooled_height,
                            'pooled_width': pooled_width,
                            'spatial_scale': spatial_scale})
    return out


def roi_align(input, rois, pooled_height=1, pooled_width=1,
              spatial_scale=1.0, sampling_ratio=-1, name=None):
    """RoI align (reference operators/roi_align_op.cc). On TPU
    sampling_ratio must be > 0 (static sample grid)."""
    helper = LayerHelper('roi_align', name=name)
    c = input.shape[1] if input.shape else -1
    out = helper.create_variable_for_type_inference(
        input.dtype, shape=(-1, c, pooled_height, pooled_width))
    helper.append_op(type='roi_align',
                     inputs={'X': [input], 'ROIs': [rois]},
                     outputs={'Out': [out]},
                     attrs={'pooled_height': pooled_height,
                            'pooled_width': pooled_width,
                            'spatial_scale': spatial_scale,
                            'sampling_ratio': sampling_ratio})
    return out


def psroi_pool(input, rois, output_channels, spatial_scale, pooled_height,
               pooled_width, name=None):
    """Position-sensitive RoI pooling (reference operators/psroi_pool_op.cc)."""
    helper = LayerHelper('psroi_pool', name=name)
    out = helper.create_variable_for_type_inference(
        input.dtype, shape=(-1, output_channels, pooled_height,
                            pooled_width))
    helper.append_op(type='psroi_pool',
                     inputs={'X': [input], 'ROIs': [rois]},
                     outputs={'Out': [out]},
                     attrs={'output_channels': output_channels,
                            'spatial_scale': spatial_scale,
                            'pooled_height': pooled_height,
                            'pooled_width': pooled_width})
    return out


def linear_chain_crf(input, label, param_attr=None, name=None):
    """Linear-chain CRF negative log-likelihood (reference layers/nn.py
    linear_chain_crf / linear_chain_crf_op.cc). `input` is the ragged
    emission [total, n_tags] with LoD; creates the Transition parameter
    [n_tags + 2, n_tags] (rows: start, end, transition matrix). Returns
    the per-sequence cost [num_seqs, 1]; minimize its mean."""
    helper = LayerHelper('linear_chain_crf', param_attr=param_attr,
                         name=name)
    size = input.shape[-1]
    transition = helper.create_parameter(
        attr=helper.param_attr, shape=[size + 2, size], dtype=input.dtype)
    alpha = helper.create_variable_for_type_inference(dtype=input.dtype)
    emission_exps = helper.create_variable_for_type_inference(
        dtype=input.dtype)
    transition_exps = helper.create_variable_for_type_inference(
        dtype=input.dtype)
    log_likelihood = helper.create_variable_for_type_inference(
        dtype=input.dtype)
    helper.append_op(
        type='linear_chain_crf',
        inputs={'Emission': [input], 'Transition': [transition],
                'Label': [label]},
        outputs={'Alpha': [alpha], 'EmissionExps': [emission_exps],
                 'TransitionExps': [transition_exps],
                 'LogLikelihood': [log_likelihood]})
    return log_likelihood


def crf_decoding(input, param_attr, label=None, name=None):
    """Viterbi decode with a trained CRF Transition parameter (reference
    crf_decoding_op.cc). With `label`, returns the 0/1 correctness mask."""
    helper = LayerHelper('crf_decoding', param_attr=param_attr, name=name)
    transition = helper.get_parameter(helper.param_attr.name)
    viterbi_path = helper.create_variable_for_type_inference(dtype='int64')
    inputs = {'Emission': [input], 'Transition': [transition]}
    if label is not None:
        inputs['Label'] = [label]
    helper.append_op(type='crf_decoding', inputs=inputs,
                     outputs={'ViterbiPath': [viterbi_path]})
    return viterbi_path


def warpctc(input, label, blank=0, norm_by_times=False):
    """CTC loss on unnormalized ragged logits (reference warpctc_op.cc —
    softmax applied internally). Returns per-sequence loss [num_seqs, 1]."""
    helper = LayerHelper('warpctc')
    loss_out = helper.create_variable_for_type_inference(dtype=input.dtype)
    helper.append_op(
        type='warpctc', inputs={'Logits': [input], 'Label': [label]},
        outputs={'Loss': [loss_out]},
        attrs={'blank': blank, 'norm_by_times': norm_by_times})
    return loss_out


def ctc_greedy_decoder(input, blank, name=None):
    """Greedy CTC decode: per-step argmax then merge-repeats/strip-blanks
    (reference layers/nn.py ctc_greedy_decoder = top_k + ctc_align). Output
    keeps the input LoD; each sequence is left-justified with -1 padding
    (static-shape adaptation of ctc_align_op.cc's shrinking output)."""
    helper = LayerHelper('ctc_greedy_decoder', name=name)
    _, topk_indices = topk(input, k=1)
    out = helper.create_variable_for_type_inference(dtype='int64')
    helper.append_op(type='ctc_align', inputs={'Input': [topk_indices]},
                     outputs={'Output': [out]},
                     attrs={'blank': blank, 'merge_repeated': True})
    return out


def edit_distance(input, label, normalized=True, ignored_tokens=None):
    """Levenshtein distance between ragged hyp/ref id sequences (reference
    edit_distance_op.cc). Returns (distance [num_seqs, 1], seq_num)."""
    helper = LayerHelper('edit_distance')
    if ignored_tokens:
        erased_in = helper.create_variable_for_type_inference(
            dtype=input.dtype)
        helper.append_op(type='sequence_erase', inputs={'X': [input]},
                         outputs={'Out': [erased_in]},
                         attrs={'tokens': list(ignored_tokens)})
        input = erased_in
        erased_lab = helper.create_variable_for_type_inference(
            dtype=label.dtype)
        helper.append_op(type='sequence_erase', inputs={'X': [label]},
                         outputs={'Out': [erased_lab]},
                         attrs={'tokens': list(ignored_tokens)})
        label = erased_lab
    out = helper.create_variable_for_type_inference(dtype='float32')
    seq_num = helper.create_variable_for_type_inference(dtype='int64')
    helper.append_op(type='edit_distance',
                     inputs={'Hyps': [input], 'Refs': [label]},
                     outputs={'Out': [out], 'SequenceNum': [seq_num]},
                     attrs={'normalized': normalized})
    return out, seq_num


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """Host-callback op (reference layers/nn.py py_func / py_func_op.cc):
    runs `func` on host over the inputs' numpy values via jax.pure_callback.
    `out` vars must declare full static shapes. With `backward_func`, the
    gradient is a second host callback receiving (inputs..., out_grads...)
    and returning grads for each input."""
    from ..ops.misc_ops import register_py_func
    helper = LayerHelper('py_func')
    xs = x if isinstance(x, (list, tuple)) else [x]
    outs = out if isinstance(out, (list, tuple)) else [out]
    attrs = {'forward_callable_id': register_py_func(func)}
    if backward_func is not None:
        attrs['backward_callable_id'] = register_py_func(backward_func)
        if skip_vars_in_backward_input:
            skips = skip_vars_in_backward_input
            skips = skips if isinstance(skips, (list, tuple)) else [skips]
            attrs['backward_skip_inputs'] = [
                v.name if hasattr(v, 'name') else v for v in skips]
    helper.append_op(type='py_func', inputs={'X': list(xs)},
                     outputs={'Out': list(outs)}, attrs=attrs)
    return out


def unpool(input, indices, ksize, strides=None, paddings=None, name=None):
    """Max unpooling with the indices from max_pool2d_with_index
    (reference unpool_op.cc)."""
    helper = LayerHelper('unpool', name=name)
    strides = strides or [1, 1]
    paddings = paddings or [0, 0]
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(type='unpool',
                     inputs={'X': [input], 'Indices': [indices]},
                     outputs={'Out': [out]},
                     attrs={'ksize': list(ksize), 'strides': list(strides),
                            'paddings': list(paddings)})
    return out


def spp(input, pyramid_height, pool_type='max', name=None):
    """Spatial pyramid pooling (reference spp_op.cc)."""
    helper = LayerHelper('spp', name=name)
    c = input.shape[1] if input.shape else -1
    total = 0
    for l in range(pyramid_height):
        total += (2 ** l) ** 2
    out = helper.create_variable_for_type_inference(
        input.dtype, shape=(-1, c * total if c > 0 else -1))
    helper.append_op(type='spp', inputs={'X': [input]},
                     outputs={'Out': [out]},
                     attrs={'pyramid_height': pyramid_height,
                            'pooling_type': pool_type})
    return out


def adaptive_pool2d(input, pool_size, pool_type='max', require_index=False,
                    name=None):
    """reference layers/nn.py:2597 adaptive_pool2d: pool to a fixed output
    grid regardless of input size (pool2d with adaptive=True; the
    require_index variant routes through max_pool2d_with_index)."""
    if pool_type not in ('max', 'avg'):
        raise ValueError("'pool_type' must be 'max' or 'avg'")
    if require_index and pool_type != 'max':
        raise ValueError("require_index is only valid with max pooling")
    pool_size = list(_pair(pool_size))
    n, c = input.shape[0], input.shape[1]
    out_shape = (n, c, pool_size[0], pool_size[1])
    if require_index:
        helper = LayerHelper('max_pool2d_with_index', name=name)
        out = helper.create_variable_for_type_inference(
            input.dtype, shape=out_shape)
        mask = helper.create_variable_for_type_inference(
            'int32', shape=out_shape)
        helper.append_op(
            type='max_pool2d_with_index', inputs={'X': [input]},
            outputs={'Out': [out], 'Mask': [mask]},
            attrs={'ksize': pool_size, 'strides': [1, 1],
                   'paddings': [0, 0], 'adaptive': True})
        return out, mask
    helper = LayerHelper('pool2d', name=name)
    out = helper.create_variable_for_type_inference(
        input.dtype, shape=out_shape)
    helper.append_op(
        type='pool2d', inputs={'X': [input]}, outputs={'Out': [out]},
        attrs={'pooling_type': pool_type, 'ksize': pool_size,
               'strides': [1, 1], 'paddings': [0, 0], 'adaptive': True})
    return out


def adaptive_pool3d(input, pool_size, pool_type='max', require_index=False,
                    name=None):
    """reference layers/nn.py adaptive_pool3d (pool3d with adaptive=True)."""
    if pool_type not in ('max', 'avg'):
        raise ValueError("'pool_type' must be 'max' or 'avg'")
    if require_index and pool_type != 'max':
        raise ValueError("require_index is only valid with max pooling")
    pool_size = list(_pair(pool_size, 3))
    n, c = input.shape[0], input.shape[1]
    out_shape = (n, c) + tuple(pool_size)
    if require_index:
        helper = LayerHelper('max_pool3d_with_index', name=name)
        out = helper.create_variable_for_type_inference(
            input.dtype, shape=out_shape)
        mask = helper.create_variable_for_type_inference(
            'int32', shape=out_shape)
        helper.append_op(
            type='max_pool3d_with_index', inputs={'X': [input]},
            outputs={'Out': [out], 'Mask': [mask]},
            attrs={'ksize': pool_size, 'strides': [1, 1, 1],
                   'paddings': [0, 0, 0], 'adaptive': True})
        return out, mask
    helper = LayerHelper('pool3d', name=name)
    out = helper.create_variable_for_type_inference(
        input.dtype, shape=out_shape)
    helper.append_op(
        type='pool3d', inputs={'X': [input]}, outputs={'Out': [out]},
        attrs={'pooling_type': pool_type, 'ksize': pool_size,
               'strides': [1, 1, 1], 'paddings': [0, 0, 0],
               'adaptive': True})
    return out


def dice_loss(input, label, epsilon=1e-5):
    """reference layers/nn.py:6582 dice_loss: 1 - 2*intersection/total
    over one-hot labels, composed from existing ops like the reference."""
    label = one_hot(label, depth=input.shape[-1])
    reduce_dim = list(range(1, len(input.shape)))
    inse = reduce_sum(elementwise_mul(input, label), dim=reduce_dim)
    dice_denominator = elementwise_add(
        reduce_sum(input, dim=reduce_dim),
        reduce_sum(label, dim=reduce_dim))
    dice_score = scale(
        elementwise_div(
            inse, scale(dice_denominator, scale=1.0, bias=epsilon)),
        scale=-2.0, bias=1.0)
    return reduce_mean(dice_score)


def image_resize_short(input, out_short_len, resample='BILINEAR'):
    """reference layers/nn.py:7030 image_resize_short: resize keeping the
    aspect ratio so the SHORT side equals out_short_len."""
    in_shape = input.shape
    if len(in_shape) != 4:
        raise ValueError("The rank of input must be 4 (NCHW).")
    hw = list(in_shape[2:4])
    short_idx = hw.index(min(hw))
    long_idx = 1 - short_idx
    out_shape = list(hw)
    out_shape[short_idx] = out_short_len
    out_shape[long_idx] = int(
        float(out_shape[long_idx]) *
        (float(out_short_len) / float(hw[short_idx])) + 0.5)
    return image_resize(input=input, out_shape=out_shape, resample=resample)


def lstm(input, init_h, init_c, max_len, hidden_size, num_layers,
         dropout_prob=0.0, is_bidirec=False, is_test=False, name=None,
         default_initializer=None, seed=-1):
    """reference layers/nn.py:491 lstm — the cudnn_lstm-backed dense LSTM
    (gates [i,f,c,o], no peepholes). Weight blob layout is documented on
    the cudnn_lstm op (ops/rnn_ops.py): per layer/direction
    Wx|Wh|bx|bh."""
    helper = LayerHelper('cudnn_lstm', name=name)
    dtype = input.dtype
    input_size = input.shape[-1]
    dirs = 2 if is_bidirec else 1
    weight_size = 0
    for layer in range(num_layers):
        in_l = input_size if layer == 0 else hidden_size * dirs
        weight_size += dirs * (in_l * 4 * hidden_size
                               + hidden_size * 4 * hidden_size
                               + 8 * hidden_size)
    weight = helper.create_parameter(
        attr=helper.param_attr, shape=[weight_size], dtype=dtype,
        default_initializer=default_initializer)
    out = helper.create_variable_for_type_inference(
        dtype, shape=(input.shape[0], input.shape[1],
                      hidden_size * dirs))
    last_h = helper.create_variable_for_type_inference(
        dtype, shape=(num_layers * dirs, input.shape[1], hidden_size))
    last_c = helper.create_variable_for_type_inference(
        dtype, shape=(num_layers * dirs, input.shape[1], hidden_size))
    helper.append_op(
        type='cudnn_lstm',
        inputs={'Input': [input], 'InitH': [init_h], 'InitC': [init_c],
                'W': [weight]},
        outputs={'Out': [out], 'last_h': [last_h], 'last_c': [last_c]},
        attrs={'max_len': max_len, 'hidden_size': hidden_size,
               'num_layers': num_layers, 'is_bidirec': is_bidirec,
               'input_size': input_size, 'dropout_prob': dropout_prob,
               'is_test': is_test, 'seed': seed})
    return out, last_h, last_c


def lstm_unit(x_t, hidden_t_prev, cell_t_prev, forget_bias=0.0,
              param_attr=None, bias_attr=None, name=None):
    """reference layers/nn.py:4089 lstm_unit: fc([x_t, h_prev]) -> 4D
    gates -> lstm_unit op (gate order [i,f,o,j])."""
    from .tensor import concat
    helper = LayerHelper('lstm_unit', param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    size = cell_t_prev.shape[-1]
    concat_out = concat([x_t, hidden_t_prev], axis=1)
    fc_out = fc(input=concat_out, size=4 * size,
                param_attr=helper.param_attr, bias_attr=helper.bias_attr)
    h = helper.create_variable_for_type_inference(
        x_t.dtype, shape=cell_t_prev.shape)
    c = helper.create_variable_for_type_inference(
        x_t.dtype, shape=cell_t_prev.shape)
    helper.append_op(
        type='lstm_unit',
        inputs={'X': [fc_out], 'C_prev': [cell_t_prev]},
        outputs={'H': [h], 'C': [c]},
        attrs={'forget_bias': forget_bias})
    return h, c


def conv3d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=None,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None):
    """reference layers/nn.py:3477 conv3d_transpose (NCDHW)."""
    helper = LayerHelper('conv3d_transpose', param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    n, c, d_in, h, w_in = input.shape
    groups = groups or 1
    stride = _pair(stride, 3)
    padding = _pair(padding, 3)
    dilation = _pair(dilation, 3)
    if filter_size is None:
        if output_size is None:
            raise ValueError("output_size or filter_size required")
        output_size = _pair(output_size, 3)
        in_sp = [d_in, h, w_in]
        filter_size = [
            (output_size[i] - (in_sp[i] - 1) * stride[i] + 2 * padding[i]
             - 1) // dilation[i] + 1 for i in range(3)]
    else:
        filter_size = list(_pair(filter_size, 3))
    wvar = helper.create_parameter(
        attr=helper.param_attr,
        shape=[c, num_filters // groups] + filter_size, dtype=dtype)
    in_sp = [d_in, h, w_in]
    out_sp = [
        (in_sp[i] - 1) * stride[i] - 2 * padding[i] +
        dilation[i] * (filter_size[i] - 1) + 1 for i in range(3)]
    pre_bias = helper.create_variable_for_type_inference(
        dtype, shape=(n, num_filters) + tuple(out_sp))
    helper.append_op(
        type='conv3d_transpose',
        inputs={'Input': [input], 'Filter': [wvar]},
        outputs={'Output': [pre_bias]},
        attrs={'strides': list(stride), 'paddings': list(padding),
               'dilations': list(dilation), 'groups': groups})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def similarity_focus(input, axis, indexes, name=None):
    """reference layers/nn.py:9414 similarity_focus wrapper."""
    helper = LayerHelper('similarity_focus', name=name)
    if axis not in (1, 2, 3):
        raise ValueError("axis must be 1, 2 or 3")
    if not indexes:
        raise ValueError("indexes can not be empty")
    out = helper.create_variable_for_type_inference(
        input.dtype, shape=input.shape)
    helper.append_op(
        type='similarity_focus', inputs={'X': [input]},
        outputs={'Out': [out]},
        attrs={'axis': axis, 'indexes': list(indexes)})
    return out


def tree_conv(nodes_vector, edge_set, output_size, num_filters=1,
              max_depth=2, act='tanh', param_attr=None, bias_attr=None,
              name=None):
    """reference layers/nn.py:10307 tree_conv (TBCNN) wrapper."""
    helper = LayerHelper('tree_conv', param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = nodes_vector.dtype
    feature_size = nodes_vector.shape[2]
    wvar = helper.create_parameter(
        attr=helper.param_attr,
        shape=[feature_size, 3, output_size, num_filters], dtype=dtype)
    out = helper.create_variable_for_type_inference(
        dtype, shape=(nodes_vector.shape[0], nodes_vector.shape[1],
                      output_size, num_filters))
    helper.append_op(
        type='tree_conv',
        inputs={'NodesVector': [nodes_vector], 'EdgeSet': [edge_set],
                'Filter': [wvar]},
        outputs={'Out': [out]},
        attrs={'max_depth': max_depth})
    if helper.bias_attr:
        out = helper.append_bias_op(out, dim_start=3, dim_end=4)
    return helper.append_activation(out)


def merge_selected_rows(x, name=None):
    """reference layers/nn.py:9146 merge_selected_rows wrapper."""
    helper = LayerHelper('merge_selected_rows', name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    helper.append_op(type='merge_selected_rows', inputs={'X': [x]},
                     outputs={'Out': [out]})
    return out


def get_tensor_from_selected_rows(x, name=None):
    """reference layers/nn.py:9891 get_tensor_from_selected_rows wrapper."""
    helper = LayerHelper('get_tensor_from_selected_rows', name=name)
    out = helper.create_variable_for_type_inference(x.dtype, shape=x.shape)
    helper.append_op(type='get_tensor_from_selected_rows',
                     inputs={'X': [x]}, outputs={'Out': [out]})
    return out


def switch_moe(input, num_experts, d_ff, capacity_factor=1.25,
               param_attr=None, name=None):
    """Switch (top-1) Mixture-of-Experts FFN layer with expert parallelism
    (TPU-native extension; functional core parallel/moe.py). Returns
    (out, aux_loss): add `out` to the residual stream and `aux_loss`
    (scaled) to the training loss."""
    helper = LayerHelper('switch_moe', param_attr=param_attr, name=name)
    d = input.shape[-1]
    # five distinct parameters: a shared ParamAttr would collide on name
    # (create_parameter assigns attr.name in place); an explicit user name
    # is suffixed per parameter — on COPIES, never the caller's objects
    import copy as _copy
    attrs = [_copy.deepcopy(a) for a in helper.multiple_param_attr(5)]
    for i, a in enumerate(attrs):
        if isinstance(a, ParamAttr) and a.name:
            a.name = '%s.p%d' % (a.name, i)
    rw = helper.create_parameter(attr=attrs[0],
                                 shape=[d, num_experts], dtype=input.dtype)
    wi = helper.create_parameter(attr=attrs[1],
                                 shape=[num_experts, d, d_ff],
                                 dtype=input.dtype)
    bi = helper.create_parameter(attr=attrs[2],
                                 shape=[num_experts, d_ff],
                                 dtype=input.dtype, is_bias=True)
    wo = helper.create_parameter(attr=attrs[3],
                                 shape=[num_experts, d_ff, d],
                                 dtype=input.dtype)
    bo = helper.create_parameter(attr=attrs[4],
                                 shape=[num_experts, d],
                                 dtype=input.dtype, is_bias=True)
    out = helper.create_variable_for_type_inference(
        input.dtype, shape=input.shape)
    aux = helper.create_variable_for_type_inference(
        input.dtype, shape=(1,))
    helper.append_op(
        type='switch_moe',
        inputs={'X': [input], 'RouterW': [rw], 'ExpertWIn': [wi],
                'ExpertBIn': [bi], 'ExpertWOut': [wo],
                'ExpertBOut': [bo]},
        outputs={'Out': [out], 'AuxLoss': [aux]},
        attrs={'capacity_factor': capacity_factor})
    return out, aux


def flash_attention(q, k, v, scale=None, causal=True, name=None):
    """Fused multi-head attention layer over the blocked pallas kernel
    (ops/attention_ops.py): q/k/v [B, H, L, dh]. Under an SPMD mesh the
    kernel runs per shard (ring attention when the sequence axis is
    sharded). TPU-native extension exposed at the layers surface."""
    helper = LayerHelper('flash_attention', name=name)
    out = helper.create_variable_for_type_inference(q.dtype, shape=q.shape)
    # omitted scale attr = kernel default dh**-0.5; a present attr (even
    # 0.0) is taken literally
    attrs = {'causal': bool(causal)}
    if scale is not None:
        attrs['scale'] = float(scale)
    helper.append_op(
        type='flash_attention',
        inputs={'Q': [q], 'K': [k], 'V': [v]},
        outputs={'Out': [out]},
        attrs=attrs)
    return out
