"""Neural-network layers.

Reference parity: python/paddle/v2/fluid/layers/nn.py — same signatures, so
reference model scripts port by changing only the import.  Each layer
appends registry ops; the Executor fuses the whole block into one XLA
program (no per-layer kernel dispatch).
"""
from ..core.program import Variable
from ..initializer import ConstantInitializer, NormalInitializer
from .layer_helper import LayerHelper

__all__ = [
    'fc', 'embedding', 'conv2d', 'conv3d', 'pool2d', 'pool3d', 'batch_norm',
    'layer_norm', 'rms_norm', 'rotary_embedding', 'moe_ffn', 'dropout',
    'cross_entropy', 'square_error_cost',
    'accuracy', 'softmax_with_cross_entropy', 'fused_linear_softmax_ce',
    'conv2d_transpose',
    'reduce_sum', 'reduce_mean', 'reduce_max', 'reduce_min', 'reduce_prod',
    'split', 'matmul', 'topk', 'l2_normalize', 'one_hot', 'cos_sim', 'lrn',
    'warpctc', 'nce', 'bilinear_tensor_product', 'prelu', 'pad',
    'im2sequence', 'multiplex', 'row_conv', 'auc', 'roi_pool',
    'detection_output',
]


def fc(input,
       size,
       num_flatten_dims=1,
       param_attr=None,
       bias_attr=None,
       act=None,
       name=None,
       **kwargs):
    """Fully connected: parity with fluid.layers.fc (ref
    python/paddle/v2/fluid/layers/nn.py:fc; kernel operators/mul_op.cc).
    Runs as a single MXU matmul per input."""
    helper = LayerHelper('fc', **locals())
    dtype = helper.input_dtype()
    # fp32 master weights under bf16 activations: the op casts at use,
    # the optimizer updates full-precision params (mixed-precision recipe)
    p_dtype = 'float32' if dtype in ('bfloat16', 'float16') else dtype
    lod = max(v.lod_level for v in helper.multiple_input())
    mul_results = []
    flatten = num_flatten_dims
    for input_var, param_attr in helper.iter_inputs_and_params():
        input_shape = input_var.shape
        # Ragged inputs are padded [B, T, D] here (the reference sees the
        # flattened [sum_T, D] LoD layout), so flatten features only.
        flatten = num_flatten_dims
        if input_var.lod_level > 0 and num_flatten_dims == 1:
            flatten = len(input_shape) - 1
        param_shape = [
            _prod(input_shape[flatten:])
        ] + [size]
        w = helper.create_parameter(
            attr=param_attr, shape=param_shape, dtype=p_dtype,
            is_bias=False)
        tmp = helper.create_tmp_variable(dtype, lod_level=input_var.lod_level)
        helper.append_op(
            type='mul',
            inputs={'X': [input_var], 'Y': [w]},
            outputs={'Out': [tmp]},
            attrs={'x_num_col_dims': flatten, 'y_num_col_dims': 1})
        _copy_len(helper, input_var, tmp)
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_tmp_variable(dtype, lod_level=lod)
        helper.append_op(type='sum', inputs={'X': mul_results},
                         outputs={'Out': [pre_bias]})
        if lod > 0:
            _copy_len(helper, mul_results[0], pre_bias)
    # Bias broadcasts over everything left of the size dim; base it on the
    # combined lod (pre_bias is [B, T, size] if ANY input was ragged), not
    # on whichever input the loop visited last.
    bias_dim = len(pre_bias.shape) - 1 if lod > 0 else num_flatten_dims
    pre_activation = helper.append_bias_op(pre_bias, dim_start=bias_dim)
    return helper.append_activation(pre_activation)


def _prod(t):
    p = 1
    for d in t:
        p *= int(d)
    return p


def embedding(input, size, is_sparse=False, padding_idx=None,
              param_attr=None, dtype='float32', **kwargs):
    """Parity with fluid.layers.embedding (operators/lookup_table_op)."""
    helper = LayerHelper('embedding', **locals())
    w = helper.create_parameter(
        attr=helper.param_attr, shape=size, dtype=dtype, is_bias=False)
    tmp = helper.create_tmp_variable(dtype, lod_level=input.lod_level)
    # declared vocab height rides the op: the kernel must resolve a
    # negative padding_idx against the TRUE height even when the staged
    # table carries sentinel pad rows past it (sharded-embedding plans
    # leave the padded [V_pad, D] buffer in the scope)
    attrs = {'is_sparse': is_sparse, 'height': int(size[0])}
    if padding_idx is not None:
        attrs['padding_idx'] = padding_idx
    helper.append_op(
        type='lookup_table',
        inputs={'Ids': [input], 'W': [w]},
        outputs={'Out': [tmp]},
        attrs=attrs)
    _copy_len(helper, input, tmp)
    return tmp


def _copy_len(helper, src, dst):
    """Propagate the @LEN companion var for ragged tensors."""
    helper.copy_len(src, dst)


def conv2d(input,
           num_filters,
           filter_size,
           stride=None,
           padding=None,
           groups=None,
           param_attr=None,
           bias_attr=None,
           use_cudnn=True,
           act=None,
           name=None,
           data_format='NCHW',
           dtype=None,
           **kwargs):
    """Parity with fluid.layers.conv2d (operators/conv_op.cc).  data_format
    'NHWC' selects the TPU-preferred layout."""
    helper = LayerHelper('conv2d', **locals())
    dtype = dtype or helper.input_dtype()
    stride = _pair(stride or [1, 1])
    padding = _pair(padding or [0, 0])
    filter_size = _pair(filter_size)
    c_axis = 1 if data_format == 'NCHW' else 3
    num_channels = input.shape[c_axis]
    groups = groups or 1
    filter_shape = [num_filters, num_channels // groups] + filter_size
    std = (2.0 / (filter_size[0] * filter_size[1] * num_channels)) ** 0.5
    # fp32 master weights for low-precision activations (op casts at use)
    p_dtype = 'float32' if dtype in ('bfloat16', 'float16') else dtype
    w = helper.create_parameter(
        attr=helper.param_attr, shape=filter_shape, dtype=p_dtype,
        default_initializer=NormalInitializer(0.0, std))
    pre_bias = helper.create_tmp_variable(dtype)
    helper.append_op(
        type='conv2d',
        inputs={'Input': [input], 'Filter': [w]},
        outputs={'Output': [pre_bias]},
        attrs={'strides': stride, 'paddings': padding, 'groups': groups,
               'dilations': [1, 1], 'data_format': data_format})
    pre_act = helper.append_bias_op(
        pre_bias, dim_start=c_axis, dim_end=c_axis + 1)
    return helper.append_activation(pre_act)


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return [int(x) for x in v]
    return [int(v)] * n


def conv3d(input, num_filters, filter_size, stride=None, padding=None,
           groups=None, param_attr=None, bias_attr=None, act=None,
           name=None, **kwargs):
    helper = LayerHelper('conv3d', **locals())
    dtype = helper.input_dtype()
    stride = _pair(stride or [1, 1, 1], 3)
    padding = _pair(padding or [0, 0, 0], 3)
    filter_size = _pair(filter_size, 3)
    num_channels = input.shape[1]
    groups = groups or 1
    filter_shape = [num_filters, num_channels // groups] + filter_size
    w = helper.create_parameter(attr=helper.param_attr, shape=filter_shape,
                                dtype=dtype, is_bias=False)
    pre_bias = helper.create_tmp_variable(dtype)
    helper.append_op(
        type='conv3d',
        inputs={'Input': [input], 'Filter': [w]},
        outputs={'Output': [pre_bias]},
        attrs={'strides': stride, 'paddings': padding, 'groups': groups,
               'dilations': [1, 1, 1]})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=None, stride=None, dilation=None,
                     param_attr=None, bias_attr=None, act=None, name=None,
                     **kwargs):
    """Parity with fluid.layers.conv2d_transpose
    (operators/conv_transpose_op.cc)."""
    helper = LayerHelper('conv2d_transpose', **locals())
    dtype = helper.input_dtype()
    stride = _pair(stride or [1, 1])
    padding = _pair(padding or [0, 0])
    dilation = _pair(dilation or [1, 1])
    input_channel = input.shape[1]
    if filter_size is None:
        if output_size is None:
            raise ValueError("output_size must be set when filter_size is "
                             "None")
        output_size = _pair(output_size)
        h_in, w_in = input.shape[2], input.shape[3]
        filter_size = [
            (output_size[0] - (h_in - 1) * stride[0] + 2 * padding[0] - 1)
            // dilation[0] + 1,
            (output_size[1] - (w_in - 1) * stride[1] + 2 * padding[1] - 1)
            // dilation[1] + 1,
        ]
    else:
        filter_size = _pair(filter_size)
    filter_shape = [input_channel, num_filters] + filter_size
    w = helper.create_parameter(attr=helper.param_attr, shape=filter_shape,
                                dtype=dtype, is_bias=False)
    pre_bias = helper.create_tmp_variable(dtype)
    helper.append_op(
        type='conv2d_transpose',
        inputs={'Input': [input], 'Filter': [w]},
        outputs={'Output': [pre_bias]},
        attrs={'strides': stride, 'paddings': padding,
               'dilations': dilation})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def pool2d(input, pool_size=-1, pool_type='max', pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, name=None, data_format='NCHW', **kwargs):
    """Parity with fluid.layers.pool2d (operators/pool_op.cc)."""
    if pool_type not in ["max", "avg"]:
        raise ValueError("Unknown pool_type: %r" % pool_type)
    helper = LayerHelper('pool2d', **locals())
    dtype = helper.input_dtype()
    tmp = helper.create_tmp_variable(dtype)
    helper.append_op(
        type='pool2d',
        inputs={'X': [input]},
        outputs={'Out': [tmp]},
        attrs={'pooling_type': pool_type, 'ksize': _pair(pool_size),
               'global_pooling': global_pooling,
               'strides': _pair(pool_stride),
               'paddings': _pair(pool_padding),
               'data_format': data_format})
    return tmp


def pool3d(input, pool_size=-1, pool_type='max', pool_stride=1,
           pool_padding=0, global_pooling=False, name=None, **kwargs):
    helper = LayerHelper('pool3d', **locals())
    tmp = helper.create_tmp_variable(helper.input_dtype())
    helper.append_op(
        type='pool3d',
        inputs={'X': [input]},
        outputs={'Out': [tmp]},
        attrs={'pooling_type': pool_type, 'ksize': _pair(pool_size, 3),
               'global_pooling': global_pooling,
               'strides': _pair(pool_stride, 3),
               'paddings': _pair(pool_padding, 3)})
    return tmp


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-05,
               param_attr=None, bias_attr=None, data_layout='NCHW',
               name=None, moving_mean_name=None, moving_variance_name=None,
               **kwargs):
    """Parity with fluid.layers.batch_norm (operators/batch_norm_op.cc).
    Running stats are persistable vars updated in-graph (donated buffers);
    stats are fp32 even for bf16 activations."""
    helper = LayerHelper('batch_norm', **locals())
    dtype = helper.input_dtype()
    input_shape = input.shape
    if data_layout == 'NCHW':
        channel_num = input_shape[1]
    else:
        channel_num = input_shape[-1]
    param_shape = [channel_num]

    scale = helper.create_parameter(
        attr=helper.param_attr, shape=param_shape, dtype='float32',
        default_initializer=ConstantInitializer(1.0))
    bias = helper.create_parameter(
        attr=helper.bias_attr, shape=param_shape, dtype='float32',
        is_bias=True)

    mean = helper.create_global_variable(
        name=moving_mean_name or helper.name + '.mean',
        persistable=True, shape=param_shape, dtype='float32')
    helper.set_variable_initializer(mean, ConstantInitializer(0.0))
    variance = helper.create_global_variable(
        name=moving_variance_name or helper.name + '.var',
        persistable=True, shape=param_shape, dtype='float32')
    helper.set_variable_initializer(variance, ConstantInitializer(1.0))

    saved_mean = helper.create_tmp_variable('float32', stop_gradient=True)
    saved_variance = helper.create_tmp_variable('float32',
                                                stop_gradient=True)
    batch_norm_out = helper.create_tmp_variable(dtype)
    helper.append_op(
        type='batch_norm',
        inputs={'X': [input], 'Scale': [scale], 'Bias': [bias],
                'Mean': [mean], 'Variance': [variance]},
        outputs={'Y': [batch_norm_out], 'MeanOut': [mean],
                 'VarianceOut': [variance], 'SavedMean': [saved_mean],
                 'SavedVariance': [saved_variance]},
        attrs={'momentum': momentum, 'epsilon': epsilon, 'is_test': is_test,
               'data_layout': data_layout})
    return helper.append_activation(batch_norm_out)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-05, param_attr=None, bias_attr=None, act=None,
               name=None, **kwargs):
    helper = LayerHelper('layer_norm', **locals())
    dtype = helper.input_dtype()
    param_shape = [_prod(input.shape[begin_norm_axis:])]
    inputs = {'X': [input]}
    if scale:
        s = helper.create_parameter(
            attr=helper.param_attr, shape=param_shape, dtype='float32',
            default_initializer=ConstantInitializer(1.0))
        inputs['Scale'] = [s]
    if shift:
        b = helper.create_parameter(
            attr=helper.bias_attr, shape=param_shape, dtype='float32',
            is_bias=True)
        inputs['Bias'] = [b]
    mean_out = helper.create_tmp_variable('float32', stop_gradient=True)
    var_out = helper.create_tmp_variable('float32', stop_gradient=True)
    out = helper.create_tmp_variable(dtype)
    helper.append_op(
        type='layer_norm', inputs=inputs,
        outputs={'Y': [out], 'Mean': [mean_out], 'Variance': [var_out]},
        attrs={'epsilon': epsilon, 'begin_norm_axis': begin_norm_axis})
    return helper.append_activation(out)


def rms_norm(input, epsilon=1e-05, param_attr=None, name=None, **kwargs):
    """RMSNorm over the last axis with a learned scale (init 1),
    computed in float32 (ops/moe.py ``rms_norm``)."""
    helper = LayerHelper('rms_norm', **locals())
    scale = helper.create_parameter(
        attr=helper.param_attr, shape=[input.shape[-1]], dtype='float32',
        default_initializer=ConstantInitializer(1.0))
    out = helper.create_tmp_variable(helper.input_dtype())
    helper.append_op(type='rms_norm',
                     inputs={'X': [input], 'Scale': [scale]},
                     outputs={'Out': [out]}, attrs={'epsilon': epsilon})
    return out


def rotary_embedding(x, pos=None, theta=10000.0, yarn=None,
                     interleaved=False, **kwargs):
    """Rotary position embedding of ``x`` [..., T, H, Dh]; ``pos``
    [..., T] int positions (0..T-1 when None).  ``yarn`` = {factor,
    beta_fast, beta_slow, original_max} takes YaRN's frequencies;
    ``interleaved`` pairs lanes (2j, 2j + 1), not (j, j + Dh/2)."""
    helper = LayerHelper('rotary_embedding', **locals())
    out = helper.create_tmp_variable(x.dtype)
    inputs = {'X': [x]}
    if pos is not None:
        inputs['Pos'] = [pos]
    attrs = {'theta': float(theta), 'interleaved': bool(interleaved)}
    if yarn:
        attrs.update(yarn_factor=float(yarn['factor']),
                     yarn_beta_fast=float(yarn.get('beta_fast', 32.0)),
                     yarn_beta_slow=float(yarn.get('beta_slow', 1.0)),
                     yarn_original_max=int(yarn.get('original_max', 4096)))
    helper.append_op(type='rotary_embedding', inputs=inputs,
                     outputs={'Out': [out]}, attrs=attrs)
    return out


def moe_ffn(input, num_experts, expert_size, top_k, norm_topk_prob=False,
            router_attr=None, gate_attr=None, up_attr=None, down_attr=None,
            dtype='float32', name=None, n_group=0, topk_group=0,
            routed_scaling_factor=1.0, bias_attr=None, router_width=None,
            first_expert=None, shared_size=0, shared_gate_attr=None,
            shared_up_attr=None, shared_down_attr=None, **kwargs):
    """Routed-expert FFN (ops/moe.py ``moe_ffn``): every token of
    ``input`` [..., D] reaches its ``top_k`` of ``num_experts`` SiLU-gated
    experts of width ``expert_size``; no capacity, nothing dropped.  The
    experts are three stacked parameters of ``dtype`` ([E, D, F], [E, D,
    F], [E, F, D]); the router [D, E] is float32.  Returns (out, counts)
    — counts [E] int32, tokens routed to each expert.

    ``n_group`` > 0 takes the grouped sigmoid router (``topk_group``
    groups kept, weights times ``routed_scaling_factor``, a float32
    correction bias [router width] under ``bias_attr``).  With
    ``first_expert`` the layer holds ``num_experts`` experts, ``first_expert
    ..``, of a router ``router_width`` wide (counts gains a last entry:
    assignments to experts held elsewhere).  ``shared_size`` > 0 adds a
    shared expert of that width."""
    helper = LayerHelper('moe_ffn', **locals())
    d, e, f = int(input.shape[-1]), int(num_experts), int(expert_size)
    r = int(router_width or e)
    to_attr = helper.param_attr.to_attr
    slots = [('RouterW', router_attr, [d, r], 'float32'),
             ('GateW', gate_attr, [e, d, f], dtype),
             ('UpW', up_attr, [e, d, f], dtype),
             ('DownW', down_attr, [e, f, d], dtype)]
    attrs = {'top_k': int(top_k), 'norm_topk_prob': bool(norm_topk_prob)}
    if n_group:
        slots.append(('RouterBias', bias_attr, [r], 'float32'))
        attrs.update(n_group=int(n_group), topk_group=int(topk_group),
                     routed_scaling_factor=float(routed_scaling_factor))
    if first_expert is not None:
        attrs['first_expert'] = int(first_expert)
    if shared_size:
        fs = int(shared_size)
        slots += [('SharedGateW', shared_gate_attr, [d, fs], dtype),
                  ('SharedUpW', shared_up_attr, [d, fs], dtype),
                  ('SharedDownW', shared_down_attr, [fs, d], dtype)]
    params = {slot: [helper.create_parameter(
        attr=to_attr(attr), shape=shape, dtype=dt)]
        for slot, attr, shape, dt in slots}
    out = helper.create_tmp_variable(helper.input_dtype())
    counts = helper.create_tmp_variable('int32', stop_gradient=True)
    helper.append_op(
        type='moe_ffn', inputs=dict(params, X=[input]),
        outputs={'Out': [out], 'Counts': [counts]}, attrs=attrs)
    return out, counts


def dropout(x, dropout_prob, is_test=False, seed=0, **kwargs):
    helper = LayerHelper('dropout', **locals())
    out = helper.create_tmp_variable(x.dtype)
    mask = helper.create_tmp_variable(x.dtype, stop_gradient=True)
    helper.append_op(
        type='dropout',
        inputs={'X': [x]},
        outputs={'Out': [out], 'Mask': [mask]},
        attrs={'dropout_prob': dropout_prob, 'is_test': is_test,
               'seed': seed})
    return out


def cross_entropy(input, label, soft_label=False, **kwargs):
    helper = LayerHelper('cross_entropy', **locals())
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(
        type='cross_entropy',
        inputs={'X': [input], 'Label': [label]},
        outputs={'Y': [out]},
        attrs={'soft_label': soft_label})
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False, **kwargs):
    helper = LayerHelper('softmax_with_cross_entropy', **locals())
    softmax = helper.create_tmp_variable(logits.dtype)
    loss = helper.create_tmp_variable(logits.dtype)
    helper.append_op(
        type='softmax_with_cross_entropy',
        inputs={'Logits': [logits], 'Label': [label]},
        outputs={'Softmax': [softmax], 'Loss': [loss]},
        attrs={'soft_label': soft_label})
    return loss


def fused_linear_softmax_ce(input, label, size, num_flatten_dims=1,
                            param_attr=None, bias_attr=None, chunk=4096,
                            mode='auto', **kwargs):
    """Vocab projection + softmax cross-entropy as ONE chunked op: the
    [N, size] logits never materialize in HBM (ops/chunked_ce.py).  The
    TPU-first form of ``fc(size=V) → softmax_with_cross_entropy`` for
    large ``size``; same fp32-master-weight recipe as fc, so a plain fc
    sharing ``param_attr``/``bias_attr`` names reuses the trained head
    for inference/decoding."""
    helper = LayerHelper('fused_linear_softmax_ce', **locals())
    dtype = helper.input_dtype()
    p_dtype = 'float32' if dtype in ('bfloat16', 'float16') else dtype
    input_shape = input.shape
    flatten = num_flatten_dims
    if input.lod_level > 0 and num_flatten_dims == 1:
        flatten = len(input_shape) - 1
    w = helper.create_parameter(
        attr=param_attr, shape=[_prod(input_shape[flatten:]), size],
        dtype=p_dtype, is_bias=False)
    inputs = {'X': [input], 'W': [w], 'Label': [label]}
    if bias_attr is not False:
        from ..param_attr import ParamAttr
        battr = bias_attr if bias_attr is not None else ParamAttr()
        b = helper.create_parameter(attr=battr, shape=[size],
                                    dtype=p_dtype, is_bias=True)
        inputs['Bias'] = [b]
    loss = helper.create_tmp_variable('float32')
    helper.append_op(
        type='fused_linear_softmax_ce', inputs=inputs,
        outputs={'Loss': [loss]},
        attrs={'chunk': int(chunk), 'mode': mode, 'flatten': flatten},
        infer_shape=False)
    loss.shape = tuple(input_shape[:flatten]) + (1,)
    return loss


def square_error_cost(input, label, **kwargs):
    helper = LayerHelper('square_error_cost', **locals())
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(
        type='square_error_cost',
        inputs={'X': [input], 'Y': [label]},
        outputs={'Out': [out]})
    return out


def accuracy(input, label, k=1, correct=None, total=None, **kwargs):
    """Parity with fluid.layers.accuracy (operators/accuracy_op +
    top_k_op)."""
    helper = LayerHelper('accuracy', **locals())
    topk_out = helper.create_tmp_variable(dtype=input.dtype)
    topk_indices = helper.create_tmp_variable(dtype='int32',
                                              stop_gradient=True)
    helper.append_op(
        type='top_k',
        inputs={'X': [input]},
        outputs={'Out': [topk_out], 'Indices': [topk_indices]},
        attrs={'k': k})
    acc_out = helper.create_tmp_variable(dtype='float32',
                                         stop_gradient=True)
    if correct is None:
        correct = helper.create_tmp_variable(dtype='int32',
                                             stop_gradient=True)
    if total is None:
        total = helper.create_tmp_variable(dtype='int32',
                                           stop_gradient=True)
    # the reference accuracy_op also declares top_k's 'Out' as an input,
    # but only ever reads Indices/Label (accuracy_op.h) — the IR
    # verifier flags the vestigial slot, so it is not declared here
    helper.append_op(
        type='accuracy',
        inputs={'Indices': [topk_indices], 'Label': [label]},
        outputs={'Accuracy': [acc_out], 'Correct': [correct],
                 'Total': [total]})
    return acc_out


def auc(input, label, curve='ROC', num_thresholds=200, **kwargs):
    helper = LayerHelper('auc', **locals())
    out = helper.create_tmp_variable('float32', stop_gradient=True)
    helper.append_op(
        type='auc',
        inputs={'Out': [input], 'Label': [label]},
        outputs={'AUC': [out]},
        attrs={'curve': curve, 'num_thresholds': num_thresholds})
    return out


def _reduce_layer(op_name):
    def _layer(input, dim=None, keep_dim=False, name=None, **kwargs):
        helper = LayerHelper(op_name, **locals())
        out = helper.create_tmp_variable(input.dtype)
        helper.append_op(
            type=op_name,
            inputs={'X': [input]},
            outputs={'Out': [out]},
            attrs={'dim': dim, 'keep_dim': keep_dim,
                   'reduce_all': dim is None})
        return out

    _layer.__name__ = op_name
    return _layer


reduce_sum = _reduce_layer('reduce_sum')
reduce_mean = _reduce_layer('reduce_mean')
reduce_max = _reduce_layer('reduce_max')
reduce_min = _reduce_layer('reduce_min')
reduce_prod = _reduce_layer('reduce_prod')


def split(input, num_or_sections, dim=-1, **kwargs):
    helper = LayerHelper('split', **locals())
    input_shape = input.shape
    dim = (len(input_shape) + dim) if dim < 0 else dim
    if isinstance(num_or_sections, int):
        num = num_or_sections
        attrs = {'num': num_or_sections, 'axis': dim, 'sections': []}
    else:
        num = len(num_or_sections)
        attrs = {'sections': list(num_or_sections), 'axis': dim, 'num': 0}
    outs = [helper.create_tmp_variable(input.dtype) for _ in range(num)]
    helper.append_op(type='split', inputs={'X': [input]},
                     outputs={'Out': outs}, attrs=attrs)
    return outs


def matmul(x, y, transpose_x=False, transpose_y=False, name=None, **kwargs):
    helper = LayerHelper('matmul', **locals())
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(
        type='matmul',
        inputs={'X': [x], 'Y': [y]},
        outputs={'Out': [out]},
        attrs={'transpose_X': transpose_x, 'transpose_Y': transpose_y})
    return out


def topk(input, k, **kwargs):
    helper = LayerHelper('top_k', **locals())
    values = helper.create_tmp_variable(input.dtype)
    indices = helper.create_tmp_variable('int32', stop_gradient=True)
    helper.append_op(
        type='top_k',
        inputs={'X': [input]},
        outputs={'Out': [values], 'Indices': [indices]},
        attrs={'k': k})
    return values, indices


def l2_normalize(x, axis, epsilon=1e-12, name=None, **kwargs):
    helper = LayerHelper('l2_normalize', **locals())
    out = helper.create_tmp_variable(x.dtype)
    norm = helper.create_tmp_variable(x.dtype)
    helper.append_op(
        type='norm',
        inputs={'X': [x]},
        outputs={'Out': [out], 'Norm': [norm]},
        attrs={'axis': axis, 'epsilon': epsilon})
    return out


def one_hot(input, depth, **kwargs):
    helper = LayerHelper('one_hot', **locals())
    out = helper.create_tmp_variable('float32')
    helper.append_op(
        type='one_hot',
        inputs={'X': [input]},
        outputs={'Out': [out]},
        attrs={'depth': depth})
    return out


def cos_sim(X, Y, **kwargs):
    helper = LayerHelper('cos_sim', **locals())
    out = helper.create_tmp_variable(X.dtype)
    xnorm = helper.create_tmp_variable(X.dtype)
    ynorm = helper.create_tmp_variable(X.dtype)
    helper.append_op(
        type='cos_sim',
        inputs={'X': [X], 'Y': [Y]},
        outputs={'Out': [out], 'XNorm': [xnorm], 'YNorm': [ynorm]})
    return out


def lrn(input, n=5, k=2.0, alpha=1e-4, beta=0.75, name=None, **kwargs):
    helper = LayerHelper('lrn', **locals())
    out = helper.create_tmp_variable(input.dtype)
    mid = helper.create_tmp_variable(input.dtype, stop_gradient=True)
    helper.append_op(
        type='lrn',
        inputs={'X': [input]},
        outputs={'Out': [out], 'MidOut': [mid]},
        attrs={'n': n, 'k': k, 'alpha': alpha, 'beta': beta})
    return out


def warpctc(input, label, blank=0, norm_by_times=False, **kwargs):
    from ..core.program import LEN_SUFFIX
    helper = LayerHelper('warpctc', **locals())
    loss = helper.create_tmp_variable(input.dtype)
    grad = helper.create_tmp_variable(input.dtype, stop_gradient=True)
    inputs = {'Logits': [input], 'Label': [label]}
    block = helper.main_program.current_block()
    if block.has_var_recursive(input.name + LEN_SUFFIX):
        inputs['LogitsLen'] = [block.var_recursive(input.name + LEN_SUFFIX)]
    if block.has_var_recursive(label.name + LEN_SUFFIX):
        inputs['LabelLen'] = [block.var_recursive(label.name + LEN_SUFFIX)]
    helper.append_op(
        type='warpctc',
        inputs=inputs,
        outputs={'Loss': [loss], 'WarpCTCGrad': [grad]},
        attrs={'blank': blank, 'norm_by_times': norm_by_times})
    return loss


def nce(input, label, num_total_classes, sample_weight=None,
        param_attr=None, bias_attr=None, num_neg_samples=None, **kwargs):
    helper = LayerHelper('nce', **locals())
    dim = input.shape[1]
    w = helper.create_parameter(
        attr=helper.param_attr, shape=[num_total_classes, dim],
        dtype=input.dtype, is_bias=False)
    b = helper.create_parameter(
        attr=helper.bias_attr, shape=[num_total_classes],
        dtype=input.dtype, is_bias=True)
    cost = helper.create_tmp_variable(input.dtype)
    sample_logits = helper.create_tmp_variable(input.dtype,
                                               stop_gradient=True)
    sample_labels = helper.create_tmp_variable('int32', stop_gradient=True)
    helper.append_op(
        type='nce',
        inputs={'Input': [input], 'Label': [label], 'Weight': [w],
                'Bias': [b]},
        outputs={'Cost': [cost], 'SampleLogits': [sample_logits],
                 'SampleLabels': [sample_labels]},
        attrs={'num_total_classes': num_total_classes,
               'num_neg_samples': num_neg_samples or 10})
    return cost


def bilinear_tensor_product(x, y, size, act=None, name=None,
                            param_attr=None, bias_attr=None, **kwargs):
    helper = LayerHelper('bilinear_tensor_product', **locals())
    dtype = helper.input_dtype('x')
    param_shape = [size, x.shape[1], y.shape[1]]
    w = helper.create_parameter(attr=helper.param_attr, shape=param_shape,
                                dtype=dtype, is_bias=False)
    out = helper.create_tmp_variable(dtype)
    inputs = {'X': [x], 'Y': [y], 'Weight': [w]}
    if helper.bias_attr:
        bias_size = [1, size]
        bias = helper.create_parameter(attr=helper.bias_attr,
                                       shape=bias_size, dtype=dtype,
                                       is_bias=True)
        inputs['Bias'] = [bias]
    helper.append_op(type='bilinear_tensor_product', inputs=inputs,
                     outputs={'Out': [out]})
    return helper.append_activation(out)


def prelu(x, mode='all', param_attr=None, name=None, **kwargs):
    helper = LayerHelper('prelu', **locals())
    if mode == 'all':
        alpha_shape = [1]
    elif mode == 'channel':
        alpha_shape = [1, x.shape[1], 1, 1]
    else:
        alpha_shape = [1] + list(x.shape[1:])
    alpha = helper.create_parameter(
        attr=helper.param_attr, shape=alpha_shape, dtype='float32',
        default_initializer=ConstantInitializer(0.25))
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(type='prelu', inputs={'X': [x], 'Alpha': [alpha]},
                     outputs={'Out': [out]}, attrs={'mode': mode})
    return out


def pad(x, paddings, pad_value=0.0, name=None, **kwargs):
    helper = LayerHelper('pad', **locals())
    out = helper.create_tmp_variable(x.dtype)
    helper.append_op(
        type='pad', inputs={'X': [x]}, outputs={'Out': [out]},
        attrs={'paddings': list(paddings), 'pad_value': float(pad_value)})
    return out


def im2sequence(input, filter_size=1, stride=1, padding=0, name=None,
                **kwargs):
    helper = LayerHelper('im2sequence', **locals())
    out = helper.create_tmp_variable(input.dtype)
    helper.append_op(
        type='im2sequence', inputs={'X': [input]}, outputs={'Out': [out]},
        attrs={'kernels': _pair(filter_size), 'strides': _pair(stride),
               'paddings': _pair(padding, 4)})
    return out


def multiplex(inputs, index, **kwargs):
    helper = LayerHelper('multiplex', **locals())
    out = helper.create_tmp_variable(inputs[0].dtype)
    helper.append_op(
        type='multiplex',
        inputs={'X': list(inputs), 'Ids': [index]},
        outputs={'Out': [out]})
    return out


def row_conv(input, future_context_size, param_attr=None, act=None,
             **kwargs):
    helper = LayerHelper('row_conv', **locals())
    dtype = helper.input_dtype()
    filter_shape = [future_context_size + 1, input.shape[-1]]
    w = helper.create_parameter(attr=helper.param_attr, shape=filter_shape,
                                dtype=dtype, is_bias=False)
    out = helper.create_tmp_variable(dtype)
    helper.append_op(
        type='row_conv',
        inputs={'X': [input], 'Filter': [w]},
        outputs={'Out': [out]})
    return helper.append_activation(out)


def roi_pool(input, rois, pooled_height, pooled_width, spatial_scale=1.0,
             **kwargs):
    """RoI max pooling (ref operators/roi_pool_op.cc): input [N, C, H, W],
    rois [R, 5] rows (batch_idx, x1, y1, x2, y2) -> [R, C, ph, pw]."""
    helper = LayerHelper('roi_pool', **locals())
    dtype = helper.input_dtype()
    out = helper.create_tmp_variable(dtype)
    argmax = helper.create_tmp_variable('int32')  # x64 disabled under jax
    helper.append_op(
        type='roi_pool',
        inputs={'X': [input], 'ROIs': [rois]},
        outputs={'Out': [out], 'Argmax': [argmax]},
        attrs={'pooled_height': pooled_height,
               'pooled_width': pooled_width,
               'spatial_scale': spatial_scale})
    return out


def detection_output(loc, conf, prior_box, num_classes,
                     background_label_id=0, nms_threshold=0.45,
                     confidence_threshold=0.01, nms_top_k=400,
                     keep_top_k=200, **kwargs):
    """SSD post-processing (ref operators/detection_output_op.cc): decode
    prior boxes, per-class NMS, global top-k -> [N, keep_top_k, 6] rows
    (label, score, xmin, ymin, xmax, ymax); label -1 pads."""
    helper = LayerHelper('detection_output', **locals())
    out = helper.create_tmp_variable('float32')
    helper.append_op(
        type='detection_output',
        inputs={'Loc': [loc], 'Conf': [conf], 'PriorBox': [prior_box]},
        outputs={'Out': [out]},
        attrs={'num_classes': num_classes,
               'background_label_id': background_label_id,
               'nms_threshold': nms_threshold,
               'confidence_threshold': confidence_threshold,
               'nms_top_k': nms_top_k, 'keep_top_k': keep_top_k})
    return out
