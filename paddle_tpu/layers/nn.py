"""Neural-network layers.

Parity: python/paddle/fluid/layers/nn.py — same 58-layer surface, same
signatures (param_attr/bias_attr/act/name). Each layer appends IR ops; the
kernels live in paddle_tpu/ops and compile through XLA onto the MXU.
"""
from ..layer_helper import LayerHelper
from ..framework import Variable
from ..initializer import Normal, Constant
from .. import unique_name
from . import tensor as tensor_layers

__all__ = [
    'fc', 'embedding', 'dynamic_lstm', 'dynamic_lstmp', 'dynamic_gru',
    'gru_unit', 'linear_chain_crf', 'crf_decoding', 'cos_sim',
    'cross_entropy', 'square_error_cost', 'chunk_eval', 'sequence_conv',
    'conv2d', 'sequence_pool', 'sequence_softmax', 'softmax', 'pool2d',
    'batch_norm', 'beam_search_decode', 'conv2d_transpose',
    'sequence_expand', 'lstm_unit', 'reduce_sum', 'reduce_mean',
    'reduce_max', 'reduce_min', 'reduce_prod', 'sequence_first_step',
    'sequence_last_step', 'dropout', 'split', 'ctc_greedy_decoder',
    'edit_distance', 'l2_normalize', 'matmul', 'topk', 'warpctc',
    'sequence_reshape', 'transpose', 'im2sequence', 'nce', 'beam_search',
    'row_conv', 'multiplex', 'layer_norm', 'softmax_with_cross_entropy',
    'smooth_l1', 'one_hot', 'autoincreased_step_counter', 'reshape',
    'lod_reset', 'lrn', 'pad', 'label_smooth', 'roi_pool', 'dice_loss',
    'expand',
    'bilinear_interp', 'gather', 'squeeze', 'unsqueeze',
    'prelu', 'maxout', 'log_loss', 'huber_loss', 'rank_loss',
    'margin_rank_loss', 'hinge_loss', 'modified_huber_loss', 'unpool',
    'spp', 'max_pool2d_with_index', 'squared_l2_distance',
    'squared_l2_norm', 'l1_norm',
    'flash_attention',
    'sequence_concat',
    'rms_norm', 'causal_conv1d', 'ssd_scan', 'mamba2_mixer',
    'rotary_embedding', 'router_scores', 'routed_experts',
]


def _conv_out(size, k, p, s, d=1):
    if size < 0:
        return -1
    ke = d * (k - 1) + 1
    return (size + 2 * p - ke) // s + 1


def fc(input, size, num_flatten_dims=1, param_attr=None, bias_attr=None,
       use_mkldnn=False, act=None, is_test=False, name=None):
    """Fully connected. Parity: layers/nn.py::fc — multiple inputs each get
    a weight; results are summed; one shared bias; then activation."""
    helper = LayerHelper("fc", input=input, param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = helper.input_dtype()
    mul_results = []
    for input_var, p_attr in helper.iter_inputs_and_params():
        input_shape = input_var.shape
        param_shape = [
            _prod(input_shape[num_flatten_dims:])
        ] + [size]
        w = helper.create_parameter(attr=p_attr, shape=param_shape,
                                    dtype=dtype, is_bias=False)
        out_shape = tuple(input_shape[:num_flatten_dims]) + (size,)
        tmp = helper.create_tmp_variable(dtype, shape=out_shape,
                                         lod_level=input_var.lod_level)
        helper.append_op(
            type="mul", inputs={"X": input_var, "Y": w},
            outputs={"Out": tmp},
            attrs={"x_num_col_dims": num_flatten_dims,
                   "y_num_col_dims": 1})
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_tmp_variable(
            dtype, shape=mul_results[0].shape,
            lod_level=mul_results[0].lod_level)
        helper.append_op(type="sum", inputs={"X": mul_results},
                         outputs={"Out": pre_bias})
    pre_activation = helper.append_bias_op(pre_bias,
                                           dim_start=num_flatten_dims)
    return helper.append_activation(pre_activation)


def _prod(dims):
    r = 1
    for d in dims:
        r *= int(d)
    return abs(r)


# Below this table size (elements) the sparse row-gradient path is
# never-better on TPU: the duplicate-id merge sort costs more than the
# dense-table traffic it saves (measured r4 on v5e — see PERF.md sparse
# table; 100k x 64 = 6.4M elems ran 0.93x). is_sparse=True falls back
# to the dense kernel below it, so the flag is never-worse (VERDICT r3
# #5; ref lookup_table_op.cc:37 always honors the flag, but its CPU
# SelectedRows path has no merge-sort cost cliff to fall off).
_SPARSE_MIN_TABLE_ELEMS = [32 * 1024 * 1024]
_SPARSE_FALLBACK_WARNED = [False]


def set_sparse_fallback_threshold(n_elems):
    """Override the is_sparse dense-fallback threshold (elements in the
    [vocab, dim] table). 0 always honors is_sparse=True."""
    prev = _SPARSE_MIN_TABLE_ELEMS[0]
    _SPARSE_MIN_TABLE_ELEMS[0] = int(n_elems)
    return prev


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype='float32'):
    """Parity: layers/nn.py::embedding (lookup_table op). ``is_sparse``
    is honored (r3): the backward produces ROW gradients instead of a
    dense [vocab, d] table gradient, and SGD/Adagrad/Adam update only
    the touched rows (the TPU-native SelectedRows — ref
    operators/lookup_table_op.cc:37 and the sgd/adam SelectedRows
    paths). See core/lowering.py sparse-carrier machinery. Small tables
    auto-route to the dense path (never-worse heuristic, r4) — override
    with set_sparse_fallback_threshold(0)."""
    helper = LayerHelper('embedding', param_attr=param_attr)
    w = helper.create_parameter(attr=helper.param_attr, shape=size,
                                dtype=dtype, is_bias=False)
    in_shape = tuple(input.shape)
    if in_shape and in_shape[-1] == 1:
        out_shape = in_shape[:-1] + (size[1],)
    else:
        out_shape = in_shape + (size[1],)
    tmp = helper.create_tmp_variable(dtype, shape=out_shape,
                                     lod_level=input.lod_level)
    padding_idx = -1 if padding_idx is None else (
        padding_idx if padding_idx >= 0 else size[0] + padding_idx)
    if is_sparse and _prod(size) < _SPARSE_MIN_TABLE_ELEMS[0]:
        # ADVICE r4: the reference always honors is_sparse
        # (lookup_table_op.cc); the rewrite is numerics-identical but
        # visible in the program, so say it once per process
        import warnings
        if not _SPARSE_FALLBACK_WARNED[0]:
            _SPARSE_FALLBACK_WARNED[0] = True
            warnings.warn(
                "embedding(is_sparse=True) on a %s table (< %d elements) "
                "routes to the DENSE gradient path (measured never-worse "
                "below the break-even on TPU). Numerics are identical; "
                "override with set_sparse_fallback_threshold(0)."
                % ('x'.join(str(s) for s in size),
                   _SPARSE_MIN_TABLE_ELEMS[0]))
        is_sparse = False
    attrs = {'is_sparse': is_sparse, 'padding_idx': padding_idx}
    if is_sparse:
        w.sparse_grad = True
        from .. import unique_name
        # per-op grad carrier: rows differentiate instead of the table
        attrs['sparse_carrier'] = unique_name.generate(
            w.name + '@SCARRIER')
    helper.append_op(type='lookup_table',
                     inputs={'Ids': input, 'W': w},
                     outputs={'Out': tmp},
                     attrs=attrs)
    return tmp


def cross_entropy(input, label, soft_label=False):
    helper = LayerHelper('cross_entropy', **{})
    out = helper.create_tmp_variable(dtype=input.dtype,
                                     shape=tuple(input.shape[:-1]) + (1,),
                                     lod_level=input.lod_level)
    helper.append_op(type='cross_entropy',
                     inputs={'X': [input], 'Label': [label]},
                     outputs={'Y': [out]},
                     attrs={'soft_label': soft_label})
    return out


def square_error_cost(input, label):
    helper = LayerHelper('square_error_cost', **{})
    out = helper.create_tmp_variable(dtype=input.dtype, shape=input.shape)
    helper.append_op(type='square_error_cost',
                     inputs={'X': [input], 'Label': [label]},
                     outputs={'Out': [out]})
    return out


def cos_sim(X, Y):
    helper = LayerHelper('cos_sim', **{})
    out = helper.create_tmp_variable(dtype=X.dtype,
                                     shape=(X.shape[0], 1))
    xnorm = helper.create_tmp_variable(dtype=X.dtype)
    ynorm = helper.create_tmp_variable(dtype=X.dtype)
    helper.append_op(type='cos_sim', inputs={'X': [X], 'Y': [Y]},
                     outputs={'Out': [out], 'XNorm': [xnorm],
                              'YNorm': [ynorm]})
    return out


def dropout(x, dropout_prob, is_test=False, seed=None, name=None):
    helper = LayerHelper('dropout', name=name)
    out = helper.create_tmp_variable(dtype=x.dtype, shape=x.shape,
                                     lod_level=x.lod_level)
    mask = helper.create_tmp_variable(dtype=x.dtype, stop_gradient=True)
    helper.append_op(type='dropout', inputs={'X': [x]},
                     outputs={'Out': [out], 'Mask': [mask]},
                     attrs={'dropout_prob': dropout_prob,
                            'is_test': is_test,
                            'seed': seed if seed is not None else 0})
    return out


def softmax(input, param_attr=None, bias_attr=None, use_cudnn=True,
            name=None):
    helper = LayerHelper('softmax', name=name)
    out = helper.create_tmp_variable(dtype=input.dtype, shape=input.shape,
                                     lod_level=input.lod_level)
    helper.append_op(type='softmax', inputs={'X': [input]},
                     outputs={'Out': [out]})
    return out


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=None, param_attr=None, bias_attr=None, use_cudnn=True,
           use_mkldnn=False, act=None, name=None):
    """Parity: layers/nn.py::conv2d (NCHW)."""
    num_channels = input.shape[1]
    helper = LayerHelper('conv2d', param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = helper.input_dtype() if isinstance(input, Variable) else \
        input.dtype
    groups = groups or 1
    if num_channels % groups != 0:
        raise ValueError("num_channels must be divisible by groups")
    num_filter_channels = num_channels // groups
    filter_size = _pair(filter_size)
    stride = _pair(stride)
    padding = _pair(padding)
    dilation = _pair(dilation)
    filter_shape = [num_filters, int(num_filter_channels)] + \
        list(filter_size)

    def _get_default_param_initializer():
        std = (2.0 / (filter_size[0] ** 2 * num_channels)) ** 0.5
        return Normal(0.0, std, 0)

    filter_param = helper.create_parameter(
        attr=helper.param_attr, shape=filter_shape, dtype=dtype,
        default_initializer=_get_default_param_initializer())
    out_shape = (input.shape[0], num_filters,
                 _conv_out(input.shape[2], filter_size[0], padding[0],
                           stride[0], dilation[0]),
                 _conv_out(input.shape[3], filter_size[1], padding[1],
                           stride[1], dilation[1]))
    pre_bias = helper.create_tmp_variable(dtype, shape=out_shape)
    helper.append_op(
        type='conv2d',
        inputs={'Input': input, 'Filter': filter_param},
        outputs={'Output': pre_bias},
        attrs={'strides': list(stride), 'paddings': list(padding),
               'dilations': list(dilation), 'groups': groups,
               'use_cudnn': use_cudnn})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def _pair(v):
    return list(v) if isinstance(v, (list, tuple)) else [v, v]


def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, param_attr=None,
                     bias_attr=None, use_cudnn=True, act=None, name=None):
    helper = LayerHelper("conv2d_transpose", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    input_channel = input.shape[1]
    padding = _pair(padding)
    stride = _pair(stride)
    dilation = _pair(dilation)
    if filter_size is None:
        if output_size is None:
            raise ValueError(
                "output_size must be set when filter_size is None")
        output_size = _pair(output_size)
        h_in, w_in = input.shape[2], input.shape[3]
        filter_size_h = (output_size[0] - (h_in - 1) * stride[0] +
                         2 * padding[0] - 1) // dilation[0] + 1
        filter_size_w = (output_size[1] - (w_in - 1) * stride[1] +
                         2 * padding[1] - 1) // dilation[1] + 1
        filter_size = [filter_size_h, filter_size_w]
    else:
        filter_size = _pair(filter_size)
    filter_shape = [int(input_channel), num_filters] + filter_size
    img_filter = helper.create_parameter(dtype=input.dtype,
                                         shape=filter_shape,
                                         attr=helper.param_attr)

    def _out(size, k, p, s, d):
        if size < 0:
            return -1
        return (size - 1) * s - 2 * p + d * (k - 1) + 1
    out_shape = (input.shape[0], num_filters,
                 _out(input.shape[2], filter_size[0], padding[0], stride[0],
                      dilation[0]),
                 _out(input.shape[3], filter_size[1], padding[1], stride[1],
                      dilation[1]))
    pre_bias = helper.create_tmp_variable(dtype=input.dtype,
                                          shape=out_shape)
    helper.append_op(type='conv2d_transpose',
                     inputs={'Input': [input], 'Filter': [img_filter]},
                     outputs={'Output': pre_bias},
                     attrs={'strides': stride, 'paddings': padding,
                            'dilations': dilation})
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, use_cudnn=True,
           ceil_mode=False, use_mkldnn=False, name=None,
           exclusive=True):
    if pool_type not in ["max", "avg"]:
        raise ValueError("pool_type must be 'max' or 'avg'")
    if global_pooling is False and pool_size == -1:
        raise ValueError("pool_size must be set when not global pooling")
    pool_size = _pair(pool_size)
    pool_padding = _pair(pool_padding)
    pool_stride = _pair(pool_stride)
    helper = LayerHelper('pool2d', name=name)
    dtype = helper.input_dtype(input_param_name='input') \
        if isinstance(input, list) else input.dtype
    if global_pooling:
        out_shape = (input.shape[0], input.shape[1], 1, 1)
    else:
        out_shape = (input.shape[0], input.shape[1],
                     _conv_out(input.shape[2], pool_size[0], pool_padding[0],
                               pool_stride[0]),
                     _conv_out(input.shape[3], pool_size[1], pool_padding[1],
                               pool_stride[1]))
    out = helper.create_tmp_variable(dtype, shape=out_shape)
    helper.append_op(type='pool2d', inputs={'X': input},
                     outputs={'Out': out},
                     attrs={'pooling_type': pool_type,
                            'exclusive': exclusive,
                            'ksize': pool_size,
                            'global_pooling': global_pooling,
                            'strides': pool_stride,
                            'paddings': pool_padding,
                            'ceil_mode': ceil_mode})
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-05,
               param_attr=None, bias_attr=None, data_layout='NCHW',
               in_place=False, use_mkldnn=False, name=None,
               moving_mean_name=None, moving_variance_name=None,
               do_model_average_for_mean_and_var=False):
    helper = LayerHelper('batch_norm', param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    input_shape = input.shape
    if data_layout == 'NCHW':
        channel_num = input_shape[1] if len(input_shape) > 2 else \
            input_shape[-1]
    elif data_layout == 'NHWC':
        channel_num = input_shape[-1]
    else:
        raise ValueError("unsupported data layout: %s" % data_layout)
    param_shape = [int(channel_num)]

    scale = helper.create_parameter(attr=helper.param_attr,
                                    shape=param_shape, dtype=dtype,
                                    default_initializer=Constant(1.0))
    bias = helper.create_parameter(attr=helper.bias_attr, shape=param_shape,
                                   dtype=dtype, is_bias=True)

    mean = helper.create_parameter(
        attr=__import__('paddle_tpu.param_attr', fromlist=['ParamAttr'])
        .ParamAttr(name=moving_mean_name, initializer=Constant(0.0),
                   trainable=False),
        shape=param_shape, dtype=dtype)
    variance = helper.create_parameter(
        attr=__import__('paddle_tpu.param_attr', fromlist=['ParamAttr'])
        .ParamAttr(name=moving_variance_name, initializer=Constant(1.0),
                   trainable=False),
        shape=param_shape, dtype=dtype)
    mean.stop_gradient = True
    variance.stop_gradient = True

    saved_mean = helper.create_tmp_variable(dtype=dtype, stop_gradient=True)
    saved_variance = helper.create_tmp_variable(dtype=dtype,
                                                stop_gradient=True)
    batch_norm_out = input if in_place else \
        helper.create_tmp_variable(dtype, shape=input_shape)

    helper.append_op(
        type="batch_norm",
        inputs={"X": input, "Scale": scale, "Bias": bias, "Mean": mean,
                "Variance": variance},
        outputs={"Y": batch_norm_out, "MeanOut": mean,
                 "VarianceOut": variance, "SavedMean": saved_mean,
                 "SavedVariance": saved_variance},
        attrs={"momentum": momentum, "epsilon": epsilon,
               "is_test": is_test, "data_layout": data_layout})
    return helper.append_activation(batch_norm_out)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-05, param_attr=None, bias_attr=None, act=None,
               name=None):
    helper = LayerHelper('layer_norm', param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    dtype = input.dtype
    input_shape = input.shape
    param_shape = [_prod(input_shape[begin_norm_axis:])]
    inputs = {'X': input}
    if scale:
        scale_p = helper.create_parameter(
            attr=helper.param_attr, shape=param_shape, dtype=dtype,
            default_initializer=Constant(1.0))
        inputs['Scale'] = scale_p
    if shift:
        bias_p = helper.create_parameter(attr=helper.bias_attr,
                                         shape=param_shape, dtype=dtype,
                                         is_bias=True)
        inputs['Bias'] = bias_p
    mean_out = helper.create_tmp_variable(dtype=dtype, stop_gradient=True)
    variance_out = helper.create_tmp_variable(dtype=dtype,
                                              stop_gradient=True)
    layer_norm_out = helper.create_tmp_variable(dtype, shape=input_shape)
    helper.append_op(type="layer_norm", inputs=inputs,
                     outputs={"Y": layer_norm_out, "Mean": mean_out,
                              "Variance": variance_out},
                     attrs={"epsilon": epsilon,
                            "begin_norm_axis": begin_norm_axis})
    return helper.append_activation(layer_norm_out)


def softmax_with_cross_entropy(logits, label, soft_label=False):
    helper = LayerHelper('softmax_with_cross_entropy', **{})
    softmax_v = helper.create_tmp_variable(dtype=logits.dtype,
                                           shape=logits.shape)
    loss = helper.create_tmp_variable(
        dtype=logits.dtype, shape=tuple(logits.shape[:-1]) + (1,))
    helper.append_op(type='softmax_with_cross_entropy',
                     inputs={'Logits': logits, 'Label': label},
                     outputs={'Softmax': softmax_v, 'Loss': loss},
                     attrs={'soft_label': soft_label})
    return loss


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper('smooth_l1_loss', **{})
    diff = helper.create_tmp_variable(dtype=x.dtype)
    loss = helper.create_tmp_variable(dtype=x.dtype,
                                      shape=(x.shape[0], 1))
    helper.append_op(type='smooth_l1',
                     inputs={'X': x, 'Y': y, 'InsideWeight': inside_weight,
                             'OutsideWeight': outside_weight},
                     outputs={'Diff': diff, 'Out': loss},
                     attrs={'sigma': sigma if sigma is not None else 1.0})
    return loss


def one_hot(input, depth):
    helper = LayerHelper("one_hot", **{})
    shape = tuple(input.shape[:-1]) + (depth,) if (
        input.shape and input.shape[-1] == 1) else \
        tuple(input.shape) + (depth,)
    one_hot_out = helper.create_tmp_variable(dtype='float32', shape=shape)
    helper.append_op(type="one_hot", inputs={'X': input},
                     attrs={'depth': depth},
                     outputs={'Out': one_hot_out})
    return one_hot_out


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    """Persistable int64 step counter incremented once per step program run.
    Parity: layers/nn.py::autoincreased_step_counter."""
    helper = LayerHelper('global_step_counter')
    if counter_name is None:
        counter_name = '@STEP_COUNTER@'
    program = helper.main_program
    counter = program.global_block().create_var(
        name=counter_name, dtype='int64', shape=(1,), persistable=True)
    startup = helper.startup_program.global_block()
    sv = startup.create_var(name=counter_name, dtype='int64', shape=(1,),
                            persistable=True)
    Constant(value=float(begin - 1))(sv, startup)
    if not getattr(counter, '_step_op_added', False):
        helper.main_program.global_block().prepend_op(
            type='increment', inputs={'X': [counter]},
            outputs={'Out': [counter]}, attrs={'step': float(step)})
        counter._step_op_added = True
    counter.stop_gradient = True
    return counter


def reshape(x, shape, actual_shape=None, act=None, inplace=True, name=None):
    """``actual_shape`` overrides ``shape`` (reference nn.py:3441-3529:
    the Shape input wins at runtime). On the static-shape XLA path a
    Variable actual_shape is lowered as a STATIC feed: the Executor binds
    its value at trace time (part of the jit cache key) — the TPU analog
    of the reference's runtime shape tensor. A mid-graph computed
    actual_shape (not a feed) raises at lowering."""
    helper = LayerHelper("reshape", name=name, act=act)
    if actual_shape is not None and not hasattr(actual_shape, 'name'):
        # python list/tuple/ndarray: a fully static override
        shape = [int(s) for s in actual_shape]
    new_shape = []
    for i, s in enumerate(shape):
        if s == 0:
            new_shape.append(x.shape[i])
        else:
            new_shape.append(s)
    if -1 in new_shape:
        known = _prod([s for s in new_shape if s > 0])
        total = _prod(x.shape)
        idx = new_shape.index(-1)
        if all(d >= 0 for d in x.shape) and known:
            new_shape[idx] = total // known
    out = helper.create_tmp_variable(dtype=x.dtype, shape=tuple(new_shape))
    inputs = {"X": x}
    if actual_shape is not None and hasattr(actual_shape, 'name'):
        inputs["Shape"] = actual_shape
    helper.append_op(type="reshape", inputs=inputs,
                     attrs={"shape": list(shape)}, outputs={"Out": out})
    return helper.append_activation(out)


def squeeze(input, axes=None, name=None):
    helper = LayerHelper("squeeze", name=name)
    shape = [s for i, s in enumerate(input.shape)
             if not (s == 1 and (axes is None or i in axes))]
    out = helper.create_tmp_variable(dtype=input.dtype, shape=tuple(shape))
    helper.append_op(type="squeeze", inputs={"X": input},
                     attrs={"axes": axes or []}, outputs={"Out": out})
    return out


def unsqueeze(input, axes, name=None):
    helper = LayerHelper("unsqueeze", name=name)
    shape = list(input.shape)
    for a in sorted(axes):
        shape.insert(a, 1)
    out = helper.create_tmp_variable(dtype=input.dtype, shape=tuple(shape))
    helper.append_op(type="unsqueeze", inputs={"X": input},
                     attrs={"axes": list(axes)}, outputs={"Out": out})
    return out


def transpose(x, perm, name=None):
    if len(perm) != len(x.shape):
        raise ValueError("perm length must match input rank")
    helper = LayerHelper('transpose', name=name)
    out = helper.create_tmp_variable(
        x.dtype, shape=tuple(x.shape[p] for p in perm))
    helper.append_op(type='transpose', inputs={'X': [x]},
                     outputs={'Out': [out]}, attrs={'axis': list(perm)})
    return out


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper('split', name=name)
    input_shape = input.shape
    dim_ = dim if dim >= 0 else len(input_shape) + dim
    if isinstance(num_or_sections, int):
        num = num_or_sections
        sections = []
        seg = input_shape[dim_] // num if input_shape[dim_] > 0 else -1
        out_shapes = [tuple(s if i != dim_ else seg
                            for i, s in enumerate(input_shape))] * num
    else:
        sections = list(num_or_sections)
        num = len(sections)
        out_shapes = [tuple(s if i != dim_ else sec
                            for i, s in enumerate(input_shape))
                      for sec in sections]
    outs = [helper.create_tmp_variable(dtype=input.dtype, shape=sh)
            for sh in out_shapes]
    helper.append_op(type='split', inputs={'X': input},
                     outputs={'Out': outs},
                     attrs={'num': num if not sections else 0,
                            'sections': sections, 'axis': dim_})
    return outs


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper('matmul', name=name)
    xs = list(x.shape)
    ys = list(y.shape)
    if transpose_x and len(xs) >= 2:
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if transpose_y and len(ys) >= 2:
        ys[-1], ys[-2] = ys[-2], ys[-1]
    batch = xs[:-2] if len(xs) > 2 else (ys[:-2] if len(ys) > 2 else [])
    m = xs[-2] if len(xs) >= 2 else 1
    n = ys[-1] if len(ys) >= 2 else 1
    out_shape = tuple(batch) + ((m, n) if (len(xs) >= 2 and len(ys) >= 2)
                                else (m,) if len(xs) >= 2 else (n,))
    out = helper.create_tmp_variable(dtype=x.dtype, shape=out_shape)
    helper.append_op(type='matmul', inputs={'X': x, 'Y': y},
                     outputs={'Out': out},
                     attrs={'transpose_X': transpose_x,
                            'transpose_Y': transpose_y, 'alpha': alpha})
    return out


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    shape = tuple(input.shape[:-1]) + (k,)
    values = helper.create_tmp_variable(dtype=input.dtype, shape=shape)
    indices = helper.create_tmp_variable(dtype="int64", shape=shape)
    helper.append_op(type="top_k", inputs={"X": [input]},
                     outputs={"Out": [values], "Indices": [indices]},
                     attrs={"k": k})
    values.stop_gradient = True
    indices.stop_gradient = True
    return values, indices


def _reduce_layer(op_type):
    def layer(input, dim=None, keep_dim=False, name=None):
        helper = LayerHelper(op_type, name=name)
        if dim is None:
            shape = (1,)
        else:
            dims = [dim] if isinstance(dim, int) else list(dim)
            dims = [d if d >= 0 else d + len(input.shape) for d in dims]
            if keep_dim:
                shape = tuple(1 if i in dims else s
                              for i, s in enumerate(input.shape))
            else:
                shape = tuple(s for i, s in enumerate(input.shape)
                              if i not in dims) or (1,)
        out = helper.create_tmp_variable(dtype=input.dtype, shape=shape)
        helper.append_op(
            type=op_type, inputs={'X': input}, outputs={'Out': out},
            attrs={'dim': dim if dim is not None else 0,
                   'keep_dim': keep_dim,
                   'reduce_all': True if dim is None else False})
        return out
    layer.__name__ = op_type
    return layer


reduce_sum = _reduce_layer('reduce_sum')
reduce_mean = _reduce_layer('reduce_mean')
reduce_max = _reduce_layer('reduce_max')
reduce_min = _reduce_layer('reduce_min')
reduce_prod = _reduce_layer('reduce_prod')


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    if len(x.shape) == 1:
        axis = 0
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_tmp_variable(dtype=x.dtype, shape=x.shape)
    norm = helper.create_tmp_variable(dtype=x.dtype)
    helper.append_op(type="norm", inputs={"X": x},
                     outputs={"Out": out, "Norm": norm},
                     attrs={"axis": 1 if axis is None else axis,
                            "epsilon": epsilon})
    return out


def multiplex(inputs, index):
    helper = LayerHelper('multiplex', **{})
    if not isinstance(inputs, list) and len(inputs) < 2:
        raise ValueError("inputs should be a list object and contains at "
                         "least 2 elements.")
    out = helper.create_tmp_variable(dtype=inputs[0].dtype,
                                     shape=inputs[0].shape)
    helper.append_op(type='multiplex',
                     inputs={'X': inputs, 'Ids': index},
                     outputs={'Out': [out]})
    return out


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper('lrn', name=name)
    dtype = input.dtype
    input_shape = input.shape
    if len(input_shape) != 4:
        raise ValueError("Input's dimension size of Op(lrn) must be 4, but "
                         "received %d." % (len(input_shape)))
    mid_out = helper.create_tmp_variable(dtype=dtype, stop_gradient=True)
    lrn_out = helper.create_tmp_variable(dtype, shape=input_shape)
    helper.append_op(type="lrn", inputs={"X": input},
                     outputs={"Out": lrn_out, "MidOut": mid_out},
                     attrs={"n": n, "k": k, "alpha": alpha, "beta": beta})
    return lrn_out


def pad(x, paddings, pad_value=0., name=None):
    helper = LayerHelper('pad', name=name)
    dtype = x.dtype
    shape = tuple(
        (s + paddings[2 * i] + paddings[2 * i + 1]) if s >= 0 else -1
        for i, s in enumerate(x.shape))
    out = helper.create_tmp_variable(dtype, shape=shape)
    helper.append_op(type='pad', inputs={'X': x}, outputs={'Out': out},
                     attrs={'paddings': list(paddings),
                            'pad_value': float(pad_value)})
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32",
                 name=None):
    if epsilon > 1. or epsilon < 0.:
        raise ValueError("The value of epsilon must be between 0 and 1.")
    helper = LayerHelper("label_smooth", name=name)
    label.stop_gradient = True
    smooth_label = helper.create_tmp_variable(dtype, shape=label.shape)
    helper.append_op(type="label_smooth",
                     inputs={"X": label, "PriorDist": prior_dist}
                     if prior_dist else {"X": label},
                     outputs={"Out": smooth_label},
                     attrs={"epsilon": float(epsilon)})
    return smooth_label


def expand(x, expand_times, name=None):
    """Tile x along each dim. Parity: paddle/fluid/operators/expand_op.cc."""
    helper = LayerHelper('expand', **{})
    shape = tuple(-1 if s < 0 else s * t
                  for s, t in zip(x.shape, expand_times))
    out = helper.create_tmp_variable(dtype=x.dtype, shape=shape)
    helper.append_op(type='expand', inputs={'X': [x]},
                     outputs={'Out': [out]},
                     attrs={'expand_times': list(expand_times)})
    return out


def roi_pool(input, rois, pooled_height=1, pooled_width=1,
             spatial_scale=1.0):
    helper = LayerHelper('roi_pool', **{})
    dtype = input.dtype
    pool_out = helper.create_tmp_variable(
        dtype, shape=(-1, input.shape[1], pooled_height, pooled_width))
    argmaxes = helper.create_tmp_variable(dtype='int32',
                                          stop_gradient=True)
    helper.append_op(type="roi_pool",
                     inputs={"X": input, "ROIs": rois},
                     outputs={"Out": pool_out, "Argmax": argmaxes},
                     attrs={"pooled_height": pooled_height,
                            "pooled_width": pooled_width,
                            "spatial_scale": spatial_scale})
    return pool_out


def dice_loss(input, label, epsilon=0.00001):
    helper = LayerHelper('dice_loss', **{})
    out = helper.create_tmp_variable(dtype=input.dtype, shape=(1,))
    helper.append_op(type="dice_loss",
                     inputs={"X": input, "Label": label},
                     outputs={"Out": out},
                     attrs={"epsilon": epsilon})
    return out


def bilinear_interp(input, out_h, out_w, name=None):
    helper = LayerHelper('bilinear_interp', name=name)
    out = helper.create_tmp_variable(
        input.dtype, shape=(input.shape[0], input.shape[1], out_h, out_w))
    helper.append_op(type="bilinear_interp",
                     inputs={"X": input},
                     outputs={"Out": out},
                     attrs={"out_h": out_h, "out_w": out_w})
    return out


def gather(input, index):
    helper = LayerHelper('gather', **{})
    out = helper.create_tmp_variable(
        dtype=input.dtype,
        shape=(index.shape[0],) + tuple(input.shape[1:]))
    helper.append_op(type="gather",
                     inputs={"X": input, "Index": index},
                     outputs={"Out": out})
    return out


def im2sequence(input, filter_size=1, stride=1, padding=0, name=None):
    helper = LayerHelper('im2sequence', name=name)
    filter_size = _pair(filter_size)
    stride = _pair(stride)
    if isinstance(padding, int):
        padding = [padding, padding]
    if len(padding) == 2:
        padding.append(padding[0])
        padding.append(padding[1])
    out = helper.create_tmp_variable(dtype=input.dtype, lod_level=1)
    helper.append_op(type='im2sequence', inputs={'X': input},
                     outputs={'Out': out},
                     attrs={'kernels': filter_size, 'strides': stride,
                            'paddings': padding})
    return out


def nce(input, label, num_total_classes, sample_weight=None,
        param_attr=None, bias_attr=None, num_neg_samples=None):
    helper = LayerHelper('nce', param_attr=param_attr, bias_attr=bias_attr)
    dim = input.shape[1]
    w = helper.create_parameter(attr=helper.param_attr,
                                shape=[num_total_classes, dim],
                                dtype=input.dtype, is_bias=False)
    b = helper.create_parameter(attr=helper.bias_attr,
                                shape=[num_total_classes],
                                dtype=input.dtype, is_bias=True)
    cost = helper.create_tmp_variable(dtype=input.dtype,
                                      shape=(input.shape[0], 1))
    sample_logits = helper.create_tmp_variable(dtype=input.dtype)
    sample_labels = helper.create_tmp_variable(dtype='int64',
                                               stop_gradient=True)
    num_neg_samples = 10 if num_neg_samples is None else int(num_neg_samples)
    inputs = {'Input': input, 'Label': label, 'Weight': w, 'Bias': b}
    if sample_weight is not None:
        # per-example loss weight (reference nce layer threads it as the
        # SampleWeight input, nn.py:2966; nce_op.h scales each row's cost)
        inputs['SampleWeight'] = sample_weight
    helper.append_op(type='nce',
                     inputs=inputs,
                     outputs={'Cost': cost, 'SampleLogits': sample_logits,
                              'SampleLabels': sample_labels},
                     attrs={'num_total_classes': int(num_total_classes),
                            'num_neg_samples': num_neg_samples})
    return cost


def row_conv(input, future_context_size, param_attr=None, act=None):
    helper = LayerHelper('row_conv', param_attr=param_attr, act=act)
    dtype = input.dtype
    filter_shape = [future_context_size + 1, input.shape[-1]]
    filter_param = helper.create_parameter(attr=helper.param_attr,
                                           shape=filter_shape, dtype=dtype)
    out = helper.create_tmp_variable(dtype, shape=input.shape,
                                     lod_level=input.lod_level)
    helper.append_op(type='row_conv',
                     inputs={'X': [input], 'Filter': [filter_param]},
                     outputs={'Out': [out]})
    return helper.append_activation(out)


# ---- sequence layers (kernels in ops/sequence_ops.py) ---------------------------
def sequence_conv(input, num_filters, filter_size=3, filter_stride=1,
                  padding=None, bias_attr=None, param_attr=None, act=None):
    helper = LayerHelper('sequence_conv', param_attr=param_attr,
                         bias_attr=bias_attr, act=act)
    dtype = input.dtype
    filter_shape = [filter_size * input.shape[-1], num_filters]
    filter_param = helper.create_parameter(attr=helper.param_attr,
                                           shape=filter_shape, dtype=dtype)
    pre_bias = helper.create_tmp_variable(
        dtype, shape=tuple(input.shape[:-1]) + (num_filters,), lod_level=1)
    helper.append_op(type='sequence_conv',
                     inputs={'X': [input], 'Filter': [filter_param]},
                     outputs={'Out': pre_bias},
                     attrs={'contextStride': filter_stride,
                            'contextStart': -int(filter_size // 2),
                            'contextLength': filter_size})
    pre_act = helper.append_bias_op(pre_bias, dim_start=len(
        pre_bias.shape) - 1)
    return helper.append_activation(pre_act)


def sequence_pool(input, pool_type):
    helper = LayerHelper('sequence_pool', **{})
    dtype = input.dtype
    if getattr(input, 'lod_level', 1) >= 2:
        # pooling drops the innermost LoD level: still a sequence (now
        # level-1) of rows with the same feature dims — the declared
        # [-1, feat...] shape is unchanged, only the lod level drops
        pool_out = helper.create_tmp_variable(dtype, shape=input.shape,
                                              lod_level=1)
    else:
        out_shape = (input.shape[0],) + tuple(input.shape[2:]) \
            if len(input.shape) > 2 else input.shape
        pool_out = helper.create_tmp_variable(dtype, shape=out_shape)
    max_index = helper.create_tmp_variable(dtype='int32',
                                           stop_gradient=True)
    helper.append_op(type="sequence_pool",
                     inputs={"X": input},
                     outputs={"Out": pool_out, "MaxIndex": max_index},
                     attrs={"pooltype": pool_type.upper()})
    return pool_out


def sequence_first_step(input):
    return sequence_pool(input=input, pool_type="first")


def sequence_last_step(input):
    return sequence_pool(input=input, pool_type="last")


def sequence_softmax(input, param_attr=None, bias_attr=None,
                     use_cudnn=True):
    helper = LayerHelper('sequence_softmax', **{})
    out = helper.create_tmp_variable(dtype=input.dtype, shape=input.shape,
                                     lod_level=input.lod_level)
    helper.append_op(type="sequence_softmax", inputs={"X": input},
                     outputs={"Out": out})
    return out


def sequence_expand(x, y, name=None):
    helper = LayerHelper('sequence_expand', name=name)
    out = helper.create_tmp_variable(dtype=x.dtype, shape=x.shape,
                                     lod_level=max(1, y.lod_level))
    helper.append_op(type='sequence_expand', inputs={'X': x, 'Y': y},
                     outputs={'Out': out})
    return out


def sequence_reshape(input, new_dim):
    helper = LayerHelper('sequence_reshape', **{})
    out = helper.create_tmp_variable(
        dtype=input.dtype,
        shape=tuple(input.shape[:-1]) + (new_dim,), lod_level=1)
    helper.append_op(type='sequence_reshape', inputs={'X': [input]},
                     outputs={'Out': [out]},
                     attrs={'new_dim': new_dim})
    return out


def lod_reset(x, y=None, target_lod=None):
    helper = LayerHelper('lod_reset', **{})
    out = helper.create_tmp_variable(dtype=x.dtype, shape=x.shape,
                                     lod_level=1)
    if y is not None:
        helper.append_op(type="lod_reset", inputs={'X': x, 'Y': y},
                         outputs={'Out': out})
    elif target_lod is not None:
        helper.append_op(type="lod_reset", inputs={'X': x},
                         attrs={'target_lod': list(target_lod)},
                         outputs={'Out': out})
    else:
        raise ValueError("y and target_lod should not be both None.")
    return out


# ---- RNN layers (kernels in ops/rnn_ops.py) -------------------------------------
def dynamic_lstm(input, size, param_attr=None, bias_attr=None,
                 use_peepholes=True, is_reverse=False,
                 gate_activation='sigmoid', cell_activation='tanh',
                 candidate_activation='tanh', dtype='float32', name=None):
    helper = LayerHelper('lstm', param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    size = size // 4
    weight = helper.create_parameter(attr=helper.param_attr,
                                     shape=[size, 4 * size], dtype=dtype)
    bias_size = [1, 7 * size] if use_peepholes else [1, 4 * size]
    bias = helper.create_parameter(attr=helper.bias_attr, shape=bias_size,
                                   dtype=dtype, is_bias=True)
    hidden = helper.create_tmp_variable(
        dtype, shape=tuple(input.shape[:-1]) + (size,), lod_level=1)
    cell = helper.create_tmp_variable(
        dtype, shape=tuple(input.shape[:-1]) + (size,), lod_level=1)
    batch_gate = helper.create_tmp_variable(dtype, stop_gradient=True)
    batch_cell_pre_act = helper.create_tmp_variable(dtype,
                                                    stop_gradient=True)
    helper.append_op(
        type='dynamic_lstm',
        inputs={'Input': input, 'Weight': weight, 'Bias': bias},
        outputs={'Hidden': hidden, 'Cell': cell, 'BatchGate': batch_gate,
                 'BatchCellPreAct': batch_cell_pre_act},
        attrs={'use_peepholes': use_peepholes, 'is_reverse': is_reverse,
               'gate_activation': gate_activation,
               'cell_activation': cell_activation,
               'candidate_activation': candidate_activation})
    return hidden, cell


def dynamic_lstmp(input, size, proj_size, param_attr=None, bias_attr=None,
                  use_peepholes=True, is_reverse=False,
                  gate_activation='sigmoid', cell_activation='tanh',
                  candidate_activation='tanh', proj_activation='tanh',
                  dtype='float32', name=None):
    helper = LayerHelper('lstmp', param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    size = size // 4
    weight = helper.create_parameter(attr=helper.param_attr,
                                     shape=[proj_size, 4 * size],
                                     dtype=dtype)
    proj_weight = helper.create_parameter(attr=helper.param_attr,
                                          shape=[size, proj_size],
                                          dtype=dtype)
    bias_size = [1, 7 * size] if use_peepholes else [1, 4 * size]
    bias = helper.create_parameter(attr=helper.bias_attr, shape=bias_size,
                                   dtype=dtype, is_bias=True)
    projection = helper.create_tmp_variable(
        dtype, shape=tuple(input.shape[:-1]) + (proj_size,), lod_level=1)
    cell = helper.create_tmp_variable(
        dtype, shape=tuple(input.shape[:-1]) + (size,), lod_level=1)
    helper.append_op(
        type='dynamic_lstmp',
        inputs={'Input': input, 'Weight': weight,
                'ProjWeight': proj_weight, 'Bias': bias},
        outputs={'Projection': projection, 'Cell': cell},
        attrs={'use_peepholes': use_peepholes, 'is_reverse': is_reverse,
               'gate_activation': gate_activation,
               'cell_activation': cell_activation,
               'candidate_activation': candidate_activation,
               'proj_activation': proj_activation})
    return projection, cell


def dynamic_gru(input, size, param_attr=None, bias_attr=None,
                is_reverse=False, gate_activation='sigmoid',
                candidate_activation='tanh', h_0=None):
    helper = LayerHelper('gru', param_attr=param_attr,
                         bias_attr=None if bias_attr is False
                         else bias_attr)
    dtype = input.dtype
    weight = helper.create_parameter(attr=helper.param_attr,
                                     shape=[size, 3 * size], dtype=dtype)
    inputs = {'Input': input, 'Weight': weight}
    if bias_attr is not False:
        inputs['Bias'] = helper.create_parameter(
            attr=helper.bias_attr, shape=[1, 3 * size], dtype=dtype,
            is_bias=True)
    if h_0 is not None:
        inputs['H0'] = h_0
    hidden = helper.create_tmp_variable(
        dtype, shape=tuple(input.shape[:-1]) + (size,), lod_level=1)
    helper.append_op(type='dynamic_gru', inputs=inputs,
                     outputs={'Hidden': hidden},
                     attrs={'is_reverse': is_reverse,
                            'gate_activation': gate_activation,
                            'activation': candidate_activation})
    return hidden


def gru_unit(input, hidden, size, param_attr=None, bias_attr=None,
             activation='tanh', gate_activation='sigmoid'):
    helper = LayerHelper('gru_unit', param_attr=param_attr,
                         bias_attr=bias_attr)
    dtype = input.dtype
    size = size // 3
    weight = helper.create_parameter(attr=helper.param_attr,
                                     shape=[size, 3 * size], dtype=dtype)
    gate = helper.create_tmp_variable(dtype, shape=(input.shape[0],
                                                    3 * size))
    reset_hidden_pre = helper.create_tmp_variable(dtype)
    updated_hidden = helper.create_tmp_variable(dtype,
                                                shape=(input.shape[0],
                                                       size))
    inputs = {'Input': input, 'HiddenPrev': hidden, 'Weight': weight}
    if bias_attr is not False:
        bias_size = [1, 3 * size]
        bias = helper.create_parameter(attr=helper.bias_attr,
                                       shape=bias_size, dtype=dtype,
                                       is_bias=True)
        inputs['Bias'] = bias
    helper.append_op(type='gru_unit', inputs=inputs,
                     outputs={'Gate': gate,
                              'ResetHiddenPrev': reset_hidden_pre,
                              'Hidden': updated_hidden},
                     attrs={'activation': activation,
                            'gate_activation': gate_activation})
    return updated_hidden, reset_hidden_pre, gate


def lstm_unit(x_t, hidden_t_prev, cell_t_prev, forget_bias=0.0,
              param_attr=None, bias_attr=None, name=None):
    helper = LayerHelper('lstm_unit', name=name)
    if len(x_t.shape) != 2:
        raise ValueError("Rank of x_t must be 2.")
    size = cell_t_prev.shape[1]
    concat_out = concat_ = fc(input=[x_t, hidden_t_prev], size=4 * size,
                              param_attr=param_attr, bias_attr=bias_attr)
    cell_t = helper.create_tmp_variable(x_t.dtype,
                                        shape=(x_t.shape[0], size))
    hidden_t = helper.create_tmp_variable(x_t.dtype,
                                          shape=(x_t.shape[0], size))
    helper.append_op(type='lstm_unit',
                     inputs={"X": concat_out, "C_prev": cell_t_prev},
                     outputs={"C": cell_t, "H": hidden_t},
                     attrs={"forget_bias": forget_bias})
    return hidden_t, cell_t


# ---- CRF / CTC / decode (kernels in ops/sequence_ops.py) ------------------------
def linear_chain_crf(input, label, param_attr=None):
    helper = LayerHelper('linear_chain_crf', param_attr=param_attr)
    size = input.shape[-1]
    transition = helper.create_parameter(attr=helper.param_attr,
                                         shape=[size + 2, size],
                                         dtype=helper.input_dtype())
    alpha = helper.create_tmp_variable(dtype=helper.input_dtype())
    emission_exps = helper.create_tmp_variable(dtype=helper.input_dtype())
    transition_exps = helper.create_tmp_variable(dtype=helper.input_dtype())
    log_likelihood = helper.create_tmp_variable(dtype=helper.input_dtype())
    helper.append_op(type='linear_chain_crf',
                     inputs={"Emission": [input], "Transition": transition,
                             "Label": label},
                     outputs={"Alpha": [alpha],
                              "EmissionExps": [emission_exps],
                              "TransitionExps": transition_exps,
                              "LogLikelihood": log_likelihood})
    return log_likelihood


def crf_decoding(input, param_attr, label=None):
    helper = LayerHelper('crf_decoding', **{})
    transition = helper.get_parameter(param_attr.name)
    viterbi_path = helper.create_tmp_variable(dtype='int64', lod_level=1)
    inputs = {"Emission": [input], "Transition": transition}
    if label is not None:
        inputs["Label"] = label
    helper.append_op(type='crf_decoding', inputs=inputs,
                     outputs={"ViterbiPath": [viterbi_path]})
    return viterbi_path


def warpctc(input, label, blank=0, norm_by_times=False):
    helper = LayerHelper('warpctc', **{})
    loss_out = helper.create_tmp_variable(dtype=input.dtype,
                                          shape=(-1, 1))
    grad_out = helper.create_tmp_variable(dtype=input.dtype,
                                          stop_gradient=True)
    helper.append_op(type='warpctc',
                     inputs={'Logits': [input], 'Label': [label]},
                     outputs={'WarpCTCGrad': [grad_out],
                              'Loss': [loss_out]},
                     attrs={'blank': blank,
                            'norm_by_times': norm_by_times})
    return loss_out


def ctc_greedy_decoder(input, blank, name=None):
    helper = LayerHelper("ctc_greedy_decoder", name=name)
    ctc_out = helper.create_tmp_variable(dtype='int64', lod_level=1)
    helper.append_op(type="ctc_align",
                     inputs={"Input": [input]},
                     outputs={"Output": [ctc_out]},
                     attrs={"merge_repeated": True, "blank": blank})
    return ctc_out


def edit_distance(input, label, normalized=True, ignored_tokens=None,
                  name=None):
    helper = LayerHelper("edit_distance", name=name)
    edit_distance_out = helper.create_tmp_variable(dtype='float32',
                                                   shape=(-1, 1))
    sequence_num = helper.create_tmp_variable(dtype='int64', shape=(1,))
    helper.append_op(type="edit_distance",
                     inputs={"Hyps": [input], "Refs": [label]},
                     outputs={"Out": [edit_distance_out],
                              "SequenceNum": [sequence_num]},
                     attrs={"normalized": normalized,
                            "tokens": ignored_tokens or []})
    return edit_distance_out, sequence_num


def chunk_eval(input, label, chunk_scheme, num_chunk_types,
               excluded_chunk_types=None):
    helper = LayerHelper("chunk_eval", **{})
    precision = helper.create_tmp_variable(dtype="float32", shape=(1,))
    recall = helper.create_tmp_variable(dtype="float32", shape=(1,))
    f1_score = helper.create_tmp_variable(dtype="float32", shape=(1,))
    num_infer_chunks = helper.create_tmp_variable(dtype="int64", shape=(1,))
    num_label_chunks = helper.create_tmp_variable(dtype="int64", shape=(1,))
    num_correct_chunks = helper.create_tmp_variable(dtype="int64",
                                                    shape=(1,))
    helper.append_op(type="chunk_eval",
                     inputs={"Inference": [input], "Label": [label]},
                     outputs={"Precision": [precision], "Recall": [recall],
                              "F1-Score": [f1_score],
                              "NumInferChunks": [num_infer_chunks],
                              "NumLabelChunks": [num_label_chunks],
                              "NumCorrectChunks": [num_correct_chunks]},
                     attrs={"num_chunk_types": num_chunk_types,
                            "chunk_scheme": chunk_scheme,
                            "excluded_chunk_types":
                                excluded_chunk_types or []})
    return (precision, recall, f1_score, num_infer_chunks,
            num_label_chunks, num_correct_chunks)


def beam_search(pre_ids, ids, scores, beam_size, end_id, level=0):
    helper = LayerHelper('beam_search', **{})
    score_type = scores.dtype
    id_type = ids.dtype
    selected_scores = helper.create_tmp_variable(dtype=score_type,
                                                 lod_level=2)
    selected_ids = helper.create_tmp_variable(dtype=id_type, lod_level=2)
    # TPU design: parent beam slots are an explicit output (the reference
    # recovers parentage from LoD offsets); beam_search_decode consumes it
    parent_idx = helper.create_tmp_variable(dtype='int32')
    helper.append_op(type='beam_search',
                     inputs={'pre_ids': pre_ids, 'ids': ids,
                             'scores': scores},
                     outputs={'selected_ids': selected_ids,
                              'selected_scores': selected_scores,
                              'parent_idx': parent_idx},
                     attrs={'level': level, 'beam_size': beam_size,
                            'end_id': end_id})
    selected_ids.parent_idx = parent_idx
    return selected_ids, selected_scores


def beam_search_decode(ids, scores, parents=None, name=None):
    """ids/scores: tensor arrays (array_write once per step). parents:
    the matching array of parent_idx outputs from beam_search (required
    by the static-shape backtracking kernel)."""
    helper = LayerHelper('beam_search_decode', name=name)
    sentence_ids = helper.create_tmp_variable(dtype=ids.dtype, lod_level=2)
    sentence_scores = helper.create_tmp_variable(dtype=scores.dtype,
                                                 lod_level=2)
    inputs = {"Ids": ids, "Scores": scores}
    if parents is not None:
        inputs["Parents"] = parents
    helper.append_op(type="beam_search_decode",
                     inputs=inputs,
                     outputs={"SentenceIds": sentence_ids,
                              "SentenceScores": sentence_scores})
    return sentence_ids, sentence_scores


# ---- long-tail losses / pooling variants (ops/misc_ops.py kernels) ------------
def _simple_loss(op_type, inputs, dtype, shape=None, attrs=None,
                 extra_outs=()):
    helper = LayerHelper(op_type, **{})
    out = helper.create_tmp_variable(dtype=dtype, shape=shape)
    outputs = {'Out': [out]}
    for slot in extra_outs:
        outputs[slot] = [helper.create_tmp_variable(dtype=dtype)]
    helper.append_op(type=op_type, inputs=inputs, outputs=outputs,
                     attrs=attrs or {})
    return out


def hinge_loss(input, label):
    """Parity: hinge_loss_op.cc — L = max(0, 1 - input*(2*label-1))."""
    helper = LayerHelper('hinge_loss', **{})
    out = helper.create_tmp_variable(dtype=input.dtype, shape=input.shape)
    helper.append_op(type='hinge_loss',
                     inputs={'Logits': [input], 'Labels': [label]},
                     outputs={'Loss': [out]})
    return out


def huber_loss(input, label, delta=1.0):
    """Parity: huber_loss_op.cc."""
    return _simple_loss('huber_loss', {'X': [input], 'Y': [label]},
                        input.dtype, input.shape, {'delta': float(delta)},
                        extra_outs=('Residual',))


def log_loss(input, label, epsilon=1e-4, name=None):
    """Parity: log_loss_op.cc."""
    helper = LayerHelper('log_loss', name=name)
    out = helper.create_tmp_variable(dtype=input.dtype, shape=input.shape)
    helper.append_op(type='log_loss',
                     inputs={'Predicted': [input], 'Labels': [label]},
                     outputs={'Loss': [out]},
                     attrs={'epsilon': float(epsilon)})
    return out


def rank_loss(label, left, right, name=None):
    """Parity: rank_loss_op.cc (RankNet pairwise loss)."""
    helper = LayerHelper('rank_loss', name=name)
    out = helper.create_tmp_variable(dtype=left.dtype, shape=left.shape)
    helper.append_op(type='rank_loss',
                     inputs={'Label': [label], 'Left': [left],
                             'Right': [right]},
                     outputs={'Out': [out]})
    return out


def margin_rank_loss(label, left, right, margin=0.1, name=None):
    """Parity: margin_rank_loss_op.cc — relu(-label*(left-right)+margin)."""
    helper = LayerHelper('margin_rank_loss', name=name)
    out = helper.create_tmp_variable(dtype=left.dtype, shape=left.shape)
    act = helper.create_tmp_variable(dtype=left.dtype)
    helper.append_op(type='margin_rank_loss',
                     inputs={'Label': [label], 'X1': [left], 'X2': [right]},
                     outputs={'Out': [out], 'Activated': [act]},
                     attrs={'margin': float(margin)})
    return out


def modified_huber_loss(input, label):
    """Parity: modified_huber_loss_op.cc."""
    return _simple_loss('modified_huber_loss',
                        {'X': [input], 'Y': [label]},
                        input.dtype, input.shape,
                        extra_outs=('IntermediateVal',))


def squared_l2_distance(x, y):
    """Parity: squared_l2_distance_op.cc — rowwise ||x-y||^2, shape [N,1]."""
    return _simple_loss('squared_l2_distance', {'X': [x], 'Y': [y]},
                        x.dtype, (x.shape[0], 1),
                        extra_outs=('sub_result',))


def squared_l2_norm(x):
    """Parity: squared_l2_norm_op.cc — sum(x^2), shape [1]."""
    return _simple_loss('squared_l2_norm', {'X': [x]}, x.dtype, (1,))


def l1_norm(x):
    """Parity: l1_norm_op.cc — sum(|x|), shape [1]."""
    return _simple_loss('l1_norm', {'X': [x]}, x.dtype, (1,))


def prelu(x, mode='all', param_attr=None, name=None):
    """Parity: prelu_op.cc. mode: 'all' one alpha; 'channel' per-channel."""
    helper = LayerHelper('prelu', param_attr=param_attr, name=name)
    if mode == 'channel' and len(x.shape) > 1:
        alpha_shape = [x.shape[1]]
    else:
        alpha_shape = [1]
    from ..initializer import Constant
    alpha = helper.create_parameter(attr=helper.param_attr,
                                    shape=alpha_shape, dtype=x.dtype,
                                    is_bias=False,
                                    default_initializer=Constant(0.25))
    out = helper.create_tmp_variable(dtype=x.dtype, shape=x.shape)
    helper.append_op(type='prelu',
                     inputs={'X': [x], 'Alpha': [alpha]},
                     outputs={'Out': [out]})
    return out


def maxout(x, groups, name=None):
    """Parity: maxout_op.cc — NCHW, C_out = C // groups."""
    helper = LayerHelper('maxout', name=name)
    n, c, h, w = x.shape
    out = helper.create_tmp_variable(dtype=x.dtype,
                                     shape=(n, c // groups, h, w))
    helper.append_op(type='maxout', inputs={'X': [x]},
                     outputs={'Out': [out]}, attrs={'groups': groups})
    return out


def max_pool2d_with_index(x, pool_size, pool_stride=1, pool_padding=0,
                          global_pooling=False, name=None):
    """Parity: pool_with_index_op.cc — returns (out, mask of argmax h*W+w)."""
    helper = LayerHelper('max_pool2d_with_index', name=name)
    ksize = [pool_size, pool_size] if isinstance(pool_size, int) \
        else list(pool_size)
    strides = [pool_stride, pool_stride] if isinstance(pool_stride, int) \
        else list(pool_stride)
    paddings = [pool_padding, pool_padding] \
        if isinstance(pool_padding, int) else list(pool_padding)
    n, c, h, w = x.shape
    if global_pooling:
        ho = wo = 1
    else:
        ho = _conv_out(h, ksize[0], paddings[0], strides[0])
        wo = _conv_out(w, ksize[1], paddings[1], strides[1])
    out = helper.create_tmp_variable(dtype=x.dtype, shape=(n, c, ho, wo))
    mask = helper.create_tmp_variable(dtype='int32', shape=(n, c, ho, wo),
                                      stop_gradient=True)
    helper.append_op(type='max_pool2d_with_index',
                     inputs={'X': [x]},
                     outputs={'Out': [out], 'Mask': [mask]},
                     attrs={'ksize': ksize, 'strides': strides,
                            'paddings': paddings,
                            'global_pooling': global_pooling})
    return out, mask


def unpool(x, indices, pool_size, pool_stride=1, pool_padding=0, name=None):
    """Parity: unpool_op.cc — max-unpool via recorded indices."""
    helper = LayerHelper('unpool', name=name)
    ksize = [pool_size, pool_size] if isinstance(pool_size, int) \
        else list(pool_size)
    strides = [pool_stride, pool_stride] if isinstance(pool_stride, int) \
        else list(pool_stride)
    paddings = [pool_padding, pool_padding] \
        if isinstance(pool_padding, int) else list(pool_padding)
    n, c, ho, wo = x.shape
    out_h = (ho - 1) * strides[0] - 2 * paddings[0] + ksize[0]
    out_w = (wo - 1) * strides[1] - 2 * paddings[1] + ksize[1]
    out = helper.create_tmp_variable(dtype=x.dtype,
                                     shape=(n, c, out_h, out_w))
    helper.append_op(type='unpool',
                     inputs={'X': [x], 'Indices': [indices]},
                     outputs={'Out': [out]},
                     attrs={'ksize': ksize, 'strides': strides,
                            'paddings': paddings,
                            'unpooling_type': 'max'})
    return out


def spp(x, pyramid_height, pool_type='max', name=None):
    """Parity: spp_op.cc — spatial pyramid pooling to
    [N, C * sum(4^level)]."""
    helper = LayerHelper('spp', name=name)
    n, c = x.shape[0], x.shape[1]
    width = c * sum(4 ** l for l in range(pyramid_height))
    out = helper.create_tmp_variable(dtype=x.dtype, shape=(n, width))
    helper.append_op(type='spp', inputs={'X': [x]},
                     outputs={'Out': [out]},
                     attrs={'pyramid_height': pyramid_height,
                            'pooling_type': pool_type})
    return out


def flash_attention(q, k, v, num_heads=1, causal=True, num_kv_heads=None,
                    head_dim=None, window=None, name=None):
    """Multi-head scaled-dot-product attention on the Pallas flash
    kernel (paddle_tpu-native addition; the reference's composite is
    nets.scaled_dot_product_attention). q: [B, T, num_heads * dh], k and
    v: [B, T, num_kv_heads * dh]; ``dh`` is ``head_dim``, or q's width
    over ``num_heads`` without it; ``num_kv_heads`` (default
    ``num_heads``) query heads share a KV head in runs of num_heads /
    num_kv_heads. ``window`` (causal only; None: none): a query attends
    to the ``window`` keys up to and with its own position, j <= i and
    i - j < window; a model's window layers and full layers are this
    one op with and without it. Engages the blockwise Mosaic kernel on
    TPU at long sequence lengths and the identical-math XLA reference
    elsewhere (ops/pallas_kernels.py engagement policy); the engaged
    kernels skip the tiles a window leaves dead as they skip those
    above the diagonal, so a window layer costs its band
    (``compiler.passes.flash_counts(by=('route', 'window'))`` says
    which route a windowed lowering took)."""
    helper = LayerHelper('flash_attention', **locals())
    kv_heads = int(num_kv_heads or num_heads)
    if num_heads % kv_heads:
        raise ValueError('flash_attention: num_heads %d is not a multiple '
                         'of num_kv_heads %d' % (num_heads, kv_heads))
    if window is not None and (window < 1 or not causal):
        raise ValueError('flash_attention: window %r needs causal '
                         'attention and at least one key' % (window,))
    out = helper.create_tmp_variable(dtype=q.dtype, shape=q.shape)
    helper.append_op(
        type='flash_attention',
        inputs={'Q': q, 'K': k, 'V': v},
        outputs={'Out': out},
        attrs={'num_heads': num_heads, 'causal': causal,
               'num_kv_heads': kv_heads, 'head_dim': int(head_dim or 0),
               'window': int(window or 0)})
    return out


def rms_norm(input, epsilon=1e-05, begin_norm_axis=1, group_size=None,
             param_attr=None, name=None):
    """x / sqrt(mean(x^2) + epsilon) * w over the dims from
    ``begin_norm_axis`` (paddle_tpu-native addition). ``group_size``
    norms each run of that many channels apart (a gated norm whose
    groups lie on different tensor-parallel chips). Statistics in
    float32; the output keeps the input's dtype, as layer_norm."""
    helper = LayerHelper('rms_norm', param_attr=param_attr, name=name)
    width = _prod(input.shape[begin_norm_axis:])
    if group_size and width % int(group_size):
        raise ValueError('rms_norm: group_size %d does not divide %d'
                         % (group_size, width))
    scale = helper.create_parameter(
        attr=helper.param_attr, shape=[width], dtype=input.dtype,
        default_initializer=Constant(1.0))
    out = helper.create_tmp_variable(input.dtype, shape=input.shape)
    helper.append_op(type='rms_norm', inputs={'X': input, 'Scale': scale},
                     outputs={'Y': out},
                     attrs={'epsilon': epsilon,
                            'begin_norm_axis': begin_norm_axis,
                            'group_size': int(group_size or 0)})
    return out


def causal_conv1d(input, filter_size, act=None, param_attr=None,
                  bias_attr=None, name=None):
    """Causal depthwise conv over time on [B, T, C]: channel c at step t
    reads its own last ``filter_size`` steps (zeros before the start).
    Filter [C, filter_size], bias [C] unless ``bias_attr`` is False;
    ``act`` 'silu' or None."""
    helper = LayerHelper('causal_conv1d', param_attr=param_attr,
                         bias_attr=bias_attr, name=name)
    C = int(input.shape[-1])
    inputs = {'X': input, 'Filter': helper.create_parameter(
        attr=helper.param_attr, shape=[C, int(filter_size)],
        dtype=input.dtype)}
    if bias_attr is not False:
        inputs['Bias'] = helper.create_parameter(
            attr=helper.bias_attr, shape=[C], dtype=input.dtype,
            is_bias=True)
    out = helper.create_tmp_variable(input.dtype, shape=input.shape)
    helper.append_op(type='causal_conv1d', inputs=inputs,
                     outputs={'Out': out}, attrs={'act': act or ''})
    return out


def ssd_scan(x, dt, b, c, num_heads, head_dim, state_size, n_groups=1,
             chunk_size=128, name=None):
    """The selective scan of a Mamba-2 mixer, in chunks (state-space
    duality): dt = softplus(dt + dt_bias), S_t = exp(dt_t A) S_{t-1} +
    dt_t x_t (x) B_t with A = -exp(A_log) a head, y_t = S_t C_t + D x_t.
    x [B, T, num_heads * head_dim], dt [B, T, num_heads], b and c
    [B, T, n_groups * state_size]; head h reads group h // (num_heads /
    n_groups). Creates A_log, D, dt_bias [num_heads]. The decays, dt
    and the state carried between chunks stay float32 under AMP."""
    helper = LayerHelper('ssd_scan', name=name)
    if num_heads % n_groups:
        raise ValueError('ssd_scan: num_heads %d is not a multiple of '
                         'n_groups %d' % (num_heads, n_groups))

    def head_param(value):
        from ..param_attr import ParamAttr
        return helper.create_parameter(
            attr=ParamAttr(), shape=[num_heads], dtype='float32',
            default_initializer=Constant(value))

    # dt_bias: softplus^-1(0.01), the geometric middle of Mamba-2's
    # time-step range [0.001, 0.1]
    inputs = {'X': x, 'Dt': dt, 'B': b, 'C': c,
              'ALog': head_param(0.0), 'D': head_param(1.0),
              'DtBias': head_param(-4.6002)}
    out = helper.create_tmp_variable(x.dtype, shape=x.shape)
    helper.append_op(type='ssd_scan', inputs=inputs, outputs={'Out': out},
                     attrs={'num_heads': num_heads, 'head_dim': head_dim,
                            'n_groups': n_groups, 'state_size': state_size,
                            'chunk_size': chunk_size})
    return out


def mamba2_mixer(input, num_heads, head_dim, state_size, n_groups=1,
                 conv_kernel=4, chunk_size=128, epsilon=1e-05, name=None):
    """A Mamba-2 mixer (Dao & Gu, arXiv:2405.21060) on [B, T, D], from
    the layers above: [z | xBC | dt] = x W_in; xBC = silu(causal conv);
    y = ssd_scan(x', dt, B, C); y = group_rms_norm(y silu(z)), one norm
    a group; out = y W_out. No bias but the conv's. ``num_heads`` and
    ``n_groups`` are what this chip holds of them."""
    inner, gn = num_heads * head_dim, n_groups * state_size
    zxbcdt = fc(input, 2 * inner + 2 * gn + num_heads, num_flatten_dims=2,
                bias_attr=False)
    z, xbc, dt = split(zxbcdt, [inner, inner + 2 * gn, num_heads], dim=2)
    xbc = causal_conv1d(xbc, conv_kernel, act='silu')
    xs, b, c = split(xbc, [inner, gn, gn], dim=2)
    y = ssd_scan(xs, dt, b, c, num_heads, head_dim, state_size,
                 n_groups=n_groups, chunk_size=chunk_size)
    from .ops import swish
    y = rms_norm(y * swish(z), epsilon=epsilon, begin_norm_axis=2,
                 group_size=inner // n_groups)
    return fc(y, int(input.shape[-1]), num_flatten_dims=2, bias_attr=False)


def rotary_embedding(input, head_dim, base=10000.0, name=None):
    """Rotary position embedding (Su et al., arXiv:2104.09864) of
    [B, T, heads * head_dim] at positions 0..T-1: every head's
    ``head_dim`` dimensions, paired half against half (dimension i with
    i + head_dim / 2: the rotate_half form), turned by t *
    base^(-2 i / head_dim). No parameter. The angles and the rotation
    are float32 under AMP; the output keeps the input's dtype. Apply it
    to q and k before ``flash_attention`` (paddle_tpu-native addition)."""
    helper = LayerHelper('rotary_embedding', name=name)
    width = int(input.shape[-1])
    if head_dim % 2 or width % head_dim:
        raise ValueError('rotary_embedding: head_dim %d must be even and '
                         'divide the width %d' % (head_dim, width))
    out = helper.create_tmp_variable(input.dtype, shape=input.shape)
    helper.append_op(type='rotary_embedding', inputs={'X': input},
                     outputs={'Out': out},
                     attrs={'head_dim': int(head_dim), 'base': float(base)})
    return out


def router_scores(input, num_experts, param_attr=None, name=None):
    """Router of a mixture of experts: sigmoid(x W) over ``num_experts``
    outputs, in float32 whatever the stream's dtype (the operands take
    the MXU in bf16 under AMP, the logits do not come back rounded: the
    choice of experts is a top-k over them)."""
    helper = LayerHelper('router_scores', param_attr=param_attr, name=name)
    w = helper.create_parameter(
        attr=helper.param_attr, shape=[int(input.shape[-1]), num_experts],
        dtype='float32')
    out = helper.create_tmp_variable(
        'float32', shape=tuple(input.shape[:-1]) + (num_experts,))
    helper.append_op(type='router_scores', inputs={'X': input, 'W': w},
                     outputs={'Out': out})
    return out


def routed_experts(input, scores, hidden_size, num_experts, top_k,
                   experts_held=None, routed_scaling_factor=1.0,
                   act='relu2', name=None):
    """The routed experts of a mixture, for the experts this chip
    holds. ``scores`` [B, T, num_experts] float32 are routed over ALL
    experts: the ``top_k`` of scores + bias choose (bias: the
    ``e_score_correction_bias`` buffer [num_experts], zero, which no
    gradient reaches), the scores weigh (normalised over the chosen,
    times ``routed_scaling_factor``).
    ``experts_held`` = (first, count), default all: stacked weights
    W1 [count, D, hidden_size], W2 [count, hidden_size, D]; out = sum
    over the chosen experts held of w_e W2_e relu(W1_e x)^2 (``act``
    'relu2'), or of the gated expert w_e W2_e (silu(W1_e x) * (W3_e x))
    (``act`` 'swiglu': a third stacked weight W3 [count, D, hidden_size],
    created after W1; three grouped products forward, nine with the
    gradients, through the same kernels). The activation, the gate's
    product and the sum over experts are float32 under AMP. No token is
    dropped whatever the routing. Returns (out, tokens_per_expert
    [count] int32). What the experts held elsewhere add is their
    chips' to compute and an exchange's to sum.

    The grouped products over the held experts run in the Pallas
    grouped matmul (ops/pallas_kernels.py::grouped_matmul) on a TPU
    backend when their operands are bf16 (AMP) and D and ``hidden_size``
    are multiples of 128, and in ``lax.ragged_dot`` everywhere else;
    nothing here chooses. ``compiler.passes.moe_counts()`` says which
    route a lowering took."""
    helper = LayerHelper('routed_experts', name=name)
    from ..param_attr import ParamAttr
    first, count = experts_held or (0, num_experts)
    if first < 0 or count < 1 or first + count > num_experts:
        raise ValueError('routed_experts: experts_held %r outside 0..%d'
                         % ((first, count), num_experts))
    if act not in ('relu2', 'swiglu'):
        raise ValueError("routed_experts: act %r is neither 'relu2' nor "
                         "'swiglu'" % (act,))
    D = int(input.shape[-1])
    inputs = {'X': input, 'Scores': scores}
    inputs['W1'] = helper.create_parameter(
        attr=ParamAttr(), shape=[count, D, hidden_size], dtype='float32')
    if act == 'swiglu':
        inputs['W3'] = helper.create_parameter(
            attr=ParamAttr(), shape=[count, D, hidden_size],
            dtype='float32')
    inputs['W2'] = helper.create_parameter(
        attr=ParamAttr(), shape=[count, hidden_size, D], dtype='float32')
    bias = helper.create_parameter(
        attr=ParamAttr(trainable=False), shape=[num_experts],
        dtype='float32', default_initializer=Constant(0.0))
    bias.stop_gradient = True
    inputs['Bias'] = bias
    out = helper.create_tmp_variable(input.dtype, shape=input.shape)
    tokens = helper.create_tmp_variable('int32', shape=(count,),
                                        stop_gradient=True)
    helper.append_op(
        type='routed_experts', inputs=inputs,
        outputs={'Out': out, 'TokensPerExpert': tokens},
        attrs={'num_experts': num_experts, 'top_k': top_k,
               'first_expert': first, 'held': count,
               'routed_scaling_factor': float(routed_scaling_factor),
               'act': act})
    return out, tokens


def sequence_concat(input, name=None):
    """Concatenate corresponding sequences along time. Parity:
    operators/sequence_concat_op.cc (axis-0, level-0 concat of LoD
    tensors)."""
    helper = LayerHelper('sequence_concat', **locals())
    out = helper.create_tmp_variable(
        dtype=helper.input_dtype(input_param_name='input'),
        shape=input[0].shape, lod_level=input[0].lod_level)
    helper.append_op(type='sequence_concat', inputs={'X': input},
                     outputs={'Out': out})
    return out
