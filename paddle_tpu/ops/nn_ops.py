"""Neural-net kernels: conv/pool/norm/losses/dropout/metrics.

Parity: paddle/fluid/operators/{conv,pool,batch_norm,layer_norm,lrn,softmax,
cross_entropy,dropout,accuracy,auc,...}_op.* — all lowered to XLA HLO that
maps onto the MXU (convs as conv_general_dilated, losses fused into the
surrounding graph).
"""
import jax
import jax.numpy as jnp

from ..core.registry import register_kernel
from .common import unwrap, rewrap, f32


def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v), int(v))


@register_kernel('conv2d')
@register_kernel('depthwise_conv2d')
def _conv2d(ctx):
    """NCHW conv. groups/dilation per operators/conv_op.cc. bf16-friendly:
    dtype follows the input; XLA tiles onto the MXU."""
    x = unwrap(ctx.input('Input'))
    w = unwrap(ctx.input('Filter'))
    strides = _pair(ctx.attr('strides', [1, 1]))
    pads = _pair(ctx.attr('paddings', [0, 0]))
    dilations = _pair(ctx.attr('dilations', [1, 1]))
    groups = ctx.attr('groups', 1) or 1
    if ctx.op.type == 'depthwise_conv2d':
        groups = x.shape[1]
    from ..core.amp import mxu_compute, conv_layout
    nhwc = conv_layout() == 'NHWC'

    def conv(a, b):
        # NHWC: channels-last on the TPU lanes; XLA cancels the
        # transposes between back-to-back convs, leaving boundary ones
        if nhwc:
            a, b = a.transpose(0, 2, 3, 1), b.transpose(2, 3, 1, 0)
        out = jax.lax.conv_general_dilated(
            a, b, window_strides=strides,
            padding=[(pads[0], pads[0]), (pads[1], pads[1])],
            rhs_dilation=dilations, feature_group_count=groups,
            dimension_numbers=('NHWC', 'HWIO', 'NHWC') if nhwc
            else ('NCHW', 'OIHW', 'NCHW'))
        return out.transpose(0, 3, 1, 2) if nhwc else out

    ctx.set_output('Output', mxu_compute(conv, x, w))


@register_kernel('conv2d_transpose')
def _conv2d_transpose(ctx):
    x = unwrap(ctx.input('Input'))
    w = unwrap(ctx.input('Filter'))  # [in_c, out_c, kh, kw]
    strides = _pair(ctx.attr('strides', [1, 1]))
    pads = _pair(ctx.attr('paddings', [0, 0]))
    dilations = _pair(ctx.attr('dilations', [1, 1]))
    kh, kw = w.shape[2], w.shape[3]
    # grad-of-conv formulation: transposed conv == lhs-dilated conv with
    # flipped kernel (parity: conv2d_transpose_op.cc uses col2im)
    out = jax.lax.conv_general_dilated(
        x, jnp.flip(w, (2, 3)).swapaxes(0, 1),
        window_strides=(1, 1),
        padding=[(dilations[0] * (kh - 1) - pads[0],
                  dilations[0] * (kh - 1) - pads[0]),
                 (dilations[1] * (kw - 1) - pads[1],
                  dilations[1] * (kw - 1) - pads[1])],
        lhs_dilation=strides, rhs_dilation=dilations,
        dimension_numbers=('NCHW', 'OIHW', 'NCHW'))
    ctx.set_output('Output', out)


@register_kernel('pool2d')
def _pool2d(ctx):
    x = unwrap(ctx.input('X'))
    ptype = ctx.attr('pooling_type', 'max')
    ksize = _pair(ctx.attr('ksize', [2, 2]))
    strides = _pair(ctx.attr('strides', [1, 1]))
    pads = _pair(ctx.attr('paddings', [0, 0]))
    if ctx.attr('adaptive', False):
        # ref pooling.h AdaptivePool: out grid = ksize; bin edges
        # floor(i*H/out) .. ceil((i+1)*H/out)
        H, W = int(x.shape[2]), int(x.shape[3])
        oh, ow = ksize
        rows = []
        for i in range(oh):
            cols = []
            hs, he = (i * H) // oh, -((-(i + 1) * H) // oh)
            for j in range(ow):
                ws, we = (j * W) // ow, -((-(j + 1) * W) // ow)
                win = x[:, :, hs:he, ws:we]
                cols.append(win.max((2, 3)) if ptype == 'max'
                            else win.mean((2, 3)))
            rows.append(jnp.stack(cols, -1))
        ctx.set_output('Out', jnp.stack(rows, -2))
        return
    if ctx.attr('global_pooling', False):
        ksize = (x.shape[2], x.shape[3])
        strides = ksize
        pads = (0, 0)
    # ceil_mode (ref pool_op.cc PoolOutputSize): the output grid uses
    # ceil division; realized as extra bottom/right padding whose
    # clipped windows only see in-image values (exclusive counts)
    extra = (0, 0)
    if ctx.attr('ceil_mode', False):
        def _ceil_extra(sz, k, p, s):
            o = -((-(sz + 2 * p - k)) // s) + 1
            return max((o - 1) * s + k - (sz + 2 * p), 0)
        extra = (_ceil_extra(int(x.shape[2]), ksize[0], pads[0],
                             strides[0]),
                 _ceil_extra(int(x.shape[3]), ksize[1], pads[1],
                             strides[1]))
    window = (1, 1) + ksize
    strides4 = (1, 1) + strides
    padding = [(0, 0), (0, 0),
               (pads[0], pads[0] + extra[0]),
               (pads[1], pads[1] + extra[1])]
    if ptype == 'max':
        init = -jnp.inf
        out = jax.lax.reduce_window(x, init, jax.lax.max, window, strides4,
                                    padding)
    else:
        s = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides4,
                                  padding)
        if ctx.attr('exclusive', True) and (pads[0] or pads[1] or
                                            extra[0] or extra[1]):
            ones = jnp.ones_like(x)
            cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                        strides4, padding)
            out = s / cnt
        else:
            out = s / float(ksize[0] * ksize[1])
    ctx.set_output('Out', out)


@register_kernel('batch_norm')
def _batch_norm(ctx):
    """Train: batch stats + moving-average update (MeanOut/VarianceOut write
    back to the persistable stats). Test: moving stats.
    Parity: operators/batch_norm_op.cc."""
    x_in = unwrap(ctx.input('X'))
    scale = unwrap(ctx.input('Scale'))
    bias = unwrap(ctx.input('Bias'))
    mean = unwrap(ctx.input('Mean'))
    var = unwrap(ctx.input('Variance'))
    momentum = ctx.attr('momentum', 0.9)
    eps = ctx.attr('epsilon', 1e-5)
    layout = ctx.attr('data_layout', 'NCHW')
    # bf16 activation flow: statistics and the normalization math run in
    # f32 (XLA fuses the casts into the reduction/elementwise kernels,
    # so HBM traffic stays at 2 bytes/elem); output returns to bf16
    bf16_io = x_in.dtype == jnp.bfloat16
    x = x_in.astype(jnp.float32) if bf16_io else x_in
    axes = tuple(i for i in range(x.ndim)
                 if i != (1 if layout == 'NCHW' and x.ndim > 2 else
                          x.ndim - 1))
    c_axis = 1 if (layout == 'NCHW' and x.ndim > 2) else x.ndim - 1
    bshape = [1] * x.ndim
    bshape[c_axis] = x.shape[c_axis]

    if ctx.is_test():
        use_mean, use_var = mean, var
    else:
        # single-pass moments (E[x^2] - E[x]^2): one fused HBM read for
        # both statistics instead of jnp.var's mean-then-deviations
        # second pass; f32 accumulation keeps it well-conditioned for
        # BN-scale data
        use_mean = jnp.mean(x, axis=axes)
        use_var = jnp.maximum(
            jnp.mean(jnp.square(x), axis=axes) - jnp.square(use_mean),
            0.0)
        new_mean = mean * momentum + use_mean * (1.0 - momentum)
        new_var = var * momentum + use_var * (1.0 - momentum)
        ctx.set_output('MeanOut', jax.lax.stop_gradient(new_mean))
        ctx.set_output('VarianceOut', jax.lax.stop_gradient(new_var))
        ctx.set_output('SavedMean', use_mean)
        ctx.set_output('SavedVariance', use_var)
    inv = jax.lax.rsqrt(use_var + eps)
    y = (x - use_mean.reshape(bshape)) * inv.reshape(bshape) * \
        scale.reshape(bshape) + bias.reshape(bshape)
    ctx.set_output('Y', y.astype(x_in.dtype) if bf16_io else y)


@register_kernel('layer_norm')
def _layer_norm(ctx):
    x_in = unwrap(ctx.input('X'))
    begin = ctx.attr('begin_norm_axis', 1)
    eps = ctx.attr('epsilon', 1e-5)
    # bf16 activation flow: statistics/normalization in f32 (casts fuse;
    # HBM traffic stays bf16), output returns to the input dtype
    bf16_io = x_in.dtype == jnp.bfloat16
    x = x_in.astype(jnp.float32) if bf16_io else x_in
    axes = tuple(range(begin, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.maximum(jnp.mean(jnp.square(x), axis=axes, keepdims=True)
                      - jnp.square(mean), 0.0)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    norm_shape = x.shape[begin:]
    if ctx.has_input('Scale'):
        y = y * unwrap(ctx.input('Scale')).reshape(norm_shape)
    if ctx.has_input('Bias'):
        y = y + unwrap(ctx.input('Bias')).reshape(norm_shape)
    ctx.set_output('Y', y.astype(x_in.dtype) if bf16_io else y)
    ctx.set_output('Mean', mean.reshape(x.shape[:begin] + (1,) * 0)
                   .reshape((-1,)))
    ctx.set_output('Variance', var.reshape((-1,)))


@register_kernel('lrn')
def _lrn(ctx):
    x = unwrap(ctx.input('X'))
    n = ctx.attr('n', 5)
    k = ctx.attr('k', 2.0)
    alpha = ctx.attr('alpha', 1e-4)
    beta = ctx.attr('beta', 0.75)
    sq = jnp.square(x)
    half = n // 2
    pad = jnp.pad(sq, [(0, 0), (half, half), (0, 0), (0, 0)])
    acc = sum(pad[:, i:i + x.shape[1]] for i in range(n))
    ctx.set_output('Out', x / jnp.power(k + alpha * acc, beta))
    ctx.set_output('MidOut', k + alpha * acc)


@register_kernel('softmax')
def _softmax(ctx):
    x = ctx.input('X')
    ctx.set_output('Out', rewrap(x, jax.nn.softmax(f32(unwrap(x)),
                                                   axis=-1)))


@register_kernel('cross_entropy')
def _cross_entropy(ctx):
    x_in = ctx.input('X')
    x = f32(unwrap(x_in))
    label = unwrap(ctx.input('Label'))
    eps = 1e-8
    if ctx.attr('soft_label', False):
        loss = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        idx = label.astype('int32')
        if idx.ndim == x.ndim:
            idx = idx.reshape(idx.shape[:-1])
        p = jnp.take_along_axis(x, idx[..., None], axis=-1)
        loss = -jnp.log(p + eps)
    from ..lod import SequenceTensor
    if isinstance(x_in, SequenceTensor):
        # padded time steps carry zero probs; zero their loss so reduced
        # costs see only real tokens (the reference never has padding —
        # its LoD layout is packed)
        T = loss.shape[1]
        m = (jnp.arange(T)[None, :] <
             jnp.asarray(x_in.lengths)[:, None])
        loss = loss * m.reshape(m.shape + (1,) * (loss.ndim - 2))\
            .astype(loss.dtype)
        ctx.set_output('Y', SequenceTensor(loss, x_in.lengths,
                                           x_in.sub_lengths))
        return
    ctx.set_output('Y', loss)


def _row_lse(x):
    """A row's float32 ``max + log sum exp(x - max)`` over the last axis,
    [..., 1]; ``x`` is read in the dtype it came in."""
    return jax.nn.logsumexp(f32(x), axis=-1, keepdims=True)


@jax.custom_vjp
def _lse_loss(x, xf, idx):
    """Hard-label softmax cross-entropy ``lse(x) - x[idx]``, [..., 1]
    float32, of logits ``x`` [..., V] at integer labels ``idx`` [...];
    ``xf`` is the caller's one widening ``f32(x)``. Nothing the size of
    the logits is made or kept beside ``x`` itself (bf16 under AMP):
    the label's logit is a gather from ``x``, the residuals are ``x``,
    its rows' ``lse`` and ``idx``, and the backward is one elementwise
    pass whose one-hot is a comparison. The cotangent goes to ``xf`` in
    float32, so it meets whatever else read ``xf`` (the Softmax output)
    before the widening's transpose rounds the sum to ``x``'s dtype,
    once, where ordinary autodiff rounds it. Autodiff of
    ``take_along_axis(log_softmax(f32(x)))`` scatters into float32 zeros
    the size of the logits and reduces them again (PERF.md, PR 36)."""
    return _lse_loss_fwd(x, xf, idx)[0]


def _lse_loss_fwd(x, xf, idx):
    lse = _row_lse(xf)
    picked = jnp.take_along_axis(x, idx[..., None], axis=-1)
    return lse - f32(picked), (x, lse, idx)


def _lse_loss_bwd(res, g):
    x, lse, idx = res
    p = jnp.exp(f32(x) - lse)
    hot = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1) \
        == idx[..., None]
    return None, (p - hot.astype(p.dtype)) * g, None


_lse_loss.defvjp(_lse_loss_fwd, _lse_loss_bwd)


@register_kernel('softmax_with_cross_entropy')
def _softmax_with_cross_entropy(ctx):
    """Hard labels take ``_lse_loss`` on the logits as they came; soft
    labels the dense ``-sum(label * log_softmax)``. Softmax is
    ``exp(x - lse)`` by ordinary autodiff either way, dropped by XLA
    where nobody reads it. A lowering that takes ``_lse_loss`` counts
    once in ``loss_lowerings_total``
    (compiler/passes.py::loss_counts)."""
    logits = unwrap(ctx.input('Logits'))
    label = unwrap(ctx.input('Label'))
    xf = f32(logits)        # widened once: its transpose is one rounding
    logp = xf - _row_lse(xf)
    if ctx.attr('soft_label', False):
        loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
    else:
        from .. import observability as _obs
        _obs.default_registry().counter(
            'loss_lowerings_total',
            help='softmax_with_cross_entropy lowerings that took the '
                 'hard-label rule: the logits kept as they came and a '
                 'row\'s float32 lse').inc()
        idx = label.astype('int32')
        if idx.ndim == logits.ndim:
            idx = idx.reshape(idx.shape[:-1])
        # a negative label counts from the end, as take_along_axis reads it
        idx = jnp.where(idx < 0, idx + logits.shape[-1], idx)
        loss = _lse_loss(logits, xf, idx)
    ctx.set_output('Softmax', jnp.exp(logp))
    ctx.set_output('Loss', loss)


@register_kernel('sigmoid_cross_entropy_with_logits')
def _sigmoid_xent(ctx):
    x = f32(unwrap(ctx.input('X')))
    label = f32(unwrap(ctx.input('Label')))
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ctx.set_output('Out', loss)


@register_kernel('dropout')
def _dropout(ctx):
    """Old-fluid semantics (operators/dropout_op.cc): train out = x * mask,
    infer out = x * (1 - p) — no inverted scaling."""
    x = ctx.input('X')
    xd = unwrap(x)
    p = ctx.attr('dropout_prob', 0.5)
    if ctx.is_test():
        ctx.set_output('Out', rewrap(x, xd * (1.0 - p)))
        return
    key = ctx.next_rng()
    mask = jax.random.bernoulli(key, 1.0 - p, xd.shape).astype(xd.dtype)
    ctx.set_output('Out', rewrap(x, xd * mask))
    if ctx.output_names('Mask'):
        ctx.set_output('Mask', mask)


@register_kernel('accuracy')
def _accuracy(ctx):
    idx = unwrap(ctx.input('Indices'))
    label = unwrap(ctx.input('Label')).astype('int32')
    label_cmp = label if label.ndim == idx.ndim else label[:, None]
    correct = jnp.any(idx.astype('int32') == label_cmp, axis=-1)
    acc = jnp.mean(correct.astype('float32')).reshape((1,))
    ctx.set_output('Accuracy', acc)
    if ctx.output_names('Correct'):
        ctx.set_output('Correct', jnp.sum(correct.astype('int32'))
                       .reshape((1,)))
    if ctx.output_names('Total'):
        ctx.set_output('Total', jnp.asarray([correct.shape[0]], 'int32'))


@register_kernel('auc')
def _auc(ctx):
    """Streaming-free single-batch AUC (trapezoidal over thresholds).
    Parity: operators/auc_op.cc."""
    probs = unwrap(ctx.input('Predict'))
    label = unwrap(ctx.input('Label')).reshape((-1,)).astype('float32')
    pos_score = probs[:, 1] if probs.ndim == 2 and probs.shape[1] > 1 \
        else probs.reshape((-1,))
    num_t = ctx.attr('num_thresholds', 200)
    th = jnp.linspace(0.0, 1.0, num_t)
    pred = pos_score[None, :] >= th[:, None]
    tp = jnp.sum(pred * label[None, :], axis=1)
    fp = jnp.sum(pred * (1 - label)[None, :], axis=1)
    pos = jnp.maximum(jnp.sum(label), 1e-6)
    neg = jnp.maximum(jnp.sum(1 - label), 1e-6)
    tpr = tp / pos
    fpr = fp / neg
    auc = -jnp.trapezoid(tpr, fpr) if hasattr(jnp, 'trapezoid') else \
        -jnp.trapz(tpr, fpr)
    ctx.set_output('AUC', jnp.abs(auc).reshape((1,)))


@register_kernel('bilinear_interp')
def _bilinear_interp(ctx):
    """Corner-aligned bilinear resize: ratio = (in-1)/(out-1), like
    bilinear_interp_op.h (jax.image.resize is half-pixel-aligned and
    diverges at every non-corner sample)."""
    x = unwrap(ctx.input('X'))
    out_h = int(ctx.attr('out_h'))
    out_w = int(ctx.attr('out_w'))
    n, c, h, w = x.shape
    ratio_h = (h - 1.0) / (out_h - 1.0) if out_h > 1 else 0.0
    ratio_w = (w - 1.0) / (out_w - 1.0) if out_w > 1 else 0.0
    sy = jnp.arange(out_h, dtype=jnp.float32) * ratio_h
    sx = jnp.arange(out_w, dtype=jnp.float32) * ratio_w
    y0 = jnp.floor(sy).astype(jnp.int32)
    x0 = jnp.floor(sx).astype(jnp.int32)
    y1 = jnp.minimum(y0 + 1, h - 1)
    x1 = jnp.minimum(x0 + 1, w - 1)
    dy = (sy - y0).reshape(1, 1, out_h, 1).astype(x.dtype)
    dx = (sx - x0).reshape(1, 1, 1, out_w).astype(x.dtype)
    # separable: vertical lerp at the narrow (.., out_h, w) size first,
    # then two column gathers — half the gather/multiply work
    rows = jnp.take(x, y0, axis=2) * (1 - dy) + \
        jnp.take(x, y1, axis=2) * dy
    ctx.set_output('Out', jnp.take(rows, x0, axis=3) * (1 - dx) +
                   jnp.take(rows, x1, axis=3) * dx)


@register_kernel('label_smooth')
def _label_smooth(ctx):
    x = unwrap(ctx.input('X'))
    eps = ctx.attr('epsilon', 0.1)
    if ctx.has_input('PriorDist'):
        prior = unwrap(ctx.input('PriorDist'))
        out = (1 - eps) * x + eps * prior
    else:
        out = (1 - eps) * x + eps / x.shape[-1]
    ctx.set_output('Out', out)


@register_kernel('dice_loss')
def _dice_loss(ctx):
    x = unwrap(ctx.input('X'))
    label = unwrap(ctx.input('Label')).astype(x.dtype)
    eps = ctx.attr('epsilon', 1e-5)
    reduce_dims = tuple(range(1, x.ndim))
    inter = 2.0 * jnp.sum(x * label, axis=reduce_dims)
    union = jnp.sum(x, axis=reduce_dims) + jnp.sum(label, axis=reduce_dims)
    ctx.set_output('Out', jnp.mean(1.0 - inter / (union + eps)).reshape((1,)))


@register_kernel('nce')
def _nce(ctx):
    """Sampled NCE loss, REFERENCE-EXACT math (operators/nce_op.h
    forward, oracled by tests/unittests/test_nce.py): per sample s the
    op takes o = sigmoid(logit(s)) and scores true samples with
    -log(o / (o + b)) and sampled negatives with -log(b / (o + b)),
    b = num_neg / num_classes — NOT the classic raw-score NCE ratio.
    Multi-column labels supported; SampleLogits are the post-sigmoid
    values [B, num_true + k]; SampleLabels = [labels..., sampled...].
    TPU-first: fixed sample count (static shape), uniform sampling."""
    x = unwrap(ctx.input('Input'))
    labels = unwrap(ctx.input('Label')).astype('int32')
    if labels.ndim == 1:
        labels = labels[:, None]
    w = unwrap(ctx.input('Weight'))
    num_neg = ctx.attr('num_neg_samples', 10)
    num_classes = ctx.attr('num_total_classes', w.shape[0])
    custom = ctx.attr('custom_neg_classes')
    if custom:
        # ref nce_op.cc custom_neg_classes attr: fixed negatives so
        # unit tests can pin the sampled set
        neg = jnp.asarray(list(custom), jnp.int32)
        num_neg = int(neg.shape[0])
    else:
        key = ctx.next_rng()
        neg = jax.random.randint(key, (num_neg,), 0, num_classes)
    b_in = unwrap(ctx.input('Bias')) if ctx.has_input('Bias') else None

    B = x.shape[0]
    # logits for the true columns [B, T] and the shared negatives [B, k]
    true_logit = jnp.einsum('bd,btd->bt', x, jnp.take(w, labels, axis=0))
    neg_logit = jnp.einsum('bd,kd->bk', x, jnp.take(w, neg, axis=0))
    if b_in is not None:
        true_logit = true_logit + jnp.take(b_in, labels)
        neg_logit = neg_logit + jnp.take(b_in, neg)[None, :]
    o_neg = jax.nn.sigmoid(neg_logit)
    bnoise = float(num_neg) / float(num_classes)
    # true-sample term in the numerically stable identity
    # -log(sig(s)/(sig(s)+b)) = logaddexp(log1p(b), log(b) - s)
    # (exact same value; the naive sigmoid-then-log form overflows to
    # inf for strongly negative logits)
    cost = jnp.logaddexp(jnp.log1p(bnoise),
                         jnp.log(bnoise) - true_logit) \
        .sum(-1, keepdims=True) \
        + (-jnp.log(bnoise / (o_neg + bnoise))).sum(-1, keepdims=True)
    if ctx.has_input('SampleWeight'):
        # nce_op.h: sample_weight[i] scales example i's whole cost row
        sw = unwrap(ctx.input('SampleWeight')).reshape((-1, 1))
        cost = cost * sw.astype(cost.dtype)
    ctx.set_output('Cost', cost)
    if ctx.output_names('SampleLogits'):
        ctx.set_output('SampleLogits',
                       jnp.concatenate([jax.nn.sigmoid(true_logit),
                                        o_neg], axis=1))
    if ctx.output_names('SampleLabels'):
        ctx.set_output('SampleLabels', jnp.concatenate(
            [labels, jnp.broadcast_to(neg[None, :], (B, num_neg))],
            axis=1))


@register_kernel('im2sequence')
def _im2sequence(ctx):
    """Image patches -> sequence. Parity: operators/im2sequence_op.cc.
    Output is a SequenceTensor [N, L, C*kh*kw] with equal lengths."""
    from ..lod import SequenceTensor
    x = unwrap(ctx.input('X'))
    kh, kw = _pair(ctx.attr('kernels', [1, 1]))
    sh, sw = _pair(ctx.attr('strides', [1, 1]))
    pads = ctx.attr('paddings', [0, 0, 0, 0])
    n, c, h, w = x.shape
    xp = jnp.pad(x, [(0, 0), (0, 0), (pads[0], pads[2]), (pads[1], pads[3])])
    oh = (xp.shape[2] - kh) // sh + 1
    ow = (xp.shape[3] - kw) // sw + 1
    patches = jax.lax.conv_general_dilated_patches(
        xp, (kh, kw), (sh, sw), 'VALID',
        dimension_numbers=('NCHW', 'OIHW', 'NCHW'))
    seq = patches.reshape(n, c * kh * kw, oh * ow).transpose(0, 2, 1)
    ctx.set_output('Out', SequenceTensor(
        seq, jnp.full((n,), oh * ow, dtype='int32')))


def _triple(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v),) * 3


@register_kernel('conv3d')
def _conv3d(ctx):
    """NCDHW conv. Parity: operators/conv_op.cc REGISTER conv3d (no
    python layer exists at this reference version; op-level parity).
    Honors the NHWC layout mode as channels-last NDHWC."""
    x = unwrap(ctx.input('Input'))
    w = unwrap(ctx.input('Filter'))
    strides = _triple(ctx.attr('strides', [1, 1, 1]))
    pads = _triple(ctx.attr('paddings', [0, 0, 0]))
    dilations = _triple(ctx.attr('dilations', [1, 1, 1]))
    groups = ctx.attr('groups', 1) or 1
    from ..core.amp import mxu_compute, conv_layout
    cl = conv_layout() == 'NHWC'

    def conv(a, b):
        if cl:
            a = a.transpose(0, 2, 3, 4, 1)
            b = b.transpose(2, 3, 4, 1, 0)
        out = jax.lax.conv_general_dilated(
            a, b, window_strides=strides,
            padding=[(p, p) for p in pads],
            rhs_dilation=dilations, feature_group_count=groups,
            dimension_numbers=('NDHWC', 'DHWIO', 'NDHWC') if cl
            else ('NCDHW', 'OIDHW', 'NCDHW'))
        return out.transpose(0, 4, 1, 2, 3) if cl else out

    ctx.set_output('Output', mxu_compute(conv, x, w))


@register_kernel('conv3d_transpose')
def _conv3d_transpose(ctx):
    """Parity: conv_transpose_op.cc conv3d_transpose — grad-of-conv
    formulation (lhs-dilated conv with flipped kernel); grouped filters
    ([in_c, out_c/g, ...]) convolve per group and concat on channels."""
    x = unwrap(ctx.input('Input'))
    w = unwrap(ctx.input('Filter'))  # [in_c, out_c/g, kd, kh, kw]
    strides = _triple(ctx.attr('strides', [1, 1, 1]))
    pads = _triple(ctx.attr('paddings', [0, 0, 0]))
    dilations = _triple(ctx.attr('dilations', [1, 1, 1]))
    groups = ctx.attr('groups', 1) or 1
    ks = w.shape[2:]
    pad = [(dilations[i] * (ks[i] - 1) - pads[i],) * 2 for i in range(3)]

    def one(xg, wg):
        return jax.lax.conv_general_dilated(
            xg, jnp.flip(wg, (2, 3, 4)).swapaxes(0, 1),
            window_strides=(1, 1, 1), padding=pad,
            lhs_dilation=strides, rhs_dilation=dilations,
            dimension_numbers=('NCDHW', 'OIDHW', 'NCDHW'))

    if groups == 1:
        out = one(x, w)
    else:
        cg = x.shape[1] // groups
        out = jnp.concatenate(
            [one(x[:, g * cg:(g + 1) * cg], w[g * cg:(g + 1) * cg])
             for g in range(groups)], axis=1)
    ctx.set_output('Output', out)


@register_kernel('pool3d')
def _pool3d(ctx):
    """Parity: pool_op.cc pool3d / math/pooling.cc 3D kernels (avg
    divides by the window clipped to the image)."""
    x = unwrap(ctx.input('X'))
    ptype = ctx.attr('pooling_type', 'max')
    ksize = _triple(ctx.attr('ksize', [2, 2, 2]))
    strides = _triple(ctx.attr('strides', [1, 1, 1]))
    pads = _triple(ctx.attr('paddings', [0, 0, 0]))
    ceil_mode = bool(ctx.attr('ceil_mode', False))
    if ctx.attr('global_pooling', False):
        ksize = x.shape[2:]
        pads = (0, 0, 0)
    dims = (1, 1) + ksize
    strd = (1, 1) + strides
    spatial_pads = [(0, 0), (0, 0)] + [(p, p) for p in pads]
    if ceil_mode:
        for i in range(3):
            in_sz = x.shape[2 + i]
            k, s, p = ksize[i], strides[i], pads[i]
            ceil_out = -(-(in_sz - k + 2 * p) // s) + 1
            floor_out = (in_sz - k + 2 * p) // s + 1
            if ceil_out > floor_out:
                lo, hi = spatial_pads[2 + i]
                spatial_pads[2 + i] = (lo, hi + s)
    if ptype == 'max':
        out = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, dims, strd, spatial_pads)
    else:
        s = jax.lax.reduce_window(
            x, 0.0, jax.lax.add, dims, strd, spatial_pads)
        if ctx.attr('exclusive', True) and any(pads):
            # divide by the window clipped to the image (pooling.cc)
            ones = jnp.ones(x.shape[:1] + (1,) + x.shape[2:], x.dtype)
            cnt = jax.lax.reduce_window(
                ones, 0.0, jax.lax.add, dims, strd, spatial_pads)
            out = s / jnp.maximum(cnt, 1.0)
        else:
            out = s / float(ksize[0] * ksize[1] * ksize[2])
    ctx.set_output('Out', out)
