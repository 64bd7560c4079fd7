"""Long-tail ops: extra losses, pooling variants, proximal optimizers.

Parity: paddle/fluid/operators/{hinge_loss,huber_loss,log_loss,rank_loss,
margin_rank_loss,modified_huber_loss,squared_l2_distance,squared_l2_norm,
l1_norm,minus,fill,prelu,maxout,pool_with_index,unpool,spp,proximal_gd,
proximal_adagrad}_op.* — elementwise formulas re-expressed as jnp traces
(XLA fuses them), window ops via lax.reduce_window / patch extraction so
they tile onto the TPU vector unit instead of the reference's per-pixel
CPU/CUDA loops.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .. import observability as _obs
from ..core.registry import register_kernel
from .common import unwrap


# ---- losses ---------------------------------------------------------------------
@register_kernel('hinge_loss')
def _hinge_loss(ctx):
    """ref hinge_loss_op.h: L = max(0, 1 - x*(2y-1))."""
    x = unwrap(ctx.input('Logits'))
    y = unwrap(ctx.input('Labels'))
    ctx.set_output('Loss', jnp.maximum(0.0, 1.0 - x * (2.0 * y - 1.0)))


@register_kernel('huber_loss')
def _huber_loss(ctx):
    """ref huber_loss_op.h: r = y - x; L = 0.5 r^2 if |r|<=d else d(|r|-d/2)."""
    x = unwrap(ctx.input('X'))
    y = unwrap(ctx.input('Y'))
    d = ctx.attr('delta', 1.0)
    r = y - x
    a = jnp.abs(r)
    loss = jnp.where(a <= d, 0.5 * r * r, d * (a - 0.5 * d))
    ctx.set_output('Residual', r)
    ctx.set_output('Out', loss)


@register_kernel('log_loss')
def _log_loss(ctx):
    """ref log_loss_op.h: L = -y log(p+eps) - (1-y) log(1-p+eps)."""
    p = unwrap(ctx.input('Predicted'))
    y = unwrap(ctx.input('Labels'))
    eps = ctx.attr('epsilon', 1e-4)
    loss = -(y * jnp.log(p + eps)) - (1.0 - y) * jnp.log(1.0 - p + eps)
    ctx.set_output('Loss', loss)


@register_kernel('rank_loss')
def _rank_loss(ctx):
    """ref rank_loss_op.h: L = log(1 + exp(l-r)) - label*(l-r), stable form."""
    label = unwrap(ctx.input('Label'))
    left = unwrap(ctx.input('Left'))
    right = unwrap(ctx.input('Right'))
    d = left - right
    ctx.set_output('Out', jnp.logaddexp(0.0, d) - label * d)


@register_kernel('margin_rank_loss')
def _margin_rank_loss(ctx):
    """ref margin_rank_loss_op.h: L = relu(-label*(x1-x2) + margin)."""
    label = unwrap(ctx.input('Label'))
    x1 = unwrap(ctx.input('X1'))
    x2 = unwrap(ctx.input('X2'))
    margin = ctx.attr('margin', 0.0)
    act = -label * (x1 - x2) + margin
    ctx.set_output('Activated', (act > 0).astype(x1.dtype))
    ctx.set_output('Out', jnp.maximum(act, 0.0))


@register_kernel('modified_huber_loss')
def _modified_huber_loss(ctx):
    """ref modified_huber_loss_op.h: a = x*(2y-1);
    L = -4a if a<-1; (1-a)^2 if -1<=a<1; 0 otherwise."""
    x = unwrap(ctx.input('X'))
    y = unwrap(ctx.input('Y'))
    a = x * (2.0 * y - 1.0)
    loss = jnp.where(a < -1.0, -4.0 * a,
                     jnp.where(a < 1.0, jnp.square(1.0 - a), 0.0))
    ctx.set_output('IntermediateVal', a)
    ctx.set_output('Out', loss)


@register_kernel('squared_l2_distance')
def _squared_l2_distance(ctx):
    """ref squared_l2_distance_op.h: rows flattened; Out[i] = ||x_i - y_i||^2.
    Y may have 1 row (broadcast)."""
    x = unwrap(ctx.input('X'))
    y = unwrap(ctx.input('Y'))
    x2 = x.reshape(x.shape[0], -1)
    y2 = y.reshape(y.shape[0], -1)
    sub = x2 - y2
    ctx.set_output('sub_result', sub)
    ctx.set_output('Out', jnp.sum(jnp.square(sub), axis=1, keepdims=True))


@register_kernel('squared_l2_norm')
def _squared_l2_norm(ctx):
    x = unwrap(ctx.input('X'))
    ctx.set_output('Out', jnp.sum(jnp.square(x)).reshape(1))


@register_kernel('l1_norm')
def _l1_norm(ctx):
    x = unwrap(ctx.input('X'))
    ctx.set_output('Out', jnp.sum(jnp.abs(x)).reshape(1))


@register_kernel('minus')
def _minus(ctx):
    ctx.set_output('Out', unwrap(ctx.input('X')) - unwrap(ctx.input('Y')))


@register_kernel('fill')
def _fill(ctx):
    """ref fill_op.cc: Out = reshape(attr value list, attr shape)."""
    from ..core.lowering import runtime_dtype
    shape = ctx.attr('shape')
    dt = runtime_dtype(ctx.attr('dtype', 'float32'))
    val = np.asarray(ctx.attr('value'), dtype=dt)
    ctx.set_output('Out', jnp.asarray(val).reshape(shape))


# ---- prelu / maxout / pooling variants ------------------------------------------
@register_kernel('prelu')
def _prelu(ctx):
    """ref prelu_op.cc: Out = x if x > 0 else alpha * x (alpha broadcasts)."""
    x = unwrap(ctx.input('X'))
    alpha = unwrap(ctx.input('Alpha'))
    a = jnp.reshape(alpha, (-1,))
    if a.shape[0] == 1:
        a = a[0]
    elif x.ndim > 1 and a.shape[0] == x.shape[1]:
        # channel-shared alpha on NCHW
        a = a.reshape((1, -1) + (1,) * (x.ndim - 2))
    ctx.set_output('Out', jnp.where(x > 0, x, a * x))


@register_kernel('maxout')
def _maxout(ctx):
    """ref math/maxouting.cc: NCHW, Out[:, c] = max over the group's feature
    maps; C_out = C / groups."""
    x = unwrap(ctx.input('X'))
    g = ctx.attr('groups')
    n, c, h, w = x.shape
    ctx.set_output('Out', jnp.max(x.reshape(n, c // g, g, h, w), axis=2))


def _pool_geometry(in_size, k, s, p, adaptive_bins=None):
    if adaptive_bins is not None:
        k = -(-in_size // adaptive_bins)
        p = (k * adaptive_bins - in_size + 1) // 2
        return k, k, p
    return k, s, p


@register_kernel('max_pool2d_with_index')
def _max_pool2d_with_index(ctx):
    """ref pool_with_index_op.* / math/pooling.cc MaxPool2dWithIndex:
    Out = max over window, Mask = flat h*W+w index of the argmax.

    TPU design: one patch extraction (conv_general_dilated_patches, which XLA
    tiles) + argmax over the window axis — no per-pixel loops.
    """
    x = unwrap(ctx.input('X'))
    kh, kw = ctx.attr('ksize')
    sh, sw = ctx.attr('strides', [1, 1])
    ph, pw = ctx.attr('paddings', [0, 0])
    if ctx.attr('global_pooling', False):
        kh, kw = x.shape[2], x.shape[3]
        ph = pw = 0
    n, c, h, w = x.shape
    # finite sentinel below any f32 activation: finfo.min would round to
    # -inf in bf16 on TPU and 0 * -inf = NaN inside the patch conv
    neg = jnp.asarray(-3.3e38, x.dtype)
    patches = lax.conv_general_dilated_patches(
        jnp.pad(x, ((0, 0), (0, 0), (ph, ph), (pw, pw))),
        (kh, kw), (sh, sw), 'VALID',
        dimension_numbers=('NCHW', 'OIHW', 'NCHW'))
    ho, wo = patches.shape[2], patches.shape[3]
    patches = patches.reshape(n, c, kh * kw, ho, wo)
    # Mask pad cells out of the argmax explicitly (the reference clips
    # windows to the image, math/pooling.cc, so Mask is always a real
    # pixel; relying on pad == dtype-min would pick padding whenever
    # data ties with it — ADVICE r1).
    ones = jnp.ones((1, 1, h, w), x.dtype)
    valid = lax.conv_general_dilated_patches(
        jnp.pad(ones, ((0, 0), (0, 0), (ph, ph), (pw, pw))),
        (kh, kw), (sh, sw), 'VALID',
        dimension_numbers=('NCHW', 'OIHW', 'NCHW'))
    valid = valid.reshape(1, 1, kh * kw, ho, wo) > 0.5
    score = jnp.where(valid, patches, neg)
    local = jnp.argmax(score, axis=2)
    out = jnp.max(score, axis=2)
    lh, lw = local // kw, local % kw
    gh = jnp.arange(ho).reshape(1, 1, ho, 1) * sh - ph + lh
    gw = jnp.arange(wo).reshape(1, 1, 1, wo) * sw - pw + lw
    # belt for degenerate fully-padded windows: clamp into the image
    gh = jnp.clip(gh, 0, h - 1)
    gw = jnp.clip(gw, 0, w - 1)
    ctx.set_output('Out', out)
    ctx.set_output('Mask', (gh * w + gw).astype(jnp.int32))


@register_kernel('unpool')
def _unpool(ctx):
    """ref unpool_op.* / math/unpooling.cc: max-unpool — scatter each pooled
    value back to its recorded flat h*W+w position in the larger map."""
    x = unwrap(ctx.input('X'))
    idx = unwrap(ctx.input('Indices')).astype(jnp.int32)
    ksize = ctx.attr('ksize')
    strides = ctx.attr('strides', [1, 1])
    paddings = ctx.attr('paddings', [0, 0])
    n, c, ho, wo = x.shape
    out_h = (ho - 1) * strides[0] - 2 * paddings[0] + ksize[0]
    out_w = (wo - 1) * strides[1] - 2 * paddings[1] + ksize[1]
    flat_x = x.reshape(n * c, ho * wo)
    flat_i = idx.reshape(n * c, ho * wo)
    out = jnp.zeros((n * c, out_h * out_w), x.dtype)
    rows = jnp.arange(n * c)[:, None]
    out = out.at[rows, flat_i].set(flat_x)
    ctx.set_output('Out', out.reshape(n, c, out_h, out_w))


@register_kernel('spp')
def _spp(ctx):
    """ref spp_op.h: spatial pyramid pool — levels 0..pyramid_height-1 with
    2^level bins each; adaptive kernel/stride/padding per level; outputs
    flattened + concatenated to [N, C * sum(4^level)]."""
    x = unwrap(ctx.input('X'))
    height = ctx.attr('pyramid_height')
    ptype = ctx.attr('pooling_type', 'max')
    n, c, h, w = x.shape
    outs = []
    for level in range(height):
        bins = 2 ** level
        kh, sh_, ph = _pool_geometry(h, None, None, None, bins)
        kw, sw_, pw = _pool_geometry(w, None, None, None, bins)
        if ptype == 'max':
            init, op = jnp.finfo(x.dtype).min, lax.max
            padded = jnp.pad(x, ((0, 0), (0, 0), (ph, ph), (pw, pw)),
                             constant_values=init)
            pooled = lax.reduce_window(padded, init, op,
                                       (1, 1, kh, kw), (1, 1, sh_, sw_),
                                       'VALID')
        else:
            padded = jnp.pad(x, ((0, 0), (0, 0), (ph, ph), (pw, pw)))
            sums = lax.reduce_window(padded, 0.0, lax.add,
                                     (1, 1, kh, kw), (1, 1, sh_, sw_),
                                     'VALID')
            # ref math/pooling.cc divides by the CLIPPED (in-image) window
            # size, not kh*kw — count real pixels per bin the same way
            ones = jnp.pad(jnp.ones((1, 1, h, w), x.dtype),
                           ((0, 0), (0, 0), (ph, ph), (pw, pw)))
            counts = lax.reduce_window(ones, 0.0, lax.add,
                                       (1, 1, kh, kw), (1, 1, sh_, sw_),
                                       'VALID')
            pooled = sums / jnp.maximum(counts, 1.0)
        outs.append(pooled[:, :, :bins, :bins].reshape(n, -1))
    ctx.set_output('Out', jnp.concatenate(outs, axis=1))


# ---- proximal optimizers --------------------------------------------------------
def _prox(prox_param, lr, l1, l2):
    return (jnp.sign(prox_param)
            * jnp.maximum(jnp.abs(prox_param) - lr * l1, 0.0)
            / (1.0 + lr * l2))


@register_kernel('proximal_gd')
def _proximal_gd(ctx):
    """ref proximal_gd_op.h: prox = p - lr*g;
    p' = sign(prox) * max(|prox| - lr*l1, 0) / (1 + lr*l2)."""
    p = unwrap(ctx.input('Param'))
    g = unwrap(ctx.input('Grad'))
    lr = unwrap(ctx.input('LearningRate')).reshape(())
    l1, l2 = ctx.attr('l1', 0.0), ctx.attr('l2', 0.0)
    ctx.set_output('ParamOut', _prox(p - lr * g, lr, l1, l2))


@register_kernel('proximal_adagrad')
def _proximal_adagrad(ctx):
    """ref proximal_adagrad_op.h: m' = m + g^2;
    prox = p - lr*g/sqrt(m'); shrinkage uses the scalar lr."""
    p = unwrap(ctx.input('Param'))
    g = unwrap(ctx.input('Grad'))
    m = unwrap(ctx.input('Moment'))
    lr = unwrap(ctx.input('LearningRate')).reshape(())
    l1, l2 = ctx.attr('l1', 0.0), ctx.attr('l2', 0.0)
    m_out = m + g * g
    # ref proximal_adagrad_op.h: lr_t only scales the grad step; the
    # l1/l2 shrinkage uses the SCALAR lr (lr*l1, 1+lr*l2)
    ctx.set_output('MomentOut', m_out)
    ctx.set_output('ParamOut',
                   _prox(p - lr * g / jnp.sqrt(m_out), lr, l1, l2))


# ---- metric ops -----------------------------------------------------------------
@register_kernel('precision_recall')
def _precision_recall(ctx):
    """ref precision_recall_op.h: per-class TP/FP/TN/FN states + macro/micro
    precision/recall/F1. One-hot scatter instead of the per-sample loop."""
    idx = unwrap(ctx.input('Indices')).reshape(-1).astype(jnp.int32)
    label = unwrap(ctx.input('Labels')).reshape(-1).astype(jnp.int32)
    C = ctx.attr('class_number')
    w = unwrap(ctx.input('Weights'))
    w = (jnp.ones(idx.shape, jnp.float32) if w is None
         else jnp.asarray(w).reshape(-1).astype(jnp.float32))
    oh_idx = jax.nn.one_hot(idx, C, dtype=jnp.float32)
    oh_lab = jax.nn.one_hot(label, C, dtype=jnp.float32)
    match = (idx == label).astype(jnp.float32)[:, None]
    tp = jnp.sum(w[:, None] * match * oh_idx, axis=0)
    fp = jnp.sum(w[:, None] * (1 - match) * oh_idx, axis=0)
    fn = jnp.sum(w[:, None] * (1 - match) * oh_lab, axis=0)
    # TN: every sample adds w to all classes except its idx (and its label
    # when mispredicted)
    tn = (jnp.sum(w) - jnp.sum(w[:, None] * oh_idx, axis=0)
          - jnp.sum(w[:, None] * (1 - match) * oh_lab, axis=0))
    batch_states = jnp.stack([tp, fp, tn, fn], axis=1)  # [C, 4]

    prior = ctx.input('StatesInfo')
    accum_states = batch_states if prior is None else \
        batch_states + jnp.asarray(unwrap(prior)).astype(jnp.float32)

    def metrics(states):
        tp_, fp_, _, fn_ = (states[:, 0], states[:, 1], states[:, 2],
                            states[:, 3])

        def safe(n, d):
            return jnp.where((n > 0) | (d > 0), n / jnp.maximum(n + d,
                                                                1e-30), 1.0)

        def f1(p, r):
            return jnp.where((p > 0) | (r > 0),
                             2 * p * r / jnp.maximum(p + r, 1e-30), 0.0)

        mac_p = jnp.mean(safe(tp_, fp_))
        mac_r = jnp.mean(safe(tp_, fn_))
        mic_p = safe(tp_.sum(), fp_.sum())
        mic_r = safe(tp_.sum(), fn_.sum())
        return jnp.stack([mac_p, mac_r, f1(mac_p, mac_r),
                          mic_p, mic_r, f1(mic_p, mic_r)])

    ctx.set_output('BatchMetrics', metrics(batch_states))
    ctx.set_output('AccumMetrics', metrics(accum_states))
    ctx.set_output('AccumStatesInfo', accum_states)


@register_kernel('positive_negative_pair')
def _positive_negative_pair(ctx):
    """ref positive_negative_pair_op.h: per-query pairwise order counts.
    Pairs with equal labels are ignored; pair weight = mean of both docs'
    weights; equal scores count as neutral AND negative (ref ternary)."""
    score = unwrap(ctx.input('Score'))
    label = unwrap(ctx.input('Label')).reshape(-1)
    query = unwrap(ctx.input('QueryID')).reshape(-1)
    col = ctx.attr('column', -1)
    s = score[:, col].reshape(-1)
    w_in = ctx.input('Weight')
    w = (jnp.ones(s.shape, s.dtype) if w_in is None
         else jnp.asarray(unwrap(w_in)).reshape(-1))
    same_q = query[:, None] == query[None, :]
    upper = jnp.triu(jnp.ones((s.shape[0], s.shape[0]), bool), k=1)
    ld = label[:, None] - label[None, :]
    sd = s[:, None] - s[None, :]
    pw = 0.5 * (w[:, None] + w[None, :])
    valid = same_q & upper & (ld != 0)
    vw = jnp.where(valid, pw, 0.0)
    pos = jnp.sum(jnp.where(sd * ld > 0, vw, 0.0))
    neg = jnp.sum(jnp.where(sd * ld <= 0, vw, 0.0))
    neu = jnp.sum(jnp.where(sd == 0, vw, 0.0))
    for slot, val in (('PositivePair', pos), ('NegativePair', neg),
                      ('NeutralPair', neu)):
        acc = ctx.input('Accumulate%s' % slot[:-4] + 'Pair')
        if acc is not None:
            val = val + jnp.asarray(unwrap(acc)).reshape(())
        ctx.set_output(slot, val.reshape(1))


# ---- reference op-type aliases --------------------------------------------------
# The reference registers the recurrent kernels as 'lstm'/'lstmp'/'gru'
# (paddle/fluid/operators/{lstm,lstmp,gru}_op.cc); our layers append the
# fluid layer names. Register both so reference-built ProgramDescs lower.
def _alias(name, target):
    from ..core import registry
    if not registry.has_kernel(name):
        register_kernel(name)(registry.get_kernel(target))


_alias('lstm', 'dynamic_lstm')
_alias('lstmp', 'dynamic_lstmp')
_alias('gru', 'dynamic_gru')
_alias('smooth_l1_loss', 'smooth_l1')


# ---- distributed markers --------------------------------------------------------
@register_kernel('send_marker', side_effect=True)
def _send_marker(ctx):
    """Parity: operators/send_op.cc (gRPC push to a pserver). On the TPU
    stack gradient exchange is implicit in the SPMD step (XLA psum over
    ICI/DCN; see parallel/transpiler.py), so a Send inside a program
    lowers to identity: each requested get_var receives the matching
    send_var's value (the pserver round-trip is a no-op because the
    'pserver state' is the locally sharded optimizer state). Registered
    as a side-effect op so prune-to-fetches never drops it."""
    xs = ctx.inputs('X')
    for i, name in enumerate(ctx.output_names('Out')):
        if xs:
            ctx.env[name] = xs[min(i, len(xs) - 1)]


@register_kernel('recv_marker', side_effect=True)
def _recv_marker(ctx):
    """Parity: operators/recv_op.cc. Identity for the same reason as
    send_marker: parameters are already resident (replicated or
    ZeRO-sharded) on every device. A reference-shaped recv (no X
    inputs) materialises zeros for shaped outputs — the value arrives
    via the sharded state, not this op."""
    xs = ctx.inputs('X')
    for i, name in enumerate(ctx.output_names('Out')):
        if i < len(xs):
            ctx.env[name] = xs[i]
            continue
        var = ctx.runner.block._find_var_recursive(name)
        if var is not None and var.shape:
            from ..core.lowering import runtime_dtype
            # Declared recv shapes may carry -1 (dynamic) dims; substitute
            # 1 so the placeholder still materialises instead of raising.
            shape = tuple(d if d > 0 else 1 for d in var.shape)
            ctx.env[name] = jnp.zeros(shape, runtime_dtype(var.dtype))


@register_kernel('listen_and_serv_marker', side_effect=True)
def _listen_and_serv_marker(ctx):
    """Parity: operators/listen_and_serv_op.cc (pserver gRPC loop). No
    server exists on the TPU stack; the op is a no-op placeholder so
    pserver-style launcher programs execute cleanly."""


@register_kernel('flash_attention')
def _flash_attention_op(ctx):
    """paddle_tpu-native multi-head attention op backed by the Pallas
    flash kernel (ops/pallas_kernels.py) — engaged on TPU at long seq
    lens, identical-math XLA reference elsewhere. Inputs Q [B, T,
    num_heads * dh], K/V [B, T, num_kv_heads * dh]; dh is attr head_dim,
    or D / num_heads without it; num_kv_heads (default num_heads)
    divides num_heads. This is the op behind
    layers.flash_attention, the fluid route to the kernels (the OPT
    cell of benchmark/chip builds its attention from it). The engaged
    kernels take Q, K, V and write Out in this very layout (a program
    addresses its heads as a 128-lane block of D), so the head split
    below is a reshape on both routes and nothing is transposed or
    copied between a projection and a kernel; a head shape the lane
    blocks cannot take (odd num_heads at head size 64, a head size
    other than 64 or a multiple of 128) counts as route=xla. Attr
    ``window`` (0: none; causal only): a query keeps the ``window``
    keys up to its own position on either route, and the kernels skip
    the tiles below that band; a window that reaches every query's
    first key (>= T) is no window.

    QK^T and PV are matmuls, so under AMP the op is on the MXU path
    like mul/matmul/conv2d (core/amp.py::mxu_compute): f32 q, k, v are
    cast to bf16 on either route, the dots accumulate f32 and the
    softmax state stays f32 inside the kernels, and the output flows
    bf16 under act_bf16(). Each lowering counts once in
    ``flash_attention_lowerings_total{route=, dtype=, diag=, kv_heads=,
    window=}`` (compiler/passes.py::flash_counts)."""
    from .pallas_kernels import (effective_window, flash_attention,
                                 flash_diag, flash_plan)
    from ..core.amp import mxu_compute
    heads = int(ctx.attr('num_heads', 1))
    kv_heads = int(ctx.attr('num_kv_heads', 0) or heads)
    head_dim = int(ctx.attr('head_dim', 0) or 0)
    causal = bool(ctx.attr('causal', True))

    def attend(q, k, v):
        B, T, D = q.shape
        dh = head_dim or D // heads
        qh = q.reshape(B, T, heads, dh)
        kh = k.reshape(B, T, kv_heads, dh)
        vh = v.reshape(B, T, kv_heads, dh)
        if kv_heads != heads:
            # grouped queries: each KV head repeated for the query heads
            # that share it, before either route; the repeat's transpose
            # sums their dK, dV
            kh = jnp.repeat(kh, heads // kv_heads, axis=2)
            vh = jnp.repeat(vh, heads // kv_heads, axis=2)
        window = effective_window(ctx.attr('window', 0), T, causal)
        plan = flash_plan(qh, causal=causal, window=window)
        _obs.default_registry().counter(
            'flash_attention_lowerings_total',
            help='flash_attention op lowerings, by the route taken '
                 '(pallas kernels / xla reference), the operand dtype '
                 'the attention ran in, the body the kernels give '
                 'a tile on the diagonal (chunked<r> / whole / none), '
                 'the KV heads the query heads share and the window '
                 '(0: none, or one that reaches every key)',
            route='xla' if plan is None else 'pallas',
            dtype={'bfloat16': 'bf16', 'float32': 'f32'}.get(
                qh.dtype.name, qh.dtype.name),
            diag=flash_diag(plan, causal, window),
            kv_heads=str(kv_heads), window=str(window or 0)).inc()
        # NB: flash_attention applies the 1/sqrt(dh) logit scale itself
        out = flash_attention(qh, kh, vh, causal=causal, window=window)
        return out.reshape(B, T, heads * dh)

    ctx.set_output('Out', mxu_compute(
        attend, unwrap(ctx.input('Q')), unwrap(ctx.input('K')),
        unwrap(ctx.input('V'))))
