"""Math / elementwise / activation / reduction kernels.

Parity: paddle/fluid/operators/{elementwise_*,activation,mul,matmul,reduce_*,
sum,scale,cast,clip,cumsum,cos_sim,...}_op.* — re-expressed as jnp traces so
XLA fuses them into neighbouring matmuls (HBM-bandwidth win; no hand
scheduling).
"""
import jax
import jax.numpy as jnp

from .. import observability as _obs
from ..core import amp
from ..core.registry import register_kernel
from .common import unwrap, rewrap, seq_of, bcast_y


# ---- elementwise binary ---------------------------------------------------------
def _amp_flow(op, x, y, out, y_broadcasts):
    """AMP's activation rule (core/amp.py::act_bf16) where type promotion
    widened a bf16/f32 pair of operands to f32: a bf16 X whose f32 Y
    broadcasts (a bias, a per-channel scale: not of X's own shape) was
    computed in f32 and returns to X's dtype, as batch_norm and
    layer_norm do, so the cotangent that comes back is bf16 too; an
    f32 X, or an f32 Y of X's own shape (the residual stream), keeps the
    f32 result. Each such lowering counts once per trace in
    ``amp_elementwise_lowerings_total{op=, result=}``
    (compiler/passes.py::amp_elementwise_counts)."""
    if (out.dtype != jnp.float32
            or {x.dtype, y.dtype} != {jnp.dtype(jnp.bfloat16),
                                      jnp.dtype(jnp.float32)}
            or not amp.act_bf16()):
        return out
    keep = x.dtype == jnp.bfloat16 and y_broadcasts
    _obs.default_registry().counter(
        'amp_elementwise_lowerings_total',
        help='binary elementwise lowerings under bf16 activation flow '
             'that met one bf16 and one f32 operand, by op and result: '
             'kept_bf16 (bf16 X, broadcast f32 Y: computed in f32, '
             'returned to bf16) / widened_f32 (an f32 X, or an f32 Y '
             'shaped like X: the f32 stream stays f32)',
        op=op, result='kept_bf16' if keep else 'widened_f32').inc()
    return out.astype(x.dtype) if keep else out


def _elementwise(name, fn):
    @register_kernel(name)
    def _k(ctx, fn=fn):
        x, y = ctx.input('X'), ctx.input('Y')
        tmpl = seq_of(x, y)
        xd, yd = unwrap(x), unwrap(y)
        axis = ctx.attr('axis', -1)
        from ..lod import SequenceTensor
        if (isinstance(x, SequenceTensor) and not x.packed_mode
                and not isinstance(y, SequenceTensor)
                and axis not in (None, -1) and axis >= 1):
            # IR shapes follow the reference's packed [total, ...] layout;
            # runtime data is padded [B, T, ...] so dims >= 1 shift by one.
            # packed-mode data IS the reference layout: no shift.
            axis += 1
        if (isinstance(x, SequenceTensor) and not x.packed_mode
                and not isinstance(y, SequenceTensor) and axis == 0
                and getattr(yd, 'ndim', 0) >= 1 and xd.ndim >= 2
                and _prod(yd.shape) == xd.shape[0] * xd.shape[1]):
            # reference row-broadcast: y is one value per PACKED row
            # ([total]); padded rows are [B, T] row-major, same order
            # (attention weight scaling in benchmark/fluid
            # machine_translation's simple_attention)
            yd = jnp.asarray(yd).reshape(
                (xd.shape[0], xd.shape[1]) + (1,) * (xd.ndim - 2))
            axis = -1
        xd = jnp.asarray(xd)
        y_broadcasts = jnp.shape(yd) != xd.shape
        yd = bcast_y(xd, yd, axis)
        out = fn(xd, yd)
        if ctx.attr('scale', None) not in (None, 1.0):
            out = out * ctx.attr('scale')
        out = _amp_flow(name, xd, yd, out, y_broadcasts)
        ctx.set_output('Out', rewrap(tmpl, out) if tmpl is not None else out)


_elementwise('elementwise_add', jnp.add)
_elementwise('elementwise_sub', jnp.subtract)
_elementwise('elementwise_mul', jnp.multiply)
_elementwise('elementwise_div', jnp.divide)
_elementwise('elementwise_max', jnp.maximum)
_elementwise('elementwise_min', jnp.minimum)
_elementwise('elementwise_pow', jnp.power)


def _logical(name, fn, unary=False):
    @register_kernel(name)
    def _k(ctx, fn=fn, unary=unary):
        x = unwrap(ctx.input('X'))
        out = fn(x) if unary else fn(x, unwrap(ctx.input('Y')))
        ctx.set_output('Out', out.astype(jnp.bool_))


_logical('logical_and', jnp.logical_and)
_logical('logical_or', jnp.logical_or)
_logical('logical_xor', jnp.logical_xor)
_logical('logical_not', jnp.logical_not, unary=True)


@register_kernel('compare')
@register_kernel('less_than')
@register_kernel('less_equal')
@register_kernel('greater_than')
@register_kernel('greater_equal')
@register_kernel('equal')
@register_kernel('not_equal')
def _compare(ctx):
    op = {'less_than': jnp.less, 'less_equal': jnp.less_equal,
          'greater_than': jnp.greater, 'greater_equal': jnp.greater_equal,
          'equal': jnp.equal, 'not_equal': jnp.not_equal}[ctx.op.type]
    x, y = unwrap(ctx.input('X')), unwrap(ctx.input('Y'))
    ctx.set_output('Out', op(jnp.asarray(x), jnp.asarray(y)))


# ---- activations ----------------------------------------------------------------
_ACTS = {
    'sigmoid': jax.nn.sigmoid,
    'logsigmoid': jax.nn.log_sigmoid,
    'exp': jnp.exp,
    'relu': jax.nn.relu,
    'tanh': jnp.tanh,
    'tanh_shrink': lambda x: x - jnp.tanh(x),
    'sqrt': jnp.sqrt,
    'abs': jnp.abs,
    'ceil': jnp.ceil,
    'floor': jnp.floor,
    'cos': jnp.cos,
    'sin': jnp.sin,
    'round': jnp.round,
    'reciprocal': lambda x: 1.0 / x,
    'log': jnp.log,
    'square': jnp.square,
    'softplus': jax.nn.softplus,
    'softsign': jax.nn.soft_sign,
}


def _register_acts():
    for name, fn in _ACTS.items():
        @register_kernel(name)
        def _k(ctx, fn=fn):
            x = ctx.input('X')
            ctx.set_output('Out', rewrap(x, fn(unwrap(x))))


_register_acts()


@register_kernel('brelu')
def _brelu(ctx):
    x = ctx.input('X')
    t_min, t_max = ctx.attr('t_min', 0.0), ctx.attr('t_max', 24.0)
    ctx.set_output('Out', rewrap(x, jnp.clip(unwrap(x), t_min, t_max)))


@register_kernel('leaky_relu')
def _leaky_relu(ctx):
    x = ctx.input('X')
    alpha = ctx.attr('alpha', 0.02)
    ctx.set_output('Out', rewrap(x, jax.nn.leaky_relu(unwrap(x), alpha)))


@register_kernel('soft_relu')
def _soft_relu(ctx):
    x = ctx.input('X')
    threshold = ctx.attr('threshold', 40.0)
    xd = jnp.clip(unwrap(x), -threshold, threshold)
    ctx.set_output('Out', rewrap(x, jnp.log1p(jnp.exp(xd))))


@register_kernel('elu')
def _elu(ctx):
    x = ctx.input('X')
    ctx.set_output('Out', rewrap(x, jax.nn.elu(unwrap(x),
                                               ctx.attr('alpha', 1.0))))


@register_kernel('relu6')
def _relu6(ctx):
    x = ctx.input('X')
    ctx.set_output('Out', rewrap(x, jnp.clip(unwrap(x), 0,
                                             ctx.attr('threshold', 6.0))))


@register_kernel('pow')
def _pow(ctx):
    x = ctx.input('X')
    ctx.set_output('Out', rewrap(x, jnp.power(unwrap(x),
                                              ctx.attr('factor', 1.0))))


@register_kernel('stanh')
def _stanh(ctx):
    x = ctx.input('X')
    a = ctx.attr('scale_a', 2.0 / 3.0)
    b = ctx.attr('scale_b', 1.7159)
    ctx.set_output('Out', rewrap(x, b * jnp.tanh(a * unwrap(x))))


@register_kernel('hard_shrink')
def _hard_shrink(ctx):
    x = ctx.input('X')
    t = ctx.attr('threshold', 0.5)
    xd = unwrap(x)
    ctx.set_output('Out', rewrap(x, jnp.where(jnp.abs(xd) > t, xd, 0.0)))


@register_kernel('softshrink')
def _softshrink(ctx):
    x = ctx.input('X')
    lam = ctx.attr('lambda', 0.5)
    xd = unwrap(x)
    out = jnp.where(xd > lam, xd - lam, jnp.where(xd < -lam, xd + lam, 0.0))
    ctx.set_output('Out', rewrap(x, out))


@register_kernel('thresholded_relu')
def _thresholded_relu(ctx):
    x = ctx.input('X')
    t = ctx.attr('threshold', 1.0)
    xd = unwrap(x)
    ctx.set_output('Out', rewrap(x, jnp.where(xd > t, xd, 0.0)))


@register_kernel('hard_sigmoid')
def _hard_sigmoid(ctx):
    x = ctx.input('X')
    slope = ctx.attr('slope', 0.2)
    offset = ctx.attr('offset', 0.5)
    ctx.set_output('Out', rewrap(x, jnp.clip(slope * unwrap(x) + offset,
                                             0.0, 1.0)))


@register_kernel('swish')
def _swish(ctx):
    x = ctx.input('X')
    beta = ctx.attr('beta', 1.0)
    xd = unwrap(x)
    ctx.set_output('Out', rewrap(x, xd * jax.nn.sigmoid(beta * xd)))


# ---- matmul family --------------------------------------------------------------
@register_kernel('mul')
def _mul(ctx):
    """fc matmul. X flattened by x_num_col_dims, Y by y_num_col_dims.
    Parity: operators/mul_op.cc. Feeds the MXU directly.

    Sequence inputs: the reference packs time into dim 0 ([total, D]); our
    runtime layout is padded [B, T, D], so the time dim joins the row dims
    and the result stays a SequenceTensor."""
    x_in, y = ctx.input('X'), unwrap(ctx.input('Y'))
    x = unwrap(x_in)
    xd = ctx.attr('x_num_col_dims', 1)
    yd = ctx.attr('y_num_col_dims', 1)
    from ..lod import SequenceTensor
    is_seq = isinstance(x_in, SequenceTensor)
    if is_seq and not x_in.packed_mode:
        xd += 1  # [B, T] both count as row dims
    # packed mode keeps the reference's [total, D] layout: xd stays 1
    xs, ys = x.shape, y.shape
    x2 = x.reshape((_prod(xs[:xd]), _prod(xs[xd:])))
    y2 = y.reshape((_prod(ys[:yd]), _prod(ys[yd:])))
    from ..core.amp import mxu_compute
    out = mxu_compute(jnp.matmul, x2, y2)
    out = out.reshape(tuple(xs[:xd]) + tuple(ys[yd:]))
    ctx.set_output('Out', rewrap(x_in, out) if is_seq else out)


def _prod(t):
    r = 1
    for v in t:
        r *= int(v)
    return r


@register_kernel('matmul')
def _matmul(ctx):
    x, y = unwrap(ctx.input('X')), unwrap(ctx.input('Y'))
    tx, ty = ctx.attr('transpose_X', False), ctx.attr('transpose_Y', False)
    alpha = ctx.attr('alpha', 1.0)
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    if x.ndim == 1:
        x = x[None, :]
    if y.ndim == 1:
        y = y[:, None]
    if tx:
        x = jnp.swapaxes(x, -1, -2)
    if ty:
        y = jnp.swapaxes(y, -1, -2)
    from ..core.amp import mxu_compute
    out = mxu_compute(jnp.matmul, x, y)
    if alpha != 1.0:
        out = out * alpha
    ctx.set_output('Out', out)


# ---- reductions -----------------------------------------------------------------
def _reduce(name, fn):
    @register_kernel(name)
    def _k(ctx, fn=fn):
        x = unwrap(ctx.input('X'))
        dim = ctx.attr('dim', None)
        keep_dim = ctx.attr('keep_dim', False)
        reduce_all = ctx.attr('reduce_all', False)
        if reduce_all or dim is None:
            axis = None
        else:
            axis = tuple(dim) if isinstance(dim, (list, tuple)) else (dim,)
        out = fn(x, axis=axis, keepdims=keep_dim)
        ctx.set_output('Out', out)


_reduce('reduce_sum', jnp.sum)
_reduce('reduce_mean', jnp.mean)
_reduce('reduce_max', jnp.max)
_reduce('reduce_min', jnp.min)
_reduce('reduce_prod', jnp.prod)


@register_kernel('mean')
def _mean(ctx):
    from .common import f32
    x_in = ctx.input('X')
    x = f32(unwrap(x_in))
    from ..lod import SequenceTensor
    if isinstance(x_in, SequenceTensor):
        # average over REAL tokens only (reference means over the packed
        # [total, ...] rows, which has no padding)
        T = x.shape[1]
        m = (jnp.arange(T)[None, :] <
             jnp.asarray(x_in.lengths)[:, None])
        m = m.reshape(m.shape + (1,) * (x.ndim - 2)).astype(x.dtype)
        denom = jnp.maximum(jnp.sum(m), 1.0) * _prod(x.shape[2:])
        ctx.set_output('Out',
                       (jnp.sum(x * m) / denom).reshape((1,)))
        return
    ctx.set_output('Out', jnp.mean(x).reshape((1,)))


@register_kernel('sum')
def _sum(ctx):
    xs = [unwrap(v) for v in ctx.inputs('X')]
    out = xs[0]
    for v in xs[1:]:
        out = out + v
    tmpl = seq_of(*ctx.inputs('X'))
    ctx.set_output('Out', rewrap(tmpl, out) if tmpl is not None else out)


@register_kernel('scale')
def _scale(ctx):
    x = ctx.input('X')
    s = ctx.attr('scale', 1.0)
    bias = ctx.attr('bias', 0.0)
    bias_after = ctx.attr('bias_after_scale', True)
    xd = unwrap(x)
    out = xd * s + bias if bias_after else (xd + bias) * s
    ctx.set_output('Out', rewrap(x, out))


@register_kernel('clip')
def _clip(ctx):
    x = ctx.input('X')
    ctx.set_output('Out', rewrap(x, jnp.clip(unwrap(x), ctx.attr('min'),
                                             ctx.attr('max'))))


@register_kernel('clip_by_norm')
def _clip_by_norm(ctx):
    x = unwrap(ctx.input('X'))
    max_norm = ctx.attr('max_norm')
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    scale = jnp.minimum(max_norm / jnp.maximum(norm, 1e-12), 1.0)
    ctx.set_output('Out', x * scale)


@register_kernel('cumsum')
def _cumsum(ctx):
    x = unwrap(ctx.input('X'))
    axis = ctx.attr('axis', -1)
    out = jnp.cumsum(x, axis=axis)
    if ctx.attr('reverse', False):
        out = jnp.flip(jnp.cumsum(jnp.flip(x, axis), axis=axis), axis)
    if ctx.attr('exclusive', False):
        out = out - x
    ctx.set_output('Out', out)


@register_kernel('cos_sim')
def _cos_sim(ctx):
    x, y = unwrap(ctx.input('X')), unwrap(ctx.input('Y'))
    xn = jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True))
    yn = jnp.sqrt(jnp.sum(jnp.square(y), -1, keepdims=True))
    xy = jnp.sum(x * y, -1, keepdims=True)
    ctx.set_output('Out', xy / jnp.maximum(xn * yn, 1e-12))
    ctx.set_output('XNorm', xn)
    ctx.set_output('YNorm', yn)


@register_kernel('square_error_cost')
def _square_error_cost(ctx):
    x, y = unwrap(ctx.input('X')), unwrap(ctx.input('Label'))
    ctx.set_output('Out', jnp.square(x - y))


@register_kernel('smooth_l1')
def _smooth_l1(ctx):
    x, y = unwrap(ctx.input('X')), unwrap(ctx.input('Y'))
    sigma = ctx.attr('sigma', 1.0)
    s2 = sigma * sigma
    diff = x - y
    if ctx.has_input('InsideWeight'):
        diff = diff * unwrap(ctx.input('InsideWeight'))
    ad = jnp.abs(diff)
    loss = jnp.where(ad < 1.0 / s2, 0.5 * s2 * diff * diff, ad - 0.5 / s2)
    if ctx.has_input('OutsideWeight'):
        loss = loss * unwrap(ctx.input('OutsideWeight'))
    ctx.set_output('Out', jnp.sum(loss.reshape(loss.shape[0], -1), -1,
                                  keepdims=True))
    if ctx.output_names('Diff'):
        ctx.set_output('Diff', diff)


@register_kernel('l2_normalize')
@register_kernel('norm')
def _l2_normalize(ctx):
    x = unwrap(ctx.input('X'))
    eps = ctx.attr('epsilon', 1e-10)
    if ctx.has_input('Scale'):
        # reference norm_op.cc (SSD cross-channel norm): per spatial
        # position, out = Scale[c] * x / sqrt(sum_c x^2 + eps)
        scale = unwrap(ctx.input('Scale')).reshape(1, -1, 1, 1)
        denom = jnp.sqrt(jnp.sum(jnp.square(x), axis=1,
                                 keepdims=True) + eps)
        ctx.set_output('Out', scale * x / denom)
        return
    axis = ctx.attr('axis', -1)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True))
    out = x / jnp.maximum(norm, eps)
    ctx.set_output('Out', out)
    if ctx.output_names('Norm'):
        ctx.set_output('Norm', norm)


@register_kernel('iou_similarity')
def _iou_similarity(ctx):
    x, y = unwrap(ctx.input('X')), unwrap(ctx.input('Y'))
    area = lambda b: jnp.maximum(b[..., 2] - b[..., 0], 0) * \
        jnp.maximum(b[..., 3] - b[..., 1], 0)
    xe = x[:, None, :]
    ye = y[None, :, :]
    lt = jnp.maximum(xe[..., :2], ye[..., :2])
    rb = jnp.minimum(xe[..., 2:], ye[..., 2:])
    wh = jnp.maximum(rb - lt, 0)
    inter = wh[..., 0] * wh[..., 1]
    union = area(xe) + area(ye) - inter
    ctx.set_output('Out', inter / jnp.maximum(union, 1e-10))


@register_kernel('bilinear_tensor_product')
def _bilinear_tensor_product(ctx):
    x, y, w = (unwrap(ctx.input('X')), unwrap(ctx.input('Y')),
               unwrap(ctx.input('Weight')))
    out = jnp.einsum('bi,oij,bj->bo', x, w, y)
    if ctx.has_input('Bias'):
        out = out + unwrap(ctx.input('Bias'))
    ctx.set_output('Out', out)


@register_kernel('conv_shift')
def _conv_shift(ctx):
    x, y = unwrap(ctx.input('X')), unwrap(ctx.input('Y'))
    b, m = x.shape
    n = y.shape[1]
    half = (n - 1) // 2
    idx = (jnp.arange(m)[:, None] + jnp.arange(-half, n - half)[None, :]) % m
    ctx.set_output('Out', jnp.einsum('bmn,bn->bm', x[:, idx], y))
