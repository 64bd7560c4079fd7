"""ResNet-50 (He et al., arXiv:1512.03385, Table 1, 50-layer column) for
the chip benchmark: the Fluid program under test, the plain float32
reference, and the operations the algorithm requires.

The three exports every model module of this benchmark has:

``build(cfg, traffic)``     the Fluid program (the system under test)
``Reference(cfg)``          plain jax.numpy, float32, no kernels; imports
                            nothing of the program
``required_flops(cfg, traffic)``  operations a training step requires

The reference and the program get the same initial weights because the
*benchmark* makes them from the seed (``Reference.init``) and hands them
to both; neither takes anything the other made.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

STAGES = ((64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2))
BN_EPS = 1e-5


# ---- the program under test ------------------------------------------------
def build(cfg, traffic):
    """``resnet_imagenet(depth=50)`` -> softmax cross-entropy -> Momentum,
    as every Fluid script builds it. Returns the pieces the harness
    drives; parameter order is creation order, which is the order
    ``Reference.leaves`` lists."""
    del traffic     # the batch dimension is dynamic in a Fluid program
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import resnet as resnet_m
    size = cfg['image_size']
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        img = fluid.layers.data(name='data', shape=[3, size, size],
                                dtype='float32')
        label = fluid.layers.data(name='label', shape=[1], dtype='int64')
        predict = resnet_m.resnet_imagenet(
            img, class_dim=cfg['num_classes'], depth=50)
        cost = fluid.layers.cross_entropy(input=predict, label=label)
        loss = fluid.layers.mean(x=cost)
        opt = cfg['optimizer']
        fluid.optimizer.Momentum(learning_rate=opt['learning_rate'],
                                 momentum=opt['momentum']).minimize(loss)
    names = [p.name for p in main.global_block().all_parameters()]
    return {'main': main, 'startup': startup, 'loss': loss,
            'param_names': names,
            # the optimizer's state that holds the first gradient:
            # velocity after one step from zero IS that gradient
            'grad_state': lambda n: n + '_velocity_0',
            'grad_scale': 1.0}


def draw_batch(cfg, traffic, key):
    """One step's feed from a PRNG key: standard-normal images and
    uniform labels, every row different."""
    B, size = traffic['batch'], cfg['image_size']
    k1, k2 = jax.random.split(key)
    return {'data': jax.random.normal(k1, (B, 3, size, size), jnp.float32),
            'label': jax.random.randint(k2, (B, 1), 0, cfg['num_classes'],
                                        jnp.int32)}


# ---- the plain reference ---------------------------------------------------
def _conv_specs(cfg):
    """(cout, cin, k, stride, pad) of every conv in creation order, with
    the block structure the forward pass follows."""
    specs = [(64, 3, 7, 2, 3)]
    cin = 64
    for width, count, stride in STAGES:
        for i in range(count):
            s = stride if i == 0 else 1
            if cin != width * 4 or s != 1:
                specs.append((width * 4, cin, 1, s, 0))      # shortcut
            specs.append((width, cin, 1, s, 0))
            specs.append((width, width, 3, 1, 1))
            specs.append((width * 4, width, 1, 1, 0))
            cin = width * 4
    return specs


class Reference(object):
    """Forward, loss, gradients and the momentum update in float32 at
    ``highest`` matmul precision. Each bottleneck block is rematerialised
    so that batch 256 fits beside nothing else on a 16 GB chip (batch
    norm couples the rows of a batch, so it cannot be cut by rows)."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.specs = _conv_specs(cfg)

    # leaves in the program's creation order: conv w, bn scale, bn bias,
    # bn moving mean, bn moving variance, ..., fc w, fc b
    def leaves(self):
        out = []
        for i, (co, ci, k, _, _) in enumerate(self.specs):
            out.append(('conv%d.w' % i, (co, ci, k, k), True))
            out.append(('bn%d.scale' % i, (co,), True))
            out.append(('bn%d.bias' % i, (co,), True))
            out.append(('bn%d.mean' % i, (co,), False))
            out.append(('bn%d.var' % i, (co,), False))
        out.append(('fc.w', (2048, self.cfg['num_classes']), True))
        out.append(('fc.b', (self.cfg['num_classes'],), True))
        return out

    def init(self, key):
        """Initial weights from a PRNG key, float32: He-normal convs,
        unit batch norm except the last of each bottleneck, which starts
        at the configuration's ``residual_bn_scale_init`` (see its
        ``assumed``), Xavier-uniform classifier."""
        last_of_block = set()
        i, cin = 1, 64
        for width, count, stride in STAGES:
            for b in range(count):
                shortcut = cin != width * 4 or (stride if b == 0 else 1) != 1
                i += 4 if shortcut else 3
                last_of_block.add('bn%d.scale' % (i - 1))
                cin = width * 4
        params = {}
        for i, (name, shape, _) in enumerate(self.leaves()):
            k = jax.random.fold_in(key, i)
            if name.endswith('.w') and len(shape) == 4:
                fan_in = shape[1] * shape[2] * shape[3]
                params[name] = jax.random.normal(k, shape, jnp.float32) \
                    * math.sqrt(2.0 / fan_in)
            elif name == 'fc.w':
                lim = math.sqrt(6.0 / (shape[0] + shape[1]))
                params[name] = jax.random.uniform(
                    k, shape, jnp.float32, -lim, lim)
            elif name in last_of_block:
                params[name] = jnp.full(
                    shape, self.cfg['residual_bn_scale_init'], jnp.float32)
            elif name.endswith('.scale') or name.endswith('.var'):
                params[name] = jnp.ones(shape, jnp.float32)
            else:
                params[name] = jnp.zeros(shape, jnp.float32)
        return params

    def trainable(self):
        return [n for n, _, t in self.leaves() if t]

    def _conv_bn(self, params, i, x, act, dot):
        _, _, _, s, p = self.specs[i]
        y = dot.conv(x, params['conv%d.w' % i], s, p)
        mean = jnp.mean(y, axis=(0, 2, 3), keepdims=True)
        var = jnp.mean(jnp.square(y - mean), axis=(0, 2, 3), keepdims=True)
        y = (y - mean) * lax.rsqrt(var + BN_EPS)
        y = y * params['bn%d.scale' % i].reshape(1, -1, 1, 1) \
            + params['bn%d.bias' % i].reshape(1, -1, 1, 1)
        return jnp.maximum(y, 0.0) if act else y

    def loss(self, params, batch, dot=None):
        dot = dot or Float32Dots()
        x = batch['data'].astype(jnp.float32)
        labels = batch['label'].reshape(-1)
        x = self._conv_bn(params, 0, x, True, dot)
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2),
                              ((0, 0), (0, 0), (1, 1), (1, 1)))
        i = 1
        cin = 64
        for width, count, stride in STAGES:
            for b in range(count):
                s = stride if b == 0 else 1
                has_short = cin != width * 4 or s != 1
                idx = i

                def block(p, x, idx=idx, has_short=has_short):
                    j = idx
                    short = x
                    if has_short:
                        short = self._conv_bn(p, j, x, False, dot)
                        j += 1
                    y = self._conv_bn(p, j, x, True, dot)
                    y = self._conv_bn(p, j + 1, y, True, dot)
                    y = self._conv_bn(p, j + 2, y, False, dot)
                    return jnp.maximum(short + y, 0.0)

                x = jax.checkpoint(block)(params, x)
                i += 4 if has_short else 3
                cin = width * 4
        x = jnp.mean(x, axis=(2, 3))
        logits = dot.matmul(x, params['fc.w']) + params['fc.b']
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, labels[:, None], axis=1)
        return -jnp.mean(picked)

    def new_opt_state(self, params):
        return {n: jnp.zeros_like(params[n]) for n in self.trainable()}

    def update(self, params, grads, opt_state, step):
        """Momentum (Polyak, as Sutskever et al. 2013 write it):
        v <- mu v + g; p <- p - lr v."""
        del step
        opt = self.cfg['optimizer']
        new_p, new_v = dict(params), {}
        for n in self.trainable():
            v = opt['momentum'] * opt_state[n] + grads[n]
            new_v[n] = v
            new_p[n] = params[n] - opt['learning_rate'] * v
        return new_p, new_v


class Float32Dots(object):
    """Convolutions and matmuls as the configuration's plain reference
    computes them: float32 operands, ``highest`` precision."""

    def conv(self, x, w, stride, pad):
        return lax.conv_general_dilated(
            x, w, (stride, stride), ((pad, pad), (pad, pad)),
            dimension_numbers=('NCHW', 'OIHW', 'NCHW'),
            precision=lax.Precision.HIGHEST)

    def matmul(self, a, b):
        return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


# ---- operations the algorithm requires -------------------------------------
def forward_macs_per_image(cfg):
    """Multiply-adds of one forward pass of one image: every conv and
    the classifier; batch norm, ReLU, pooling and the loss are left out
    (under 1 % of the total)."""
    size = cfg['image_size']
    macs = 0
    hw = size

    def out_hw(h, k, s, p):
        return (h + 2 * p - k) // s + 1

    specs = _conv_specs(cfg)
    # walk the net to know each conv's input resolution
    h = out_hw(hw, 7, 2, 3)
    macs += specs[0][0] * specs[0][1] * 49 * h * h
    h = out_hw(h, 3, 2, 1)          # max pool
    i = 1
    cin = 64
    for width, count, stride in STAGES:
        for b in range(count):
            s = stride if b == 0 else 1
            ho = out_hw(h, 1, s, 0)
            if cin != width * 4 or s != 1:
                macs += width * 4 * cin * ho * ho
                i += 1
            macs += width * cin * ho * ho               # 1x1, strided
            macs += width * width * 9 * ho * ho         # 3x3
            macs += width * 4 * width * ho * ho         # 1x1
            i += 3
            h = ho
            cin = width * 4
    macs += 2048 * cfg['num_classes']
    return macs


def required_flops(cfg, traffic):
    """Operations one training step requires: two per multiply-add,
    forward once and backward twice (gradients with respect to the
    input and to the weights), for every image of the batch."""
    return 3 * 2 * forward_macs_per_image(cfg) * traffic['batch']


def items_per_step(cfg, traffic):
    return traffic['batch']


class ControlDots(Float32Dots):
    """The control: every conv and matmul operand rounded to fp8, in the
    forward and in the backward products."""

    def conv(self, x, w, stride, pad):
        from lowprec import fq8, fq8_grad
        return fq8_grad(Float32Dots.conv(self, fq8(x), fq8(w), stride, pad))

    def matmul(self, a, b):
        from lowprec import fq8, fq8_grad
        return fq8_grad(Float32Dots.matmul(self, fq8(a), fq8(b)))
