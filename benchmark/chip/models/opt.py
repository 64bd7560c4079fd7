"""OPT (Zhang et al., arXiv:2205.01068; ``facebook/opt-1.3b`` config.json)
for the chip benchmark: the Fluid program under test, the plain float32
reference, and the operations the algorithm requires.

Decoder-only, pre-LayerNorm, learned positions (table offset 2), ReLU
FFN, biases on every projection, final LayerNorm, output head tied to
the token embedding. Same three exports as every model module here:
``build``, ``Reference``, ``required_flops``.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

POS_OFFSET = 2          # OPTLearnedPositionalEmbedding
LN_EPS = 1e-5


def _dims(cfg):
    return (cfg['vocab_size'], cfg['hidden_size'], cfg['ffn_dim'],
            cfg['num_attention_heads'], cfg['num_hidden_layers'],
            cfg['max_position_embeddings'] + POS_OFFSET)


# ---- the program under test ------------------------------------------------
def build(cfg, traffic):
    import paddle_tpu.fluid as fluid
    V, H, F, heads, L, P = _dims(cfg)
    S = traffic['seq_len']
    layers = fluid.layers
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        tok = layers.data(name='data', shape=[S], dtype='int64')
        label = layers.data(name='label', shape=[S, 1], dtype='int64')
        pos = layers.data(name='pos', shape=[S], dtype='int64')
        x = layers.embedding(input=tok, size=[V, H],
                             param_attr='embed_tokens')
        p = layers.embedding(input=pos, size=[P, H],
                             param_attr='embed_positions')
        x = x + p

        def proj(inp, size, act=None):
            return layers.fc(input=inp, size=size, num_flatten_dims=2,
                             act=act)

        for _ in range(L):
            ln = layers.layer_norm(x, begin_norm_axis=2, epsilon=LN_EPS)
            q, k, v = proj(ln, H), proj(ln, H), proj(ln, H)
            att = layers.flash_attention(q, k, v, num_heads=heads,
                                         causal=True)
            x = x + proj(att, H)
            ln2 = layers.layer_norm(x, begin_norm_axis=2, epsilon=LN_EPS)
            x = x + proj(proj(ln2, F, act='relu'), H)
        x = layers.layer_norm(x, begin_norm_axis=2, epsilon=LN_EPS)
        # the head is the embedding, transposed: one parameter, two uses
        embed = main.global_block().var('embed_tokens')
        logits = layers.matmul(x, embed, transpose_y=True)
        loss = layers.mean(x=layers.softmax_with_cross_entropy(
            logits=logits, label=label))
        opt = cfg['optimizer']
        fluid.optimizer.Adam(learning_rate=opt['learning_rate'],
                             beta1=opt['beta1'], beta2=opt['beta2'],
                             epsilon=opt['epsilon']).minimize(loss)
    names = [p.name for p in main.global_block().all_parameters()]
    return {'main': main, 'startup': startup, 'loss': loss,
            'param_names': names,
            # Adam's first moment after one step from zero is
            # (1 - beta1) times the first gradient
            'grad_state': lambda n: n + '_moment1_0',
            'grad_scale': 1.0 / (1.0 - opt['beta1'])}


def draw_batch(cfg, traffic, key):
    """One step's feed from a PRNG key: B sequences of S+1 token ids;
    the inputs are the first S, the labels the last S (next token)."""
    B, S = traffic['batch'], traffic['seq_len']
    ids = jax.random.randint(key, (B, S + 1), 0, cfg['vocab_size'],
                             jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32) + POS_OFFSET,
                           (B, S))
    return {'data': ids[:, :-1], 'label': ids[:, 1:, None], 'pos': pos}


# ---- the plain reference ---------------------------------------------------
class Reference(object):
    """Forward, loss, gradients and Adam in float32 at ``highest``
    matmul precision; attention is the full S x S softmax. Each layer is
    rematerialised so that the step fits one chip."""

    def __init__(self, cfg):
        self.cfg = cfg

    def leaves(self):
        V, H, F, _, L, P = _dims(self.cfg)
        out = [('embed_tokens', (V, H), True),
               ('embed_positions', (P, H), True)]
        for i in range(L):
            pre = 'l%d.' % i
            out += [(pre + 'ln1.scale', (H,), True),
                    (pre + 'ln1.bias', (H,), True)]
            for nm in ('q', 'k', 'v', 'o'):
                out += [(pre + nm + '.w', (H, H), True),
                        (pre + nm + '.b', (H,), True)]
            out += [(pre + 'ln2.scale', (H,), True),
                    (pre + 'ln2.bias', (H,), True),
                    (pre + 'fc1.w', (H, F), True),
                    (pre + 'fc1.b', (F,), True),
                    (pre + 'fc2.w', (F, H), True),
                    (pre + 'fc2.b', (H,), True)]
        out += [('ln_f.scale', (H,), True), ('ln_f.bias', (H,), True)]
        return out

    def trainable(self):
        return [n for n, _, _ in self.leaves()]

    def init(self, key):
        """normal(0, init_std) matrices and tables, zero biases, unit
        LayerNorm — OPT's own initialisation."""
        std = self.cfg['init_std']
        params = {}
        for i, (name, shape, _) in enumerate(self.leaves()):
            if name.endswith('.scale'):
                params[name] = jnp.ones(shape, jnp.float32)
            elif len(shape) == 1:
                params[name] = jnp.zeros(shape, jnp.float32)
            else:
                params[name] = std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
        return params

    @staticmethod
    def _ln(x, scale, bias):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        return (x - mean) * lax.rsqrt(var + LN_EPS) * scale + bias

    def _layer(self, p, x, pre, dot):
        heads = self.cfg['num_attention_heads']
        B, S, H = x.shape
        D = H // heads
        h = self._ln(x, p[pre + 'ln1.scale'], p[pre + 'ln1.bias'])

        def lin(t, nm):
            return dot.matmul(t, p[pre + nm + '.w']) + p[pre + nm + '.b']

        def split(t):
            return t.reshape(B, S, heads, D).transpose(0, 2, 1, 3)

        q, k, v = split(lin(h, 'q')), split(lin(h, 'k')), split(lin(h, 'v'))
        s = dot.einsum('bhqd,bhkd->bhqk', q, k) / math.sqrt(D)
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = dot.einsum('bhqk,bhkd->bhqd', a, v)
        o = o.transpose(0, 2, 1, 3).reshape(B, S, H)
        x = x + lin(o, 'o')
        h = self._ln(x, p[pre + 'ln2.scale'], p[pre + 'ln2.bias'])
        return x + lin(jnp.maximum(lin(h, 'fc1'), 0.0), 'fc2')

    def loss(self, params, batch, dot=None):
        dot = dot or Float32Dots()
        L = self.cfg['num_hidden_layers']
        x = params['embed_tokens'][batch['data']] \
            + params['embed_positions'][batch['pos']]
        for i in range(L):
            pre = 'l%d.' % i
            x = jax.checkpoint(
                lambda p, x, pre=pre: self._layer(p, x, pre, dot))(params, x)
        x = self._ln(x, params['ln_f.scale'], params['ln_f.bias'])
        labels = batch['label'][..., 0]

        def head(p_embed, x):
            logits = dot.matmul(x, p_embed.T)
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.mean(jnp.take_along_axis(
                logp, labels[..., None], axis=-1))

        return jax.checkpoint(head)(params['embed_tokens'], x)

    def new_opt_state(self, params):
        return {n: (jnp.zeros_like(params[n]), jnp.zeros_like(params[n]))
                for n in self.trainable()}

    def update(self, params, grads, opt_state, step):
        """Adam (Kingma & Ba 2015, section 2's efficient form):
        alpha_t = alpha sqrt(1 - beta2^t) / (1 - beta1^t);
        p <- p - alpha_t m / (sqrt(v) + eps). ``step`` counts from 1."""
        o = self.cfg['optimizer']
        b1, b2 = o['beta1'], o['beta2']
        lr_t = o['learning_rate'] * jnp.sqrt(1.0 - b2 ** step) \
            / (1.0 - b1 ** step)
        new_p, new_s = {}, {}
        for n in self.trainable():
            m, v = opt_state[n]
            g = grads[n]
            m = b1 * m + (1.0 - b1) * g
            v = b2 * v + (1.0 - b2) * jnp.square(g)
            new_s[n] = (m, v)
            new_p[n] = params[n] - lr_t * m / (jnp.sqrt(v) + o['epsilon'])
        return new_p, new_s


class Float32Dots(object):
    def matmul(self, a, b):
        return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)


# ---- operations the algorithm requires -------------------------------------
def matmul_weights(cfg):
    """Weights that take part in a matrix multiplication per token: the
    four attention projections and the two FFN matrices of every layer,
    and the output head (the tied embedding, used as a matmul once)."""
    V, H, F, _, L, _ = _dims(cfg)
    return L * (4 * H * H + 2 * H * F) + V * H


def attention_flops_per_token(cfg, seq_len, passes=3):
    """Causal attention: QK^T and PV are two matmuls of 2*S*H operations
    a token each in full, half of that under the causal mask; backward
    needs twice the forward (four matmuls). Recomputing S in a blockwise
    backward is the implementation's choice and is not counted."""
    _, H, _, _, L, _ = _dims(cfg)
    return passes * L * 2 * seq_len * H


def required_flops(cfg, traffic):
    """Operations one training step requires: 6 per matmul weight per
    token (2 forward, 4 backward) plus causal attention."""
    tokens = traffic['batch'] * traffic['seq_len']
    per_token = 6 * matmul_weights(cfg) \
        + attention_flops_per_token(cfg, traffic['seq_len'])
    return per_token * tokens


def items_per_step(cfg, traffic):
    return traffic['batch'] * traffic['seq_len']


def _flash_shape(cfg, traffic):
    _, H, _, heads, L, _ = _dims(cfg)
    return traffic['batch'], heads, traffic['seq_len'], H // heads, L


def flash_fwd_work(cfg, traffic, chips):
    """(operations, HBM bytes) of causal attention forward in one step:
    every layer's QK^T and PV under the mask; reads Q, K, V and writes O
    once each in bf16, whatever implements it."""
    del chips
    B, heads, S, D, L = _flash_shape(cfg, traffic)
    flops = L * B * heads * 2 * (2 * S * S * D) // 2
    nbytes = L * 4 * B * heads * S * D * 2
    return flops, nbytes


def flash_bwd_work(cfg, traffic, chips):
    """Backward: four matmuls where forward has two; reads Q, K, V, O,
    dO and writes dQ, dK, dV once each in bf16."""
    del chips
    flops, _ = flash_fwd_work(cfg, traffic, 1)
    B, heads, S, D, L = _flash_shape(cfg, traffic)
    return 2 * flops, L * 8 * B * heads * S * D * 2


class ControlDots(Float32Dots):
    """The control: every matmul operand rounded to fp8, in the forward
    and in the backward products."""

    def matmul(self, a, b):
        from lowprec import fq8, fq8_grad
        return fq8_grad(Float32Dots.matmul(self, fq8(a), fq8(b)))

    def einsum(self, spec, a, b):
        from lowprec import fq8, fq8_grad
        return fq8_grad(Float32Dots.einsum(self, spec, fq8(a), fq8(b)))
