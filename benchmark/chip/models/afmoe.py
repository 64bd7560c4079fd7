"""AFMoE (``model_type`` ``afmoe``; arcee-ai/Trinity-Mini ``config.json``)
for the chip benchmark: the Fluid program under test, the plain float32
reference, and the operations the algorithm requires.

A stack of residual blocks with four RMS norms each, ``h <- h +
norm(attention(norm(h)))`` then ``h <- h + norm(mlp(norm(h)))``.
Attention follows ``layer_types``: a ``sliding_attention`` layer keeps
the last ``sliding_window`` keys and turns q and k by rotary positions,
a ``full_attention`` layer keeps every earlier key and has no position
term; both norm q and k a head, share KV heads among query heads and
gate the attention output by sigmoid(x W_g). The first
``num_dense_layers`` MLPs are a gated (SwiGLU) FFN, the others a
sigmoid-routed mixture of gated experts with one shared expert. The
embedding is scaled by sqrt(hidden), the head untied, no bias anywhere.
The configuration gives this chip's share of a layer (the query and KV
heads, routed experts and vocabulary rows held here: PERF.md, section
4); program and reference take the same share. Same exports as every
model module here: ``build``, ``Reference``, ``required_flops``, the
work functions of its kernels.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

SCORE_EPS = 1e-20       # route_norm's denominator
SLIDING, FULL = 'sliding_attention', 'full_attention'


class Dims(object):
    """The sizes one chip runs, read from the configuration."""

    def __init__(self, cfg):
        self.kinds = list(cfg['layer_types'][:cfg['num_hidden_layers']])
        if set(self.kinds) - {SLIDING, FULL}:
            raise ValueError('unknown layer types %r' % (self.kinds,))
        self.D = cfg['hidden_size']
        self.V = cfg['vocab_size']
        self.eps = cfg['rms_norm_eps']
        # attention
        self.Hq, self.Hkv = (cfg['num_attention_heads'],
                             cfg['num_key_value_heads'])
        self.dh = cfg['head_dim']
        self.window = cfg['sliding_window']
        self.theta = float(cfg['rope_theta'])
        # MLPs
        self.dense = cfg['num_dense_layers']
        self.I = cfg['intermediate_size']
        self.F = cfg['moe_intermediate_size']
        self.S = cfg['moe_intermediate_size'] * cfg['num_shared_experts']
        self.E = cfg['router_num_experts']
        self.held = (cfg.get('experts_first', 0), cfg['num_experts'])
        self.top_k = cfg['num_experts_per_tok']
        self.scale = float(cfg['route_scale'])
        self.embed_scale = math.sqrt(self.D) if cfg['mup_enabled'] else 1.0
        if cfg['score_func'] != 'sigmoid' or not cfg['route_norm'] \
                or cfg['hidden_act'] != 'silu' \
                or cfg['tie_word_embeddings']:
            raise ValueError('this module builds sigmoid scores normalised '
                             'over the chosen experts, silu gates and an '
                             'untied head')

    def window_of(self, kind):
        return self.window if kind == SLIDING else None

    def experts(self, layer):
        return layer >= self.dense

    @property
    def n_expert_layers(self):
        return len(self.kinds) - self.dense


# ---- the program under test ------------------------------------------------
def _proj(layers, x, size):
    return layers.fc(input=x, size=size, num_flatten_dims=2,
                     bias_attr=False)


def gated_ffn(layers, x, width, out):
    """(silu(x W_gate) * (x W_up)) W_down from fc, swish and a multiply."""
    gate = layers.swish(_proj(layers, x, width))
    return _proj(layers, gate * _proj(layers, x, width), out)


def head_norm(layers, x, heads, d):
    """RMS norm over each head's ``dh`` dimensions of [B, T, heads * dh],
    one [dh] weight shared by the heads."""
    T = int(x.shape[1])
    x = layers.reshape(x, shape=[-1, T, heads, d.dh])
    x = layers.rms_norm(x, epsilon=d.eps, begin_norm_axis=3)
    return layers.reshape(x, shape=[-1, T, heads * d.dh])


def attention_branch(layers, x, d, kind):
    q, k = _proj(layers, x, d.Hq * d.dh), _proj(layers, x, d.Hkv * d.dh)
    v, g = _proj(layers, x, d.Hkv * d.dh), _proj(layers, x, d.Hq * d.dh)
    q, k = head_norm(layers, q, d.Hq, d), head_norm(layers, k, d.Hkv, d)
    if kind == SLIDING:
        q = layers.rotary_embedding(q, d.dh, base=d.theta)
        k = layers.rotary_embedding(k, d.dh, base=d.theta)
    att = layers.flash_attention(
        q, k, v, num_heads=d.Hq, causal=True, num_kv_heads=d.Hkv,
        head_dim=d.dh, window=d.window_of(kind))
    return _proj(layers, att * layers.sigmoid(g), d.D)


def routed_branch(layers, x, d):
    """Router over all experts and the held experts' part of the routed
    sum. Returns (out, tokens a held expert)."""
    scores = layers.router_scores(x, d.E)
    return layers.routed_experts(
        x, scores, hidden_size=d.F, num_experts=d.E, top_k=d.top_k,
        experts_held=d.held, routed_scaling_factor=d.scale, act='swiglu')


def shared_branch(layers, x, d):
    return gated_ffn(layers, x, d.S, d.D)


def dense_branch(layers, x, d):
    return gated_ffn(layers, x, d.I, d.D)


def build(cfg, traffic):
    import paddle_tpu.fluid as fluid
    d = Dims(cfg)
    S = traffic['seq_len']
    layers = fluid.layers
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        tok = layers.data(name='data', shape=[S], dtype='int64')
        label = layers.data(name='label', shape=[S, 1], dtype='int64')
        x = layers.scale(layers.embedding(input=tok, size=[d.V, d.D]),
                         scale=d.embed_scale)

        def norm(t):
            return layers.rms_norm(t, epsilon=d.eps, begin_norm_axis=2)

        expert_tokens = []
        for i, kind in enumerate(d.kinds):
            x = x + norm(attention_branch(layers, norm(x), d, kind))
            m = norm(x)
            if d.experts(i):
                y, tokens = routed_branch(layers, m, d)
                expert_tokens.append(tokens)
                y = y + shared_branch(layers, m, d)
            else:
                y = dense_branch(layers, m, d)
            x = x + norm(y)
        logits = _proj(layers, norm(x), d.V)
        loss = layers.mean(x=layers.softmax_with_cross_entropy(
            logits=logits, label=label))
        opt = cfg['optimizer']
        fluid.optimizer.Adam(learning_rate=opt['learning_rate'],
                             beta1=opt['beta1'], beta2=opt['beta2'],
                             epsilon=opt['epsilon']).minimize(loss)
    names = [p.name for p in main.global_block().all_parameters()]
    return {'main': main, 'startup': startup, 'loss': loss,
            'param_names': names,
            # an expert layer's routed tokens a held expert, for whoever
            # fetches them beside the loss (the timed step does not)
            'expert_tokens': expert_tokens,
            # Adam's first moment after one step from zero is
            # (1 - beta1) times the first gradient
            'grad_state': lambda n: n + '_moment1_0',
            'grad_scale': 1.0 / (1.0 - opt['beta1'])}


def draw_batch(cfg, traffic, key):
    """One step's feed from a PRNG key: B sequences of S+1 ids drawn
    from the vocabulary slice held here; the inputs are the first S,
    the labels the last S (next token)."""
    B, S = traffic['batch'], traffic['seq_len']
    ids = jax.random.randint(key, (B, S + 1), 0, cfg['vocab_size'],
                             jnp.int32)
    return {'data': ids[:, :-1], 'label': ids[:, 1:, None]}


# ---- the plain reference ---------------------------------------------------
class Reference(object):
    """Forward, loss, gradients and Adam in float32 at ``highest`` matmul
    precision, nothing of the program: attention is the full masked
    softmax over every key (the causal and the band mask written out),
    the experts a loop over those held with a dense 0/weight column
    each. Every block is rematerialised, attention goes in blocks of
    query rows (each against all the keys) and the loss in row blocks,
    so that three steps with Adam fit one chip."""

    LOSS_ROWS = 1024
    QUERY_ROWS = 1024

    def __init__(self, cfg):
        self.cfg = cfg
        self.d = Dims(cfg)

    # -- leaves, in the program's creation order ----------------------------
    def block_leaves(self, layer, pre):
        d = self.d
        out = [(pre + 'norm_in', (d.D,), True),
               (pre + 'q', (d.D, d.Hq * d.dh), True),
               (pre + 'k', (d.D, d.Hkv * d.dh), True),
               (pre + 'v', (d.D, d.Hkv * d.dh), True),
               (pre + 'gate', (d.D, d.Hq * d.dh), True),
               (pre + 'q_norm', (d.dh,), True),
               (pre + 'k_norm', (d.dh,), True),
               (pre + 'o', (d.Hq * d.dh, d.D), True),
               (pre + 'norm_post_attn', (d.D,), True),
               (pre + 'norm_pre_mlp', (d.D,), True)]
        if d.experts(layer):
            held = d.held[1]
            out += [(pre + 'router', (d.D, d.E), True),
                    (pre + 'e_gate', (held, d.D, d.F), True),
                    (pre + 'e_up', (held, d.D, d.F), True),
                    (pre + 'e_down', (held, d.F, d.D), True),
                    (pre + 'expert_bias', (d.E,), False),
                    (pre + 's_gate', (d.D, d.S), True),
                    (pre + 's_up', (d.D, d.S), True),
                    (pre + 's_down', (d.S, d.D), True)]
        else:
            out += [(pre + 'f_gate', (d.D, d.I), True),
                    (pre + 'f_up', (d.D, d.I), True),
                    (pre + 'f_down', (d.I, d.D), True)]
        return out + [(pre + 'norm_post_mlp', (d.D,), True)]

    def leaves(self):
        d = self.d
        out = [('embed', (d.V, d.D), True)]
        for i in range(len(d.kinds)):
            out += self.block_leaves(i, 'l%d.' % i)
        return out + [('norm_f', (d.D,), True), ('head', (d.D, d.V), True)]

    def trainable(self):
        return [n for n, _, t in self.leaves() if t]

    def init(self, key):
        """normal(0, initializer_range) matrices, the embedding
        normal(0, embedding_std) (the configuration's ``assumed.init``
        says why), unit norms, expert bias 0."""
        cfg = self.cfg
        params = {}
        for i, (name, shape, _) in enumerate(self.leaves()):
            k = jax.random.fold_in(key, i)
            leaf = name.split('.', 1)[-1]
            if 'norm' in leaf:
                v = jnp.ones(shape, jnp.float32)
            elif leaf == 'expert_bias':
                v = jnp.zeros(shape, jnp.float32)
            elif leaf == 'embed':
                v = cfg['embedding_std'] \
                    * jax.random.normal(k, shape, jnp.float32)
            else:
                v = cfg['initializer_range'] \
                    * jax.random.normal(k, shape, jnp.float32)
            params[name] = v
        return params

    # -- the layers -----------------------------------------------------------
    def rms_norm(self, x, w):
        return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + self.d.eps) * w

    def rotary(self, x):
        """x [B, T, H, dh] at positions 0..T-1: the pairs (i, i + dh/2)
        turned by t * theta^(-2i/dh) (rotate_half)."""
        d = self.d
        half = d.dh // 2
        inv = 1.0 / (d.theta ** (jnp.arange(half, dtype=jnp.float32) * 2.0
                                 / d.dh))
        angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
        cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None]
        sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None]
        turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
        return x * cos + turned * sin

    def attention(self, p, x, pre, dot, kind):
        d = self.d
        Bsz, T, _ = x.shape
        q = dot.matmul(x, p[pre + 'q']).reshape(Bsz, T, d.Hq, d.dh)
        k = dot.matmul(x, p[pre + 'k']).reshape(Bsz, T, d.Hkv, d.dh)
        v = dot.matmul(x, p[pre + 'v']).reshape(Bsz, T, d.Hkv, d.dh)
        gate = jax.nn.sigmoid(dot.matmul(x, p[pre + 'gate']))
        q = self.rms_norm(q, p[pre + 'q_norm'])
        k = self.rms_norm(k, p[pre + 'k_norm'])
        if kind == SLIDING:
            q, k = self.rotary(q), self.rotary(k)
        k = jnp.repeat(k, d.Hq // d.Hkv, axis=2)
        v = jnp.repeat(v, d.Hq // d.Hkv, axis=2)
        window = d.window_of(kind)
        kpos = jnp.arange(T)

        def rows(q_blk, first):
            """The query rows from ``first`` against every key."""
            qpos = first + jnp.arange(q_blk.shape[1])
            keep = qpos[:, None] >= kpos[None, :]               # causal
            if window:
                keep = keep & (qpos[:, None] - kpos[None, :] < window)
            s = dot.einsum('bqhd,bkhd->bhqk', q_blk, k) / math.sqrt(d.dh)
            s = jnp.where(keep[None, None], s, -jnp.inf)
            return dot.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(s, axis=-1),
                              v)

        step = self.QUERY_ROWS
        if T % step == 0 and T > step:
            # the same full-row softmax, a block of query rows at a time
            blocks = q.reshape(Bsz, T // step, step, d.Hq, d.dh) \
                .swapaxes(0, 1)
            o = lax.map(lambda a: jax.checkpoint(rows)(a[0], a[1]),
                        (blocks, jnp.arange(0, T, step)))
            o = o.swapaxes(0, 1)
        else:
            o = rows(q, 0)
        o = o.reshape(Bsz, T, d.Hq * d.dh) * gate
        return dot.matmul(o, p[pre + 'o'])

    def ffn(self, x, gate, up, down, dot):
        return dot.matmul(jax.nn.silu(dot.matmul(x, gate))
                          * dot.matmul(x, up), down)

    def routing(self, p, x, pre, dot):
        """Scores over all experts and, a token, the chosen experts and
        their weights: [.., E], [.., top_k] twice. The bias enters the
        choice only."""
        d = self.d
        s = jax.nn.sigmoid(dot.matmul(x, p[pre + 'router']))
        _, idx = lax.top_k(s + p[pre + 'expert_bias'], d.top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + SCORE_EPS)
        return s, idx, w * d.scale

    def routed(self, p, x, pre, dot):
        """The held experts' part of the routed sum."""
        d = self.d
        _, idx, w = self.routing(p, x, pre, dot)

        def expert(r, held):
            j, gate, up, down = held
            col = jnp.sum(jnp.where(idx == d.held[0] + j, w, 0.0),
                          axis=-1, keepdims=True)
            return r + col * self.ffn(x, gate, up, down, dot), None

        # a loop over the experts held, rolled: sixteen copies of these
        # products at ``highest`` add minutes to the step's compile
        r, _ = lax.scan(expert, jnp.zeros_like(x), (
            jnp.arange(d.held[1]), p[pre + 'e_gate'], p[pre + 'e_up'],
            p[pre + 'e_down']))
        return r

    def shared(self, p, x, pre, dot):
        return self.ffn(x, p[pre + 's_gate'], p[pre + 's_up'],
                        p[pre + 's_down'], dot)

    def mlp(self, layer, p, x, pre, dot):
        if self.d.experts(layer):
            return self.routed(p, x, pre, dot) + self.shared(p, x, pre, dot)
        return self.ffn(x, p[pre + 'f_gate'], p[pre + 'f_up'],
                        p[pre + 'f_down'], dot)

    def block(self, layer, p, x, dot):
        pre = 'l%d.' % layer
        a = self.attention(p, self.rms_norm(x, p[pre + 'norm_in']), pre,
                           dot, self.d.kinds[layer])
        x = x + self.rms_norm(a, p[pre + 'norm_post_attn'])
        y = self.mlp(layer, p, self.rms_norm(x, p[pre + 'norm_pre_mlp']),
                     pre, dot)
        return x + self.rms_norm(y, p[pre + 'norm_post_mlp'])

    def loss(self, params, batch, dot=None):
        dot = dot or Float32Dots()
        x = params['embed'][batch['data']] * self.d.embed_scale
        for i in range(len(self.d.kinds)):
            x = jax.checkpoint(
                lambda p, x, i=i: self.block(i, p, x, dot))(params, x)
        x = self.rms_norm(x, params['norm_f']).reshape(-1, self.d.D)
        labels = batch['label'].reshape(-1)

        def head(w, rows, lab):
            logp = jax.nn.log_softmax(dot.matmul(rows, w), axis=-1)
            return -jnp.sum(jnp.take_along_axis(logp, lab[:, None], axis=-1))

        n, step = x.shape[0], self.LOSS_ROWS
        total = sum(jax.checkpoint(head)(params['head'], x[i:i + step],
                                         labels[i:i + step])
                    for i in range(0, n, step))
        return total / n

    def new_opt_state(self, params):
        return {n: (jnp.zeros_like(params[n]), jnp.zeros_like(params[n]))
                for n in self.trainable()}

    def update(self, params, grads, opt_state, step):
        """Adam (Kingma & Ba 2015, section 2's efficient form):
        alpha_t = alpha sqrt(1 - beta2^t) / (1 - beta1^t);
        p <- p - alpha_t m / (sqrt(v) + eps). ``step`` counts from 1.
        The expert bias is a buffer: it stays as it is."""
        o = self.cfg['optimizer']
        b1, b2 = o['beta1'], o['beta2']
        lr_t = o['learning_rate'] * jnp.sqrt(1.0 - b2 ** step) \
            / (1.0 - b1 ** step)
        new_p, new_s = dict(params), {}
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


class ControlDots(Float32Dots):
    """The control: every matmul operand rounded to fp8, in the forward
    and in the backward products."""

    def matmul(self, a, b):
        from lowprec import fq8, fq8_grad
        return fq8_grad(Float32Dots.matmul(self, fq8(a), fq8(b)))

    def einsum(self, spec, a, b):
        from lowprec import fq8, fq8_grad
        return fq8_grad(Float32Dots.einsum(self, spec, fq8(a), fq8(b)))


# ---- operations the algorithm requires -------------------------------------
def routed_pairs_per_token(d):
    """(token, held expert) pairs a token gives under a balanced
    routing: top_k of E experts chosen, ``held`` of them here."""
    return d.top_k * d.held[1] / float(d.E)


def matmul_weights_per_token(cfg):
    """Weights that meet a token in a matrix multiplication: a layer's
    five attention projections, the dense layers' FFN, an expert
    layer's router, shared expert and the held experts a balanced
    routing sends it to, and the head."""
    d = Dims(cfg)
    attn = 3 * d.D * d.Hq * d.dh + 2 * d.D * d.Hkv * d.dh
    expert = d.D * d.E + 3 * d.D * d.S \
        + routed_pairs_per_token(d) * 3 * d.D * d.F
    return len(d.kinds) * attn + d.dense * 3 * d.D * d.I \
        + d.n_expert_layers * expert + d.D * d.V


def kept_pairs(S, window=None):
    """(query, key) pairs the mask of one sequence keeps: the causal
    triangle, or under a window the band of its last ``window`` keys."""
    if not window or window >= S:
        return S * (S + 1) // 2
    return window * (window + 1) // 2 + (S - window) * window


def attention_pairs(d, S):
    """The pairs all the layers' masks keep, a sequence."""
    return sum(kept_pairs(S, d.window_of(kind)) for kind in d.kinds)


def required_flops(cfg, traffic):
    """Operations one training step requires: 6 per matmul weight per
    token (2 forward, 4 backward) and attention over the pairs each
    layer's mask keeps (QK^T and PV, 2 dh multiply-adds a pair a head;
    backward twice the forward)."""
    d = Dims(cfg)
    B, S = traffic['batch'], traffic['seq_len']
    attention = 3 * B * d.Hq * 2 * 2 * d.dh * attention_pairs(d, S)
    return 6 * matmul_weights_per_token(cfg) * B * S + attention


def items_per_step(cfg, traffic):
    return traffic['batch'] * traffic['seq_len']


def flash_fwd_work(cfg, traffic, chips):
    """(operations, HBM bytes) of the attention forward in one step:
    QK^T and PV over the pairs each layer's mask keeps (the band of a
    window layer, the triangle of a full one: what the algorithm
    requires whatever implements it) for the query heads held; reads Q
    and writes O over the query heads, reads K and V over the KV heads,
    once each in bf16."""
    del chips
    d = Dims(cfg)
    B, S = traffic['batch'], traffic['seq_len']
    flops = B * d.Hq * 2 * 2 * d.dh * attention_pairs(d, S)
    nbytes = len(d.kinds) * B * S * d.dh * 2 * (2 * d.Hq + 2 * d.Hkv)
    return flops, nbytes


def flash_bwd_work(cfg, traffic, chips):
    """Backward: four matmuls where forward has two; reads Q, O, dO and
    writes dQ over the query heads, reads K, V and writes dK, dV over
    the KV heads, in bf16."""
    d = Dims(cfg)
    B, S = traffic['batch'], traffic['seq_len']
    flops, _ = flash_fwd_work(cfg, traffic, chips)
    return 2 * flops, \
        len(d.kinds) * B * S * d.dh * 2 * (4 * d.Hq + 4 * d.Hkv)


def expert_mm_work(cfg, traffic, chips):
    """(operations, HBM bytes) of the nine grouped products over the
    held experts, forward and backward, in one step under a balanced
    routing: 6 operations a weight a routed (token, expert) pair; each
    held expert's three matrices read in bf16 by the forward and by the
    backward and their float32 gradient written once (8 bytes a
    weight), and a pair's rows (input, gate and up hidden, output)
    moved three times in bf16."""
    del chips
    d = Dims(cfg)
    n = d.n_expert_layers
    pairs = traffic['batch'] * traffic['seq_len'] * routed_pairs_per_token(d)
    weights = d.held[1] * 3 * d.D * d.F
    flops = n * 6 * pairs * 3 * d.D * d.F
    nbytes = n * (8 * weights + 3 * 2 * pairs * (2 * d.D + 2 * d.F))
    return int(flops), int(nbytes)
