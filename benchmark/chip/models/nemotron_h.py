"""Nemotron-H (``model_type`` ``nemotron_h``; NVIDIA-Nemotron-3-Super-
120B-A12B-BF16 ``config.json``) for the chip benchmark: the Fluid
program under test, the plain float32 reference, and the operations the
algorithm requires.

A stack of pre-RMSNorm residual blocks ``x <- x + mixer(rms_norm(x))``
whose mixers follow ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer,
``*`` grouped-query attention with no position term, ``E`` a latent
mixture of experts (router over all experts, experts in a latent
space, one shared expert). Final RMSNorm, untied head, no bias but the
conv's. The configuration gives this chip's share of a layer: the Mamba
heads and groups, query and KV heads, routed experts and vocabulary
rows held here (PERF.md, section 4); program and reference take the
same share. Same exports as every model module here: ``build``,
``Reference``, ``required_flops``, the work functions of its kernels.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

SCORE_EPS = 1e-20       # norm_topk_prob's denominator


class Dims(object):
    """The sizes one chip runs, read from the configuration."""

    def __init__(self, cfg):
        self.pattern = cfg['hybrid_override_pattern'][
            :cfg['num_hidden_layers']]
        self.D = cfg['hidden_size']
        self.V = cfg['vocab_size']
        self.eps = cfg['layer_norm_epsilon']
        # Mamba-2
        self.H, self.P = cfg['mamba_num_heads'], cfg['mamba_head_dim']
        self.G, self.N = cfg['n_groups'], cfg['ssm_state_size']
        self.K, self.chunk = cfg['conv_kernel'], cfg['chunk_size']
        self.inner = self.H * self.P
        self.conv = self.inner + 2 * self.G * self.N
        # attention
        self.Hq, self.Hkv = (cfg['num_attention_heads'],
                             cfg['num_key_value_heads'])
        self.dh = cfg['head_dim']
        # experts
        self.E = cfg['router_num_experts']
        self.held = (cfg.get('experts_first', 0), cfg['n_routed_experts'])
        self.top_k = cfg['num_experts_per_tok']
        self.L, self.F = cfg['moe_latent_size'], cfg['moe_intermediate_size']
        self.S = cfg['moe_shared_expert_intermediate_size']
        self.scale = float(cfg['routed_scaling_factor'])
        if not cfg['norm_topk_prob']:
            raise ValueError('routing weights are normalised over the '
                             'chosen experts: norm_topk_prob must hold')

    def count(self, kind):
        return self.pattern.count(kind)


# ---- the program under test ------------------------------------------------
def _proj(layers, x, size, act=None):
    return layers.fc(input=x, size=size, num_flatten_dims=2, act=act,
                     bias_attr=False)


def mamba_branch(layers, x, d):
    return layers.mamba2_mixer(
        x, num_heads=d.H, head_dim=d.P, state_size=d.N, n_groups=d.G,
        conv_kernel=d.K, chunk_size=d.chunk, epsilon=d.eps)


def attention_branch(layers, x, d):
    q = _proj(layers, x, d.Hq * d.dh)
    k, v = _proj(layers, x, d.Hkv * d.dh), _proj(layers, x, d.Hkv * d.dh)
    att = layers.flash_attention(q, k, v, num_heads=d.Hq, causal=True,
                                 num_kv_heads=d.Hkv, head_dim=d.dh)
    return _proj(layers, att, d.D)


def routed_branch(layers, x, d):
    """The routed experts' part: router over all experts, down to the
    latent, the held experts, up again. Returns (out, tokens a held
    expert)."""
    scores = layers.router_scores(x, d.E)
    u = _proj(layers, x, d.L)
    r, tokens = layers.routed_experts(
        u, scores, hidden_size=d.F, num_experts=d.E, top_k=d.top_k,
        experts_held=d.held, routed_scaling_factor=d.scale)
    return _proj(layers, r, d.D), tokens


def shared_branch(layers, x, d):
    return _proj(layers, layers.square(_proj(layers, x, d.S, act='relu')),
                 d.D)


def build(cfg, traffic):
    import paddle_tpu.fluid as fluid
    d = Dims(cfg)
    S = traffic['seq_len']
    layers = fluid.layers
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        tok = layers.data(name='data', shape=[S], dtype='int64')
        label = layers.data(name='label', shape=[S, 1], dtype='int64')
        x = layers.embedding(input=tok, size=[d.V, d.D])

        def norm(t):
            return layers.rms_norm(t, epsilon=d.eps, begin_norm_axis=2)

        expert_tokens = []
        for kind in d.pattern:
            h = norm(x)
            if kind == 'M':
                x = x + mamba_branch(layers, h, d)
            elif kind == '*':
                x = x + attention_branch(layers, h, d)
            elif kind == 'E':
                routed, tokens = routed_branch(layers, h, d)
                expert_tokens.append(tokens)
                x = x + (routed + shared_branch(layers, h, d))
            else:
                raise ValueError('unknown layer kind %r' % kind)
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
    precision, nothing of the program: the scan is the sequential
    recurrence over time, attention the full S x S softmax, the experts
    a loop over those held with a dense 0/weight column each. Every
    block is rematerialised (the scan in segments) and the loss goes in
    row blocks, so that three steps with Adam fit one chip."""

    LOSS_ROWS = 1024
    SCAN_SEGMENT = 64

    def __init__(self, cfg):
        self.cfg = cfg
        self.d = Dims(cfg)

    # -- leaves, in the program's creation order ----------------------------
    def block_leaves(self, kind, pre):
        d = self.d
        out = [(pre + 'norm', (d.D,), True)]
        if kind == 'M':
            out += [(pre + 'in_proj', (d.D, 2 * d.inner + 2 * d.G * d.N
                                       + d.H), True),
                    (pre + 'conv.w', (d.conv, d.K), True),
                    (pre + 'conv.b', (d.conv,), True),
                    (pre + 'A_log', (d.H,), True),
                    (pre + 'D', (d.H,), True),
                    (pre + 'dt_bias', (d.H,), True),
                    (pre + 'gate_norm', (d.inner,), True),
                    (pre + 'out_proj', (d.inner, d.D), True)]
        elif kind == '*':
            out += [(pre + 'q', (d.D, d.Hq * d.dh), True),
                    (pre + 'k', (d.D, d.Hkv * d.dh), True),
                    (pre + 'v', (d.D, d.Hkv * d.dh), True),
                    (pre + 'o', (d.Hq * d.dh, d.D), True)]
        else:
            out += [(pre + 'router', (d.D, d.E), True),
                    (pre + 'down', (d.D, d.L), True),
                    (pre + 'w1', (d.held[1], d.L, d.F), True),
                    (pre + 'w2', (d.held[1], d.F, d.L), True),
                    (pre + 'e_score_correction_bias', (d.E,), False),
                    (pre + 'up', (d.L, d.D), True),
                    (pre + 'shared1', (d.D, d.S), True),
                    (pre + 'shared2', (d.S, d.D), True)]
        return out

    def leaves(self):
        d = self.d
        out = [('embed', (d.V, d.D), True)]
        for i, kind in enumerate(d.pattern):
            out += self.block_leaves(kind, 'l%d.' % i)
        return out + [('norm_f', (d.D,), True), ('head', (d.D, d.V), True)]

    def trainable(self):
        return [n for n, _, t in self.leaves() if t]

    def init(self, key):
        """normal(0, initializer_range) matrices; every branch's output
        projection divided by sqrt(published depth) (the family's
        ``rescale_prenorm_residual``, which names the Mamba one) and a
        unit-variance embedding, so that the stream stays the token's
        own and an untrained router sees what a trained, balanced one
        does (PERF.md, PR 31: with 0.02 everywhere the held experts
        draw 0.3 to 3 times their share, by the seed); unit norms, the
        conv as torch's Conv1d default (uniform within
        1/sqrt(taps)), A = the head's index from 1, D = 1, dt_bias =
        softplus^-1 of a log-uniform time step in [time_step_min,
        time_step_max] floored at time_step_floor, correction bias 0."""
        cfg, d = self.cfg, self.d
        std = cfg['initializer_range']
        params = {}
        for i, (name, shape, _) in enumerate(self.leaves()):
            k = jax.random.fold_in(key, i)
            leaf = name.split('.', 1)[-1]
            if leaf in ('norm', 'gate_norm', 'norm_f', 'D'):
                v = jnp.ones(shape, jnp.float32)
            elif leaf == 'e_score_correction_bias':
                v = jnp.zeros(shape, jnp.float32)
            elif leaf in ('conv.w', 'conv.b'):
                bound = 1.0 / math.sqrt(d.K)
                v = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
            elif leaf == 'A_log':
                v = jnp.log(jnp.arange(1, d.H + 1, dtype=jnp.float32))
            elif leaf == 'dt_bias':
                lo, hi = (math.log(cfg['time_step_min']),
                          math.log(cfg['time_step_max']))
                dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                                lo, hi))
                dt = jnp.maximum(dt, cfg['time_step_floor'])
                v = dt + jnp.log(-jnp.expm1(-dt))
            elif leaf == 'embed':
                v = jax.random.normal(k, shape, jnp.float32)
            else:
                v = std * jax.random.normal(k, shape, jnp.float32)
                if leaf in ('out_proj', 'o', 'up', 'shared2'):
                    v = v / math.sqrt(
                        cfg['published']['num_hidden_layers'])
            params[name] = v
        return params

    # -- the layers -----------------------------------------------------------
    def rms_norm(self, x, w, group=None):
        shape = x.shape
        if group:
            x = x.reshape(shape[:-1] + (shape[-1] // group, group))
        x = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + self.d.eps)
        return x.reshape(shape) * w

    def scan(self, x, dt, a, b, c):
        """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t, y_t = S_t C_t,
        one step a position. x [B, T, H, P], dt [B, T, H], a [H],
        b, c [B, T, G, N] -> y [B, T, H, P]."""
        Bsz, T, H, P = x.shape
        G, N = b.shape[2:]
        r = H // G
        b = jnp.repeat(b, r, axis=2)
        c = jnp.repeat(c, r, axis=2)

        def step(s, inp):
            x_t, dt_t, b_t, c_t = inp
            s = jnp.exp(dt_t * a)[..., None, None] * s \
                + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
            return s, jnp.sum(s * c_t[:, :, None, :], axis=-1)

        def run(s, seq):
            return lax.scan(step, s, seq)

        seq = tuple(t.swapaxes(0, 1) for t in (x, dt, b, c))
        s0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
        seg = self.SCAN_SEGMENT
        if T % seg == 0 and T > seg:
            # the same recurrence, rematerialised a segment at a time
            seq = tuple(t.reshape((T // seg, seg) + t.shape[1:])
                        for t in seq)
            _, y = lax.scan(jax.checkpoint(run), s0, seq)
            y = y.reshape((T,) + y.shape[2:])
        else:
            _, y = run(s0, seq)
        return y.swapaxes(0, 1)

    def mamba(self, p, x, pre, dot):
        d = self.d
        Bsz, T, _ = x.shape
        gn = d.G * d.N
        zxbcdt = dot.matmul(x, p[pre + 'in_proj'])
        z, xbc, dt = jnp.split(zxbcdt, [d.inner, d.inner + d.conv], axis=-1)
        padded = jnp.pad(xbc, ((0, 0), (d.K - 1, 0), (0, 0)))
        w = p[pre + 'conv.w']
        xbc = sum(padded[:, k:k + T] * w[:, k] for k in range(d.K)) \
            + p[pre + 'conv.b']
        xbc = jax.nn.silu(xbc)
        xs, b, c = jnp.split(xbc, [d.inner, d.inner + gn], axis=-1)
        dt = jax.nn.softplus(dt + p[pre + 'dt_bias'])
        xs = xs.reshape(Bsz, T, d.H, d.P)
        y = self.scan(xs, dt, -jnp.exp(p[pre + 'A_log']),
                      b.reshape(Bsz, T, d.G, d.N),
                      c.reshape(Bsz, T, d.G, d.N))
        y = y + p[pre + 'D'][:, None] * xs
        y = y.reshape(Bsz, T, d.inner) * jax.nn.silu(z)
        y = self.rms_norm(y, p[pre + 'gate_norm'], group=d.inner // d.G)
        return dot.matmul(y, p[pre + 'out_proj'])

    def attention(self, p, x, pre, dot):
        d = self.d
        Bsz, T, _ = x.shape

        def heads(t, n):
            return t.reshape(Bsz, T, n, d.dh).transpose(0, 2, 1, 3)

        q = heads(dot.matmul(x, p[pre + 'q']), d.Hq)
        k = heads(dot.matmul(x, p[pre + 'k']), d.Hkv)
        v = heads(dot.matmul(x, p[pre + 'v']), d.Hkv)
        k = jnp.repeat(k, d.Hq // d.Hkv, axis=1)
        v = jnp.repeat(v, d.Hq // d.Hkv, axis=1)
        s = dot.einsum('bhqd,bhkd->bhqk', q, k) / math.sqrt(d.dh)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        o = dot.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(s, axis=-1), v)
        o = o.transpose(0, 2, 1, 3).reshape(Bsz, T, d.Hq * d.dh)
        return dot.matmul(o, p[pre + 'o'])

    def routing(self, p, x, pre, dot):
        """Scores over all experts and, a token, the chosen experts and
        their weights: [.., E], [.., top_k] twice."""
        d = self.d
        s = jax.nn.sigmoid(dot.matmul(x, p[pre + 'router']))
        _, idx = lax.top_k(s + p[pre + 'e_score_correction_bias'], d.top_k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + SCORE_EPS)
        return s, idx, w * d.scale

    def routed(self, p, x, pre, dot):
        """The held experts' part of the routed sum, up-projected."""
        d = self.d
        _, idx, w = self.routing(p, x, pre, dot)
        u = dot.matmul(x, p[pre + 'down'])

        def expert(r, held):
            j, w1, w2 = held
            col = jnp.sum(jnp.where(idx == d.held[0] + j, w, 0.0),
                          axis=-1, keepdims=True)
            h = jnp.square(jnp.maximum(dot.matmul(u, w1), 0.0))
            return r + col * dot.matmul(h, w2), None

        # a loop over the experts held, rolled: eight copies of these
        # products at ``highest`` add minutes to the step's compile
        r, _ = lax.scan(expert, jnp.zeros_like(u), (
            jnp.arange(d.held[1]), p[pre + 'w1'], p[pre + 'w2']))
        return dot.matmul(r, p[pre + 'up'])

    def shared(self, p, x, pre, dot):
        h = jnp.square(jnp.maximum(dot.matmul(x, p[pre + 'shared1']), 0.0))
        return dot.matmul(h, p[pre + 'shared2'])

    def branch(self, kind, p, x, pre, dot):
        if kind == 'M':
            return self.mamba(p, x, pre, dot)
        if kind == '*':
            return self.attention(p, x, pre, dot)
        return self.routed(p, x, pre, dot) + self.shared(p, x, pre, dot)

    def loss(self, params, batch, dot=None):
        dot = dot or Float32Dots()
        x = params['embed'][batch['data']]
        for i, kind in enumerate(self.d.pattern):
            pre = 'l%d.' % i

            def block(p, x, kind=kind, pre=pre):
                return x + self.branch(
                    kind, p, self.rms_norm(x, p[pre + 'norm']), pre, dot)
            x = jax.checkpoint(block)(params, x)
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
        The correction bias is a buffer: it stays as it is."""
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
    """Weights that meet a token in a matrix multiplication: all of a
    Mamba or attention layer's projections, an expert layer's router,
    latent projections and shared expert, the held experts a balanced
    routing sends it to, and the head."""
    d = Dims(cfg)
    mamba = d.D * (2 * d.inner + 2 * d.G * d.N + d.H) + d.inner * d.D
    attn = 2 * d.D * d.Hq * d.dh + 2 * d.D * d.Hkv * d.dh
    expert = d.D * d.E + 2 * d.D * d.L + 2 * d.D * d.S \
        + routed_pairs_per_token(d) * 2 * d.L * d.F
    return d.count('M') * mamba + d.count('*') * attn \
        + d.count('E') * expert + d.D * d.V


def scan_flops_per_token(d):
    """The recurrence itself, a head: decay, outer product and add into
    the [P, N] state (3 P N), the read-out against C (2 P N); and the
    depthwise conv's K multiply-adds a channel."""
    return d.H * 5 * d.P * d.N + 2 * d.K * d.conv


def required_flops(cfg, traffic):
    """Operations one training step requires: 6 per matmul weight per
    token (2 forward, 4 backward), causal attention (QK^T and PV at
    half, backward twice the forward), three times the scan's."""
    d = Dims(cfg)
    S = traffic['seq_len']
    attention = 3 * d.count('*') * 2 * S * d.Hq * d.dh
    per_token = 6 * matmul_weights_per_token(cfg) + attention \
        + 3 * d.count('M') * scan_flops_per_token(d)
    return per_token * traffic['batch'] * S


def items_per_step(cfg, traffic):
    return traffic['batch'] * traffic['seq_len']


def flash_fwd_work(cfg, traffic, chips):
    """(operations, HBM bytes) of causal attention forward in one step:
    QK^T and PV under the mask for the query heads held; reads Q and
    writes O over the query heads, reads K and V over the KV heads,
    once each in bf16."""
    del chips
    d = Dims(cfg)
    B, S, n = traffic['batch'], traffic['seq_len'], d.count('*')
    flops = n * B * d.Hq * 2 * (2 * S * S * d.dh) // 2
    nbytes = n * B * S * d.dh * 2 * (2 * d.Hq + 2 * d.Hkv)
    return flops, nbytes


def flash_bwd_work(cfg, traffic, chips):
    """Backward: four matmuls where forward has two; reads Q, O, dO and
    writes dQ over the query heads, reads K, V and writes dK, dV over
    the KV heads, in bf16."""
    d = Dims(cfg)
    B, S, n = traffic['batch'], traffic['seq_len'], d.count('*')
    flops, _ = flash_fwd_work(cfg, traffic, chips)
    return 2 * flops, n * B * S * d.dh * 2 * (4 * d.Hq + 4 * d.Hkv)


def ssd_work(cfg, traffic, chips):
    """(operations, HBM bytes) of the scan op, forward and backward, in
    one step: three times the recurrence's operations; forward reads x,
    B, C, dt and writes y in bf16, backward reads them and dy and
    writes dx, dB, dC, ddt: three times the forward's bytes."""
    del chips
    d = Dims(cfg)
    tokens = traffic['batch'] * traffic['seq_len'] * d.count('M')
    flops = 3 * tokens * d.H * 5 * d.P * d.N
    nbytes = 3 * tokens * 2 * (2 * d.inner + 2 * d.G * d.N + d.H)
    return flops, nbytes


def expert_mm_work(cfg, traffic, chips):
    """(operations, HBM bytes) of the six grouped products over the held
    experts in the latent width, forward and backward, in one step under
    a balanced routing: 6 operations a weight a routed (token, expert)
    pair; each held expert's two matrices read in bf16 by the forward
    and by the backward and their float32 gradient written once (8
    bytes a weight), and a pair's rows (latent in, hidden, latent out)
    moved three times in bf16. ``routed_experts_roofline.tok`` divides
    this by the time of the whole ``routed_experts`` op, which also
    runs the choice of experts (``top_k`` over the 512 scores), the
    rows' placement (sort, gather, scatter-add), ``relu2`` and the
    weighing: none of these is counted, as in ``afmoe.expert_mm_work``.
    The latent projections and the router are ops of their own."""
    del chips
    d = Dims(cfg)
    n = d.count('E')
    pairs = traffic['batch'] * traffic['seq_len'] * routed_pairs_per_token(d)
    weights = d.held[1] * 2 * d.L * d.F
    flops = n * 6 * pairs * 2 * d.L * d.F
    nbytes = n * (8 * weights + 3 * 2 * pairs * (2 * d.L + d.F))
    return int(flops), int(nbytes)
