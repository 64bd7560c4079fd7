"""TPU-native transformer LM — the paddle_tpu flagship.

This is the framework's headline long-context model: a decoder-only
transformer expressed directly in JAX with explicit mesh shardings, so one
jitted training step scales over a `jax.sharding.Mesh` with axes

    dp — data parallel (batch dim; gradients psum over ICI)
    tp — tensor parallel (hidden/head dim; Megatron-style column/row splits)
    sp — sequence parallel (sequence dim; ring attention over a ppermute ring)

Design notes (vs the reference, paddle/fluid has no transformer — this is the
capability ceiling of its machine_translation seq2seq+attention stack
re-imagined for TPU):
  * all matmuls run in bfloat16 on the MXU with f32 accumulation
    (preferred_element_type), params kept in f32.
  * attention: online-softmax blockwise attention; over the sp axis the KV
    blocks rotate around the ring via `jax.lax.ppermute` so no device ever
    materialises the full [T, T] score matrix (ring attention).
  * the whole step (fwd + bwd + adam) is ONE XLA program; param/opt state is
    donated.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


__all__ = ['TransformerConfig', 'init_params', 'forward', 'loss_fn',
           'make_train_step', 'param_specs', 'ring_attention',
           'stack_pipeline_params', 'unstack_pipeline_params',
           'make_pipeline_fn', 'forward_pipelined',
           'pipeline_param_specs', 'make_pipeline_train_step',
           'shard_params', 'init_adam_state']


class TransformerConfig(object):
    def __init__(self, vocab=32000, d_model=512, n_heads=8, n_layers=4,
                 d_ff=2048, max_len=2048, dtype=jnp.bfloat16,
                 remat=False):
        assert d_model % n_heads == 0
        self.vocab = vocab
        self.d_model = d_model
        self.n_heads = n_heads
        self.n_layers = n_layers
        self.d_ff = d_ff
        self.max_len = max_len
        self.dtype = dtype
        self.remat = remat
        self.d_head = d_model // n_heads


def _init(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def init_params(cfg, seed=0):
    """f32 master params as a flat dict pytree."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 2 + cfg.n_layers)
    p = {
        'embed': _init(ks[0], (cfg.vocab, cfg.d_model), 0.02),
        'pos': _init(ks[1], (cfg.max_len, cfg.d_model), 0.02),
        'ln_f_g': jnp.ones((cfg.d_model,), jnp.float32),
        'ln_f_b': jnp.zeros((cfg.d_model,), jnp.float32),
    }
    for i in range(cfg.n_layers):
        kq, kk, kv, ko, k1, k2 = jax.random.split(ks[2 + i], 6)
        s = 0.02
        so = 0.02 / math.sqrt(2 * cfg.n_layers)
        p['l%d' % i] = {
            'ln1_g': jnp.ones((cfg.d_model,), jnp.float32),
            'ln1_b': jnp.zeros((cfg.d_model,), jnp.float32),
            'wq': _init(kq, (cfg.d_model, cfg.d_model), s),
            'wk': _init(kk, (cfg.d_model, cfg.d_model), s),
            'wv': _init(kv, (cfg.d_model, cfg.d_model), s),
            'wo': _init(ko, (cfg.d_model, cfg.d_model), so),
            'ln2_g': jnp.ones((cfg.d_model,), jnp.float32),
            'ln2_b': jnp.zeros((cfg.d_model,), jnp.float32),
            'w1': _init(k1, (cfg.d_model, cfg.d_ff), s),
            'b1': jnp.zeros((cfg.d_ff,), jnp.float32),
            'w2': _init(k2, (cfg.d_ff, cfg.d_model), so),
            'b2': jnp.zeros((cfg.d_model,), jnp.float32),
        }
    return p


def param_specs(cfg):
    """PartitionSpecs: Megatron column/row splits over 'tp'; vocab over 'tp'
    for the (large) embedding."""
    lp = {
        'ln1_g': P(), 'ln1_b': P(), 'ln2_g': P(), 'ln2_b': P(),
        'wq': P(None, 'tp'), 'wk': P(None, 'tp'), 'wv': P(None, 'tp'),
        'wo': P('tp', None),
        'w1': P(None, 'tp'), 'b1': P('tp'),
        'w2': P('tp', None), 'b2': P(),
    }
    specs = {'embed': P('tp', None), 'pos': P(), 'ln_f_g': P(),
             'ln_f_b': P()}
    for i in range(cfg.n_layers):
        specs['l%d' % i] = dict(lp)
    return specs


def _layer_norm(x, g, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps) * g + b
    return out.astype(x.dtype)


def _causal_attention(q, k, v, q_off=0, k_off=0):
    """Plain blockwise causal attention (ring-attention building block).
    q,k,v: [B, T, H, Dh] (bf16); offsets give the global positions of the
    local blocks. Math lives in ops/pallas_kernels.attention_reference."""
    from ..ops.pallas_kernels import attention_reference
    return attention_reference(q, k, v, causal=True, q_off=q_off,
                               k_off=k_off)


def ring_attention(q, k, v, axis_name='sp'):
    """Causal ring attention inside shard_map: the sequence dim is sharded
    over `axis_name`; KV blocks rotate around the ring (ppermute over ICI)
    while each device merges per-block (out, lse) partials by exact
    logsumexp weighting. Memory per device: O(T_local) when the Pallas
    kernel engages (TPU, 128-aligned blocks >= _FLASH_MIN_T),
    O(T_local^2) on the XLA fallback — never O(T^2) either way.

    Per ring step the held KV block is globally either entirely in the
    PAST (full unmasked attention), the DIAGONAL (plain causal), or the
    FUTURE (contributes nothing) — so each partial is computed by the
    Pallas flash kernel (ops/pallas_kernels.flash_attention_with_lse;
    XLA reference off-TPU) with NO positional offsets, and lse gradients
    flow through the merge via the kernel's lse-aware backward.

    q,k,v: [B, T_local, H, Dh]. Returns [B, T_local, H, Dh].
    """
    from ..ops.pallas_kernels import flash_attention_with_lse
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, T, H, Dh = q.shape

    def partial_block(k_cur, v_cur, kind):
        # kind: 0 = past (full), 1 = diagonal (causal), 2 = future (skip)
        def past(_):
            return flash_attention_with_lse(q, k_cur, v_cur,
                                            causal=False)
        def diag(_):
            return flash_attention_with_lse(q, k_cur, v_cur,
                                            causal=True)
        def future(_):
            # finite "empty" sentinel: -inf would make 0 * nan gradients
            # through logaddexp; exp(-1e30 - real_lse) is exactly 0
            return (jnp.zeros_like(q),
                    jnp.full((B, H, T), -1e30, jnp.float32))
        return jax.lax.switch(kind, (past, diag, future), None)

    def step(carry, i):
        acc, lse_acc, k_cur, v_cur = carry
        src = (idx - i) % n            # whose KV block we hold this step
        kind = jnp.where(src == idx, 1, jnp.where(src < idx, 0, 2))
        out_b, lse_b = partial_block(k_cur, v_cur, kind)
        # exact merge of normalized partials by logsumexp weights
        lse_new = jnp.logaddexp(lse_acc, lse_b)
        w_acc = jnp.exp(lse_acc - lse_new)
        w_b = jnp.exp(lse_b - lse_new)
        # weights are [B, H, T]; outputs are [B, T, H, Dh]
        wt = lambda w: jnp.transpose(w, (0, 2, 1))[..., None]
        acc = acc * wt(w_acc) + out_b.astype(jnp.float32) * wt(w_b)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (acc, lse_new, k_nxt, v_nxt), None

    acc0 = jnp.zeros((B, T, H, Dh), jnp.float32)
    lse0 = jnp.full((B, H, T), -1e30, jnp.float32)
    (acc, _, _, _), _ = jax.lax.scan(step, (acc0, lse0, k, v),
                                     jnp.arange(n))
    return acc.astype(q.dtype)


def _block(x, lp, cfg, attn_fn):
    h = _layer_norm(x, lp['ln1_g'], lp['ln1_b'])
    B, T, D = h.shape
    H, Dh = cfg.n_heads, cfg.d_head
    dt = cfg.dtype
    q = (h @ lp['wq'].astype(dt)).reshape(B, T, H, Dh)
    k = (h @ lp['wk'].astype(dt)).reshape(B, T, H, Dh)
    v = (h @ lp['wv'].astype(dt)).reshape(B, T, H, Dh)
    a = attn_fn(q, k, v).reshape(B, T, D)
    x = x + a @ lp['wo'].astype(dt)
    h = _layer_norm(x, lp['ln2_g'], lp['ln2_b'])
    h = jax.nn.gelu(h @ lp['w1'].astype(dt) + lp['b1'].astype(dt))
    return x + h @ lp['w2'].astype(dt) + lp['b2'].astype(dt)


def forward(params, tokens, cfg, attn_fn=None, pos_offset=0):
    """tokens [B, T] int32 -> logits [B, T, vocab] f32."""
    if attn_fn is None:
        # Pallas flash-attention on TPU (ops/pallas_kernels.py); identical
        # -math XLA fallback elsewhere / for non-block-aligned shapes.
        from ..ops.pallas_kernels import flash_attention
        attn_fn = lambda q, k, v: flash_attention(q, k, v, causal=True)
    dt = cfg.dtype
    x = params['embed'].astype(dt)[tokens]
    T = tokens.shape[1]
    x = x + jax.lax.dynamic_slice_in_dim(
        params['pos'].astype(dt), pos_offset, T, 0)[None]
    blk = _block
    if cfg.remat:
        blk = jax.checkpoint(_block, static_argnums=(2, 3))
    for i in range(cfg.n_layers):
        x = blk(x, params['l%d' % i], cfg, attn_fn)
    x = _layer_norm(x, params['ln_f_g'], params['ln_f_b'])
    return (x @ params['embed'].astype(dt).T).astype(jnp.float32)


def loss_fn(params, inputs, targets, cfg, attn_fn=None, pos_offset=0):
    """Next-token cross entropy. inputs/targets: [B, T] (targets = inputs
    shifted by one; split on the host so the sequence dim stays divisible
    by the sp axis)."""
    logits = forward(params, inputs, cfg, attn_fn, pos_offset)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


# ---------------------------------------------------------------------------
# sharded train step
# ---------------------------------------------------------------------------
def init_adam_state(params):
    z = lambda p: jnp.zeros_like(p)
    return {'m': jax.tree_util.tree_map(z, params),
            'v': jax.tree_util.tree_map(z, params),
            't': jnp.zeros((), jnp.int32)}


def _adam_update(params, grads, opt, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8):
    t = opt['t'] + 1
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g,
                               opt['m'], grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                               opt['v'], grads)
    tc = t.astype(jnp.float32)
    corr = jnp.sqrt(1 - b2 ** tc) / (1 - b1 ** tc)
    new_p = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * corr * m / (jnp.sqrt(v) + eps),
        params, m, v)
    return new_p, {'m': m, 'v': v, 't': t}


# ---------------------------------------------------------------------------
# pipeline parallelism (pp axis)
# ---------------------------------------------------------------------------
def stack_pipeline_params(params, cfg, n_stages):
    """Per-layer trees l0..l{L-1} -> one 'layers' tree whose leaves are
    [n_stages, L/n_stages, ...] (stage-major), ready to shard over the
    'pp' mesh axis on dim 0. Non-layer params pass through."""
    L = cfg.n_layers
    assert L % n_stages == 0, (L, n_stages)
    per = L // n_stages
    layer_trees = [params['l%d' % i] for i in range(L)]
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs).reshape((n_stages, per) + xs[0].shape),
        *layer_trees)
    rest = {k: v for k, v in params.items() if not _is_layer_key(k)}
    rest['layers'] = stacked
    return rest


def unstack_pipeline_params(params, cfg):
    """Inverse of stack_pipeline_params."""
    stacked = params['layers']
    L = cfg.n_layers
    out = {k: v for k, v in params.items() if k != 'layers'}
    flat = jax.tree_util.tree_map(
        lambda x: x.reshape((L,) + x.shape[2:]), stacked)
    for i in range(L):
        out['l%d' % i] = jax.tree_util.tree_map(lambda x: x[i], flat)
    return out


def _is_layer_key(k):
    return k.startswith('l') and k[1:].isdigit()


def make_pipeline_fn(cfg, mesh, attn_fn, n_micro, axis_name='pp'):
    """The pipelined middle of the network: [B, T, D] -> [B, T, D]
    through all transformer blocks, GPipe fill/drain over the pp axis.

    shard_map covers ONLY the block stack — embedding/ln_f/unembed stay
    outside under the SPMD partitioner, so shard_map's replication rules
    insert the right gradient psums (activations enter replicated over
    pp; stage weights enter sharded over pp). Per tick every stage runs
    its local layers and ppermutes the activation to the next stage;
    stage 0 injects microbatch t, the last stage collects microbatch
    t-(S-1). Bubble fraction is (S-1)/(n_micro+S-1).
    """
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    S = axes[axis_name]
    per = cfg.n_layers // S
    if attn_fn is None:
        from ..ops.pallas_kernels import flash_attention
        attn_fn = lambda q, k, v: flash_attention(q, k, v, causal=True)

    def run(layers, x):
        # layers leaves arrive [1, per, ...]; x arrives [B_local, T, D]
        layers = jax.tree_util.tree_map(lambda v: v[0], layers)
        stage = jax.lax.axis_index(axis_name)
        B, T, D = x.shape
        assert B % n_micro == 0, (B, n_micro)
        bm = B // n_micro
        x_micro = x.reshape(n_micro, bm, T, D)

        blk = _block
        if cfg.remat:
            blk = jax.checkpoint(_block, static_argnums=(2, 3))

        def apply_stage(h):
            for j in range(per):
                lp = jax.tree_util.tree_map(lambda v: v[j], layers)
                h = blk(h, lp, cfg, attn_fn)
            return h

        def tick(carry, t):
            state, outbuf = carry
            inj = x_micro[jnp.minimum(t, n_micro - 1)]
            x_in = jnp.where(stage == 0, inj, state)
            y = apply_stage(x_in)
            out_t = t - (S - 1)
            idx = jnp.clip(out_t, 0, n_micro - 1)
            is_out = (stage == S - 1) & (out_t >= 0)
            cur = jax.lax.dynamic_index_in_dim(outbuf, idx, 0,
                                               keepdims=False)
            outbuf = jax.lax.dynamic_update_index_in_dim(
                outbuf, jnp.where(is_out, y, cur), idx, 0)
            perm = [(i, (i + 1) % S) for i in range(S)]
            state = jax.lax.ppermute(y, axis_name, perm)
            return (state, outbuf), None

        state0 = jnp.zeros((bm, T, D), x.dtype)
        outbuf0 = jnp.zeros((n_micro, bm, T, D), x.dtype)
        (_, outbuf), _ = jax.lax.scan(
            tick, (state0, outbuf0), jnp.arange(n_micro + S - 1))
        # outputs live on the last stage; replicate them over pp
        outbuf = jax.lax.psum(
            jnp.where(stage == S - 1, outbuf, jnp.zeros_like(outbuf)),
            axis_name)
        return outbuf.reshape(B, T, D)

    layers_specs = _stacked_layer_specs(cfg, S, axis_name)
    batch_axis = 'dp' if axes.get('dp', 1) > 1 else None
    return jax.shard_map(
        run, mesh=mesh,
        in_specs=(layers_specs, P(batch_axis, None, None)),
        out_specs=P(batch_axis, None, None),
        check_vma=False)


def forward_pipelined(params, tokens, cfg, pipe_fn, pos_offset=0):
    """Pipelined forward: embed -> pp block pipeline -> ln_f/unembed.
    params must be in stacked form (stack_pipeline_params)."""
    dt = cfg.dtype
    x = params['embed'].astype(dt)[tokens]
    T = tokens.shape[1]
    x = x + jax.lax.dynamic_slice_in_dim(
        params['pos'].astype(dt), pos_offset, T, 0)[None]
    x = pipe_fn(params['layers'], x)
    x = _layer_norm(x, params['ln_f_g'], params['ln_f_b'])
    return (x @ params['embed'].astype(dt).T).astype(jnp.float32)


def _stacked_layer_specs(cfg, n_stages, axis_name='pp'):
    """PartitionSpec tree for stack_pipeline_params' 'layers' entry:
    stage dim over `axis_name`, everything else replicated."""
    sample = jax.eval_shape(
        lambda: stack_pipeline_params(init_params(cfg, 0), cfg,
                                      n_stages))['layers']
    return jax.tree_util.tree_map(
        lambda x: P(*((axis_name,) + (None,) * (x.ndim - 1))), sample)


def pipeline_param_specs(cfg, n_stages, mesh=None, axis_name='pp'):
    """PartitionSpecs for the stacked form: stage dim over `axis_name`,
    everything else from param_specs' non-layer entries (axis names
    absent from `mesh` degrade to replicated)."""
    base = param_specs(cfg)
    specs = {k: v for k, v in base.items() if not _is_layer_key(k)}
    if mesh is not None:
        from ..parallel.mesh import clean_spec
        specs = jax.tree_util.tree_map(
            lambda s: P(*clean_spec(tuple(s), mesh)), specs,
            is_leaf=lambda x: isinstance(x, P))
    specs['layers'] = _stacked_layer_specs(cfg, n_stages, axis_name)
    return specs


def make_pipeline_train_step(cfg, mesh, lr=1e-3, n_micro=4,
                             axis_name='pp'):
    """(stacked_params, opt, inputs, targets) -> (loss, params', opt')
    with pipeline parallelism over the mesh's 'pp' axis (+ dp batch
    sharding). v1 scope: dp x pp meshes (tensor/sequence axes compose
    via make_train_step instead)."""
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    assert axes.get(axis_name, 1) > 1, "mesh has no %s axis" % axis_name
    pipe_fn = make_pipeline_fn(cfg, mesh, None, n_micro, axis_name)

    pspecs = pipeline_param_specs(cfg, axes[axis_name], mesh, axis_name)
    param_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, P))
    opt_sh = {'m': param_sh, 'v': param_sh,
              't': NamedSharding(mesh, P())}
    tok_sh = NamedSharding(mesh, P('dp') if axes.get('dp', 1) > 1
                           else P())

    def loss_pp(params, inputs, targets):
        logits = forward_pipelined(params, inputs, cfg, pipe_fn)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None],
                                   axis=-1)[..., 0]
        return jnp.mean(nll)

    def step(params, opt, inputs, targets):
        loss, grads = jax.value_and_grad(loss_pp)(params, inputs,
                                                  targets)
        new_params, new_opt = _adam_update(params, grads, opt, lr)
        return loss, new_params, new_opt

    return jax.jit(
        step,
        in_shardings=(param_sh, opt_sh, tok_sh, tok_sh),
        out_shardings=(NamedSharding(mesh, P()), param_sh, opt_sh),
        donate_argnums=(0, 1))


def make_train_step(cfg, mesh, lr=1e-3, seq_parallel=None):
    """One jitted (params, opt, tokens) -> (loss, params', opt') step over
    `mesh`. Sequence parallelism (ring attention) activates when the mesh
    has an 'sp' axis of size > 1 (or when `seq_parallel` forces it).
    """
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    use_sp = seq_parallel if seq_parallel is not None else \
        axes.get('sp', 1) > 1

    pspecs = param_specs(cfg)
    param_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, P))
    opt_sh = {'m': param_sh, 'v': param_sh,
              't': NamedSharding(mesh, P())}
    tok_spec = P('dp', 'sp') if use_sp else P('dp')
    tok_sh = NamedSharding(mesh, tok_spec)

    if use_sp:
        # ring attention runs under shard_map over the sp axis only;
        # dp/tp stay with the SPMD partitioner.
        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P(None, 'sp', None, None),) * 3,
            out_specs=P(None, 'sp', None, None),
            check_vma=False)
        def attn_fn(q, k, v):
            return ring_attention(q, k, v, 'sp')
    else:
        attn_fn = None

    def step(params, opt, inputs, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, inputs, targets,
                                                  cfg, attn_fn)
        new_params, new_opt = _adam_update(params, grads, opt, lr)
        return loss, new_params, new_opt

    return jax.jit(
        step,
        in_shardings=(param_sh, opt_sh, tok_sh, tok_sh),
        out_shardings=(NamedSharding(mesh, P()), param_sh, opt_sh),
        donate_argnums=(0, 1))


def shard_params(params, cfg, mesh):
    pspecs = param_specs(cfg)
    return jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, pspecs)
