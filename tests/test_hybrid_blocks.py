"""The hybrid blocks (RMS norm, Mamba-2 mixer with a chunked scan,
grouped-query attention, routed experts over the experts held) against
the plain reference of benchmark/chip/models/nemotron_h.py, at tiny
widths that keep the structure: 2 groups, 4 Mamba heads, 4 query heads on
2 KV heads, 16 experts top 3, pattern ``ME*E``. CPU, float32.
"""
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu.ops import hybrid_ops
from paddle_tpu.ops import pallas_kernels as pk

# by path: ``models`` is also benchmark/fluid's module, which other
# tests of this suite import by that name
_spec = importlib.util.spec_from_file_location(
    'chip_models_nemotron_h', os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        'benchmark', 'chip', 'models', 'nemotron_h.py'))
nemotron_h = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(nemotron_h)
_spec = importlib.util.spec_from_file_location(
    'chip_models_afmoe', os.path.join(
        os.path.dirname(_spec.origin), 'afmoe.py'))
afmoe = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(afmoe)

T = 37                  # not a multiple of the chunk
TRAFFIC = {'batch': 2, 'seq_len': T}


def tiny_cfg(**over):
    cfg = {
        'hybrid_override_pattern': 'ME*E', 'num_hidden_layers': 4,
        'hidden_size': 32, 'vocab_size': 64, 'layer_norm_epsilon': 1e-5,
        'mamba_num_heads': 4, 'mamba_head_dim': 8, 'n_groups': 2,
        'ssm_state_size': 16, 'conv_kernel': 4, 'chunk_size': 16,
        'num_attention_heads': 4, 'num_key_value_heads': 2, 'head_dim': 8,
        'router_num_experts': 16, 'n_routed_experts': 16,
        'experts_first': 0, 'num_experts_per_tok': 3,
        'moe_latent_size': 12, 'moe_intermediate_size': 20,
        'moe_shared_expert_intermediate_size': 24,
        'routed_scaling_factor': 2.5, 'norm_topk_prob': True,
        'initializer_range': 0.2, 'time_step_min': 0.001,
        'time_step_max': 0.1, 'time_step_floor': 1e-4,
        'published': {'num_hidden_layers': 4},
        'optimizer': {'learning_rate': 1e-2, 'beta1': 0.9, 'beta2': 0.95,
                      'epsilon': 1e-8},
    }
    cfg.update(over)
    return cfg


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    scale = max(1e-6, float(np.max(np.abs(b))))
    assert np.max(np.abs(a - b)) <= tol * scale, (
        np.max(np.abs(a - b)) / scale)


def run_branch(build_fn, x, weights, cfg, dims=nemotron_h.Dims):
    """One branch of the program on a fed [B, T, D] input with
    ``weights`` (the reference's leaves, creation order) put in its
    parameters: the branch's output, its gradient in x and in every
    trainable parameter under sum(out * g), and the extra fetches."""
    d = dims(cfg)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        xin = fluid.layers.data(name='x', shape=list(x.shape[1:]),
                                dtype='float32')
        xin.stop_gradient = False
        out = build_fn(fluid.layers, xin, d)
        extra = []
        if isinstance(out, tuple):
            out, extra = out[0], list(out[1:])
        g = fluid.layers.data(name='g', shape=list(out.shape[1:]),
                              dtype='float32')
        params = main.global_block().all_parameters()
        loss = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(out, g))
        train = [p for p in params if p.trainable]
        grads = fluid.gradients(loss, [xin] + train)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        assert [tuple(p.shape) for p in params] == \
            [tuple(np.shape(w)) for w in weights]
        for p, w in zip(params, weights):
            scope.set_var(p.name, jnp.array(w, copy=True))
        rng = np.random.RandomState(5)
        gval = rng.randn(*x.shape[:-1], int(out.shape[-1])) \
            .astype('float32')
        got = exe.run(main, feed={'x': x, 'g': gval},
                      fetch_list=[out] + grads + extra)
    n = 1 + len(grads)
    return got[0], got[1:n], gval, got[n:]


def ref_branch(fn, x, gval, p):
    """Reference output of ``fn(p, x)`` and its gradients in x and in p
    under sum(out * g)."""
    def loss(x, p):
        out = fn(p, x)
        return jnp.sum(out * gval), out
    (_, out), (dx, dp) = jax.value_and_grad(loss, argnums=(0, 1),
                                            has_aux=True)(jnp.asarray(x), p)
    return out, dx, dp


def block_params(cfg, kind, seed=3):
    ref = nemotron_h.Reference(cfg)
    pattern = cfg['hybrid_override_pattern']
    i = pattern.index(kind)
    full = ref.init(jax.random.PRNGKey(seed))
    pre = 'l%d.' % i
    names = [n for n, _, _ in ref.block_leaves(kind, pre)][1:]   # no norm
    return ref, pre, names, {n: full[n] for n in names}


def stream(seed=0, width=32):
    return np.random.RandomState(seed).randn(2, T, width).astype('float32')


# ---- each new op against the reference, forward and all gradients ----------
@pytest.mark.parametrize('group', [None, 8])
def test_rms_norm_matches_reference(group):
    cfg = tiny_cfg()
    ref = nemotron_h.Reference(cfg)
    w = np.random.RandomState(1).rand(32).astype('float32') + 0.5
    x = stream()

    def build(layers, xin, d):
        return layers.rms_norm(xin, epsilon=d.eps, begin_norm_axis=2,
                               group_size=group)
    out, grads, g, _ = run_branch(build, x, [w], cfg)
    want, dx, dp = ref_branch(
        lambda p, t: ref.rms_norm(t, p['w'], group=group), x, g,
        {'w': jnp.asarray(w)})
    close(out, want)
    close(grads[0], dx)
    close(grads[1], dp['w'])


@pytest.mark.parametrize('t_len,chunk', [(37, 16), (64, 16), (9, 16)])
def test_chunked_scan_equals_the_sequential_recurrence(t_len, chunk):
    """Forward and every gradient, at T below, at and not a multiple of
    the chunk."""
    ref = nemotron_h.Reference(tiny_cfg())
    rng = np.random.RandomState(2)
    H, P, G, N = 4, 8, 2, 16
    x = jnp.asarray(rng.randn(2, t_len, H, P), jnp.float32)
    dt = jnp.asarray(rng.rand(2, t_len, H) * 0.5 + 0.01, jnp.float32)
    a_log = jnp.asarray(np.log(np.arange(1, H + 1)), jnp.float32)
    b = jnp.asarray(rng.randn(2, t_len, G, N), jnp.float32)
    c = jnp.asarray(rng.randn(2, t_len, G, N), jnp.float32)
    g = jnp.asarray(rng.randn(2, t_len, H, P), jnp.float32)

    def chunked(x, dt, a_log, b, c):
        return jnp.sum(hybrid_ops.ssd_chunked(x, dt, a_log, b, c, chunk) * g)

    def sequential(x, dt, a_log, b, c):
        return jnp.sum(ref.scan(x, dt, -jnp.exp(a_log), b, c) * g)

    args = (x, dt, a_log, b, c)
    close(hybrid_ops.ssd_chunked(*args, chunk),
          ref.scan(x, dt, -jnp.exp(a_log), b, c), 1e-4)
    for got, want in zip(jax.grad(chunked, argnums=range(5))(*args),
                         jax.grad(sequential, argnums=range(5))(*args)):
        close(got, want, 1e-4)


def test_mamba_mixer_matches_reference():
    cfg = tiny_cfg()
    ref, pre, names, p = block_params(cfg, 'M')
    x = stream()
    out, grads, g, _ = run_branch(nemotron_h.mamba_branch, x,
                                  [p[n] for n in names], cfg)
    want, dx, dp = ref_branch(
        lambda p, t: ref.mamba(p, t, pre, nemotron_h.Float32Dots()),
        x, g, p)
    close(out, want, 1e-4)
    close(grads[0], dx, 1e-4)
    for got, n in zip(grads[1:], names):
        close(got, dp[n], 2e-4)


def test_attention_with_fewer_kv_heads_matches_reference():
    cfg = tiny_cfg()
    ref, pre, names, p = block_params(cfg, '*')
    x = stream()
    out, grads, g, _ = run_branch(nemotron_h.attention_branch, x,
                                  [p[n] for n in names], cfg)
    want, dx, dp = ref_branch(
        lambda p, t: ref.attention(p, t, pre, nemotron_h.Float32Dots()),
        x, g, p)
    close(out, want, 1e-4)
    close(grads[0], dx, 1e-4)
    for got, n in zip(grads[1:], names):
        close(got, dp[n], 1e-4)


def _routed_case(cfg, bias=None, seed=3):
    ref, pre, names, p = block_params(cfg, 'E', seed)
    names = [n for n in names if 'shared' not in n]
    if bias is not None:
        p[pre + 'e_score_correction_bias'] = jnp.asarray(bias, jnp.float32)
    x = stream()
    out, grads, g, extra = run_branch(nemotron_h.routed_branch, x,
                                      [p[n] for n in names], cfg)
    want, dx, dp = ref_branch(
        lambda p, t: ref.routed(p, t, pre, nemotron_h.Float32Dots()),
        x, g, p)
    close(out, want, 1e-4)
    close(grads[0], dx, 1e-4)
    train = [n for n in names if 'correction' not in n]
    for got, n in zip(grads[1:], train):
        close(got, dp[n], 2e-4)
    _, idx, _ = ref.routing(p, jnp.asarray(x), pre,
                            nemotron_h.Float32Dots())
    first, held = cfg.get('experts_first', 0), cfg['n_routed_experts']
    want_tokens = [int(jnp.sum(idx == first + j)) for j in range(held)]
    assert list(np.asarray(extra[0])) == want_tokens
    return want_tokens


@pytest.fixture
def route(request, monkeypatch):
    """The grouped products' route: 'ragged_dot' as the CPU takes it,
    or 'pallas' forced through the interpreter (the rule itself says
    no on this backend), the rows then summed back into their tokens
    by the Pallas kernel too (interpreted, in row blocks of a row
    tile). Returns what the configuration needs for the rules' shape
    side to hold: latent and expert widths of whole 128-lane tiles, a
    chunk of whole row tiles."""
    if request.param == 'ragged_dot':
        return {}
    plan, summed = pk.grouped_plan, pk.row_sum_plan
    monkeypatch.setattr(pk, 'grouped_plan', lambda rows, w, interpret=None:
                        plan(rows, w, True))
    monkeypatch.setattr(pk, 'row_sum_plan', lambda y, n, interpret=None:
                        summed(y, n, True))
    monkeypatch.setattr(pk, '_ROW_SUM_BLOCK_ROWS', pk._GROUPED_ROW_TILE)
    monkeypatch.setattr(hybrid_ops, '_ROW_QUANTUM', pk._GROUPED_ROW_TILE)
    return {'moe_latent_size': 128, 'moe_intermediate_size': 256}


def _last_chunk_is_partly_filled(tokens, chunk):
    """The second chunk of a skewed routing holds fewer pairs than rows:
    the rows past them belong to no expert, and the grouped products
    give them exact zeros."""
    assert 0 < sum(tokens) - chunk < chunk
    live, total = pk.live_row_tiles(tokens, chunk, hybrid_ops._ROW_QUANTUM)
    assert live <= total == 2 * chunk // hybrid_ops._ROW_QUANTUM


def _routes_taken(fn):
    from paddle_tpu.compiler.passes import moe_counts
    before = moe_counts(by=('route',))
    out = fn()
    return out, {k[0] for k, n in moe_counts(by=('route',)).items()
                 if n != before.get(k, 0)}


@pytest.mark.parametrize('route', ['ragged_dot', 'pallas'], indirect=True)
@pytest.mark.parametrize('held', [(0, 16), (4, 8), (13, 3)])
def test_routed_experts_match_reference(held, route, request):
    """All experts, and two shares: routed over all 16, computed for the
    experts held; through ``lax.ragged_dot`` and through the Pallas
    grouped matmul (interpreted)."""
    tokens, taken = _routes_taken(lambda: _routed_case(tiny_cfg(
        experts_first=held[0], n_routed_experts=held[1], **route)))
    assert sum(tokens) > 0
    assert taken == {request.node.callspec.params['route']}


@pytest.mark.parametrize('route', ['ragged_dot', 'pallas'], indirect=True)
def test_no_token_is_dropped_under_a_skewed_routing(route, monkeypatch):
    """Every token is sent to experts 5 and 6 (and one other): each sees
    all 2 T tokens, more pairs than one chunk of rows (twice the
    balanced load) holds, and the result is still the reference's, on
    either route of the grouped products."""
    if not route:
        monkeypatch.setattr(hybrid_ops, '_ROW_QUANTUM', 8)
    cfg = tiny_cfg(experts_first=4, n_routed_experts=4, **route)
    chunk = hybrid_ops.expert_chunk_rows(2 * T, 3, 4, 16)
    # a balanced routing fits one chunk ...
    assert sum(_routed_case(cfg)) <= chunk
    # ... this one overflows into a second
    bias = np.zeros(16, 'float32')
    bias[5] = bias[6] = 100.0
    tokens = _routed_case(cfg, bias=bias)
    assert tokens[1] == tokens[2] == 2 * T
    assert chunk < sum(tokens) <= 2 * chunk
    _last_chunk_is_partly_filled(tokens, chunk)


def test_shared_expert_matches_reference():
    cfg = tiny_cfg()
    ref, pre, names, p = block_params(cfg, 'E')
    names = [n for n in names if 'shared' in n]
    x = stream()
    out, grads, g, _ = run_branch(nemotron_h.shared_branch, x,
                                  [p[n] for n in names], cfg)
    want, dx, dp = ref_branch(
        lambda p, t: ref.shared(p, t, pre, nemotron_h.Float32Dots()),
        x, g, p)
    close(out, want, 1e-4)
    close(grads[0], dx, 1e-4)
    for got, n in zip(grads[1:], names):
        close(got, dp[n], 1e-4)


# ---- the shares add up to the uncut layer ----------------------------------
def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips hold 4 experts each: their routed parts (each through
    its own copy of router and latent projections, which every chip
    computes alike) plus the shared expert counted once equal the uncut
    reference layer."""
    cfg = tiny_cfg()
    ref, pre, names, p = block_params(cfg, 'E')
    x = stream()
    dots = nemotron_h.Float32Dots()
    want = ref.branch('E', p, jnp.asarray(x), pre, dots)
    routed = [n for n in names if 'shared' not in n]
    total = 0.0
    for first in range(0, 16, 4):
        share = dict(p)
        share[pre + 'w1'] = p[pre + 'w1'][first:first + 4]
        share[pre + 'w2'] = p[pre + 'w2'][first:first + 4]
        part, _, _, _ = run_branch(
            nemotron_h.routed_branch, x, [share[n] for n in routed],
            tiny_cfg(experts_first=first, n_routed_experts=4))
        total = total + part
    once, _, _, _ = run_branch(
        nemotron_h.shared_branch, x,
        [p[n] for n in names if 'shared' in n], cfg)
    close(total + once, want, 1e-4)


def test_mamba_head_shares_add_up_to_the_uncut_layer():
    """Two chips hold one group (2 heads, its B and C, its slice of the
    gated norm) each; their out-projections' partial sums add up."""
    cfg = tiny_cfg()
    ref, pre, names, p = block_params(cfg, 'M')
    d = nemotron_h.Dims(cfg)
    x = stream()
    want = ref.mamba(p, jnp.asarray(x), pre, nemotron_h.Float32Dots())
    hp, gn = d.inner // d.G, d.N                 # a group's x and B widths
    heads = d.H // d.G
    total = 0.0
    for g in range(d.G):
        xs = np.arange(g * hp, (g + 1) * hp)
        bs = d.inner + np.arange(g * gn, (g + 1) * gn)
        cs = d.inner + d.G * d.N + np.arange(g * gn, (g + 1) * gn)
        conv = np.concatenate([xs, bs, cs])
        hs = np.arange(g * heads, (g + 1) * heads)
        cols = np.concatenate([xs, d.inner + conv, d.inner + d.conv + hs])
        share = [p[pre + 'in_proj'][:, cols], p[pre + 'conv.w'][conv],
                 p[pre + 'conv.b'][conv], p[pre + 'A_log'][hs],
                 p[pre + 'D'][hs], p[pre + 'dt_bias'][hs],
                 p[pre + 'gate_norm'][xs], p[pre + 'out_proj'][xs]]
        part, _, _, _ = run_branch(
            nemotron_h.mamba_branch, x, share,
            tiny_cfg(mamba_num_heads=heads, n_groups=1))
        total = total + part
    close(total, want, 1e-4)


def test_attention_head_shares_add_up_to_the_uncut_layer():
    """Two chips hold one KV head and its two query heads each."""
    cfg = tiny_cfg()
    ref, pre, names, p = block_params(cfg, '*')
    d = nemotron_h.Dims(cfg)
    x = stream()
    want = ref.attention(p, jnp.asarray(x), pre, nemotron_h.Float32Dots())
    per = d.Hq // d.Hkv
    total = 0.0
    for kv in range(d.Hkv):
        qs = np.arange(kv * per * d.dh, (kv + 1) * per * d.dh)
        ks = np.arange(kv * d.dh, (kv + 1) * d.dh)
        share = [p[pre + 'q'][:, qs], p[pre + 'k'][:, ks],
                 p[pre + 'v'][:, ks], p[pre + 'o'][qs]]
        part, _, _, _ = run_branch(
            nemotron_h.attention_branch, x, share,
            tiny_cfg(num_attention_heads=per, num_key_value_heads=1))
        total = total + part
    close(total, want, 1e-4)


# ---- the whole tiny model --------------------------------------------------
def _three_adam_steps(model, cfg, same_step):
    """The tiny model through the Executor against ``model.Reference``:
    each loss, every leaf of the first gradient as the optimizer got
    it, and the parameters' change after three Adam steps, which
    ``same_step(got, want)`` compares leaf by leaf; a buffer stays as
    it was."""
    ref = model.Reference(cfg)
    built = model.build(cfg, TRAFFIC)
    leaves = ref.leaves()
    key = jax.random.PRNGKey(7)
    init = ref.init(key)
    batches = [{k: np.asarray(v) for k, v in model.draw_batch(
        cfg, TRAFFIC, jax.random.fold_in(key, 100 + i)).items()}
        for i in range(3)]
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    names = built['param_names']
    assert len(names) == len(leaves)
    with fluid.scope_guard(scope):
        exe.run(built['startup'])
        for n, (leaf, shape, _) in zip(names, leaves):
            assert tuple(np.shape(scope.raw(n))) == tuple(shape), leaf
            scope.set_var(n, jnp.array(init[leaf], copy=True))
        losses, grads = [], None
        for i, b in enumerate(batches):
            out = exe.run(built['main'], feed=b, fetch_list=[built['loss']])
            losses.append(float(np.ravel(out[0])[0]))
            if i == 0:
                grads = {leaf: np.asarray(scope.raw(built['grad_state'](n)))
                         * built['grad_scale']
                         for n, (leaf, _, t) in zip(names, leaves) if t}
        final = {leaf: np.asarray(scope.raw(n))
                 for n, (leaf, _, _) in zip(names, leaves)}

    params, state = dict(init), ref.new_opt_state(init)
    for i, b in enumerate(batches):
        loss, g = jax.value_and_grad(lambda p: ref.loss(p, b))(params)
        assert abs(losses[i] - float(loss)) <= 2e-5 * abs(float(loss))
        if i == 0:
            assert set(grads) == set(ref.trainable())
            for n in ref.trainable():
                close(grads[n], g[n], 5e-4)
        params, state = ref.update(params, g, state, jnp.float32(i + 1))
    for n, _, t in leaves:
        if t:
            same_step(final[n] - np.asarray(init[n]),
                      np.asarray(params[n] - init[n]))
        else:
            np.testing.assert_array_equal(final[n], np.asarray(init[n]))


@pytest.mark.parametrize('held', [(0, 16), (8, 8)])
def test_tiny_model_loss_gradients_and_three_adam_steps(held):
    _three_adam_steps(
        nemotron_h, tiny_cfg(experts_first=held[0], n_routed_experts=held[1]),
        lambda got, want: close(got, want, 5e-3))


# ---- shapes, counters, AMP -------------------------------------------------
def test_shape_inference_covers_the_new_ops():
    from paddle_tpu.analysis import infer
    assert {'rms_norm', 'causal_conv1d', 'ssd_scan', 'router_scores',
            'routed_experts', 'flash_attention'} \
        <= set(infer.registered_shape_ops())
    built = nemotron_h.build(tiny_cfg(), TRAFFIC)
    env, diags, _ = infer.infer_program(built['main'])
    assert [d for d in diags if d.severity == 'error'] == []
    ops = {op.type: op for op in built['main'].global_block().ops}
    tokens = env[ops['routed_experts'].outputs['TokensPerExpert'][0]]
    assert tokens.shape == (16,) and tokens.dtype == 'int32'
    scores = env[ops['router_scores'].outputs['Out'][0]]
    assert scores.shape[-1] == 16 and scores.dtype == 'float32'

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q = fluid.layers.data(name='q', shape=[T, 32], dtype='float32')
        kv = fluid.layers.data(name='kv', shape=[T, 32], dtype='float32')
        fluid.layers.flash_attention(q, kv, kv, num_heads=4,
                                     num_kv_heads=2, head_dim=8)
    _, diags, _ = infer.infer_program(main)
    assert [d.code for d in diags if d.severity == 'error'] \
        == ['rank-mismatch'] * 2


def test_lowerings_are_counted():
    from paddle_tpu.compiler.passes import (flash_counts, moe_counts,
                                            ssd_counts)
    built = nemotron_h.build(tiny_cfg(experts_first=8, n_routed_experts=8),
                             TRAFFIC)
    batch = {k: np.asarray(v) for k, v in nemotron_h.draw_batch(
        tiny_cfg(), TRAFFIC, jax.random.PRNGKey(0)).items()}
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(built['startup'])
        before = moe_counts(), ssd_counts(), flash_counts(by=('kv_heads',))
        exe.lowered(built['main'], feed=batch, fetch_list=[built['loss']])
        after = moe_counts(), ssd_counts(), flash_counts(by=('kv_heads',))
    moved = [{k: n - was.get(k, 0) for k, n in now.items()
              if n != was.get(k, 0)} for was, now in zip(before, after)]
    assert moved == [{('16', '8', '3', 'ragged_dot'): 2},
                     {('xla', '16'): 1}, {('2',): 1}]


@pytest.mark.parametrize('backend,amp_on,widths,route,row_sum', [
    ('tpu', True, (128, 256), 'pallas', 'pallas'),
    ('tpu', False, (128, 256), 'ragged_dot', 'pallas'),  # float32 operands
    ('tpu', True, (12, 20), 'ragged_dot', 'xla'),   # not whole lane tiles
    ('cpu', True, (128, 256), 'ragged_dot', 'xla'),
    ('tpu', True, (128, 256), 'pallas', 'xla'),     # not whole row blocks
], ids=['chip-amp', 'chip-f32', 'chip-odd-widths', 'cpu-amp',
        'chip-odd-chunk'])
def test_moe_counter_names_the_route_on_each_side_of_the_rule(
        backend, amp_on, widths, route, row_sum, amp, request, monkeypatch):
    """``moe_lowerings_total{route=, row_sum=}`` says which grouped
    product a lowering took: 'pallas' on a TPU backend with bf16
    operands (AMP) and widths of whole 128-lane tiles, 'ragged_dot' on
    every other side of that rule; and how the rows were summed back
    into their tokens: 'pallas' on a TPU backend where the width is
    whole 128-lane tiles and the chunk whole row blocks (its rows are
    float32 with AMP or without), 'xla' on every other side.
    moe_row_sum_counts() reads the first. Lowered, not run: the backend
    is only said to be a TPU."""
    from paddle_tpu.compiler.passes import moe_counts, moe_row_sum_counts
    amp.set_amp(amp_on)
    monkeypatch.setattr(pk, '_on_tpu', lambda: backend == 'tpu')
    monkeypatch.setattr(hybrid_ops, '_ROW_QUANTUM', pk._GROUPED_ROW_TILE)
    if request.node.callspec.id == 'chip-odd-chunk':
        monkeypatch.setattr(pk, '_ROW_SUM_BLOCK_ROWS', 512)
    # what the rule engages is lowered for this backend's interpreter
    kernels, ragged_dot, calls = pk._grouped, jax.lax.ragged_dot, []
    monkeypatch.setattr(pk, '_grouped', lambda rows, w, visits, tm, _:
                        calls.append('pallas')
                        or kernels(rows, w, visits, tm, True))
    monkeypatch.setattr(jax.lax, 'ragged_dot', lambda *a, **kw:
                        calls.append('ragged_dot') or ragged_dot(*a, **kw))
    summed, row_sum_call = [], pk._row_sum_call
    monkeypatch.setattr(pk, '_row_sum_call', lambda *a, **kw:
                        summed.append('pallas')
                        or row_sum_call(*a, **{**kw, 'interpret': True}))
    cfg = tiny_cfg(experts_first=8, n_routed_experts=8,
                   hybrid_override_pattern='E', num_hidden_layers=1,
                   moe_latent_size=widths[0],
                   moe_intermediate_size=widths[1])
    cfg['published'] = {'num_hidden_layers': 1}
    built = nemotron_h.build(cfg, TRAFFIC)
    batch = {k: np.asarray(v) for k, v in nemotron_h.draw_batch(
        cfg, TRAFFIC, jax.random.PRNGKey(0)).items()}
    exe = fluid.Executor(fluid.CPUPlace())
    by = ('experts', 'held', 'top_k', 'route', 'row_sum')
    with fluid.scope_guard(fluid.Scope()):
        exe.run(built['startup'])
        before = moe_counts(), moe_counts(by), moe_row_sum_counts()
        exe.lowered(built['main'], feed=batch, fetch_list=[built['loss']])
        after = moe_counts(), moe_counts(by), moe_row_sum_counts()
    moved = [{k: n - was.get(k, 0) for k, n in now.items()
              if n != was.get(k, 0)} for was, now in zip(before, after)]
    assert moved == [{('16', '8', '3', route): 1},
                     {('16', '8', '3', route, row_sum): 1},
                     {('pallas',): 1} if row_sum == 'pallas' else {}]
    assert calls and set(calls) == {route}
    assert bool(summed) == (row_sum == 'pallas')


def test_amp_keeps_scores_and_the_carried_state_float32(amp):
    """Under forced AMP the router's scores and the state carried
    between chunks stay float32, and the products take bf16 operands."""
    amp.set_amp(True)
    built = nemotron_h.build(tiny_cfg(), TRAFFIC)
    batch = {k: np.asarray(v) for k, v in nemotron_h.draw_batch(
        tiny_cfg(), TRAFFIC, jax.random.PRNGKey(0)).items()}
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(built['startup'])
        text = exe.lowered(built['main'], feed=batch,
                           fetch_list=[built['loss']]).as_text()
    import re
    # the scan's carry: one while a mixer, forward, and its transpose
    # [.., P, N] = [.., 8, 16]: the state a scan's loop carries
    states = re.findall(r'tensor<[0-9x]*x8x16x(\w+)>', ' '.join(
        re.findall(r'stablehlo\.while.*', text)))
    assert states and set(states) == {'f32'}, states
    scores = re.findall(r'chlo\.top_k.*: tensor<74x16x(\w+)>', text)
    assert scores and set(scores) == {'f32'}
    dots = re.findall(
        r'stablehlo\.dot_general.*: \(tensor<[0-9x]*x(\w+)>, '
        r'tensor<[0-9x]*x(\w+)>\) -> tensor<[0-9x]*x(\w+)>', text)
    assert ('bf16', 'bf16', 'f32') in set(dots)


# ============================================================================
# A window / full attention stack with rotary positions, gated attention
# and a mixture of gated experts (benchmark/chip/models/afmoe.py), at tiny
# widths that keep the structure: layers S S F S after... the first dense,
# 16 query heads on 2 KV heads, 16 experts top 3, a window of 16 of 37
# positions.
# ============================================================================
def af_cfg(**over):
    cfg = {
        'layer_types': [afmoe.SLIDING, afmoe.SLIDING, afmoe.FULL,
                        afmoe.SLIDING],
        'num_hidden_layers': 4, 'num_dense_layers': 1,
        'hidden_size': 32, 'vocab_size': 64, 'rms_norm_eps': 1e-5,
        'num_attention_heads': 16, 'num_key_value_heads': 2, 'head_dim': 8,
        'sliding_window': 16, 'rope_theta': 100.0,
        'intermediate_size': 40, 'moe_intermediate_size': 20,
        'num_shared_experts': 1, 'router_num_experts': 16,
        'num_experts': 16, 'experts_first': 0, 'num_experts_per_tok': 3,
        'route_scale': 2.826, 'route_norm': True, 'score_func': 'sigmoid',
        'hidden_act': 'silu', 'mup_enabled': True,
        'tie_word_embeddings': False, 'initializer_range': 0.2,
        'embedding_std': 0.2,
        'optimizer': {'learning_rate': 1e-2, 'beta1': 0.9, 'beta2': 0.95,
                      'epsilon': 1e-8},
    }
    cfg.update(over)
    return cfg


def af_block(cfg, layer, seed=3):
    """(reference, leaf prefix, the layer's leaves by name) at seeded
    weights, the norms off 1 so that they show."""
    ref = afmoe.Reference(cfg)
    full = ref.init(jax.random.PRNGKey(seed))
    pre = 'l%d.' % layer
    p = {n: full[n] for n, _, _ in ref.block_leaves(layer, pre)}
    for i, n in enumerate(sorted(p)):
        if 'norm' in n:
            p[n] = 1.0 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(100 + i), p[n].shape)
    return ref, pre, p


_ATT = ('q', 'k', 'v', 'gate', 'q_norm', 'k_norm', 'o')
_ROUTED = ('router', 'e_gate', 'e_up', 'e_down', 'expert_bias')
_SHARED = ('s_gate', 's_up', 's_down')


def _af_check(build_fn, ref_fn, cfg, p, names, tol=1e-4):
    x = stream(width=cfg['hidden_size'])
    out, grads, g, extra = run_branch(build_fn, x, [p[n] for n in names],
                                      cfg, afmoe.Dims)
    want, dx, dp = ref_branch(ref_fn, x, g, p)
    close(out, want, tol)
    close(grads[0], dx, tol)
    train = [n for n in names if 'expert_bias' not in n]
    for got, n in zip(grads[1:], train):
        close(got, dp[n], 2 * tol)
    return extra


# ---- rotary positions -------------------------------------------------------
def _rotary_complex(x, head_dim, base):
    """The complex-number form: the pair (x_i, x_{i + dh/2}) of a head is
    the number x_i + i x_{i + dh/2}, turned by exp(i t base^(-2i/dh))."""
    B, T_, D = x.shape
    half = head_dim // 2
    xh = x.reshape(B, T_, D // head_dim, 2, half)
    z = xh[..., 0, :] + 1j * xh[..., 1, :]
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / head_dim)
    turn = jnp.exp(1j * jnp.arange(T_, dtype=jnp.float32)[:, None] * inv)
    z = z * turn[None, :, None, :]
    return jnp.stack([z.real, z.imag], axis=-2).reshape(B, T_, D)


def test_rotary_matches_the_complex_form():
    """Forward and the gradient in x; position 0 is left as it is."""
    x = stream()
    out, grads, g, _ = run_branch(
        lambda layers, t, d: layers.rotary_embedding(t, 8, base=100.0),
        x, [], af_cfg(), afmoe.Dims)
    want, dx, _ = ref_branch(lambda p, t: _rotary_complex(t, 8, 100.0), x,
                             g, {})
    close(out, want, 2e-5)
    close(grads[0], dx, 2e-5)
    np.testing.assert_allclose(out[:, 0], x[:, 0], rtol=1e-6)
    # the reference's rotate_half form says the same
    ref = afmoe.Reference(af_cfg(head_dim=8))
    close(ref.rotary(jnp.asarray(x).reshape(2, T, 4, 8)).reshape(x.shape),
          want, 2e-5)


def test_rotary_angles_stay_float32_under_amp(amp):
    """Under forced AMP a bf16 stream is turned by float32 angles: cos
    and sin are taken of float32 and the output returns to bf16."""
    from paddle_tpu.compiler.passes import rotary_counts
    amp.set_amp(True)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name='x', shape=[T, 32], dtype='float32')
        y = fluid.layers.fc(x, 32, num_flatten_dims=2, bias_attr=False)
        out = fluid.layers.rotary_embedding(y, 8)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        before = rotary_counts()
        text = exe.lowered(main, feed={'x': stream()},
                           fetch_list=[out]).as_text()
        after = rotary_counts()
    import re
    trig = re.findall(r'stablehlo\.(?:cosine|sine) .*tensor<[0-9x]*x(\w+)>',
                      text)
    assert trig and set(trig) == {'f32'}, trig
    assert after.get(('8', 'bfloat16'), 0) \
        == before.get(('8', 'bfloat16'), 0) + 1
    assert re.search(r'-> \(?tensor<2x37x32xbf16>', text)


# ---- attention: window or full, rotary or none, gated ----------------------
@pytest.mark.parametrize('layer', [1, 2], ids=['window-rotary', 'full'])
def test_gated_attention_matches_reference(layer):
    cfg = af_cfg()
    ref, pre, p = af_block(cfg, layer)
    kind = cfg['layer_types'][layer]
    _af_check(lambda layers, t, d: afmoe.attention_branch(layers, t, d, kind),
              lambda p, t: ref.attention(p, t, pre, afmoe.Float32Dots(),
                                         kind),
              cfg, p, [pre + n for n in _ATT])
    # the window shows: the same weights without it give another output
    other = afmoe.FULL if kind == afmoe.SLIDING else afmoe.SLIDING
    x = jnp.asarray(stream())
    dots = afmoe.Float32Dots()
    gap = jnp.max(jnp.abs(ref.attention(p, x, pre, dots, kind)
                          - ref.attention(p, x, pre, dots, other)))
    assert float(gap) > 1e-3


# ---- gated experts -----------------------------------------------------------
def _af_routed_case(cfg, bias=None):
    ref, pre, p = af_block(cfg, 1)
    if bias is not None:
        p[pre + 'expert_bias'] = jnp.asarray(bias, jnp.float32)
    first, held = cfg['experts_first'], cfg['num_experts']
    for n in ('e_gate', 'e_up', 'e_down'):
        p[pre + n] = p[pre + n][:held] if p[pre + n].shape[0] != held \
            else p[pre + n]
    extra = _af_check(
        afmoe.routed_branch,
        lambda p, t: ref.routed(p, t, pre, afmoe.Float32Dots()),
        cfg, p, [pre + n for n in _ROUTED])
    _, idx, _ = ref.routing(
        p, jnp.asarray(stream(width=cfg['hidden_size'])), pre,
        afmoe.Float32Dots())
    want = [int(jnp.sum(idx == first + j)) for j in range(held)]
    assert list(np.asarray(extra[0])) == want
    return want


def _af_route_widths(route):
    return {'hidden_size': 128, 'moe_intermediate_size': 256} if route \
        else {}


@pytest.mark.parametrize('route', ['ragged_dot', 'pallas'], indirect=True)
@pytest.mark.parametrize('held', [(0, 16), (4, 8), (13, 3)])
def test_gated_experts_match_reference(held, route, request):
    """silu(x W_gate) * (x W_up) through W_down, all experts and two
    shares, on both grouped routes (the Pallas one interpreted); the
    counter names route and activation."""
    from paddle_tpu.compiler.passes import moe_counts
    by = ('route', 'act')
    before = moe_counts(by)
    tokens = _af_routed_case(af_cfg(
        experts_first=held[0], num_experts=held[1],
        **_af_route_widths(route)))
    assert sum(tokens) > 0
    moved = {k for k, n in moe_counts(by).items() if n != before.get(k, 0)}
    assert moved == {(request.node.callspec.params['route'], 'swiglu')}


@pytest.mark.parametrize('route', ['ragged_dot', 'pallas'], indirect=True)
def test_gated_experts_drop_no_token_when_two_take_them_all(
        route, monkeypatch):
    """Two held experts take every token: more pairs than a chunk's rows
    (twice the balanced load), and the result is still the reference's
    on either route."""
    if not route:
        monkeypatch.setattr(hybrid_ops, '_ROW_QUANTUM', 8)
    cfg = af_cfg(experts_first=4, num_experts=4, **_af_route_widths(route))
    chunk = hybrid_ops.expert_chunk_rows(2 * T, 3, 4, 16)
    assert sum(_af_routed_case(cfg)) <= chunk
    bias = np.zeros(16, 'float32')
    bias[5] = bias[6] = 100.0
    tokens = _af_routed_case(cfg, bias=bias)
    assert tokens[1] == tokens[2] == 2 * T
    assert chunk < sum(tokens) <= 2 * chunk
    _last_chunk_is_partly_filled(tokens, chunk)


def _move_rows(p, x, transpose=False):
    """The placement by 0/1 matrices the op first ran, kept here as what
    the linear one is held to: ``p @ x`` (``p.T @ x``) for a 0/1 matrix
    with at most one 1 a row."""
    spec = 'rn,rl->nl' if transpose else 'rn,nl->rl'
    return jnp.einsum(spec, p.astype(x.dtype), x,
                      precision=jax.lax.Precision.HIGHEST)


@pytest.fixture
def row_sum(request, monkeypatch):
    """The route that sums a chunk's rows back into their tokens: 'xla'
    (the scatter-add, as the CPU takes it) or 'pallas' forced through
    the interpreter, which fills what the kernel has not written with
    NaN, in row blocks of 16 rows (the rule itself says no on this
    backend). Returns the route and the list of the kernel's calls."""
    calls = []
    if request.param == 'pallas':
        from jax.experimental.pallas import tpu as pltpu
        plan, kernel = pk.row_sum_plan, pk._row_sum_call
        nan_filled = pltpu.InterpretParams(uninitialized_memory='nan')
        monkeypatch.setattr(pk, '_ROW_SUM_BLOCK_ROWS', 16)
        monkeypatch.setattr(pk, 'row_sum_plan', lambda y, n, interpret=None:
                            plan(y, n, nan_filled))
        monkeypatch.setattr(pk, '_row_sum_call', lambda *a, **kw:
                            calls.append(kw) or kernel(*a, **kw))
    return request.param, calls


_PLACED = {'first': (0, 0.15), 'overflow': (1, 0.33),
           'no-live-row': (1, 0.15), 'every-row-live': (0, 0.33)}


def _placement(case, width=128, seed=0):
    """Four held experts over 74 tokens in chunks of 64 rows: chunk ``c``
    of a routing that chooses a pair with probability ``p``; the pairs'
    places, rows, weights and tokens u [74, width]."""
    c, p = _PLACED[case]
    rng = np.random.RandomState(seed)
    n, held, chunk = 74, 4, 64
    chosen = jnp.asarray(rng.rand(held, n) < p)
    counts = jnp.sum(chosen, axis=1, dtype=jnp.int32)
    place = (jnp.cumsum(counts) - counts)[:, None] \
        + jnp.cumsum(chosen, axis=1, dtype=jnp.int32) - 1
    place = jnp.where(chosen, place, -1)
    u = jnp.asarray(rng.randn(n, width).astype('float32'))
    weight = jnp.where(chosen, jnp.asarray(
        rng.rand(held, n).astype('float32')), 0.0)
    rows = c * chunk + jnp.arange(chunk, dtype=jnp.int32)
    order = hybrid_ops.pairs_in_row_order(place, chunk)
    live = int(np.clip(int(jnp.sum(counts)) - c * chunk, 0, chunk))
    return dict(u=u, weight=weight, order=order, counts=counts, rows=rows,
                place=place, live=live, chunk=chunk)


@pytest.mark.parametrize('row_sum', ['xla', 'pallas'], indirect=True)
@pytest.mark.parametrize('c', [0, 1])
def test_rows_are_placed_as_the_pick_matrix_places_them(c, row_sum):
    """_place_rows against _move_rows on the same operands: the rows
    picked, their weights, and what summing rows back gives, in the
    first chunk and in one that a skewed routing overflows into, on
    either route of the sum; a row past the routed pairs is a zero row
    with weight 0, and tokens several held experts name get all their
    rows."""
    route, calls = row_sum
    k = _placement('overflow' if c else 'first')
    place, rows, u, weight = k['place'], k['rows'], k['u'], k['weight']
    total = int(jnp.sum(k['counts']))
    assert c * k['chunk'] < total and (c or total <= k['chunk'])
    xs, row_w, back = hybrid_ops._place_rows(
        u, weight, k['order'], k['counts'], rows)
    # pick[r, n]: token n's pair lies in row r
    pick = jnp.any(place[:, None, :] == rows[None, :, None], axis=0)
    assert int(jnp.max(jnp.sum(pick, axis=0))) > 1     # shared tokens
    np.testing.assert_array_equal(np.asarray(xs),
                                  np.asarray(_move_rows(pick, u)))
    want_w = jnp.sum(jnp.where(
        place[:, None, :] == rows[None, :, None], weight[:, None, :], 0.0),
        axis=(0, 2))
    np.testing.assert_array_equal(np.asarray(row_w), np.asarray(want_w))
    y = jnp.asarray(np.random.RandomState(1).randn(
        k['chunk'], u.shape[1]).astype('float32'))
    np.testing.assert_allclose(
        np.asarray(back(y)), np.asarray(_move_rows(pick, y, transpose=True)),
        rtol=1e-6, atol=1e-6)
    dead = np.asarray(rows) >= total
    assert dead.any() and not np.asarray(xs)[dead].any() \
        and not np.asarray(row_w)[dead].any()
    assert len(calls) == (route == 'pallas')


def _place_and_sum(k, u, y, xla=False):
    """(rows picked, rows summed back) of _place_rows on ``u`` and ``y``;
    with ``xla`` the XLA forms the CPU route runs, written out."""
    xs, _, back = hybrid_ops._place_rows(u, k['weight'], k['order'],
                                         k['counts'], k['rows'])
    if not xla:
        return xs, back(y)
    n = u.shape[0]
    ids = k['order'][k['rows'][0]:k['rows'][0] + k['chunk']]
    tok = jnp.where(k['rows'] < jnp.sum(k['counts']), ids % n, n)
    return (jnp.take(u, tok, axis=0, mode='fill', fill_value=0),
            jnp.zeros_like(u).at[tok].add(y, mode='drop'))


@pytest.mark.parametrize('row_sum', ['xla', 'pallas'], indirect=True)
@pytest.mark.parametrize('case', sorted(_PLACED))
def test_rows_past_the_live_ones_are_ignored_both_ways(case, row_sum):
    """The rows past a chunk's live ones hold NaN, in the rows summed
    back and in the cotangent of the rows picked: the sum, the gradient
    of the sum in its rows and the gradient of the pick in the tokens
    are what they are on clean rows, with exact zeros past the live
    rows; with no live row (a chunk a balanced routing leaves empty) and
    with every row live (a skewed one fills it) alike."""
    route, calls = row_sum
    k = _placement(case)
    live, chunk, u = k['live'], k['chunk'], k['u']
    assert live == {'no-live-row': 0, 'every-row-live': chunk}.get(
        case, live) and (case in ('no-live-row', 'every-row-live')
                         or 0 < live < chunk)
    rng = np.random.RandomState(2)
    y = rng.randn(chunk, u.shape[1]).astype('float32')
    g_out = jnp.asarray(rng.randn(*u.shape).astype('float32'))
    g_rows = rng.randn(chunk, u.shape[1]).astype('float32')
    clean = [jnp.asarray(t) for t in (y, g_rows)]
    y[live:], g_rows[live:] = np.nan, np.nan

    def run(y, g_rows):
        (xs, out), vjp = jax.vjp(lambda u, y: _place_and_sum(k, u, y), u, y)
        d_u, _ = vjp((g_rows, jnp.zeros_like(out)))
        _, d_y = vjp((jnp.zeros_like(xs), g_out))
        return [np.asarray(t) for t in (out, d_y, d_u)]
    got, want = run(jnp.asarray(y), jnp.asarray(g_rows)), run(*clean)
    for name, a, b in zip(('sum', 'd_rows', 'd_tokens'), got, want):
        assert np.isfinite(a).all(), name
        np.testing.assert_array_equal(a, b, err_msg=name)
    assert not got[1][live:].any()
    if live == 0:
        assert not got[0].any() and not got[2].any()
    assert bool(calls) == (route == 'pallas')


@pytest.mark.parametrize('case', sorted(_PLACED))
def test_the_kernels_gradients_are_the_xla_forms_transposes(
        case, monkeypatch):
    """row_pick and row_sum (the Pallas route, interpreted) against
    jax's own transposes of the XLA forms the CPU route runs (a gather
    with zeros past the live rows, a scatter-add that drops them): the
    same values forward, the same gradients in the tokens and in the
    rows."""
    from jax.experimental.pallas import tpu as pltpu
    plan = pk.row_sum_plan
    monkeypatch.setattr(pk, '_ROW_SUM_BLOCK_ROWS', 16)
    monkeypatch.setattr(pk, 'row_sum_plan', lambda y, n, interpret=None:
                        plan(y, n, pltpu.InterpretParams(
                            uninitialized_memory='nan')))
    k = _placement(case)
    rng = np.random.RandomState(3)
    y = jnp.asarray(rng.randn(k['chunk'], 128).astype('float32'))
    cts = (jnp.asarray(rng.randn(k['chunk'], 128).astype('float32')),
           jnp.asarray(rng.randn(74, 128).astype('float32')))
    got = jax.vjp(lambda u, y: _place_and_sum(k, u, y), k['u'], y)
    want = jax.vjp(lambda u, y: _place_and_sum(k, u, y, xla=True), k['u'], y)
    for a, b in zip(got[0] + got[1](cts), want[0] + want[1](cts)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


# ---- the shares add up to the uncut layer -----------------------------------
def test_afmoe_shares_add_up_to_the_uncut_layer():
    """Eight chips share a layer: each holds 2 of the 16 query heads on 1
    of the 2 KV heads (a KV head lies on four of them) with its slices
    of W_q, W_g and W_o, and 2 of the 16 experts. Their attention parts
    add up to the uncut attention (before the norm that follows the
    exchange); their routed parts, with the shared expert counted once,
    to the uncut MLP."""
    cfg = af_cfg()
    ref, pre, p = af_block(cfg, 1)
    d = afmoe.Dims(cfg)
    x = stream()
    dots = afmoe.Float32Dots()
    kind = cfg['layer_types'][1]
    want = ref.attention(p, jnp.asarray(x), pre, dots, kind)
    total = 0.0
    for share in range(8):
        qs = np.arange(share * 2 * d.dh, (share + 1) * 2 * d.dh)
        kv = share // 4
        ks = np.arange(kv * d.dh, (kv + 1) * d.dh)
        w = {'q': p[pre + 'q'][:, qs], 'k': p[pre + 'k'][:, ks],
             'v': p[pre + 'v'][:, ks], 'gate': p[pre + 'gate'][:, qs],
             'q_norm': p[pre + 'q_norm'], 'k_norm': p[pre + 'k_norm'],
             'o': p[pre + 'o'][qs]}
        part, _, _, _ = run_branch(
            lambda layers, t, dd: afmoe.attention_branch(layers, t, dd, kind),
            x, [w[n] for n in _ATT],
            af_cfg(num_attention_heads=2, num_key_value_heads=1),
            afmoe.Dims)
        total = total + part
    close(total, want, 1e-4)

    want = ref.mlp(1, p, jnp.asarray(x), pre, dots)
    total = 0.0
    for first in range(0, 16, 2):
        w = dict(p)
        for n in ('e_gate', 'e_up', 'e_down'):
            w[pre + n] = p[pre + n][first:first + 2]
        part, _, _, _ = run_branch(
            afmoe.routed_branch, x, [w[pre + n] for n in _ROUTED],
            af_cfg(experts_first=first, num_experts=2), afmoe.Dims)
        total = total + part
    once, _, _, _ = run_branch(
        afmoe.shared_branch, x, [p[pre + n] for n in _SHARED], cfg,
        afmoe.Dims)
    close(total + once, want, 1e-4)


# ---- the whole tiny model ----------------------------------------------------
@pytest.mark.parametrize('held', [(0, 8), (8, 8)])
def test_afmoe_tiny_model_loss_gradients_and_three_adam_steps(held):
    def same_step(got, want):
        # by the leaf's norm: Adam divides by sqrt(v), so where an
        # element's gradient is next to nothing, round-off in it moves
        # that element's step by a step
        assert np.linalg.norm(got - want) <= 1e-2 * np.linalg.norm(want)
    _three_adam_steps(
        afmoe, af_cfg(experts_first=held[0], num_experts=held[1],
                      num_attention_heads=4), same_step)


# ---- shapes and counters -------------------------------------------------------
def test_shape_inference_covers_the_window_stack():
    from paddle_tpu.analysis import infer
    assert 'rotary_embedding' in set(infer.registered_shape_ops())
    built = afmoe.build(af_cfg(), TRAFFIC)
    env, diags, _ = infer.infer_program(built['main'])
    assert [d for d in diags if d.severity == 'error'] == []
    block = built['main'].global_block()
    rot = [op for op in block.ops if op.type == 'rotary_embedding']
    assert len(rot) == 2 * 3                      # q and k, window layers
    assert env[rot[0].outputs['Out'][0]].shape[-1] == 16 * 8
    gated = [op for op in block.ops if op.type == 'routed_experts']
    assert all(op.attrs['act'] == 'swiglu' and op.inputs.get('W3')
               for op in gated)

    # a gated expert's up projection must be shaped as its gate
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name='x', shape=[T, 32], dtype='float32')
        scores = fluid.layers.router_scores(x, 16)
        fluid.layers.routed_experts(x, scores, 20, 16, 3, act='swiglu')
        op = main.global_block().ops[-1]
        main.global_block().var(op.inputs['W3'][0]).shape = (16, 32, 24)
        with pytest.raises(ValueError):
            fluid.layers.routed_experts(x, scores, 20, 16, 3, act='gelu')
        with pytest.raises(ValueError):
            fluid.layers.rotary_embedding(x, 6)     # 6 does not divide 32
    _, diags, _ = infer.infer_program(main)
    assert [d.code for d in diags if d.severity == 'error'] \
        == ['rank-mismatch']


def test_window_stack_lowerings_are_counted():
    """One trace of the tiny stack: four attention ops, three of them
    under the window (the route is xla here; window_flash_counts() is
    what took the kernels), three gated expert layers, six rotary
    ops."""
    from paddle_tpu.compiler.passes import (flash_counts, moe_counts,
                                            rotary_counts,
                                            window_flash_counts)
    cfg = af_cfg(experts_first=8, num_experts=8)
    built = afmoe.build(cfg, TRAFFIC)
    batch = {k: np.asarray(v) for k, v in afmoe.draw_batch(
        cfg, TRAFFIC, jax.random.PRNGKey(0)).items()}
    exe = fluid.Executor(fluid.CPUPlace())

    def counts():
        return (flash_counts(by=('route', 'window')),
                moe_counts(by=('held', 'act')),
                rotary_counts(), window_flash_counts())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(built['startup'])
        before = counts()
        exe.lowered(built['main'], feed=batch, fetch_list=[built['loss']])
        after = counts()
    moved = [{k: n - was.get(k, 0) for k, n in now.items()
              if n != was.get(k, 0)} for was, now in zip(before, after)]
    assert moved == [{('xla', '16'): 3, ('xla', '0'): 1},
                     {('8', 'swiglu'): 3},
                     {('8', 'float32'): 6}, {}]
