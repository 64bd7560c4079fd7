"""The afmoe cell at a size a test run can hold, through the harness (a
tiny root of its own, ``tiny_afmoe/``): the Fluid program agrees with
the plain reference; the fp8 control, a step on half of the batch and a
program whose window layers ignore their window come out as not
correct; the model module's counts of parameters and required work
match hand counts at the published sizes; the new per-layer readers
read a hand-made trace.
"""
import json
import os
import time

import numpy as np
import pytest

from conftest import CHIP

TINY = os.path.join(CHIP, 'tests', 'tiny_afmoe')
CELL = 'trinity-mini-b1-s8192'
SEED = 2 ** 31 + 17


@pytest.fixture(scope='module')
def man():
    import manifest
    return manifest.Manifest(time.perf_counter(), root=TINY, data=TINY)


def _run(man, tmp, break_path=None):
    import jax
    import harness
    man.t_start = time.perf_counter()
    return harness.run_cell(man, CELL, SEED, 0.5, False, jax.devices()[:1],
                            os.path.join(str(tmp), 'run'),
                            break_path=break_path)


def _failed(res):
    return [k for k, v in res['compared'].items()
            if not v['value'] <= v['limit']]


def test_program_agrees_with_reference(man, tmp_path):
    res = _run(man, tmp_path)
    assert res['correct'] is True, res['compared']
    assert res['failed'] == 0 and res['attempted'] >= 1
    assert set(res['metrics']) == {'tok_per_s', 'step_p90_ms', 'setup_s'}


def test_expert_tokens_are_the_reference_routing_count(man):
    """``build`` hands out each expert layer's second output: fetched
    beside the loss, the first expert layer's tokens a held expert are
    what the reference's routing sends there, and none is dropped."""
    import jax
    import jax.numpy as jnp
    import harness
    cfg = man.config(man.workload(CELL)['config'])
    traffic = man.traffic(man.workload(CELL)['traffic'])
    model = harness.model_module(cfg)
    ref = model.Reference(cfg)
    d = ref.d
    assert not d.experts(0) and d.experts(1)
    sess = harness.Session(model, cfg, traffic, jax.devices()[:1])
    sess.start()
    wkey = jax.random.fold_in(harness.key_of(SEED), 0)
    p = ref.init(wkey)
    # a copy for the program: its step donates what it is given
    sess.set_params([(n, jnp.array(p[n])) for n, _, _ in ref.leaves()])
    batch = harness.Feeder(model, cfg, dict(traffic, placement='host'),
                           SEED, None).feed(0)
    tokens = sess.built['expert_tokens']
    assert len(tokens) == d.n_expert_layers
    got = sess.exe0.run(sess.main, feed=batch, fetch_list=tokens[:1],
                        scope=sess.scope)[0]
    dot = model.Float32Dots()
    x = p['embed'][batch['data']] * d.embed_scale
    x = ref.block(0, p, x, dot)
    a = ref.attention(p, ref.rms_norm(x, p['l1.norm_in']), 'l1.', dot,
                      d.kinds[1])
    x = x + ref.rms_norm(a, p['l1.norm_post_attn'])
    _, idx, _ = ref.routing(p, ref.rms_norm(x, p['l1.norm_pre_mlp']), 'l1.',
                            dot)
    first, held = d.held
    want = [int(jnp.sum(idx == first + j)) for j in range(held)]
    assert list(np.asarray(got).reshape(-1)) == want
    assert 0 < sum(want) <= idx.size


def test_low_precision_control_is_not_correct(man):
    import jax
    import harness
    cfg = man.config(man.workload(CELL)['config'])
    traffic = man.traffic(man.workload(CELL)['traffic'])
    limits = man.limits(CELL)
    model = harness.model_module(cfg)
    ref = model.Reference(cfg)
    devices = jax.devices()[:1]
    failed_on = []
    for seed in (SEED, SEED + 1, SEED + 2):
        wkey = jax.random.fold_in(harness.key_of(seed), 0)
        feeder = harness.Feeder(model, cfg, dict(traffic, placement='host'),
                                seed, None)
        want = harness.reference_steps(ref, wkey, feeder.first(3), devices)
        ctrl = harness.reference_steps(ref, wkey, feeder.first(3), devices,
                                       dot=model.ControlDots())
        numbers, _ = harness.compare(ctrl, want)
        failed_on.append([k for k in limits if not numbers[k] <= limits[k]])
    assert all(failed_on), failed_on


def half_batch(sess):
    """Half of the batch is left out; the mean is over the rest."""
    inner = sess.dispatch
    sess.dispatch = lambda feed: inner(
        {k: np.asarray(v)[:len(v) // 2] for k, v in feed.items()})


def test_half_of_the_batch_left_out_is_not_correct(man, tmp_path):
    res = _run(man, tmp_path, break_path=half_batch)
    assert res['correct'] is False
    assert _failed(res), res['compared']


def ignore_the_window(sess):
    """Every layer attends as a full layer does: the window layers keep
    their rotary positions and lose their band."""
    ops = [op for op in sess.main.global_block().ops
           if op.type == 'flash_attention']
    assert sum(1 for op in ops if op.attrs['window']) == 3 < len(ops)
    for op in ops:
        op.attrs['window'] = 0


def test_a_program_that_ignores_the_window_is_not_correct(man, tmp_path):
    res = _run(man, tmp_path, break_path=ignore_the_window)
    assert res['correct'] is False
    assert _failed(res), res['compared']


def test_required_work_matches_hand_counts():
    """At the published widths and this chip's share (ISSUE 33's
    arithmetic, at the 8 experts the compiled step's live bytes left):
    parameters held, forward operations a token, and the kernels'
    work."""
    from models import afmoe
    with open(os.path.join(CHIP, 'configs', 'trinity-mini.json')) as f:
        cfg = json.load(f)
    assert (cfg['hidden_size'], cfg['head_dim'], cfg['intermediate_size'],
            cfg['moe_intermediate_size'], cfg['router_num_experts'],
            cfg['num_experts_per_tok'], cfg['sliding_window']) \
        == (2048, 128, 6144, 1024, 128, 8, 2048)
    ref = afmoe.Reference(cfg)
    d = ref.d
    assert d.kinds == [afmoe.SLIDING] * 3 + [afmoe.FULL, afmoe.SLIDING]
    assert [d.experts(i) for i in range(5)] == [False] + [True] * 4
    held = d.held[1]
    assert held in (8, 16)
    count = {n: int(np.prod(s)) for n, s, _ in ref.leaves()}
    per = {i: sum(v for n, v in count.items() if n.startswith('l%d.' % i))
           for i in range(5)}
    att = sum(count['l1.' + n] for n in ('q', 'k', 'v', 'gate', 'o'))
    assert att == 2048 * (3 * 512 + 2 * 128)              # 3.67 M
    expert = 3 * 2048 * 1024                              # 6.29 M
    norms = 4 * 2048 + 2 * 128
    assert per[0] == att + 3 * 2048 * 6144 + norms        # 41.4 M
    for i in range(1, 5):
        # router 0.26 M, its buffer, the shared expert, the held ones
        assert per[i] == att + 2048 * 128 + 128 + expert * (1 + held) \
            + norms
    total = sum(count.values())
    assert total == sum(per.values()) + 2 * 25024 * 2048 + 2048
    assert abs(total - (386.2e6 if held == 8 else 587.5e6)) < 0.5e6
    traffic = {'batch': 1, 'seq_len': 8192}
    S = 8192
    band = 2048 * 2049 // 2 + (S - 2048) * 2048
    triangle = S * (S + 1) // 2
    assert afmoe.kept_pairs(S, 2048) == band
    assert afmoe.kept_pairs(S) == afmoe.kept_pairs(S, S) == triangle
    assert 0.43 < band / triangle < 0.44
    pairs = 4 * band + triangle
    f, b = afmoe.flash_fwd_work(cfg, traffic, 1)
    assert f == 4 * 2 * 2 * 128 * pairs
    assert b == 5 * S * 128 * 2 * (2 * 4 + 2 * 1)
    assert afmoe.flash_bwd_work(cfg, traffic, 1)[0] == 2 * f
    weights = 5 * att + 3 * 2048 * 6144 \
        + 4 * (2048 * 128 + expert * (1 + held / 16.0)) + 2048 * 25024
    assert afmoe.matmul_weights_per_token(cfg) == weights
    assert afmoe.required_flops(cfg, traffic) \
        == 6 * weights * S + 3 * f
    fwd = afmoe.required_flops(cfg, traffic) / 3.0
    assert (2.5e12 if held == 8 else 2.7e12) < fwd < 2.9e12
    ef, eb = afmoe.expert_mm_work(cfg, traffic, 1)
    assert ef == 4 * 6 * (S * held // 16) * expert
    assert eb > 4 * 8 * held * expert


def test_new_readers_on_a_hand_made_trace(man):
    """``routed_experts_ms.tok`` is the device time under the op's
    scopes, forward and backward, the grouped products' kernels and the
    row moves included (no kernel is read by name); its roofline
    divides the nine products' least time by it; ``rope_ms.tok`` reads
    the rotary op's scopes; ``window_flash_engaged`` the program's
    count of windowed lowerings on the Pallas route. A program without
    the op or the counter reads nothing."""
    import readers_hybrid as rh
    from reduce_trace import Event
    from models import afmoe
    from paddle_tpu import observability as obs
    pre = 'jit(fn)/jvp(forward)/'
    bwd = 'jit(fn)/transpose(jvp(forward))/'
    scopes = {'jit_fn|aa|0': {
        'fusion.1': pre + 'routed_experts:r.tmp_0/top_k',
        '_gmm_kernel.2': pre + 'routed_experts:r.tmp_0/pallas_call',
        'fusion.3': bwd + 'routed_experts:r.tmp_0/scatter-add',
        'fusion.4': pre + 'rotary_embedding:rot.tmp_0/cos',
        'fusion.5': bwd + 'rotary_embedding:rot.tmp_1/mul',
        'fusion.6': bwd + 'mul:fc.tmp_0/dot_general'}}
    dev = [Event('fusion.1', 0.0, 1.0), Event('_gmm_kernel.2', 1.0, 3.0),
           Event('fusion.3', 3.0, 4.0), Event('fusion.4', 4.0, 4.25),
           Event('fusion.5', 4.25, 4.5), Event('fusion.6', 4.5, 6.0)]
    cfg = man.config('trinity-mini')
    traffic = man.traffic('b1-s8192')
    ctx = {'trace': {'devices': {'a': dev}, 'host': []},
           'trace_window': (0.0, 10.0), 'trace_steps': 2,
           'program_scopes': scopes, 'man': man, 'cfg': cfg,
           'traffic': traffic, 'chips': 1, 'model': afmoe,
           'device_kind': 'TPU v5 lite'}

    def spec(name):
        with open(os.path.join(CHIP, 'layer_metrics', name + '.json')) as f:
            return json.load(f)
    moe = spec('routed_experts_ms.tok')
    assert 'unscoped' not in moe
    assert rh.scope_ms(ctx, moe) == pytest.approx(1e3 * 4.0 / 2)
    assert rh.scope_ms(ctx, spec('rope_ms.tok')) \
        == pytest.approx(1e3 * 0.5 / 2)
    flops, nbytes = afmoe.expert_mm_work(cfg, traffic, 1)
    least = max(flops / 197e12, nbytes / 819e9)
    assert rh.scope_roofline(ctx, spec('routed_experts_roofline.tok')) \
        == pytest.approx(100 * least / 2.0)
    none = dict(ctx, program_scopes={'jit_fn|aa|0': {
        'fusion.6': bwd + 'mul:fc.tmp_0/dot_general'}})
    none.pop('by_scope', None)
    assert rh.scope_ms(none, moe) is None
    assert rh.scope_ms(none, spec('rope_ms.tok')) is None
    assert rh.scope_roofline(none, spec('routed_experts_roofline.tok')) \
        is None

    engaged = spec('window_flash_engaged')
    from paddle_tpu.compiler import passes
    was = sum(passes.window_flash_counts().values())
    counter = obs.default_registry().counter(
        'flash_attention_lowerings_total', route='pallas', dtype='bf16',
        diag='chunked2', kv_heads='1', window='2048')
    for _ in range(4):
        counter.inc()
    obs.default_registry().counter(
        'flash_attention_lowerings_total', route='xla', dtype='bf16',
        diag='none', kv_heads='1', window='2048').inc()
    obs.default_registry().counter(
        'flash_attention_lowerings_total', route='pallas', dtype='bf16',
        diag='chunked2', kv_heads='1', window='0').inc()
    assert rh.program_count(ctx, engaged) == was + 4
    assert rh.program_count(ctx, {'counts': 'no_such_counts'}) is None
