"""The nemotron_h cell at a size a test run can hold, through the
harness (a tiny root of its own, ``tiny_nemotron/``): the Fluid program
agrees with the plain reference, the fp8 control and a step on half of
the batch come out as not correct, and the model module's counts of
required work match hand counts at the published sizes.
"""
import json
import os
import time

import numpy as np
import pytest

from conftest import CHIP

TINY = os.path.join(CHIP, 'tests', 'tiny_nemotron')
CELL = 'nemotron3-super-120b-a12b-b1-s4096'
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
    assert d.pattern[:2] == 'ME'
    sess = harness.Session(model, cfg, traffic, jax.devices()[:1])
    sess.start()
    wkey = jax.random.fold_in(harness.key_of(SEED), 0)
    p = ref.init(wkey)
    # a copy for the program: its step donates what it is given
    sess.set_params([(n, jnp.array(p[n])) for n, _, _ in ref.leaves()])
    batch = harness.Feeder(model, cfg, dict(traffic, placement='host'),
                           SEED, None).feed(0)
    tokens = sess.built['expert_tokens']
    assert len(tokens) == d.count('E')
    got = sess.exe0.run(sess.main, feed=batch, fetch_list=tokens[:1],
                        scope=sess.scope)[0]
    dot = model.Float32Dots()
    x = p['embed'][batch['data']]
    x = x + ref.branch('M', p, ref.rms_norm(x, p['l0.norm']), 'l0.', dot)
    _, idx, _ = ref.routing(p, ref.rms_norm(x, p['l1.norm']), 'l1.', dot)
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
    assert [k for k, v in res['compared'].items()
            if not v['value'] <= v['limit']], res['compared']


def test_required_work_matches_hand_counts():
    """At the published widths and this chip's share (ISSUE 31's
    arithmetic): parameters held, forward operations a token, and the
    kernels' work."""
    from models import nemotron_h
    with open(os.path.join(CHIP, 'configs',
                           'nemotron3-super-120b-a12b.json')) as f:
        cfg = json.load(f)
    ref = nemotron_h.Reference(cfg)
    held = sum(int(np.prod(s)) for _, s, _ in ref.leaves())
    assert abs(held - 700.9e6) < 0.5e6
    per = {k: sum(int(np.prod(s)) for _, s, _ in ref.block_leaves(k, ''))
           for k in 'M*E'}
    assert abs(per['M'] - 13.71e6) < 0.02e6
    assert abs(per['*'] - 5.25e6) < 0.01e6
    assert abs(per['E'] - 98.57e6) < 0.02e6
    traffic = {'batch': 1, 'seq_len': 4096}
    fwd = nemotron_h.required_flops(cfg, traffic) / 3.0 / 4096
    assert 0.84e9 < fwd < 0.87e9            # the issue reckons 855 M
    d = nemotron_h.Dims(cfg)
    assert d.pattern == 'MEMEMEM*EME'
    assert nemotron_h.routed_pairs_per_token(d) * 4096 / 8 == 176
    f, b = nemotron_h.flash_fwd_work(cfg, traffic, 1)
    assert f == 4 * 2 * (2 * 4096 * 4096 * 128) // 2
    assert b == 4096 * 128 * 2 * (2 * 4 + 2 * 1)
    assert nemotron_h.flash_bwd_work(cfg, traffic, 1)[0] == 2 * f
    sf, sb = nemotron_h.ssd_work(cfg, traffic, 1)
    assert sf == 3 * 5 * 4096 * 16 * 5 * 64 * 128
    ef, eb = nemotron_h.expert_mm_work(cfg, traffic, 1)
    assert ef == 5 * 6 * 1408 * 2 * 1024 * 2688
    assert eb > 5 * 8 * 8 * 2 * 1024 * 2688
    # the products' least time is HBM's, 2.39 ms a step: what
    # routed_experts_roofline.tok divides by the op's device time
    assert ef / 197e12 < eb / 819e9
    assert 2.38e-3 < eb / 819e9 < 2.40e-3


def test_hybrid_readers_on_a_hand_made_trace(man):
    """The nemotron cell reads the routed op through the trinity cell's
    two metrics: ``routed_experts_ms.tok`` is the device time under the
    op's scopes, forward and backward, the grouped products' Pallas
    kernels, the routing and the row moves included (no kernel is read
    by name); ``routed_experts_roofline.tok`` divides the least time of
    the six grouped products' required work over the latent width
    (``expert_mm_work``) by it. ``ssd_ms.tok`` and its roofline read the
    scan's scopes the same way; a program without the op, the map or
    the counter reads nothing."""
    import readers_hybrid as rh
    from reduce_trace import Event
    from models import nemotron_h
    pre = 'jit(fn)/jvp(forward)/'
    bwd = 'jit(fn)/transpose(jvp(forward))/'
    scopes = {'jit_fn|aa|0': {
        'fusion.1': pre + 'routed_experts:r.tmp_0/top_k',
        '_gmm_kernel.7': pre + 'routed_experts:r.tmp_0/pallas_call',
        'fusion.2': bwd + 'routed_experts:r.tmp_0/mul',
        '_tgmm_kernel.8': bwd + 'routed_experts:r.tmp_0/pallas_call',
        'fusion.3': pre + 'ssd_scan:s.tmp_0/dot_general',
        'fusion.4': bwd + 'mul:fc.tmp_0/dot_general'}}
    dev = [Event('fusion.1', 0.0, 1.0), Event('_gmm_kernel.7', 1.0, 3.0),
           Event('fusion.2', 3.0, 4.0), Event('fusion.3', 4.0, 4.5),
           Event('fusion.4', 4.5, 6.0), Event('_tgmm_kernel.8', 6.0, 6.5)]
    cfg = man.config('nemotron3-super-120b-a12b')
    traffic = man.traffic('b1-s4096')
    ctx = {'trace': {'devices': {'a': dev}, 'host': []},
           'trace_window': (0.0, 10.0), 'trace_steps': 2,
           'program_scopes': scopes, 'man': man, 'cfg': cfg,
           'traffic': traffic, 'chips': 1, 'model': nemotron_h,
           'device_kind': 'TPU v5 lite'}

    def spec(name):
        with open(os.path.join(CHIP, 'layer_metrics', name + '.json')) as f:
            return json.load(f)
    moe = spec('routed_experts_ms.tok')
    assert set(moe) == {'reader', 'op_type'}
    assert rh.scope_ms(ctx, moe) == pytest.approx(1e3 * 4.5 / 2)
    assert rh.scope_ms(ctx, spec('ssd_ms.tok')) \
        == pytest.approx(1e3 * 0.5 / 2)
    assert rh.scope_ms(ctx, {'op_type': 'conv2d'}) is None
    flops, nbytes = nemotron_h.ssd_work(cfg, traffic, 1)
    least = max(flops / 197e12, nbytes / 819e9)
    assert rh.scope_roofline(ctx, spec('ssd_roofline.tok')) \
        == pytest.approx(100 * least / 0.25)
    flops, nbytes = nemotron_h.expert_mm_work(cfg, traffic, 1)
    least = max(flops / 197e12, nbytes / 819e9)
    assert rh.scope_roofline(ctx, spec('routed_experts_roofline.tok')) \
        == pytest.approx(100 * least / 2.25)
    assert rh.scope_ms({'trace': None}, moe) is None
    none = dict(ctx, program_scopes={'jit_fn|aa|0': {
        'fusion.4': bwd + 'mul:fc.tmp_0/dot_general'}})
    none.pop('by_scope', None)
    assert rh.scope_ms(none, moe) is None
    assert rh.scope_roofline(none, spec('routed_experts_roofline.tok')) \
        is None
    assert rh.program_count(ctx, {'counts': 'no_such_counts'}) is None
