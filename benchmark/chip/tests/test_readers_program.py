"""The program readers on a hand-made trace dictionary and on a small
recorded trace of a tiny Fluid training program
(``record_tiny_step.py``)."""
import json
import os

import pytest

import readers_program as rp
from reduce_trace import Event
import reduce_trace as rt

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, 'tiny_step.xplane.pb')
TINY_SCOPES = os.path.join(HERE, 'tiny_step.scopes.json')
FWD = 'jit(fn)/jvp(forward)/conv2d:c1.tmp_0/conv_general_dilated'
BWD = 'jit(fn)/transpose(jvp(forward))/conv2d:c1.tmp_0/conv_general_dilated'
OPT = 'jit(fn)/optimizer/momentum:c1.w_0/sub'
METRICS = ('exe_run_ms', 'exe_prep_ms', 'exe_launch_ms', 'idle_in_exe_ms',
           'fwd_ms', 'bwd_ms', 'opt_ms', 'scope_coverage')


def _spec(name, suffix='img'):
    with open(os.path.join(os.path.dirname(HERE), 'layer_metrics',
                           '%s.%s.json' % (name, suffix))) as f:
        return json.load(f)


def _read(ctx, name, suffix='img'):
    spec = _spec(name, suffix)
    mod, fn = spec['reader'].split(':')
    assert mod == 'readers_program'
    return getattr(rp, fn)(ctx, spec)


def _hand_made():
    """Two steps in a window 0-20 s. Device A (the busiest): forward
    1-4, backward 4-9, a copy without a scope 9-10, optimizer 10-11 in
    the first step; idle 0-1 and 11-13; forward 13-15, backward 15-18,
    optimizer 18-19 in the second; idle 19-20. ``exe/run`` spans 0.5-3
    and 12-14: the gap 0-1 lies half inside the first, the gap 11-13
    half inside the second. Device B: one short operation, to be passed
    over."""
    a = [Event('fusion.1', 1.0, 4.0), Event('fusion.2', 4.0, 9.0),
         Event('copy-done.3', 9.0, 10.0), Event('fusion.4', 10.0, 11.0),
         Event('fusion.1', 13.0, 15.0), Event('fusion.2', 15.0, 18.0),
         Event('fusion.4', 18.0, 19.0)]
    b = [Event('fusion.1', 1.0, 2.0)]
    host = [Event('feed', 0.0, 0.1), Event('dispatch', 0.4, 3.1),
            Event('exe/run', 0.5, 3.0), Event('exe/prep', 0.5, 1.5),
            Event('exe/launch', 1.5, 2.5), Event('exe/commit', 2.5, 3.0),
            Event('fetch', 3.1, 11.5),
            Event('dispatch', 11.9, 14.1),
            Event('exe/run', 12.0, 14.0), Event('exe/prep', 12.0, 13.5),
            Event('exe/launch', 13.5, 13.8),
            Event('exe/commit', 13.8, 14.0),
            Event('fetch', 14.1, 20.0),
            # outside the window: not read
            Event('exe/run', 30.0, 40.0)]
    scopes = {'jit_fn|aa|0': {'fusion.1': FWD, 'fusion.2': BWD,
                              'fusion.4': OPT},
              # a module of another program that shares one name
              'jit_fn|bb|1': {'fusion.1': OPT},
              '?|cc|2': {'error': 'ValueError: not lowerable'}}
    return {'trace': {'devices': {'/device:TPU:0': a, '/device:TPU:1': b},
                      'host': host},
            'trace_window': (0.0, 20.0), 'trace_steps': 2,
            'program_scopes': scopes}


def test_spans_are_medians_inside_the_window():
    ctx = _hand_made()
    assert _read(ctx, 'exe_run_ms') == pytest.approx(1e3 * 2.25)
    assert _read(ctx, 'exe_prep_ms') == pytest.approx(1e3 * 1.25)
    assert _read(ctx, 'exe_launch_ms') == pytest.approx(1e3 * 0.65)
    assert _read(ctx, 'exe_run_ms', 'tok') == _read(ctx, 'exe_run_ms')


def test_idle_inside_a_run_takes_the_half_of_a_gap_that_is_inside():
    ctx = _hand_made()
    # gaps 0-1, 11-13, 19-20; inside exe/run: 0.5-1 and 12-13
    assert _read(ctx, 'idle_in_exe_ms') == pytest.approx(1e3 * 1.5 / 2)


def test_phases_coverage_and_the_operation_without_one():
    ctx = _hand_made()
    assert _read(ctx, 'fwd_ms') == pytest.approx(1e3 * 5 / 2)
    assert _read(ctx, 'bwd_ms') == pytest.approx(1e3 * 8 / 2)
    assert _read(ctx, 'opt_ms') == pytest.approx(1e3 * 2 / 2)
    assert _read(ctx, 'scope_coverage') == pytest.approx(100 * 15 / 16.0)
    res = rp.by_scope(ctx)
    assert res['busy'] == pytest.approx(8.0)
    assert res['unnamed'] == {'copy-done.3': pytest.approx(0.5)}
    assert res['op']['backward', 'conv2d:c1.tmp_0'] == pytest.approx(4.0)
    total = sum(res['phase'].values())
    assert total == pytest.approx(
        res['busy'] * _read(ctx, 'scope_coverage') / 100)
    assert rp.table(res, 'phase')[1].split()[-1] == 'backward'


def test_nested_operations_count_once():
    loop = [Event('while.1', 0.0, 10.0), Event('fusion.1', 1.0, 4.0),
            Event('fusion.2', 4.0, 9.0)]
    own = {e.name: s for e, s in rp.self_times(loop)}
    assert own == {'while.1': pytest.approx(2.0), 'fusion.1': 3.0,
                   'fusion.2': 5.0}


def test_a_program_without_spans_or_scopes_reads_nothing():
    """The parent of the PR that brought these readers: no ``exe/*``
    span in the trace, no ``scope_map`` in the program."""
    ctx = _hand_made()
    ctx['trace']['host'] = [e for e in ctx['trace']['host']
                            if not e.name.startswith('exe/')]
    ctx['program_scopes'] = {}
    for m in METRICS:
        assert _read(ctx, m) is None
    ctx = dict(_hand_made(), trace=None)
    for m in METRICS:
        assert _read(ctx, m) is None


@pytest.mark.skipif(not os.path.isfile(TINY),
                    reason='no recorded trace beside the test')
def test_recorded_trace_gives_every_reader_a_number():
    tr = rt.load(TINY)
    lo, _ = rt.window_of(tr, 'feed')
    _, hi = rt.window_of(tr, 'fetch')
    with open(TINY_SCOPES) as f:
        scopes = json.load(f)
    ctx = {'trace': tr, 'trace_window': (lo, hi), 'trace_steps': 3,
           'program_scopes': scopes}
    got = {m: _read(ctx, m) for m in METRICS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    runs = [e for e in tr['host'] if e.name == 'exe/run'
            and lo <= e.start and e.end <= hi]
    assert len(runs) == 3
    assert got['exe_prep_ms'] + got['exe_launch_ms'] <= got['exe_run_ms']
    res = rp.by_scope(ctx)
    assert got['fwd_ms'] + got['bwd_ms'] + got['opt_ms'] == pytest.approx(
        1e3 * res['busy'] * got['scope_coverage'] / 100)
    # the reducer clips an operation that straddles the window's edge,
    # the readers leave it out: 6 us of this 0.2 ms step
    busy = max(rt.busy(tr, lo, hi).values()) / 3
    assert res['busy'] == pytest.approx(busy, rel=0.02)
    assert got['idle_in_exe_ms'] <= 1e3 * ((hi - lo) / 3 - busy) + 1e-9
    ops = {op.split(':')[0] for (_, op) in res['op'] if op}
    assert {'conv2d', 'momentum'} <= ops
    assert ('backward', 'conv2d:conv2d_1.tmp_0') in res['op']
