"""The reducer on a hand-made case and on a small recorded trace."""
import os

import pytest

import reduce_trace as rt
from reduce_trace import Event

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, 'tiny_trace.xplane.pb')


def _hand_made():
    """Device A: fusion 0-4, all-reduce 3-7 (1 s hidden behind the
    fusion, 3 s exposed), _flash_kernel 8-9; idle 7-8 and 9-10.
    Device B: one fusion 0-2."""
    a = [Event('fusion.1', 0.0, 4.0, 'jit(f)/conv2d/conv'),
         Event('all-reduce.2', 3.0, 7.0),
         Event('_flash_kernel.3', 8.0, 9.0)]
    b = [Event('%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8] %p)', 0.0, 2.0)]
    host = [Event('dispatch', 0.0, 0.5), Event('fetch', 0.5, 7.2),
            Event('feed', 7.2, 7.3), Event('dispatch', 7.3, 7.9),
            Event('fetch', 7.9, 10.0)]
    return {'devices': {'/device:TPU:0': a, '/device:TPU:1': b},
            'host': host}


def test_event_names_come_from_the_hlo_text():
    e = _hand_made()['devices']['/device:TPU:1'][0]
    assert e.name == 'fusion.1' and e.text.startswith('%fusion.1 = bf16')
    assert rt.short_name('_flash_kernel.3') == '_flash_kernel.3'


def test_interval_arithmetic():
    assert rt.union([(0, 2), (1, 3), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]
    assert rt.total([(0, 3), (5, 6)]) == 4
    assert rt.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4),
                                                         (6, 10)]
    assert rt.subtract([(3, 7)], [(0, 4)]) == [(4, 7)]
    assert rt.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]


def test_busy_idle_names_and_exposed_collectives():
    tr = _hand_made()
    lo, hi = rt.window_of(tr, 'dispatch')[0], rt.window_of(tr, 'fetch')[1]
    assert (lo, hi) == (0.0, 10.0)
    busy = rt.busy(tr, lo, hi)
    assert busy == {'/device:TPU:0': 8.0, '/device:TPU:1': 2.0}
    assert 1 - max(busy.values()) / (hi - lo) == pytest.approx(0.2)
    assert rt.time_by_name(tr, r'^_flash_kernel', lo, hi)[
        '/device:TPU:0'] == (1.0, 1)
    assert rt.time_by_name(tr, r'/conv2d/', lo, hi, 'scope')[
        '/device:TPU:0'] == (4.0, 1)
    assert rt.time_by_name(tr, r'^nothing', lo, hi)['/device:TPU:0'] \
        == (0, 0)
    assert rt.exposed_collective(tr, lo, hi) == {
        '/device:TPU:0': 3.0, '/device:TPU:1': 0.0}
    assert rt.top_ops(tr, lo, hi)[:2] == [('fusion.1', 4.0),
                                          ('all-reduce.2', 4.0)]
    # the gap 7-8 lies mostly under the second dispatch (7.3-7.9), the
    # gap 9-10 under the last fetch
    assert dict(rt.idle_gaps(tr, lo, hi, ('feed', 'dispatch', 'fetch'))) \
        == {'dispatch': 1.0, 'fetch': 1.0}


def test_the_tpus_fused_collectives_count_as_collectives():
    """On the TPU a reduce-scatter is a fusion that calls an
    ``all-reduce-scatter`` computation, and an asynchronous collective
    fusion shows as a start and a done; a computing fusion with a
    collective inside it stays computing (as read in the four-chip
    cell's trace)."""
    evs = [Event('%fusion.25 = bf16[512,1000]{0,1} fusion(bf16[2048,1000]'
                 ' %x), kind=kCustom, calls=%all-reduce-scatter.24',
                 0.0, 1.0),
           Event('async-collective-start.3', 1.0, 1.5),
           Event('%fusion.1966 = (bf16[64,3,7,7], f32[4,1,256]) fusion('
                 'bf16[256,3,224,224] %p), kind=kOutput, '
                 'calls=%fused_computation.2845', 1.5, 4.0),
           Event('async-collective-done.3', 4.0, 4.25)]
    assert [rt.is_collective(e) for e in evs] == [True, True, False, True]
    tr = {'devices': {'/device:TPU:0': evs}, 'host': []}
    assert rt.exposed_collective(tr, 0.0, 4.25) == {'/device:TPU:0': 1.75}


@pytest.mark.skipif(not os.path.isfile(TINY),
                    reason='no recorded trace beside the test')
def test_recorded_trace():
    tr = rt.load(TINY)
    assert tr['devices'], 'a device plane with operations'
    spans = [e.name for e in tr['host']
             if e.name in ('feed', 'dispatch', 'fetch')]
    assert spans.count('dispatch') == 3 and spans.count('fetch') == 3
    lo, _ = rt.window_of(tr, 'feed')
    _, hi = rt.window_of(tr, 'fetch')
    busy = rt.busy(tr, lo, hi)
    assert all(0 < b < hi - lo for b in busy.values())
    # three steps of the same two-operation program: every operation's
    # name shows three times
    dev = next(iter(tr['devices']))
    inside = [e for e in tr['devices'][dev] if lo <= e.start <= hi]
    assert len(inside) >= 3 and len(inside) % 3 == 0
    assert rt.exposed_collective(tr, lo, hi)[dev] == 0.0
    gaps = dict(rt.idle_gaps(tr, lo, hi, ('feed', 'dispatch', 'fetch')))
    assert sum(gaps.values()) == pytest.approx(
        (hi - lo) - max(busy.values()), rel=1e-6)
