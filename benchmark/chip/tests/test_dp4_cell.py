"""The four-chip cell ``resnet50-dp4-b1024-resident``: its place in the
manifest, and every metric the manifest gives it read to a number on the
tiny four-device run (``tests/tiny``). The CPU's profiler has no device
plane, so the device trace and the program's scopes are those
``record_tiny_dp4.py`` recorded of the same run on four chips; the rest
of the run (the program, its spans in the window, its compile log and
counters) is this process's own."""
import gzip
import json
import math
import os

import pytest

import record_tiny_dp4 as rec

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, 'tiny_dp4.trace.json.gz')
SCOPES = os.path.join(HERE, 'tiny_dp4.scopes.json.gz')
ONE_CHIP = 'resnet50-b256-resident'


def test_the_cell_is_the_one_chip_cell_on_four():
    import manifest
    man = manifest.Manifest(0.0)
    cells = {w['name']: w for w in man.doc['workloads']}
    assert cells[rec.CELL]['chips'] == 4
    one, dp4 = (man.traffic(cells[c]['traffic']) for c in (ONE_CHIP,
                                                           rec.CELL))
    assert cells[rec.CELL]['config'] == cells[ONE_CHIP]['config']
    assert dp4['executor'] == 'parallel' and one['executor'] == 'executor'
    assert dp4['batch'] == 4 * one['batch']
    for key in ('pool', 'placement', 'ahead', 'warm_steps', 'trace_steps'):
        assert dp4[key] == one[key], key
    # every metric of the one-chip cell is the four-chip cell's too
    for m in man.doc['end_to_end'] + man.doc['per_layer']:
        if ONE_CHIP in m.get('workloads', ()):
            assert rec.CELL in m['workloads'], m['name']


@pytest.fixture
def recorded(monkeypatch):
    """The harness reads the recorded trace and scopes in place of its
    own; returns the recorded trace."""
    import reduce_trace
    from paddle_tpu.observability import perf
    trace = rec.load_trace(TRACE)
    with gzip.open(SCOPES, 'rt') as f:
        scopes = json.load(f)
    monkeypatch.setattr(reduce_trace, 'find_xplane', lambda d: TRACE)
    monkeypatch.setattr(reduce_trace, 'load', lambda path: trace)
    monkeypatch.setattr(perf, 'scope_map', lambda **kw: scopes)
    return trace


def test_every_metric_of_the_cell_reads_a_number(recorded, tmp_path):
    import jax
    import harness
    man = rec.tiny_manifest()
    peaks = man.peaks
    man.peaks = lambda kind: peaks(recorded['device_kind'])
    res = harness.run_cell(man, rec.CELL, rec.SEED, 0.5, True,
                           jax.devices()[:4], str(tmp_path / 'run'))
    assert res['correct'] is True, res['compared']
    want = {m['name'] for m in man.doc['per_layer']
            if rec.CELL in m.get('workloads', [rec.CELL])}
    got = res['metrics']
    assert 'collective_exposed_ms.img' in want
    assert set(got) == want
    assert all(math.isfinite(v['value']) for v in got.values()), got
    assert got['collective_exposed_ms.img']['value'] > 0
    assert 0 < res['device']['busy_s'] <= res['device']['window_s']
