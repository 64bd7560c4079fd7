"""BENCHMARK.json against the contract's rules that a run cannot see."""
import json
import os
import re

import pytest

from conftest import CHIP, ROOT, appended_manifest

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
SOURCES = {'device_trace', 'program_span', 'program_counter', 'host_clock'}


@pytest.fixture(params=['as_committed', 'one_appended'])
def bench(request, tmp_path):
    """(manifest, its benchmark/chip): the committed one, and a copy
    with one more per-layer metric appended, as a later PR adds one:
    every rule here holds for both."""
    if request.param == 'as_committed':
        root, chip = ROOT, CHIP
    else:
        root, chip = appended_manifest(tmp_path)
    with open(os.path.join(root, 'BENCHMARK.json')) as f:
        return json.load(f), root, chip


def test_names_units_and_lengths(bench):
    doc, root, chip = bench
    assert set(doc) == {'command', 'paths', 'run_seconds', 'configs',
                        'workloads', 'end_to_end', 'per_layer'}
    names = []
    for c in doc['configs']:
        assert set(c) == {'name', 'source', 'file', 'reduced', 'why'}
        assert NAME.match(c['name'])
        assert all(NAME.match(k) for k in c['reduced'])
        assert any(c['file'].startswith(p + '/') for p in doc['paths'])
        assert os.path.isfile(os.path.join(root, c['file']))
        assert 1 <= len(c['why']) <= 200 and 1 <= len(c['source']) <= 200
    for w in doc['workloads']:
        assert set(w) == {'name', 'config', 'traffic', 'chips', 'why'}
        assert NAME.match(w['name']) and NAME.match(w['traffic'])
        assert w['chips'] in (1, 4) and 1 <= len(w['why']) <= 200
        assert w['config'] in {c['name'] for c in doc['configs']}
        for folder in ('traffic', 'limits'):
            key = w['traffic'] if folder == 'traffic' else w['name']
            assert os.path.isfile(os.path.join(chip, folder, key + '.json'))
    for m in doc['end_to_end'] + doc['per_layer']:
        assert NAME.match(m['name']) and UNIT.match(m['unit'])
        assert m['better'] in ('lower', 'higher')
        assert m['source'] in SOURCES
        names.append(m['name'])
    assert len(names) == len(set(names))
    for m in doc['end_to_end']:
        assert set(m) - {'workloads'} == {'name', 'unit', 'better', 'bound',
                                          'source'}
        assert m['source'] in ('host_clock', 'device_trace')
        assert 0.01 <= m['bound'] <= 0.1
        assert os.path.isfile(os.path.join(chip, 'end_to_end',
                                           m['name'] + '.json'))
    four = sum(1 for w in doc['workloads'] if w['chips'] == 4)
    assert four <= max(1, len(doc['workloads']) // 4)
    assert 1 <= doc['run_seconds'] <= 51
    assert os.path.getsize(os.path.join(root, 'BENCHMARK.json')) < 65536


def test_every_moves_names_a_metric_its_cells_report(bench):
    doc, _, chip = bench
    cells = [w['name'] for w in doc['workloads']]
    for m in doc['end_to_end'] + doc['per_layer']:
        assert set(m.get('workloads', cells)) <= set(cells), m['name']
    e2e = {m['name']: m.get('workloads', cells) for m in doc['end_to_end']}
    assert 'setup_s' in e2e and e2e['setup_s'] == cells
    layers = set()
    for m in doc['per_layer']:
        assert set(m) - {'workloads'} == {'name', 'unit', 'better', 'source',
                                          'layer', 'moves'}
        assert m['moves'] in e2e
        mine = m.get('workloads', e2e[m['moves']])
        assert mine and set(mine) <= set(e2e[m['moves']]), m['name']
        assert os.path.isfile(os.path.join(chip, 'layer_metrics',
                                           m['name'] + '.json'))
        layers.add(m['layer'])
        assert '\n' not in m['layer'] and len(m['layer']) <= 200
    for c in cells:
        reported_e2e = [n for n, ws in e2e.items() if c in ws]
        assert len(reported_e2e) >= 2
        assert any(c in m.get('workloads', e2e[m['moves']])
                   for m in doc['per_layer'])


def test_kernel_rooflines_stand_beside_a_step_mfu(bench):
    doc, _, _ = bench
    moved_by_mfu = {m['moves'] for m in doc['per_layer']
                    if 'mfu' in m['name'].split('.')[0].split('_')}
    for m in doc['per_layer']:
        if m['name'].split('.')[0].endswith('_roofline'):
            assert m['unit'] == '%' and m['moves'] in moved_by_mfu


def test_metrics_are_read_by_name_wherever_they_stand(tmp_path,
                                                      monkeypatch):
    """The harness finds a metric's data file by the metric's name, not
    by its place in ``per_layer``: with every entry in the opposite
    order and one appended, each cell reads the same metrics from the
    same files, and the appended one beside them in the cells it
    lists. Each reader is stood in for by one that says which file it
    was handed."""
    import manifest
    import readers
    from conftest import MADE_UP, MADE_UP_SPEC
    files = []
    real_read = manifest._read

    def read(path):
        doc = real_read(path)
        if os.sep + 'layer_metrics' + os.sep in path:
            files.append(os.path.basename(path))
            doc = dict(doc, _file=os.path.basename(path))
        return doc

    class Which(object):
        def __getattr__(self, fn):
            return lambda ctx, spec: len(spec['_file'])

    monkeypatch.setattr(manifest, '_read', read)
    monkeypatch.setattr(manifest.importlib, 'import_module',
                        lambda mod: Which())

    def read_all(root, chip):
        man = manifest.Manifest(0.0, root=root, data=chip)
        man.here = chip
        return {w['name']: man.read_layer_metrics(w['name'], {})
                for w in man.doc['workloads']}

    committed = read_all(ROOT, CHIP)
    appended = read_all(*appended_manifest(tmp_path, reverse=True))
    assert set(appended) == set(committed)
    for cell, got in committed.items():
        want = dict(got)
        if cell in MADE_UP['workloads']:
            want[MADE_UP['name']] = {
                'value': float(len(MADE_UP['name'] + '.json')),
                'unit': MADE_UP['unit']}
        assert appended[cell] == want
        assert all(v['value'] == len(name + '.json')
                   for name, v in got.items())
    assert files.count(MADE_UP['name'] + '.json') == 1
    assert readers.context_value({'n_steps': 7}, MADE_UP_SPEC) == 7
