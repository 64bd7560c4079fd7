"""Record what the four-chip cell's metric test reads
(``tests/tiny_dp4.trace.json.gz``, ``tests/tiny_dp4.scopes.json.gz``): the
tiny four-device run of ``resnet50-dp4-b1024-resident`` (``tests/tiny``:
real widths, 64x64 images, batch 16 over four chips) traced through the
harness's own ``run_cell`` with every metric the committed manifest
gives the cell. The trace is kept as ``reduce_trace.load`` returns it,
each operation by its instruction's name, start and end: the whole HLO
text the runtime names an event by would make the file tens of MB. Run
once on a four-chip host:

    python3 benchmark/chip/tests/record_tiny_dp4.py <out dir>
"""
import gzip
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
TINY = os.path.join(HERE, 'tiny')
CELL = 'resnet50-dp4-b1024-resident'
SEED = 2 ** 31 + 11


def tiny_manifest():
    """The tiny copy's cells with the committed manifest's metrics."""
    import manifest
    man = manifest.Manifest(time.perf_counter(), root=TINY, data=TINY)
    committed = manifest.Manifest(0.0)
    for kind in ('end_to_end', 'per_layer'):
        man.doc[kind] = committed.doc[kind]
    return man


def dump_trace(trace, path, device_kind):
    """``reduce_trace.load``'s dictionary as compact JSON: each event
    ``[name, start, end]`` in seconds; beside it the kind of device it
    was recorded on, whose peaks the test reads."""
    def evs(events):
        return [[e.name, e.start, e.end] for e in events]
    doc = {'devices': {d: evs(v) for d, v in trace['devices'].items()},
           'in_flight': {d: evs(v) for d, v in trace['in_flight'].items()},
           'host': evs(trace['host']), 'device_kind': device_kind}
    with gzip.open(path, 'wt') as f:
        json.dump(doc, f, separators=(',', ':'))


def load_trace(path):
    from reduce_trace import Event
    with gzip.open(path, 'rt') as f:
        doc = json.load(f)

    def evs(rows):
        return [Event(n, s, e) for n, s, e in rows]
    return {'devices': {d: evs(v) for d, v in doc['devices'].items()},
            'in_flight': {d: evs(v) for d, v in doc['in_flight'].items()},
            'host': evs(doc['host']), 'device_kind': doc['device_kind']}


def main(out):
    for p in (ROOT, CHIP):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    import harness
    import readers_program
    import reduce_trace
    os.makedirs(out, exist_ok=True)
    keep = os.path.join(out, '_xplane')
    scopes = os.path.join(out, 'tiny_dp4.scopes.json')
    os.environ[readers_program.SCOPES_OUT_ENV] = scopes
    man = tiny_manifest()
    res = harness.run_cell(man, CELL, SEED, 0.5, True, jax.devices()[:4],
                           os.path.join(out, '_run'), keep_trace=keep)
    dump_trace(reduce_trace.load(reduce_trace.find_xplane(keep)),
               os.path.join(out, 'tiny_dp4.trace.json.gz'),
               res['device']['kind'])
    shutil.rmtree(keep)
    with open(scopes, 'rb') as f, gzip.open(scopes + '.gz', 'wb') as g:
        shutil.copyfileobj(f, g)
    os.remove(scopes)
    print(json.dumps({'correct': res['correct'], 'metrics': res['metrics'],
                      'device': res['device']}))


if __name__ == '__main__':
    main(sys.argv[1])
