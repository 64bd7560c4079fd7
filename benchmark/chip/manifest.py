"""``BENCHMARK.json`` and the data files it names. A cell, a
configuration, a traffic mix, a metric or a limit is a file found by its
name; adding one needs no edit here.

    configs/<config>.json          sizes, source, optimizer, model module
    traffic/<traffic>.json         the mix's parameters
    limits/<workload>.json         the limit of each number compared
    end_to_end/<metric>.json       reader and its parameters
    layer_metrics/<metric>.json    reader and its parameters
    peaks.json                     per device_kind
"""
import importlib
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _read(path):
    with open(path) as f:
        return json.load(f)


class Manifest(object):
    def __init__(self, t_start, root=ROOT, data=HERE):
        """``root`` holds ``BENCHMARK.json`` and is what a configuration's
        ``file`` is relative to; ``data`` holds ``traffic/`` and
        ``limits/``. The benchmark's tests point both at a tiny copy;
        readers and peaks are always this directory's."""
        self.t_start = t_start
        self.root, self.data, self.here = root, data, HERE
        self.doc = _read(os.path.join(root, 'BENCHMARK.json'))

    def workload(self, name):
        for w in self.doc['workloads']:
            if w['name'] == name:
                return w
        raise KeyError('no workload %r in BENCHMARK.json (has: %s)' % (
            name, ', '.join(w['name'] for w in self.doc['workloads'])))

    def config(self, name):
        for c in self.doc['configs']:
            if c['name'] == name:
                return _read(os.path.join(self.root, c['file']))
        raise KeyError('no config %r in BENCHMARK.json' % name)

    def traffic(self, name):
        return _read(os.path.join(self.data, 'traffic', name + '.json'))

    def limits(self, workload):
        doc = _read(os.path.join(self.data, 'limits', workload + '.json'))
        return {k: float(v) for k, v in doc['limits'].items()}

    def peaks(self, device_kind):
        table = _read(os.path.join(self.here, 'peaks.json'))['devices']
        if device_kind not in table:
            raise KeyError(
                'device kind %r is not in peaks.json (has: %s): add its '
                'published peaks with their source'
                % (device_kind, ', '.join(sorted(table))))
        return table[device_kind]

    # -- metrics ---------------------------------------------------------------
    def _reported_in(self, metric, workload):
        return 'workloads' not in metric or workload in metric['workloads']

    def _read_metrics(self, kind, folder, workload, ctx):
        if self.here not in sys.path:
            sys.path.insert(0, self.here)
        out = {}
        for m in self.doc[kind]:
            if not self._reported_in(m, workload):
                continue
            spec = _read(os.path.join(self.here, folder,
                                      m['name'] + '.json'))
            mod, fn = spec['reader'].split(':')
            value = getattr(importlib.import_module(mod), fn)(ctx, spec)
            if value is not None:
                out[m['name']] = {'value': float(value), 'unit': m['unit']}
        return out

    def read_end_to_end(self, workload, ctx):
        return self._read_metrics('end_to_end', 'end_to_end', workload, ctx)

    def read_layer_metrics(self, workload, ctx):
        return self._read_metrics('per_layer', 'layer_metrics', workload,
                                  ctx)

    # -- what the benchmark takes from the program -----------------------------
    @staticmethod
    def counters():
        """The program's own exact counts (conv-fuse pass)."""
        from paddle_tpu.compiler.passes import conv_fuse_counts
        c = conv_fuse_counts()
        return {'conv_fuse_engaged': int(c['engaged']),
                'conv_fuse_fallbacks': dict(c['fallbacks'])}

    @staticmethod
    def op_label(event):
        """The label a device operation is grouped under in
        ``breakdown``: the instruction's name and the largest tensor it
        touches (this runtime's trace carries no framework scope,
        so the Fluid op type cannot be named: PERF.md, for the tracing
        issue). Mosaic kernels keep their kernel's name."""
        shapes = re.findall(r'(?:bf16|f32|s32|f16|s8|u8)\[[\d,]+\]',
                            event.text)
        if not shapes:
            return event.name

        def size(sh):
            n = 1
            for d in sh[sh.index('[') + 1:-1].split(','):
                n *= int(d)
            return n
        return event.name + ' ' + max(shapes, key=size)
