"""The benchmark's own tests run by path on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/chip/tests -q -p no:cacheprovider

They describe no TPU topology and need no chip. Four virtual CPU devices
stand in for the four-chip cell.
"""
import os
import sys

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
flags = os.environ.get('XLA_FLAGS', '')
if 'xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (
        flags + ' --xla_force_host_platform_device_count=4').strip()
os.environ.setdefault('JAX_ENABLE_COMPILATION_CACHE', 'false')

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for p in (ROOT, CHIP):
    if p not in sys.path:
        sys.path.insert(0, p)


# what a later PR does to the manifest: it appends a per-layer metric
# and adds the metric's data file; tests that must stay green after
# such a PR run on the copy ``appended_manifest`` makes as well
MADE_UP = {'name': 'made_up_ms.tok', 'unit': 'ms', 'better': 'lower',
           'source': 'program_span',
           'layer': 'entry point: Executor.run / ParallelExecutor.run',
           'moves': 'tok_per_s', 'workloads': ['opt-1.3b-b2-s2048']}
MADE_UP_SPEC = {'reader': 'readers:context_value', 'key': 'n_steps'}


def appended_manifest(tmp, reverse=False):
    """A copy of the benchmark as such a PR leaves it: ``BENCHMARK.json``
    with ``MADE_UP`` appended to ``per_layer`` (every other entry in
    the opposite order where ``reverse``), its data file beside the
    others, every other file the benchmark's own (linked). Returns
    (root, chip): the copy's root and its ``benchmark/chip``."""
    import json
    root = os.path.join(str(tmp), 'root')
    chip = os.path.join(root, 'benchmark', 'chip')
    metrics = os.path.join(chip, 'layer_metrics')
    os.makedirs(metrics)
    for name in os.listdir(CHIP):
        if name != 'layer_metrics':
            os.symlink(os.path.join(CHIP, name), os.path.join(chip, name))
    for name in os.listdir(os.path.join(CHIP, 'layer_metrics')):
        os.symlink(os.path.join(CHIP, 'layer_metrics', name),
                   os.path.join(metrics, name))
    with open(os.path.join(metrics, MADE_UP['name'] + '.json'), 'w') as f:
        json.dump(MADE_UP_SPEC, f)
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        doc = json.load(f)
    if reverse:
        doc['per_layer'].reverse()
    doc['per_layer'].append(dict(MADE_UP))
    with open(os.path.join(root, 'BENCHMARK.json'), 'w') as f:
        json.dump(doc, f, indent=1)
    return root, chip
