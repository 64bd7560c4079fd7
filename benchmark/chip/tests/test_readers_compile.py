"""The compile-log readers: a hand-made log placed before, in and after
a window; a tiny cell run end to end; a program without the log."""
import json
import os
import time
import types

import pytest

from conftest import CHIP

import readers_compile as rc

TINY = os.path.join(CHIP, 'tests', 'tiny')
NAMES = ('exe_miss_s', 'fluid_lower_s', 'mlir_lower_s', 'xla_compile_s',
         'xla_cache_misses', 'jax_modules', 'jax_retraces')


def _spec(name):
    with open(os.path.join(CHIP, 'layer_metrics', name + '.json')) as f:
        return json.load(f)


def _read(ctx):
    out = {}
    for name in NAMES:
        spec = _spec(name)
        mod, fn = spec['reader'].split(':')
        assert mod == 'readers_compile'
        out[name] = getattr(rc, fn)(ctx, spec)
    return out


def _jax(t, kind, phase='exe/launch', **more):
    return dict({'t': t, 'kind': kind, 'dur_s': 0.5, 'fun': 'jit(fn)',
                 'phase': phase, 'fp': 'abc', 'thread': 1}, **more)


def _miss(t, **parts):
    return dict({'t': t, 'kind': 'miss', 'fp': 'abc', 'phase': 'exe/run',
                 'thread': 1, 'cache': 'miss', 'retrieval_s': 0.0}, **parts)


# process start at 100, the window from 110 to 130
HAND_MADE = [
    _jax(101.0, 'backend', phase=None, cache='miss'),   # the harness's own
    _jax(103.0, 'trace'), _jax(103.5, 'mlir'),
    _jax(105.0, 'backend', cache='miss'),
    _jax(105.5, 'backend', phase='exe/compile', cache='off'),
    _miss(106.0, wall_s=5.0, verify_s=0.25, lower_s=0.5, trace_s=1.0,
          mlir_s=0.5, backend_s=2.0, first_run_s=0.25, modules=2),
    _jax(108.0, 'backend', cache='hit', retrieval_s=0.25),
    _miss(108.5, wall_s=1.0, verify_s=0.0, lower_s=0.125, trace_s=0.25,
          mlir_s=0.125, backend_s=0.25, first_run_s=0.125, modules=1),
    _jax(112.0, 'trace'), _jax(112.5, 'mlir'), _jax(113.0, 'backend',
                                                    cache='off'),
    _jax(120.0, 'trace', phase=None),                    # anybody's counts
    _jax(131.0, 'trace'), _jax(131.5, 'backend', cache='miss'),
    _miss(132.0, wall_s=64.0, verify_s=1.0, lower_s=1.0, trace_s=1.0,
          mlir_s=1.0, backend_s=50.0, first_run_s=1.0, modules=9),
]


def _ctx(t_start=100.0, setup_s=10.0, window_s=20.0):
    return {'man': types.SimpleNamespace(t_start=t_start),
            'setup_s': setup_s, 'window_s': window_s}


def test_hand_made_log_is_placed_before_in_and_after_the_window(
        monkeypatch):
    from paddle_tpu.observability import perf
    monkeypatch.setattr(perf, 'compile_log', lambda: list(HAND_MADE))
    assert _read(_ctx()) == {
        'exe_miss_s': 6.0, 'fluid_lower_s': 0.75 + 1.0 + 0.125 + 0.25,
        'mlir_lower_s': 0.625, 'xla_compile_s': 2.25,
        'xla_cache_misses': 1, 'jax_modules': 3, 'jax_retraces': 3}
    # a longer set-up takes the window's compiles into it
    late = _read(_ctx(setup_s=33.0))
    assert late['exe_miss_s'] == 70.0 and late['jax_modules'] == 12
    assert late['xla_cache_misses'] == 2 and late['jax_retraces'] == 0
    # nothing before a window that starts with the process
    assert _read(_ctx(setup_s=0.0, window_s=1.0)) == dict.fromkeys(NAMES, 0)
    # what an earlier run in the same process left is not this run's
    assert _read(_ctx(t_start=107.0, setup_s=3.0))['exe_miss_s'] == 1.0


def test_a_program_without_the_log_reads_nothing(monkeypatch):
    from paddle_tpu.observability import perf
    monkeypatch.delattr(perf, 'compile_log')
    assert _read(_ctx()) == dict.fromkeys(NAMES)


@pytest.mark.parametrize('cell', ['opt-1.3b-b2-s2048',
                                  'resnet50-b256-resident'])
def test_a_tiny_cell_reports_all_seven(cell, tmp_path):
    """The harness's own run, on the CPU: the readers are called where
    it reads its metrics (after the window, before the reference)."""
    import jax
    import harness
    import manifest
    seen = {}

    class Spy(manifest.Manifest):
        def read_end_to_end(self, workload, ctx):
            seen.update(_read(ctx), compile_s=ctx['compile_s'],
                        recompiles=ctx['recompiles'])
            return super().read_end_to_end(workload, ctx)

    man = Spy(time.perf_counter(), root=TINY, data=TINY)
    res = harness.run_cell(man, cell, 2 ** 31 + 35, 0.5, False,
                           jax.devices()[:1], str(tmp_path / 'run'))
    assert res['correct'] is True
    assert all(seen[n] is not None for n in NAMES)
    assert seen['jax_retraces'] == 0 == seen['recompiles']
    inside = seen['fluid_lower_s'] + seen['mlir_lower_s'] \
        + seen['xla_compile_s']
    assert 0 < inside <= seen['exe_miss_s'] <= seen['compile_s']
    # the startup program and the step, and the one-op modules beside
    assert seen['jax_modules'] >= 2
    # the tests run with jax's persistent cache off
    assert seen['xla_cache_misses'] == 0


@pytest.mark.parametrize('appended', [False, True],
                         ids=['as_committed', 'one_appended'])
def test_every_new_metric_is_in_the_manifest_with_its_reader(appended,
                                                              tmp_path):
    """PR 35's seven are found by name, wherever they stand: a later PR
    appends its metrics after them (``appended``: one more, as such a
    PR leaves the manifest)."""
    from conftest import ROOT, appended_manifest
    root = appended_manifest(tmp_path)[0] if appended else ROOT
    with open(os.path.join(root, 'BENCHMARK.json')) as f:
        doc = json.load(f)
    by_name = {m['name']: m for m in doc['per_layer']}
    for name in NAMES:
        m = by_name[name]
        assert 'workloads' not in m and m['better'] == 'lower'
        assert m['moves'] == ('step_p90_ms' if m['name'] == 'jax_retraces'
                              else 'setup_s')
        assert _spec(name)['reader'].startswith('readers_compile:')
