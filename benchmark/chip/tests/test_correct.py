"""How ``correct`` is decided, at a size a test run can hold.

1. Each model module's Fluid program agrees with its plain reference
   (tiny widths, CPU, float32 on both sides).
2. The low-precision control (the reference with fp8 operands put in
   the program's place) comes out as not correct.
3. The harness, driven past its look for a chip with the timed path
   broken underneath, reports ``correct`` false, once for each fault a
   training cell can have: a step that returns its state unchanged; half
   of the batch left out, the mean taken over the rest; (four-chip cell)
   the exchange between chips left out.
"""
import os
import time

import numpy as np
import pytest

from conftest import CHIP

TINY = os.path.join(CHIP, 'tests', 'tiny')
CELLS = ('resnet50-b256-resident', 'opt-1.3b-b2-s2048',
         'resnet50-dp4-b1024-resident')
SEED = 2 ** 31 + 11


@pytest.fixture(scope='module')
def man():
    import manifest
    return manifest.Manifest(time.perf_counter(), root=TINY, data=TINY)


def _run(man, cell, break_path=None, tmp='/tmp'):
    import jax
    import harness
    man.t_start = time.perf_counter()
    devices = jax.devices()[:man.workload(cell)['chips']]
    out = os.path.join(str(tmp), 'run')
    return harness.run_cell(man, cell, SEED, 0.5, False, devices, out,
                            break_path=break_path)


def _exceeds(result):
    return [k for k, v in result['compared'].items()
            if not v['value'] <= v['limit']]


# ---- 1. program against reference -----------------------------------------
@pytest.mark.parametrize('cell', CELLS)
def test_program_agrees_with_reference(man, cell, tmp_path):
    res = _run(man, cell, tmp=tmp_path)
    assert res['correct'] is True, res['compared']
    assert res['failed'] == 0 and res['attempted'] >= 1
    assert list(res)[-1] == 'compared'
    names = {m['name'] for m in man.doc['end_to_end']
             if 'workloads' not in m or cell in m['workloads']}
    assert set(res['metrics']) == names
    assert all(m['value'] > 0 for m in res['metrics'].values())


# ---- 2. the control --------------------------------------------------------
@pytest.mark.parametrize('cell', CELLS)
def test_low_precision_control_is_not_correct(man, cell):
    import jax
    import harness
    cfg = man.config(man.workload(cell)['config'])
    traffic = man.traffic(man.workload(cell)['traffic'])
    limits = man.limits(cell)
    model = harness.model_module(cfg)
    ref = model.Reference(cfg)
    devices = jax.devices()[:man.workload(cell)['chips']]
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


# ---- 3. the timed path broken underneath -----------------------------------
def unchanged_state(sess):
    """The step runs and returns its loss, but the state it hands back is
    the state it was given."""
    import jax.numpy as jnp
    inner = sess.dispatch

    def dispatch(feed):
        names = [n for n in sess.scope.keys()
                 if hasattr(sess.scope.raw(n), 'shape')]
        saved = {n: jnp.array(sess.scope.raw(n), copy=True) for n in names}
        out = inner(feed)
        for n, v in saved.items():
            sess.scope.set_var(n, v)
        return out
    sess.dispatch = dispatch


def half_batch(sess):
    """Half of the batch is left out; the mean is over the rest."""
    inner = sess.dispatch
    sess.dispatch = lambda feed: inner(
        {k: np.asarray(v)[:len(v) // 2] for k, v in feed.items()})


def no_exchange(sess):
    """What one chip computes when the exchange between chips is left
    out: its own rows alone decide the step (its shard stands in for
    every chip's)."""
    inner = sess.dispatch
    n = len(sess.devices)

    def dispatch(feed):
        own = {k: np.asarray(v)[:len(v) // n] for k, v in feed.items()}
        return inner({k: np.concatenate([v] * n) for k, v in own.items()})
    sess.dispatch = dispatch


FAULTS = [('resnet50-b256-resident', unchanged_state),
          ('resnet50-b256-resident', half_batch),
          ('opt-1.3b-b2-s2048', unchanged_state),
          ('opt-1.3b-b2-s2048', half_batch),
          ('resnet50-dp4-b1024-resident', unchanged_state),
          ('resnet50-dp4-b1024-resident', half_batch),
          ('resnet50-dp4-b1024-resident', no_exchange)]


@pytest.mark.parametrize('cell,fault', FAULTS,
                         ids=['%s-%s' % (c, f.__name__) for c, f in FAULTS])
def test_a_broken_timed_path_is_not_correct(man, cell, fault, tmp_path):
    res = _run(man, cell, break_path=fault, tmp=tmp_path)
    assert res['correct'] is False
    assert _exceeds(res), res['compared']
