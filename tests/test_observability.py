"""paddle_tpu.observability: metrics registry, run journal, and the
telemetry wiring across executor / trainer / serving / resilience
(OBSERVABILITY.md).

Acceptance pins (ISSUE 3):
- A Trainer run and a ModelServer soak both produce a JSONL journal
  that tools/obs_report.py renders without error.
- The registry exposes executor cache hit-rate and steps/s in both
  Prometheus text and JSON form.
- Executor.reset_cache_info() zeroes counters without dropping
  compiled programs.
"""
import json
import os
import sys
import threading

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu import profiler
from paddle_tpu.observability.metrics import MetricsRegistry

pytestmark = pytest.mark.observability

TOOLS = os.path.join(os.path.dirname(__file__), '..', 'tools')
sys.path.insert(0, TOOLS)

import obs_report  # noqa: E402  (tools/ has no package __init__)


# ---- metrics registry ----------------------------------------------------
def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter('widgets_total', 'widgets made')
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge('queue_depth', 'current depth')
    g.set(3.5)
    assert g.value == 3.5
    h = reg.histogram('latency_seconds', 'op latency')
    for v in (0.0001, 0.001, 0.01, 2.0):
        h.observe(v)
    assert h.count == 4 and abs(h.sum - 2.0111) < 1e-9
    assert h.quantile(0.5) <= h.quantile(1.0)

    # same (name, labels) interns to the same object; same name with a
    # different type is an error
    assert reg.counter('widgets_total') is c
    with pytest.raises(ValueError):
        reg.gauge('widgets_total')

    snap = reg.snapshot()
    assert snap['widgets_total']['type'] == 'counter'
    assert snap['widgets_total']['series'][0]['value'] == 5
    hs = snap['latency_seconds']['series'][0]
    assert hs['count'] == 4 and hs['buckets']['+Inf'] == 4
    json.dumps(snap)   # must be JSON-clean

    text = reg.exposition()
    assert '# TYPE widgets_total counter' in text
    assert 'widgets_total 5' in text
    assert '# TYPE latency_seconds histogram' in text
    assert 'latency_seconds_bucket{le="+Inf"} 4' in text
    assert 'latency_seconds_count 4' in text


def test_registry_labels_and_reset():
    reg = MetricsRegistry()
    a = reg.counter('span_seconds_total', 'spans', span='pad')
    b = reg.counter('span_seconds_total', 'spans', span='run')
    assert a is not b
    a.inc(2)
    b.inc(3)
    text = reg.exposition()
    assert 'span_seconds_total{span="pad"} 2' in text
    assert 'span_seconds_total{span="run"} 3' in text
    reg.reset()
    assert a.value == 0 and b.value == 0
    # registration survives reset: same objects come back
    assert reg.counter('span_seconds_total', span='pad') is a


def test_registry_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter('hits_total')
    h = reg.histogram('obs_seconds')

    def worker():
        for _ in range(1000):
            c.inc()
            h.observe(0.001)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    assert h.count == 8000


# ---- run journal ---------------------------------------------------------
def test_journal_roundtrip(tmp_path):
    path = str(tmp_path / 'run.jsonl')
    with obs.RunJournal(path, run_id='testrun') as j:
        j.record('step_end', step=0, loss=1.5, dur_s=0.01)
        with j.span('compile_end', fp='abc'):
            pass
        j.record('anomaly', kind='nan_inf', where='loss',
                 value=np.float32(7.0))   # numpy must coerce cleanly
    records, malformed = obs.read_journal(path)
    assert malformed == 0
    assert [r['ev'] for r in records] == \
        ['run_begin', 'step_end', 'compile_end', 'anomaly']
    assert all(r['run'] == 'testrun' for r in records)
    header = records[0]
    assert header['schema'] == obs.SCHEMA_VERSION and 'wall' in header
    ts = [r['t'] for r in records]
    assert ts == sorted(ts) and ts[0] < 0.01
    assert records[2]['dur_s'] >= 0.0
    assert records[3]['value'] == 7.0
    # writes after close are dropped, not raised
    j.record('step_end', step=1)
    assert len(obs.read_journal(path)[0]) == 4


def test_journal_install_emit(tmp_path):
    path = str(tmp_path / 'run.jsonl')
    assert not obs.journal_active()
    obs.emit('step_end', step=0)      # no journal: a no-op
    with obs.journal(path) as j:
        assert obs.get_journal() is j
        obs.emit('step_end', step=1)
    assert not obs.journal_active()
    records, _ = obs.read_journal(path)
    assert [r['ev'] for r in records] == ['run_begin', 'step_end']
    assert records[1]['step'] == 1


# ---- executor wiring -----------------------------------------------------
def _infer_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name='x', shape=[4], dtype='float32')
            y = fluid.layers.fc(input=x, size=3, act='relu')
    return main, startup, y


def test_executor_metrics_journal_and_reset(tmp_path):
    main, startup, y = _infer_program()
    exe = fluid.Executor(fluid.CPUPlace())
    reg = obs.default_registry()
    hits0 = reg.counter('executor_cache_hits_total').value
    misses0 = reg.counter('executor_cache_misses_total').value
    runs0 = reg.histogram('executor_run_seconds').count
    path = str(tmp_path / 'run.jsonl')
    feed = {'x': np.ones((2, 4), 'float32')}
    with fluid.scope_guard(fluid.Scope()):
        with obs.journal(path):
            exe.run(startup)
            exe.run(main, feed=feed, fetch_list=[y])
            exe.run(main, feed=feed, fetch_list=[y])
    assert exe.cache_info() == (1, 2, 2)   # hits, misses, size
    assert reg.counter('executor_cache_hits_total').value == hits0 + 1
    assert reg.counter('executor_cache_misses_total').value == \
        misses0 + 2
    assert reg.histogram('executor_run_seconds').count == runs0 + 3
    # the hit rate is the two counters' quotient, whoever wants it
    hits = reg.counter('executor_cache_hits_total').value
    misses = reg.counter('executor_cache_misses_total').value
    assert 0.0 < hits / (hits + misses) < 1.0
    # both exposition surfaces carry the cache series
    for series in ('executor_cache_hits_total',
                   'executor_cache_misses_total'):
        assert series in reg.exposition() and series in reg.snapshot()

    records, malformed = obs.read_journal(path)
    assert malformed == 0
    runs = [r for r in records if r['ev'] == 'exe_run']
    assert [r['cache'] for r in runs] == ['miss', 'miss', 'hit']
    assert all(r['dur_s'] >= 0 for r in runs)
    compiles = [r for r in records if r['ev'] == 'compile_end']
    assert len(compiles) == 2
    assert all('fp' in r and r['dur_s'] > 0 for r in compiles)

    # reset_cache_info zeroes counters, keeps compiled programs
    exe.reset_cache_info()
    assert exe.cache_info() == (0, 0, 2)
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)   # same program+shapes -> pure hit
    assert exe.cache_info() == (1, 0, 2)


# ---- trainer wiring ------------------------------------------------------
def _reader(n=48, batch=8, seed=0):
    rng = np.random.RandomState(seed)
    xs = rng.randn(n, 4).astype('float32')
    ys = (xs @ np.array([1.0, -2.0, 3.0, 0.5], np.float32))[:, None]

    def r():
        for i in range(0, n, batch):
            yield list(zip(xs[i:i + batch], ys[i:i + batch]))
    return r


def _train_func():
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    y = fluid.layers.data(name='y', shape=[1], dtype='float32')
    pred = fluid.layers.fc(input=x, size=1, act=None)
    return fluid.layers.mean(
        fluid.layers.square_error_cost(input=pred, label=y))


def test_trainer_journal_and_metrics(tmp_path):
    path = str(tmp_path / 'train.jsonl')
    trainer = fluid.Trainer(train_func=_train_func,
                            optimizer=fluid.optimizer.SGD(
                                learning_rate=0.01),
                            place=fluid.CPUPlace())
    with obs.journal(path):
        trainer.train(num_epochs=2, event_handler=lambda ev: None,
                      reader=_reader(), feed_order=['x', 'y'])

    records, malformed = obs.read_journal(path)
    assert malformed == 0
    steps = [r for r in records if r['ev'] == 'step_end']
    assert len(steps) == 12                      # 6 batches x 2 epochs
    for r in steps:
        assert r['examples'] == 8 and r['dur_s'] > 0
        assert np.isfinite(r['loss'])
        assert r['examples_per_s'] > 0
    assert [r['ev'] for r in records if r['ev'].startswith('epoch')] \
        == ['epoch_begin', 'epoch_end'] * 2
    assert sum(1 for r in records if r['ev'] == 'train_begin') == 1

    reg = obs.default_registry()
    assert reg.gauge('trainer_steps_per_second').value > 0
    assert reg.gauge('trainer_time_to_first_step_seconds').value > 0
    assert reg.counter('trainer_steps_total').value >= 12
    text = reg.exposition()
    assert 'trainer_steps_per_second' in text
    snap = reg.snapshot()
    assert snap['trainer_steps_per_second']['series'][0]['value'] > 0

    # the journal renders and passes the training smoke gate
    summary = obs_report.summarize(records, malformed)
    assert summary['steps']['count'] == 12
    assert np.isfinite(summary['steps']['last_loss'])
    report = obs_report.render(summary)
    assert 'training: 12 steps' in report
    assert obs_report.check_journal(path, require='step') == []


def test_trainer_checkpoint_and_anomaly_journal(tmp_path):
    from paddle_tpu.resilience import AnomalyGuard, CheckpointConfig

    path = str(tmp_path / 'train.jsonl')
    ckpt_dir = str(tmp_path / 'ckpt')

    def poisoned_reader():
        base = _reader(n=24, batch=8)
        for i, batch in enumerate(base()):
            if i == 1:
                batch = [(np.full(4, np.nan, 'float32'), row[1])
                         for row in batch]
            yield batch

    trainer = fluid.Trainer(train_func=_train_func,
                            optimizer=fluid.optimizer.SGD(
                                learning_rate=0.01),
                            place=fluid.CPUPlace())
    with obs.journal(path):
        trainer.train(
            num_epochs=1, event_handler=lambda ev: None,
            reader=lambda: poisoned_reader(), feed_order=['x', 'y'],
            checkpoint_config=CheckpointConfig(
                ckpt_dir, step_interval=2, save_interval_secs=0),
            anomaly_guard=AnomalyGuard(policy='skip_batch'))

    records, _ = obs.read_journal(path)
    evs = [r['ev'] for r in records]
    assert 'anomaly' in evs
    anomaly = next(r for r in records if r['ev'] == 'anomaly')
    assert anomaly['kind'] == 'nan_inf' and \
        anomaly['policy'] == 'skip_batch'
    saves = [r for r in records if r['ev'] == 'checkpoint_save']
    assert saves and all('serial' in r and r['dur_s'] > 0 for r in saves)
    skipped = [r for r in records
               if r['ev'] == 'step_end' and r.get('skipped')]
    assert len(skipped) == 1


# ---- serving wiring ------------------------------------------------------
def _save_model(tmp_path, name='m0', seed=7):
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name='x', shape=[6], dtype='float32')
            y = fluid.layers.fc(input=x, size=3, act=None)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    d = str(tmp_path / name)
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(d, ['x'], [y], exe,
                                      main_program=main)
    return d


def test_serving_journal_and_registry(tmp_path):
    from paddle_tpu.serving import ModelServer

    d = _save_model(tmp_path)
    path = str(tmp_path / 'serve.jsonl')
    reg = obs.default_registry()
    sub0 = reg.counter('serving_requests_submitted_total').value
    rng = np.random.RandomState(0)
    with obs.journal(path):
        with ModelServer(place=fluid.CPUPlace(), max_batch_size=8,
                         batch_timeout=0.001) as srv:
            srv.load_model('m0', d)
            srv.warmup()
            for n in (1, 3, 5, 8):
                out, = srv.infer('m0', {'x': rng.randn(n, 6).astype(
                    'float32')})
                assert out.shape == (n, 3)
    records, malformed = obs.read_journal(path)
    assert malformed == 0
    batches = [r for r in records if r['ev'] == 'serving_batch']
    assert batches
    for r in batches:
        assert r['bucket'] >= r['rows'] and r['dur_s'] > 0
    assert any(r['ev'] == 'serving_admit' for r in records)
    assert reg.counter('serving_requests_submitted_total').value > sub0
    assert 'serving_request_seconds' in reg.exposition()
    # serving_span histograms (profiler.serving_span) land too
    assert reg.get('serving_span_seconds',
                   span='serving/batch_run') is not None
    assert obs_report.check_journal(path, require='serving') == []
    report = obs_report.render(obs_report.summarize(records, malformed))
    assert 'serving:' in report


# ---- obs_report gate -----------------------------------------------------
def test_obs_report_smoke_failures(tmp_path):
    empty = tmp_path / 'empty.jsonl'
    empty.write_text('')
    assert any('no records' in p
               for p in obs_report.check_journal(str(empty)))

    bad = tmp_path / 'bad.jsonl'
    bad.write_text('{"ev":"run_begin","run":"x","t":0.0}\n'
                   'this is not json\n')
    problems = obs_report.check_journal(str(bad))
    assert any('malformed' in p for p in problems)
    assert any('zero step_end' in p for p in problems)

    ok = tmp_path / 'ok.jsonl'
    ok.write_text('{"ev":"run_begin","run":"x","t":0.0,"schema":1}\n'
                  '{"ev":"step_end","run":"x","t":0.1,"dur_s":0.1,'
                  '"loss":1.0}\n')
    assert obs_report.check_journal(str(ok)) == []
    assert obs_report.check_journal(str(ok), require='any') == []
    assert obs_report.check_journal(str(ok), require='serving') != []
    # CLI entry points agree with the library calls
    assert obs_report.main([str(ok), '--smoke']) == 0
    assert obs_report.main([str(bad), '--smoke']) == 1
    assert obs_report.main([str(ok)]) == 0


# ---- profiler metadata ---------------------------------------------------
def test_save_profile_is_self_describing(tmp_path):
    main, startup, y = _infer_program()
    exe = fluid.Executor(fluid.CPUPlace())
    profiler.reset_profiler()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        profiler.start_profiler('CPU')
        exe.run(main, feed={'x': np.ones((2, 4), 'float32')},
                fetch_list=[y])
        profiler.stop_profiler()
        with profiler.serving_span('serving/unit_test_span'):
            pass
    path = str(tmp_path / 'prof.json')
    profiler.save_profile(path)
    data = json.load(open(path))
    assert data['events']
    assert 'serving/unit_test_span' in data['serving']
    meta = data['meta']
    assert meta['run_id'] and meta['saved_at'] > 0
    assert meta['started_at_wall'] <= meta['saved_at']
    # an installed journal stamps ITS run id into the profile
    jpath = str(tmp_path / 'run.jsonl')
    with obs.journal(jpath, run_id='profrun'):
        profiler.save_profile(path)
    assert json.load(open(path))['meta']['run_id'] == 'profrun'
    profiler.reset_profiler()
    assert json.loads(
        open(profiler.save_profile(path)).read())['events'] == []


def test_registry_remove_series():
    """ISSUE 16 satellite: retire/rebuild paths drop per-entity label
    series so a long-lived fleet's registry doesn't grow monotonically
    with every replica id ever used."""
    reg = MetricsRegistry()
    reg.gauge('fleet_replica_state', 'state', replica='0').set(1)
    reg.gauge('fleet_replica_state', 'state', replica='1').set(1)
    reg.counter('other_total', 'x').inc()
    assert reg.remove('fleet_replica_state', replica='1')
    assert reg.get('fleet_replica_state', replica='1') is None
    # the sibling series and unrelated metrics survive
    assert reg.get('fleet_replica_state', replica='0').value == 1
    assert reg.get('other_total').value == 1
    # removing a missing series is a no-op, not an error
    assert not reg.remove('fleet_replica_state', replica='99')
    # re-registering after removal works (fresh series)
    g = reg.gauge('fleet_replica_state', 'state', replica='1')
    assert g.value == 0


def test_registry_remove_matching():
    reg = MetricsRegistry()
    for rid in range(3):
        reg.counter('router_routed_total', 'n', replica=str(rid),
                    model='m').inc(rid + 1)
    reg.counter('router_routed_total', 'n', replica='0',
                model='other').inc()
    assert reg.remove_matching('router_routed_total',
                               replica='0') == 2
    assert reg.get('router_routed_total', replica='0',
                   model='m') is None
    assert reg.get('router_routed_total', replica='1',
                   model='m').value == 2
    assert reg.remove_matching('router_routed_total',
                               replica='nope') == 0
