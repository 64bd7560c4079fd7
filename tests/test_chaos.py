"""Serving SLO guardrails under deterministic chaos (ISSUE 4).

Acceptance pins:
- The per-model circuit breaker opens on consecutive batch failures,
  sheds with typed CircuitOpen at admission, half-opens after the
  cooldown, and re-closes on probe successes — visible through
  ``ModelServer.health()`` AND the ``serving_breaker_state`` metric.
- The watchdog fails a hung batch's futures within its stage deadline,
  opens the breaker, and the worker survives to serve again.
- ``close(timeout=)`` returns within the timeout against a wedged
  worker: in-flight + queued futures fail with typed errors, the
  thread is abandoned.
- ``drain`` completes queued work then unloads; ``swap_model`` flips a
  replacement in without dropping the queue and a bad deploy rolls
  back.
- Post-recovery outputs are bit-identical to a fault-free run.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observability
from paddle_tpu.resilience import (FaultPlan, fault_plan, FaultInjected,
                                   RetryError, SITE_SERVING_LOAD,
                                   SITE_SERVING_RUN)
from paddle_tpu.serving import (CircuitBreaker, CircuitOpen, ModelServer,
                                ModelNotFound, ServerClosed,
                                WatchdogTimeout)
from paddle_tpu.serving.breaker import CLOSED, HALF_OPEN, OPEN

pytestmark = [pytest.mark.serving, pytest.mark.chaos]

IN_DIM, OUT_DIM = 6, 3


def _save_model(tmp_path, name='m0', seed=7):
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name='x', shape=[IN_DIM],
                                  dtype='float32')
            h = fluid.layers.fc(input=x, size=8, act='relu')
            y = fluid.layers.fc(input=h, size=OUT_DIM, act=None)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        d = str(tmp_path / name)
        fluid.io.save_inference_model(d, ['x'], [y], exe,
                                      main_program=main)
    return d


def _expected_fn(model_dir):
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    prog, _, fetch_vars = fluid.io.load_inference_model(
        model_dir, exe, scope=scope)
    lock = threading.Lock()

    def run(x):
        with lock:
            out, = exe.run(prog, feed={'x': x}, fetch_list=fetch_vars,
                           scope=scope)
        return out
    return run


def _assert_same_rows(got, want):
    """Rows that the server ran coalesced with other requests' rows in
    one bucket-sized program, against the direct run of those rows
    alone: two executables at two batch sizes, whose float32 dots XLA
    may sum in another order (one rounding step was observed, 1.8e-7
    relative). A request that runs alone in a bucket of its own size
    is the reference's program again, and stays under array_equal."""
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-7)


def _submit_when_admitted(srv, name, feeds, give_up_after=10.0):
    """Retry CircuitOpen at admission until the breaker admits (the
    client-side backoff loop), bounded so a stuck breaker fails the
    test instead of hanging it."""
    t_end = time.monotonic() + give_up_after
    sheds = 0
    while True:
        try:
            return srv.submit(name, feeds), sheds
        except CircuitOpen as e:
            sheds += 1
            if time.monotonic() > t_end:
                raise AssertionError(
                    'breaker never re-admitted: %r' % e)
            time.sleep(min(0.02, e.retry_after or 0.02))


# ---- breaker unit (fake clock: fully deterministic) ----------------------
def test_breaker_state_machine():
    t = {'now': 0.0}
    br = CircuitBreaker('m', failure_threshold=3, window=8,
                        failure_rate=0.9, cooldown=1.0,
                        probe_successes=2, clock=lambda: t['now'])
    assert br.state == CLOSED
    assert br.admit() is False               # closed: not a probe
    br.record_failure()
    br.record_failure()
    assert br.state == CLOSED                # under threshold
    br.record_failure()
    assert br.state == OPEN                  # 3 consecutive
    with pytest.raises(CircuitOpen) as e:
        br.admit()
    assert e.value.retry_after == pytest.approx(1.0)
    t['now'] = 0.5
    assert br.state == OPEN                  # cooldown not elapsed
    t['now'] = 1.0
    assert br.state == HALF_OPEN             # probing window
    assert br.admit() is True                # probe slot taken
    with pytest.raises(CircuitOpen):
        br.admit()                           # max_probes=1
    br.record_failure()                      # probe failed
    assert br.state == OPEN                  # re-opened, cooldown reset
    t['now'] = 1.5
    assert br.state == OPEN
    t['now'] = 2.1
    assert br.state == HALF_OPEN
    assert br.admit() is True
    br.record_success()
    assert br.state == HALF_OPEN             # 1 of 2 probe successes
    assert br.admit() is True
    br.record_success()
    assert br.state == CLOSED                # re-closed
    assert [to for to, _ in br.transitions] == \
        [OPEN, HALF_OPEN, OPEN, HALF_OPEN, CLOSED]


def test_breaker_windowed_failure_rate():
    """Steady partial failure that never hits the consecutive
    threshold still opens via the sliding-window rate."""
    br = CircuitBreaker('m', failure_threshold=100, window=4,
                        failure_rate=0.5, clock=lambda: 0.0)
    br.record_failure()
    br.record_success()
    br.record_failure()
    br.record_success()                      # window [F,S,F,S] full
    assert br.state == CLOSED                # successes never open
    br.record_failure()                      # window [S,F,S,F] rate .5
    assert br.state == OPEN
    assert br.transitions[0][1].startswith('windowed failure rate')


def test_breaker_release_probe_and_reset():
    t = {'now': 0.0}
    br = CircuitBreaker('m', failure_threshold=1, cooldown=1.0,
                        clock=lambda: t['now'])
    br.record_failure()
    t['now'] = 1.0
    assert br.admit() is True
    br.release_probe()                       # enqueue failed: slot back
    assert br.admit() is True
    br.reset('swap')
    assert br.state == CLOSED
    assert br.snapshot()['consecutive_failures'] == 0


# ---- breaker in the server (deterministic fault plan) --------------------
def test_server_breaker_opens_probes_and_recloses(tmp_path):
    d = _save_model(tmp_path)
    expected = _expected_fn(d)
    rng = np.random.RandomState(11)
    inputs = [rng.randn(2, IN_DIM).astype('float32') for _ in range(8)]
    reg = observability.default_registry()
    with ModelServer(place=fluid.CPUPlace(), max_batch_size=4,
                     retry_attempts=1, retry_backoff=0.0,
                     breaker_config=dict(failure_threshold=2,
                                         cooldown=0.1,
                                         probe_successes=2,
                                         window=64)) as srv:
        srv.load_model('m', d)
        srv.warmup('m')
        assert srv.health()['models']['m']['state'] == 'ready'
        plan = FaultPlan().inject(SITE_SERVING_RUN, times=2)
        with fault_plan(plan):
            # two consecutive failed batches -> breaker opens
            for i in (0, 1):
                req = srv.submit('m', {'x': inputs[i]})
                with pytest.raises(RetryError):
                    req.result(timeout=30.0)
            assert srv.breaker('m').state == OPEN
            assert srv.health()['models']['m']['state'] == 'open'
            g = reg.get('serving_breaker_state', model='m')
            assert g is not None and g.value == 2
            with pytest.raises(CircuitOpen):   # shed at admission
                srv.submit('m', {'x': inputs[2]})
            assert srv.stats_dict()['requests']['breaker_rejected'] >= 1
            # cooldown -> half-open probes -> re-close; faults are
            # exhausted so both probes succeed
            outs = []
            for i in (2, 3):
                req, _ = _submit_when_admitted(srv, 'm',
                                               {'x': inputs[i]})
                outs.append(req.result(timeout=30.0))
            assert srv.breaker('m').state == CLOSED
            assert srv.health()['models']['m']['state'] == 'ready'
            assert g.value == 0
            # post-recovery outputs bit-identical to the fault-free path
            for i, (out,) in zip((2, 3), outs):
                assert np.array_equal(np.asarray(out),
                                      np.asarray(expected(inputs[i])))
        trans = [to for to, _ in srv.breaker('m').transitions]
        assert trans == [OPEN, HALF_OPEN, CLOSED]
        assert plan.faults[SITE_SERVING_RUN] == 2
        st = srv.stats_dict()
        assert st['guardrails']['breaker_transitions'] == {
            'open': 1, 'half_open': 1, 'closed': 1}


# ---- watchdog ------------------------------------------------------------
def test_watchdog_fails_hung_batch_and_worker_survives(tmp_path):
    d = _save_model(tmp_path)
    expected = _expected_fn(d)
    x = np.ones((2, IN_DIM), 'float32')
    reg = observability.default_registry()
    trips_before = getattr(
        reg.get('serving_watchdog_trips_total', model='m'), 'value', 0)
    with ModelServer(place=fluid.CPUPlace(), max_batch_size=4,
                     retry_attempts=1, retry_backoff=0.0,
                     watchdog_poll=0.02,
                     breaker_config=dict(cooldown=0.1,
                                         probe_successes=1)) as srv:
        srv.load_model('m', d)
        srv.warmup('m')       # compiles under the default (lax) deadline
        srv.stage_timeouts[SITE_SERVING_RUN] = 0.15
        plan = FaultPlan().inject(SITE_SERVING_RUN, error=None,
                                  delay=0.6, at=[0])
        with fault_plan(plan):
            t0 = time.monotonic()
            req = srv.submit('m', {'x': x})
            with pytest.raises(WatchdogTimeout):
                req.result(timeout=10.0)
            # failed by the watchdog near the 0.15s deadline, NOT after
            # the full 0.6s hang
            assert time.monotonic() - t0 < 0.5
            assert srv.breaker('m').state == OPEN
            health = srv.health()['models']['m']
            assert health['state'] == 'open'
            assert health['watchdog_trips'] == 1
            c = reg.get('serving_watchdog_trips_total', model='m')
            assert c is not None and c.value == trips_before + 1
            # let the hang finish so the worker unwedges, then prove it
            # survived: the next admitted request completes exactly
            time.sleep(0.55)
            req2, _ = _submit_when_admitted(srv, 'm', {'x': x})
            out, = req2.result(timeout=30.0)
            assert np.array_equal(np.asarray(out),
                                  np.asarray(expected(x)))
            assert srv.health()['models']['m']['worker_alive']
        assert srv.stats_dict()['guardrails']['watchdog_trips'] == 1


# ---- close escalation ----------------------------------------------------
def test_close_timeout_returns_against_wedged_worker(tmp_path):
    d = _save_model(tmp_path)
    x = np.ones((1, IN_DIM), 'float32')
    srv = ModelServer(place=fluid.CPUPlace(), max_batch_size=4,
                      retry_attempts=1, retry_backoff=0.0,
                      stage_timeouts={SITE_SERVING_RUN: None},
                      watchdog_poll=0.02)
    srv.load_model('m', d)
    srv.warmup('m')
    plan = FaultPlan().inject(SITE_SERVING_RUN, error=None,
                              delay=1.2, at=[0])
    with fault_plan(plan):
        wedged = srv.submit('m', {'x': x})        # worker hangs 1.2s
        time.sleep(0.1)                           # worker picked it up
        queued = srv.submit('m', {'x': x})        # stuck behind it
        t0 = time.monotonic()
        srv.close(timeout=0.3)
        wall = time.monotonic() - t0
        assert wall < 1.0, 'close() hung %.2fs against a wedged worker' \
            % wall
        # escalation: both futures fail typed, nothing hangs
        with pytest.raises(ServerClosed):
            wedged.result(timeout=1.0)
        with pytest.raises(ServerClosed):
            queued.result(timeout=1.0)
        assert srv.stats_dict()['requests']['cancelled'] >= 1
        assert srv.health()['status'] == 'closed'
        srv.close()                               # idempotent
        # let the abandoned worker finish its injected hang inside the
        # plan's dynamic extent before the next test reuses the process
        time.sleep(1.0)


def test_close_without_timeout_still_graceful(tmp_path):
    d = _save_model(tmp_path)
    srv = ModelServer(place=fluid.CPUPlace(), max_batch_size=4)
    srv.load_model('m', d)
    srv.pause()
    reqs = [srv.submit('m', {'x': np.ones((1, IN_DIM), 'float32')})
            for _ in range(3)]
    srv.resume()
    srv.close()                     # default timeout: drains cleanly
    for r in reqs:
        out, = r.result(timeout=1.0)
        assert out.shape == (1, OUT_DIM)
    with pytest.raises(ServerClosed):
        srv.submit('m', {'x': np.ones((1, IN_DIM), 'float32')})


# ---- drain + hot swap ----------------------------------------------------
def test_drain_completes_queue_then_unloads(tmp_path):
    d = _save_model(tmp_path)
    expected = _expected_fn(d)
    rng = np.random.RandomState(12)
    with ModelServer(place=fluid.CPUPlace(), max_batch_size=8) as srv:
        srv.load_model('m', d)
        srv.warmup('m')
        srv.pause('m')
        xs = [rng.randn(2, IN_DIM).astype('float32') for _ in range(3)]
        reqs = [srv.submit('m', {'x': x}) for x in xs]
        # drain resumes the paused queue, completes it, unloads
        model = srv.drain('m')
        assert model is not None and model.name == 'm'
        for x, r in zip(xs, reqs):
            out, = r.result(timeout=1.0)   # already completed
            # three 2-row requests ran as one 8-row batch
            _assert_same_rows(out, expected(x))
        assert 'm' not in srv.models()
        assert 'm' not in srv.health()['models']
        with pytest.raises(ModelNotFound):
            srv.infer('m', {'x': xs[0]})


def test_health_reports_draining_state(tmp_path):
    d = _save_model(tmp_path)
    with ModelServer(place=fluid.CPUPlace(), max_batch_size=4) as srv:
        srv.load_model('m', d)
        srv._draining.add('m')      # freeze the transient mid-drain view
        assert srv.health()['models']['m']['state'] == 'draining'
        with pytest.raises(ServerClosed):
            srv.submit('m', {'x': np.ones((1, IN_DIM), 'float32')})
        srv._draining.discard('m')
        assert srv.health()['models']['m']['state'] == 'ready'


def test_swap_model_preserves_queue_and_rolls_back(tmp_path):
    da = _save_model(tmp_path, 'a', seed=1)
    db = _save_model(tmp_path, 'b', seed=2)
    ref_a, ref_b = _expected_fn(da), _expected_fn(db)
    rng = np.random.RandomState(13)
    x0 = rng.randn(2, IN_DIM).astype('float32')
    with ModelServer(place=fluid.CPUPlace(), max_batch_size=8) as srv:
        srv.load_model('m', da)
        srv.warmup('m')
        out, = srv.infer('m', {'x': x0})
        assert np.array_equal(np.asarray(out), np.asarray(ref_a(x0)))
        # queue requests, swap underneath them: they land on the NEW
        # model — nothing dropped
        srv.pause('m')
        xs = [rng.randn(2, IN_DIM).astype('float32') for _ in range(2)]
        reqs = [srv.submit('m', {'x': x}) for x in xs]
        srv.swap_model('m', db)
        srv.resume('m')
        for x, r in zip(xs, reqs):
            out, = r.result(timeout=30.0)
            # two 2-row requests ran as one 4-row batch
            _assert_same_rows(out, ref_b(x))
        # bad deploy: injected load fault -> swap raises, old (= b)
        # keeps serving, queue intact
        plan = FaultPlan().inject(SITE_SERVING_LOAD, times=1)
        with fault_plan(plan):
            with pytest.raises(FaultInjected):
                srv.swap_model('m', da)
        out, = srv.infer('m', {'x': x0})
        assert np.array_equal(np.asarray(out), np.asarray(ref_b(x0)))
        # unloadable artifact path rolls back the same way
        with pytest.raises(Exception):
            srv.swap_model('m', str(tmp_path / 'nope'))
        out, = srv.infer('m', {'x': x0})
        assert np.array_equal(np.asarray(out), np.asarray(ref_b(x0)))


# ---- the chaos bench gate ------------------------------------------------
def test_chaos_bench_smoke(tmp_path):
    """tools/chaos_bench.py --smoke passes in-process (spawning a fresh
    interpreter would re-import jax)."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        'chaos_bench', os.path.join(os.path.dirname(__file__), '..',
                                    'tools', 'chaos_bench.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main(['--smoke', '--json', str(tmp_path / 'chaos.json')])
    assert rc == 0
