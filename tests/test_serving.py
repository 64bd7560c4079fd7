"""paddle_tpu.serving: shape bucketing, multi-model registry, dynamic
micro-batching, admission control, warmup, stats (SERVING.md).

Acceptance pins (ISSUE 2):
- >=2 distinct client batch sizes per bucket -> exactly 1 compile per
  bucket, proven via Executor.cache_info().
- An 8-thread soak through ModelServer returns the outputs of serial
  Executor.run (within a float32 rounding step: _assert_same_rows) with
  zero dropped requests under capacity.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import serving
from paddle_tpu.serving import (BucketPolicy, DeadlineExceeded,
                                ModelNotFound, ModelServer,
                                ServerOverloaded, next_pow2, run_bucketed)

pytestmark = pytest.mark.serving

IN_DIM, OUT_DIM = 6, 3


def _build_trained_model(seed=7):
    """A tiny row-wise MLP with deterministic params; returns
    (main_program, scope, predict_var)."""
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():   # fc_0/fc_1 names, every call
            x = fluid.layers.data(name='x', shape=[IN_DIM],
                                  dtype='float32')
            h = fluid.layers.fc(input=x, size=8, act='relu')
            y = fluid.layers.fc(input=h, size=OUT_DIM, act=None)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    return main, scope, y


def _save_model(tmp_path, name='m0', seed=7):
    main, scope, y = _build_trained_model(seed=seed)
    exe = fluid.Executor(fluid.CPUPlace())
    d = str(tmp_path / name)
    with fluid.scope_guard(scope):
        fluid.io.save_inference_model(d, ['x'], [y], exe,
                                      main_program=main)
    return d


def _expected_fn(model_dir):
    """A serial, single-request reference path over the same artifact:
    fresh Executor + fresh scope (the server's own scope is busy being
    donated by its worker). The lock keeps it literally serial when
    client threads consult it concurrently."""
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    prog, feed_names, fetch_vars = fluid.io.load_inference_model(
        model_dir, exe, scope=scope)
    lock = threading.Lock()

    def run(x):
        with lock:
            out, = exe.run(prog, feed={'x': x}, fetch_list=fetch_vars,
                           scope=scope)
        return out
    return run


def _rand_batch(rng, n):
    return rng.randn(n, IN_DIM).astype('float32')


def _assert_same_rows(got, want, what=''):
    """Rows served from a padded bucket, or coalesced with other
    requests' rows, against the direct run of those rows alone. These
    are two executables, compiled for two batch sizes, and XLA's CPU
    dot may block and sum a [n, k] x [k, m] product in another order at
    another n: float32 results one rounding step apart were observed
    (1.8e-7 relative), so the comparison allows that and no more. Where
    both sides run the same executable the tests keep array_equal."""
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-7, err_msg=what)


# ---- bucketing policy ----------------------------------------------------
def test_next_pow2():
    assert [next_pow2(n) for n in (1, 2, 3, 4, 5, 8, 9, 17)] == \
        [1, 2, 4, 4, 8, 8, 16, 32]
    with pytest.raises(ValueError):
        next_pow2(0)


def test_bucket_policy():
    p = BucketPolicy(min_bucket=4, max_bucket=32)
    assert p.bucket_for(1) == 4          # floor clamp
    assert p.bucket_for(5) == 8
    assert p.bucket_for(32) == 32
    assert p.buckets() == [4, 8, 16, 32]
    assert p.buckets(upto=9) == [4, 8, 16]
    with pytest.raises(ValueError):
        p.bucket_for(33)                 # above the ceiling
    with pytest.raises(ValueError):
        BucketPolicy(pad_mode='reflect')


# ---- run_bucketed exactness + compile accounting -------------------------
def test_run_bucketed_exact_and_one_compile_per_bucket(tmp_path):
    """Acceptance: two distinct batch sizes per bucket, one compile per
    bucket (cache_info), the direct run's rows. The bucketed run is
    the bucket-sized program on padded rows, the direct run the
    n-sized one: _assert_same_rows."""
    d = _save_model(tmp_path)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    prog, _, fetch_vars = fluid.io.load_inference_model(d, exe,
                                                        scope=scope)
    expected = _expected_fn(d)
    policy = BucketPolicy(max_bucket=16)
    rng = np.random.RandomState(0)
    # bucket 4 <- {3, 4}; bucket 8 <- {5, 7}: 4 sizes, 2 buckets
    for n in (3, 4, 5, 7):
        x = _rand_batch(rng, n)
        out, = run_bucketed(exe, prog, {'x': x}, fetch_vars, scope=scope,
                            policy=policy)
        assert out.shape == (n, OUT_DIM)
        _assert_same_rows(
            out, expected(x),
            'bucketed result differs from direct run for n=%d' % n)
    info = exe.cache_info()
    assert info.misses == 2, info       # exactly one compile per bucket
    assert info.size == 2, info
    assert info.hits == 2, info         # the second size of each bucket


def test_run_bucketed_fallback_non_row_aligned():
    """A fetch reduced over the batch is polluted by pad rows: the
    helper must detect it, fall back to the exact run, and never pad
    that program again."""
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 3
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[IN_DIM], dtype='float32')
        y = fluid.layers.fc(input=x, size=1)
        m = fluid.layers.reduce_mean(y)       # batch-reduced fetch
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    rng = np.random.RandomState(1)
    x3 = _rand_batch(rng, 3)
    direct, = exe.run(main, feed={'x': x3}, fetch_list=[m], scope=scope)
    bucketed, = run_bucketed(exe, main, {'x': x3}, [m], scope=scope,
                             policy=BucketPolicy(max_bucket=16))
    assert np.array_equal(np.asarray(direct), np.asarray(bucketed))
    # second call goes direct immediately (program remembered as unsafe)
    misses_before = exe.cache_info().misses
    out, = run_bucketed(exe, main, {'x': _rand_batch(rng, 3)}, [m],
                        scope=scope, policy=BucketPolicy(max_bucket=16))
    assert exe.cache_info().misses == misses_before  # shape 3 cached


def test_inferencer_buckets_recompiles(tmp_path):
    """Inferencer.infer rides the bucketing helper: sweeping batch
    sizes 1..8 costs log2 compiles, and gives the rows of the direct
    run at the request's own size (another program wherever the size
    is not its bucket's: _assert_same_rows)."""
    main, scope, y = _build_trained_model(seed=11)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        fluid.io.save_params(exe, str(tmp_path / 'params'),
                             main_program=main)

    def infer_func():
        x = fluid.layers.data(name='x', shape=[IN_DIM], dtype='float32')
        h = fluid.layers.fc(input=x, size=8, act='relu')
        return fluid.layers.fc(input=h, size=OUT_DIM, act=None)

    inf = fluid.Inferencer(infer_func, str(tmp_path / 'params'),
                           place=fluid.CPUPlace())
    rng = np.random.RandomState(2)
    sizes = [1, 2, 3, 4, 5, 6, 7, 8]
    for n in sizes:
        x = _rand_batch(rng, n)
        out, = inf.infer({'x': x})
        assert out.shape == (n, OUT_DIM)
        direct, = inf.exe.run(inf.inference_program, feed={'x': x},
                              fetch_list=[inf.predict_var],
                              scope=inf.scope)
        _assert_same_rows(out, direct)
    # buckets 1,2,4,8 -> 4 compiles for 8 distinct client batch sizes
    # (+ the direct-run checks add no shapes beyond those sizes' buckets)
    info = inf.exe.cache_info()
    bucketed_shapes = {1, 2, 4, 8}
    direct_shapes = set(sizes)
    assert info.size == len(bucketed_shapes | direct_shapes)

    unbucketed = fluid.Inferencer(infer_func, str(tmp_path / 'params'),
                                  place=fluid.CPUPlace(),
                                  bucket_batches=False)
    for n in (3, 5):
        out, = unbucketed.infer({'x': _rand_batch(rng, n)})
        assert out.shape == (n, OUT_DIM)
    assert unbucketed.exe.cache_info().misses == 2   # one per raw size


# ---- ModelServer ---------------------------------------------------------
def test_server_basic_and_one_compile_per_bucket(tmp_path):
    """The server runs a request in its bucket's program, the
    reference at the request's own size: _assert_same_rows."""
    d = _save_model(tmp_path)
    expected = _expected_fn(d)
    rng = np.random.RandomState(3)
    with ModelServer(place=fluid.CPUPlace(), max_batch_size=16) as srv:
        srv.load_model('m', d)
        for n in (3, 4, 5, 7, 2, 1):
            x = _rand_batch(rng, n)
            out, = srv.infer('m', {'x': x})
            _assert_same_rows(out, expected(x))
        info = srv.cache_info()
        # buckets touched: 4 (<-3,4), 8 (<-5,7), 2 (<-2), 1 (<-1)
        assert info.misses == 4, info
        assert info.size == 4, info
        d_stats = srv.stats_dict()
        assert d_stats['requests']['completed'] == 6
        assert d_stats['requests']['shed'] == 0
        assert d_stats['compile_cache']['misses'] == 4


def test_server_soak_8_threads_bit_identical(tmp_path):
    """Acceptance: 8 client threads, mixed batch sizes, zero drops,
    outputs those of the serial Executor.run reference. The server
    coalesces concurrent requests and pads them to a bucket, the
    reference runs each alone at its own size, so the two are never the
    same executable and not bit-comparable: _assert_same_rows."""
    d = _save_model(tmp_path)
    expected = _expected_fn(d)
    n_threads, per_thread = 8, 12
    errors, lock = [], threading.Lock()
    with ModelServer(place=fluid.CPUPlace(), max_batch_size=16,
                     max_queue_depth=n_threads * per_thread,
                     batch_timeout=0.002) as srv:
        srv.load_model('m', d)
        srv.warmup('m')

        def client(tid):
            rng = np.random.RandomState(100 + tid)
            try:
                for i in range(per_thread):
                    n = int(rng.randint(1, 17))
                    x = _rand_batch(rng, n)
                    out, = srv.infer('m', {'x': x}, timeout=60.0)
                    _assert_same_rows(
                        out, expected(x),
                        'thread %d req %d (n=%d)' % (tid, i, n))
            except Exception as e:      # noqa: BLE001 — collected below
                with lock:
                    errors.append(e)

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        st = srv.stats_dict()
        assert st['requests']['completed'] == n_threads * per_thread
        assert st['requests']['shed'] == 0
        assert st['requests']['expired'] == 0
        assert st['requests']['failed'] == 0
        # warmup compiled every bucket: traffic added zero misses
        assert st['compile_cache']['misses'] == \
            len(BucketPolicy(max_bucket=16).buckets())


def test_server_multi_model_concurrent(tmp_path):
    """M models x N threads: per-model scopes stay isolated (different
    seeds -> different params -> different outputs), each the rows of
    its own model's direct run (bucket-sized against request-sized
    programs: _assert_same_rows)."""
    dirs = {name: _save_model(tmp_path, name=name, seed=seed)
            for name, seed in (('a', 1), ('b', 2))}
    refs = {name: _expected_fn(d) for name, d in dirs.items()}
    errors, lock = [], threading.Lock()
    with ModelServer(place=fluid.CPUPlace(), max_batch_size=8) as srv:
        for name, d in dirs.items():
            srv.load_model(name, d)

        def client(tid):
            rng = np.random.RandomState(200 + tid)
            name = 'a' if tid % 2 == 0 else 'b'
            try:
                for _ in range(6):
                    x = _rand_batch(rng, int(rng.randint(1, 9)))
                    out, = srv.infer(name, {'x': x}, timeout=60.0)
                    _assert_same_rows(out, refs[name](x), name)
            except Exception as e:      # noqa: BLE001
                with lock:
                    errors.append(e)

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        # sanity: the two models really differ (else isolation is vacuous)
        x = _rand_batch(np.random.RandomState(0), 4)
        assert not np.array_equal(refs['a'](x), refs['b'](x))
    assert srv.models() == ['a', 'b']


def test_server_micro_batches_coalesce(tmp_path):
    """Requests issued while the server is paused coalesce into shared
    batches on resume: fewer batches than requests, occupancy counted.
    Four 2-row requests run as one 8-row program, the reference as four
    2-row ones: _assert_same_rows."""
    d = _save_model(tmp_path)
    expected = _expected_fn(d)
    rng = np.random.RandomState(4)
    with ModelServer(place=fluid.CPUPlace(), max_batch_size=16) as srv:
        srv.load_model('m', d)
        srv.warmup('m')
        batches_before = srv.stats.batches
        srv.pause()
        xs = [_rand_batch(rng, 2) for _ in range(4)]
        reqs = [srv.submit('m', {'x': x}) for x in xs]
        srv.resume()
        outs = [r.result(timeout=60.0) for r in reqs]
        for x, (out,) in zip(xs, outs):
            _assert_same_rows(out, expected(x))
    # 4 x 2 rows coalesce into one 8-row bucket (single worker, all
    # queued before resume)
    assert srv.stats.batches - batches_before == 1
    assert srv.stats.bucket_counts.get(8, 0) >= 1


# ---- MicroBatcher edge cases (ISSUE 4 satellite) -------------------------
def _req(n, deadline=None):
    from paddle_tpu.serving.batcher import InferenceRequest
    return InferenceRequest({'x': np.ones((n, IN_DIM), 'float32')}, n,
                            deadline=deadline)


def test_batcher_expired_head_preserves_fifo_for_survivors():
    """An already-expired request at the head must not reorder the
    live requests behind it: the batch comes out in submit order."""
    b = serving.MicroBatcher()
    dead = _req(1, deadline=time.monotonic() - 1.0)
    live1, live2 = _req(2), _req(1)
    for r in (dead, live1, live2):
        b.submit(r)
    batch, expired = b.next_batch(max_rows=8, batch_timeout=0.0)
    assert expired == [dead]
    assert batch == [live1, live2]          # FIFO, coalesced
    assert b.depth() == 0


def test_batcher_all_expired_round_returns_empty_batch():
    """A round holding only dead requests hands them back NOW with an
    empty batch (the worker's `continue` path) instead of sitting on
    them until live traffic arrives."""
    b = serving.MicroBatcher()
    dead = [_req(1, deadline=time.monotonic() - 1.0) for _ in range(3)]
    for r in dead:
        b.submit(r)
    batch, expired = b.next_batch(max_rows=8, batch_timeout=0.0)
    assert batch == []
    assert expired == dead                  # all three, in order
    # the queue is clean: close() drains immediately
    b.close()
    batch, expired = b.next_batch(max_rows=8)
    assert batch is None and expired == []


def test_server_mid_batch_failure_fails_exactly_that_batch(tmp_path,
                                                           monkeypatch):
    """A worker that raises mid-batch fails exactly that batch's
    futures; the next batch serves normally on the same worker."""
    d = _save_model(tmp_path)
    expected = _expected_fn(d)
    rng = np.random.RandomState(21)
    with ModelServer(place=fluid.CPUPlace(), max_batch_size=16,
                     retry_attempts=1, retry_backoff=0.0) as srv:
        srv.load_model('m', d)
        srv.warmup('m')
        real = srv.executor.run
        boom = {'left': 1}

        def run_once_broken(*args, **kwargs):
            if boom['left'] > 0:
                boom['left'] -= 1
                raise ValueError('mid-batch explosion')
            return real(*args, **kwargs)

        monkeypatch.setattr(srv.executor, 'run', run_once_broken)
        srv.pause('m')
        xs = [_rand_batch(rng, 2) for _ in range(3)]
        doomed = [srv.submit('m', {'x': x}) for x in xs]  # one batch
        srv.resume('m')
        for r in doomed:
            with pytest.raises(ValueError):
                r.result(timeout=30.0)
        st = srv.stats_dict()['requests']
        assert st['failed'] == 3            # exactly the doomed batch
        # the worker survived: the next request is served, 3 rows in
        # the 4-row bucket's program against the 3-row direct one
        x = _rand_batch(rng, 3)
        out, = srv.infer('m', {'x': x}, timeout=30.0)
        _assert_same_rows(out, expected(x))
        assert srv.stats_dict()['requests']['failed'] == 3


def test_server_deadline_expiry(tmp_path):
    d = _save_model(tmp_path)
    with ModelServer(place=fluid.CPUPlace(), max_batch_size=8) as srv:
        srv.load_model('m', d)
        srv.pause()
        req = srv.submit('m', {'x': np.ones((2, IN_DIM), 'float32')},
                         deadline=0.01)
        time.sleep(0.05)
        srv.resume()
        with pytest.raises(DeadlineExceeded):
            req.result(timeout=30.0)
        assert srv.stats_dict()['requests']['expired'] == 1


def test_server_overload_shedding(tmp_path):
    d = _save_model(tmp_path)
    with ModelServer(place=fluid.CPUPlace(), max_batch_size=8,
                     max_queue_depth=2) as srv:
        srv.load_model('m', d)
        srv.pause()
        x = np.ones((1, IN_DIM), 'float32')
        held = [srv.submit('m', {'x': x}) for _ in range(2)]
        with pytest.raises(ServerOverloaded):
            srv.submit('m', {'x': x})
        assert srv.stats_dict()['requests']['shed'] == 1
        srv.resume()
        for r in held:                   # queued work still completes
            r.result(timeout=60.0)
        assert srv.stats_dict()['requests']['completed'] == 2


def test_server_warmup_precompiles_all_buckets(tmp_path):
    d = _save_model(tmp_path)
    with ModelServer(place=fluid.CPUPlace(), max_batch_size=8) as srv:
        srv.load_model('m', d)
        warmed = srv.warmup()
        assert warmed == {'m': [1, 2, 4, 8]}
        info = srv.cache_info()
        assert info.misses == 4
        # live traffic at any size <= 8 is compile-free
        rng = np.random.RandomState(5)
        for n in (1, 2, 3, 5, 6, 8):
            srv.infer('m', {'x': _rand_batch(rng, n)})
        assert srv.cache_info().misses == 4


def test_server_retry_absorbs_transient_failure(tmp_path, monkeypatch):
    d = _save_model(tmp_path)
    with ModelServer(place=fluid.CPUPlace(), max_batch_size=8,
                     retry_attempts=3, retry_backoff=0.0) as srv:
        srv.load_model('m', d)
        real = srv.executor.run
        flaky = {'left': 2}

        def run_flaky(*args, **kwargs):
            if flaky['left'] > 0:
                flaky['left'] -= 1
                raise OSError('simulated NFS hiccup')
            return real(*args, **kwargs)

        monkeypatch.setattr(srv.executor, 'run', run_flaky)
        out, = srv.infer('m', {'x': np.ones((2, IN_DIM), 'float32')},
                         timeout=60.0)
        assert out.shape == (2, OUT_DIM)
        st = srv.stats_dict()['requests']
        assert st['retries'] == 2
        assert st['failed'] == 0


def test_server_permanent_failure_surfaces(tmp_path, monkeypatch):
    d = _save_model(tmp_path)
    with ModelServer(place=fluid.CPUPlace(), max_batch_size=8,
                     retry_attempts=2, retry_backoff=0.0) as srv:
        srv.load_model('m', d)

        def run_broken(*args, **kwargs):
            raise OSError('disk on fire')

        monkeypatch.setattr(srv.executor, 'run', run_broken)
        req = srv.submit('m', {'x': np.ones((1, IN_DIM), 'float32')})
        with pytest.raises(Exception) as err:
            req.result(timeout=60.0)
        assert 'disk on fire' in repr(err.value.__cause__ or err.value)
        assert srv.stats_dict()['requests']['failed'] == 1


def test_server_non_row_aligned_model_exact(tmp_path):
    """A model whose fetch is batch-reduced still serves exact results
    (per-request fallback) and flips batchable off."""
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 9
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[IN_DIM], dtype='float32')
        y = fluid.layers.fc(input=x, size=1)
        m = fluid.layers.reduce_mean(y)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
    ref_scope = fluid.Scope()
    with fluid.scope_guard(ref_scope):
        exe.run(startup)
    with ModelServer(place=fluid.CPUPlace(), max_batch_size=8) as srv:
        model = srv.register_model('r', main, ['x'], [m], scope)
        rng = np.random.RandomState(6)
        for n in (2, 3):
            x_np = _rand_batch(rng, n)
            out, = srv.infer('r', {'x': x_np})
            direct, = exe.run(main, feed={'x': x_np}, fetch_list=[m],
                              scope=ref_scope)
            assert np.array_equal(np.asarray(out), np.asarray(direct))
        assert model.batchable is False


def test_server_errors_and_closed(tmp_path):
    d = _save_model(tmp_path)
    srv = ModelServer(place=fluid.CPUPlace(), max_batch_size=8)
    srv.load_model('m', d)
    with pytest.raises(ModelNotFound):
        srv.infer('nope', {'x': np.ones((1, IN_DIM), 'float32')})
    with pytest.raises(ValueError):
        srv.infer('m', {})                       # missing feed
    with pytest.raises(ValueError):              # oversized request
        srv.infer('m', {'x': np.ones((9, IN_DIM), 'float32')})
    srv.close()
    with pytest.raises(serving.ServerClosed):
        srv.submit('m', {'x': np.ones((1, IN_DIM), 'float32')})
    srv.close()                                  # idempotent


def test_stats_report_and_serving_spans(tmp_path):
    from paddle_tpu import profiler
    d = _save_model(tmp_path)
    with ModelServer(place=fluid.CPUPlace(), max_batch_size=8) as srv:
        srv.load_model('m', d)
        srv.infer('m', {'x': np.ones((3, IN_DIM), 'float32')})
        text = srv.report()
        for token in ('Serving Report', 'requests:', 'batches:',
                      'buckets:', 'latency:', 'compile cache:'):
            assert token in text, text
        st = srv.stats_dict()
        assert st['batches']['count'] == 1
        assert st['batches']['bucket_counts'] == {4: 1}
        assert 0.0 < st['batches']['occupancy'] <= 1.0
        assert st['latency']['request']['count'] == 1
    spans = profiler.serving_stats()
    assert 'serving/batch_run' in spans
    assert spans['serving/batch_run']['calls'] >= 1
    assert 'serving/pad' in spans


def test_registry_isolated_scopes(tmp_path):
    """Two models loaded into one registry share no parameter slots."""
    da = _save_model(tmp_path, 'a', seed=1)
    db = _save_model(tmp_path, 'b', seed=2)
    reg = serving.ModelRegistry()
    exe = fluid.Executor(fluid.CPUPlace())
    ma = reg.load('a', da, exe)
    mb = reg.load('b', db, exe)
    assert ma.scope is not mb.scope
    shared = set(ma.scope.vars) & set(mb.scope.vars)
    assert shared                       # same auto-generated layer names
    differing = 0
    for name in shared:
        va = np.asarray(ma.scope.raw(name))
        vb = np.asarray(mb.scope.raw(name))
        if not va.any() and not vb.any():
            continue                    # zero-initialized biases tie
        if not np.array_equal(va, vb):
            differing += 1
    assert differing, 'seeds 1/2 produced identical parameters'
    assert len(reg) == 2 and reg.names() == ['a', 'b']
    reg.unload('a')
    with pytest.raises(ModelNotFound):
        reg.get('a')


def test_serve_bench_smoke(tmp_path):
    """The load generator's --smoke gate passes against the recorded
    baseline (in-process: spawning a fresh interpreter re-imports jax)."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        'serve_bench', os.path.join(os.path.dirname(__file__), '..',
                                    'tools', 'serve_bench.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main(['--smoke', '--json', str(tmp_path / 'bench.json')])
    assert rc == 0
