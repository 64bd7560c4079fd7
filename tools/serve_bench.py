#!/usr/bin/env python
"""Serving load generator + smoke regression gate for
``paddle_tpu.serving.ModelServer``.

Builds small MLP inference artifacts in a temp dir, serves them through
a ModelServer, and fires N client threads with mixed batch sizes.
Reports throughput, latency percentiles, batch occupancy, and
compile-cache behavior as JSON.

``--smoke`` runs a short deterministic workload and compares the
*functional* counters against the recorded baseline
(``tools/serve_baseline.json``), exiting nonzero on regression. The
gate is deliberately wall-clock-light — CI boxes vary wildly — and
anchors on the invariants instead: compiles bounded by the bucket
count, zero shed/expired/failed under capacity, outputs equal to a
serial run's within a float32 rounding step, plus a
very conservative throughput floor.

    python tools/serve_bench.py                 # full load run
    python tools/serve_bench.py --smoke         # CI regression gate
    python tools/serve_bench.py --smoke --update-baseline
"""
import argparse
import json
import os
import sys
import tempfile
import threading
import time

# A CPU functional gate, never a measurement: pin the CPU backend
# before jax initializes, so it cannot take a chip from its owner.
os.environ['JAX_PLATFORMS'] = 'cpu'
sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import numpy as np  # noqa: E402

DEFAULT_BASELINE = os.path.join(os.path.dirname(__file__),
                                'serve_baseline.json')
IN_DIM, OUT_DIM = 16, 4


def _force_cpu():
    import jax
    try:
        jax.config.update('jax_platforms', 'cpu')
    except Exception:
        pass


def _build_artifacts(workdir, n_models, seed0=7):
    import paddle_tpu.fluid as fluid
    dirs = {}
    exe = fluid.Executor(fluid.CPUPlace())
    for i in range(n_models):
        main, startup = fluid.Program(), fluid.Program()
        startup.random_seed = seed0 + i
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                x = fluid.layers.data(name='x', shape=[IN_DIM],
                                      dtype='float32')
                h = fluid.layers.fc(input=x, size=32, act='relu')
                y = fluid.layers.fc(input=h, size=OUT_DIM, act=None)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            d = os.path.join(workdir, 'model_%d' % i)
            fluid.io.save_inference_model(d, ['x'], [y], exe,
                                          main_program=main)
        dirs['model_%d' % i] = d
    return dirs


def _reference_runners(dirs):
    """Serial output oracles, one per model, shared-lock serialized
    (the oracle must stay literally serial). An oracle runs a request
    at its own batch size, the server in its padded, coalesced bucket:
    two executables, whose float32 dots XLA may sum in another order
    (one ulp was observed, 1.8e-7 relative), so run_load compares them
    within rtol 1e-6."""
    import paddle_tpu.fluid as fluid
    lock = threading.Lock()
    runners = {}
    for name, d in dirs.items():
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        prog, _, fetch_vars = fluid.io.load_inference_model(
            d, exe, scope=scope)

        def run(x, _exe=exe, _prog=prog, _fv=fetch_vars, _scope=scope):
            with lock:
                out, = _exe.run(_prog, feed={'x': x}, fetch_list=_fv,
                                scope=_scope)
            return out
        runners[name] = run
    return runners


def run_load(n_models=1, n_threads=8, requests_per_thread=25,
             max_batch=16, batch_timeout=0.002, verify=False, seed=0,
             journal_path=None):
    """Returns the result dict (throughput, latency, serving stats).
    ``journal_path`` installs an observability RunJournal over the
    serving section, so the run leaves a JSONL artifact that
    ``tools/obs_report.py`` can render/validate."""
    import contextlib
    import paddle_tpu.fluid as fluid
    from paddle_tpu import observability
    from paddle_tpu.serving import ModelServer
    results = {}
    with tempfile.TemporaryDirectory(prefix='serve_bench_') as workdir:
        dirs = _build_artifacts(workdir, n_models)
        oracles = _reference_runners(dirs) if verify else None
        jctx = observability.journal(journal_path) if journal_path \
            else contextlib.nullcontext()
        with jctx, \
             ModelServer(place=fluid.CPUPlace(), max_batch_size=max_batch,
                         max_queue_depth=n_threads * requests_per_thread,
                         batch_timeout=batch_timeout) as srv:
            for name, d in dirs.items():
                srv.load_model(name, d)
            t_w0 = time.monotonic()
            warmed = srv.warmup()
            warmup_s = time.monotonic() - t_w0
            errors, lock = [], threading.Lock()

            def client(tid):
                rng = np.random.RandomState(seed * 1000 + tid)
                name = 'model_%d' % (tid % n_models)
                try:
                    for _ in range(requests_per_thread):
                        n = int(rng.randint(1, max_batch + 1))
                        x = rng.randn(n, IN_DIM).astype('float32')
                        out, = srv.infer(name, {'x': x}, timeout=120.0)
                        if out.shape != (n, OUT_DIM):
                            raise AssertionError('bad shape %r'
                                                 % (out.shape,))
                        if oracles is not None and not np.allclose(
                                np.asarray(out),
                                np.asarray(oracles[name](x)),
                                rtol=1e-6, atol=1e-7):
                            raise AssertionError(
                                'output mismatch vs serial run')
                except Exception as e:   # noqa: BLE001 — reported below
                    with lock:
                        errors.append('%s: %r' % (name, e))

            threads = [threading.Thread(target=client, args=(t,))
                       for t in range(n_threads)]
            t0 = time.monotonic()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.monotonic() - t0
            stats = srv.stats_dict()
            report = srv.report()
        total = n_threads * requests_per_thread
        results = {
            'config': {'models': n_models, 'threads': n_threads,
                       'requests_per_thread': requests_per_thread,
                       'max_batch': max_batch,
                       'batch_timeout': batch_timeout,
                       'verified': bool(verify)},
            'warmup': {'seconds': warmup_s,
                       'buckets': {k: v for k, v in warmed.items()}},
            'wall_seconds': wall,
            'throughput_rps': total / wall if wall > 0 else 0.0,
            'errors': errors,
            'stats': stats,
            'report': report,
        }
    return results


def check_smoke(results, baseline):
    """Compare a smoke run against the recorded baseline; returns a
    list of regression messages (empty = pass)."""
    problems = []
    st = results['stats']
    req = st['requests']
    if results['errors']:
        problems.append('client errors: %s' % results['errors'][:3])
    for key in ('shed', 'expired', 'failed'):
        if req[key] > baseline.get('max_%s' % key, 0):
            problems.append('%s=%d exceeds baseline max_%s=%d'
                            % (key, req[key], key,
                               baseline.get('max_%s' % key, 0)))
    expected_total = results['config']['threads'] * \
        results['config']['requests_per_thread']
    if req['completed'] < expected_total:
        problems.append('dropped requests: completed %d < submitted %d'
                        % (req['completed'], expected_total))
    cc = st['compile_cache']
    if cc['misses'] > baseline['max_compiles']:
        problems.append(
            'compile-cache misses %d exceed max_compiles=%d — shape '
            'bucketing regressed' % (cc['misses'],
                                     baseline['max_compiles']))
    if results['throughput_rps'] < baseline['min_throughput_rps']:
        problems.append('throughput %.1f rps below floor %.1f rps'
                        % (results['throughput_rps'],
                           baseline['min_throughput_rps']))
    occ = st['batches']['occupancy']
    if occ < baseline.get('min_occupancy', 0.0):
        problems.append('batch occupancy %.2f below floor %.2f'
                        % (occ, baseline['min_occupancy']))
    return problems


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--models', type=int, default=1)
    ap.add_argument('--threads', type=int, default=8)
    ap.add_argument('--requests', type=int, default=25,
                    help='requests per thread')
    ap.add_argument('--max-batch', type=int, default=16)
    ap.add_argument('--batch-timeout', type=float, default=0.002)
    ap.add_argument('--verify', action='store_true',
                    help='check every output against a serial run')
    ap.add_argument('--smoke', action='store_true',
                    help='short deterministic run gated on the baseline')
    ap.add_argument('--baseline', default=DEFAULT_BASELINE)
    ap.add_argument('--update-baseline', action='store_true')
    ap.add_argument('--json', default=None,
                    help='write the full result dict to this path')
    ap.add_argument('--journal', default=None, metavar='PATH',
                    help='write an observability run journal (JSONL) '
                         'covering the serving run; --smoke validates '
                         'it via tools/obs_report.py')
    args = ap.parse_args(argv)
    _force_cpu()

    journal_path = args.journal
    if args.smoke and journal_path is None:
        # the smoke gate always exercises the journal path end to end
        fd, journal_path = tempfile.mkstemp(prefix='serve_bench_',
                                            suffix='.jsonl')
        os.close(fd)

    if args.smoke:
        results = run_load(n_models=2, n_threads=4,
                           requests_per_thread=6, max_batch=8,
                           verify=True, seed=1,
                           journal_path=journal_path)
    else:
        results = run_load(n_models=args.models, n_threads=args.threads,
                           requests_per_thread=args.requests,
                           max_batch=args.max_batch,
                           batch_timeout=args.batch_timeout,
                           verify=args.verify,
                           journal_path=journal_path)
    if journal_path:
        print('journal written to %s' % journal_path)

    if args.json:
        payload = dict(results)
        payload.pop('report', None)
        with open(args.json, 'w') as f:
            json.dump(payload, f, indent=2, sort_keys=True)
    print(results['report'])
    print('throughput: %.1f req/s over %.2fs (warmup %.2fs)'
          % (results['throughput_rps'], results['wall_seconds'],
             results['warmup']['seconds']))

    if not args.smoke:
        return 0
    if args.update_baseline:
        # floors at ~1/4 of the observed run so normal CI jitter passes
        baseline = {
            'max_compiles': results['stats']['compile_cache']['misses'],
            'min_throughput_rps': round(
                results['throughput_rps'] / 4.0, 1),
            'min_occupancy': 0.0,
            'max_shed': 0, 'max_expired': 0, 'max_failed': 0,
        }
        with open(args.baseline, 'w') as f:
            json.dump(baseline, f, indent=2, sort_keys=True)
        print('baseline updated: %s' % args.baseline)
        return 0
    with open(args.baseline) as f:
        baseline = json.load(f)
    problems = check_smoke(results, baseline)
    if journal_path:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from obs_report import check_journal
        problems += check_journal(journal_path, require='serving')
        # every smoke request is traced end to end; an empty span set
        # means the serving pipeline lost its tracing wiring
        problems += check_journal(journal_path, require='tracing')
        # warmup ledgers every per-bucket compile when a journal is
        # active (OBSERVABILITY.md "Performance observatory"); zero
        # perf_ledger records means the capture path regressed
        problems += check_journal(journal_path, require='perf')
    if problems:
        print('SMOKE REGRESSION:', file=sys.stderr)
        for p in problems:
            print('  - %s' % p, file=sys.stderr)
        return 1
    print('smoke OK (baseline: %s)' % os.path.basename(args.baseline))
    return 0


if __name__ == '__main__':
    sys.exit(main())
