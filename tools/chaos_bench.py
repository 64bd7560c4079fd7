#!/usr/bin/env python
"""Deterministic chaos harness + smoke gate for the serving SLO
guardrails (SERVING.md "Failure domains & SLO guardrails").

Drives a ModelServer through a seeded ``FaultPlan`` that kills a
schedule of batches at the ``serving/run_batch`` injection site, then
checks the guardrail invariants:

- no worker thread dies (the server keeps serving after the faults);
- the circuit breaker opens on the consecutive failures, sheds with
  typed CircuitOpen at admission, half-opens after the cooldown, and
  re-closes on probe successes — the exact open -> half_open -> closed
  transition schedule is asserted;
- no request is silently dropped: every submitted future resolves with
  a result or a typed error, and every admission rejection is typed;
- post-recovery outputs are bit-identical to a fault-free reference
  run over the same inputs;
- a second phase wedges a worker with an injected hang and checks the
  watchdog fails the batch within its stage deadline and
  ``close(timeout=)`` returns instead of hanging.

``--smoke`` runs the seeded schedule and exits nonzero if any
invariant breaks — the CI gate alongside ``serve_bench.py --smoke``
and ``check_checkpoint.py --json``.

    python tools/chaos_bench.py            # full run, prints report
    python tools/chaos_bench.py --smoke    # CI gate
"""
import argparse
import json
import os
import sys
import tempfile
import time

# A CPU functional gate, never a measurement: pin the CPU backend
# before jax initializes, so it cannot take a chip from its owner.
os.environ['JAX_PLATFORMS'] = 'cpu'
sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import numpy as np  # noqa: E402

IN_DIM, OUT_DIM = 16, 4


def _force_cpu():
    import jax
    try:
        jax.config.update('jax_platforms', 'cpu')
    except Exception:
        pass


def _build_artifact(workdir, seed=7):
    import paddle_tpu.fluid as fluid
    exe = fluid.Executor(fluid.CPUPlace())
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = seed
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name='x', shape=[IN_DIM],
                                  dtype='float32')
            h = fluid.layers.fc(input=x, size=32, act='relu')
            y = fluid.layers.fc(input=h, size=OUT_DIM, act=None)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        d = os.path.join(workdir, 'model')
        fluid.io.save_inference_model(d, ['x'], [y], exe,
                                      main_program=main)
    return d


def _reference_fn(model_dir):
    import paddle_tpu.fluid as fluid
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    prog, _, fetch_vars = fluid.io.load_inference_model(
        model_dir, exe, scope=scope)

    def run(x):
        out, = exe.run(prog, feed={'x': x}, fetch_list=fetch_vars,
                       scope=scope)
        return np.asarray(out)
    return run


def _mesh_partitioner(mesh):
    """A dp-mesh Partitioner over the first ``mesh`` local devices, or
    None for the classic single-device run."""
    if not mesh or mesh <= 1:
        return None
    import jax
    from jax.sharding import Mesh
    from paddle_tpu.partition import Partitioner
    devs = jax.devices()
    if len(devs) < mesh:
        raise RuntimeError(
            'mesh=%d requested but only %d device(s) visible — set '
            'XLA_FLAGS=--xla_force_host_platform_device_count=%d (the '
            'CLI does this automatically)' % (mesh, len(devs), mesh))
    return Partitioner(mesh=Mesh(np.asarray(devs[:mesh]), ('dp',)))


def _sharded_reference_fn(fluid, artifact, mesh, max_batch):
    """Fault-free reference for mesh mode: a CLEAN ModelServer with the
    same partitioner/bucketing config, so 'bit-identical recovery'
    compares the faulted sharded pipeline against the identical sharded
    computation (a raw single-device executor run is a different XLA
    program; cross-mesh float reductions need not match bitwise)."""
    from paddle_tpu.serving import ModelServer
    srv = ModelServer(place=fluid.CPUPlace(), max_batch_size=max_batch,
                      partitioner=_mesh_partitioner(mesh))
    srv.load_model('ref', artifact)
    srv.warmup('ref')

    def run(x):
        out, = srv.infer('ref', {'x': x}, timeout=60.0)
        return np.asarray(out)
    run.close = srv.close
    return run


def run_chaos(n_requests=24, fault_times=3, extra_fault_at=None,
              max_batch=8, seed=1, failure_threshold=3, cooldown=0.25,
              probe_successes=2, hang_phase=True, mesh=1):
    """Returns a result dict with ``problems`` (empty = all invariants
    held). Faults and inputs are fully seeded — two runs with the same
    arguments exercise the identical schedule. ``mesh=N`` runs the
    whole plan against a SHARDED ModelServer (models distributed over
    an N-device dp mesh via the Partitioner); the guardrail invariants
    — no worker death, typed resolution, bit-identical recovery — must
    hold unchanged."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.resilience import (FaultPlan, fault_plan,
                                       SITE_SERVING_RUN)
    from paddle_tpu.serving import (CircuitOpen, ModelServer,
                                    ServingError)
    from paddle_tpu.serving.breaker import CLOSED, HALF_OPEN, OPEN

    problems = []
    rng = np.random.RandomState(seed)
    inputs = [rng.randn(int(rng.randint(1, max_batch + 1)),
                        IN_DIM).astype('float32')
              for _ in range(n_requests)]
    with tempfile.TemporaryDirectory(prefix='chaos_bench_') as workdir:
        artifact = _build_artifact(workdir)
        if mesh and mesh > 1:
            reference = _sharded_reference_fn(fluid, artifact, mesh,
                                              max_batch)
        else:
            reference = _reference_fn(artifact)
        expected = [reference(x) for x in inputs]
        if hasattr(reference, 'close'):
            reference.close()

        # ---- phase 1: batch-kill schedule vs the breaker -----------------
        plan = FaultPlan().inject(SITE_SERVING_RUN, times=fault_times)
        if extra_fault_at:
            plan.inject(SITE_SERVING_RUN, at=list(extra_fault_at))
        srv = ModelServer(
            place=fluid.CPUPlace(), max_batch_size=max_batch,
            retry_attempts=1, retry_backoff=0.0,
            partitioner=_mesh_partitioner(mesh),
            breaker_config=dict(failure_threshold=failure_threshold,
                                cooldown=cooldown,
                                probe_successes=probe_successes,
                                window=256))
        outcomes, sheds = [], 0
        with srv:
            srv.load_model('m', artifact)
            srv.warmup('m')
            with fault_plan(plan):
                for i, x in enumerate(inputs):
                    # serial client: submit (backing off while the
                    # breaker sheds), then wait — every batch is one
                    # request, so the fault schedule is deterministic
                    give_up = time.monotonic() + 30.0
                    req = None
                    while req is None:
                        try:
                            req = srv.submit('m', {'x': x})
                        except CircuitOpen as e:
                            sheds += 1
                            if time.monotonic() > give_up:
                                problems.append(
                                    'request %d: breaker never '
                                    're-admitted: %r' % (i, e))
                                break
                            time.sleep(max(0.01, min(
                                0.05, e.retry_after or 0.02)))
                    if req is None:
                        outcomes.append(('stuck', None))
                        continue
                    try:
                        out, = req.result(timeout=60.0)
                        outcomes.append(('ok', np.asarray(out)))
                    except ServingError as e:
                        outcomes.append(('typed_error', e))
                    except Exception as e:  # noqa: BLE001 — judged below
                        if type(e).__name__ in ('RetryError',
                                                'FaultInjected'):
                            outcomes.append(('typed_error', e))
                        else:
                            outcomes.append(('untyped_error', e))
            health = srv.health()
            worker_alive = health['models']['m']['worker_alive']
            final_state = health['models']['m']['state']
            transitions = [to for to, _ in srv.breaker('m').transitions]
            # recovery proof: rerun every faulted input fault-free
            recovered = 0
            for i, (kind, _payload) in enumerate(outcomes):
                if kind != 'ok':
                    continue
                if not np.array_equal(_payload, expected[i]):
                    problems.append(
                        'request %d: output differs from the '
                        'fault-free reference' % i)
                else:
                    recovered += 1
            for i, (kind, _payload) in enumerate(outcomes):
                if kind in ('typed_error',):
                    out, = srv.infer('m', {'x': inputs[i]},
                                     timeout=60.0)
                    if not np.array_equal(np.asarray(out), expected[i]):
                        problems.append(
                            'request %d: post-recovery rerun differs '
                            'from the fault-free reference' % i)
            stats = srv.stats_dict()

        # invariants
        failed = [k for k, _ in outcomes if k == 'typed_error']
        untyped = [repr(p) for k, p in outcomes if k == 'untyped_error']
        if untyped:
            problems.append('untyped client errors: %s' % untyped[:3])
        if any(k == 'stuck' for k, _ in outcomes):
            problems.append('requests permanently shed: breaker stuck')
        if not worker_alive:
            problems.append('worker thread died under the fault plan')
        expected_faults = fault_times + len(extra_fault_at or ())
        if len(failed) != expected_faults:
            problems.append(
                'expected exactly %d typed failures (the injected '
                'schedule), saw %d' % (expected_faults, len(failed)))
        # the exact schedule depends on how many kills land on probes,
        # but every run must open, pass through half-open probing, and
        # re-close via a legal path
        legal = {OPEN: (HALF_OPEN,), HALF_OPEN: (OPEN, CLOSED),
                 CLOSED: (OPEN,)}
        if (not transitions or transitions[0] != OPEN or
                transitions[-1] != CLOSED or
                any(b not in legal[a]
                    for a, b in zip(transitions, transitions[1:]))):
            problems.append(
                'breaker transitions %r are not a legal open -> '
                'half_open(-> open)* -> closed schedule'
                % (transitions,))
        if final_state != 'ready':
            problems.append('final health state %r != ready'
                            % final_state)
        if sheds < 1:
            problems.append(
                'breaker never shed at admission while open')
        if plan.faults[SITE_SERVING_RUN] != expected_faults:
            problems.append(
                'fault plan fired %d times, expected %d'
                % (plan.faults[SITE_SERVING_RUN], expected_faults))

        # ---- phase 2: wedged worker vs watchdog + close(timeout) ---------
        wedge = None
        if hang_phase:
            wedge = _run_wedge_phase(fluid, artifact, problems,
                                     mesh=mesh)

    return {
        'config': {'n_requests': n_requests, 'fault_times': fault_times,
                   'extra_fault_at': sorted(extra_fault_at or ()),
                   'max_batch': max_batch, 'seed': seed,
                   'failure_threshold': failure_threshold,
                   'cooldown': cooldown,
                   'probe_successes': probe_successes,
                   'mesh': mesh or 1},
        'outcomes': {'ok': sum(1 for k, _ in outcomes if k == 'ok'),
                     'typed_errors': len(failed),
                     'breaker_sheds': sheds,
                     'recovered_bit_identical': recovered},
        'breaker_transitions': transitions,
        'stats': stats,
        'wedge_phase': wedge,
        'problems': problems,
    }


def _run_wedge_phase(fluid, artifact, problems, mesh=1):
    """Inject a pure hang, assert the watchdog fails it on deadline and
    close(timeout=) returns instead of hanging on the wedged worker."""
    from paddle_tpu.resilience import (FaultPlan, fault_plan,
                                       SITE_SERVING_RUN)
    from paddle_tpu.serving import ModelServer, WatchdogTimeout

    srv = ModelServer(place=fluid.CPUPlace(), max_batch_size=4,
                      retry_attempts=1, retry_backoff=0.0,
                      partitioner=_mesh_partitioner(mesh),
                      watchdog_poll=0.02)
    srv.load_model('m', artifact)
    srv.warmup('m')
    srv.stage_timeouts[SITE_SERVING_RUN] = 0.2
    plan = FaultPlan().inject(SITE_SERVING_RUN, error=None, delay=1.0,
                              at=[0])
    x = np.ones((2, IN_DIM), 'float32')
    result = {'watchdog_tripped': False, 'close_seconds': None}
    with fault_plan(plan):
        req = srv.submit('m', {'x': x})
        t0 = time.monotonic()
        try:
            req.result(timeout=10.0)
            problems.append('hung batch completed instead of tripping '
                            'the watchdog')
        except WatchdogTimeout:
            result['watchdog_tripped'] = True
            if time.monotonic() - t0 > 0.8:
                problems.append('watchdog trip took longer than the '
                                'hang itself')
        except Exception as e:  # noqa: BLE001 — reported below
            problems.append('hung batch failed with %r, expected '
                            'WatchdogTimeout' % e)
        t0 = time.monotonic()
        srv.close(timeout=0.5)
        result['close_seconds'] = time.monotonic() - t0
        if result['close_seconds'] > 1.5:
            problems.append(
                'close(timeout=0.5) took %.2fs against a wedged worker'
                % result['close_seconds'])
        time.sleep(1.0)     # let the abandoned worker's hang expire
    return result


def run_kill_host(n_requests=12, seed=3, replicas=2,
                  detect_window=5.0, poll_interval=0.1):
    """Whole-host-loss chaos for the fleet tier (RESILIENCE.md
    "Surviving host loss"): every replica is a ModelServer living in
    its OWN process (``multihost.remote.spawn_cell``). Mid-stream one
    cell process is killed with SIGKILL — the remote analogue of losing
    a host and every replica on it at once. Invariants:

    - every in-flight request resolves ok or with a typed error; the
      requeue path re-runs them on the surviving cell and every
      delivered output is bit-identical to the fault-free reference;
    - the fleet detects the dead host within ``detect_window`` seconds
      (supervisor poll or a client requeue, whichever is first);
    - the supervisor rebuilds the replica through the factory — a NEW
      process — and the rebuilt cell serves bit-identical outputs.
    """
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fleet import Router
    from paddle_tpu.fleet.router import ACTIVE
    from paddle_tpu.multihost.remote import spawn_cell
    from paddle_tpu.serving import ServingError

    problems = []
    rng = np.random.RandomState(seed)
    inputs = [rng.randn(int(rng.randint(1, 5)),
                        IN_DIM).astype('float32')
              for _ in range(n_requests)]
    with tempfile.TemporaryDirectory(prefix='chaos_kill_') as workdir:
        artifact = _build_artifact(workdir)
        reference = _reference_fn(artifact)
        expected = [reference(x) for x in inputs]

        router = Router(lambda rid: spawn_cell('cell-%d' % rid),
                        replicas=replicas, supervise=True,
                        poll_interval=poll_interval, requeue_wait=60.0)
        result = {'killed_replica': None, 'killed_pid': None,
                  'detect_seconds': None, 'restart_seconds': None,
                  'restarted_pid': None, 'requeues': 0,
                  'outputs_bit_identical': 0, 'typed_errors': 0}
        try:
            router.load_model('m', artifact)
            victim = router.placement('m')[0]
            result['killed_replica'] = victim
            result['killed_pid'] = router.replica(victim).server.pid

            pending = []
            for i, x in enumerate(inputs):
                pending.append((i, router.submit('m', {'x': x},
                                                 deadline=120.0)))
            # the kill must land on live work: top up until the victim
            # holds an unresolved request
            for extra in range(64):
                if any(r.replica_id == victim and not r.done()
                       for _i, r in pending):
                    break
                j = extra % len(inputs)
                pending.append((j, router.submit('m',
                                                 {'x': inputs[j]},
                                                 deadline=120.0)))
            else:
                problems.append('could not land an in-flight request '
                                'on the victim replica')
            # SIGKILL the whole cell process: host loss takes down the
            # replica AND every batch in flight on it
            t_kill = time.monotonic()
            router.replica(victim).server.kill()
            for i, req in pending:
                try:
                    out, = req.result(timeout=120.0)
                except ServingError as e:
                    result['typed_errors'] += 1
                    problems.append('request %d resolved with typed '
                                    'error %r (expected requeue to '
                                    'deliver it)' % (i, e))
                    continue
                except Exception as e:  # noqa: BLE001 — judged here
                    problems.append('request %d failed UNTYPED: %r'
                                    % (i, e))
                    continue
                if np.array_equal(np.asarray(out), expected[i]):
                    result['outputs_bit_identical'] += 1
                else:
                    problems.append('request %d: output differs from '
                                    'the fault-free reference' % i)
            result['requeues'] = sum(
                1 for _i, req in pending if req.requeues)

            # detection: the victim must leave ACTIVE within the window
            give_up = t_kill + detect_window
            rep = router.replica(victim)
            while time.monotonic() < give_up:
                if rep.state != ACTIVE or rep.restarts > 0:
                    result['detect_seconds'] = \
                        time.monotonic() - t_kill
                    break
                time.sleep(0.01)
            if result['detect_seconds'] is None:
                problems.append(
                    'dead host never detected within %.1fs'
                    % detect_window)

            # recovery: the supervisor rebuilds the cell (new process)
            give_up = time.monotonic() + 180.0
            while time.monotonic() < give_up:
                if rep.restarts > 0 and rep.state == ACTIVE:
                    result['restart_seconds'] = \
                        time.monotonic() - t_kill
                    break
                time.sleep(0.05)
            if result['restart_seconds'] is None:
                problems.append('replica never rebuilt within 180s')
            else:
                result['restarted_pid'] = rep.server.pid
                if result['restarted_pid'] == result['killed_pid']:
                    problems.append('rebuilt replica reuses the dead '
                                    'pid %s' % result['killed_pid'])
                for i in (0, len(inputs) - 1):
                    out, = rep.server.infer('m', {'x': inputs[i]},
                                            timeout=120.0)
                    if not np.array_equal(np.asarray(out),
                                          expected[i]):
                        problems.append(
                            'rebuilt replica output %d differs from '
                            'the fault-free reference' % i)
            if result['requeues'] < 1:
                problems.append('no request was requeued — the kill '
                                'landed on an idle stream?')
        finally:
            router.close(timeout=10.0)
    result['problems'] = problems
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--requests', type=int, default=48)
    ap.add_argument('--fault-times', type=int, default=5,
                    help='consecutive batch kills at the head')
    ap.add_argument('--max-batch', type=int, default=8)
    ap.add_argument('--seed', type=int, default=1)
    ap.add_argument('--mesh', type=int, default=1,
                    help='run the plan against a ModelServer sharded '
                         'over an N-device dp mesh (host CPU devices '
                         'are provisioned automatically)')
    ap.add_argument('--smoke', action='store_true',
                    help='seeded short schedule; exit nonzero if any '
                         'guardrail invariant breaks')
    ap.add_argument('--no-hang-phase', action='store_true',
                    help='skip the wedged-worker/close(timeout) phase')
    ap.add_argument('--kill-host', action='store_true',
                    help='whole-host-loss phase: replicas in separate '
                         'processes, one SIGKILLed mid-stream; the '
                         'fleet must requeue, rebuild and recover '
                         'bit-identically')
    ap.add_argument('--detect-window', type=float, default=5.0,
                    help='--kill-host: max seconds to detect the dead '
                         'host')
    ap.add_argument('--json', default=None,
                    help='write the full result dict to this path')
    args = ap.parse_args(argv)
    if args.kill_host:
        _force_cpu()
        results = run_kill_host(
            n_requests=12 if args.smoke else args.requests,
            seed=args.seed, detect_window=args.detect_window)
        if args.json:
            with open(args.json, 'w') as f:
                json.dump(results, f, indent=2, sort_keys=True,
                          default=repr)
        print('kill-host: replica %s (pid %s) SIGKILLed | detected in '
              '%s | rebuilt as pid %s in %s | %d requeued, '
              '%d bit-identical outputs'
              % (results['killed_replica'], results['killed_pid'],
                 '%.3fs' % results['detect_seconds']
                 if results['detect_seconds'] is not None else 'NEVER',
                 results['restarted_pid'],
                 '%.1fs' % results['restart_seconds']
                 if results['restart_seconds'] is not None else 'NEVER',
                 results['requeues'],
                 results['outputs_bit_identical']))
        if results['problems']:
            print('KILL-HOST INVARIANTS BROKEN:', file=sys.stderr)
            for p in results['problems']:
                print('  - %s' % p, file=sys.stderr)
            return 1
        print('kill-host OK (whole-host loss detected, requeued, '
              'rebuilt bit-identically)')
        return 0
    if args.mesh > 1 and 'xla_force_host_platform_device_count' not in \
            os.environ.get('XLA_FLAGS', ''):
        # must land before jax initializes (first import below)
        os.environ['XLA_FLAGS'] = (
            os.environ.get('XLA_FLAGS', '') +
            ' --xla_force_host_platform_device_count=%d'
            % args.mesh).strip()
    _force_cpu()

    if args.smoke:
        # ~17% of batches killed: 3 consecutive (opens the breaker)
        # plus one isolated mid-stream failure after recovery
        results = run_chaos(n_requests=24, fault_times=3,
                            extra_fault_at=(12,), max_batch=8, seed=1,
                            failure_threshold=3, cooldown=0.25,
                            probe_successes=2,
                            hang_phase=not args.no_hang_phase,
                            mesh=args.mesh)
    else:
        results = run_chaos(n_requests=args.requests,
                            fault_times=args.fault_times,
                            extra_fault_at=(args.requests // 2,),
                            max_batch=args.max_batch, seed=args.seed,
                            hang_phase=not args.no_hang_phase,
                            mesh=args.mesh)

    if args.json:
        payload = dict(results)
        payload['problems'] = list(payload['problems'])
        with open(args.json, 'w') as f:
            json.dump(payload, f, indent=2, sort_keys=True, default=repr)

    o = results['outcomes']
    print('chaos%s: %d ok, %d typed errors, %d breaker sheds, '
          '%d bit-identical post-recovery'
          % (' (mesh=%d)' % args.mesh if args.mesh > 1 else '',
             o['ok'], o['typed_errors'], o['breaker_sheds'],
             o['recovered_bit_identical']))
    print('breaker transitions: %s'
          % ' -> '.join(results['breaker_transitions']))
    if results['wedge_phase']:
        w = results['wedge_phase']
        print('wedge phase: watchdog_tripped=%s close_seconds=%s'
              % (w['watchdog_tripped'],
                 None if w['close_seconds'] is None
                 else '%.2f' % w['close_seconds']))
    if results['problems']:
        print('CHAOS INVARIANTS BROKEN:', file=sys.stderr)
        for p in results['problems']:
            print('  - %s' % p, file=sys.stderr)
        return 1
    print('chaos OK (seeded fault schedule held every invariant)')
    return 0


if __name__ == '__main__':
    sys.exit(main())
