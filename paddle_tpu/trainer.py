"""High-level Trainer API.

Parity: python/paddle/fluid/trainer.py (Trainer, Begin/End Epoch/Step
events, build_feed_var_list). TPU design notes: `parallel=True` maps to
the pjit-SPMD ParallelExecutor (mesh data parallelism) instead of the
reference's per-GPU program clones; the pserver/NCCL2 env-var transpile
path maps onto DistributeTranspiler's collective lowering.

Resilience (RESILIENCE.md): ``train(..., checkpoint_config=
CheckpointConfig(dir))`` periodically saves params + optimizer
accumulators + trainer progress (epoch/step/RNG key) through the atomic
checkpoint protocol and TRANSPARENTLY resumes after a kill — a fresh
``Trainer().train()`` with the same config restores the newest healthy
serial and skips the already-completed steps. ``anomaly_guard=
AnomalyGuard(policy=...)`` screens feed batches and fetched losses (and
optionally gradient global norms) for NaN/Inf/spikes, reacting per
policy: ``raise`` / ``skip_batch`` / ``rollback_to_checkpoint``.
"""
import contextlib
import logging
import os
import time

import numpy as np

from . import framework
from . import executor
from . import observability as _obs
from . import io
from . import optimizer as opt_module
from . import data_feeder
from . import unique_name
from .core.lowering import RNG_KEY
from .core.places import default_place
from .parallel import parallel_executor
from .resilience import CheckpointConfig, AnomalyGuard  # noqa: F401 (API)
from .resilience import anomaly as _anomaly
from .resilience import faultinject as _fi

__all__ = ['Trainer', 'BeginEpochEvent', 'EndEpochEvent',
           'BeginStepEvent', 'EndStepEvent', 'check_and_get_place',
           'CheckpointConfig']

_logger = logging.getLogger('paddle_tpu.resilience')


class BeginEpochEvent(object):
    def __init__(self, epoch_id):
        self.epoch = epoch_id


class EndEpochEvent(object):
    def __init__(self, epoch_id):
        self.epoch = epoch_id


class BeginStepEvent(object):
    def __init__(self, epoch_id, step_id):
        self.epoch = epoch_id
        self.step = step_id
        self.fetch_metrics = True


class EndStepEvent(object):
    def __init__(self, epoch_id, step_id, metrics):
        self.epoch = epoch_id
        self.step = step_id
        self.metrics = metrics


def check_and_get_place(place):
    """``None`` resolves to device 0 of the backend JAX was started on
    (parity: trainer.py::check_and_get_place prefers CUDA)."""
    return default_place() if place is None else place


class Trainer(object):
    """train_func() builds the forward and returns [loss, *metrics] under
    this trainer's fresh programs; the optimizer is appended here."""

    def __init__(self, train_func, optimizer, param_path=None, place=None,
                 parallel=False):
        self.__stop = False
        self.parallel = parallel
        if not isinstance(optimizer, opt_module.Optimizer):
            raise TypeError(
                "The optimizer should be an instance of Optimizer")

        self.scope = executor.Scope()
        self.startup_program = framework.Program()
        self.train_program = framework.Program()

        # fresh numbering so a paired Inferencer (which also guards)
        # rebuilds the same parameter names regardless of prior builds
        with framework.program_guard(self.train_program,
                                     self.startup_program), \
                unique_name.guard():
            program_func_outs = train_func()
            self.train_func_outputs = program_func_outs if isinstance(
                program_func_outs, list) else [program_func_outs]
            self.test_program = self.train_program.clone(for_test=True)
            loss = self.train_func_outputs[0]
            optimizer.minimize(loss)

        self.place = check_and_get_place(place)
        self._dist_transpile_if_necessary()

        with self._prog_and_scope_guard():
            exe = executor.Executor(self.place)
            exe.run(self.startup_program)
        if param_path:
            with self._prog_and_scope_guard():
                io.load_persistables(executor.Executor(self.place),
                                     dirname=param_path)

    def _dist_transpile_if_necessary(self):
        """Parity: trainer.py::_dist_transpile_if_necessary. The pserver
        role is absorbed by the collective design (SURVEY §3.5): both
        TRAINER and PSERVER roles run the transpiled collective program."""
        if "PADDLE_TRAINING_ROLE" not in os.environ:
            return
        from .parallel.transpiler import DistributeTranspiler
        trainers = int(os.getenv("PADDLE_TRAINERS", "1"))
        trainer_id = int(os.getenv("PADDLE_TRAINER_ID", "0"))
        with self._prog_and_scope_guard():
            t = DistributeTranspiler()
            t.transpile(trainer_id, pservers=os.getenv(
                "PADDLE_PSERVER_IPS", ""), trainers=trainers)
            self.train_program = t.get_trainer_program()

    def stop(self):
        self.__stop = True

    def train(self, num_epochs, event_handler, reader=None,
              feed_order=None, checkpoint_config=None,
              anomaly_guard=None, prefetch=0, steps_per_dispatch=1,
              sync_interval=1, zero_stage=None, zero_bucket_bytes=None):
        """``checkpoint_config``: a resilience.CheckpointConfig — save
        progress every ``step_interval`` steps / ``epoch_interval``
        epochs through the atomic checkpoint protocol and auto-resume
        from the newest healthy serial when one exists.
        ``anomaly_guard``: a resilience.AnomalyGuard screening feeds,
        losses and (optionally) gradient norms each step.

        Pipelining knobs (PERF.md "Dispatch pipelining"; bit-exact vs
        the default step-by-step loop, pinned by tests/test_pipeline.py):

        ``prefetch=N``: run reader pulls + DataFeeder conversion + H2D
        staging N batches ahead on a background thread
        (reader.prefetch.PrefetchPipeline), so host feed work overlaps
        device compute. ``trainer_host_wait_seconds`` measures what the
        loop still waits for.

        ``steps_per_dispatch=K``: chain K steps into ONE device
        dispatch (``Executor.run_chained``); amortizes per-dispatch
        latency. Partial tails and shape changes fall back to
        sequential steps automatically. Works on both the plain
        Executor and the ParallelExecutor path — on a multi-device
        mesh the chain runs as one sharded scan (PARTITIONING.md).

        ``sync_interval=M``: materialize fetched losses only every M
        steps — between syncs, ``EndStepEvent.metrics`` carry LAZY
        device values (``np.asarray`` them to force). Ignored (forced
        to 1) when an ``anomaly_guard`` must inspect every loss.

        ``zero_stage`` (PERF.md "ZeRO-2 and collective overlap"):
        ZeRO mode for the data-parallel path — default (None) is
        stage 2 on a dp mesh: optimizer state sharded per-tensor over
        dp, gradients reduce-scattered in size-capped buckets during
        the backward, update ops consuming local shards, parameters
        all-gathered back. Bit-identical to the replicated path
        (tests/test_zero.py); ``zero_stage=0`` restores the replicated
        all-reduce tail. ``zero_bucket_bytes`` caps a gradient
        bucket's payload (default ~4 MB). Structural no-op on a
        single device."""
        if checkpoint_config is not None and not isinstance(
                checkpoint_config, CheckpointConfig):
            raise TypeError('checkpoint_config must be a '
                            'resilience.CheckpointConfig')
        if anomaly_guard is not None and not isinstance(
                anomaly_guard, AnomalyGuard):
            raise TypeError('anomaly_guard must be a '
                            'resilience.AnomalyGuard')
        if int(prefetch) < 0:
            raise ValueError('prefetch must be >= 0')
        if int(steps_per_dispatch) < 1:
            raise ValueError('steps_per_dispatch must be >= 1')
        if int(sync_interval) < 1:
            raise ValueError('sync_interval must be >= 1')
        self._checkpoint_config = checkpoint_config
        self._anomaly_guard = anomaly_guard
        self._prefetch = int(prefetch)
        self._steps_per_dispatch = int(steps_per_dispatch)
        self._sync_interval = int(sync_interval)
        self._zero_stage = zero_stage
        self._zero_bucket_bytes = zero_bucket_bytes
        if self.parallel:
            self._train_by_parallel_executor(num_epochs, event_handler,
                                             reader, feed_order)
        else:
            self._train_by_executor(num_epochs, event_handler, reader,
                                    feed_order)

    def test(self, reader, feed_order):
        return self._test_by_executor(reader, feed_order,
                                      self.train_func_outputs)

    def save_params(self, param_path):
        with self._prog_and_scope_guard():
            exe = executor.Executor(self.place)
            io.save_persistables(exe, dirname=param_path)

    @contextlib.contextmanager
    def _prog_and_scope_guard(self):
        with framework.program_guard(main_program=self.train_program,
                                     startup_program=self.startup_program):
            with executor.scope_guard(self.scope):
                yield

    def _feeder(self, program, feed_order):
        feed_var_list = build_feed_var_list(program, feed_order)
        return data_feeder.DataFeeder(feed_list=feed_var_list,
                                      place=self.place)

    def _train_by_executor(self, num_epochs, event_handler, reader,
                           feed_order):
        with self._prog_and_scope_guard():
            feeder = self._feeder(self.train_program, feed_order)
            exe = executor.Executor(self.place)
            # ZeRO on the plain-executor path: real only when the
            # executor's partitioner spans a dp mesh (a place-backed
            # Executor is a 1-device fallback — structural no-op)
            from .compiler import zero as _zero
            _zero.apply_zero(self.train_program,
                             exe.partitioner.axis_extent('dp'),
                             stage=getattr(self, '_zero_stage', None),
                             bucket_bytes=getattr(
                                 self, '_zero_bucket_bytes', None))
            self._train_loop(event_handler, exe, num_epochs, reader,
                             feeder)

    def _train_by_parallel_executor(self, num_epochs, event_handler,
                                    reader, feed_order):
        with self._prog_and_scope_guard():
            pe = self._get_or_create_parallel_executor()
            feeder = self._feeder(self.train_program, feed_order)
            self._train_loop(event_handler, pe, num_epochs, reader,
                             feeder)

    # ---- resilience helpers ---------------------------------------------
    def _grad_fetch_names(self):
        """``<param>@GRAD`` names that exist in the train program, for
        AnomalyGuard(monitor_gradients=True)."""
        block = self.train_program.global_block()
        names = []
        for p in block.all_parameters():
            g = p.name + '@GRAD'
            if block._find_var_recursive(g) is not None:
                names.append(g)
        return names

    def _rng_state(self):
        rng = self.scope.raw(RNG_KEY)
        if rng is None:
            return None
        arr = np.asarray(rng)
        return {'dtype': str(arr.dtype), 'shape': list(arr.shape),
                'data': arr.ravel().tolist()}

    def _restore_rng(self, state):
        if not state:
            return
        import jax.numpy as jnp
        arr = np.asarray(state['data'], dtype=state['dtype']).reshape(
            state['shape'])
        self.scope.set_var(RNG_KEY, jnp.asarray(arr))

    def _save_progress_checkpoint(self, cfg, epoch_id, step_id,
                                  global_step, exe=None, force=False):
        """One atomic checkpoint carrying params + optimizer
        accumulators (persistables) and the trainer's own progress, so
        a restart replays NOTHING and repeats NOTHING. ``exe`` is the
        TRAINING executor (its Partitioner's mesh/rules land in the
        manifest; sharded state saves per-shard). ``force`` bypasses
        the secs rate limit — a preemption save must always commit."""
        state = {'epoch': epoch_id, 'step': step_id,
                 'global_step': global_step, 'rng': self._rng_state()}
        io.save_checkpoint(
            exe if exe is not None else executor.Executor(self.place),
            cfg.checkpoint_dir,
            max_num_checkpoints=cfg.max_num_checkpoints,
            save_interval_secs=0 if force else cfg.save_interval_secs,
            main_program=self.train_program, backend=cfg.backend,
            trainer_state=state)

    def _reload_executor(self, exe):
        """An Executor for checkpoint restore that places restored
        state through the TRAINING executor's Partitioner — on a mesh,
        rollback/resume reshards the state back over the mesh instead
        of committing a single-device copy the sharded step would then
        refuse (RESILIENCE.md "Sharded checkpoints")."""
        return executor.Executor(
            self.place, partitioner=getattr(exe, 'partitioner', None))

    def _maybe_resume(self, cfg, exe=None):
        """Restore the newest healthy checkpoint (params into the
        scope, RNG key, progress counters). Returns (start_epoch,
        resume_step, global_step); resume_step is the LAST COMPLETED
        step index inside start_epoch (-1 = none)."""
        if cfg is None or not cfg.resume:
            return 0, -1, 0
        if not io._get_checkpoint_serials(cfg.checkpoint_dir):
            return 0, -1, 0
        reload_exe = self._reload_executor(exe) if exe is not None \
            else executor.Executor(self.place)
        cur_dir = io.load_checkpoint(reload_exe, cfg.checkpoint_dir,
                                     main_program=self.train_program)
        from .resilience import read_manifest
        manifest = read_manifest(cur_dir) or {}
        state = manifest.get('trainer_state')
        if not state:
            _logger.warning('auto-resume: %s has no trainer_state; '
                            'restored params only', cur_dir)
            return 0, -1, 0
        self._restore_rng(state.get('rng'))
        _logger.info('auto-resume: restored %s (epoch %d, step %d)',
                     cur_dir, state['epoch'], state['step'])
        return state['epoch'], state['step'], state['global_step']

    def _handle_anomaly(self, err, exe_for_reload):
        """Apply the guard's policy to a detected anomaly. Returns
        'skip' when the current batch should be dropped."""
        guard = self._anomaly_guard
        if guard.policy == 'raise':
            raise err
        if guard.policy == 'rollback_to_checkpoint':
            cfg = self._checkpoint_config
            if cfg is not None and io._get_checkpoint_serials(
                    cfg.checkpoint_dir):
                cur_dir = io.load_checkpoint(
                    exe_for_reload, cfg.checkpoint_dir,
                    main_program=self.train_program)
                from .resilience import read_manifest
                state = (read_manifest(cur_dir) or {}).get(
                    'trainer_state') or {}
                self._restore_rng(state.get('rng'))
                _logger.warning('anomaly: rolled parameters back to %s '
                                'after %s', cur_dir, err)
            else:
                _logger.warning('anomaly: rollback requested but no '
                                'checkpoint available; skipping batch '
                                '(%s)', err)
        return 'skip'

    def _feed_stream(self, reader, feeder, prefetch, stage_place):
        """(examples, feed_dict) pairs. ``prefetch > 0`` moves reader
        pulls + DataFeeder conversion + H2D staging onto a background
        pipeline; the consumer-side ``next()`` wait is then the
        measured ``trainer_host_wait_seconds`` — near zero when the
        host keeps up, the host-bound fraction when it does not.
        ``stage_place`` is the executor's Partitioner: staging goes
        through its sharded ``device_put`` — batch-dim sharded over
        the mesh on the ParallelExecutor path, plain single-device
        staging on the classic path (PARTITIONING.md; this replaced
        the PR-5 skip-staging clamp)."""
        if prefetch > 0:
            from .reader.prefetch import prefetch_feeds
            return prefetch_feeds(reader, feeder, depth=prefetch,
                                  place=stage_place)

        def gen():
            for data in reader():
                try:
                    n = len(data)
                except TypeError:
                    n = 0
                yield n, feeder.feed(data)
        return gen()

    def _train_loop(self, event_handler, exe, num_epochs, reader, feeder):
        fetch_names = [v.name for v in self.train_func_outputs]
        guard = self._anomaly_guard = getattr(self, '_anomaly_guard',
                                              None)
        cfg = self._checkpoint_config = getattr(self, '_checkpoint_config',
                                                None)
        prefetch = getattr(self, '_prefetch', 0)
        chain_k = getattr(self, '_steps_per_dispatch', 1)
        sync_interval = getattr(self, '_sync_interval', 1)
        if guard is not None:
            sync_interval = 1    # the guard inspects every loss
        lazy = sync_interval > 1
        grad_names = []
        if guard is not None and guard.monitor_gradients:
            grad_names = self._grad_fetch_names()
        reload_exe = self._reload_executor(exe)
        start_epoch, resume_step, global_step = self._maybe_resume(cfg,
                                                                   exe)
        # preemption safety (RESILIENCE.md): SIGTERM/SIGINT set a flag;
        # the loop finishes the in-flight K-step chunk, commits a
        # checkpoint at the chunk boundary, journals `preempt_save`,
        # and returns cleanly — resume is bit-identical to an
        # uninterrupted run. Handlers only install on the main thread
        # (signal.signal raises elsewhere) and when a checkpoint config
        # with preempt_save is present.
        import signal as _signal
        import threading as _threading
        preempt = {'sig': None}
        prev_handlers = {}
        if cfg is not None and getattr(cfg, 'preempt_save', True) and \
                _threading.current_thread() is _threading.main_thread():
            def _on_preempt(signum, frame):
                preempt['sig'] = signum
            for s in (_signal.SIGTERM, _signal.SIGINT):
                try:
                    prev_handlers[s] = _signal.signal(s, _on_preempt)
                except (ValueError, OSError):  # pragma: no cover
                    pass
        # telemetry (OBSERVABILITY.md): per-step metrics into the
        # process registry + typed records into the installed journal
        reg = _obs.default_registry()
        m_steps = reg.counter('trainer_steps_total',
                              'optimizer steps completed')
        m_examples = reg.counter('trainer_examples_total',
                                 'training examples consumed')
        m_step_wall = reg.histogram('trainer_step_seconds',
                                    'one training step wall time')
        m_steps_ps = reg.gauge('trainer_steps_per_second',
                               'steps/s over the current train() call')
        m_examples_ps = reg.gauge(
            'trainer_examples_per_second',
            'examples/s over the current train() call')
        m_ttfs = reg.gauge(
            'trainer_time_to_first_step_seconds',
            'train() entry to first completed step (compile included)')
        m_loss = reg.gauge('trainer_last_loss', 'last fetched loss')
        m_host_wait = reg.histogram(
            'trainer_host_wait_seconds',
            'time the train loop blocked on the next host batch (feed '
            'conversion + H2D not overlapped by prefetch)')
        m_dispatch = reg.histogram(
            'trainer_dispatch_seconds',
            'Executor dispatch wall per chunk (1 step, or K chained)')
        loop_t0 = time.monotonic()
        steps_done = examples_done = 0
        # perf observatory (OBSERVABILITY.md): the step program's
        # fingerprint keys its compiled ledger; computed once per
        # train() call, joined per step in flush()
        perf_fp = self.train_program.fingerprint()
        _obs.emit('train_begin', epochs=num_epochs,
                  start_epoch=start_epoch, global_step=global_step,
                  prefetch=prefetch, steps_per_dispatch=chain_k)
        # root of this run's span tree; under the launcher a worker
        # inherits the host-level parent via PTPU_TRACE_PARENT, so
        # trees from every host merge under one trace id
        tspan = _obs.start_span('train/run',
                                parent=_obs.parent_from_env(),
                                epochs=num_epochs,
                                steps_per_dispatch=chain_k)

        def flush(epoch_id, chunk):
            """Dispatch a collected chunk (1 step, or K chained) and run
            the per-step bookkeeping/events for each member."""
            nonlocal global_step, steps_done, examples_done
            want_fetch = bool(grad_names) or any(
                b.fetch_metrics for _, b, _, _, _ in chunk)
            run_fetches = (fetch_names + grad_names) if want_fetch \
                else []
            gs0 = global_step
            # activated on the loop thread so exe/run | exe/chain and
            # their verify/compile/dispatch children nest underneath.
            # A 1-step chunk IS the step: no wrapper span — exe/run and
            # train/step hang off train/run directly, keeping the
            # default steps_per_dispatch=1 path inside the tracing
            # overhead budget
            cspan = _obs.start_span('train/chunk', steps=len(chunk),
                                    global_step=gs0) \
                if len(chunk) > 1 else None
            t0 = time.monotonic()
            # ONE dispatch surface for both executors: the PE facade
            # forwards to the same Executor.run/run_chained (sharded
            # when its Partitioner's mesh is real) — the PR-5 clamps
            # (K forced to 1, no staging on the PE path) are gone.
            try:
                if len(chunk) > 1:
                    outs_steps = exe.run_chained(
                        feed_list=[c[2] for c in chunk],
                        fetch_list=run_fetches, async_fetch=lazy)
                else:
                    outs_steps = [exe.run(feed=chunk[0][2],
                                          fetch_list=run_fetches,
                                          async_fetch=lazy)]
            finally:
                if cspan is not None:
                    cspan.end()
            dispatch_wall = time.monotonic() - t0
            m_dispatch.observe(dispatch_wall)
            per_step = dispatch_wall / len(chunk)
            # live MFU/roofline series: one dict probe when nothing is
            # ledgered, two gauge stores when capture is on
            _obs.perf.publish_step(perf_fp, per_step)
            for (step_id, begin, feed, examples, wait_s), outs in zip(
                    chunk, outs_steps):
                metrics = outs[:len(fetch_names)] if want_fetch else outs
                grad_norm = None
                if guard is not None and want_fetch:
                    # guard active => sync_interval forced to 1, so the
                    # metrics here are concrete (materialized) values
                    err = None
                    if guard.check_metrics and metrics:
                        err = guard.inspect_loss(metrics[0])
                    if err is None and grad_names:
                        grad_norm = _anomaly.global_norm(
                            outs[len(fetch_names):])
                        err = guard.inspect_grad_norm(grad_norm)
                    if err is not None:
                        # post-step detection: the update already ran,
                        # so 'skip_batch' can only log; 'rollback'
                        # restores the last good params; 'raise' stops
                        self._handle_anomaly(err, reload_exe)
                global_step += 1
                steps_done += 1
                examples_done += examples
                step_wall = wait_s + per_step
                elapsed = time.monotonic() - loop_t0
                m_steps.inc()
                m_examples.inc(examples)
                m_step_wall.observe(step_wall)
                if elapsed > 0:
                    m_steps_ps.set(steps_done / elapsed)
                    m_examples_ps.set(examples_done / elapsed)
                if steps_done == 1:
                    m_ttfs.set(elapsed)
                loss = None
                if metrics and (not lazy or
                                global_step % sync_interval == 0):
                    # materialization point: with sync_interval=M only
                    # every M-th step pays the device->host loss sync
                    loss = _scalar_or_none(metrics[0])
                if loss is not None:
                    m_loss.set(loss)
                if _obs.journal_active():
                    rec = {'epoch': epoch_id, 'step': step_id,
                           'global_step': global_step,
                           'dur_s': round(step_wall, 6),
                           'feed_wait': round(wait_s, 6),
                           'dispatch_s': round(per_step, 6),
                           'examples': examples,
                           'examples_per_s': round(
                               examples_done / elapsed, 3)
                           if elapsed > 0 else 0.0}
                    if len(chunk) > 1:
                        rec['chain'] = len(chunk)
                    if loss is not None:
                        rec['loss'] = loss
                    if grad_norm is not None:
                        rec['grad_norm'] = grad_norm
                    _obs.emit('step_end', **rec)
                    # pre-measured: the step's share of the chunk
                    # dispatch plus its own host wait. parent=None
                    # (1-step chunk) inherits the thread's active
                    # train/run span — never a fresh root, since the
                    # journal is active here and train/run is too
                    _obs.emit_span('train/step', step_wall,
                                   parent=cspan, step=step_id,
                                   global_step=global_step)
                event_handler(EndStepEvent(epoch_id, step_id, metrics))
            if cfg is not None and (global_step // cfg.step_interval) \
                    > (gs0 // cfg.step_interval):
                # chunk-granular: the scope holds chunk-END state, so
                # the checkpoint records the chunk's last step (for
                # K=1 this is exactly the old per-step behavior)
                self._save_progress_checkpoint(cfg, epoch_id,
                                               chunk[-1][0], global_step,
                                               exe=exe)

        def commit_preempt(epoch_id, last_step):
            """Chunk-boundary preemption commit: the scope holds the
            state of the last FLUSHED chunk, so this checkpoint resumes
            exactly where the dispatch stream stopped."""
            sig = preempt['sig']
            self._save_progress_checkpoint(cfg, epoch_id, last_step,
                                           global_step, exe=exe,
                                           force=True)
            reg.counter('resilience_preempt_saves_total',
                        'chunk-boundary checkpoints committed on '
                        'SIGTERM/SIGINT').inc()
            _obs.emit('preempt_save', signal=int(sig), epoch=epoch_id,
                      step=last_step, global_step=global_step)
            j = _obs.get_journal()
            if j is not None:
                # the process is about to die: buffered records (this
                # preempt_save included) must hit disk now
                j.flush()
            _logger.warning(
                'preemption (signal %d): committed checkpoint at chunk '
                'boundary (epoch %d, step %d, global step %d); exiting '
                'cleanly', sig, epoch_id, last_step, global_step)

        try:
            for epoch_id in range(start_epoch, num_epochs):
                event_handler(BeginEpochEvent(epoch_id))
                _obs.emit('epoch_begin', epoch=epoch_id)
                epoch_t0 = time.monotonic()
                epoch_steps0 = steps_done
                stream = self._feed_stream(reader, feeder, prefetch,
                                           exe.partitioner)
                try:
                    step_id = -1
                    chunk = []  # [(step_id, begin, feed, examples, wait_s)]
                    while True:
                        if self.__stop:
                            return
                        t_wait = time.monotonic()
                        try:
                            examples, feed = next(stream)
                        except StopIteration:
                            break
                        wait_s = time.monotonic() - t_wait
                        step_id += 1
                        if epoch_id == start_epoch and \
                                step_id <= resume_step:
                            continue  # completed before the restart
                        # deterministic preemption-delivery site: a
                        # FaultPlan action here (e.g. os.kill SIGTERM)
                        # lands mid-chunk at an exact step index
                        _fi.maybe_fault(_fi.SITE_TRAINER_STEP)
                        begin = BeginStepEvent(epoch_id, step_id)
                        event_handler(begin)
                        _obs.emit('step_begin', epoch=epoch_id,
                                  step=step_id, global_step=global_step)
                        m_host_wait.observe(wait_s)
                        if guard is not None and guard.check_feeds:
                            err = guard.inspect_feed(feed)
                            if err is not None and self._handle_anomaly(
                                    err, reload_exe) == 'skip':
                                # batch never reaches the device: params
                                # stay clean; the event stream still
                                # advances so step counts match an
                                # un-poisoned run
                                global_step += 1
                                _obs.emit('step_end', epoch=epoch_id,
                                          step=step_id,
                                          global_step=global_step,
                                          skipped='anomaly')
                                event_handler(EndStepEvent(epoch_id,
                                                           step_id,
                                                           None))
                                continue
                        chunk.append((step_id, begin, feed, examples,
                                      wait_s))
                        if len(chunk) >= chain_k:
                            flush(epoch_id, chunk)
                            chunk = []
                            if preempt['sig'] is not None:
                                # the in-flight chunk just committed;
                                # checkpoint at its boundary and leave
                                commit_preempt(epoch_id, step_id)
                                return
                    if chunk:
                        flush(epoch_id, chunk)  # epoch tail (< K steps)
                    if preempt['sig'] is not None:
                        commit_preempt(epoch_id, step_id)
                        return
                finally:
                    close = getattr(stream, 'close', None)
                    if close is not None:
                        close()   # stop the prefetch worker promptly
                event_handler(EndEpochEvent(epoch_id))
                epoch_wall = time.monotonic() - epoch_t0
                _obs.emit('epoch_end', epoch=epoch_id,
                          steps=steps_done - epoch_steps0,
                          dur_s=round(epoch_wall, 6))
                if cfg is not None and \
                        (epoch_id + 1) % cfg.epoch_interval == 0:
                    # recorded as "epoch_id+1, nothing done yet": a
                    # resume lands at the top of the NEXT epoch, not a
                    # replay
                    self._save_progress_checkpoint(cfg, epoch_id + 1,
                                                   -1, global_step,
                                                   exe=exe)
        finally:
            tspan.end(steps=steps_done)
            for s, h in prev_handlers.items():
                try:
                    _signal.signal(s, h)
                except (ValueError, OSError):  # pragma: no cover
                    pass

    def _test_by_executor(self, reader, feed_order, fetch_list):
        with executor.scope_guard(self.scope):
            feeder = self._feeder(self.test_program, feed_order)
            exe = executor.Executor(self.place)
            accumulated = len(fetch_list) * [0]
            count = 0
            for data in reader():
                outs = exe.run(program=self.test_program,
                               feed=feeder.feed(data),
                               fetch_list=[v.name for v in fetch_list])
                # first element per metric, as a PLAIN float: scripts do
                # np.array(trainer.test(...)).mean(), which chokes on a
                # mix of scalars and shaped arrays (hl recommender)
                import numpy as np
                accumulated = [x[0] + float(np.asarray(x[1]).ravel()[0])
                               for x in zip(accumulated, outs)]
                count += 1
            return [x / count for x in accumulated]

    def _get_parallel_executor(self):
        return getattr(self, 'parallel_executor', None)

    def _get_or_create_parallel_executor(self):
        if self._get_parallel_executor() is None:
            self.parallel_executor = parallel_executor.ParallelExecutor(
                use_cuda=False, main_program=self.train_program,
                loss_name=self.train_func_outputs[0].name,
                zero_stage=getattr(self, '_zero_stage', None),
                zero_bucket_bytes=getattr(self, '_zero_bucket_bytes',
                                          None))
        return self._get_parallel_executor()


def _scalar_or_none(value):
    """First element of a fetched metric as a plain float, or None for
    non-numeric/empty fetches (journal fields must stay JSON-clean)."""
    try:
        v = float(np.asarray(value).ravel()[0])
    except (TypeError, ValueError, IndexError):
        return None
    return v


def build_feed_var_list(program, feed_order):
    if not isinstance(program, framework.Program):
        raise TypeError("The 'program' should be an object of Program")
    if feed_order is None:
        feed_order = [op.outputs['Out'][0]
                      for op in program.global_block().ops
                      if op.type == 'feed']
    if isinstance(feed_order, list):
        return [program.global_block().var(name) for name in feed_order]
    if not isinstance(feed_order, dict):
        raise TypeError(
            "The 'feed_order' should be either None, list or dict.")
    if sorted(feed_order.values()) != list(range(len(feed_order))):
        raise ValueError("The values of 'feed_order' should be a "
                         "permutation of [0, len(feed_order))")
    sorted_pairs = sorted(feed_order.items(), key=lambda item: item[1])
    return [program.global_block().var(name) for name, _ in sorted_pairs]
