"""Worker for the multi-host elastic runtime tests + chaos bench.

Launched by tools/launch.py (or tests/test_multihost.py directly) with
the pod env contract (PTPU_NPROC / PTPU_PROC_ID / PTPU_COORD /
PTPU_HB_DIR ...). Trains the same MLP as tests/distributed_worker.py on
a deterministic per-global-step batch, each host feeding its disjoint
row range, checkpointing every step through the CONCURRENT sharded
save path; on PTPU_RESUME=1 it restores the newest healthy checkpoint
(mesh degraded to whatever devices survive via
resilience.partitioner_for_manifest) and continues — bit-exact.

Fault hooks (all env):
  PTPU_DIE_AT=<step> + PTPU_DIE_ID=<rank>  SIGKILL self right before
      running that global step (generation 0 only) — whole-host loss.
  PTPU_PERTURB=<rank>  that rank salts its startup agreement digest;
      every host must fail fast with a typed HostMismatch (exit 3).
  PTPU_CHAINED=1  drive training through run_chained (K=2 chunks) —
      the multi-process scan-globalize path.

Prints one ``STEP <n> <repr(loss)>`` line per step (flushed, so a
killed worker's completed steps stay visible), then ``LOSSES=<json>``
and ``WORLD=<n>``; on resume also ``RESUMED_AT=<step>``.
"""
import json
import os
import signal
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# A CPU test worker: pin the CPU backend BEFORE backend init, gloo
# for cross-process collectives.
import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')
if int(os.environ.get('PTPU_NPROC', '1')) > 1:
    jax.config.update('jax_cpu_collectives_implementation', 'gloo')

import paddle_tpu.fluid as fluid  # noqa: E402
from paddle_tpu import multihost  # noqa: E402

GLOBAL_BATCH = 8


def build_program():
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = 5
    with fluid.program_guard(main_p, startup):
        x = fluid.layers.data(name='x', shape=[6], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        h = fluid.layers.fc(x, size=16, act='relu')
        pred = fluid.layers.fc(h, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=0.1).minimize(loss)
    return main_p, startup, loss


def batch_for_step(step, rank, world):
    """The global batch is a pure function of the global step; host
    ``rank`` of ``world`` feeds its disjoint row range, so any world
    size sees the SAME global data."""
    rng = np.random.RandomState(100 + step)
    xs = rng.randn(GLOBAL_BATCH, 6).astype('float32')
    ys = (xs.sum(1, keepdims=True) * 0.3).astype('float32')
    lo = rank * GLOBAL_BATCH // world
    hi = (rank + 1) * GLOBAL_BATCH // world
    return {'x': xs[lo:hi], 'y': ys[lo:hi]}


def main():
    world = int(os.environ.get('PTPU_NPROC', '1'))
    rank = int(os.environ.get('PTPU_PROC_ID',
                              os.environ.get('PTPU_TRAINER_ID', '0')))
    steps = int(os.environ.get('PTPU_STEPS', '6'))
    ckpt_dir = os.environ.get('PTPU_CKPT_DIR')
    resume = os.environ.get('PTPU_RESUME') == '1'
    generation = int(os.environ.get('PTPU_GENERATION', '0'))
    die_at = os.environ.get('PTPU_DIE_AT')
    die_id = int(os.environ.get('PTPU_DIE_ID', '-1'))
    perturb = os.environ.get('PTPU_PERTURB')
    chained = os.environ.get('PTPU_CHAINED') == '1'

    multihost.start_heartbeat()  # no-op without a launcher's PTPU_HB_DIR

    main_p, startup, loss = build_program()

    # reference-compatible bootstrap surface: transpile joins the pod
    # (bounded handshake -> typed BootstrapTimeout) and ZeRO-slices
    t = fluid.DistributeTranspiler()
    t.transpile(trainer_id=rank, program=main_p,
                pservers=os.environ.get('PTPU_COORD',
                                        '127.0.0.1:6174'),
                trainers=world)
    assert jax.process_count() == world, \
        (jax.process_count(), world)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)

    part = None
    start_step = 0
    if resume and ckpt_dir:
        from paddle_tpu import io as pio
        from paddle_tpu.resilience import read_manifest
        serials = pio._get_checkpoint_serials(ckpt_dir)
        if serials:
            manifest = read_manifest(
                pio._serial_dir(ckpt_dir, serials[-1]))
            from paddle_tpu.resilience import partitioner_for_manifest
            part = partitioner_for_manifest(manifest)
            fluid.io.load_checkpoint(exe, ckpt_dir,
                                     main_program=main_p)
            ts = fluid.io.load_checkpoint_trainer_state(ckpt_dir)
            start_step = int((ts or {}).get('step', 0))
            print('RESUMED_AT=%d' % start_step, flush=True)

    pexe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                  main_program=main_p,
                                  partitioner=part)

    try:
        multihost.agreement_check(
            program=main_p, partitioner=pexe.partitioner,
            extra=('divergent-host-%d' % rank
                   if perturb is not None and int(perturb) == rank
                   else None))
    except multihost.HostMismatch as e:
        print('AGREEMENT_MISMATCH=%s' % e, flush=True)
        sys.exit(3)

    def save(step_done):
        if ckpt_dir:
            fluid.io.save_checkpoint(
                pexe._exe, ckpt_dir, max_num_checkpoints=8,
                save_interval_secs=0, main_program=main_p,
                trainer_state={'step': step_done})

    losses = {}

    def record(step, value):
        value = float(np.ravel(np.asarray(value))[0])
        losses[step] = value
        print('STEP %d %s' % (step, repr(value)), flush=True)

    def maybe_die(step):
        if (die_at is not None and generation == 0
                and rank == die_id and step == int(die_at)):
            os.kill(os.getpid(), signal.SIGKILL)

    s = start_step
    while s < steps:
        if chained and s + 2 <= steps:
            maybe_die(s)
            feeds = [batch_for_step(s + i, rank, world)
                     for i in range(2)]
            outs = pexe.run_chained(feed_list=feeds,
                                    fetch_list=[loss])
            for i, out in enumerate(outs):
                record(s + i, out[0])
            s += 2
        else:
            maybe_die(s)
            l, = pexe.run(fetch_list=[loss],
                          feed=batch_for_step(s, rank, world))
            record(s, l)
            s += 1
        save(s)

    print('LOSSES=%s' % json.dumps(
        {str(k): v for k, v in sorted(losses.items())}), flush=True)
    print('WORLD=%d' % world, flush=True)


if __name__ == '__main__':
    main()
