"""Worker for the 2-process jax.distributed test (VERDICT r3 #3).

Launched by tests/test_distributed_multiproc.py with:
  JAX_PLATFORMS=cpu
  XLA_FLAGS=--xla_force_host_platform_device_count=2
  PADDLE_TPU_DISTRIBUTED=1
  PTPU_TRAINER_ID={0,1}  PTPU_COORD=127.0.0.1:<port>

Mirrors the reference's multi-trainer launch
(transpiler/distribute_transpiler.py:159: one process per trainer,
PADDLE_TRAINER_ID + pserver endpoint env): DistributeTranspiler
.transpile() bootstraps jax.distributed, then ParallelExecutor runs the
SAME program data-parallel over the 4-device global mesh, each process
feeding its local half of the batch. Prints per-step losses as JSON.
"""
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# A CPU test worker: pin the CPU backend BEFORE any backend
# initialization, with gloo for cross-process CPU collectives.
import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')
jax.config.update('jax_cpu_collectives_implementation', 'gloo')

import paddle_tpu.fluid as fluid  # noqa: E402


def main():
    trainer_id = int(os.environ['PTPU_TRAINER_ID'])
    coord = os.environ['PTPU_COORD']
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

    # transpile: bootstraps jax.distributed AND ZeRO-slices the Adam
    # accumulators over the dp axis, so this test also exercises
    # dp-SHARDED state across processes (not just replicated params)
    t = fluid.DistributeTranspiler()
    t.transpile(trainer_id=trainer_id, program=main_p, pservers=coord,
                trainers=2)
    assert t.sliced_vars, "expected ZeRO-sliced accumulators"
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 4, jax.devices()

    rng = np.random.RandomState(0)
    xs = rng.randn(8, 6).astype('float32')
    ys = (xs.sum(1, keepdims=True) * 0.3).astype('float32')
    # this process's local batch shard: rows [id*4, id*4+4)
    lo = trainer_id * 4
    feed = {'x': xs[lo:lo + 4], 'y': ys[lo:lo + 4]}

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    pexe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                  main_program=main_p)
    losses = []
    for _ in range(4):
        l, = pexe.run(fetch_list=[loss], feed=feed)
        losses.append(float(np.ravel(np.asarray(l))[0]))
    print('LOSSES=%s' % json.dumps(losses))

    # ---- tp ACROSS processes: mesh ('tp', 'dp') puts the tp pairs on
    # different processes, so the activation psum rides the gloo
    # cross-process transport (the multi-host ICI/DCN analogue)
    from jax.sharding import Mesh
    from paddle_tpu.parallel.mesh import set_mesh
    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2), ('tp', 'dp'))
    set_mesh(mesh)
    main2, startup2 = fluid.Program(), fluid.Program()
    main2.random_seed = startup2.random_seed = 5
    with fluid.program_guard(main2, startup2):
        x = fluid.layers.data(name='x', shape=[6], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        h = fluid.layers.fc(
            x, size=16, act='relu',
            param_attr=fluid.ParamAttr(name='tp_w1',
                                       sharding=(None, 'tp')))
        pred = fluid.layers.fc(
            h, size=1,
            param_attr=fluid.ParamAttr(name='tp_w2',
                                       sharding=('tp', None)))
        loss2 = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss2)
    # with ('tp', 'dp') every process's devices span BOTH dp shards, so
    # each process feeds the FULL batch (replicated over tp); the dp
    # split happens inside make_array_from_process_local_data
    full_feed = {'x': xs, 'y': ys}
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe2 = fluid.Executor(fluid.CPUPlace())
        exe2.run(startup2)
        pexe2 = fluid.ParallelExecutor(use_cuda=False,
                                       loss_name=loss2.name,
                                       main_program=main2, mesh=mesh)
        tp_losses = []
        for _ in range(3):
            l, = pexe2.run(fetch_list=[loss2], feed=full_feed)
            tp_losses.append(float(np.ravel(np.asarray(l))[0]))
    set_mesh(None)
    print('TP_LOSSES=%s' % json.dumps(tp_losses))


if __name__ == '__main__':
    main()
