"""Record the small trace the program readers' test reads
(``tests/tiny_step.xplane.pb``) and the scopes of the program that ran
(``tests/tiny_step.scopes.json``): three steps of a tiny Fluid training
program (conv, batch norm, fc, momentum) through ``Executor.run`` on
whatever device JAX has, each step under the harness's ``feed`` /
``dispatch`` / ``fetch`` spans. Run once on the chip:

    python3 benchmark/chip/tests/record_tiny_step.py <out dir>
"""
import json
import os
import shutil
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for p in (ROOT, CHIP):
    if p not in sys.path:
        sys.path.insert(0, p)


def build(fluid):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[16, 32, 32], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='int64')
        h = fluid.layers.conv2d(x, num_filters=32, filter_size=3,
                                padding=1, bias_attr=False)
        h = fluid.layers.batch_norm(h, act='relu')
        h = fluid.layers.conv2d(h, num_filters=32, filter_size=3,
                                padding=1, bias_attr=False)
        p = fluid.layers.fc(h, size=10, act='softmax')
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=p, label=y))
        fluid.optimizer.Momentum(learning_rate=1e-4,
                                 momentum=0.9).minimize(loss)
    return main, startup, loss


def main(out):
    import jax
    import paddle_tpu.fluid as fluid
    from paddle_tpu.observability import perf
    import reduce_trace
    dev = jax.devices()[0]
    place = fluid.TPUPlace(dev.id) if dev.platform == 'tpu' \
        else fluid.CPUPlace(0)
    prog, startup, loss = build(fluid)
    exe, scope = fluid.Executor(place), fluid.Scope()
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    feed = {'x': jax.device_put(rng.rand(64, 16, 32, 32).astype('float32'),
                                dev),
            'y': jax.device_put(rng.randint(0, 10, (64, 1)).astype('int64'),
                                dev)}

    def step(ann):
        with ann('feed'):
            f = feed
        with ann('dispatch'):
            h = exe.run(prog, feed=f, fetch_list=[loss], scope=scope,
                        return_numpy=False)[0]
        with ann('fetch'):
            return float(np.asarray(h.data).reshape(-1)[0])

    for _ in range(3):
        step(jax.profiler.TraceAnnotation)
    tdir = os.path.join(out, '_tiny_step')
    jax.profiler.start_trace(tdir)
    losses = [step(jax.profiler.TraceAnnotation) for _ in range(3)]
    jax.profiler.stop_trace()
    shutil.copy(reduce_trace.find_xplane(tdir),
                os.path.join(out, 'tiny_step.xplane.pb'))
    shutil.rmtree(tdir)
    scopes = perf.scope_map(min_runs=2)
    with open(os.path.join(out, 'tiny_step.scopes.json'), 'w') as f:
        json.dump(scopes, f, indent=0, sort_keys=True)
    print(dev.device_kind, losses, os.path.getsize(
        os.path.join(out, 'tiny_step.xplane.pb')), 'bytes;',
        {k: len(v) for k, v in scopes.items()})


if __name__ == '__main__':
    main(sys.argv[1])
