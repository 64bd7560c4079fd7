"""Benchmark harness (parity: benchmark/fluid/fluid_benchmark.py CLI).

Runs one model's training loop on synthetic data and reports throughput:

    python benchmark/fluid/fluid_benchmark.py --model resnet \
        --batch_size 64 --iterations 20 [--device TPU|CPU] [--pass_num N]

Models: mnist, vgg, resnet, se_resnext, stacked_dynamic_lstm,
machine_translation (same set the reference benchmarks).
"""
import argparse
import json
import os
import sys
import time

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
for _p in (_HERE, os.path.dirname(os.path.dirname(_HERE))):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import paddle_tpu.fluid as fluid  # noqa: E402
from models import MODELS  # noqa: E402


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument('--model', default='resnet', choices=sorted(MODELS))
    p.add_argument('--batch_size', type=int, default=32)
    p.add_argument('--iterations', type=int, default=20)
    p.add_argument('--skip_batch_num', type=int, default=3,
                   help='warmup steps excluded from timing')
    p.add_argument('--device', default='TPU', choices=['TPU', 'CPU'])
    p.add_argument('--learning_rate', type=float, default=0.01)
    p.add_argument('--pass_num', type=int, default=1,
                   help='repeat the timed loop this many times')
    p.add_argument('--no_random', action='store_true')
    return p.parse_args(argv)


def main(argv=None):
    """Run the benchmark; returns the record it prints. ``--device
    TPU`` means the TPU: with none in this process the place raises
    here, before any model is built."""
    args = parse_args(argv)
    place = fluid.TPUPlace(0) if args.device == 'TPU' else fluid.CPUPlace()
    device = place.jax_device()
    build = MODELS[args.model]

    main_prog, startup = fluid.Program(), fluid.Program()
    if args.no_random:
        main_prog.random_seed = startup.random_seed = 42
    with fluid.program_guard(main_prog, startup):
        loss, feed_fn, unit = build(args)
        opt = fluid.optimizer.Momentum(learning_rate=args.learning_rate,
                                       momentum=0.9)
        opt.minimize(loss)

    exe = fluid.Executor(place)
    exe.run(startup)

    feed = feed_fn(args.batch_size)
    for _ in range(args.skip_batch_num):
        exe.run(main_prog, feed=feed, fetch_list=[loss])
    t0 = time.perf_counter()
    last = None
    for _ in range(args.pass_num):
        for _ in range(args.iterations):
            last, = exe.run(main_prog, feed=feed, fetch_list=[loss])
    dt = time.perf_counter() - t0
    per_sec = args.pass_num * args.iterations * args.batch_size / dt
    record = {
        'model': args.model,
        'batch_size': args.batch_size,
        'iterations': args.iterations,
        'last_loss': float(np.ravel(last)[0]),
        'throughput': round(per_sec, 2),
        'unit': unit,
        'device': {'platform': device.platform,
                   'kind': device.device_kind},
    }
    print(json.dumps(record))
    return record


if __name__ == '__main__':
    main()
