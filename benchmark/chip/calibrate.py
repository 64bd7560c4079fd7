"""Read, in one process on the chip, what the limits of one cell are set
from: the program's gaps to the reference over many seeds (the lower
reading), the low-precision control's and each planted fault's gaps on
a few (the upper reading). Not part of a benchmark run.

    python3 benchmark/chip/calibrate.py --workload <cell> \
        --seeds 12 --control-seeds 3 [--first-seed N]

Faults are planted in the reference put in the program's place:
``half``  half of the batch left out, the mean taken over the rest;
``shard`` (cells on several chips) one chip's rows alone, as a step
          without the exchange between chips would see them.
A step that returns its state unchanged reads 1 on ``grad`` and
``delta`` by the measure itself and needs no run.
"""
import time
T_START = time.perf_counter()

import argparse     # noqa: E402
import gc           # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', type=int, default=12)
    ap.add_argument('--control-seeds', type=int, default=3)
    ap.add_argument('--full-seeds', type=int, default=12,
                    help='seeds whose whole first gradient is kept on '
                         'the host for grad_err (2 GB each for opt)')
    ap.add_argument('--first-seed', type=int, default=2200000001)
    ap.add_argument('--out', default=None)
    args = ap.parse_args(argv)
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    import run
    run.place_caches()
    import jax
    import numpy as np
    import harness
    import manifest
    man = manifest.Manifest(T_START)
    cell = man.workload(args.workload)
    devices = run.require_chips(cell['chips'])
    cfg = man.config(cell['config'])
    traffic = man.traffic(cell['traffic'])
    model = harness.model_module(cfg)
    ref = model.Reference(cfg)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]

    sess = harness.Session(model, cfg, traffic, devices)
    sess.start()
    got, batches = {}, {}
    for i, seed in enumerate(seeds):
        if i:
            sess.restart()
        wkey = jax.random.fold_in(harness.key_of(seed), 0)
        with jax.default_device(devices[0]):
            init = jax.jit(ref.init)(wkey)
            sess.set_params([(n, init[n]) for n, _, _ in ref.leaves()])
            del init
            feeder = harness.Feeder(model, cfg, traffic, seed, sess.stage)
        t = time.perf_counter()
        got[seed] = harness.drive_first_steps(sess, ref, feeder, wkey)
        harness.log('seed %d program steps %.1f s losses %s' % (
            seed, time.perf_counter() - t, got[seed]['loss']))
        if i >= args.full_seeds:
            got[seed]['grad_full'] = None
        if i < args.control_seeds:
            batches[seed] = [{k: np.asarray(v) for k, v in b.items()}
                             for b in feeder.first(3)]
        feeder = None
    sess.close()
    sess = None
    gc.collect()

    def cut(bs, frac):
        return [{k: v[:max(1, int(len(v) * frac))] for k, v in b.items()}
                for b in bs]

    rows = []
    control = model.ControlDots()

    def variants_of(bs):
        out = [('control', bs, control, devices),
               ('half', cut(bs, 0.5), None, devices)]
        if len(devices) > 1:
            out.append(('shard', cut(bs, 1.0 / len(devices)), None,
                        devices[:1]))
        return out

    if batches:
        # the reference step of every variant compiled at once, in
        # threads: a float32 step takes minutes to compile, and the
        # persistent cache then serves the calls below
        from concurrent.futures import ThreadPoolExecutor
        import jax.numpy as jnp
        bs0 = next(iter(batches.values()))
        wkey0 = jax.random.fold_in(harness.key_of(seeds[0]), 0)

        def warm(job):
            _, bs, dot, devs = job
            jstep, init, put = harness._reference_step_fn(
                ref, dot, tuple(devs))
            with jax.default_device(devs[0]):
                params, opt_state = init(wkey0)
                jstep.lower(params, opt_state, put(bs[0]),
                            jnp.float32(1)).compile()

        jobs = [('program', bs0, None, devices)] + variants_of(bs0)
        t = time.perf_counter()
        with ThreadPoolExecutor(len(jobs)) as pool:
            list(pool.map(warm, jobs))
        harness.log('reference steps compiled in %.1f s'
                    % (time.perf_counter() - t))
    for i, seed in enumerate(seeds):
        wkey = jax.random.fold_in(harness.key_of(seed), 0)
        if seed in batches:
            bs = batches[seed]
        else:
            with jax.default_device(devices[0]):
                f = harness.Feeder(model, cfg, dict(traffic,
                                                    placement='host'),
                                   seed, None)
            bs = f.first(3)
        t = time.perf_counter()
        want = harness.reference_steps(ref, wkey, bs, tuple(devices))
        ref_s = time.perf_counter() - t
        numbers, where = harness.compare(got[seed], want)
        row = {'seed': seed, 'kind': 'program', 'numbers': numbers,
               'where': where, 'reference_s': ref_s,
               'loss': got[seed]['loss'], 'ref_loss': want['loss']}
        rows.append(row)
        harness.log(json.dumps(row))
        got[seed] = None
        if i < args.control_seeds:
            for kind, vb, dot, devs in variants_of(bs):
                alt = harness.reference_steps(ref, wkey, vb, tuple(devs),
                                              dot=dot)
                numbers, where = harness.compare(alt, want)
                row = {'seed': seed, 'kind': kind, 'numbers': numbers,
                       'where': where, 'loss': alt['loss']}
                rows.append(row)
                harness.log(json.dumps(row))
    out = args.out or os.path.join(ROOT, 'chiprun_out',
                                   'calibrate_%s.json' % args.workload)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, 'w') as f:
        json.dump(rows, f, indent=1)
    for kind in ('program', 'control', 'half', 'shard'):
        sel = [r['numbers'] for r in rows if r['kind'] == kind]
        if sel:
            print(kind, {k: (min(r[k] for r in sel), max(r[k] for r in sel))
                         for k in sel[0]}, flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
