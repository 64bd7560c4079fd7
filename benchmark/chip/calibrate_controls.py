"""The upper readings of a one-chip cell's limits, one reference step on
the device at a time: the low-precision control's and the ``half``
fault's (half of the batch left out; where the batch is one sequence,
half of its tokens) gaps to the plain reference over a few seeds. ``calibrate.py``
reads the same numbers but warms its three reference steps up at once,
each with its own parameters and Adam state on the device, which a
configuration of 0.7 B parameters does not fit three times (25 GB); run
it with ``--control-seeds 0`` for the program's readings and this beside
it, over the same seeds (``--first-seed`` + 7919 i, as there). The next
``benchmark`` PR folds this one-step-at-a-time path into ``calibrate.py``
and deletes this file (ROADMAP, D18). Not part of a benchmark run.

    python3 benchmark/chip/calibrate_controls.py --workload <cell> \
        --seeds 3 [--first-seed N] [--budget-s S] [--no-half]
"""
import time
T_START = time.perf_counter()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', type=int, default=3)
    ap.add_argument('--first-seed', type=int, default=2200000001)
    ap.add_argument('--budget-s', type=float, default=None,
                    help='start no further seed once this many seconds '
                         'have passed')
    ap.add_argument('--no-half', action='store_true',
                    help='read the control alone')
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
    # one object for every seed: the jitted step is kept by its dots
    control = model.ControlDots()
    rows = []
    for i in range(args.seeds):
        if args.budget_s and time.perf_counter() - T_START > args.budget_s:
            harness.log('budget spent: %d of %d seeds read'
                        % (i, args.seeds))
            break
        seed = args.first_seed + 7919 * i
        wkey = jax.random.fold_in(harness.key_of(seed), 0)
        with jax.default_device(devices[0]):
            feeder = harness.Feeder(
                model, cfg, dict(traffic, placement='host'), seed, None)
        bs = feeder.first(3)
        # half of the batch left out; of one sequence, its second half
        half = [{k: v[:len(v) // 2] if len(v) > 1 else v[:, :v.shape[1] // 2]
                 for k, v in b.items()} for b in bs]
        t = time.perf_counter()
        want = harness.reference_steps(ref, wkey, bs, tuple(devices))
        harness.log('seed %d reference %.1f s' % (
            seed, time.perf_counter() - t))
        kinds = [('control', bs, control)] + (
            [] if args.no_half else [('half', half, None)])
        for kind, vb, dot in kinds:
            t = time.perf_counter()
            alt = harness.reference_steps(ref, wkey, vb, tuple(devices),
                                          dot=dot)
            numbers, where = harness.compare(alt, want)
            row = {'seed': seed, 'kind': kind, 'numbers': numbers,
                   'where': where, 'loss': alt['loss'],
                   'seconds': time.perf_counter() - t}
            rows.append(row)
            harness.log(json.dumps(row))
        want = None
    out = args.out or os.path.join(
        ROOT, 'chiprun_out', 'calibrate_controls_%s.json' % args.workload)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, 'w') as f:
        json.dump(rows, f, indent=1)
    for kind in ('control', 'half'):
        sel = [r['numbers'] for r in rows if r['kind'] == kind]
        if sel:
            print(kind, {k: (min(r[k] for r in sel), max(r[k] for r in sel))
                         for k in sel[0]}, flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
