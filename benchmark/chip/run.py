"""The chip benchmark's one command.

    python3 benchmark/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

One process that holds the chips the cell asks for: loads, warms up,
measures for ``--seconds``, compares what the timed path produced with
the plain reference, prints one JSON object as its last line, exits.
Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result: there is no CPU fallback.
"""
import time
T_START = time.perf_counter()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE = os.path.join(HERE, '_cache')


def place_caches():
    """One compile cache at a fixed path inside the checkout, unless the
    machine names one; the program's own rule takes the variable. Put
    the benchmark's files on the import path."""
    os.environ.setdefault('JAX_COMPILATION_CACHE_DIR',
                          os.path.join(CACHE, 'jax'))
    os.environ.setdefault('PADDLE_TPU_TUNING_CACHE',
                          os.path.join(CACHE, 'tuning_cache.json'))
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    for p in (ROOT, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)


def require_chips(n):
    """The devices this run may use, or exit 2."""
    import jax
    devs = jax.devices()
    if jax.default_backend() != 'tpu' or len(devs) < n:
        sys.stderr.write(
            'chip-bench: needs %d TPU chip(s); jax.default_backend() is '
            '%r with %d device(s). Refusing to run.\n'
            % (n, jax.default_backend(), len(devs)))
        sys.exit(2)
    return devs[:n]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    ap.add_argument('--keep-trace', default=None,
                    help='copy the traced run\'s .xplane.pb here')
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, 'paddle_tpu')):
        sys.stderr.write('chip-bench: no program beside the benchmark '
                         '(%s has no paddle_tpu/)\n' % ROOT)
        sys.exit(2)
    place_caches()
    import manifest
    import harness
    man = manifest.Manifest(T_START)
    cell = man.workload(args.workload)
    devices = require_chips(cell['chips'])
    out_dir = os.path.join(CACHE, 'run')
    shutil.rmtree(out_dir, ignore_errors=True)
    try:
        result = harness.run_cell(man, args.workload, args.seed,
                                  args.seconds, bool(args.trace), devices,
                                  out_dir, keep_trace=args.keep_trace)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
