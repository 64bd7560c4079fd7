"""One run of a cell whose window layers ignore their window (every
``flash_attention`` op attends as a full layer does), through the
harness's own comparison: the result line must read ``correct`` false,
or the cell's limits cannot tell a window from none. The tests make the
same run at the tiny size (``tests/test_afmoe.py``); this is the real
size, on the chip. Not part of a benchmark run.

    python3 benchmark/chip/check_window_ignored.py --workload <cell> \
        --seed <n> [--seconds S]
"""
import time
T_START = time.perf_counter()

import argparse     # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def ignore_the_window(sess):
    ops = [op for op in sess.main.global_block().ops
           if op.type == 'flash_attention']
    windowed = [op for op in ops if op.attrs.get('window')]
    if not windowed:
        raise SystemExit('no flash_attention op of this cell has a window')
    for op in windowed:
        op.attrs['window'] = 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, default=2.0)
    args = ap.parse_args(argv)
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    import run
    run.place_caches()
    import harness
    import manifest
    man = manifest.Manifest(T_START)
    devices = run.require_chips(man.workload(args.workload)['chips'])
    out_dir = os.path.join(run.CACHE, 'run_window_ignored')
    try:
        result = harness.run_cell(man, args.workload, args.seed, args.seconds,
                                  False, devices, out_dir,
                                  break_path=ignore_the_window)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    print(json.dumps({'correct': result['correct'],
                      'compared': result['compared']}), flush=True)
    return 0 if result['correct'] is False else 1


if __name__ == '__main__':
    sys.exit(main())
