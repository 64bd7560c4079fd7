"""Readers of what the program names from inside: the Executor's phases
(``exe/run`` around ``exe/prep``, ``exe/launch``, ``exe/commit``), which
it writes into the profiler's trace as ``TraceAnnotation`` events, and
the Fluid scopes (``forward`` / ``transpose(jvp(forward))`` /
``optimizer``, then ``<op.type>[:<output>]``) that its lowering leaves
in each HLO instruction's ``op_name``.

A device trace names operations by instruction only, so the scopes come
from the program's ``observability.perf.scope_map()``: the compiled
modules of the live Executors, read once per run. A program that has no
such function, span or scope (the parent of the PR that brought this
file) gives every reader here nothing to read: it returns None and the
metric is left out of the line.

As a script, on a kept trace and the scopes a run dumped beside it
(``CHIP_BENCH_SCOPES_OUT=<file>`` in the run's environment):

    python3 benchmark/chip/readers_program.py <trace> <scopes.json> \
        [--by phase|type|op|unnamed] [--top N]
"""
import json
import os
import statistics
import sys
import time

import reduce_trace as rt

RUN_SPANS = ('exe/run', 'exe/chain')
SCOPES_OUT_ENV = 'CHIP_BENCH_SCOPES_OUT'


# ---- the Executor's phases, host spans on the profiler's clock -------------
def _inside(events, lo, hi):
    return [e for e in events if lo <= e.start and e.end <= hi]


def span_ms(ctx, spec):
    """Median duration, in ms, of the program's span ``spec['span']``
    over the traced window."""
    if ctx.get('trace') is None:
        return None
    lo, hi = ctx['trace_window']
    durs = [e.dur for e in _inside(ctx['trace']['host'], lo, hi)
            if e.name == spec['span']]
    return 1e3 * statistics.median(durs) if durs else None


def _busiest(trace, lo, hi):
    b = rt.busy(trace, lo, hi)
    return max(b, key=b.get)


def idle_in_run_ms(ctx, spec):
    """Idle time of the busiest device that falls inside one of the
    program's ``exe/run`` (or ``exe/chain``) spans, per step, in ms."""
    if ctx.get('trace') is None:
        return None
    tr = ctx['trace']
    lo, hi = ctx['trace_window']
    runs = rt.clip(rt.union((e.start, e.end) for e in tr['host']
                            if e.name in RUN_SPANS), lo, hi)
    if not runs:
        return None
    ops = tr['devices'][_busiest(tr, lo, hi)]
    gaps = rt.subtract([(lo, hi)], rt.clip(
        rt.union((e.start, e.end) for e in ops), lo, hi))
    inside = rt.total(gaps) - rt.total(rt.subtract(gaps, runs))
    return 1e3 * inside / ctx['trace_steps']


# ---- Fluid scopes on device operations -------------------------------------
def self_times(events):
    """``[(event, seconds)]``: each operation's own time, that of the
    operations nested inside it (a loop's body inside the loop) taken
    off, so that the times add up to the device's busy time."""
    out, stack = [], []
    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= e.start:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= min(e.end, stack[-1][0].end) - e.start
        stack.append([e, e.dur])
    return out + [tuple(s) for s in stack]


def _program_scopes(ctx):
    """``{module: {instruction: op_name}}`` and the program's
    ``split_scope``, or None where the program has neither. A test or
    the script hands the map in as ``ctx['program_scopes']``."""
    try:
        from paddle_tpu.observability import perf
        split = perf.split_scope
    except (ImportError, AttributeError):
        return None
    maps = ctx.get('program_scopes')
    if maps is None:
        from harness import log
        t = time.perf_counter()
        # the step of the loop, not a program that ran once (startup)
        maps = perf.scope_map(min_runs=2)
        log('scope_map(): %d module(s) in %.2f s' % (
            len(maps), time.perf_counter() - t))
        for key, m in maps.items():
            if 'error' in m:
                log('scope_map(): %s not read: %s' % (key, m['error']))
        out = os.environ.get(SCOPES_OUT_ENV)
        if out:
            with open(out, 'w') as f:
                json.dump(maps, f)
    return maps, split


def by_scope(ctx):
    """Per step, on the busiest device, over the traced window:
    ``{'busy': s, 'phase': {phase: s}, 'op': {(phase, Fluid op): s},
    'type': {(phase, op.type): s}, 'unnamed': {instruction: s}}``;
    None without a trace or a scope
    map. Instruction names repeat between modules, so the module taken
    is the one whose names cover most of the device's time. Kept in
    ``ctx`` so that one run reads the program's modules once."""
    if 'by_scope' in ctx:
        return ctx['by_scope']
    ctx['by_scope'] = None
    if ctx.get('trace') is None:
        return None
    got = _program_scopes(ctx)
    if not got:
        return None
    maps, split = got
    tr = ctx['trace']
    lo, hi = ctx['trace_window']
    times = self_times(_inside(tr['devices'][_busiest(tr, lo, hi)], lo, hi))
    best, cover = None, 0.0
    for key, m in maps.items():
        c = sum(s for e, s in times if e.name in m)
        if 'error' not in m and c > cover:
            best, cover = m, c
    if best is None:
        return None
    n = float(ctx['trace_steps'])
    res = {'busy': sum(s for _, s in times) / n, 'phase': {}, 'op': {},
           'type': {}, 'unnamed': {}}
    for e, s in times:
        name = best.get(e.name)
        if name is None:
            res['unnamed'][e.name] = res['unnamed'].get(e.name, 0.0) + s / n
            continue
        phase, op = split(name)
        res['phase'][phase] = res['phase'].get(phase, 0.0) + s / n
        res['op'][phase, op] = res['op'].get((phase, op), 0.0) + s / n
        # `a:x+b:y` is a fusion that does both (a weight gradient's
        # matmul with the update as its epilogue): type `a+b`
        kind = (phase, '+'.join(p.split(':')[0] for p in op.split('+'))
                if op else None)
        res['type'][kind] = res['type'].get(kind, 0.0) + s / n
    ctx['by_scope'] = res
    if 'program_scopes' not in ctx:
        # a run leaves the tables on stderr, for PERF.md
        from harness import log
        for line in table(res, 'phase') + table(res, 'type', 12) + \
                table(res, 'op', 12) + table(res, 'unnamed', 5):
            log(line)
    return res


def phase_ms(ctx, spec):
    """Device time per step, in ms, of the operations whose phase is
    ``spec['phase']`` (``forward``, ``backward`` or ``optimizer``)."""
    res = by_scope(ctx)
    if res is None or spec['phase'] not in res['phase']:
        return None
    return 1e3 * res['phase'][spec['phase']]


def scope_coverage(ctx, spec):
    """Share, in %, of the busiest device's busy time whose operation
    got a phase."""
    res = by_scope(ctx)
    if res is None or res['busy'] <= 0:
        return None
    return 100.0 * sum(res['phase'].values()) / res['busy']


# ---- the table PERF.md's "Where the time goes" is written from -------------
def table(res, by='op', top=10):
    rows = sorted(res[by].items(), key=lambda kv: -kv[1])[:top]
    head = 'device time a step by %s (busy %.3f ms):' % (
        by, 1e3 * res['busy'])
    return [head] + ['  %8.3f ms %5.1f %%  %s' % (
        1e3 * s, 100.0 * s / res['busy'],
        ' '.join(map(str, k)) if isinstance(k, tuple) else k)
        for k, s in rows]


def main(argv):
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument('trace')
    ap.add_argument('scopes', help='the JSON a run dumped under %s'
                    % SCOPES_OUT_ENV)
    ap.add_argument('--by', choices=('phase', 'type', 'op', 'unnamed'),
                    default=None, help='one table; all four without')
    ap.add_argument('--top', type=int, default=10)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if root not in sys.path:
        sys.path.insert(0, root)
    path = rt.find_xplane(args.trace) if os.path.isdir(args.trace) \
        else args.trace
    tr = rt.load(path)
    runs = [e for e in tr['host'] if e.name in RUN_SPANS]
    lo, hi = rt.window_of(tr, 'feed')[0], rt.window_of(tr, 'fetch')[1]
    with open(args.scopes) as f:
        scopes = json.load(f)
    ctx = {'trace': tr, 'trace_window': (lo, hi),
           'trace_steps': max(1, len(_inside(runs, lo, hi))),
           'program_scopes': scopes}
    res = by_scope(ctx)
    if res is None:
        print('no scope of %s matches an operation of %s'
              % (args.scopes, path))
        return 1
    for by in [args.by] if args.by else ['phase', 'type', 'op', 'unnamed']:
        print('\n'.join(table(res, by, args.top)))
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
