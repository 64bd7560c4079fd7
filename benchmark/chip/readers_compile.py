"""Readers of the program's compile log
(``paddle_tpu.observability.perf.compile_log()``): what jax said of
every trace, jaxpr -> MLIR lowering, backend compile and cache load,
filed under the Executor phase it fell in, and the Executor's own
account of each cache miss. They lay ``setup_s`` out by layer and count
what compiled inside the window.

The harness reads the metrics after the window and the traced stretch
and before the reference runs, so the log holds the program's whole
life and nothing of the reference. An entry is placed by ``t``, the
``time.perf_counter()`` reading at its end: before the window is from
``man.t_start`` (the run's start) to ``man.t_start + setup_s``, in the
window the ``window_s`` seconds after that. A program without the log (before PR 35) gives the readers
nothing to read: each returns None and the metric is left out.
"""


def _entries(ctx, where):
    try:
        from paddle_tpu.observability import perf
        log = perf.compile_log
    except (ImportError, AttributeError):
        return None
    start = ctx['man'].t_start
    lo = start + ctx['setup_s']
    if where == 'before':
        return [e for e in log() if start <= e['t'] < lo]
    if where == 'window':
        return [e for e in log() if lo <= e['t'] < lo + ctx['window_s']]
    raise ValueError('unknown stretch %r' % where)


def miss_sum(ctx, spec):
    """The sum of ``spec['fields']`` over the ``miss`` entries before
    the window: what of set-up the Executor's cache misses spent, part
    by part."""
    entries = _entries(ctx, 'before')
    if entries is None:
        return None
    return sum(e[f] for e in entries if e['kind'] == 'miss'
               for f in spec['fields'])


def entry_count(ctx, spec):
    """How many entries of ``spec['kinds']`` ended in ``spec['where']``
    (``before`` the window or in the ``window``); of the Executor's own
    where ``spec['owner']`` is ``executor`` (a phase was open), with
    ``spec['cache']`` where given. A value, 0 too, wherever the log
    exists."""
    entries = _entries(ctx, spec['where'])
    if entries is None:
        return None
    return sum(1 for e in entries
               if e['kind'] in spec['kinds']
               and (spec.get('owner') != 'executor'
                    or e['phase'] is not None)
               and ('cache' not in spec or e.get('cache') == spec['cache']))
