"""The readers that turn a run's context into one metric each. A metric's
data file names its reader (``module:function``) and its parameters; a
reader that finds nothing to read returns None and the metric is left
out of the line. A later PR adds readers in a module of its own.
"""
import statistics


# ---- end to end: taken by the benchmark itself, host clock -----------------
def rate(ctx, spec):
    """All items of every step completed in the window over the whole
    window's time."""
    return ctx['n_steps'] * ctx['items_per_step'] / ctx['window_s']


def step_percentile(ctx, spec):
    """A percentile of the time between consecutive step completions,
    over all steps of the window, in ms."""
    from harness import percentile
    return 1e3 * percentile(list(ctx['step_gaps_s']), spec['percentile'])


def setup_seconds(ctx, spec):
    return ctx['setup_s']


# ---- per layer -------------------------------------------------------------
def span_median_ms(ctx, spec):
    return 1e3 * statistics.median(ctx['spans'][spec['span']])


def context_value(ctx, spec):
    return ctx[spec['key']]


def counter(ctx, spec):
    return ctx['counters'][spec['counter']]


def mfu(ctx, spec):
    """Operations the forward and backward passes require (the model
    module's count from the configuration's shapes) per second of the
    window, over chips times the bf16 peak."""
    peak = ctx['man'].peaks(ctx['device_kind'])['bf16_flops']
    flops = ctx['model'].required_flops(ctx['cfg'], ctx['traffic'])
    return 100.0 * flops * ctx['n_steps'] / ctx['window_s'] \
        / (ctx['chips'] * peak)


def _per_step_device_time(ctx, pattern, field):
    import reduce_trace
    lo, hi = ctx['trace_window']
    found = reduce_trace.time_by_name(ctx['trace'], pattern, lo, hi, field)
    secs, count = max(found.values())
    return secs / ctx['trace_steps'], count


def kernel_roofline(ctx, spec):
    """The least time the chip could take for the algorithm's work
    (``work`` of the model module: operations and bytes of one step on
    one chip) over the summed device time of the matching operations.
    Nothing matched: nothing returned."""
    if ctx['trace'] is None:
        return None
    secs, count = _per_step_device_time(ctx, spec['pattern'],
                                        spec.get('field', 'name'))
    from harness import log
    log('%s: %d device operations, %.6f s a step'
        % (spec['pattern'], count, secs))
    if count == 0 or secs <= 0:
        return None
    flops, nbytes = getattr(ctx['model'], spec['work'])(
        ctx['cfg'], ctx['traffic'], ctx['chips'])
    peaks = ctx['man'].peaks(ctx['device_kind'])
    least = max(flops / peaks[spec.get('peak', 'bf16_flops')],
                nbytes / peaks['hbm_bytes_per_s'])
    return 100.0 * least / secs


def exposed_collective_ms(ctx, spec):
    if ctx['trace'] is None:
        return None
    import reduce_trace
    lo, hi = ctx['trace_window']
    per_dev = reduce_trace.exposed_collective(ctx['trace'], lo, hi)
    return 1e3 * max(per_dev.values()) / ctx['trace_steps']


def device_idle(ctx, spec):
    """1 - busy / traced window on the busiest device, in %."""
    if ctx['trace'] is None:
        return None
    import reduce_trace
    lo, hi = ctx['trace_window']
    b = reduce_trace.busy(ctx['trace'], lo, hi)
    return 100.0 * (1.0 - max(b.values()) / (hi - lo))


def peak_hbm_gib(ctx, spec):
    return ctx['memory_peak_bytes'] / 2.0 ** 30
