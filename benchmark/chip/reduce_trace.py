"""From a profiler trace (``.xplane.pb``) to numbers: per-device busy
union, per-name device time, collective time not hidden behind compute,
and idle gaps joined to the host's spans. Reads with nothing but
``jax.profiler.ProfileData``; imports nothing of the program.

A device plane is one whose name starts with ``/device:``. Its
operations are the events of the line named ``XLA Ops`` (every line but
the step/module/scope summaries where that line is missing). Host spans
are the ``TraceAnnotation`` events on the host planes' lines.
"""
import glob
import os
import re

OPS_LINE = 'XLA Ops'
ASYNC_LINE = 'Async XLA Ops'      # DMA and collectives in flight
# lines of a device plane that summarise rather than list operations
_SUMMARY_LINES = ('Steps', 'XLA Modules', 'XLA TraceMe', 'TC Overlay',
                  'Framework Name Scope', 'Framework Ops', 'Source code',
                  ASYNC_LINE)
_COLLECTIVE = re.compile(
    r'^(all-reduce|all-gather|reduce-scatter|all-to-all|'
    r'collective-permute|collective-broadcast|async-collective)')
# the TPU compiler emits a reduce-scatter as a fusion named ``fusion.N``
# that calls an ``all-reduce-scatter`` computation
_CALLS_COLLECTIVE = re.compile(r'calls=%all-reduce-scatter')


class Event(object):
    """``name`` is the instruction's own name (``fusion.65``); ``text``
    is what the trace calls the event, which on this TPU runtime is the
    whole HLO instruction; ``scope`` the framework scope, where the
    trace carries one (this runtime's does not)."""
    __slots__ = ('name', 'start', 'end', 'scope', 'text')

    def __init__(self, name, start, end, scope='', text=None):
        self.start, self.end, self.scope = start, end, scope
        self.text = name if text is None else text
        self.name = short_name(name)

    @property
    def dur(self):
        return self.end - self.start


_HLO_NAME = re.compile(r'^%([^\s=]+)\s*=')


def short_name(text):
    """``fusion.65`` of ``%fusion.65 = (...) fusion(...)``."""
    m = _HLO_NAME.match(text)
    return m.group(1) if m else text


def find_xplane(trace_dir):
    """The newest ``.xplane.pb`` under a profiler output directory."""
    found = glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'),
                      recursive=True)
    if not found:
        raise FileNotFoundError('no .xplane.pb under %s' % trace_dir)
    return max(found, key=os.path.getmtime)


def _scope_of(event):
    """The framework scope an operation was lowered under, if the trace
    carries it (``jax.named_scope`` paths end up in these stats)."""
    for key, val in event.stats:
        if key in ('tf_op', 'name', 'hlo_op_name', 'long_name') \
                and isinstance(val, str) and '/' in val:
            return val
    return ''


def load(path):
    """``{'devices': {plane: [Event]}, 'in_flight': {plane: [Event]},
    'host': [Event]}``, seconds. ``in_flight`` holds the asynchronous
    operations' whole spans (start to done), which overlap the
    operations of ``devices`` and are not counted as busy time."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, in_flight, host = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith('/device:'):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE] or \
                [ln for ln in lines if ln.name not in _SUMMARY_LINES]
            evs = []
            for ln in ops:
                for e in ln.events:
                    s = e.start_ns * 1e-9
                    evs.append(Event(e.name, s, s + e.duration_ns * 1e-9,
                                     _scope_of(e)))
            if evs:
                devices[plane.name] = sorted(evs, key=lambda e: e.start)
                in_flight[plane.name] = [
                    Event(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                    for ln in lines if ln.name == ASYNC_LINE
                    for e in ln.events]
        elif plane.name.startswith('/host:'):
            for ln in plane.lines:
                for e in ln.events:
                    s = e.start_ns * 1e-9
                    host.append(Event(e.name, s, s + e.duration_ns * 1e-9))
    return {'devices': devices, 'in_flight': in_flight, 'host': host}


# ---- interval arithmetic ---------------------------------------------------
def union(intervals):
    """Merged, sorted ``[(start, end)]`` of possibly overlapping ones."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def is_collective(event):
    """A collective by its instruction's name, or a fusion that calls a
    reduce-scatter. Collectives fused into a computing fusion (the
    TPU's asynchronous collective fusions) run hidden inside it and
    count as computing."""
    return bool(_COLLECTIVE.match(event.name)
                or _CALLS_COLLECTIVE.search(event.text))


# ---- the reduction ---------------------------------------------------------
def window_of(trace, span_name=None):
    """The traced window: from the first to the last host span called
    ``span_name`` if there are any, else from the first device operation
    to the last."""
    spans = [e for e in trace['host'] if e.name == span_name] \
        if span_name else []
    if spans:
        return min(e.start for e in spans), max(e.end for e in spans)
    evs = [e for d in trace['devices'].values() for e in d]
    return min(e.start for e in evs), max(e.end for e in evs)


def busy(trace, lo, hi):
    """Per device: seconds in which some operation ran inside [lo, hi]."""
    return {name: total(clip(union((e.start, e.end) for e in evs), lo, hi))
            for name, evs in trace['devices'].items()}


def time_by_name(trace, pattern, lo, hi, field='name'):
    """Per device: summed duration of the operations whose ``field``
    (``name``, ``text`` or ``scope``) matches ``pattern``, and how many
    matched."""
    rx = re.compile(pattern)
    out = {}
    for dev, evs in trace['devices'].items():
        hit = [e for e in evs if lo <= e.start and e.end <= hi
               and rx.search(getattr(e, field))]
        out[dev] = (sum(e.dur for e in hit), len(hit))
    return out


def exposed_collective(trace, lo, hi):
    """Per device: seconds of collective operations (those on the
    operations' line, and the in-flight spans of asynchronous ones)
    during which no other operation ran on that device."""
    out = {}
    for dev, evs in trace['devices'].items():
        flying = trace.get('in_flight', {}).get(dev, [])
        coll = clip(union((e.start, e.end) for e in list(evs) + flying
                          if is_collective(e)), lo, hi)
        comp = clip(union((e.start, e.end) for e in evs
                          if not is_collective(e)), lo, hi)
        out[dev] = total(subtract(coll, comp))
    return out


def top_ops(trace, lo, hi, n=10, label=None):
    """The operations that took most device time, grouped by ``label``
    of an event (default: its name, so one instruction's executions are
    summed over the steps), on the busiest device."""
    label = label or (lambda e: e.name)
    b = busy(trace, lo, hi)
    dev = max(b, key=b.get)
    acc = {}
    for e in trace['devices'][dev]:
        if lo <= e.start and e.end <= hi:
            k = label(e)
            acc[k] = acc.get(k, 0.0) + e.dur
    return sorted(acc.items(), key=lambda kv: -kv[1])[:n]


def idle_gaps(trace, lo, hi, span_names, n=10):
    """The longest gaps between operations on the busiest device, each
    labelled by the host span (of ``span_names``) that covers most of
    it, ``host`` where none does. Gaps of one label are summed."""
    b = busy(trace, lo, hi)
    dev = max(b, key=b.get)
    merged = clip(union((e.start, e.end)
                        for e in trace['devices'][dev]), lo, hi)
    gaps = subtract([(lo, hi)], merged)
    spans = sorted((e for e in trace['host'] if e.name in span_names),
                   key=lambda e: e.start)
    acc = {}
    for s, e in gaps:
        best, cover = 'host', 0.0
        for sp in spans:
            if sp.start >= e:
                break
            c = min(e, sp.end) - max(s, sp.start)
            if c > cover:
                best, cover = sp.name, c
        acc[best] = acc.get(best, 0.0) + (e - s)
    return sorted(acc.items(), key=lambda kv: -kv[1])[:n]


def describe(path, per_line=6):
    """What a trace holds, for reading one by hand: planes, their lines,
    and the first events of each line with their stats."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines = list(plane.lines)
        out.append('plane %r: %d lines' % (plane.name, len(lines)))
        for ln in lines:
            evs = list(ln.events)
            out.append('  line %r: %d events' % (ln.name, len(evs)))
            longest = sorted(evs, key=lambda e: -e.duration_ns)[:per_line]
            for e in evs[:per_line] + longest:
                stats = {k: (v if not isinstance(v, str) else v[:120])
                         for k, v in e.stats}
                out.append('    %r start=%d dur=%d %s' % (
                    e.name, e.start_ns, e.duration_ns, stats))
    return '\n'.join(out)


def grep(path, pattern):
    """Per device: the operations whose text matches ``pattern``, by
    name, with how often each ran and for how long in all."""
    tr = load(path)
    lo, hi = window_of(tr)
    out = []
    for dev in tr['devices']:
        acc = {}
        for e in tr['devices'][dev]:
            if re.search(pattern, e.text):
                n, t = acc.get(e.name, (0, 0.0))
                acc[e.name] = (n + 1, t + e.dur)
        out.append('%s: busy %.6f s of %.6f' % (dev, busy(tr, lo, hi)[dev],
                                                hi - lo))
        out += ['  %-60s %5d  %.6f s' % (k, n, t) for k, (n, t) in
                sorted(acc.items(), key=lambda kv: -kv[1][1])]
    return '\n'.join(out)


if __name__ == '__main__':
    import sys
    path = find_xplane(sys.argv[1]) if os.path.isdir(sys.argv[1]) \
        else sys.argv[1]
    print(grep(path, sys.argv[2]) if len(sys.argv) > 2 else describe(path))
