"""Distributed tracing: propagated span trees over the run journal.

A :class:`TraceContext` is the portable identity of one unit of work —
``trace_id`` (the whole request/step tree), ``span_id`` (this node),
``parent_id`` (its parent) and the sampling decision made once at the
root. Contexts ride request objects across threads, pickle through the
multihost remote protocol unchanged, and cross the launcher boundary as
a ``PTPU_TRACE_PARENT`` env header — every process appends spans into
its *own* journal and ``tools/trace_report.py`` /
``tools/timeline.py`` reassemble the tree by trace id afterwards.

Span records are plain journal events (OBSERVABILITY.md):

=============  =========================================================
``span_begin``  name, trace, span, parent (+ caller fields)
``span_end``    same ids + ``dur_s`` (+ end fields); the only record
                trace_report needs to rebuild a tree — a ``span_begin``
                with no matching ``span_end`` marks work that died
                in flight (killed replica, crashed host)
``span_link``   trace/span of the *linking* span + ``linked_trace`` /
                ``linked_span``: a coalesced batch span links the N
                request spans it serves (N↔1, not parent-child)
=============  =========================================================

Overhead contract: with no journal installed every span API here
returns the shared :data:`NULL_SPAN` after one module-global ``None``
check — no allocation, no ids, no clock read. :class:`phase` (the
Executor's phases) besides enters a profiler ``TraceAnnotation``, which
is inert while no profiler session runs, and stores itself as the
open phase, which is what a jax compile event is filed under (the
compile log, at the end of this file). With a journal installed, sampling
is decided once per root from ``PTPU_TRACE_SAMPLE`` (default 1.0) by
hashing the trace id, so a rate of 0.25 keeps whole trees, never
orphan fragments; unsampled trees still propagate one shared inert
context so child processes agree with the root's decision.
"""
import collections
import os
import random
import threading
import time
import uuid

from . import flight as _flight
from .journal import emit as _emit, journal_active as _journal_active
from .metrics import default_registry

__all__ = ['TraceContext', 'Span', 'NULL_SPAN', 'start_span', 'span',
           'current_span', 'current_context', 'link', 'emit_span',
           'phase', 'sample_rate', 'parent_from_env', 'TRACE_PARENT_ENV',
           'TRACE_SAMPLE_ENV', 'CompileLog', 'COMPILE_LOG', 'listen_to_jax',
           'log_miss', 'retraced']

TRACE_SAMPLE_ENV = 'PTPU_TRACE_SAMPLE'
TRACE_PARENT_ENV = 'PTPU_TRACE_PARENT'


class _Local(threading.local):
    span = None         # the thread's active Span
    phase = None        # the open root ``phase``
    jax_open = 0        # jax compile events open on the thread
    jax_cache = None    # what the open backend compile's cache said


_local = _Local()


# Id generation is on the per-span hot path (uuid4 costs ~5us; this is
# ~0.5us): 64 random bits XORed with a per-process uuid4-derived salt,
# so even a process that re-seeds the random module cannot collide with
# another process, and the leading 8 hex chars stay uniformly
# distributed (the sampling hash keys on them).
_ID_SALT = uuid.uuid4().int & 0xffffffffffffffff
_randbits = random.getrandbits


def _new_id():
    return '%016x' % (_randbits(64) ^ _ID_SALT)


class TraceContext(object):
    """Immutable-by-convention span identity; pickles through the
    remote protocol (protocol 2+ handles ``__slots__`` classes)."""

    __slots__ = ('trace_id', 'span_id', 'parent_id', 'sampled')

    def __init__(self, trace_id, span_id, parent_id=None, sampled=True):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.sampled = sampled

    def child(self):
        """A fresh context one level below this one."""
        if not self.sampled:
            return _UNSAMPLED
        return TraceContext(self.trace_id, _new_id(), self.span_id, True)

    def to_header(self):
        """Env-safe wire form for the launcher contract."""
        return '%s-%s-%d' % (self.trace_id, self.span_id,
                             1 if self.sampled else 0)

    @classmethod
    def from_header(cls, header):
        """Parse :meth:`to_header` output; None on any malformation
        (a bad env var must never break a worker)."""
        parts = (header or '').strip().split('-')
        if len(parts) != 3 or not parts[0] or not parts[1]:
            return None
        return cls(parts[0], parts[1], None, parts[2] != '0')

    def __repr__(self):
        return 'TraceContext(trace=%s, span=%s, parent=%s, sampled=%s)' \
            % (self.trace_id, self.span_id, self.parent_id, self.sampled)


# One shared inert context for every unsampled tree: propagating it (at
# zero id-generation cost) is what lets a child process inherit the
# root's negative sampling decision instead of re-rolling its own.
_UNSAMPLED = TraceContext('', '', None, False)


class _NullSpan(object):
    """Shared no-op span returned when no journal is installed."""

    __slots__ = ()
    name = None
    context = None

    def end(self, **fields):
        pass

    def activate(self):
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class Span(object):
    """One live span. End exactly once — via ``with``, or ``end()``
    from whichever thread finishes the work (cross-thread spans are
    created with ``activate=False`` and carried on request objects)."""

    __slots__ = ('name', 'context', '_t0', '_ended', '_prev', '_active',
                 '_tid')

    def __init__(self, name, context):
        self.name = name
        self.context = context
        self._t0 = time.monotonic()
        self._ended = False
        self._prev = None
        self._active = False
        self._tid = 0

    def activate(self):
        """Make this the thread's current span (children nest under
        it). Deactivation happens in ``end()`` on the same thread."""
        self._prev = getattr(_local, 'span', None)
        self._active = True
        self._tid = threading.get_ident()
        _local.span = self
        return self

    def end(self, **fields):
        """Close the span (idempotent) and journal ``span_end`` with
        the measured ``dur_s``. Returns the duration in seconds."""
        dur = time.monotonic() - self._t0
        if self._ended:
            return dur
        self._ended = True
        if self._active and threading.get_ident() == self._tid:
            _local.span = self._prev
            self._active = False
        c = self.context
        if c.sampled:
            _flight.note_span_end(c)
            _emit('span_end', name=self.name, trace=c.trace_id,
                  span=c.span_id, parent=c.parent_id,
                  dur_s=round(dur, 6), **fields)
        return dur

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None and not self._ended:
            self.end(error=exc_type.__name__)
        else:
            self.end()
        return False


def sample_rate():
    """The current ``PTPU_TRACE_SAMPLE`` rate, clamped to [0, 1]."""
    try:
        r = float(os.environ.get(TRACE_SAMPLE_ENV, '1'))
    except ValueError:
        return 1.0
    return min(max(r, 0.0), 1.0)


def _sampled(trace_id):
    r = sample_rate()
    if r >= 1.0:
        return True
    if r <= 0.0:
        return False
    # hash of the trace id, not a coin flip: the decision is a pure
    # function of the id, so re-rolls anywhere agree with the root
    return int(trace_id[:8], 16) / float(0xffffffff) < r


def start_span(name, parent=None, activate=True, **fields):
    """Begin a span and journal ``span_begin``.

    ``parent`` may be a :class:`TraceContext`, a :class:`Span`, or None
    (inherit the thread's current span; a new sampled-or-not root when
    there is none). ``activate=False`` creates a span to carry across
    threads on a request object — the finishing thread calls ``end()``.
    Returns :data:`NULL_SPAN` when no journal is installed.
    """
    if not _journal_active():
        return NULL_SPAN
    if isinstance(parent, Span):
        parent = parent.context
    if parent is None:
        cur = getattr(_local, 'span', None)
        if cur is not None:
            parent = cur.context
    if parent is None:
        tid = _new_id()
        ctx = TraceContext(tid, _new_id(), None, True) \
            if _sampled(tid) else _UNSAMPLED
    else:
        ctx = parent.child()
    sp = Span(name, ctx)
    if ctx.sampled:
        # the flight recorder's live-span table is what lets a
        # postmortem bundle name the work still open at death
        _flight.note_span_begin(name, ctx)
        _emit('span_begin', name=name, trace=ctx.trace_id,
              span=ctx.span_id, parent=ctx.parent_id, **fields)
    if activate:
        sp.activate()
    return sp


def span(name, parent=None, **fields):
    """``with tracing.span('exe/run'): ...`` — an activated span."""
    return start_span(name, parent=parent, activate=True, **fields)


def current_span():
    """The thread's active :class:`Span`, or None."""
    return getattr(_local, 'span', None)


def current_context():
    """The active span's :class:`TraceContext`, or None — what request
    objects capture at creation time."""
    sp = getattr(_local, 'span', None)
    return sp.context if sp is not None else None


def link(from_span, linked_ctx):
    """Journal a ``span_link``: ``from_span`` (a coalesced batch span)
    serves the work identified by ``linked_ctx`` without being its
    parent. trace_report grafts the linked subtree under every request
    it serves when rebuilding per-request trees."""
    if from_span is None or linked_ctx is None:
        return
    ctx = from_span.context if isinstance(from_span, Span) else from_span
    if ctx is None or not ctx.sampled or not linked_ctx.sampled:
        return
    _emit('span_link', trace=ctx.trace_id, span=ctx.span_id,
          linked_trace=linked_ctx.trace_id,
          linked_span=linked_ctx.span_id)


def emit_span(name, dur_s, parent=None, context=None, **fields):
    """Journal one already-measured span (``span_end`` only, no begin)
    — for retrofitting existing timings (queue waits, step durations)
    without a second clock read. ``context`` is the span's own identity
    where it had to be handed out before the span's end (a child
    ``phase`` whose jax compile spans are its children). Returns the
    context written, or None when untraced."""
    if not _journal_active():
        return None
    ctx = context
    if ctx is None:
        if isinstance(parent, Span):
            parent = parent.context
        if parent is None:
            parent = current_context()
        if parent is None:
            tid = _new_id()
            ctx = TraceContext(tid, _new_id(), None, True) \
                if _sampled(tid) else _UNSAMPLED
        else:
            ctx = parent.child()
    if not ctx.sampled:
        return None
    _emit('span_end', name=name, trace=ctx.trace_id,
          span=ctx.span_id, parent=ctx.parent_id,
          dur_s=round(dur_s, 6), **fields)
    return ctx


_ANNOTATIONS = None


def _annotations():
    # jax is imported at first use, not at import: observability/ stays
    # import-cycle-free and stdlib-only for whoever never runs a phase
    global _ANNOTATIONS
    if _ANNOTATIONS is None:
        from jax import profiler
        _ANNOTATIONS = (profiler.TraceAnnotation,
                        profiler.StepTraceAnnotation)
    return _ANNOTATIONS


class phase(object):
    """One named phase of a call, on two sinks and one clock.

    Entering always enters a ``jax.profiler.TraceAnnotation`` of the
    phase's name, so the phase lies in the profiler's trace on the
    clock the device's operations are on; with no profiler session
    running that is an inert object (well under a microsecond).
    The journal gets the same name from the same call site under the
    journal's own rule — installed, and a parent span active:

    - a root (no ``parent``) opens a real span (``span_begin`` /
      ``span_end``) under the thread's current span, not activated;
      with ``step_num`` it is a ``StepTraceAnnotation``, which fills
      the device plane's ``Steps`` line;
    - a child (``parent`` is the root ``phase``) writes one
      ``span_end`` through :func:`emit_span` when it exits.

    ``t0`` and ``dur_s`` (``time.perf_counter``) stay readable after
    the block, for the series that were fed from hand-held timers;
    ``note()`` adds fields to the ``span_end`` record."""

    __slots__ = ('name', 'span', 't0', 'dur_s', '_ann', '_parent',
                 '_fields', '_end', '_prev', '_open', '_ctx')

    def __init__(self, name, parent=None, step_num=None, **fields):
        plain, step = _annotations()
        self.name = name
        self.span = None
        self.t0 = self.dur_s = 0.0
        self._ann = plain(name) if step_num is None \
            else step(name, step_num=step_num)
        self._parent, self._fields, self._end = parent, fields, None
        self._open = self._ctx = None

    @property
    def context(self):
        """The :class:`TraceContext` this phase's journal span is
        written under, None untraced. A child's is made at the first
        ask: its span is one ``span_end`` at exit, and a jax compile
        inside it wants a parent before that."""
        if self.span is not None:
            return self.span.context
        if self._ctx is None and self._parent is not None \
                and self._parent.span is not None:
            self._ctx = self._parent.span.context.child()
        return self._ctx

    def note(self, **fields):
        self._end = dict(self._end or (), **fields)

    def __enter__(self):
        self._ann.__enter__()
        # the open phase is what a jax compile event on this thread is
        # filed under (the compile log below): the thread's root, and
        # on the root its innermost open child
        parent = self._parent
        if parent is None:
            pctx = current_context()
            if pctx is not None:
                self.span = start_span(self.name, parent=pctx,
                                       activate=False, **self._fields)
            self._prev = _local.phase
            _local.phase = self
        else:
            self._prev = parent._open
            parent._open = self
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.dur_s = time.perf_counter() - self.t0
        if self._parent is None:
            _local.phase = self._prev
        else:
            self._parent._open = self._prev
        self._ann.__exit__(exc_type, exc, tb)
        end = self._end or {}
        if exc_type is not None:
            end['error'] = exc_type.__name__
        if self.span is not None:
            self.span.end(**end)
        elif self._parent is not None and self._parent.span is not None:
            end.update(self._fields)
            emit_span(self.name, self.dur_s, parent=self._parent.span,
                      context=self._ctx, **end)
        return False


# ---- the compile path, told by jax itself -----------------------------------
# jax records an event at every trace of a jitted function, every
# jaxpr -> MLIR lowering and every backend compile (a scalar event at
# the start, a duration event at the end, each with ``fun_name``), and
# what its persistent cache did inside a backend compile. They fire at
# a trace or a compile and never on the cached dispatch path, so
# listening costs a steady step nothing.
_JAX_KINDS = {
    '/jax/core/compile/jaxpr_trace_duration': 'trace',
    '/jax/core/compile/jaxpr_to_mlir_module_duration': 'mlir',
    '/jax/core/compile/backend_compile_duration': 'backend',
}
_JAX_SPANS = {'trace': 'jax/trace', 'mlir': 'jax/mlir',
              'backend': 'jax/xla_compile'}
_CACHE_HIT = '/jax/compilation_cache/cache_hits'
# jax records it where it writes an entry: a compile the cache did not
# hold and found worth keeping
_CACHE_MISS = '/jax/compilation_cache/cache_misses'
_CACHE_SECONDS = {
    '/jax/compilation_cache/cache_retrieval_time_sec': 'retrieval_s',
    '/jax/compilation_cache/compile_time_saved_sec': 'saved_s',
}


class CompileLog(object):
    """The process's jax compile events, oldest first, and the
    Executor's own account of each miss (``kind`` ``miss``): a bounded
    list, a running count of everything ever logged and a count of what
    the bound dropped. ``count`` is what a steady step reads, once
    before its jitted call and once after, to learn that nothing
    compiled in between."""

    CAP = 4096

    def __init__(self):
        self._lock = threading.Lock()
        self._entries = collections.deque(maxlen=self.CAP)
        self.count = 0
        self.dropped = 0

    def add(self, entry):
        with self._lock:
            if len(self._entries) == self.CAP:
                self.dropped += 1
            self._entries.append(entry)
            self.count += 1

    def entries(self, since=None, thread=None):
        """Copies, oldest first; those that ended at or after ``since``
        (``time.perf_counter``) on ``thread`` where given."""
        with self._lock:
            found = list(self._entries)
        return [dict(e) for e in found
                if (since is None or e['t'] >= since)
                and (thread is None or e['thread'] == thread)]


COMPILE_LOG = CompileLog()
_JAX_SERIES = {}


def _jax_series(kind, owner):
    series = _JAX_SERIES.get((kind, owner))
    if series is None:
        reg = default_registry()
        series = _JAX_SERIES[(kind, owner)] = (
            reg.counter('jax_compile_events_total',
                        'jax trace / jaxpr->MLIR / backend-compile events, '
                        'nested ones folded into the outermost; owner is '
                        'executor inside an Executor phase',
                        kind=kind, owner=owner),
            reg.counter('jax_compile_seconds_total',
                        'seconds inside those events',
                        kind=kind, owner=owner))
    return series


def _cache_series(result):
    return default_registry().counter(
        'jax_persistent_cache_total',
        'backend compiles jax\'s persistent cache served (hit) or '
        'compiled and kept (miss)', result=result)


def _on_jax_start(event, value, **kw):
    if event in _JAX_KINDS:
        _local.jax_open += 1
        if _local.jax_open == 1 and _JAX_KINDS[event] == 'backend':
            _local.jax_cache = {'cache': 'off'}


def _on_jax_event(event, **kw):
    note = _local.jax_cache
    if note is not None and event in (_CACHE_HIT, _CACHE_MISS):
        note['cache'] = 'hit' if event == _CACHE_HIT else 'miss'


def _on_jax_seconds(event, secs, **kw):
    kind = _JAX_KINDS.get(event)
    if kind is None:
        field = _CACHE_SECONDS.get(event)
        if field is not None and _local.jax_cache is not None:
            _local.jax_cache[field] = secs
        return
    # a listener installed inside an open event sees its end alone
    _local.jax_open = max(_local.jax_open - 1, 0)
    if _local.jax_open:
        # begun while another was open on this thread (the jnp
        # functions inside the step's trace, a lower_fun inside a
        # lowering): its time is the outermost's already
        return
    ph = _local.phase
    if ph is not None:
        ph = ph._open or ph
    entry = {'t': time.perf_counter(), 'kind': kind, 'dur_s': secs,
             'fun': kw.get('fun_name'), 'phase': None, 'fp': None,
             'thread': threading.get_ident()}
    span_name = _JAX_SPANS[kind]
    if kind == 'backend' and _local.jax_cache is not None:
        entry.update(_local.jax_cache)
        _local.jax_cache = None
        if entry['cache'] != 'off':
            _cache_series(entry['cache']).inc()
        if entry['cache'] == 'hit':
            span_name = 'jax/cache_load'
    if ph is not None:
        root = ph._parent or ph
        entry['phase'] = ph.name
        entry['fp'] = (root._end or {}).get('fp')
    events, seconds = _jax_series(
        kind, 'executor' if ph is not None else 'other')
    events.inc()
    seconds.inc(max(secs, 0.0))
    COMPILE_LOG.add(entry)
    if ph is not None and _journal_active():
        ctx = ph.context
        if ctx is not None:
            emit_span(span_name, secs, parent=ctx, fun=entry['fun'],
                      fp=entry['fp'])


_LISTENING = []


def listen_to_jax():
    """Register the one ``jax.monitoring`` listener of the tree, once a
    process (``core.compile_cache.configure_compile_cache`` calls it at
    package import). Every jax compile event from then on is filed
    under the Executor phase open on its thread, in the three sinks
    that exist: the registry (``jax_compile_events_total``,
    ``jax_compile_seconds_total``, ``jax_persistent_cache_total``), the
    journal (``jax/trace``, ``jax/mlir``, ``jax/xla_compile`` or
    ``jax/cache_load`` under the phase's span) and :data:`COMPILE_LOG`
    (``observability.perf.compile_log()``)."""
    if _LISTENING:
        return
    _LISTENING.append(True)
    from jax import monitoring
    monitoring.register_scalar_listener(_on_jax_start)
    monitoring.register_event_listener(_on_jax_event)
    monitoring.register_event_duration_secs_listener(_on_jax_seconds)


def log_miss(top, prep, launch, verify_s, lower_s):
    """The Executor's account of one cache miss, from the jax events
    that fell inside the call on this thread (``prep``'s start to
    ``launch``'s end): one ``miss`` entry in :data:`COMPILE_LOG`, whose
    parts are disjoint. ``verify_s`` / ``lower_s`` (``exe/verify``,
    ``exe/compile``) and ``first_run_s`` (``exe/launch``) are those
    phases less the jax events inside them; ``trace_s`` / ``mlir_s`` /
    ``backend_s`` sum the events, wherever in the call they fell.
    Returns the entry's fields but ``kind``, ``t`` and ``thread``."""
    mine = COMPILE_LOG.entries(since=prep.t0,
                               thread=threading.get_ident())
    mine = [e for e in mine if e['kind'] in _JAX_SPANS]
    inside = {'exe/verify': verify_s, 'exe/compile': lower_s,
              'exe/launch': launch.dur_s}
    total = dict.fromkeys(_JAX_SPANS, 0.0)
    for e in mine:
        total[e['kind']] += e['dur_s']
        if e['phase'] in inside:
            inside[e['phase']] -= e['dur_s']
    backends = [e for e in mine if e['kind'] == 'backend']
    caches = {e['cache'] for e in backends}
    miss = {
        'fp': (top._end or {}).get('fp'), 'phase': top.name,
        'wall_s': launch.t0 + launch.dur_s - prep.t0,
        'verify_s': max(inside['exe/verify'], 0.0),
        'lower_s': max(inside['exe/compile'], 0.0),
        'trace_s': total['trace'], 'mlir_s': total['mlir'],
        'backend_s': total['backend'],
        # jax and the phases read two clocks: a hair below zero is zero
        'first_run_s': max(inside['exe/launch'], 0.0),
        'cache': next((c for c in ('miss', 'hit') if c in caches), 'off'),
        'retrieval_s': sum(e.get('retrieval_s', 0.0) for e in backends),
        'modules': len(backends)}
    COMPILE_LOG.add(dict(miss, kind='miss', t=launch.t0 + launch.dur_s,
                         thread=threading.get_ident()))
    return miss


def retraced(launch):
    """True where a jax trace or compile ended inside ``launch`` on
    this thread: asked only when :data:`COMPILE_LOG`'s count moved over
    a jitted call whose run was no miss — a retrace by jax under a key
    the Executor holds."""
    return any(e['phase'] == launch.name for e in COMPILE_LOG.entries(
        since=launch.t0, thread=threading.get_ident()))


def parent_from_env(environ=None):
    """The :class:`TraceContext` published by a parent process through
    ``PTPU_TRACE_PARENT`` (the launcher env contract), or None."""
    env = os.environ if environ is None else environ
    header = env.get(TRACE_PARENT_ENV)
    if not header:
        return None
    return TraceContext.from_header(header)
