"""Per-shape autotuning with an on-disk cache.

BENCH_FULL measured NCHW-vs-NHWC conv layout at ~5% and flash-attention
tile choice at ~5% (dtype-dependent) — per (program, shape, backend)
decisions no static default gets right everywhere. The
:class:`Autotuner` times candidate configs through the real Executor
path and persists the winner in a :class:`TuningCache` keyed by
``(program fingerprint, shape signature, backend)``:

- ``Executor`` consults the cache at compile time (miss path) and bakes
  the winning entry into the traced program; the entry token joins the
  jit-cache key, so a new tuning result can never serve a stale
  compiled program.
- ``ModelServer.warmup()`` preloads the cache from disk before
  pre-compiling buckets, so a fresh serving process cold-starts with
  the tuned configs instead of re-searching (COMPILER.md).

Cache file: ``$PADDLE_TPU_TUNING_CACHE`` or ``tuning_cache.json``
beside the compile cache in the checkout (core/compile_cache.py;
atomic tmp->rename writes).
"""
import contextlib
import functools
import hashlib
import json
import os
import threading
import time

from .. import observability as _obs

__all__ = ['TuningCache', 'Autotuner', 'default_cache',
           'set_default_cache', 'shape_signature', 'backend',
           'apply_entry', 'wrap_jitted', 'flash_blocks',
           'conv_schedule', 'CONV_SCHEDULE_DEFAULTS']

SCHEMA = 1

# Tunable knobs an entry may carry; apply_entry() knows how to install
# each one for the duration of a traced call.
KNOWN_KNOBS = ('conv_layout', 'flash_block_q', 'flash_block_k',
               'conv_block_h', 'conv_block_c', 'conv_vector_width',
               'conv_epilogue')

# Flash tile override consulted by the flash_attention op kernel
# (ops/misc_ops.py); None -> the kernel's dtype-aware defaults.
_FLASH_OVERRIDE = [None]

# Conv schedule consulted by the fused-conv Pallas kernels
# (ops/pallas_kernels.py): H-tile target for 1x1 convs, output-channel
# block target, store-granularity quantum (the lane alignment bc must
# honor on real TPUs), and the epilogue master switch. The defaults
# live HERE, not in the kernels — tools/lint_repo.py's
# ``hardcoded-schedule`` rule keeps block/tile constants out of ops/.
CONV_SCHEDULE_DEFAULTS = {
    'block_h': 8,           # output-row tile target (1x1 convs)
    'block_c': 256,         # output-channel block target
    'vector_width': 128,    # lane quantum bc must divide by on TPU
    'epilogue': 'on',       # 'off' -> fused_conv replays unfused
}
_CONV_OVERRIDE = [None]


def flash_blocks():
    ov = _FLASH_OVERRIDE[0]
    return ov if ov is not None else (None, None)


def conv_schedule():
    """The live conv schedule: defaults overlaid with the active tuning
    entry's ``conv_*`` knobs (installed by :func:`apply_entry` for the
    duration of a traced call)."""
    sched = dict(CONV_SCHEDULE_DEFAULTS)
    ov = _CONV_OVERRIDE[0]
    if ov:
        sched.update(ov)
    return sched


def backend():
    """Device-kind-qualified backend token for cache keys. Winners are
    per device KIND, not just platform family — a v5e schedule is not a
    v4 schedule. Collapses to the bare platform when the device kind
    adds nothing (cpu/interpreters), so cpu-keyed entries stay bare."""
    import jax
    plat = jax.default_backend()
    kind = str(jax.local_devices()[0].device_kind)
    kind = kind.strip().lower().replace(' ', '-')
    return plat if kind == plat else '%s:%s' % (plat, kind)


def shape_signature(feed_sig):
    """Stable short token for a prepared-feed spec tuple (the
    ``(name, (shape, dtype))`` pairs Executor keys its cache by)."""
    return hashlib.sha1(repr(feed_sig).encode()).hexdigest()[:16]


def entry_token(entry):
    if not entry:
        return '-'
    return hashlib.sha1(json.dumps(entry, sort_keys=True,
                                   default=str).encode()).hexdigest()[:12]


def _default_path():
    from ..core.compile_cache import cache_root
    return os.environ.get('PADDLE_TPU_TUNING_CACHE') or os.path.join(
        cache_root(), 'tuning_cache.json')


class TuningCache(object):
    """Thread-safe (program fp, shape sig, backend) -> entry store with
    on-disk persistence and hit/miss telemetry
    (``tuning_cache_{hits,misses}_total``)."""

    def __init__(self, path=None):
        self.path = path or _default_path()
        self._entries = {}
        self._lock = threading.RLock()
        self._loaded = False
        reg = _obs.default_registry()
        self._m_hits = reg.counter(
            'tuning_cache_hits_total',
            'compile-time tuning-cache lookups that found an entry')
        self._m_misses = reg.counter(
            'tuning_cache_misses_total',
            'compile-time tuning-cache lookups that found nothing')

    @staticmethod
    def key(program_fp, shape_sig, back):
        return '%s|%s|%s' % (program_fp, shape_sig, back)

    # ---- persistence -----------------------------------------------------
    def preload(self):
        """Load the on-disk cache (idempotent; merges over in-memory
        entries without clobbering newer puts). Returns the number of
        entries now resident. Serving warmup calls this so cold-start
        compiles run under tuned configs."""
        with self._lock:
            n_before = len(self._entries)
            try:
                with open(self.path) as f:
                    data = json.load(f)
                if data.get('schema') == SCHEMA:
                    for k, v in data.get('entries', {}).items():
                        self._entries.setdefault(k, v)
            except (OSError, ValueError):
                pass
            self._loaded = True
            n = len(self._entries)
        _obs.emit('tuning_preload', path=self.path, entries=n,
                  loaded=n - n_before)
        return n

    def save(self):
        with self._lock:
            payload = {'schema': SCHEMA, 'entries': dict(self._entries)}
        d = os.path.dirname(os.path.abspath(self.path))
        try:
            os.makedirs(d)
        except OSError:
            pass
        tmp = self.path + '.tmp.%d' % os.getpid()
        with open(tmp, 'w') as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.path)

    # ---- lookup / store --------------------------------------------------
    def _ensure_loaded(self):
        if not self._loaded:
            self.preload()

    def lookup(self, program_fp, shape_sig, back, count=True):
        """The tuned entry dict, or None. ``count=False`` is the quiet
        form used per-run for cache-key tokens (metrics track COMPILES,
        not every step)."""
        self._ensure_loaded()
        with self._lock:
            hit = self._entries.get(self.key(program_fp, shape_sig,
                                             back))
        if count:
            (self._m_hits if hit else self._m_misses).inc()
            _obs.emit('tuning_lookup', fp=program_fp, hit=bool(hit))
        return dict(hit['entry']) if hit else None

    def token(self, program_fp, shape_sig, back):
        """Short stable token of the entry (or '-') for jit-cache keys:
        a tuning-cache update changes the token, forcing exactly the
        affected program to recompile."""
        self._ensure_loaded()
        with self._lock:
            hit = self._entries.get(self.key(program_fp, shape_sig,
                                             back))
        return entry_token(hit['entry']) if hit else '-'

    def put(self, program_fp, shape_sig, back, entry, measured_ms=None,
            persist=True):
        rec = {'entry': dict(entry), 'measured_ms': measured_ms,
               'backend': back, 'stored_at': time.time()}
        with self._lock:
            self._entries[self.key(program_fp, shape_sig, back)] = rec
        if persist:
            try:
                self.save()
            except OSError:
                pass
        _obs.emit('tuning_put', fp=program_fp, backend=back,
                  entry=dict(entry), measured_ms=measured_ms)

    def clear(self):
        with self._lock:
            self._entries.clear()
            self._loaded = True

    def __len__(self):
        with self._lock:
            return len(self._entries)


_DEFAULT = [None]


def default_cache():
    if _DEFAULT[0] is None:
        _DEFAULT[0] = TuningCache()
    return _DEFAULT[0]


def set_default_cache(cache):
    """Install (or with None, reset) the process default — tests and
    benchmarks point it at a temp path."""
    prev = _DEFAULT[0]
    _DEFAULT[0] = cache
    return prev


@contextlib.contextmanager
def apply_entry(entry):
    """Install a tuning entry's knobs for the duration of a call (the
    executor wraps the jitted fn with this, so the knobs are live at
    trace time and every re-execution)."""
    if not entry:
        yield
        return
    from ..core import amp
    prev_layout = amp._STATE.get('conv_layout')
    prev_flash = _FLASH_OVERRIDE[0]
    prev_conv = _CONV_OVERRIDE[0]
    try:
        if entry.get('conv_layout'):
            amp.set_conv_layout(entry['conv_layout'])
        if entry.get('flash_block_q') or entry.get('flash_block_k'):
            _FLASH_OVERRIDE[0] = (entry.get('flash_block_q'),
                                  entry.get('flash_block_k'))
        sched = {}
        for knob, key in (('conv_block_h', 'block_h'),
                          ('conv_block_c', 'block_c'),
                          ('conv_vector_width', 'vector_width'),
                          ('conv_epilogue', 'epilogue')):
            if entry.get(knob) is not None:
                sched[key] = entry[knob]
        if sched:
            _CONV_OVERRIDE[0] = sched
        yield
    finally:
        amp._STATE['conv_layout'] = prev_layout
        _FLASH_OVERRIDE[0] = prev_flash
        _CONV_OVERRIDE[0] = prev_conv


def wrap_jitted(fn, entry):
    """Wrap a compiled callable so every invocation (including the
    first, compiling one) runs under the entry's knobs."""
    if not entry:
        return fn

    def wrapped(*args, **kwargs):
        with apply_entry(entry):
            return fn(*args, **kwargs)

    # for whoever lowers the callable again under the same knobs
    # (observability.perf.scope_map); deliberately not a ``lower``
    # attribute, which the AOT store takes for a plain jit
    wrapped.__wrapped__ = fn
    wrapped.knobs = functools.partial(apply_entry, entry)
    return wrapped


def _block_op_types(program):
    types = set()
    for b in program.blocks:
        for op in b.ops:
            types.add(op.type)
    return types


# The conv schedule space the measured search draws from when the
# ledger says the program is worth tuning (bandwidth-bound, or no
# ledger yet). Curated, not exhaustive: the ledger prunes, the
# max_candidates cap bounds, and every dropped point is journalled.
_CONV_SCHEDULE_SPACE = (
    {'conv_block_h': 4, 'conv_block_c': 128, 'conv_vector_width': 128},
    {'conv_block_h': 8, 'conv_block_c': 128, 'conv_vector_width': 128},
    {'conv_block_h': 8, 'conv_block_c': 256, 'conv_vector_width': 128},
    {'conv_block_h': 16, 'conv_block_c': 256, 'conv_vector_width': 128},
    {'conv_block_h': 8, 'conv_block_c': 512, 'conv_vector_width': 256},
    {'conv_block_h': 16, 'conv_block_c': 512, 'conv_vector_width': 256},
)


class Autotuner(object):
    """Measured-cost schedule search (TVM-style: time candidates, keep
    the winner) over the knobs that measurably matter: conv layout
    (NCHW/NHWC), the fused-conv epilogue schedule (H/channel block
    sizes, vectorization width, epilogue on/off) and flash-attention
    tile sizes. The PR 14 perf ledger seeds and prunes the space —
    compute-bound conv programs skip the schedule sweep (tiling cannot
    move an MXU-bound roofline), bandwidth-bound ones get the full
    space. Each candidate is timed through a private Executor (so the
    caller's program cache stays untouched); a candidate that crashes
    or OOMs records a poisoned report entry and the sweep continues.
    The winner lands in the :class:`TuningCache` for every later
    compile of the same (program, shape, device-kind backend)."""

    def __init__(self, place=None, cache=None, warmup=1, steps=3,
                 max_candidates=12):
        self.place = place
        # `cache or ...` would drop an EMPTY injected cache: TuningCache
        # defines __len__, so a fresh one is falsy.
        self.cache = cache if cache is not None else default_cache()
        self.warmup = warmup
        self.steps = steps
        self.max_candidates = max_candidates
        reg = _obs.default_registry()
        self._m_candidates = reg.counter(
            'autotune_candidates_total',
            'schedule-search candidates measured (incl. poisoned)')

    @staticmethod
    def _ledger_bound(program):
        """Roofline classification from the PR 14 ledger book, or None
        when this program was never ledgered."""
        try:
            from ..observability import perf as _perf
            led = _perf.book().get(program.fingerprint())
            return led.roofline_bound if led is not None else None
        except Exception:
            return None

    def candidates(self, program):
        """Ordered candidate entries. Also computes ``self.last_pruned``
        (schedule points dropped by ledger seeding / the cap) for the
        search-end journal event."""
        types = _block_op_types(program)
        cands = [{}]
        pruned = 0
        if types & {'conv2d', 'depthwise_conv2d', 'conv3d',
                    'fused_conv'}:
            cands.append({'conv_layout': 'NHWC'})
            cands.append({'conv_layout': 'NCHW'})
            cands.append({'conv_epilogue': 'off'})
            bound = self._ledger_bound(program)
            if bound == 'compute':
                # MXU-bound: tile/vectorize knobs only move HBM traffic
                pruned += len(_CONV_SCHEDULE_SPACE)
            else:
                space = _CONV_SCHEDULE_SPACE if bound == 'bandwidth' \
                    else _CONV_SCHEDULE_SPACE[:2]   # no ledger: modest
                pruned += len(_CONV_SCHEDULE_SPACE) - len(space)
                cands.extend(dict(c) for c in space)
        if 'flash_attention' in types:
            # the set swept on the chip for the [B, T, H*dh] kernels
            # (PERF.md, PR 27)
            for bq, bk in ((1024, 1024), (512, 1024), (512, 512),
                           (256, 512)):
                cands.append({'flash_block_q': bq, 'flash_block_k': bk})
        # dedupe, keep order
        seen, out = set(), []
        for c in cands:
            t = entry_token(c)
            if t not in seen:
                seen.add(t)
                out.append(c)
        if len(out) > self.max_candidates:
            pruned += len(out) - self.max_candidates
            out = out[:self.max_candidates]
        self.last_pruned = pruned
        return out

    def tune(self, program, feed, fetch_list, scope=None, persist=True,
             name=None):
        """Measure every candidate; persist and return
        ``(best_entry, report)``. ``report`` maps entry tokens to mean
        step milliseconds — or to a ``'poisoned: ...'`` marker for
        candidates that crashed/OOMed mid-measurement (the sweep never
        aborts, and a poisoned candidate can never win or land in the
        cache)."""
        from ..executor import Executor, Scope, _spec
        from ..resilience import faultinject as _fi
        label = name or program.fingerprint()[:10]
        t_begin = time.perf_counter()
        cands = self.candidates(program)
        pruned = getattr(self, 'last_pruned', 0)
        _obs.emit('autotune', phase='begin', program=label,
                  fp=program.fingerprint(), candidates=len(cands),
                  pruned=pruned)
        report = {}
        best, best_ms = None, None
        poisoned = 0
        prepared_sig = None
        for cand in cands:
            tok = entry_token(cand) if cand else 'baseline'
            exe = Executor(self.place)
            cscope = scope if scope is not None else Scope()
            self._m_candidates.inc()
            try:
                with apply_entry(cand):
                    _fi.maybe_fault(_fi.SITE_TUNING_MEASURE)
                    if prepared_sig is None:
                        pf = exe._prepare_feed(program, dict(feed))
                        prepared_sig = tuple(sorted(
                            (n, _spec(v)) for n, v in pf.items()))
                    for _ in range(self.warmup):
                        exe.run(program, feed=dict(feed),
                                fetch_list=fetch_list, scope=cscope)
                    t0 = time.perf_counter()
                    for _ in range(self.steps):
                        exe.run(program, feed=dict(feed),
                                fetch_list=fetch_list, scope=cscope)
                    ms = (time.perf_counter() - t0) / self.steps * 1e3
            except Exception as err:
                # candidate invalid/crashed on this backend: poison it
                # and keep sweeping — never abort, never cache it
                poisoned += 1
                report[tok] = 'poisoned: %s' % type(err).__name__
                _obs.emit('autotune', phase='candidate_poisoned',
                          program=label, candidate=dict(cand),
                          error=type(err).__name__)
                continue
            report[tok] = round(ms, 3)
            if best_ms is None or ms < best_ms:
                best, best_ms = cand, ms
        if best_ms is not None and prepared_sig is not None:
            # cache the baseline {} winner too: "defaults win" is a
            # measured answer, and tune_if_missing must hit on it
            # (lookup returns the empty entry, not None)
            self.cache.put(program.fingerprint(),
                           shape_signature(prepared_sig), backend(),
                           best or {}, measured_ms=round(best_ms, 3),
                           persist=persist)
        dur_s = time.perf_counter() - t_begin
        _obs.default_registry().histogram(
            'autotune_seconds',
            'wall seconds per schedule search',
            program=label).observe(dur_s)
        _obs.emit('autotune', phase='end', program=label,
                  fp=program.fingerprint(), candidates=len(report),
                  poisoned=poisoned, pruned=pruned,
                  winner=dict(best or {}),
                  best_ms=round(best_ms, 3) if best_ms else None,
                  seconds=round(dur_s, 3))
        _obs.emit('tuning_search', fp=program.fingerprint(),
                  candidates=len(report), best=dict(best or {}),
                  best_ms=round(best_ms, 3) if best_ms else None)
        return best or {}, report

    def tune_if_missing(self, program, feed, fetch_list, scope=None,
                        persist=True, name=None):
        """Search only when the cache has no entry for this
        (program, shape, device-kind). Returns ``(entry, searched)`` —
        the serving ``warmup(autotune=True)`` building block: the
        second warmup of a process (or any process that preloaded the
        on-disk cache) does zero searches."""
        from ..executor import Executor, _spec
        exe = Executor(self.place)
        pf = exe._prepare_feed(program, dict(feed))
        sig = shape_signature(tuple(sorted(
            (n, _spec(v)) for n, v in pf.items())))
        hit = self.cache.lookup(program.fingerprint(), sig, backend(),
                                count=False)
        if hit is not None:
            return hit, False
        best, _report = self.tune(program, feed, fetch_list,
                                  scope=scope, persist=persist,
                                  name=name)
        return best, True
