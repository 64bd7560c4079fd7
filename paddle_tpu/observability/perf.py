"""Performance observatory: per-program cost/memory ledgers, live MFU
and HBM attribution, and the on-disk perf-regression baseline.

Every perf claim in PERF.md ultimately reduces to one artifact — the
flops/bytes "ledger" XLA computes for a compiled program. This module
makes it a first-class runtime surface:

- :class:`ProgramLedger` — captured on the Executor's compile-cache
  MISS path (one extra AOT ``lower().compile()`` against abstract
  avals; zero steady-state cost) for every jitted program: XLA
  ``cost_analysis()`` flops / bytes-accessed plus ``memory_analysis()``
  temp/argument/output bytes, the compile wall, device kind, and the
  partition mesh signature so dp/ZeRO variants ledger separately.
- :class:`LedgerBook` — the process-wide store; feeds the
  ``perf_hbm_live_bytes`` / ``perf_hbm_watermark_bytes`` gauges.
- :func:`publish_step` — joins a ledger with the measured step wall
  into ``perf_mfu{program=}`` and ``perf_roofline_bound{program=}``
  (1.0 = compute-bound, 0.0 = bandwidth-bound). Two gauge stores per
  step; the Trainer calls it from its dispatch path.
- ``perf_ledger`` journal events carry the tracing trace id, so a
  regressed program resolves to a renderable span tree
  (``tools/trace_report.py``).
- :class:`PerfBaseline` — on-disk JSON keyed
  ``fingerprint|shape-sig|backend|mesh``; ``tools/perf_report.py``
  diffs a run against it and exits nonzero on regressions.

Overhead contract (mirrors tracing/journal): capture is OFF by default
— ``capture_enabled()`` is one list read (+ an env probe on the
compile-miss path only). Enable with :func:`enable_capture`, the
:func:`capture_scope` context manager, or ``PTPU_PERF=1`` in the
environment.

Lint contract: this file is the ONLY place allowed to call XLA's
``cost_analysis()`` directly (``tools/lint_repo.py`` rule
``direct-cost-analysis``; ``Executor.cost_analysis`` is the seeded
allowlist exception it delegates through).
"""
import contextlib
import hashlib
import json
import os
import re
import threading
import weakref

# NB: the package __init__ rebinds the name ``journal`` to the
# contextmanager, so import the emit hook directly (not the submodule)
from .journal import emit as _emit
from . import metrics as _metrics
from . import tracing as _tracing

__all__ = [
    'PERF_ENV', 'UnknownDeviceKindError',
    'ProgramLedger', 'LedgerBook', 'PerfBaseline',
    'capture_enabled', 'enable_capture', 'capture_scope',
    'capture_compiled', 'seal', 'publish_step',
    'book', 'get_ledger', 'ledgers', 'clear',
    'peak_flops_for', 'hbm_gbps_for', 'mesh_signature',
    'shape_signature', 'memory_dict',
    'abstract_args', 'register_executor', 'scope_map', 'parse_scopes',
    'split_scope', 'PHASES', 'compile_log',
]

PERF_ENV = 'PTPU_PERF'              # '1' -> capture on for the process

# Published per-chip peaks, keyed by PJRT ``device_kind`` substring
# (first match wins; v5e reports 'TPU v5 lite'). bf16 flop/s and HBM
# GB/s; v5e from the Google Cloud "TPU v5e" page. A device that is not
# here has no roofline: asking for one is an error, never another
# chip's numbers.
PEAK_BF16 = (('v6', 918e12), ('v5p', 459e12), ('v5', 197e12),
             ('v4', 275e12), ('v3', 123e12), ('v2', 45e12))
HBM_GBPS = (('v6', 1640.0), ('v5p', 2765.0), ('v5', 819.0),
            ('v4', 1228.0), ('v3', 900.0), ('v2', 700.0))

BASELINE_SCHEMA = 1

# Relative drift allowed on compile-time-deterministic fields (flops,
# bytes) before the baseline diff calls it a mismatch; XLA version
# bumps move these by well under a percent.
DETERMINISTIC_RTOL = 0.02

_TRUTHY = ('1', 'true', 'on', 'yes')


class UnknownDeviceKindError(ValueError):
    """``device_kind`` has no entry in the peak tables."""


def _peak_lookup(table, what, device_kind):
    kind = (device_kind or '').lower()
    for sub, value in table:
        if sub in kind:
            return value
    raise UnknownDeviceKindError(
        'no %s known for device_kind %r; add it to '
        'paddle_tpu.observability.perf with its source' %
        (what, device_kind))


def peak_flops_for(device_kind):
    """bf16 peak flop/s for a PJRT ``device_kind`` string."""
    return _peak_lookup(PEAK_BF16, 'bf16 peak flop/s', device_kind)


def hbm_gbps_for(device_kind):
    """HBM bandwidth in GB/s for a PJRT ``device_kind`` string."""
    return _peak_lookup(HBM_GBPS, 'HBM bandwidth', device_kind)


# ---- capture gate ---------------------------------------------------------
# tri-state like tracing's sample override: None -> the env decides.
_CAPTURE = [None]


def capture_enabled():
    v = _CAPTURE[0]
    if v is not None:
        return v
    return os.environ.get(PERF_ENV, '').lower() in _TRUTHY


def enable_capture(on=True):
    """Force ledger capture on/off for the process (overrides
    ``PTPU_PERF``); ``None`` restores env control. Returns the previous
    override so callers can restore it."""
    prev = _CAPTURE[0]
    _CAPTURE[0] = None if on is None else bool(on)
    return prev


@contextlib.contextmanager
def capture_scope(on=True):
    """Scoped :func:`enable_capture` — serving ``warmup()`` wraps its
    per-bucket pre-compiles in this so every bucket ledgers."""
    prev = enable_capture(on)
    try:
        yield
    finally:
        _CAPTURE[0] = prev


# ---- signatures -----------------------------------------------------------
def shape_signature(feed, state):
    """Stable short token of the (feed, state) leaf shapes/dtypes —
    the shape axis of the baseline key."""
    import jax
    leaves = jax.tree_util.tree_leaves((feed, state))
    items = [(tuple(getattr(v, 'shape', ()) or ()),
              str(getattr(v, 'dtype', type(v).__name__)))
             for v in leaves]
    return hashlib.sha1(repr(items).encode()).hexdigest()[:16]


def mesh_signature(describe=None):
    """Canonical mesh token for ledger/baseline keys: ``'single'`` off
    the mesh, else sorted ``axis=extent`` pairs from
    ``Partitioner.describe()['axes']`` (e.g. ``'dp=2'``)."""
    if not describe:
        return 'single'
    axes = describe.get('axes') if isinstance(describe, dict) else None
    if not axes:
        return 'single'
    return ','.join('%s=%d' % (k, int(v))
                    for k, v in sorted(axes.items()))


# ---- the ledger -----------------------------------------------------------
class ProgramLedger(object):
    """One compiled program's XLA-counted cost/memory accounting."""

    __slots__ = ('fingerprint', 'shape_sig', 'backend', 'device_kind',
                 'mesh', 'devices', 'chain', 'flops', 'bytes_accessed',
                 'output_bytes', 'temp_bytes', 'argument_bytes',
                 'compile_wall_s', 'measured_ms', 'trace', 'label')

    def __init__(self, fingerprint, shape_sig='', backend='',
                 device_kind='', mesh='single', devices=1, chain=0,
                 flops=0.0, bytes_accessed=0.0, output_bytes=0.0,
                 temp_bytes=0, argument_bytes=0, label=''):
        self.fingerprint = fingerprint
        self.shape_sig = shape_sig
        self.backend = backend
        self.device_kind = device_kind
        self.mesh = mesh
        self.devices = int(devices)
        self.chain = int(chain)
        self.flops = float(flops)
        self.bytes_accessed = float(bytes_accessed)
        self.output_bytes = float(output_bytes)
        self.temp_bytes = int(temp_bytes)
        self.argument_bytes = int(argument_bytes)
        self.compile_wall_s = None
        self.measured_ms = None
        self.trace = None
        self.label = label

    # -- derived ------------------------------------------------------------
    @property
    def live_bytes(self):
        """Per-device bytes the compiled program holds while running:
        arguments + outputs + XLA temp buffers."""
        return int(self.argument_bytes + self.output_bytes
                   + self.temp_bytes)

    @property
    def _has_roofline(self):
        """False for a ledger captured on the CPU backend: it has no
        roofline, so its derived fields are absent — never computed
        against another chip's peaks. Any other kind must be in the
        tables (the lookups raise)."""
        return self.device_kind != 'cpu'

    @property
    def peak_flops(self):
        return peak_flops_for(self.device_kind)

    @property
    def hbm_gbps(self):
        return hbm_gbps_for(self.device_kind)

    def bandwidth_bound_s(self, hbm_gbps=None):
        bw = self.hbm_gbps if hbm_gbps is None else hbm_gbps
        return self.bytes_accessed / (bw * 1e9)

    def compute_bound_s(self, peak=None):
        pk = self.peak_flops if peak is None else peak
        return self.flops / pk

    @property
    def roofline_bound(self):
        """Which roofline leg binds this program: the larger of the two
        bound times is the constraint the measured step cannot beat.
        None where the device has no roofline."""
        if not self._has_roofline:
            return None
        return ('compute' if self.compute_bound_s()
                >= self.bandwidth_bound_s() else 'bandwidth')

    def mfu(self, measured_ms=None, peak=None):
        """XLA-counted flops over the measured step against bf16 peak;
        None until a measured step time is known, and where the device
        has no roofline."""
        ms = self.measured_ms if measured_ms is None else measured_ms
        if not ms:
            return None
        if peak is None:
            if not self._has_roofline:
                return None
            peak = self.peak_flops
        return self.flops / (ms / 1e3) / peak

    # -- serialization ------------------------------------------------------
    def as_dict(self):
        d = {
            'fp': self.fingerprint, 'shape_sig': self.shape_sig,
            'backend': self.backend, 'device_kind': self.device_kind,
            'mesh': self.mesh, 'devices': self.devices,
            'chain': self.chain, 'flops': self.flops,
            'bytes_accessed': self.bytes_accessed,
            'output_bytes': self.output_bytes,
            'temp_bytes': self.temp_bytes,
            'argument_bytes': self.argument_bytes,
            'live_bytes': self.live_bytes,
        }
        if self._has_roofline:
            d['bandwidth_bound_ms'] = round(
                self.bandwidth_bound_s() * 1e3, 3)
            d['compute_bound_ms'] = round(self.compute_bound_s() * 1e3, 3)
            d['roofline'] = self.roofline_bound
        if self.label:
            d['program'] = self.label
        if self.compile_wall_s is not None:
            d['compile_wall_s'] = round(self.compile_wall_s, 6)
        if self.measured_ms is not None:
            d['measured_ms'] = round(self.measured_ms, 3)
            m = self.mfu()
            if m is not None:
                d['mfu'] = round(m, 4)
        return d


class LedgerBook(object):
    """Thread-safe (fp, shape_sig, backend, mesh) -> ledger store;
    owns the process HBM live/watermark gauges."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries = {}    # full key -> ProgramLedger
        self._by_fp = {}      # fingerprint -> latest ProgramLedger
        self._watermark = 0

    @staticmethod
    def key(ledger):
        return '%s|%s|%s|%s' % (ledger.fingerprint, ledger.shape_sig,
                                ledger.backend, ledger.mesh)

    def record(self, ledger):
        with self._lock:
            self._entries[self.key(ledger)] = ledger
            self._by_fp[ledger.fingerprint] = ledger
            live = sum(l.live_bytes for l in self._entries.values())
            self._watermark = max(self._watermark, live)
            wm = self._watermark
        reg = _metrics.default_registry()
        reg.gauge('perf_hbm_live_bytes',
                  'sum of live bytes (args+outputs+temps) over all '
                  'ledgered compiled programs, per device').set(live)
        reg.gauge('perf_hbm_watermark_bytes',
                  'high-water mark of perf_hbm_live_bytes over the '
                  'process lifetime').set(wm)
        return ledger

    def get(self, fingerprint):
        with self._lock:
            return self._by_fp.get(fingerprint)

    def ledgers(self):
        with self._lock:
            return list(self._entries.values())

    def __len__(self):
        with self._lock:
            return len(self._entries)

    def clear(self):
        with self._lock:
            self._entries.clear()
            self._by_fp.clear()
            self._watermark = 0


_BOOK = LedgerBook()
_PUBLISHED = set()    # fingerprints whose measured journal update went out
_GAUGES = {}          # fingerprint -> (mfu_gauge, roofline_gauge);
#                       registry lookups are lock+label-sort, too slow
#                       for the per-flush publish path


def book():
    return _BOOK


def get_ledger(fingerprint):
    return _BOOK.get(fingerprint)


def ledgers():
    return _BOOK.ledgers()


def clear():
    """Drop every recorded ledger and the measured-once markers (test /
    benchmark phase isolation; gauges re-publish on next record)."""
    _BOOK.clear()
    _PUBLISHED.clear()
    _GAUGES.clear()


# ---- capture / seal / publish ---------------------------------------------
def _capture_failures():
    return _metrics.default_registry().counter(
        'perf_capture_failures_total',
        'ledger captures that raised and were dropped (capture is '
        'diagnostic; it never fails the run)')


def capture_compiled(jitted, feed, state, fingerprint, backend='',
                     device_kind='', mesh='single', devices=1,
                     chain=0, label=''):
    """AOT-compile ``jitted`` against the abstract avals of ``(feed,
    state)`` and read XLA's cost/memory analysis into a
    :class:`ProgramLedger`. Returns None when capture is disabled or
    anything goes wrong — the ledger is diagnostic and must never take
    down an execution. Call under the same device/mesh context the
    program will execute in (the Executor does)."""
    if not capture_enabled():
        return None
    try:
        comp = jitted.lower(*abstract_args(feed, state)).compile()
        ca = comp.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        ca = ca or {}
        ma = comp.memory_analysis()
        ledger = ProgramLedger(
            fingerprint=fingerprint,
            shape_sig=shape_signature(feed, state),
            backend=backend, device_kind=device_kind, mesh=mesh,
            devices=devices, chain=chain,
            flops=float(ca.get('flops', 0.0)),
            bytes_accessed=float(ca.get('bytes accessed', 0.0)),
            output_bytes=float(ca.get('bytes accessedout{}', 0.0)),
            temp_bytes=int(ma.temp_size_in_bytes),
            argument_bytes=int(ma.argument_size_in_bytes),
            label=label)
        try:
            ledger.output_bytes = float(ma.output_size_in_bytes)
        except AttributeError:
            pass
        return ledger
    except Exception:
        _capture_failures().inc()
        return None


def seal(ledger, compile_wall_s, trace=None):
    """Finish a captured ledger on the compile-miss seal path: attach
    the compile wall and the trace context, record into the book, and
    journal the ``perf_ledger`` event (with the trace-id exemplar when
    the compile ran under a sampled trace)."""
    if ledger is None:
        return None
    ledger.compile_wall_s = float(compile_wall_s)
    if trace is not None and getattr(trace, 'sampled', False):
        ledger.trace = trace.trace_id
    _BOOK.record(ledger)
    fields = ledger.as_dict()
    if ledger.trace:
        fields['trace'] = ledger.trace
    _emit('perf_ledger', **fields)
    return ledger


def publish_step(fingerprint, seconds_per_step):
    """Join a measured per-step wall with the program's ledger into the
    live derived series. Steady-state cost: one dict probe when nothing
    is ledgered; two gauge stores when a ledger exists. The first
    measurement per program also journals a ``perf_ledger`` update
    carrying ``measured_ms``/``mfu``."""
    if not _BOOK._by_fp:      # nothing captured -> free
        return None
    ledger = _BOOK.get(fingerprint)
    if ledger is None or not seconds_per_step:
        return None
    ms = seconds_per_step * 1e3
    ledger.measured_ms = ms
    mfu = ledger.mfu()
    pair = _GAUGES.get(fingerprint)
    if pair is None:
        reg = _metrics.default_registry()
        pair = (
            reg.gauge('perf_mfu',
                      'XLA-counted flops / measured step / bf16 peak, '
                      'per compiled program', program=fingerprint),
            reg.gauge('perf_roofline_bound',
                      'roofline classification per program: 1.0 = '
                      'compute-bound, 0.0 = bandwidth-bound',
                      program=fingerprint))
        _GAUGES[fingerprint] = pair
    if mfu is not None:      # a device with a roofline (not the CPU)
        pair[0].set(mfu)
        pair[1].set(1.0 if ledger.roofline_bound == 'compute' else 0.0)
    if fingerprint not in _PUBLISHED:
        _PUBLISHED.add(fingerprint)
        _emit('perf_ledger', fp=fingerprint, phase='measured',
                      measured_ms=round(ms, 3),
                      mfu=round(mfu, 4) if mfu is not None else None,
                      roofline=ledger.roofline_bound)
    return mfu


# ---- from device operations back to Fluid scopes --------------------------
# core/lowering.py lowers a block under ``forward`` (differentiated where
# the program has a backward marker, so JAX names it ``jvp(forward)`` and
# its backward ``transpose(jvp(forward))``) and ``optimizer``, and every
# op under ``<op.type>[:<first output>]``. XLA keeps that path in each
# instruction's ``metadata op_name``; a device trace names operations by
# instruction only, so the compiled module's text is the join.
PHASES = ('forward', 'backward', 'optimizer')
_EXECUTORS = weakref.WeakSet()
_COMPUTATION = re.compile(r'^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$')
_INSTRUCTION = re.compile(r'^\s+(ROOT\s+)?%?([\w.\-]+)\s*=\s')
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r'\bcalls=%?([\w.\-]+)')
_HEAVY = re.compile(r'\s(convolution|dot)\(')     # a TPU matmul is one too
_MODULE = re.compile(r'^HloModule\s+([^\s,]+)')
_WRAPPERS = ('checkpoint', 'rematted_computation', 'while', 'body',
             'cond', 'branch')


def register_executor(exe):
    """Executors announce themselves (weakly) so that
    :func:`scope_map` finds their compiled programs on demand."""
    _EXECUTORS.add(exe)


def abstract_args(feed, state, shardings=None):
    """``(feed, state)`` as shapes and dtypes alone, each carrying its
    name's sharding of ``shardings`` (``(feed shardings, state
    shardings)`` of a Partitioner) where given: what an AOT ``lower()``
    of the step takes in place of arrays."""
    import jax

    def avals(tree, named):
        return {n: jax.tree_util.tree_map(
            lambda v: jax.ShapeDtypeStruct(
                v.shape, v.dtype, sharding=(named or {}).get(n)), t)
            for n, t in tree.items()}

    feeds_s, state_s = shardings or (None, None)
    return avals(feed, feeds_s), avals(state, state_s)


def _fluid_at(parts):
    """Index of the Fluid op's component in a split ``op_name``: the
    one after the last phase scope (loop and remat wrappers skipped),
    None where the path ends at its primitive there."""
    at = [i for i, c in enumerate(parts)
          if c in ('forward', 'optimizer') or '(forward)' in c]
    rest = [i for i in range(at[-1] + 1, len(parts))
            if parts[i] not in _WRAPPERS] if at else []
    return rest[0] if len(rest) > 1 else None


def split_scope(op_name):
    """``(phase, Fluid op)`` of a ``metadata op_name`` path: ``backward``
    if the path holds ``transpose(``, ``optimizer`` if it holds the
    ``optimizer`` scope, else ``forward``; the Fluid op is the component
    after the phase's scope (``conv2d:res2a_branch2a.tmp_0``; two
    joined by ``+`` where :func:`parse_scopes` found a fusion doing
    both), None where the path ends there."""
    parts = op_name.split('/')
    if 'transpose(' in op_name:
        phase = 'backward'
    elif 'optimizer' in parts:
        phase = 'optimizer'
    else:
        phase = 'forward'
    at = _fluid_at(parts)
    return phase, (parts[at] if at is not None else None)


def parse_scopes(hlo_text):
    """``(module name, {instruction name: op_name})`` of a compiled
    module's text. One fusion is one operation to a trace, so it gets
    one scope. Without a convolution or dot inside, that is the latest
    phase found inside, since it runs when the last of what it holds
    can run (forward ops inside a backward fusion are recomputed there;
    the gradient's last casts ride inside Adam's update): its own
    ``op_name`` if that is of this phase, else the most frequent one of
    this phase inside. With one, it is the convolution's or dot's (a
    TPU matmul is a convolution), and where the fusion goes on into a
    later phase — a weight gradient's matmul with that weight's update
    as its epilogue — the later Fluid op is joined to the matmul's
    (``mul:fc_5.tmp_0+adam:fc_5.w_0``), so that a table shows both. An
    instruction that has no metadata, in itself or inside, is left
    out."""
    module, comps, cur = '', {}, None
    for line in hlo_text.splitlines():
        if cur is None:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
            m = _COMPUTATION.match(line)
            if m:
                cur = comps.setdefault(m.group(1), [])
            continue
        if line.startswith('}'):
            cur = None
            continue
        m = _INSTRUCTION.match(line)
        if m:
            name = _OP_NAME.search(line)
            calls = _CALLS.search(line)
            cur.append((m.group(2), name.group(1) if name else None,
                        calls.group(1) if calls else None,
                        bool(_HEAVY.search(line))))

    def rank(op_name):
        return PHASES.index(split_scope(op_name)[0])

    def of_fusion(own, comp):
        inside = [n for _, n, _, _ in comps.get(comp, ()) if n]
        if own:
            inside.append(own)
        if not inside:
            return None
        latest = max(map(rank, inside))
        last = [n for n in inside if rank(n) == latest]
        tail = own if own and rank(own) == latest \
            else max(set(last), key=last.count)
        heavy = [n for _, n, _, h in comps.get(comp, ()) if n and h]
        if not heavy:
            return tail
        parts = heavy[0].split('/')
        at, then = _fluid_at(parts), split_scope(tail)[1]
        if rank(heavy[0]) < latest and at is not None and then:
            parts[at] += '+' + then
        return '/'.join(parts)

    scopes = {}
    for rows in comps.values():
        for inst, name, calls, _ in rows:
            if calls:
                name = of_fusion(name, calls)
            if name:
                scopes[inst] = name
    return module, scopes


def _scoped(text):
    return any(('/%s/' % s) in text or ('(%s)' % s) in text
               for s in ('forward', 'optimizer'))


def _compiled_text(jitted, abstract):
    """The optimized HLO text of a cached entry, compiled once more
    from the persistent cache. JAX leaves metadata out of that cache's
    key, so the cache may hand back an executable that another version
    of the program compiled, with that version's scopes: where the text
    lacks this version's, the entry is lowered and compiled afresh
    (metadata in the key, nothing written back)."""
    import jax
    if not hasattr(jitted, 'trace'):          # an AOT-loaded Compiled
        return jitted.as_text()
    traced = jitted.trace(*abstract)
    text = traced.lower().compile().as_text()
    if _scoped(text):
        return text
    cfg = jax.config
    keep = (cfg.jax_compilation_cache_include_metadata_in_key,
            cfg.jax_persistent_cache_min_compile_time_secs)
    cfg.update('jax_compilation_cache_include_metadata_in_key', True)
    cfg.update('jax_persistent_cache_min_compile_time_secs', 1e9)
    try:
        # JAX also remembers, in memory and by module, the executable
        # the compile above was handed; dropping that memo costs a
        # later re-lowering one more load, and no running program
        from jax._src.interpreters import pxla
        pxla._cached_compilation.cache_clear()
        return traced.lower(
            lowering_platforms=(jax.default_backend(),)
        ).compile().as_text()
    finally:
        cfg.update('jax_compilation_cache_include_metadata_in_key',
                   keep[0])
        cfg.update('jax_persistent_cache_min_compile_time_secs', keep[1])


def scope_map(min_runs=1, executors=None):
    """``{module: {instruction name: op_name}}`` for the compiled
    programs of ``executors`` (every live Executor by default) that ran
    at least ``min_runs`` times (a training loop's step, not its
    startup program). On demand only — a trace reader or a tool calls
    this, ``Executor.run`` never does: each entry is lowered and
    compiled once more from the abstract arguments of its miss, which
    costs seconds (the persistent compile cache serves the compile). A
    module is keyed ``<hlo module>|<fingerprint>|<n>``; an entry that
    cannot be read maps to ``{'error': <why>}`` under the same key."""
    out = {}
    for exe in list(_EXECUTORS) if executors is None else executors:
        for fp, jitted, abstract, sharded, runs in exe.lowerable_entries():
            if runs < min_runs or jitted is None:
                continue
            key = '|%s|%d' % (fp, len(out))
            try:
                with exe.device_context(sharded):
                    text = _compiled_text(jitted, abstract)
                module, scopes = parse_scopes(text)
                out[module + key] = scopes
            except Exception as e:  # noqa: BLE001 — diagnostic: the map
                # says which entry it could not read, and why
                out['?' + key] = {'error': '%s: %s' % (type(e).__name__, e)}
    return out


# ---- shared offline helpers ----------------------------------------------
def compile_log():
    """What jax said of the compile path so far, oldest first (copies):
    one entry ``{t, kind, dur_s, fun, phase, fp, thread}`` a ``trace``,
    ``mlir`` or ``backend`` event (``cache`` and, on a hit,
    ``retrieval_s`` / ``saved_s`` on a ``backend`` one), and the
    Executor's own ``miss`` entry a cache miss (OBSERVABILITY.md, "The
    compile path"). ``t`` is ``time.perf_counter()`` at the event's
    end."""
    return _tracing.COMPILE_LOG.entries()


def memory_dict(comp):
    """Per-device byte accounting of an AOT-compiled executable —
    the shared ``memory_analysis()`` reader (ParallelExecutor
    ``compile_stats``)."""
    ma = comp.memory_analysis()
    return {'argument_bytes': int(ma.argument_size_in_bytes),
            'output_bytes': int(ma.output_size_in_bytes),
            'temp_bytes': int(ma.temp_size_in_bytes)}


# ---- regression baseline --------------------------------------------------
class PerfBaseline(object):
    """On-disk perf baseline: schema'd JSON of
    entries keyed ``fingerprint|shape-sig|backend|mesh``. Deterministic
    fields (flops, bytes) must MATCH within ``DETERMINISTIC_RTOL``;
    timing fields (``step_ms``, ``mfu``), when present on both sides,
    gate regressions at the caller's tolerance."""

    def __init__(self, path):
        self.path = path
        self.entries = {}

    @staticmethod
    def key(fingerprint, shape_sig, backend, mesh):
        return '%s|%s|%s|%s' % (fingerprint, shape_sig, backend, mesh)

    @classmethod
    def entry_from_ledger(cls, ledger, with_timings=False):
        e = {'program': ledger.label or ledger.fingerprint[:12],
             'device_kind': ledger.device_kind,
             'flops': ledger.flops,
             'bytes_accessed': ledger.bytes_accessed,
             'temp_bytes': ledger.temp_bytes,
             'argument_bytes': ledger.argument_bytes,
             'output_bytes': ledger.output_bytes}
        if with_timings and ledger.measured_ms:
            e['step_ms'] = round(ledger.measured_ms, 3)
            m = ledger.mfu()
            if m is not None:
                e['mfu'] = round(m, 4)
        return e

    # -- persistence --------------------------------------------------------
    def load(self):
        try:
            with open(self.path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            return self
        if data.get('schema') == BASELINE_SCHEMA:
            self.entries = dict(data.get('entries', {}))
        return self

    def save(self):
        payload = {'schema': BASELINE_SCHEMA,
                   'entries': dict(self.entries)}
        d = os.path.dirname(os.path.abspath(self.path))
        try:
            os.makedirs(d)
        except OSError:
            pass
        tmp = self.path + '.tmp.%d' % os.getpid()
        with open(tmp, 'w') as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write('\n')
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.path)

    def put(self, key, entry):
        self.entries[key] = dict(entry)

    # -- the sentinel -------------------------------------------------------
    def diff(self, current, tol=0.10, det_rtol=DETERMINISTIC_RTOL):
        """Compare ``current`` ({key: entry}) against the baseline.
        Returns a list of problem strings, each naming the program —
        empty means the run is clean. Baseline keys absent from the
        run are reported (a program stopped compiling); run keys absent
        from the baseline are NOT (new programs ratchet in via
        ``--update-baseline``)."""
        problems = []
        for key, base in sorted(self.entries.items()):
            name = base.get('program') or key.split('|')[0][:12]
            cur = current.get(key)
            if cur is None:
                problems.append(
                    '%s: program missing from run (baseline key %s)'
                    % (name, key))
                continue
            for f in ('flops', 'bytes_accessed'):
                b, c = base.get(f), cur.get(f)
                if b is None or c is None:
                    continue
                if abs(c - b) > det_rtol * max(abs(b), 1.0):
                    problems.append(
                        '%s: %s drifted %.4g -> %.4g (> %.0f%% rtol)'
                        % (name, f, b, c, det_rtol * 100))
            b_ms, c_ms = base.get('step_ms'), cur.get('step_ms')
            if b_ms and c_ms and c_ms > b_ms * (1.0 + tol):
                problems.append(
                    '%s: step time regressed %.3f ms -> %.3f ms '
                    '(> %.0f%% tolerance)' % (name, b_ms, c_ms,
                                              tol * 100))
            b_m, c_m = base.get('mfu'), cur.get('mfu')
            if b_m and c_m and c_m < b_m * (1.0 - tol):
                problems.append(
                    '%s: MFU regressed %.4f -> %.4f (> %.0f%% '
                    'tolerance)' % (name, b_m, c_m, tol * 100))
        return problems
