"""AOT executable cold-start cache: millisecond replica warmup.

Scale-out is only reactive when a fresh replica can start serving
before the traffic spike is over, and on TPU-class programs the cold
path is compile-bound — tens of seconds of XLA for a model that then
answers in milliseconds. This module turns the Executor's compile-miss
path into a persisted-artifact store (the TuningCache/PerfBaseline
pattern, SERVING.md "Self-driving fleet"):

- on compile-miss the Executor — behind the ``PTPU_AOT_CACHE`` gate —
  AOT-compiles (``lower().compile()``) instead of letting ``jax.jit``
  compile lazily, serializes the executable via
  ``jax.experimental.serialize_executable`` and persists it keyed by
  the existing ``program_cache_key`` (so anything that would change
  the compilation — program fingerprint, shapes/dtypes, pass pipeline
  token, partition/mesh token — changes the file name);
- a fresh replica's ``warmup()`` drives the same misses, finds the
  entries and **deserializes instead of recompiling** — cold start
  drops from compile-bound to I/O-bound (gated in
  ``tools/fleet_bench.py --smoke``).

Every entry embeds an invalidation token (jax/jaxlib versions,
backend, device kind, device count, mesh signature): a cache written
by a different toolchain or topology is silently a miss, never a
wrong executable. Writes are atomic (tmp + ``os.replace``, the
TuningCache idiom) so concurrent replicas can share one directory;
every failure mode (corrupt file, version skew, serialization refusal)
degrades to a counted miss — the run path never breaks because the
cache did.

This module is the ONE place allowed to call AOT compile on the
warmup path (``tools/lint_repo.py`` pins that); everything else goes
through :class:`AotStore`.

Telemetry: ``coldstart_hits_total`` / ``coldstart_misses_total`` /
``coldstart_saves_total`` / ``coldstart_failures_total`` /
``coldstart_invalidated_total`` counters,
``coldstart_load_seconds`` / ``coldstart_save_seconds`` histograms,
and a ``coldstart`` journal event per hit/save/invalidation.
"""
import contextlib
import hashlib
import logging
import os
import pickle
import tempfile
import threading
import time

from .. import observability as _obs

__all__ = ['AOT_CACHE_ENV', 'AotStore', 'cache_dir', 'cache_scope',
           'enabled', 'default_store', 'export_env', 'key_hash',
           'token']

logger = logging.getLogger('paddle_tpu.fleet')

AOT_CACHE_ENV = 'PTPU_AOT_CACHE'
# schema 2: entries are sealed WITHOUT state donation — a schema-1
# executable carries input_output_alias metadata whose jax-side
# dispatch bookkeeping does not survive the serialize round trip, and
# deserializing one corrupts state buffers shared across shape buckets
# schema 3: entries record the devices they were compiled for
_SCHEMA = 3
_SUFFIX = '.aotx'

_lock = threading.Lock()
_override_dir = None          # process override (cache_scope / tests)
_stores = {}                  # realpath -> AotStore


def cache_dir():
    """The active cache directory, or None (gate closed). A process
    override (:func:`cache_scope`) wins over ``PTPU_AOT_CACHE``."""
    if _override_dir is not None:
        return _override_dir
    return os.environ.get(AOT_CACHE_ENV) or None


def enabled():
    return cache_dir() is not None


def export_env(env):
    """Spawned-replica env contract (RESILIENCE.md "Cross-host
    elasticity"): copy the ACTIVE cache dir — including a
    process-local :func:`cache_scope` override the child could never
    observe — into ``env`` as ``PTPU_AOT_CACHE``, so a remote cell's
    ``warmup()`` deserializes from the same store the parent sealed.
    No-op when the gate is closed. Returns ``env``."""
    d = cache_dir()
    if d:
        env[AOT_CACHE_ENV] = os.path.abspath(d)
    return env


@contextlib.contextmanager
def cache_scope(dirname):
    """Scoped enable for tests/benches: the AOT store lives under
    ``dirname`` for the duration, regardless of the environment."""
    global _override_dir
    with _lock:
        prev, _override_dir = _override_dir, str(dirname)
    try:
        yield
    finally:
        with _lock:
            _override_dir = prev


def default_store():
    """The (memoized) store for the active cache dir, or None when the
    gate is closed."""
    d = cache_dir()
    if d is None:
        return None
    key = os.path.realpath(d)
    with _lock:
        store = _stores.get(key)
        if store is None:
            store = _stores[key] = AotStore(d)
        return store


def key_hash(cache_key):
    """Stable filename for a ``program_cache_key`` tuple. The tuple
    mixes strings, bools, bytes (shape/dtype signatures via
    ``tobytes()``) and compiler/partition tokens; ``repr`` of it is
    deterministic within a process *and* across processes because
    every component is content-derived, so its sha256 is the on-disk
    identity of the compilation."""
    return hashlib.sha256(repr(cache_key).encode('utf-8')).hexdigest()


def token(backend='', device_kind='', devices=1, mesh=''):
    """Invalidation token persisted with every entry: an executable
    only deserializes into the toolchain + topology that built it."""
    import jax
    try:
        import jaxlib
        jaxlib_v = getattr(jaxlib, '__version__', '')
    except Exception:  # pragma: no cover - jaxlib always ships with jax
        jaxlib_v = ''
    return {'schema': _SCHEMA, 'jax': jax.__version__,
            'jaxlib': jaxlib_v, 'backend': str(backend),
            'device_kind': str(device_kind), 'devices': int(devices),
            'mesh': str(mesh or '')}


class AotStore(object):
    """Atomic on-disk store of AOT-serialized executables.

    One file per compilation: ``<dir>/<sha256(program_cache_key)>.aotx``
    holding a pickled ``{'token', 'payload', 'in_tree', 'out_tree',
    'device_ids'}`` record. The payload is what
    ``serialize_executable.serialize`` returns; the trees are the
    PyTreeDefs needed to rebuild the ``Compiled``'s calling convention;
    the device ids are the devices it was compiled for, in assignment
    order — ``deserialize_and_load`` otherwise loads onto every local
    device, and a one-device executable then demands one shard per
    device. Trust model: the cache dir is
    operator-provided, the same trust domain as the TuningCache — do
    not point it at hostile data.
    """

    def __init__(self, dirname):
        self.dirname = str(dirname)
        reg = _obs.default_registry()
        self.m_hits = reg.counter(
            'coldstart_hits_total',
            'compile-misses warmed from the AOT executable cache')
        self.m_misses = reg.counter(
            'coldstart_misses_total',
            'compile-misses with no usable AOT cache entry')
        self.m_saves = reg.counter(
            'coldstart_saves_total',
            'AOT-serialized executables persisted to the cache')
        self.m_failures = reg.counter(
            'coldstart_failures_total',
            'AOT cache operations that failed and degraded to the '
            'compile path')
        self.m_invalid = reg.counter(
            'coldstart_invalidated_total',
            'AOT cache entries rejected by the invalidation token '
            '(toolchain/topology skew)')
        self.m_load = reg.histogram(
            'coldstart_load_seconds',
            'wall seconds to deserialize an AOT executable')
        self.m_save = reg.histogram(
            'coldstart_save_seconds',
            'wall seconds to AOT-serialize + persist an executable')

    def path(self, cache_key):
        return os.path.join(self.dirname, key_hash(cache_key) + _SUFFIX)

    # ---- read path -------------------------------------------------------
    def load(self, cache_key, **token_kw):
        """The deserialized ``Compiled`` for this compilation, or None
        (miss). Never raises: corrupt/mismatched entries count as
        failures/invalidations and fall back to compiling."""
        path = self.path(cache_key)
        t0 = time.perf_counter()
        try:
            with open(path, 'rb') as f:
                rec = pickle.load(f)
        except FileNotFoundError:
            self.m_misses.inc()
            return None
        except Exception as e:  # noqa: BLE001 — corrupt entry: degrade
            self.m_failures.inc()
            self.m_misses.inc()
            logger.warning('coldstart: unreadable entry %s: %r', path, e)
            return None
        want = token(**token_kw)
        if rec.get('token') != want:
            self.m_invalid.inc()
            self.m_misses.inc()
            _obs.emit('coldstart', action='invalid',
                      key=key_hash(cache_key)[:12],
                      have=rec.get('token'), want=want)
            return None
        try:
            import jax
            from jax.experimental.serialize_executable import \
                deserialize_and_load
            local = {d.id: d for d in jax.local_devices()}
            compiled = deserialize_and_load(
                rec['payload'], rec['in_tree'], rec['out_tree'],
                execution_devices=[local[i] for i in rec['device_ids']])
        except Exception as e:  # noqa: BLE001 — skew the token missed
            self.m_failures.inc()
            self.m_misses.inc()
            logger.warning('coldstart: deserialize failed for %s: %r',
                           path, e)
            return None
        dur = time.perf_counter() - t0
        self.m_hits.inc()
        self.m_load.observe(dur)
        _obs.emit('coldstart', action='hit',
                  key=key_hash(cache_key)[:12],
                  bytes=len(rec['payload']), dur_s=round(dur, 6))
        return compiled

    # ---- write path ------------------------------------------------------
    def save(self, cache_key, compiled, **token_kw):
        """Serialize + atomically persist a ``Compiled``. Returns True
        on success; failures are counted and swallowed (an unsaveable
        executable — host callbacks, unserializable custom calls —
        just stays process-local)."""
        t0 = time.perf_counter()
        try:
            from jax.experimental.serialize_executable import serialize
            payload, in_tree, out_tree = serialize(compiled)
            rec = {'token': token(**token_kw), 'payload': payload,
                   'in_tree': in_tree, 'out_tree': out_tree,
                   'device_ids': [
                       d.id for d in
                       compiled.runtime_executable().local_devices()]}
            blob = pickle.dumps(rec, protocol=pickle.HIGHEST_PROTOCOL)
            os.makedirs(self.dirname, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=self.dirname,
                                       suffix=_SUFFIX + '.tmp')
            try:
                with os.fdopen(fd, 'wb') as f:
                    f.write(blob)
                os.replace(tmp, self.path(cache_key))
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
        except Exception as e:  # noqa: BLE001 — persistence is an
            # optimization; the compiled executable still serves
            self.m_failures.inc()
            logger.warning('coldstart: save failed: %r', e)
            return False
        dur = time.perf_counter() - t0
        self.m_saves.inc()
        self.m_save.observe(dur)
        _obs.emit('coldstart', action='save',
                  key=key_hash(cache_key)[:12], bytes=len(blob),
                  dur_s=round(dur, 6))
        return True

    # ---- compile path ----------------------------------------------------
    @staticmethod
    def aot_compile(jitted, feed, state, shardings=None):
        """The one AOT ``lower().compile()`` allowed on the warmup path
        (lint-pinned): turn a lazily-compiling ``jax.jit`` object into
        the concrete ``Compiled`` this store persists. The Executor
        seals only what it jitted (never a profiled, dynamic or guarded
        step), so ``jitted`` always lowers.

        ``shardings``, when given, is a ``(feed_shardings,
        state_shardings)`` pair of name->Sharding dicts from the
        Partitioner. Bare avals lower to a single-device executable
        even when the live dispatch is mesh-committed, and XLA refuses
        the sharding mismatch at call time — so on the sharded path
        the avals must carry the same shardings the dispatch will use."""
        import jax

        def aval(v, s=None):
            if s is not None:
                return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=s)
            return jax.ShapeDtypeStruct(v.shape, v.dtype)

        if shardings is None:
            abstract = jax.tree_util.tree_map(aval, (feed, state))
        else:
            feeds_s, state_s = shardings
            abstract = (
                {n: aval(v, (feeds_s or {}).get(n))
                 for n, v in feed.items()},
                {n: aval(v, (state_s or {}).get(n))
                 for n, v in state.items()})
        return jitted.lower(*abstract).compile()

    def entries(self):
        """Hash prefixes of the entries on disk (ops/debug)."""
        try:
            names = os.listdir(self.dirname)
        except OSError:
            return []
        return sorted(n[:-len(_SUFFIX)] for n in names
                      if n.endswith(_SUFFIX))
