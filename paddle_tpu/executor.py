"""Executor / Scope.

Parity: python/paddle/fluid/executor.py (Executor.run, global_scope,
scope_guard, fetch_var) and paddle/fluid/framework/{executor.cc,scope.cc}.

TPU design: ``run`` fingerprints (program, feed signature, fetch list) and
compiles the whole block once via :mod:`paddle_tpu.core.lowering`; repeat
steps hit the executable cache. Persistable state (parameters, optimizer
accumulators, BN moving stats, step counters, PRNG key) flows through the
executable as donated buffers, so a training step is a single device
computation with no host round-trips.
"""
import collections
import contextlib
import itertools
import threading

import numpy as np
import jax
import jax.numpy as jnp

from . import framework
from . import observability as _obs
from .observability import perf as _perf, tracing as _tracing
from .framework import Program, Variable, default_main_program
from .core import places as _places
from .core import lowering
from .core.lowering import (lower_block, runtime_dtype, RNG_KEY,
                            _op_reads)
from .lod import SequenceTensor
from .resilience import anomaly as _anomaly
from . import analysis as _analysis
from .analysis import ProgramInvalid

__all__ = ['Executor', 'CacheInfo', 'global_scope', 'scope_guard',
           'switch_scope', 'fetch_var', 'as_numpy']

CacheInfo = collections.namedtuple('CacheInfo', ['hits', 'misses', 'size'])


def _dynamic_memoized(program):
    """:func:`_is_dynamic_program`, once per program fingerprint."""
    memo = program.__dict__.setdefault('_dynamic_memo', {})
    fp = program.fingerprint()
    if fp not in memo:
        memo[fp] = _is_dynamic_program(program)
    return memo[fp]


class _Lowerable(object):
    __slots__ = ('abstract', 'sharded', 'runs')

    def __init__(self, abstract, sharded):
        self.abstract, self.sharded, self.runs = abstract, sharded, 1


# What exe/prep hands to exe/launch and exe/commit: the compiled
# callable with its arguments, and what the lookup found. ``lowered``
# is ``(verify_s, lower_s)`` where this call will trace and compile (a
# miss that no AOT entry served), else None; ``checked`` where the
# callable is checkify-wrapped; ``seen`` the compile log's count as
# exe/prep ends.
_Step = collections.namedtuple('_Step', [
    'jitted', 'feed', 'state', 'fp', 'fetch_names', 'sharded', 'cache',
    'lowered', 'checked', 'ledger', 'seen'])

def _coldstart_store():
    """The active AOT cold-start store (SERVING.md "Self-driving
    fleet"), or None when the ``PTPU_AOT_CACHE`` gate is closed. The
    fleet package imports serving which imports this module, so the
    reach into fleet.coldstart must be lazy (run time, import cycle
    safe) — and when the gate is closed and the module was never
    imported (no ``cache_scope`` override can exist), one env check
    answers without importing the fleet tier at all."""
    import os
    import sys
    mod = sys.modules.get('paddle_tpu.fleet.coldstart')
    if mod is None:
        if not os.environ.get('PTPU_AOT_CACHE'):
            return None
        from .fleet import coldstart as mod
    return mod.default_store()


def _mesh_committed(v):
    """True for a jax.Array committed to more than one device. An
    unsharded dispatch can still see such args when the scope is shared
    with a sharded Executor (partition parity tests do exactly this);
    a single-device sealed executable would refuse them at call time,
    so the seal path must detect and stand down to lazy jit."""
    s = getattr(v, 'sharding', None)
    return s is not None and len(getattr(s, 'device_set', ())) > 1


class VarBinding(object):
    """Live handle to a scope slot. Parity: the runtime ``Variable``
    returned by ``Scope::FindVar`` — reference scripts write pretrained
    params through ``find_var(name).get_tensor().set(np, place)``
    (book/test_label_semantic_roles.py:204-208). Reads delegate to the
    current value, so jax-array attributes (``sharding``,
    ``addressable_shards``, ``shape``) keep working on the handle."""

    __slots__ = ('_scope', '_name')

    def __init__(self, scope, name):
        object.__setattr__(self, '_scope', scope)
        object.__setattr__(self, '_name', name)

    def value(self):
        return self._scope.raw(self._name)

    def get_tensor(self):
        return self

    def set(self, array, place=None):
        import jax.numpy as jnp
        val = self.value()
        if isinstance(val, SequenceTensor):
            val.set(array, place)
            return
        arr = np.asarray(array)
        if val is not None and hasattr(val, 'dtype'):
            arr = arr.astype(val.dtype)
        # write into the scope that actually owns the slot
        s = self._scope
        while s is not None and self._name not in s.vars:
            s = s.parent
        (s or self._scope).vars[self._name] = jnp.asarray(arr)

    def lod(self):
        val = self.value()
        return val.lod() if isinstance(val, SequenceTensor) else []

    def __array__(self, dtype=None, copy=None):
        arr = np.asarray(as_numpy(self.value()))
        return arr.astype(dtype) if dtype is not None else arr

    def __getattr__(self, attr):
        return getattr(self.value(), attr)

    def __repr__(self):
        return "VarBinding(%r -> %r)" % (self._name, self.value())


class Scope(object):
    """name -> runtime value (jax array / SequenceTensor). Parity: Scope."""

    def __init__(self, parent=None):
        self.vars = {}
        self.parent = parent

    def raw(self, name):
        """The stored runtime value (internal fast path)."""
        s = self
        while s is not None:
            if name in s.vars:
                return s.vars[name]
            s = s.parent
        return None

    def find_var(self, name):
        """Reference-style handle (or None): supports ``.get_tensor()``
        ``.set(np, place)`` and delegates reads to the live value.

        A slot whose stored value is None counts as NOT found — callers
        (e.g. _state_names_uncached, RNG init) rely on the classic
        'find_var(...) is not None' presence test."""
        s = self
        while s is not None:
            if name in s.vars:
                if s.vars[name] is None:
                    return None
                return VarBinding(self, name)
            s = s.parent
        return None

    def var(self, name):
        """Declare (or fetch) a slot and return a usable binding, so the
        reference pattern ``scope.var(n)`` / ``...get_tensor().set(...)``
        works even before any value lands in the slot (ADVICE r4:
        find_var treats a None slot as absent by design — the presence
        test contract — so declaration must hand out its own binding)."""
        self.vars.setdefault(name, None)
        return VarBinding(self, name)

    def set_var(self, name, value):
        self.vars[name] = value

    def new_scope(self):
        return Scope(parent=self)

    def drop_kids(self):
        pass

    def keys(self):
        return self.vars.keys()


_global_scope = Scope()


def global_scope():
    return _global_scope


def switch_scope(scope):
    global _global_scope
    prev = _global_scope
    _global_scope = scope
    return prev


@contextlib.contextmanager
def scope_guard(scope):
    prev = switch_scope(scope)
    yield
    switch_scope(prev)


def as_numpy(value):
    if isinstance(value, VarBinding):
        value = value.value()
    if isinstance(value, np.ndarray):
        # already a host array: hand it back as-is instead of running it
        # through np.asarray again (the half-inference _to_f32_fetch
        # path used to double-convert here)
        return value
    if isinstance(value, SequenceTensor):
        if value.lengths is None:
            # packed/dense-wrapped mode: preserve offsets, not lengths
            out = SequenceTensor(np.asarray(value.data), None)
            out._packed = out.data
            out._offsets = None if value._offsets is None else \
                [list(level) for level in value._offsets]
            return out
        return SequenceTensor(np.asarray(value.data),
                              np.asarray(value.lengths),
                              None if value.sub_lengths is None
                              else np.asarray(value.sub_lengths))
    if isinstance(value, (list, tuple)):
        return [as_numpy(v) for v in value]
    return np.asarray(value)


def _to_f32_fetch(f):
    """Half-inference boundary: float fetches back to f32, preserving
    SequenceTensor structure (incl. packed mode). A fetch that is
    already a HOST numpy array is converted host-side — the old
    ``jnp.asarray`` spelling shipped it device-ward only for
    ``as_numpy`` to immediately pull it back (a redundant H2D+D2H round
    trip per fetch)."""
    def _cast(arr):
        if isinstance(arr, np.ndarray):
            # jnp.issubdtype also recognizes ml_dtypes halves (bf16)
            # that numpy's own issubdtype does not class as floating
            if jnp.issubdtype(arr.dtype, jnp.floating) and \
                    arr.dtype != np.float32:
                return arr.astype(np.float32)
            return arr
        arr = jnp.asarray(arr)
        if jnp.issubdtype(arr.dtype, jnp.floating):
            return arr.astype(jnp.float32)
        return arr

    if isinstance(f, SequenceTensor):
        if f._packed is not None and f._offsets:
            p = _cast(f._packed)
            if p is f._packed:
                return f
            return SequenceTensor.from_packed(p, f._offsets)
        d = _cast(f.data)
        if d is f.data:
            return f
        return SequenceTensor(d, f.lengths, f.sub_lengths)
    if hasattr(f, 'dtype'):
        return _cast(f)
    return f


def fetch_var(name, scope=None, return_numpy=True):
    scope = scope or global_scope()
    val = scope.raw(name)
    if return_numpy and val is not None:
        return as_numpy(val)
    return val


def _side_effect_ops():
    from .core.registry import SIDE_EFFECT_OPS
    return SIDE_EFFECT_OPS


def _spec(val):
    if isinstance(val, SequenceTensor):
        return ('seq', tuple(val.data.shape), str(val.data.dtype),
                val.sub_lengths is not None)
    arr = np.asarray(val) if not hasattr(val, 'shape') else val
    return (tuple(arr.shape), str(arr.dtype))


def program_cache_key(program, feed, static_env, fetch_names, state_in,
                      state_out, guard, *extra):
    """The jit-cache key shared by Executor.run and ParallelExecutor.run
    — ONE builder so a new invalidation dimension can never be added to
    one executor and missed in the other (static shape-feed VALUES are
    part of the key: a new shape value must retrace). The compiler's
    pass-pipeline signature rides in here too, so toggling optimization
    can never serve a stale compiled program. Callers append
    the Partitioner's cache token via ``*extra`` — (mesh shape, device
    ids, resolved sharding signature) — so one Executor can serve the
    same program on different meshes/shardings with exactly one
    compile per (fingerprint, sharding, mesh) triple."""
    from . import compiler as _compiler
    fp = program.fingerprint()
    feed_sig = tuple(sorted((n, _spec(v)) for n, v in feed.items()))
    return (fp, feed_sig,
            tuple(sorted((n, v.dtype.str, v.shape, v.tobytes())
                         for n, v in static_env.items())),
            tuple(fetch_names), tuple(state_in), tuple(state_out),
            guard, _compiler.pipeline_signature()) + tuple(extra)


def _stack_steps(*xs):
    """Stack K per-step feed leaves onto a leading [K] axis for
    run_chained. Host numpy leaves stack on host, so the whole chunk
    crosses to the device as ONE transfer at dispatch; device-resident
    leaves stack on device."""
    if all(isinstance(x, np.ndarray) for x in xs):
        return np.stack(xs)
    return jnp.stack([jnp.asarray(x) for x in xs])


def _block_has(block, types):
    for op in block.ops:
        if op.type in types:
            return True
        sub = op.attrs.get('sub_block')
        if sub is not None and _block_has(sub, types):
            return True
    return False


def _is_dynamic_program(program):
    """True when a While sub-block contains beam search AND the program
    feeds 2-level LoD data (the reference decode's init_ids/init_scores):
    beam topology is then data-dependent — row counts shrink per step —
    so the program executes EAGERLY (host control flow + concrete
    values, exactly the reference Executor's model). A static-beam
    decode ([B*K] dense rows, no multi-level-LoD feeds) keeps the
    jitted whole-block path: its While lowers to lax.while_loop."""
    beam_whiles = []
    for b in program.blocks:
        for op in b.ops:
            sub = op.attrs.get('sub_block')
            if op.type == 'while' and sub is not None and _block_has(
                    sub, ('beam_search',)):
                beam_whiles.append(op)
    if not beam_whiles:
        return False
    # restrict the lod-2 test to vars that actually REACH a beam While
    # (transitive producers of its inputs): an unrelated nested-sequence
    # feed elsewhere must not force a 146x-slower eager decode
    producers = {}
    for b in program.blocks:
        for op in b.ops:
            for n in op.output_arg_names:
                producers.setdefault(n, []).append(op)
    for w_op in beam_whiles:
        seen, frontier = set(), list(_op_reads(w_op))
        while frontier:
            n = frontier.pop()
            if n in seen:
                continue
            seen.add(n)
            var = program.global_block()._find_var_recursive(n)
            if var is not None and getattr(var, 'is_data', False) and \
                    getattr(var, 'lod_level', 0) >= 2:
                return True
            for p in producers.get(n, ()):
                if p is not w_op:
                    # sub-block aware: a producing control-flow op may
                    # read the lod-2 feed only inside its sub-block
                    frontier.extend(_op_reads(p))
    return False


class Executor(object):
    def __init__(self, place=None, partitioner=None):
        self.place = place or _places.default_place()
        # Placement owner (PARTITIONING.md): every Executor dispatches
        # through a Partitioner. None defers to the lazy CPU-fallback
        # partitioner for `place` (a 1-device mesh -> plain jit,
        # bit-identical to the classic single-device executor);
        # ParallelExecutor and a sharded ModelServer pass a real-mesh
        # partitioner and the SAME run/run_chained code paths compile
        # sharded programs instead.
        self._partitioner = partitioner
        # serving worker threads share one Executor so padded batches of
        # every model land in ONE compiled-program cache; the lock makes
        # lookup+insert atomic (lower_block itself is cheap — XLA
        # compilation happens lazily at first call, outside the lock,
        # under jax.jit's own thread-safe cache)
        self._cache = {}
        self._cache_lock = threading.RLock()
        # beside each jitted entry, what it was first called with (shapes
        # and shardings, no arrays) and how often it ran: enough for
        # observability.perf.scope_map() to lower it again on demand
        self._lowerable = {}
        # step_num of this Executor's exe/run and exe/chain phases
        self._step_nums = itertools.count()
        self._cache_hits = 0
        self._cache_misses = 0
        # process-wide telemetry (OBSERVABILITY.md): every Executor
        # publishes into the same registry series; the per-instance
        # ints above stay the source of the per-Executor cache_info()
        # contract the serving tests pin.
        reg = _obs.default_registry()
        self._m_hits = reg.counter(
            'executor_cache_hits_total',
            'compiled-program cache hits across all Executors')
        self._m_misses = reg.counter(
            'executor_cache_misses_total',
            'compiled-program cache misses (each one is a trace+compile)')
        self._m_run = reg.histogram(
            'executor_run_seconds',
            'host wall of the jitted call (exe/launch): under '
            'asynchronous dispatch the enqueue, not the device\'s time')
        self._m_compile = reg.histogram(
            'executor_compile_seconds',
            'lowering + first (compiling) execution wall per cache miss')
        _perf.register_executor(self)

    @property
    def partitioner(self):
        if self._partitioner is None:
            from .partition import Partitioner
            self._partitioner = Partitioner.for_place(self.place)
        return self._partitioner

    def set_partitioner(self, partitioner):
        """Swap the placement owner. Compiled programs for the old
        mesh stay cached (their keys carry the old partition token);
        subsequent runs compile/lookup under the new one."""
        self._partitioner = partitioner
        return partitioner

    def cache_info(self):
        """Compiled-program cache counters: a serving-layer SLI. A miss
        means a fresh trace+compile (seconds); shape bucketing exists to
        keep this at one miss per (program, bucket)."""
        with self._cache_lock:
            return CacheInfo(self._cache_hits, self._cache_misses,
                             len(self._cache))

    def reset_cache_info(self):
        """Zero the hit/miss counters WITHOUT dropping compiled
        programs, so benchmark phases can be measured independently
        instead of accumulating over the process lifetime. The
        process-wide registry counters stay cumulative (Prometheus
        semantics); use ``observability.default_registry().reset()`` to
        zero those too."""
        with self._cache_lock:
            self._cache_hits = 0
            self._cache_misses = 0

    # -------------------------------------------------------------------------
    def _prepare_feed(self, program, feed, dynamic=False):
        block = program.global_block()
        # Float16Transpiler contract: the USER keeps feeding f32; the
        # boundary cast folds into the dtype selection below (the
        # reference appends cast ops instead,
        # contrib/float16/float16_transpiler.py). numpy casting
        # (ml_dtypes) keeps host feeds host-side so device placement
        # still happens under the run's default_device.
        half = getattr(program, '_half_inference', None)

        def _dt(d):
            d = runtime_dtype(d)
            return half if half and d == 'float32' else d

        out = {}
        for name, val in feed.items():
            var = block._find_var_recursive(name)
            if dynamic and isinstance(val, SequenceTensor) and \
                    val._packed is not None and val._offsets and \
                    len(val._offsets) >= 2:
                # eager dynamic programs consume 2-level (beam-world)
                # feeds in the reference's packed-rows + offset-LoD
                # layout directly; level-1 sequence feeds keep the
                # padded layout for the scan-based sequence kernels
                out[name] = SequenceTensor.from_packed(
                    jnp.asarray(val._packed), val._offsets)
                continue
            if isinstance(val, SequenceTensor) and val.lengths is None:
                # imperative LoDTensor with set() but no set_lod():
                # a plain dense tensor in reference semantics
                val = val.data
            elif isinstance(val, SequenceTensor) and \
                    val._packed is not None and var is not None and \
                    not getattr(var, 'lod_level', 0):
                # LoD metadata on a feed whose var is declared dense
                # (lod_level 0): reference semantics treat the lod as
                # row bookkeeping over the same packed data — drop it
                val = val._packed
            if isinstance(val, SequenceTensor):
                if isinstance(val.data, jax.Array):
                    # Device-resident sequence feed: no host round-trip.
                    dt = _dt(var.dtype if var else val.data.dtype)
                    data = val.data if str(val.data.dtype) == dt \
                        else val.data.astype(dt)
                    out[name] = SequenceTensor(data, val.lengths,
                                               val.sub_lengths)
                    continue
                data = np.asarray(val.data)
                dt = _dt(var.dtype if var else data.dtype)
                out[name] = SequenceTensor(
                    data.astype(dt), np.asarray(val.lengths, np.int32),
                    None if val.sub_lengths is None
                    else np.asarray(val.sub_lengths, np.int32))
            elif isinstance(val, jax.Array):
                # Device-resident feed: never round-trip through the host.
                dt = _dt(var.dtype if var else val.dtype)
                out[name] = val if str(val.dtype) == dt else val.astype(dt)
            else:
                arr = np.asarray(val)
                dt = _dt(var.dtype if var else arr.dtype)
                out[name] = arr.astype(np.dtype(dt))
        return out

    def _state_names(self, program, scope):
        # Steady-state steps skip the whole-block var scan: the result
        # only changes when the program mutates (fingerprint) or the
        # scope chain gains/loses vars. The memo lives ON the scope so
        # it dies with it (no id()-reuse aliasing, no unbounded growth
        # in a long-lived Executor).
        census, name_hash = 0, 0
        s = scope
        while s is not None:
            census += len(s.vars)
            for n in s.vars:
                # Order-independent fold over the chain's var NAMES, so
                # replacing a var with a differently-named one (count
                # unchanged) still invalidates. census guards the
                # duplicate-name-across-scopes xor cancellation.
                name_hash ^= hash(n)
            s = s.parent
        memo = getattr(scope, '_state_names_memo', None)
        if memo is None:
            memo = scope._state_names_memo = {}
        key = (program.fingerprint(), census, name_hash)
        hit = memo.get(key)
        if hit is not None:
            return hit
        result = self._state_names_uncached(program, scope)
        memo[key] = result
        return result

    def _state_names_uncached(self, program, scope):
        names_in, names_out = [], set()
        for b in program.blocks:
            for v in b.vars.values():
                if v.persistable and scope.find_var(v.name) is not None:
                    names_in.append(v.name)
            for op in b.ops:
                for n in op.output_arg_names:
                    var = b._find_var_recursive(n)
                    if var is not None and var.persistable:
                        names_out.add(n)
        names_in = sorted(set(names_in))
        names_out = sorted(names_out | set(names_in))
        return names_in, names_out

    def _maybe_prune(self, program, fetch_names):
        """Inference-style programs (no backward, no control flow) lower
        only the ancestors of the fetches + persistable-state writes.

        TPU rationale: the whole block becomes ONE XLA program, so dead
        branches would otherwise be traced (and their feeds required) even
        though XLA DCEs them post-compile. Training programs (backward
        marker) and programs with sub-blocks are lowered whole.
        """
        if not fetch_names:
            return program
        block = program.global_block()
        persist_outs = []
        for op in block.ops:
            if op.type in _side_effect_ops():
                # training step / host side effects: lower the whole block
                return program
            if any(isinstance(v, framework.Block)
                   for v in op.attrs.values()):
                return program
            for n in op.output_arg_names:
                var = block._find_var_recursive(n)
                if var is not None and var.persistable:
                    persist_outs.append(n)
        targets = list(fetch_names) + persist_outs
        pruned = program.prune(targets)
        return pruned

    def _optimized_program(self, program, fetch_names, scope=None,
                           dynamic=False):
        """The compiler hook: prune to fetches (as before), then run
        the canonical pass pipeline (paddle_tpu.compiler, COMPILER.md)
        over a clone. Memoized per (fingerprint, pipeline signature,
        fetch set) on the program, so steady-state runs never re-run
        the passes. Dynamic (eager beam-decode) programs lower raw."""
        from . import compiler as _compiler
        pruned = self._maybe_prune(program, fetch_names)
        if dynamic or not _compiler.enabled():
            return pruned
        memo = program.__dict__.setdefault('_compiler_memo', {})
        key = (program.fingerprint(), _compiler.pipeline_signature(),
               tuple(sorted(fetch_names)))
        hit = memo.get(key)
        if hit is not None:
            return hit
        try:
            opt, _results = _compiler.optimize(
                pruned, fetch_names=fetch_names, scope=scope,
                clone=pruned is program)
        except ProgramInvalid:
            # the pass sanitizer (PTPU_VERIFY_PASSES) caught a pass
            # breaking an invariant — that is a deliberate, named
            # failure, not an optimizer bug to degrade past
            raise
        except Exception:
            # an optimizer bug must degrade to raw lowering, never take
            # the step down with it
            opt = pruned
        memo[key] = opt
        return opt

    def _pull_program_readers(self, program, feed, scope=None,
                              consume=True, fetch_names=None):
        """Program readers (open_recordio_file / random_data_generator
        + decorator chain): when the program binds a host-side reader
        and its slot vars are not explicitly fed, pull the next batch
        and inject it — the TPU-native analogue of the reference's
        ``read`` op pulling from the ReaderHolder
        (paddle/fluid/operators/read_op.cc).

        Stream state (iterator, pending peeked batch, sticky EOF) lives
        PER SCOPE, like the reference's ReaderHolder — a fresh scope is
        a fresh stream; ``reader.reset()`` bumps the var's generation
        so every scope restarts. ``consume=False`` peeks: the batch is
        stashed and handed to the next consuming run (analysis paths
        must not drop data). Raises core.EOFException at stream end;
        EOF is sticky until reset."""
        from .layers.io import ReaderVar
        readers = [v for v in program.global_block().vars.values()
                   if isinstance(v, ReaderVar)
                   and getattr(v, 'source', None) is not None]
        if not readers:
            return feed
        # only readers whose slot vars this RUN actually consumes get a
        # batch pulled — the reference's reader produces data only when
        # its read op executes (read_op.cc). Consumption = input of an
        # op that survives fetch-pruning, or a direct fetch (read_file
        # outputs fetched with no downstream op). An unconsumed reader
        # bound in the same program (the demo's test reader built
        # alongside the train one) or one feeding a pruned-away branch
        # must not be drained.
        consumed = program.__dict__.setdefault('_consumed_memo', {})
        key = (program.fingerprint(),
               tuple(sorted(fetch_names)) if fetch_names else None)
        used = consumed.get(key)
        if used is None:
            src_prog = self._maybe_prune(program, list(fetch_names or []))
            used = set(fetch_names or [])
            for blk in src_prog.blocks:
                for op in blk.ops:
                    used.update(op.input_arg_names)
            consumed[key] = used
        readers = [rv for rv in readers
                   if any(fv.name in used for fv in rv.feed_vars)]
        if not readers:
            return feed
        scope = scope or global_scope()
        # keyed by the reader OBJECT (auto-generated names can collide
        # across programs sharing a scope); the entry pins rv so ids
        # stay unique for the scope's lifetime
        states = scope.__dict__.setdefault('_reader_states', {})
        feed = dict(feed)
        for rv in readers:
            names = [fv.name for fv in rv.feed_vars]
            fed = [n for n in names if n in feed]
            if len(fed) == len(names):
                continue
            if fed:
                raise ValueError(
                    'program reader %s: slots %s were fed but %s were '
                    'not — feed all of a reader\'s slots or none (a '
                    'partial feed would pair your data with an '
                    'unrelated pulled batch)' % (
                        rv.name, fed,
                        [n for n in names if n not in feed]))
            from .core import EOFException
            gen = rv.__dict__.get('_generation', 0)
            key = id(rv)
            st = states.get(key)
            if st is None or st['gen'] != gen:
                from .reader_io import iterate_reader
                st = states[key] = {
                    'rv': rv, 'gen': gen, 'iter': iterate_reader(rv),
                    'pending': None, 'eof': False}
            if st['eof']:
                raise EOFException(
                    'program reader %s is exhausted; call '
                    'reader.reset() to restart it' % rv.name)
            if st['pending'] is not None:
                batch = st['pending']
                if consume:
                    st['pending'] = None
            else:
                try:
                    batch = next(st['iter'])
                except StopIteration:
                    st['eof'] = True      # sticky, like ReaderHolder
                    raise EOFException(
                        'program reader %s is exhausted; call '
                        'reader.reset() to restart it'
                        % rv.name) from None
                if not consume:
                    st['pending'] = batch
            for n, val in zip(names, batch):
                feed[n] = val
        return feed

    def _prep_lowering(self, program, feed, fetch_list, scope,
                       dynamic=False, consume_readers=True):
        """Shared lowering preamble (run / cost_analysis /
        ParallelExecutor): program-reader batch injection, fetch-name
        normalization, feed preparation, shape-feed extraction,
        persistable-state name union with the PRNG key. Analysis paths
        pass consume_readers=False so they PEEK (no training batch is
        dropped). Returns a 5-tuple ending with ``static_env`` — feeds
        consumed only through shape-defining slots, bound statically at
        trace time (their values must join any jit cache key)."""
        fetch_names = [f.name if isinstance(f, Variable) else f
                       for f in fetch_list]
        feed = self._pull_program_readers(program, feed, scope,
                                          consume=consume_readers,
                                          fetch_names=fetch_names)
        feed = self._prepare_feed(program, feed, dynamic=dynamic)
        static_env = self._extract_static_feeds(program, feed)
        state_in, state_out = self._state_names(program, scope)
        if scope.find_var(RNG_KEY) is None:
            scope.set_var(RNG_KEY,
                          jax.random.PRNGKey(program.random_seed or 0))
        state_in = sorted(set(state_in) | {RNG_KEY})
        state_out = sorted(set(state_out) | {RNG_KEY})
        return fetch_names, feed, state_in, state_out, static_env

    def _extract_static_feeds(self, program, feed):
        """Pop feeds consumed ONLY through shape-defining input slots
        (lowering.SHAPE_INPUT_SLOTS) and return them as concrete numpy
        values to bake into the trace — the TPU analog of the
        reference's runtime shape tensors (e.g. reshape's Shape input).
        Their values join the program-cache key."""
        memo = program.__dict__.setdefault('_shape_feed_memo', {})
        fp = program.fingerprint()
        names = memo.get(fp)
        if names is None:
            shape_only, data_used = set(), set()
            for blk in program.blocks:
                for op in blk.ops:
                    for slot, vals in (op.inputs or {}).items():
                        vlist = vals if isinstance(vals, (list, tuple)) \
                            else [vals]
                        for v in vlist:
                            n = getattr(v, 'name', v)
                            if (op.type, slot) in lowering.SHAPE_INPUT_SLOTS:
                                shape_only.add(n)
                            else:
                                data_used.add(n)
            names = memo[fp] = frozenset(shape_only - data_used)
        static_env = {}
        for n in names & set(feed.keys()):
            static_env[n] = np.asarray(as_numpy(feed.pop(n)))
        return static_env

    def device_context(self, sharded):
        """Where a jitted call, or an AOT lowering of one, runs: the
        mesh scope when sharded, this Executor's device otherwise."""
        if sharded:
            return self.partitioner.run_context()
        return jax.default_device(self.place.jax_device())

    def lowerable_entries(self):
        """``[(fingerprint, callable, abstract args, sharded, runs)]``
        of the compiled-program cache: what
        ``observability.perf.scope_map`` lowers once more, on demand,
        under :meth:`device_context`. ``runs`` counts the entry's
        dispatches."""
        with self._cache_lock:
            return [(key[0], self._cache.get(key), e.abstract, e.sharded,
                     e.runs) for key, e in self._lowerable.items()]

    def _settle(self, top, prep, launch, step, new_state, scope, **chain):
        """What both run paths owe once the jitted call has returned:
        the registry series, the compile and run events, and the new
        state in the scope."""
        self._m_run.observe(launch.dur_s)
        if step.lowered is not None:
            # jax.jit compiles lazily at the first call, so the real
            # XLA compile wall is the whole miss: exe/prep's start to
            # the end of this first exe/launch (an AOT warm start never
            # compiled: its wall lives in coldstart_load_seconds / the
            # 'coldstart' journal event). What jax said inside it
            # splits it part by part (OBSERVABILITY.md, "The compile
            # path").
            miss = _tracing.log_miss(top, prep, launch, *step.lowered)
            self._m_compile.observe(miss['wall_s'])
            _obs.emit('compile_end', dur_s=round(miss['wall_s'], 6),
                      **{k: round(v, 6) if isinstance(v, float) else v
                         for k, v in miss.items()}, **chain)
            if step.ledger is not None:
                _perf.seal(step.ledger, miss['wall_s'], trace=top.context)
        elif _tracing.COMPILE_LOG.count != step.seen \
                and _tracing.retraced(launch):
            # jax traced or compiled under a key this Executor holds
            top.note(retraced=True)
        if _obs.journal_active():
            _obs.emit('exe_run', cache=step.cache, fp=step.fp,
                      dur_s=round(launch.dur_s, 6), **chain)
        for n, v in new_state.items():
            scope.set_var(n, v)

    def run(self, program=None, feed=None, fetch_list=None,
            feed_var_name='feed', fetch_var_name='fetch', scope=None,
            return_numpy=True, use_program_cache=True,
            async_fetch=False):
        """``async_fetch=True`` exploits JAX async dispatch: the fetches
        come back as LAZY device handles (no host transfer, no sync) so
        the caller's loop can enqueue the next step while this one still
        executes; materialize later with ``as_numpy``/``np.asarray`` or
        ``jax.block_until_ready``. Overrides ``return_numpy``. An
        installed AnomalyGuard still observes every fetch (observation
        materializes — guard correctness beats overlap).

        The call is four phases (``observability.phase``,
        OBSERVABILITY.md "Distributed tracing"): ``exe/run`` around
        ``exe/prep``, ``exe/launch`` and ``exe/commit``, each in the
        profiler's trace whenever one is taken, and in the journal
        under an active parent span (a serving batch, a trainer step):
        bare runs stay span-free."""
        if program is None:
            program = default_main_program()
        if not isinstance(program, Program):
            raise TypeError("Executor requires Program as its Parameter. But "
                            "you passed in %s" % type(program))
        scope = scope or global_scope()
        with _obs.phase('exe/run', step_num=next(self._step_nums)) as top:
            with _obs.phase('exe/prep', top) as prep:
                step = self._prep_run(top, program, feed or {},
                                      fetch_list or [], scope)
            with _obs.phase('exe/launch', top, cache=step.cache) as launch, \
                    self.device_context(step.sharded):
                if step.checked:
                    err, (fetches, new_state) = step.jitted(step.feed,
                                                            step.state)
                    err.throw()
                else:
                    # profiling path is eager; its guard checks raise
                    # inline
                    fetches, new_state = step.jitted(step.feed, step.state)
            with _obs.phase('exe/commit', top):
                self._settle(top, prep, launch, step, new_state, scope)
                if getattr(program, '_half_inference', None):
                    # boundary contract: fetches come back float32 even
                    # though the net ran in half (Float16Transpiler)
                    fetches = [_to_f32_fetch(f) for f in fetches]
                if _anomaly.any_active():
                    # resilience hook: an installed AnomalyGuard
                    # inspects every fetch (NaN/Inf policy for raw
                    # exe.run loops); no-op by default
                    _anomaly.observe_fetches(step.fetch_names, fetches)
                if async_fetch:
                    # lazy device handles: dispatch returned, values
                    # unforced
                    top.note(dispatched=True)
                elif return_numpy:
                    with _obs.phase('exe/fetch', top):
                        fetches = [as_numpy(f) for f in fetches]
                else:
                    # reference contract: fetches are LoDTensors; a
                    # dense fetch still answers .lod() (with []) — wrap
                    # bare arrays
                    fetches = [SequenceTensor(f, None) if isinstance(
                        f, (jax.Array, np.ndarray)) else f
                        for f in fetches]
                return fetches

    def _prep_run(self, top, program, feed, fetch_list, scope):
        """``exe/prep`` of :meth:`run`: everything from the raw feed to
        the compiled callable with its arguments. On a cache miss it
        holds ``exe/verify`` and ``exe/compile`` (passes, lowering and
        the jit wrapper; XLA itself compiles inside the first
        ``exe/launch``)."""
        # feed validation runs on the RAW feed: _prepare_feed casts to
        # the declared dtype, which would mask exactly the mismatches
        # the check exists to name (FeedInvalid, ANALYSIS.md)
        _analysis.check_feeds_for_executor(program, feed)

        dynamic = _dynamic_memoized(program)
        fetch_names, feed, state_in_names, state_out_names, static_env = \
            self._prep_lowering(program, feed, fetch_list, scope,
                                dynamic=dynamic)

        from .debugging import nan_checks_enabled
        from . import profiler as _prof
        guard = nan_checks_enabled()
        profiling = _prof.op_profiling_enabled()
        part = self.partitioner
        # eager paths (per-op profiling, dynamic beam decode) cannot run
        # a sharded whole-block program; they stay single-device
        sharded = part.active and not (profiling or dynamic)
        key = program_cache_key(program, feed, static_env, fetch_names,
                                state_in_names, state_out_names, guard,
                                profiling, part.cache_token(program))
        top.note(fp=key[0])
        feeds_s = state_s = None
        with self._cache_lock:
            entry = self._cache.get(key)
            if sharded:
                # memoized per (fingerprint, mesh, names): the commit
                # below needs them every sharded step without a
                # per-step block walk
                state_s = part.state_shardings(program, state_in_names)
            if sharded and (entry is None or part.multiprocess):
                feeds_s = part.feed_shardings(feed)
            aot_store = aot_token = None
            aot_hit = False
            if entry is None:
                self._cache_misses += 1
                if not (profiling or dynamic or guard) \
                        and not (sharded and part.multiprocess):
                    aot_store = _coldstart_store()
                if aot_store is not None:
                    aot_token = dict(
                        backend=jax.default_backend(),
                        device_kind=getattr(self.place.jax_device(),
                                            'device_kind', ''),
                        devices=part.device_count if sharded else 1,
                        mesh=_perf.mesh_signature(
                            part.describe() if sharded else None))
                    loaded = aot_store.load(key, **aot_token)
                    if loaded is not None:
                        # AOT warm start (fleet/coldstart.py): the
                        # persisted executable replaces lowering AND
                        # the XLA compile. Safe to skip the static
                        # verify: the key embeds the program
                        # fingerprint + pass/partition tokens, so the
                        # entry was verified when first built.
                        jitted = self._cache[key] = loaded
                        aot_hit = True
            lowered = None
            if entry is None and not aot_hit:
                verify_s = 0.0
                if not dynamic:
                    # static verify BEFORE any lowering: a mis-wired
                    # program raises typed ProgramInvalid naming the
                    # offending op instead of an XLA trace error
                    with _obs.phase('exe/verify', top) as verify:
                        _analysis.verify_for_executor(
                            program,
                            feed_names=set(feed) | set(static_env),
                            fetch_names=fetch_names)
                    verify_s = verify.dur_s
                _obs.emit('compile_begin', fp=key[0])
                with _obs.phase('exe/compile', top, fp=key[0]) as lower:
                    jitted = self._lower_step(
                        program, feed, fetch_names, state_in_names,
                        state_out_names, static_env, scope,
                        dynamic=dynamic, profiling=profiling, guard=guard,
                        sharded=sharded, donate=aot_store is None,
                        feeds_s=feeds_s, state_s=state_s)
                lowered = (verify_s, lower.dur_s)
                self._cache[key] = jitted
            elif entry is not None:
                self._cache_hits += 1
                jitted = entry
                known = self._lowerable.get(key)
                if known is not None:
                    known.runs += 1
        was_miss = entry is None
        (self._m_misses if was_miss else self._m_hits).inc()

        state = {n: scope.raw(n) for n in state_in_names}
        if sharded and part.multiprocess:
            feed, state = part.globalize(feed, state, feeds_s, state_s)
        elif sharded:
            # pjit refuses mesh-committed args whose sharding drifted
            # from the declared in_shardings (e.g. state committed
            # replicated before a ZeRO re-annotation): re-commit just
            # those through the Partitioner; everything else passes
            # untouched
            state = part.reconcile_state(state, state_s)

        if was_miss and not (profiling or dynamic):
            # what observability.perf.scope_map() lowers again on
            # demand: shapes and shardings only, nothing compiled here
            self._lowerable[key] = _Lowerable(_perf.abstract_args(
                feed, state, (feeds_s, state_s) if sharded else None),
                sharded)

        _ledger = None
        if was_miss and not aot_hit and not (profiling or dynamic) \
                and not (sharded and part.multiprocess) \
                and _perf.capture_enabled():
            # perf observatory (OBSERVABILITY.md): ledger the program's
            # XLA cost/memory accounting on the miss path only — one
            # extra AOT lower().compile() against abstract avals per
            # compile, zero steady-state cost. Runs under the same
            # device/mesh context as the dispatch and never raises.
            with self.device_context(sharded):
                _ledger = _perf.capture_compiled(
                    jitted, feed, state, key[0],
                    backend=jax.default_backend(),
                    device_kind=getattr(self.place.jax_device(),
                                        'device_kind', ''),
                    mesh=_perf.mesh_signature(
                        part.describe() if sharded else None),
                    devices=part.device_count if sharded else 1)

        if was_miss and not aot_hit and aot_store is not None:
            # seal the fresh compilation into the cold-start store:
            # one eager AOT lower().compile() now (jit would have
            # compiled lazily on the dispatch below anyway),
            # serialized for the next replica's warmup; the dispatch
            # uses the Compiled directly so the compile happens once.
            with self.device_context(sharded):
                try:
                    if not sharded and (
                            any(map(_mesh_committed, feed.values()))
                            or any(map(_mesh_committed,
                                       state.values()))):
                        compiled = None
                    else:
                        compiled = aot_store.aot_compile(
                            jitted, feed, state,
                            shardings=(feeds_s, state_s) if sharded
                            else None)
                except Exception:  # noqa: BLE001 — persistence is an
                    # optimization; lazy jit still serves the request
                    aot_store.m_failures.inc()
                    compiled = None
            if compiled is not None:
                aot_store.save(key, compiled, **aot_token)
                jitted = compiled
                with self._cache_lock:
                    self._cache[key] = compiled

        return _Step(jitted, feed, state, key[0], fetch_names, sharded,
                     'miss' if was_miss else 'hit', lowered,
                     guard and not (profiling or dynamic), _ledger,
                     _tracing.COMPILE_LOG.count)

    def _lower_step(self, program, feed, fetch_names, state_in_names,
                    state_out_names, static_env, scope, dynamic,
                    profiling, guard, sharded, donate, feeds_s, state_s):
        """``exe/compile`` of a single step: the pass pipeline, the
        lowering of the block, and the jit (or partition) wrapper.
        Nothing is traced or compiled by XLA here."""
        part = self.partitioner
        lower_prog = self._optimized_program(
            program, fetch_names, scope=scope, dynamic=dynamic)
        fn = lower_block(lower_prog, lower_prog.global_block(),
                         sorted(feed.keys()), fetch_names,
                         state_in_names, state_out_names,
                         dynamic=dynamic, static_env=static_env)
        # State donation is unsafe for compilations that get sealed to
        # the AOT store: serialize_executable keeps the XLA-side
        # input_output_alias but the round trip loses jax's
        # dispatch-side donation bookkeeping, and a deserialized
        # aliased executable scribbles over state buffers other bucket
        # executables still hold (silent garbage, not an error).
        # Donation-free sealing costs one state-buffer copy per
        # dispatch on AOT-gated runs.
        donate = (1,) if donate else ()
        if profiling or dynamic:
            # Per-op profiling and dynamic (beam-decode) programs run
            # UN-jitted: the lowering executes op by op on the device
            # with concrete values and host control flow.
            jitted = fn
        elif sharded:
            out_state_s = part.state_shardings(program, state_out_names)
            # fetches come back fully replicated: every process must be
            # able to materialize numpy, and leaving them unspecified
            # lets XLA pick a dp-sharded layout that the donated
            # (replicated) state buffers cannot alias — a runtime
            # INTERNAL error on same-global-shape pairs (caught by the
            # verify drive on the sharded inference path)
            fetch_s = part.replicated
            fn = part.trace_wrap(fn)
            if guard:
                from jax.experimental import checkify
                jitted = part.partition(
                    checkify.checkify(fn),
                    in_shardings=(feeds_s, state_s),
                    out_shardings=(None, (fetch_s, out_state_s)))
            else:
                jitted = part.partition(
                    fn, in_shardings=(feeds_s, state_s),
                    out_shardings=(fetch_s, out_state_s),
                    donate_argnums=donate)
        elif guard:
            # Debug mode: functionalize the per-op NaN/Inf checks. No
            # donation — on a thrown error the scope must still hold
            # live (pre-step) state buffers.
            from jax.experimental import checkify
            jitted = jax.jit(checkify.checkify(fn))
        else:
            jitted = part.partition(fn, donate_argnums=donate)
        return jitted

    def run_chained(self, program=None, feed_list=None, fetch_list=None,
                    scope=None, return_numpy=True, async_fetch=False):
        """Run K training steps as ONE device dispatch (PERF.md
        "Dispatch pipelining").

        ``feed_list`` is a list of K per-step feed dicts; the K prepared
        feeds are stacked on a leading axis and executed through
        :func:`core.lowering.lower_block_chained` (``lax.scan`` over the
        single-step lowering, persistable state threaded through the
        carry, state donated). Returns a list of K per-step fetch lists
        — bit-exact vs K sequential :meth:`run` calls (same RNG splits,
        same optimizer updates; pinned by tests/test_pipeline.py).

        Falls back to sequential :meth:`run` calls (identical results,
        K dispatches) whenever chaining can't hold: dynamic (eager)
        programs, per-op profiling, checkify NaN-guard mode, program
        readers, feeds whose specs differ across the chunk (ragged tail
        batches), shape-feed values that differ, or persistable-state
        churn mid-chunk.
        """
        if program is None:
            program = default_main_program()
        if not isinstance(program, Program):
            raise TypeError("Executor requires Program as its Parameter."
                            " But you passed in %s" % type(program))
        feed_list = list(feed_list or [])
        fetch_list = fetch_list or []
        scope = scope or global_scope()
        if not feed_list:
            return []

        k = len(feed_list)
        dynamic = _dynamic_memoized(program)
        from .debugging import nan_checks_enabled
        from . import profiler as _prof
        from .layers.io import ReaderVar
        has_reader = any(
            isinstance(v, ReaderVar) and getattr(v, 'source', None)
            is not None
            for v in program.global_block().vars.values())
        if not (k == 1 or dynamic or nan_checks_enabled()
                or _prof.op_profiling_enabled() or has_reader):
            steps_out = self._run_chain(program, feed_list, fetch_list,
                                        scope, return_numpy, async_fetch)
            if steps_out is not None:
                return steps_out
        return [self.run(program, feed=f, fetch_list=fetch_list,
                         scope=scope, return_numpy=return_numpy,
                         async_fetch=async_fetch)
                for f in feed_list]

    def _run_chain(self, program, feed_list, fetch_list, scope,
                   return_numpy, async_fetch):
        """The chunk as one dispatch: ``exe/chain`` around the same
        ``exe/prep``, ``exe/launch`` and ``exe/commit`` as :meth:`run`.
        None where the chunk turns out not to chain while it is
        prepared (the trace then holds an ``exe/chain`` with its
        ``exe/prep`` alone, and the caller's K ``exe/run`` after it)."""
        k = len(feed_list)
        with _obs.phase('exe/chain', step_num=next(self._step_nums),
                        steps=k) as top:
            with _obs.phase('exe/prep', top) as prep:
                step = self._prep_chain(top, program, feed_list,
                                        fetch_list, scope)
            if step is None:
                top.note(fallback=True)
                return None
            with _obs.phase('exe/launch', top, cache=step.cache) as launch, \
                    self.device_context(step.sharded):
                fetches, new_state = step.jitted(step.feed, step.state)
            with _obs.phase('exe/commit', top):
                self._settle(top, prep, launch, step, new_state, scope,
                             chain=k)
                if getattr(program, '_half_inference', None):
                    fetches = [_to_f32_fetch(f) for f in fetches]
                anomaly_on = _anomaly.any_active()
                to_host = return_numpy and not async_fetch
                with _obs.phase('exe/fetch', top) if to_host \
                        else contextlib.nullcontext():
                    steps_out = []
                    for i in range(k):
                        row = [jax.tree_util.tree_map(lambda x: x[i], f)
                               for f in fetches]
                        if anomaly_on:
                            _anomaly.observe_fetches(step.fetch_names, row)
                        if to_host:
                            row = [as_numpy(f) for f in row]
                        elif not async_fetch:
                            row = [SequenceTensor(f, None) if isinstance(
                                f, (jax.Array, np.ndarray)) else f
                                for f in row]
                        steps_out.append(row)
                return steps_out

    def _prep_chain(self, top, program, feed_list, fetch_list, scope):
        """``exe/prep`` of a chained chunk: the K feeds prepared and
        stacked, the K-step program looked up or lowered
        (``exe/verify``, ``exe/compile``), the state gathered and
        committed. None where the chunk cannot chain."""
        k = len(feed_list)
        part = self.partitioner
        fetch_names = [f.name if isinstance(f, Variable) else f
                       for f in fetch_list]
        prepped, static_envs = [], []
        for f in feed_list:
            pf = self._prepare_feed(program, dict(f))
            static_envs.append(self._extract_static_feeds(program, pf))
            prepped.append(pf)
        specs = [tuple(sorted((n, _spec(v)) for n, v in pf.items()))
                 for pf in prepped]
        env0 = tuple(sorted((n, v.dtype.str, v.shape, v.tobytes())
                            for n, v in static_envs[0].items()))
        static_same = all(
            tuple(sorted((n, v.dtype.str, v.shape, v.tobytes())
                         for n, v in se.items())) == env0
            for se in static_envs[1:])
        if any(s != specs[0] for s in specs[1:]) or not static_same:
            return None      # ragged tail / shape-feed churn

        state_in_names, state_out_names = self._state_names(program,
                                                            scope)
        if scope.find_var(RNG_KEY) is None:
            scope.set_var(RNG_KEY,
                          jax.random.PRNGKey(program.random_seed or 0))
        state_in_names = sorted(set(state_in_names) | {RNG_KEY})
        state_out_names = sorted(set(state_out_names) | {RNG_KEY})
        if state_in_names != state_out_names:
            # the scan carry must be treedef-stable step to step; a
            # program writing persistables absent from the scope would
            # grow it mid-chain
            return None

        try:
            stacked = jax.tree_util.tree_map(_stack_steps, *prepped)
        except (ValueError, TypeError):
            return None      # heterogeneous feed structure

        key = program_cache_key(program, prepped[0], static_envs[0],
                                fetch_names, state_in_names,
                                state_out_names, False, 'chain',
                                part.cache_token(program))
        top.note(fp=key[0])
        state_s = stacked_s = None
        with self._cache_lock:
            entry = self._cache.get(key)
            if part.active:
                # the commit below needs these every chunk (state
                # shardings are memoized per fingerprint; the stacked
                # feed shardings walk only the feed dict)
                state_s = part.state_shardings(program, state_in_names)
                stacked_s = part.stacked_feed_shardings(prepped[0])
            lowered = None
            if entry is None:
                self._cache_misses += 1
                with _obs.phase('exe/verify', top) as verify:
                    _analysis.verify_for_executor(
                        program,
                        feed_names=set(prepped[0]) | set(static_envs[0]),
                        fetch_names=fetch_names)
                _obs.emit('compile_begin', fp=key[0], chain=k)
                with _obs.phase('exe/compile', top, fp=key[0]) as lower:
                    lower_prog = self._optimized_program(
                        program, fetch_names, scope=scope)
                    fn = lowering.lower_block_chained(
                        lower_prog, lower_prog.global_block(),
                        sorted(prepped[0].keys()), fetch_names,
                        state_in_names, state_out_names,
                        static_env=static_envs[0])
                    if part.active:
                        # K-step chain over the mesh: stacked feeds
                        # shard their per-step batch dim, the scan
                        # carry keeps each state var's own sharding
                        out_state_s = part.state_shardings(
                            program, state_out_names)
                        jitted = part.partition(
                            part.trace_wrap(fn),
                            in_shardings=(stacked_s, state_s),
                            # stacked fetches replicated (prefix-
                            # broadcast over the fetch list) for the
                            # same donation-aliasing reason as the
                            # single-step path
                            out_shardings=(part.replicated, out_state_s),
                            donate_argnums=(1,))
                    else:
                        jitted = part.partition(fn, donate_argnums=(1,))
                lowered = (verify.dur_s, lower.dur_s)
                self._cache[key] = jitted
            else:
                self._cache_hits += 1
                jitted = entry
                known = self._lowerable.get(key)
                if known is not None:
                    known.runs += 1
        was_miss = entry is None
        (self._m_misses if was_miss else self._m_hits).inc()

        state = {n: scope.raw(n) for n in state_in_names}
        multiproc = part.active and part.multiprocess
        if multiproc:
            # multi-process chain: the stacked [K, local_batch, ...]
            # feeds ARE the per-step process-local shards, so one
            # globalize of the stack threads per-step globalize through
            # the scan (make_array_from_process_local_data scales the
            # batch dim by the process span; the K axis is unsharded).
            # Anything globalize can't express falls back LOUDLY to
            # sequential run() — never a silently mis-shaped feed.
            try:
                stacked, state = part.globalize(stacked, state,
                                                stacked_s, state_s)
            except Exception as e:  # noqa: BLE001 — any globalize
                import warnings
                warnings.warn(
                    'run_chained: multi-process globalize of the '
                    '%d-step chunk failed (%r); falling back to %d '
                    'sequential run() dispatches' % (k, e, k),
                    RuntimeWarning, stacklevel=4)
                _obs.emit('multihost', action='chain_fallback',
                          steps=k, error=repr(e))
                return None
        if was_miss:
            self._lowerable[key] = _Lowerable(_perf.abstract_args(
                stacked, state,
                (stacked_s, state_s) if part.active else None),
                part.active)
        _ledger = None
        with self.device_context(part.active):
            if was_miss and not multiproc and _perf.capture_enabled():
                # chained programs ledger separately (K steps fused
                # into one XLA program — flops/bytes are per-CHUNK,
                # chain=k)
                _ledger = _perf.capture_compiled(
                    jitted, stacked, state,
                    key[0], backend=jax.default_backend(),
                    device_kind=getattr(self.place.jax_device(),
                                        'device_kind', ''),
                    mesh=_perf.mesh_signature(
                        part.describe() if part.active else None),
                    devices=part.device_count if part.active else 1,
                    chain=k)
            if not multiproc:
                # commit the state to its run placement BEFORE the
                # first call: prefetch-staged feeds arrive committed,
                # while fresh startup state is uncommitted — without
                # this the second chunk's jit signature differs (state
                # now = committed jit outputs) and silently
                # retraces+recompiles the whole K-step program once
                # more. The Partitioner owns the placement: single
                # device on the fallback mesh, per-var NamedSharding on
                # a real one (the PR-5 "single-device commits fight
                # pjit's NamedSharding" conflict dissolves here).
                # device_put on already-committed matching arrays is a
                # no-op. (Multi-process state is already committed
                # global by globalize above.)
                state = part.commit_state(state, state_s)
                if part.active:
                    # device-stacked prefetch-staged feeds come out of
                    # jnp.stack committed with whatever sharding XLA
                    # propagated; re-commit any that drifted from the
                    # declared in_shardings
                    stacked = part.reconcile(stacked, stacked_s)
        return _Step(jitted, stacked, state, key[0], fetch_names,
                     part.active, 'miss' if was_miss else 'hit', lowered,
                     False, _ledger, _tracing.COMPILE_LOG.count)

    def lowered(self, program, feed, fetch_list, scope=None):
        """The ``jax.stages.Lowered`` of the single-device step
        :meth:`run` compiles for these arguments — same prune, same
        pass pipeline, same lowering, nothing executed. Its
        ``as_text()`` is where to look for what the step really
        contains (chip_smoke.py checks the Mosaic custom calls of the
        Pallas kernels there)."""
        return self._lower(program, feed, fetch_list, scope,
                           optimize=True)

    def _lower(self, program, feed, fetch_list, scope, optimize):
        """Shared by :meth:`lowered` (the pass pipeline's program) and
        :meth:`cost_analysis` (the pruned program as written)."""
        scope = scope or global_scope()
        fetch_names, feed, state_in_names, state_out_names, static_env = \
            self._prep_lowering(program, feed, fetch_list, scope,
                                consume_readers=False)
        if optimize:
            lower_prog = self._optimized_program(program, fetch_names,
                                                 scope=scope)
        else:
            lower_prog = self._maybe_prune(program, fetch_names)
        fn = lower_block(lower_prog, lower_prog.global_block(),
                         sorted(feed.keys()), fetch_names,
                         state_in_names, state_out_names,
                         static_env=static_env)
        state = {n: scope.raw(n) for n in state_in_names}
        with jax.default_device(self.place.jax_device()):
            return jax.jit(fn).lower(feed, state)

    def cost_analysis(self, program, feed, fetch_list, scope=None):
        """XLA's own ledger for the step this program compiles to:
        flops, HBM bytes accessed (per-fusion sums), and compiled
        buffer sizes. Powers PERF.md's roofline accounting (the
        reference exposes per-op timings via its profiler; here the
        whole block is ONE XLA program so the ledger is the natural
        analog)."""
        comp = self._lower(program, feed, fetch_list, scope,
                           optimize=False).compile()
        ca = comp.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        ma = comp.memory_analysis()
        return {
            'flops': float(ca.get('flops', 0.0)),
            'bytes_accessed': float(ca.get('bytes accessed', 0.0)),
            'output_bytes': float(ca.get('bytes accessedout{}', 0.0)),
            'temp_bytes': int(ma.temp_size_in_bytes),
            'argument_bytes': int(ma.argument_size_in_bytes),
        }

    def close(self):
        with self._cache_lock:
            self._cache.clear()
            self._lowerable.clear()
            self._cache_hits = 0
            self._cache_misses = 0
