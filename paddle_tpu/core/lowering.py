"""Lowering: Program block -> single jitted XLA computation.

Parity: replaces the reference's per-op interpreter
(paddle/fluid/framework/executor.cc: for each op -> OperatorWithKernel::Run on
a DeviceContext) with a whole-block trace. One ``exe.run`` on a training
program compiles to ONE XLA executable computing forward + backward +
optimizer update, with persistable state donated across steps.

Gradient construction (parity with python/paddle/fluid/backward.py):
``append_backward`` plants a ``backward_marker`` op. At lowering time the ops
before the marker are replayed inside ``jax.value_and_grad(..., has_aux=True)``
so the forward is traced exactly once; gradients bind to the reference's
``<param>@GRAD`` names and downstream ops (grad clip, regularizers, optimizer
update ops) consume them as ordinary environment values.
"""
import contextlib
import functools
import re
import time

import jax
import jax.numpy as jnp

from .registry import get_kernel
from ..framework import convert_np_dtype

RNG_KEY = '__rng__'


class SparseRows(object):
    """Row-sparse gradient — the TPU-native SelectedRows (parity:
    paddle/fluid/framework/selected_rows.h as a GRADIENT carrier).
    ``items``: list of (rows [.., D], ids [..]) pairs, one per lookup
    of the shared table; duplicate ids are NOT pre-merged (SGD's
    scatter-add absorbs them; Adagrad/Adam merge via
    ops/optim_ops._merge_rows)."""

    __slots__ = ('items', 'vocab')

    def __init__(self, items, vocab):
        self.items = items
        self.vocab = vocab

    def __repr__(self):
        return 'SparseRows(%d lookups, vocab=%d)' % (len(self.items),
                                                     self.vocab)

# Mesh (+ optional spec resolver) for with_sharding_constraint on
# Variable.sharding-annotated values. Set by the Partitioner's
# trace_wrap while tracing a sharded program; the CPU-fallback path
# lowers identically but unconstrained. The resolver (when given) is
# Partitioner.resolve_spec — logical axis names resolve through its
# rules; without one, raw mesh-axis specs are sanitized by clean_spec.
_SHARDING_MESH = [None]
_SHARDING_RESOLVER = [None]


@contextlib.contextmanager
def sharding_mesh(mesh, resolver=None):
    prev, prev_r = _SHARDING_MESH[0], _SHARDING_RESOLVER[0]
    _SHARDING_MESH[0] = mesh
    _SHARDING_RESOLVER[0] = resolver
    try:
        yield
    finally:
        _SHARDING_MESH[0] = prev
        _SHARDING_RESOLVER[0] = prev_r


def active_sharding_mesh():
    """(mesh, resolver) of the trace in progress, or (None, None)."""
    return _SHARDING_MESH[0], _SHARDING_RESOLVER[0]


def _constrain(val, spec, mesh, resolver=None):
    from jax.sharding import NamedSharding, PartitionSpec as P
    if not isinstance(val, jax.Array) or not getattr(val, 'ndim', 0):
        return val
    if resolver is not None:
        spec = resolver(spec, ndim=val.ndim, shape=val.shape)
    else:
        from ..parallel.mesh import clean_spec
        spec = clean_spec(spec, mesh, ndim=val.ndim)
    if all(e is None for e in spec):
        return val
    return jax.lax.with_sharding_constraint(
        val, NamedSharding(mesh, P(*spec)))

# JAX default (x64 disabled) canonicalizes these anyway; do it explicitly so
# cache keys and feeds are stable. TPU has no fast f64/i64 path.
_RUNTIME_DTYPE = {'int64': 'int32', 'float64': 'float32', 'uint64': 'uint32'}


def runtime_dtype(dtype):
    d = convert_np_dtype(dtype)
    return _RUNTIME_DTYPE.get(d, d)


class OpCtx(object):
    """Kernel-facing view of one op during lowering."""

    __slots__ = ('op', 'env', 'runner')

    def __init__(self, op, env, runner):
        self.op = op
        self.env = env
        self.runner = runner

    # ---- inputs -----------------------------------------------------------------
    def input(self, slot, idx=0):
        names = self.op.inputs.get(slot) or []
        if not names:
            return None
        return self.env[names[idx]]

    def inputs(self, slot):
        return [self.env[n] for n in self.op.inputs.get(slot, [])]

    def has_input(self, slot):
        return bool(self.op.inputs.get(slot))

    def input_name(self, slot, idx=0):
        return self.op.inputs[slot][idx]

    # ---- outputs ----------------------------------------------------------------
    def set_output(self, slot, val, idx=0):
        self.env[self.op.outputs[slot][idx]] = val

    def output_name(self, slot, idx=0):
        return self.op.outputs[slot][idx]

    def output_names(self, slot):
        return self.op.outputs.get(slot, [])

    def out_var(self, slot, idx=0):
        return self.runner.block._find_var_recursive(
            self.op.outputs[slot][idx])

    def in_var(self, slot, idx=0):
        return self.runner.block._find_var_recursive(self.op.inputs[slot][idx])

    # ---- attrs / misc -----------------------------------------------------------
    def attr(self, name, default=None):
        return self.op.attrs.get(name, default)

    def next_rng(self):
        k1, k2 = jax.random.split(self.env[RNG_KEY])
        self.env[RNG_KEY] = k1
        return k2

    def out_dtype(self, slot, idx=0):
        var = self.out_var(slot, idx)
        return runtime_dtype(var.dtype if var is not None else 'float32')

    def is_test(self):
        return bool(self.attr('is_test', False))


class BlockRunner(object):
    """Executes a Block's op list into an environment of traced values.

    ``dynamic`` marks the eager dynamic-program mode (executor runs the
    whole block unjitted with host control flow — beam decode); kernels
    branch on it for representations that cannot thread a lax loop
    (list-backed tensor arrays, packed-LoD rows).

    ``keep`` guards the compiler's liveness annotations: the
    buffer_reuse pass marks each op with the names whose LAST reader it
    is (``__release__`` attr) and run_ops drops those environment
    references once the op completes — unless the name is in ``keep``
    (fetches, persistable state, the PRNG key), which the pass could
    not know statically."""

    def __init__(self, block, grad_mode=False, dynamic=False, keep=None):
        self.block = block
        self.grad_mode = grad_mode
        self.dynamic = dynamic
        self.keep = keep if keep is not None else frozenset()

    def run_ops(self, ops, env):
        from ..debugging import nan_checks_enabled
        from .. import profiler as _prof
        guard = nan_checks_enabled()
        profiling = _prof.op_profiling_enabled()
        for op in ops:
            kernel = get_kernel(op.type)
            t0 = time.perf_counter() if profiling else 0.0
            try:
                # named_scope stamps the op into HLO metadata, which is
                # how a device operation finds its Fluid op again
                # (observability.perf.scope_map)
                with jax.named_scope(op_scope(op)):
                    kernel(OpCtx(op, env, self))
            except Exception as e:
                raise type(e)(
                    "while lowering op %r (%s -> %s): %s" %
                    (op.type, op.inputs, op.outputs, e)) from e
            if profiling:
                outs = [env[n] for n in op.output_arg_names if n in env]
                # only time real (eager) execution — during tracing the
                # values are tracers and a timer would measure nothing
                if not any(isinstance(o, jax.core.Tracer)
                           for o in jax.tree_util.tree_leaves(outs)):
                    try:
                        jax.block_until_ready(outs)
                    except Exception:
                        pass
                    _prof.record_op_event(op.type,
                                          time.perf_counter() - t0,
                                          start=t0)
            if guard:
                _check_outputs(op, env)
            if self.grad_mode:
                for name in op.output_arg_names:
                    var = self.block._find_var_recursive(name)
                    if var is None or name not in env:
                        continue
                    if var.stop_gradient and _is_float(env[name]):
                        env[name] = jax.tree_util.tree_map(
                            jax.lax.stop_gradient, env[name])
                    eclip = getattr(var, 'error_clip', None)
                    if eclip is not None and _is_float(env[name]):
                        # Variable.set_error_clip on an ACTIVATION: the
                        # reference clips <var>@GRAD as the backward
                        # passes through (clip_op appended by
                        # error_clip_callback); the fused-autodiff
                        # analog is a cotangent-clip identity barrier
                        env[name] = jax.tree_util.tree_map(
                            lambda v: _clip_cotangent(
                                v, float(eclip.min), float(eclip.max)),
                            env[name])
            mesh = _SHARDING_MESH[0]
            if mesh is not None:
                for name in op.output_arg_names:
                    var = self.block._find_var_recursive(name)
                    spec = getattr(var, 'sharding', None)
                    if spec and name in env:
                        env[name] = _constrain(env[name], spec, mesh,
                                               _SHARDING_RESOLVER[0])
            rel = op.attrs.get('__release__')
            if rel:
                # compiler buffer_reuse annotation: this op was the
                # last reader — drop the reference so the buffer is
                # reusable (eager mode frees it now; under jit XLA's
                # live range ends here instead of at block end)
                for name in rel:
                    if name not in self.keep:
                        env.pop(name, None)
        return env


# The scopes lower_block lowers under. With the backward marker the
# ops before it are differentiated, so JAX names their forward
# ``jvp(forward)`` and their backward ``transpose(jvp(forward))``.
FORWARD_SCOPE, OPTIMIZER_SCOPE = 'forward', 'optimizer'
_SCOPE_LEGAL = re.compile(r'^[A-Za-z0-9_.\-]+$')


def op_scope(op):
    """``<op.type>:<first output>`` (``conv2d:res2a_branch2a.tmp_0``),
    so that two convs of different stages are two names; the type alone
    where the output's name would not survive in a scope path
    (``x@GRAD``)."""
    outs = op.output_arg_names
    if outs and _SCOPE_LEGAL.match(outs[0]):
        return '%s:%s' % (op.type, outs[0])
    return op.type


def _is_float(val):
    leaves = jax.tree_util.tree_leaves(val)
    return any(jnp.issubdtype(jnp.asarray(l).dtype, jnp.floating)
               for l in leaves)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _clip_cotangent(x, lo, hi):
    """Identity whose COTANGENT is clipped to [lo, hi] — the
    fused-backward form of the reference's error-clip op on
    <var>@GRAD (clip.py ErrorClipByValue.append_clip_op)."""
    return x


def _clip_cotangent_fwd(x, lo, hi):
    return x, None


def _clip_cotangent_bwd(lo, hi, _, g):
    return (jnp.clip(g, lo, hi),)


_clip_cotangent.defvjp(_clip_cotangent_fwd, _clip_cotangent_bwd)


def _check_outputs(op, env):
    """Debug-mode NaN/Inf guard: one check per float output, carrying op
    provenance (type, output, inputs). Under a trace it functionalizes
    via checkify; on concrete (eager/profiling) values it raises
    directly."""
    from jax.experimental import checkify
    for name in op.output_arg_names:
        if name not in env:
            continue
        for leaf in jax.tree_util.tree_leaves(env[name]):
            arr = jnp.asarray(leaf)
            if not jnp.issubdtype(arr.dtype, jnp.floating):
                continue
            msg = ("NaN/Inf detected in output '%s' of op '%s' "
                   "(inputs: %s)" % (name, op.type,
                                     sorted(op.input_arg_names)))
            if isinstance(arr, jax.core.Tracer):
                checkify.check(
                    jnp.isfinite(arr.astype(jnp.float32)).all(), msg)
            elif not bool(jnp.isfinite(
                    arr.astype(jnp.float32)).all()):
                raise FloatingPointError(msg)


def _find_marker(ops):
    for i, op in enumerate(ops):
        if op.type == 'backward_marker':
            return i
    return -1


def _op_reads(op):
    """All names an op (incl. nested sub-blocks) may read from the
    enclosing environment."""
    reads = list(op.input_arg_names)
    sub = op.attrs.get('sub_block')
    if sub is not None:
        produced = set()
        for sop in sub.ops:
            reads.extend(n for n in _op_reads(sop) if n not in produced)
            produced.update(sop.output_arg_names)
    return reads


def _op_writes(op):
    writes = list(op.output_arg_names)
    sub = op.attrs.get('sub_block')
    if sub is not None:
        for sop in sub.ops:
            writes.extend(_op_writes(sop))
    return writes


# public names for backward.calc_gradient's path analysis
op_reads = _op_reads
op_writes = _op_writes


def find_op_path(ops, input_names, target_names, no_grad):
    """Ops both forward-reachable from ``input_names`` and
    backward-reachable from ``target_names``; reachability cut at
    ``no_grad``. Parity: the reference's _find_op_path_
    (python/paddle/fluid/backward.py:564). Returns (path_ops,
    forward-reachable name set)."""
    reachable = set(input_names)
    fwd = [False] * len(ops)
    for i, op in enumerate(ops):
        if not input_names or any(n in reachable for n in _op_reads(op)):
            fwd[i] = True
            for n in _op_writes(op):
                if n not in no_grad:
                    reachable.add(n)
    needed = set(target_names)
    keep = [False] * len(ops)
    for i in reversed(range(len(ops))):
        if fwd[i] and any(n in needed for n in _op_writes(ops[i])):
            keep[i] = True
            for n in _op_reads(ops[i]):
                if n not in no_grad:
                    needed.add(n)
    return [ops[i] for i in range(len(ops)) if keep[i]], reachable


def _register_gradient_marker():
    """calc_gradient's runtime (parity: python/paddle/fluid/backward.py:604).

    The marker replays the input->target op path under ``jax.vjp`` with
    the inputs as leaves: targets' cotangents are the given
    target_gradients (ones when absent), explicit ``no_grad`` names are
    stop_gradient'ed as they are produced, and the resulting input
    cotangents bind to the declared grad names. Self-contained — works
    anywhere in the block, composes with backward_marker (the vjp nests
    inside value_and_grad for double-backward), and repeated calls
    don't collide because no internal grad vars exist."""
    from .registry import register_kernel

    @register_kernel('gradient_marker')
    def _gradient_marker(ctx):
        op, env = ctx.op, ctx.env
        block = ctx.runner.block
        ops = list(block.ops)
        # keep earlier gradient_markers in the path: their kernel is
        # itself differentiable JAX code, so grad-of-grad (gradient
        # penalty) composes as nested vjp; only backward_marker (whose
        # semantics live in lower_block) is opaque here
        idx = next(i for i, o in enumerate(ops) if o is op)
        pre = [o for o in ops[:idx] if o.type != 'backward_marker']
        input_names = list(op.inputs['Inputs'])
        target_names = list(op.inputs['Targets'])
        tgrad_names = list(op.attrs['target_grads'])
        out_grads = list(op.outputs['OutGrads'])
        no_grad = set(op.attrs.get('no_grad') or ())
        path, _ = find_op_path(pre, set(input_names), set(target_names),
                               no_grad)
        base_env = dict(env)
        dynamic = ctx.runner.dynamic

        def g(input_vals):
            genv = dict(base_env)
            genv.update(input_vals)
            runner = BlockRunner(block, grad_mode=True, dynamic=dynamic,
                                 keep=frozenset(target_names))
            for o in path:
                runner.run_ops([o], genv)
                for n in o.output_arg_names:
                    if n in no_grad and n in genv and _is_float(genv[n]):
                        genv[n] = jax.tree_util.tree_map(
                            jax.lax.stop_gradient, genv[n])
            return tuple(genv[t] for t in target_names)

        input_vals = {n: env[n] for n in input_names}
        primals, vjp_fn = jax.vjp(g, input_vals)
        cots = []
        for tg, primal in zip(tgrad_names, primals):
            if tg is None:
                cots.append(jax.tree_util.tree_map(jnp.ones_like, primal))
            else:
                cots.append(env[tg])
        grads, = vjp_fn(tuple(cots))

        def _fix_float0(gleaf, pleaf):
            # float0 marks a non-differentiable leaf: zero it for float
            # primals; carry the primal for integer structure leaves
            # (SequenceTensor lengths, ids) so the grad stays usable
            if getattr(gleaf, 'dtype', None) == jax.dtypes.float0:
                p = jnp.asarray(pleaf)
                if jnp.issubdtype(p.dtype, jnp.floating):
                    return jnp.zeros_like(p)
                return p
            return gleaf

        for n, gname in zip(input_names, out_grads):
            env[gname] = jax.tree_util.tree_map(
                _fix_float0, grads[n], env[n])


_register_gradient_marker()


def _run_remat_segments(block, ops, env, grad_mode, keep=None):
    """memory_optimize() path: execute the forward as ~sqrt(N) segments,
    each under jax.checkpoint, so backward keeps only segment-boundary
    activations and recomputes inside segments (classic sqrt-N remat).
    A single whole-forward checkpoint would NOT shrink the peak — the
    recompute re-materializes every activation at once (measured r3:
    2360 -> 2263 MB only); segmentation is what trades FLOPs for peak
    memory."""
    import math
    n_seg = max(2, int(math.sqrt(len(ops))))
    bounds = [len(ops) * i // n_seg for i in range(n_seg + 1)]
    for s in range(n_seg):
        chunk = ops[bounds[s]:bounds[s + 1]]
        if not chunk:
            continue
        produced = set()
        reads, writes = [], []
        for op in chunk:
            for n in _op_reads(op):
                if n not in produced and n in env and n not in reads:
                    reads.append(n)
            for n in _op_writes(op):
                produced.add(n)
                if n not in writes:
                    writes.append(n)
        if RNG_KEY in env:
            # Stochastic ops advance the key in-place (next_rng); the
            # segment must both read it AND return the advanced key, or
            # every segment/step would reuse the same dropout mask.
            if RNG_KEY not in reads:
                reads.append(RNG_KEY)
            if RNG_KEY not in writes:
                writes.append(RNG_KEY)

        def seg(vals, _chunk=tuple(chunk), _reads=tuple(reads),
                _writes=tuple(writes)):
            senv = dict(zip(_reads, vals))
            BlockRunner(block, grad_mode=grad_mode, keep=keep).run_ops(
                list(_chunk), senv)
            return tuple(senv.get(n) for n in _writes)

        outs = jax.checkpoint(seg)(tuple(env[n] for n in reads))
        for n, v in zip(writes, outs):
            if v is not None:
                env[n] = v
    return env


# op input slots whose VALUES define shapes: feeds consumed only through
# these are bound statically at trace time (part of the jit cache key) —
# the TPU analog of the reference's runtime shape tensors
SHAPE_INPUT_SLOTS = frozenset({('reshape', 'Shape')})


def lower_block_chained(program, block, feed_names, fetch_names,
                        state_in_names, state_out_names, static_env=None):
    """K training steps inside ONE jitted program.

    Dispatch amortization (PERF.md "Dispatch pipelining"): every
    ``Executor.run`` pays one host dispatch, so at small step walls the
    product training loop can be dispatch-bound (how much on a local
    chip: not measured). This builds ``fn(stacked_feeds, state) ->
    (stacked_fetches, final_state)`` where feeds carry a leading [K]
    axis and the single-step computation from :func:`lower_block` runs
    under ``jax.lax.scan`` — persistable state (params, optimizer
    accumulators, PRNG key) threads step-to-step as the scan carry, and
    each step's fetches come back stacked on the same [K] axis.

    Because the scan body IS the single-step lowering, the K-step
    program performs the exact op sequence of K sequential ``run``
    calls: same RNG splits, same optimizer updates — bit-exactness is
    pinned by tests/test_pipeline.py. K itself is not baked into the
    trace; the same compiled program serves any chain length of the
    same per-step feed spec (XLA recompiles per distinct K through the
    jit shape cache, which the executor's cache key mirrors).

    Not valid for dynamic (eager) programs, per-op profiling, or
    checkify NaN-guard mode — the executor falls back to sequential
    single-step runs for those.

    ZeRO-2 collective overlap (PERF.md "ZeRO-2 and collective
    overlap"): when the step carries ``zero_reduce_scatter`` bucket
    ops, those collectives live INSIDE the scan body, so each
    iteration's bucketed gradient collectives and the parameter
    all-gather are scheduled by XLA against the same iteration's
    remaining backward and the carry hand-off — no host barrier ever
    separates a microbatch's collectives from the next microbatch's
    compute. The sharded optimizer state (``Variable.sharding`` on the
    accumulators) threads the donated carry, so moment shards stay
    resident per-device across all K steps.
    """
    step = lower_block(program, block, feed_names, fetch_names,
                       state_in_names, state_out_names,
                       dynamic=False, static_env=static_env)

    def fn(stacked_feeds, state):
        def body(carry, feeds_i):
            fetches, new_state = step(feeds_i, carry)
            return new_state, tuple(fetches)

        final_state, stacked = jax.lax.scan(body, state, stacked_feeds)
        return list(stacked), final_state

    return fn


def lower_block(program, block, feed_names, fetch_names, state_in_names,
                state_out_names, dynamic=False, static_env=None):
    """Build ``fn(feeds, state) -> (fetches, new_state)`` for jit.

    ``feeds``/``state`` are dicts name->array (SequenceTensor allowed).
    ``state`` includes the PRNG key under ``RNG_KEY``.
    ``static_env`` binds names to CONCRETE numpy values baked into the
    trace (shape-like feeds; see SHAPE_INPUT_SLOTS).
    """
    ops = list(block.ops)
    marker_idx = _find_marker(ops)

    # names the compiler's release annotations must never drop from the
    # environment: the epilogue below still reads them
    keep = (frozenset(fetch_names) | frozenset(state_out_names)
            | frozenset(static_env or ()) | {RNG_KEY})

    def fn(feeds, state):
        env = {}
        if static_env:
            env.update(static_env)
        env.update(state)
        env.update(feeds)
        if marker_idx < 0:
            with jax.named_scope(FORWARD_SCOPE):
                BlockRunner(block, dynamic=dynamic, keep=keep).run_ops(
                    ops, env)
        else:
            marker = ops[marker_idx]
            param_names = [p for p in marker.attrs['params']]
            grad_names = list(marker.attrs['grads'])
            loss_name = marker.inputs['Loss'][0]
            pre, post = ops[:marker_idx], ops[marker_idx + 1:]
            # sparse embedding tables: differentiate the gathered ROWS
            # (zero carriers added to each lookup's output) instead of
            # the [vocab, d] table; the optimizer sees SparseRows.
            # Requires the ids to be live before the trace (feeds);
            # mid-graph ids fall back to the dense path.
            sparse_map = {
                w: pairs
                for w, pairs in (marker.attrs.get('sparse') or {}).items()
                if w in env and all(p[0] in env for p in pairs)}
            diff_names = [p for p in param_names if p not in sparse_map]
            base_env = {k: v for k, v in env.items()
                        if k not in set(diff_names)}

            def _rows_of(ids_val):
                from ..lod import SequenceTensor
                data = ids_val.data if isinstance(ids_val,
                                                  SequenceTensor) \
                    else jnp.asarray(ids_val)
                shp = tuple(data.shape)
                if shp and shp[-1] == 1:
                    shp = shp[:-1]
                return data.reshape(shp), shp

            remat = bool(getattr(program, '_remat', False))

            # sparse lookup ids are read through marker ATTRS (invisible
            # to the liveness pass) — pin them alongside the loss
            gkeep = keep | {loss_name} | {
                p[0] for pairs in (marker.attrs.get('sparse') or {}
                                   ).values() for p in pairs}

            @jax.named_scope(FORWARD_SCOPE)
            def g(param_vals):
                genv = dict(base_env)
                genv.update(param_vals)
                if remat:
                    # memory_optimize() hint: sqrt-N segmented
                    # rematerialization (the TPU-meaningful analogue of
                    # the reference's liveness-based buffer reuse)
                    _run_remat_segments(block, pre, genv, True,
                                        keep=gkeep)
                else:
                    BlockRunner(block, grad_mode=True, dynamic=dynamic,
                                keep=gkeep).run_ops(pre, genv)
                loss = genv[loss_name]
                return jnp.sum(loss), genv

            param_vals = {p: env[p] for p in diff_names}
            for w, pairs in sparse_map.items():
                d = env[w].shape[1]
                for ids_name, carrier in pairs:
                    _, shp = _rows_of(env[ids_name])
                    param_vals[carrier] = jnp.zeros(
                        shp + (d,), env[w].dtype)
            from .. import profiler as _prof
            _profiling = _prof.op_profiling_enabled() and not any(
                isinstance(v, jax.core.Tracer)
                for v in jax.tree_util.tree_leaves(param_vals))
            _t0 = time.perf_counter() if _profiling else 0.0
            (_, env2), pgrads = jax.value_and_grad(
                g, has_aux=True)(param_vals)
            if _profiling:
                # the fused fwd+bwd region is one XLA program; per-op
                # attribution inside it would be fiction
                jax.block_until_ready(pgrads)
                _prof.record_op_event('fwd_bwd(value_and_grad)',
                                      time.perf_counter() - _t0,
                                      start=_t0)
            env = env2
            env.update({p: param_vals[p] for p in diff_names})
            scale = marker.attrs.get('loss_scale', None)
            # everything after the backward: un-scaling the gradients,
            # then the ops behind the marker (clip, regularizers, the
            # optimizer's updates)
            with jax.named_scope(OPTIMIZER_SCOPE):
                for p, gname in zip(param_names, grad_names):
                    if p in sparse_map:
                        items = []
                        for ids_name, carrier in sparse_map[p]:
                            rows = pgrads[carrier]
                            if scale is not None and scale != 1.0:
                                rows = rows * scale
                            ids, _ = _rows_of(env[ids_name])
                            items.append((rows, ids))
                        env[gname] = SparseRows(items,
                                                int(env[p].shape[0]))
                        continue
                    gval = pgrads[p]
                    if scale is not None and scale != 1.0:
                        gval = gval * scale
                    env[gname] = gval
                BlockRunner(block, dynamic=dynamic, keep=keep).run_ops(
                    post, env)

        fetches = [env[n] for n in fetch_names]
        new_state = {}
        for n in state_out_names:
            if n in env:
                new_state[n] = env[n]
            elif n in state:
                new_state[n] = state[n]
        return fetches, new_state

    return fn
