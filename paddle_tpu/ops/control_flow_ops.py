"""Control-flow op kernels: sub-blocks -> XLA structured control flow.

Parity: paddle/fluid/operators/{while_op,conditional_block_op,
recurrent_op,tensor_array_read_write_op,lod_rank_table_op,
shrink_rnn_memory_op}.cc and python/paddle/fluid/layers/control_flow.py
consumers.

TPU design (SURVEY.md §2.3): the reference interprets sub-blocks on the
host per iteration; here every sub-block lowers into the SAME traced XLA
computation via lax.while_loop / lax.scan, so a whole training or decode
step stays on-device.

Tensor arrays (the reference's LOD_TENSOR_ARRAY) are represented as a
fixed-capacity buffer ``{'buf': [cap, *elem], 'len': i32}`` — a plain
pytree, so arrays thread through loop carries. Capacity comes from the
writing context (padded seq len for lod_tensor_to_array; a default cap
otherwise; PADDLE_TPU_ARRAY_CAP overrides).
"""
import os

import jax
import jax.numpy as jnp

from ..core.registry import register_kernel
from ..core.lowering import BlockRunner, RNG_KEY
from ..lod import SequenceTensor

_DEFAULT_CAP = int(os.environ.get('PADDLE_TPU_ARRAY_CAP', 128))


# ---- tensor arrays --------------------------------------------------------------
def _is_array(v):
    return isinstance(v, dict) and (('buf' in v) or ('list' in v)) \
        and 'len' in v


def _is_list_array(v):
    return isinstance(v, dict) and 'list' in v


def make_array(buf, length):
    return {'buf': buf, 'len': jnp.asarray(length, jnp.int32)}


def _list_to_buf(arr):
    """Promote a list-backed array to the uniform buffer form (needed
    when a traced index reaches it inside lax control flow). Elements
    must share a shape by then — true for static decode paths. Gaps
    left by non-contiguous writes become zero elements."""
    elems = [None if e is None else
             (jnp.asarray(e.data) if isinstance(e, SequenceTensor)
              else jnp.asarray(e)) for e in arr['list']]
    proto = next((e for e in elems if e is not None), None)
    if proto is None:
        raise ValueError("cannot promote an all-empty tensor array")
    elems = [jnp.zeros_like(proto) if e is None else e for e in elems]
    return make_array(jnp.stack(elems), len(elems))


@register_kernel('write_to_array')
def _write_to_array(ctx):
    x = ctx.input('X')
    i = jnp.asarray(ctx.input('I')).reshape(()).astype(jnp.int32)
    name = ctx.output_name('Out')
    arr = ctx.env.get(name)
    concrete_i = None
    try:
        concrete_i = int(i)
    except Exception:
        pass  # traced index (inside a loop): capacity must already fit
    if ctx.runner.dynamic and concrete_i is not None and (
            arr is None or not _is_array(arr) or _is_list_array(arr)):
        # Eager dynamic mode only: host-indexed writes keep a LIST of
        # heterogeneous elements — the reference's LoDTensorArray.
        # Shapes and LoD may differ per step (dynamic beam decode);
        # SequenceTensors survive intact. Jitted/profiling runs keep
        # the uniform buffer so lax loops can carry the array.
        lst = list(arr['list']) if _is_list_array(arr) else []
        while len(lst) <= concrete_i:
            lst.append(None)
        lst[concrete_i] = x
        ctx.env[name] = {'list': lst, 'len': len(lst)}
        return
    if _is_list_array(arr):
        arr = _list_to_buf(arr)
    x = jnp.asarray(x.data) if isinstance(x, SequenceTensor) else \
        jnp.asarray(x)
    if not _is_array(arr):
        cap = _DEFAULT_CAP if concrete_i is None else \
            max(_DEFAULT_CAP, concrete_i + 1)
        buf = jnp.zeros((cap,) + tuple(x.shape), x.dtype)
        arr = make_array(buf, 0)
    elif concrete_i is not None and concrete_i >= arr['buf'].shape[0]:
        # grow: concrete out-of-range writes must not silently clamp
        grow = max(concrete_i + 1 - arr['buf'].shape[0],
                   arr['buf'].shape[0])
        pad = [(0, grow)] + [(0, 0)] * (arr['buf'].ndim - 1)
        arr = make_array(jnp.pad(arr['buf'], pad), arr['len'])
    # an array holds one dtype, its first write's: a later element that
    # arrives narrower (a bf16 activation under AMP into a float32 array)
    # is stored in the array's
    buf = jax.lax.dynamic_update_index_in_dim(
        arr['buf'], x.astype(arr['buf'].dtype), i, 0)
    ctx.env[name] = make_array(buf, jnp.maximum(arr['len'], i + 1))


@register_kernel('read_from_array')
def _read_from_array(ctx):
    arr = ctx.input('X')
    if not _is_array(arr):
        raise TypeError("read_from_array on a non-array value")
    i = jnp.asarray(ctx.input('I')).reshape(()).astype(jnp.int32)
    if _is_list_array(arr):
        try:
            # clamp like the buffer path (dynamic_index_in_dim semantics)
            idx = min(max(int(i), 0), len(arr['list']) - 1)
            val = arr['list'][idx]
            if val is None:
                # gap left by a non-contiguous write: a zero element,
                # matching the buffer path
                proto = next(e for e in arr['list'] if e is not None)
                val = jnp.zeros_like(
                    proto.data if isinstance(proto, SequenceTensor)
                    else jnp.asarray(proto))
            ctx.set_output('Out', val)
            return
        except jax.errors.TracerIntegerConversionError:
            arr = _list_to_buf(arr)
    ctx.set_output('Out', jax.lax.dynamic_index_in_dim(
        arr['buf'], i, 0, keepdims=False))


@register_kernel('lod_array_length')
def _lod_array_length(ctx):
    arr = ctx.input('X')
    ctx.set_output('Out', jnp.reshape(
        jnp.asarray(arr['len'], jnp.int32), (1,)))


# ---- LoD rank table machinery ---------------------------------------------------
@register_kernel('lod_rank_table')
def _lod_rank_table(ctx):
    st = ctx.input('X')
    if not isinstance(st, SequenceTensor):
        raise TypeError("lod_rank_table needs a SequenceTensor input")
    lens = jnp.asarray(st.lengths, jnp.int32)
    # reference sorts items by length descending (stable)
    order = jnp.argsort(-lens, stable=True).astype(jnp.int32)
    ctx.env[ctx.output_name('Out')] = {
        'lengths': lens, 'index': order,
        'padded_len': jnp.asarray(st.data.shape[1])}


@register_kernel('max_sequence_len')
def _max_sequence_len(ctx):
    table = ctx.input('RankTable')
    ctx.set_output('Out', jnp.reshape(
        jnp.max(table['lengths']), (1,)).astype(jnp.int32))


@register_kernel('lod_tensor_to_array')
def _lod_tensor_to_array(ctx):
    st = ctx.input('X')
    table = ctx.input('RankTable')
    data = jnp.asarray(st.data)
    # rank-sorted batch, time-major: buf[t] = batch slice at step t
    sorted_rows = jnp.take(data, table['index'], axis=0)
    buf = jnp.moveaxis(sorted_rows, 1, 0)
    arr = make_array(buf, jnp.max(table['lengths']))
    if st.sub_lengths is not None:
        # level-2 input: stamp the inner lengths (ORIGINAL order) on
        # the array itself — exact provenance, so array_to_lod_tensor
        # restores the full LoD only on arrays that really came from a
        # level-2 tensor (a shape heuristic collides whenever a fresh
        # While array's capacity equals the outer bucket pad)
        arr['sub_lengths'] = jnp.asarray(st.sub_lengths, jnp.int32)
    ctx.env[ctx.output_name('Out')] = arr


@register_kernel('array_to_lod_tensor')
def _array_to_lod_tensor(ctx):
    arr = ctx.input('X')
    table = ctx.input('RankTable')
    data = jnp.moveaxis(arr['buf'], 0, 1)  # [B, cap, ...]
    inv = jnp.argsort(table['index']).astype(jnp.int32)
    data = jnp.take(data, inv, axis=0)
    lengths = jnp.take(jnp.take(table['lengths'], table['index']), inv)
    # level-2 round trip: only arrays stamped by lod_tensor_to_array
    # carry sub_lengths; per-step emissions written to fresh arrays
    # (make_array drops extra keys) stay level-1 by construction
    ctx.set_output('Out', SequenceTensor(
        data, lengths, arr.get('sub_lengths')))


@register_kernel('reorder_lod_tensor_by_rank')
def _reorder_lod_tensor_by_rank(ctx):
    x = ctx.input('X')
    table = ctx.input('RankTable')
    order = table['index']
    if isinstance(x, SequenceTensor):
        ctx.set_output('Out', SequenceTensor(
            jnp.take(jnp.asarray(x.data), order, axis=0),
            jnp.take(jnp.asarray(x.lengths), order, axis=0),
            None if x.sub_lengths is None else
            jnp.take(jnp.asarray(x.sub_lengths), order, axis=0)))
    else:
        ctx.set_output('Out', jnp.take(jnp.asarray(x), order, axis=0))


@register_kernel('split_lod_tensor')
def _split_lod_tensor(ctx):
    """Masked formulation: both branches see the full batch; selection
    happens in merge_lod_tensor (SURVEY §2.3 — data-dependent batch
    splitting replaced by masking, the XLA-friendly design)."""
    x = ctx.input('X')
    ctx.set_output('OutTrue', x)
    ctx.set_output('OutFalse', x)


@register_kernel('merge_lod_tensor')
def _merge_lod_tensor(ctx):
    mask = ctx.input('Mask')
    t = ctx.input('InTrue')
    f = ctx.input('InFalse')
    td = jnp.asarray(t.data if isinstance(t, SequenceTensor) else t)
    fd = jnp.asarray(f.data if isinstance(f, SequenceTensor) else f)
    m = jnp.asarray(mask.data if isinstance(mask, SequenceTensor)
                    else mask)
    m = m.astype(bool) if m.dtype == jnp.bool_ else (m != 0)
    if m.size == 1:
        m = m.reshape(())
    else:
        m = m.reshape((m.shape[0],) + (1,) * (td.ndim - 1))
    out = jnp.where(m, td, fd)
    if isinstance(t, SequenceTensor) or isinstance(f, SequenceTensor):
        # blend lengths row-wise too: a row taken from InFalse must carry
        # InFalse's valid length (dense side defaults to full width)
        full = jnp.full((td.shape[0],), td.shape[1]
                        if td.ndim > 1 else 1, jnp.int32)
        tl = jnp.asarray(t.lengths, jnp.int32) \
            if isinstance(t, SequenceTensor) else full
        fl = jnp.asarray(f.lengths, jnp.int32) \
            if isinstance(f, SequenceTensor) else full
        lens = jnp.where(m.reshape(-1) if m.ndim else m, tl, fl)
        out = SequenceTensor(out, lens)
    ctx.set_output('Out', out)


# ---- sub-block execution helpers ------------------------------------------------
def _written_names(block):
    """All names assigned by ops of ``block`` (incl. nested sub-blocks)."""
    names = []
    for op in block.ops:
        for n in op.output_arg_names:
            if n not in names:
                names.append(n)
        sub = op.attrs.get('sub_block')
        if sub is not None:
            for n in _written_names(sub):
                if n not in names:
                    names.append(n)
    return names


def _carried(new, entered):
    """A loop-carried value in the float dtype it entered the loop with:
    lax loops want one type per carry, and under AMP an update may arrive
    narrower than the state it replaces (a bf16 fc output assigned to a
    float32 memory)."""
    def keep(a, b):
        da, db = jnp.result_type(a), jnp.result_type(b)
        if da != db and jnp.issubdtype(da, jnp.floating) \
                and jnp.issubdtype(db, jnp.floating):
            return jnp.asarray(a).astype(db)
        return a
    return jax.tree_util.tree_map(keep, new, entered)


def _run_sub_block(block, env, grad_mode, dynamic=False):
    runner = BlockRunner(block, grad_mode=grad_mode, dynamic=dynamic)
    runner.run_ops(list(block.ops), env)
    return env


@register_kernel('while')
def _while(ctx):
    """lax.while_loop over the sub-block. Carried state = vars the body
    writes that already exist outside the loop (parity: WhileOp's var
    analysis in paddle/fluid/operators/while_op.cc), plus the PRNG key."""
    block = ctx.attr('sub_block')
    cond_name = ctx.input_name('Condition')
    env = ctx.env
    cond0 = env.get(cond_name)
    if ctx.runner.dynamic and cond0 is not None and \
            not isinstance(cond0, jax.core.Tracer):
        # Eager dynamic mode (reference while_op semantics): the
        # condition is concrete, so interpret the loop on the host.
        # Each iteration runs with its OWN shapes — beam widths and
        # row counts may grow step to step (dynamic decode). The policy
        # deciding which programs run this way lives in ONE place:
        # executor._is_dynamic_program.
        grad_mode = ctx.runner.grad_mode
        iters = 0
        while bool(jnp.asarray(env[cond_name]).reshape(())):
            _run_sub_block(block, env, grad_mode, dynamic=True)
            iters += 1
            if iters > 100000:
                raise RuntimeError("while: >100000 host iterations — "
                                   "non-terminating loop?")
        return
    carry_names = [n for n in _written_names(block) if n in env]
    if cond_name not in carry_names:
        if cond_name not in env:
            raise KeyError("while condition %r not computed before the "
                           "loop" % cond_name)
        carry_names.append(cond_name)
    has_rng = RNG_KEY in env
    if has_rng and RNG_KEY not in carry_names:
        carry_names.append(RNG_KEY)
    base_env = {k: v for k, v in env.items() if k not in carry_names}
    grad_mode = ctx.runner.grad_mode

    def cond_fn(carry):
        return jnp.asarray(carry[cond_name]).reshape(()).astype(bool)

    def body_fn(carry):
        benv = dict(base_env)
        benv.update(carry)
        _run_sub_block(block, benv, grad_mode)
        return {n: _carried(benv[n], init[n]) for n in carry_names}

    init = {n: env[n] for n in carry_names}
    final = jax.lax.while_loop(cond_fn, body_fn, init)
    env.update(final)


@register_kernel('conditional_block')
def _conditional_block(ctx):
    """Run the sub-block and blend its writes with the condition.

    TPU design: XLA computes both sides of a select anyway for small
    bodies; running unconditionally + where-blend avoids lax.cond's
    same-structure constraint and keeps Switch/IfElse (incl. piecewise LR
    decay) fully traceable. Pre-existing vars are blended; fresh vars are
    exported as-is (IfElse merges them later via merge_lod_tensor)."""
    block = ctx.attr('sub_block')
    conds = ctx.inputs('Cond')
    env = ctx.env
    c = None
    for v in conds:
        cv = jnp.asarray(v.data if isinstance(v, SequenceTensor) else v)
        cv = cv if cv.dtype == jnp.bool_ else (cv != 0)
        c = cv if c is None else jnp.logical_and(c, cv)
    written = _written_names(block)
    old = {n: env[n] for n in written if n in env}
    benv = dict(env)
    _run_sub_block(block, benv, ctx.runner.grad_mode,
                   dynamic=ctx.runner.dynamic)
    scalar = bool(ctx.attr('is_scalar_condition', False))
    for n in written:
        if n not in benv:
            continue
        new = benv[n]
        if n in old and not _is_array(new):
            oldv = old[n]
            nd = jnp.asarray(new.data if isinstance(new, SequenceTensor)
                             else new)
            od = jnp.asarray(oldv.data if isinstance(oldv, SequenceTensor)
                             else oldv)
            if scalar or c.size == 1:
                cc = c.reshape(())
            elif c.ndim >= 1 and nd.ndim >= 1 and c.shape[0] == nd.shape[0]:
                cc = c.reshape((c.shape[0],) + (1,) * (nd.ndim - 1))
            else:
                cc = c.reshape(())
            blended = jnp.where(cc, nd, od)
            if isinstance(new, SequenceTensor):
                blended = SequenceTensor(blended, new.lengths,
                                         new.sub_lengths)
            env[n] = blended
        else:
            env[n] = new


# ---- StaticRNN ------------------------------------------------------------------
@register_kernel('static_rnn')
def _static_rnn(ctx):
    """lax.scan over time-major [T, B, ...] step inputs.
    Parity: paddle/fluid/operators/recurrent_op.cc (RecurrentOp)."""
    block = ctx.attr('sub_block')
    step_in_names = list(ctx.attr('step_inputs'))
    pre_mems = list(ctx.attr('pre_mems'))
    mems = list(ctx.attr('mems'))
    step_out_names = list(ctx.attr('step_outputs'))
    xs = [jnp.asarray(v.data if isinstance(v, SequenceTensor) else v)
          for v in ctx.inputs('Inputs')]
    boots = ctx.inputs('Boots')
    env = ctx.env
    grad_mode = ctx.runner.grad_mode
    has_rng = RNG_KEY in env

    carry0 = {p: jnp.asarray(b) for p, b in zip(pre_mems, boots)}
    if has_rng:
        carry0[RNG_KEY] = env[RNG_KEY]

    def body(carry, x_t):
        benv = dict(env)
        benv.update(carry)
        for n, x in zip(step_in_names, x_t):
            benv[n] = x
        _run_sub_block(block, benv, grad_mode)
        new_carry = {p: _carried(benv[m], carry0[p])
                     for p, m in zip(pre_mems, mems)}
        if has_rng:
            new_carry[RNG_KEY] = benv[RNG_KEY]
        ys = [benv[o] for o in step_out_names]
        return new_carry, ys

    final_carry, ys = jax.lax.scan(body, carry0, xs)
    if has_rng:
        env[RNG_KEY] = final_carry[RNG_KEY]
    for name, y in zip(ctx.output_names('Outputs'), ys):
        env[name] = y


# ---- DynamicRNN -----------------------------------------------------------------
@register_kernel('dynamic_rnn')
def _dynamic_rnn(ctx):
    """Masked lax.scan over SequenceTensor inputs.

    The reference (DynamicRNN via lod_rank_table + shrink_rnn_memory)
    shrinks the live batch every step; the TPU-native equivalent keeps the
    full padded batch and freezes each row's memory once its sequence
    ends — identical results, static shapes."""
    block = ctx.attr('sub_block')
    step_in_names = list(ctx.attr('step_inputs'))
    static_inside = list(ctx.attr('static_inside'))
    mem_info = list(ctx.attr('mem_info'))
    step_out_names = list(ctx.attr('step_outputs'))
    seq_inputs = ctx.inputs('Inputs')
    statics = ctx.inputs('Statics')
    boots = list(ctx.inputs('Boots'))
    env = ctx.env
    grad_mode = ctx.runner.grad_mode
    has_rng = RNG_KEY in env

    st0 = seq_inputs[0]
    if not isinstance(st0, SequenceTensor):
        raise TypeError("dynamic_rnn inputs must be SequenceTensors")
    B, T = st0.data.shape[:2]
    lengths = jnp.asarray(st0.lengths, jnp.int32)
    xs = [jnp.moveaxis(jnp.asarray(s.data), 0, 1) for s in seq_inputs]
    step_mask = (jnp.arange(T)[:, None] < lengths[None, :])  # [T, B]

    carry0 = {}
    bi = 0
    for m in mem_info:
        if m['has_init']:
            init = boots[bi]
            bi += 1
            carry0[m['pre']] = jnp.asarray(
                init.data if isinstance(init, SequenceTensor) else init)
        else:
            shape = (B,) + tuple(int(s) for s in m['shape'])
            carry0[m['pre']] = jnp.full(shape, float(m['value']),
                                        jnp.float32)
    if has_rng:
        carry0[RNG_KEY] = env[RNG_KEY]

    base_env = dict(env)
    for outer, inner in zip(statics, static_inside):
        base_env[inner] = outer

    def body(carry, scan_in):
        x_t, m_t = scan_in
        benv = dict(base_env)
        benv.update(carry)
        for n, x in zip(step_in_names, x_t):
            benv[n] = x
        _run_sub_block(block, benv, grad_mode)
        new_carry = {}
        for m in mem_info:
            newv = jnp.asarray(benv[m['new']])
            oldv = carry[m['pre']]
            mm = m_t.reshape((B,) + (1,) * (newv.ndim - 1))
            new_carry[m['pre']] = jnp.where(mm, newv, oldv)
        if has_rng:
            new_carry[RNG_KEY] = benv[RNG_KEY]
        ys = []
        for o in step_out_names:
            y = jnp.asarray(benv[o])
            ys.append(y * m_t.reshape((B,) + (1,) * (y.ndim - 1))
                      .astype(y.dtype))
        return new_carry, ys

    final_carry, ys = jax.lax.scan(body, carry0, (xs, step_mask))
    if has_rng:
        env[RNG_KEY] = final_carry[RNG_KEY]
    for name, y in zip(ctx.output_names('Outputs'), ys):
        env[name] = SequenceTensor(jnp.moveaxis(y, 0, 1), lengths)


@register_kernel('shrink_rnn_memory')
def _shrink_rnn_memory(ctx):
    # masked-scan design keeps the full batch; shrink is the identity
    ctx.set_output('Out', ctx.input('X'))
