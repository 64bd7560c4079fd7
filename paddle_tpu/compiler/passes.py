"""The canonical program-level passes.

Pipeline order (``default_pipeline``)::

    constant_fold -> dead_op_elim -> conv_epilogue_fuse ->
    elementwise_fuse -> buffer_reuse

plus ``bn_fold`` at the head for inference programs
(``inference_pipeline`` / the legacy ``InferenceTranspiler`` facade).

The exact passes replay the very same registered kernels, so
optimized-vs-raw outputs are bit-identical (pinned by
tests/test_compiler.py). ``bn_fold`` re-associates the BN affine into
conv/fc weights and documents <= 1e-5 drift; ``conv_epilogue_fuse``
inherits the same tolerance when its Pallas path engages (on TPU or
under the test force-hook) and is an exact replay everywhere else.
"""
import numpy as np

from .. import observability as _obs
from ..framework import Block, Operator
from ..core.registry import SIDE_EFFECT_OPS, get_kernel, register_kernel
from ..core.lowering import (BlockRunner, OpCtx, RNG_KEY, _op_reads,
                             _op_writes)
from .pass_base import Pass, PassResult, register_pass

__all__ = ['DeadOpElimination', 'ConstantFolding', 'ElementwiseFusion',
           'ConvEpilogueFusion', 'BufferReuse', 'BatchNormFolding',
           'DEFAULT_PASSES', 'INFERENCE_PASSES', 'RNG_OPS',
           'FUSED_ELEMENTWISE_OP', 'FUSED_CONV_OP',
           'conv_fuse_counts', 'flash_counts', 'amp_elementwise_counts',
           'moe_counts', 'ssd_counts', 'loss_counts']

# Ops that consume the threaded PRNG key: removing one would shift the
# RNG stream of every later stochastic op, silently changing numerics —
# dead-op elimination must keep them even when their outputs are dead.
RNG_OPS = frozenset({
    'dropout', 'gaussian_random', 'gaussian_random_batch_size_like',
    'truncated_gaussian_random', 'uniform_random',
    'uniform_random_batch_size_like', 'nce', 'sampling_id',
})

# Ops the dead-op pass must never drop regardless of liveness.
_ALWAYS_KEEP = frozenset({'feed', 'fetch'})


def _has_sub_block(op):
    return any(isinstance(v, Block) for v in op.attrs.values())


def _hidden_reads(op):
    """Names consumed through ATTRS, invisible to ``_op_reads``: the
    gradient markers' cotangent sources and sparse-lookup ids. Every
    liveness-style analysis here must treat them as reads."""
    if op.type == 'gradient_marker':
        return [n for n in (op.attrs.get('target_grads') or ()) if n]
    if op.type == 'backward_marker':
        return [p[0] for pairs in (op.attrs.get('sparse') or {}).values()
                for p in pairs]
    return []


def _program_has_sub_blocks(program):
    return len(program.blocks) > 1 or any(
        _has_sub_block(op) for op in program.global_block().ops)


@register_pass
class DeadOpElimination(Pass):
    """Remove global-block ops whose outputs reach neither a protected
    (fetch) name, a persistable var, nor a side-effecting/kept op.

    Parity: the executor's prune-before-run, generalized — it also runs
    on training programs, where it drops fetch-dead metric branches
    (accuracy heads nobody fetched this run) that the reference
    interpreter would have executed anyway. Conservative keeps: side
    effects, sub-block carriers, RNG consumers (stream stability),
    feed/fetch ops, persistable writers."""

    name = 'dead_op_elim'

    def _forced_keep(self, block, op):
        """Liveness aside, must this op survive? Side effects, feed/
        fetch, RNG stream consumers, sub-block carriers, attr-only
        definers, persistable writers. The sanitizer's
        side-effect-preserved invariant is exactly this predicate's
        contract — tests seed mutations here."""
        if (op.type in SIDE_EFFECT_OPS or op.type in _ALWAYS_KEEP
                or op.type in RNG_OPS or _has_sub_block(op)
                or not op.output_arg_names):
            return True
        for nm in op.output_arg_names:
            var = block._find_var_recursive(nm)
            if var is not None and var.persistable:
                return True
        return False

    def run(self, program, ctx):
        res = PassResult(self.name)
        if not ctx.protected:
            # no fetch information: every leaf could be the caller's
            # target, so there is nothing provably dead
            res.note = 'no protected names; skipped'
            return res
        block = program.global_block()
        ops = block.ops
        live = set(ctx.protected)
        keep = [False] * len(ops)
        for i in reversed(range(len(ops))):
            op = ops[i]
            forced = self._forced_keep(block, op)
            if forced or any(nm in live for nm in op.output_arg_names):
                keep[i] = True
                live.update(_op_reads(op))
                live.update(_hidden_reads(op))
        removed = keep.count(False)
        if removed:
            block.ops = [op for i, op in enumerate(ops) if keep[i]]
            program._bump_version()
        res.changed = bool(removed)
        res.ops_removed = removed
        return res


# Pure, deterministic, dense-safe op types constant folding may
# evaluate at pass time. RNG ops are excluded by construction (and
# would fail the eval anyway: no PRNG key in the fold environment).
_FOLDABLE = frozenset({
    'elementwise_add', 'elementwise_sub', 'elementwise_mul',
    'elementwise_div', 'elementwise_max', 'elementwise_min',
    'elementwise_pow', 'scale', 'cast', 'concat', 'sum', 'minus',
    'square', 'sqrt', 'exp', 'log', 'abs', 'relu', 'tanh', 'sigmoid',
    'softmax', 'transpose', 'reverse', 'clip', 'pow', 'mean',
    'fill_zeros_like', 'assign', 'one_hot', 'ceil', 'floor', 'round',
    'reciprocal', 'softplus', 'softsign', 'reshape', 'split',
})

_CONST_PRODUCERS = frozenset({'fill_constant', 'assign_value'})

# Don't bake arrays bigger than this into the program (attr bloat +
# fingerprint hashing cost outweigh the folded flops).
_MAX_FOLD_ELEMS = 1 << 16


@register_pass
class ConstantFolding(Pass):
    """Evaluate compile-time-constant subgraphs once, at pass time.

    Op outputs reachable only from ``fill_constant``/``assign_value``
    producers are computed by running the registered kernels eagerly;
    consumers outside the constant region read a baked ``assign_value``
    instead. Interior ops of the folded region are dropped here; the
    orphaned producers fall to the following dead-op pass."""

    name = 'constant_fold'

    def run(self, program, ctx):
        res = PassResult(self.name)
        block = program.global_block()
        ops = block.ops
        const_env = {}     # name -> (np value, producer idx, foldable?)
        folded = set()     # indices of evaluated FOLDABLE ops
        need_mat = {}      # producer idx -> set(names to materialize)

        def _note_reads(op):
            for nm in list(_op_reads(op)) + _hidden_reads(op):
                hit = const_env.get(nm)
                if hit is not None and hit[2]:
                    need_mat.setdefault(hit[1], set()).add(nm)

        for i, op in enumerate(ops):
            if op.type in _CONST_PRODUCERS and not _has_sub_block(op):
                vals = self._eval(block, op, const_env)
                if vals is not None:
                    for nm, v in vals.items():
                        const_env[nm] = (v, i, False)
                    continue
            writes_persistable = False
            for nm in op.output_arg_names:
                var = block._find_var_recursive(nm)
                if var is not None and var.persistable:
                    writes_persistable = True
            if (op.type in _FOLDABLE and not _has_sub_block(op)
                    and not writes_persistable and op.input_arg_names
                    and all(n in const_env
                            for n in op.input_arg_names)):
                vals = self._eval(block, op, const_env)
                if vals is not None:
                    for nm, v in vals.items():
                        const_env[nm] = (v, i, True)
                    folded.add(i)
                    continue
            # not folded: its reads of constants must materialize, and
            # its writes (incl. nested) shadow any same-named constant
            _note_reads(op)
            for nm in _op_writes(op):
                const_env.pop(nm, None)
        for nm in ctx.protected:
            hit = const_env.get(nm)
            if hit is not None and hit[2]:
                need_mat.setdefault(hit[1], set()).add(nm)

        if not folded:
            return res
        new_ops = []
        for i, op in enumerate(ops):
            if i not in folded:
                new_ops.append(op)
                continue
            for nm in sorted(need_mat.get(i, ())):
                val = const_env[nm][0]
                new_ops.append(Operator(
                    block, 'assign_value', inputs={},
                    outputs={'Out': [nm]},
                    attrs={'shape': list(val.shape),
                           'dtype': str(val.dtype),
                           'values': val}))
        res.ops_folded = len(folded)
        res.ops_removed = len(ops) - len(new_ops)
        res.changed = True
        block.ops = new_ops
        program._bump_version()
        return res

    @staticmethod
    def _eval(block, op, const_env):
        """Run ``op``'s registered kernel on concrete values; None on
        any failure (dynamic shape, unexpected structure, too big)."""
        try:
            env = {n: np.asarray(const_env[n][0])
                   for n in op.input_arg_names}
            get_kernel(op.type)(OpCtx(op, env, BlockRunner(block)))
            out = {}
            for nm in op.output_arg_names:
                if nm not in env:
                    return None
                v = np.asarray(env[nm])
                if v.size > _MAX_FOLD_ELEMS:
                    return None
                out[nm] = v
            return out
        except Exception:
            return None


# Pure elementwise/activation op types: no RNG, no reductions over the
# batch, no sequence re-shaping — a chain of these replayed in order is
# the exact computation of the original ops.
_ELEMENTWISE = frozenset({
    'elementwise_add', 'elementwise_sub', 'elementwise_mul',
    'elementwise_div', 'elementwise_max', 'elementwise_min',
    'elementwise_pow', 'scale', 'clip', 'relu', 'sigmoid', 'tanh',
    'exp', 'log', 'sqrt', 'abs', 'square', 'softplus', 'softsign',
    'ceil', 'floor', 'round', 'reciprocal', 'logsigmoid',
    'tanh_shrink', 'brelu', 'leaky_relu', 'soft_relu', 'elu', 'relu6',
    'pow', 'stanh', 'hard_shrink', 'softshrink', 'thresholded_relu',
    'hard_sigmoid', 'swish',
})

FUSED_ELEMENTWISE_OP = 'fused_elementwise'


def _attrs_fusable(attrs):
    for v in attrs.values():
        if not isinstance(v, (int, float, bool, str, bytes, type(None),
                              list, tuple)):
            return False
        if isinstance(v, (list, tuple)) and not all(
                isinstance(e, (int, float, bool, str)) for e in v):
            return False
    return True


def _capture_region(members):
    """(external inputs, sub_ops attr tuples) for an op region that is
    about to collapse into one fused op. An input is external when no
    earlier member produced it; sub_ops is the replayable capture
    format shared by fused_elementwise and fused_conv."""
    produced = set()
    ext_inputs = []
    for m in members:
        for nm in m.input_arg_names:
            if nm not in produced and nm not in ext_inputs:
                ext_inputs.append(nm)
        produced.update(m.output_arg_names)
    sub_ops = [(m.type, {s: list(v) for s, v in m.inputs.items()},
                {s: list(v) for s, v in m.outputs.items()},
                {k: (list(v) if isinstance(v, tuple) else v)
                 for k, v in m.attrs.items()})
               for m in members]
    return ext_inputs, sub_ops


def _materialized_sub_ops(ctx):
    """The fused op's captured region as live Operators, memoized on
    the op instance (one materialization per compile)."""
    ops = ctx.op.__dict__.get('_materialized')
    if ops is None:
        ops = [Operator(ctx.runner.block, t, inputs=dict(i),
                        outputs=dict(o), attrs=dict(a))
               for t, i, o, a in ctx.attr('sub_ops')]
        ctx.op.__dict__['_materialized'] = ops
    return ops


@register_kernel(FUSED_ELEMENTWISE_OP)
def _fused_elementwise_kernel(ctx):
    """Lower one fused region as ONE kernel: the captured sub-ops
    replay inside a single named scope, so the whole chain lands in one
    HLO region (XLA fuses it into one loop — the introspection hook the
    acceptance test asserts on). Gradients flow through the replay
    exactly as through the original ops."""
    import jax
    ops = _materialized_sub_ops(ctx)
    with jax.named_scope(FUSED_ELEMENTWISE_OP):
        ctx.runner.run_ops(ops, ctx.env)


@register_pass
class ElementwiseFusion(Pass):
    """Merge single-consumer chains of pure elementwise/activation ops
    into one ``fused_elementwise`` op that lowers as a single kernel.

    Chain link rule: op_i's ``Out`` is read by exactly ONE op anywhere
    in the program, that reader is a later elementwise op in the global
    block, and the intermediate is neither protected, persistable, nor
    hazarded (no op between the members writes a name the members read
    or write). The fused op sits at the LAST member's position — every
    external input is already produced there, and no dropped
    intermediate had any other reader."""

    name = 'elementwise_fuse'

    def _extension_hazard(self, ops, cur, j, hazard):
        """WAR/WAW hazard: an interloper between chain tail ``cur`` and
        candidate ``j`` writing anything the chain touches would
        see/change the wrong value once the members move to j's
        position. The sanitizer's read-order-hazard invariant is the
        post-hoc twin of this check — tests seed mutations here."""
        for k in range(cur + 1, j):
            if set(_op_writes(ops[k])) & hazard:
                return True
        return False

    def run(self, program, ctx):
        res = PassResult(self.name)
        block = program.global_block()
        ops = block.ops
        # readers across ALL blocks (a sub-block read makes an
        # intermediate external, breaking the chain)
        read_count = {}
        for b in program.blocks:
            for op in b.ops:
                for nm in list(op.input_arg_names) + _hidden_reads(op):
                    read_count[nm] = read_count.get(nm, 0) + 1
        global_reader = {}
        for j, op in enumerate(ops):
            for nm in op.input_arg_names:
                global_reader.setdefault(nm, []).append(j)
        # fused_conv producers (conv_epilogue_fuse runs just before this
        # pass): Out name -> index, for absorbing elementwise chains
        # across the conv boundary into the epilogue
        fc_out = {}
        for j, op in enumerate(ops):
            if op.type == FUSED_CONV_OP and 'Out' in op.outputs:
                fc_out[op.outputs['Out'][0]] = j

        def _sole_out(op):
            outs = op.output_arg_names
            if len(outs) != 1 or list(op.outputs) != ['Out']:
                return None
            return outs[0]

        used = set()
        chains = []
        for i, op in enumerate(ops):
            if i in used or op.type not in _ELEMENTWISE \
                    or not _attrs_fusable(op.attrs):
                continue
            chain = [i]
            hazard = set(_op_reads(op)) | set(_op_writes(op))
            cur = i
            while True:
                out = _sole_out(ops[cur])
                if out is None or read_count.get(out, 0) != 1:
                    break
                readers = global_reader.get(out, [])
                if len(readers) != 1 or readers[0] <= cur:
                    break
                j = readers[0]
                nxt = ops[j]
                if nxt.type not in _ELEMENTWISE or j in used \
                        or not _attrs_fusable(nxt.attrs):
                    break
                if out in ctx.protected:
                    break
                var = block._find_var_recursive(out)
                if var is not None and var.persistable:
                    break
                if self._extension_hazard(ops, cur, j, hazard):
                    break
                hazard |= set(_op_reads(nxt)) | set(_op_writes(nxt))
                chain.append(j)
                cur = j
            if len(chain) >= 2:
                chains.append(chain)
                used.update(chain)
            elif any(nm in fc_out for nm in op.input_arg_names):
                # a lone elementwise op behind a fused_conv is still
                # worth absorbing into that conv's epilogue
                chains.append(chain)

        if not chains:
            return res
        drop, insert_at = set(), {}

        def _absorb_into_conv(chain, members):
            """Cross-conv-boundary absorption: when the chain's head
            consumes the sole-read output of an earlier ``fused_conv``,
            fold the whole chain into that conv's epilogue region
            instead of emitting a separate fused_elementwise — the
            Pallas lowering then applies it in-register on the conv
            output tiles. Returns True when absorbed."""
            head = members[0]
            for nm in head.input_arg_names:
                p = fc_out.get(nm)
                if p is None or p >= chain[0] or p in drop:
                    continue
                fc = ops[p]
                if read_count.get(nm, 0) != 1 or nm in ctx.protected:
                    continue
                var = block._find_var_recursive(nm)
                if var is not None and var.persistable:
                    continue
                # the conv op MOVES to the chain tail: its other
                # outputs (train-BN stats) must have no reader at or
                # before the new position, and no hidden/sub-block
                # reads we cannot place
                ok = True
                for out_nm in fc.output_arg_names:
                    if out_nm == nm:
                        continue
                    own = sum(1 for nm2 in fc.input_arg_names
                              if nm2 == out_nm)
                    gl = [j for j in global_reader.get(out_nm, ())
                          if j != p]
                    if read_count.get(out_nm, 0) - own != len(gl) or \
                            any(j <= chain[-1] for j in gl):
                        ok = False
                        break
                if not ok:
                    continue
                # interlopers between the conv and the chain tail must
                # not write anything the moved region reads or writes
                hz = set(_op_reads(fc)) | set(_op_writes(fc))
                for m in members:
                    hz |= set(_op_reads(m)) | set(_op_writes(m))
                in_chain = set(chain)
                for k in range(p + 1, chain[-1]):
                    if k in in_chain:
                        continue
                    if set(_op_writes(ops[k])) & hz:
                        ok = False
                        break
                if not ok:
                    continue
                chain_ext, chain_sub = _capture_region(members)
                produced = set(fc.output_arg_names)
                new_ext = list(fc.inputs.get('X', ()))
                for enm in chain_ext:
                    if enm not in produced and enm not in new_ext:
                        new_ext.append(enm)
                outputs = {'Out': [members[-1].outputs['Out'][0]]}
                if 'Stats' in fc.outputs:
                    outputs['Stats'] = list(fc.outputs['Stats'])
                merged = Operator(
                    block, FUSED_CONV_OP,
                    inputs={'X': new_ext}, outputs=outputs,
                    attrs={'sub_ops': list(fc.attrs['sub_ops'])
                           + chain_sub,
                           'fused_types': list(fc.attrs['fused_types'])
                           + [m.type for m in members],
                           'fused_count': fc.attrs['fused_count']
                           + len(members)})
                insert_at[chain[-1]] = merged
                drop.update(chain)
                drop.add(p)
                res.ops_fused += len(members)
                return True
            return False

        for chain in chains:
            members = [ops[k] for k in chain]
            if _absorb_into_conv(chain, members):
                continue
            if len(chain) < 2:
                continue
            ext_inputs, sub_ops = _capture_region(members)
            final_out = members[-1].outputs['Out'][0]
            fused = Operator(
                block, FUSED_ELEMENTWISE_OP,
                inputs={'X': ext_inputs},
                outputs={'Out': [final_out]},
                attrs={'sub_ops': sub_ops,
                       'fused_types': [m.type for m in members],
                       'fused_count': len(members)})
            insert_at[chain[-1]] = fused
            drop.update(chain)
            res.ops_fused += len(members)
        if not insert_at:
            return res
        new_ops = []
        for k, op in enumerate(ops):
            if k in insert_at:
                new_ops.append(insert_at[k])
            elif k not in drop:
                new_ops.append(op)
        block.ops = new_ops
        program._bump_version()
        res.changed = True
        res.ops_removed = len(ops) - len(new_ops)
        return res


# ---- fused conv + epilogue -----------------------------------------------

FUSED_CONV_OP = 'fused_conv'

# Epilogue op types conv_epilogue_fuse may absorb behind a conv: BN
# plus every pure elementwise/activation op. The fused_conv lowering
# maps each onto an in-register epilogue stage (ops/pallas_kernels.py);
# anything it cannot map at a given shape/dtype replays the exact
# unfused kernels instead — counted and journalled, never wrong.
_EPILOGUE_OPS = _ELEMENTWISE | {'batch_norm'}

_EPI_BIN_OPS = frozenset({
    'elementwise_add', 'elementwise_sub', 'elementwise_mul',
    'elementwise_div', 'elementwise_max', 'elementwise_min',
    'elementwise_pow'})

# parameterized activations: (attr name, default) per stage argument,
# mirroring the ops/math_ops.py kernel signatures one-for-one
_EPI_PARAM_ACTS = {
    'brelu': (('t_min', 0.0), ('t_max', 24.0)),
    'leaky_relu': (('alpha', 0.02),),
    'soft_relu': (('threshold', 40.0),),
    'elu': (('alpha', 1.0),),
    'relu6': (('threshold', 6.0),),
    'pow': (('factor', 1.0),),
    'stanh': (('scale_a', 2.0 / 3.0), ('scale_b', 1.7159)),
    'hard_shrink': (('threshold', 0.5),),
    'softshrink': (('lambda', 0.5),),
    'thresholded_relu': (('threshold', 1.0),),
    'hard_sigmoid': (('slope', 0.2), ('offset', 0.5)),
    'swish': (('beta', 1.0),),
    'clip': (('min', None), ('max', None)),
}


def _pair2(v):
    if isinstance(v, (list, tuple)):
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


def _classify_aux(x_shape, y, axis):
    """Map a binary elementwise operand against the NCHW conv output
    (shape ``x_shape``) onto an epilogue aux kind, mirroring
    ops/common.py::bcast_y: 'c' per-channel [1, C], 'nc' per-sample
    channel vector [N, C] (the SE excitation), 't' full tensor (the
    residual), 's' scalar. Returns (kind, NHWC-shaped value) or None
    when the broadcast pattern has no epilogue equivalent."""
    import jax.numpy as jnp
    n, c, h, w = x_shape
    if y.ndim == 0:
        return 's', jnp.reshape(y, (1, 1))
    if tuple(int(d) for d in y.shape) == tuple(x_shape):
        return 't', jnp.transpose(y, (0, 2, 3, 1))
    ys = [int(d) for d in y.shape]
    if axis is None or axis == -1:
        axis = 4 - len(ys)
    while ys and axis + len(ys) > 4 and ys[-1] == 1:
        ys.pop()
    if axis < 0 or axis + len(ys) > 4 or \
            list(x_shape[axis:axis + len(ys)]) != ys:
        return None
    b = [1] * axis + ys + [1] * (4 - axis - len(ys))
    val = jnp.reshape(y, tuple(b))
    if b == [1, c, 1, 1]:
        return 'c', jnp.reshape(val, (1, c))
    if b == [n, c, 1, 1]:
        return 'nc', jnp.reshape(val, (n, c))
    if b == [1, 1, 1, 1]:
        return 's', jnp.reshape(val, (1, 1))
    return None


def _lower_fused_conv(ctx, ops, mode):
    """Try the single-kernel Pallas lowering for a fused_conv region;
    returns None on success or a fallback-reason string (nothing is
    written to the environment on failure)."""
    import jax
    import jax.numpy as jnp
    from ..lod import SequenceTensor
    from ..ops import pallas_kernels as pk

    conv = ops[0]
    if conv.type not in ('conv2d', 'depthwise_conv2d'):
        return 'head:%s' % conv.type
    if _pair2(conv.attrs.get('dilations', (1, 1))) != (1, 1):
        return 'dilation'
    x = ctx.env.get(conv.inputs['Input'][0])
    w = ctx.env.get(conv.inputs['Filter'][0])
    if isinstance(x, SequenceTensor) or isinstance(w, SequenceTensor):
        return 'sequence-input'
    x = jnp.asarray(x)
    w = jnp.asarray(w)
    if x.ndim != 4 or w.ndim != 4:
        return 'rank'
    if x.dtype not in (jnp.float32, jnp.bfloat16) or w.dtype != x.dtype:
        return 'dtype'
    n, cin, h, w_in = (int(d) for d in x.shape)
    groups = int(conv.attrs.get('groups', 1) or 1)
    # conv2d with groups == channels and a [C, 1, KH, KW] filter IS a
    # depthwise conv (what layers.conv2d(groups=C) builds)
    depthwise = conv.type == 'depthwise_conv2d' or (
        groups == cin and int(w.shape[0]) == cin
        and int(w.shape[1]) == 1)
    if depthwise:
        if int(w.shape[0]) != cin or int(w.shape[1]) != 1:
            return 'depthwise-multiplier'
        cout = cin
    else:
        if groups != 1:
            return 'groups'   # se_resnext cardinality convs fall back
        if int(w.shape[1]) != cin:
            return 'filter-shape'
        cout = int(w.shape[0])
    strides = _pair2(conv.attrs.get('strides', (1, 1)))
    pads = _pair2(conv.attrs.get('paddings', (0, 0)))
    kh, kw = int(w.shape[2]), int(w.shape[3])
    ho = (h + 2 * pads[0] - kh) // strides[0] + 1
    wo = (w_in + 2 * pads[1] - kw) // strides[1] + 1
    out_shape = (n, cout, ho, wo)

    # map the epilogue members onto kernel stages + aux operands
    stages, aux, kinds = [], [], []
    train_bn = None
    cur = conv.outputs['Output'][0]
    for op in ops[1:]:
        if op.type not in _EPI_BIN_OPS and \
                op.inputs.get('X', [None])[0] != cur:
            return 'chain-slot'
        if op.type == 'batch_norm':
            if op.attrs.get('data_layout', 'NCHW') != 'NCHW':
                return 'bn-layout'
            if not op.attrs.get('is_test', False):
                # train-mode BN: batch moments need the full conv
                # output, so it must sit directly on the conv (the
                # kernel emits moment partials; everything after is
                # applied on the normalized value outside)
                if train_bn is not None or op is not ops[1]:
                    return 'train-bn-order'
                if x.dtype != jnp.float32:
                    return 'train-bn-dtype'
                train_bn = op
                cur = op.outputs['Y'][0]
                continue
            eps = float(op.attrs.get('epsilon', 1e-5))
            scale = jnp.asarray(ctx.env[op.inputs['Scale'][0]],
                                jnp.float32)
            bias = jnp.asarray(ctx.env[op.inputs['Bias'][0]],
                               jnp.float32)
            mean = jnp.asarray(ctx.env[op.inputs['Mean'][0]],
                               jnp.float32)
            var = jnp.asarray(ctx.env[op.inputs['Variance'][0]],
                              jnp.float32)
            alpha = scale * jax.lax.rsqrt(var + eps)
            beta = bias - mean * alpha
            aux += [alpha.reshape(1, cout), beta.reshape(1, cout)]
            kinds += ['c', 'c']
            stages.append(('affine', len(aux) - 2, len(aux) - 1))
            cur = op.outputs['Y'][0]
            continue
        if op.type in _EPI_BIN_OPS:
            xin = op.inputs.get('X', [None])[0]
            yin = op.inputs.get('Y', [None])[0]
            if xin == cur:
                swap, other_nm = False, yin
            elif yin == cur:
                swap, other_nm = True, xin
            else:
                return 'chain-slot'
            other = ctx.env.get(other_nm)
            if other is None or isinstance(other, SequenceTensor):
                return 'aux-missing'
            other = jnp.asarray(other)
            if not jnp.issubdtype(other.dtype, jnp.floating):
                return 'aux-dtype'
            if swap:
                # chain value is the Y operand (resnet residual:
                # elementwise_add(x=short, y=conv_out)); bcast_y leaves
                # Y untouched only for equal shapes
                if tuple(int(d) for d in other.shape) != out_shape:
                    return 'aux-shape'
                got = ('t', jnp.transpose(other, (0, 2, 3, 1)))
            else:
                got = _classify_aux(out_shape, other,
                                    op.attrs.get('axis', -1))
                if got is None:
                    return 'aux-shape'
            kinds.append(got[0])
            aux.append(got[1])
            stages.append(('bin', op.type, len(aux) - 1, swap))
            s = op.attrs.get('scale', None)
            if s not in (None, 1.0):
                stages.append(('postmul', float(s)))
        elif op.type == 'scale':
            stages.append(('scale', float(op.attrs.get('scale', 1.0)),
                           float(op.attrs.get('bias', 0.0)),
                           bool(op.attrs.get('bias_after_scale',
                                             True))))
        elif op.type in _EPI_PARAM_ACTS:
            params = []
            for attr, dflt in _EPI_PARAM_ACTS[op.type]:
                v = op.attrs.get(attr, dflt)
                if v is None:
                    return 'act-attr:%s' % op.type
                params.append(float(v))
            stages.append(('act_p', op.type, tuple(params)))
        elif op.type in pk._EPI_ACTS:
            stages.append(('act', op.type))
        else:
            return 'stage:%s' % op.type
        cur = op.outputs['Out'][0]

    interpret = mode == 'interpret'
    x_nhwc = jnp.transpose(x, (0, 2, 3, 1))
    w_k = (jnp.transpose(w[:, 0], (1, 2, 0)) if depthwise
           else jnp.transpose(w, (2, 3, 1, 0)))
    if train_bn is None:
        got, why = pk.fused_conv_epilogue(
            x_nhwc, w_k, tuple(aux), tuple(kinds), strides, pads,
            depthwise, tuple(stages), interpret=interpret)
        if why is not None:
            return why
        ctx.set_output('Out', jnp.transpose(got, (0, 3, 1, 2)))
        return None

    # train-BN path: the kernel emits f32 moment partials alongside the
    # conv output; normalization, the moving-average update and any
    # post-BN stages run on the NHWC value here (bn kernel math,
    # ops/nn_ops.py)
    got, why = pk.fused_conv_epilogue(
        x_nhwc, w_k, (), (), strides, pads, depthwise, (),
        emit_stats=True, interpret=interpret)
    if why is not None:
        return why
    y, psum, psumsq = got
    count = float(n * ho * wo)
    bmean = jnp.sum(psum, axis=(0, 1)) / count
    bvar = jnp.maximum(
        jnp.sum(psumsq, axis=(0, 1)) / count - jnp.square(bmean), 0.0)
    bn = train_bn
    scale = jnp.asarray(ctx.env[bn.inputs['Scale'][0]])
    bias = jnp.asarray(ctx.env[bn.inputs['Bias'][0]])
    mean = jnp.asarray(ctx.env[bn.inputs['Mean'][0]])
    var = jnp.asarray(ctx.env[bn.inputs['Variance'][0]])
    momentum = float(bn.attrs.get('momentum', 0.9))
    eps = float(bn.attrs.get('epsilon', 1e-5))
    inv = jax.lax.rsqrt(bvar + eps)
    yn = (y - bmean[None, None, None, :]) * inv[None, None, None, :] \
        * scale.reshape(1, 1, 1, -1) + bias.reshape(1, 1, 1, -1)

    def fetch4(idx):
        kind2 = kinds[idx]
        o = aux[idx].astype(jnp.float32)
        if kind2 == 't':
            return o
        if kind2 == 'nc':
            return o[:, None, None, :]
        if kind2 == 's':
            return o.reshape(())
        return o.reshape(1, 1, 1, -1)

    for st in stages:
        yn = pk._apply_stage(yn, st, fetch4)
    ctx.set_output('Out', jnp.transpose(yn, (0, 3, 1, 2)))
    new_mean = mean * momentum + bmean * (1.0 - momentum)
    new_var = var * momentum + bvar * (1.0 - momentum)
    ctx.set_output('Stats', jax.lax.stop_gradient(new_mean), 0)
    ctx.set_output('Stats', jax.lax.stop_gradient(new_var), 1)
    ctx.set_output('Stats', bmean, 2)
    ctx.set_output('Stats', bvar, 3)
    return None


@register_kernel(FUSED_CONV_OP)
def _fused_conv_kernel(ctx):
    """Lower a fused conv region: one Pallas kernel (conv + in-register
    epilogue) when engaged and supported, exact replay of the captured
    sub-ops otherwise. Replay is bit-identical to the unfused program —
    the pass can absorb liberally because correctness never rides on
    the Pallas path. What the kernel cannot take is refused by
    predicate (a reason string) and counted by reason in
    ``conv_fuse_fallbacks_total{reason=}``; lowerings that engaged
    count in ``conv_fuse_engaged_total``; the off-TPU replay is not a
    fallback. A kernel that raises while engaged is a failure of the
    step, not a replay."""
    import jax
    from ..ops import pallas_kernels as pk
    ops = _materialized_sub_ops(ctx)
    mode = pk.conv_epilogue_mode()
    if mode:
        why = _lower_fused_conv(ctx, ops, mode)
        reg = _obs.default_registry()
        if why is None:
            reg.counter(
                'conv_fuse_engaged_total',
                help='fused_conv lowerings that ran as one Pallas '
                     'kernel').inc()
            return
        reg.counter(
            'conv_fuse_fallbacks_total',
            help='fused_conv lowerings that fell back to exact replay '
                 '(Pallas engaged but shape/dtype/layout refused), by '
                 'reason', reason=why).inc()
        _obs.emit('conv_fuse_fallback', reason=why,
                  types=list(ctx.attr('fused_types', ())),
                  out=ctx.op.outputs['Out'][0])
    with jax.named_scope(FUSED_CONV_OP):
        ctx.runner.run_ops(ops, ctx.env)


def _series(name):
    """The live series of one metric of the default registry."""
    return _obs.default_registry().snapshot().get(name, {}).get(
        'series', ())


def conv_fuse_counts():
    """``{'engaged': n, 'fallbacks': {reason: n}}``: how the process's
    fused_conv lowerings went so far (counted per trace, i.e. per
    compile)."""
    return {
        'engaged': int(sum(s['value'] for s in
                           _series('conv_fuse_engaged_total'))),
        'fallbacks': {s['labels']['reason']: int(s['value'])
                      for s in _series('conv_fuse_fallbacks_total')
                      if s['value']}}


def _label_counts(name, by):
    """``{labels named by ``by``: n}`` of one counter, summed over its
    other labels; series still at 0 are left out."""
    counts = {}
    for s in _series(name):
        if s['value']:
            key = tuple(s['labels'][label] for label in by)
            counts[key] = counts.get(key, 0) + int(s['value'])
    return counts


def flash_counts(by=('route', 'dtype')):
    """``{(route, dtype): n}``: how the process's flash_attention op
    lowerings went so far (ops/misc_ops.py; counted per trace, as the
    conv-fuse counts are). route is 'pallas' or 'xla', dtype the
    operand dtype the attention ran in ('bf16', 'f32'). ``by`` names
    the labels of the key, the counter summed over the others: 'diag'
    is the body the kernels give a tile on the causal diagonal
    (pallas_kernels.flash_diag: 'chunked<r>', 'whole', or 'none' for
    the xla route or no mask), 'kv_heads' the KV heads the query heads
    share (num_heads where every query head has its own), 'window' the
    window the attention ran under ('0': none, or one that reaches
    every key)."""
    return _label_counts('flash_attention_lowerings_total', by)


def window_flash_counts():
    """``{(window,): n}``: the flash_attention lowerings with a window
    that took the Pallas route, whose kernels skip the tiles below the
    band; a windowed lowering that fell to the XLA route (masked, not
    skipped) is not in it."""
    return {(w,): n for (route, w), n in
            flash_counts(by=('route', 'window')).items()
            if route == 'pallas' and w != '0'}


def moe_counts(by=('experts', 'held', 'top_k', 'route')):
    """``{(experts, held, top_k, route): n}`` (``by`` may also name
    'act', the expert's activation: 'relu2' or 'swiglu'): the
    process's
    routed_experts op lowerings so far (ops/hybrid_ops.py; counted per
    trace, as the others are): the experts routed over, the experts
    held here, the experts a token takes (strings, as labels are) and
    the route of the grouped products: 'pallas' (the Pallas grouped
    matmul of ops/pallas_kernels.py: a TPU backend, bf16 operands,
    latent and expert widths multiples of 128) or 'ragged_dot'
    (``lax.ragged_dot``: everything else, the CPU tests among it).
    ops/pallas_kernels.py::grouped_plan is the rule. 'row_sum' names
    how a chunk's rows are summed back into their tokens: 'pallas' or
    'xla' (moe_row_sum_counts); 'row_gather' how that sum's transpose
    gathers the rows' gradients back, the same way
    (moe_row_gather_counts)."""
    return _label_counts('moe_lowerings_total', by)


def moe_row_sum_counts():
    """``{('pallas',): n}``: the routed_experts lowerings whose rows are
    summed back into their tokens (the forward's combine and the row
    pick's transpose) by the Pallas kernel that adds only a chunk's live
    rows (ops/pallas_kernels.py::row_sum_plan is the rule); a lowering
    that kept XLA's scatter-add is not in it."""
    return {k: n for k, n in moe_counts(by=('row_sum',)).items()
            if k == ('pallas',)}


def moe_row_gather_counts():
    """``{('pallas',): n}``: the routed_experts lowerings whose sum back
    is transposed (the rows' gradients gathered from the output's) by
    the Pallas kernel that copies only a chunk's live rows
    (ops/pallas_kernels.py::row_gather_plan is the rule); a lowering
    that kept XLA's ``jnp.take`` is not in it. The forward's row pick
    is ``jnp.take`` on every route."""
    return {k: n for k, n in moe_counts(by=('row_gather',)).items()
            if k == ('pallas',)}


def loss_counts():
    """``{(): n}``: the softmax_with_cross_entropy lowerings that took
    the hard-label rule (ops/nn_ops.py::_lse_loss: the logits kept in
    the dtype they came in, a row's float32 lse, the logits' gradient
    in one pass), one a traced program; a soft-label lowering (a
    float32 log_softmax the size of the logits) counts nothing."""
    return _label_counts('loss_lowerings_total', ())


def rotary_counts(by=('dim', 'dtype')):
    """``{(dim, dtype): n}``: the process's rotary_embedding op
    lowerings so far (ops/hybrid_ops.py): the head size turned and the
    dtype of the stream it turned ('bfloat16' under AMP; the angles,
    cos and sin are float32 in all)."""
    return _label_counts('rotary_lowerings_total', by)


def ssd_counts(by=('route', 'chunk')):
    """``{(route, chunk): n}``: the process's ssd_scan op lowerings so
    far (ops/hybrid_ops.py): route 'xla' (the chunked form in
    jax.numpy; no Pallas scan yet) and the chunk length."""
    return _label_counts('ssd_lowerings_total', by)


def amp_elementwise_counts(by=('result',)):
    """``{(result,): n}``: the process's binary elementwise lowerings
    that met one bf16 and one f32 operand under bf16 activation flow
    (ops/math_ops.py::_amp_flow; counted per trace, as the other two
    are). result is 'kept_bf16' (a bf16 X against a broadcast f32 Y
    returned to bf16) or 'widened_f32' (the f32 stream stayed f32);
    lowerings with no such pair count nothing. ``by`` may name 'op'
    (the elementwise_* type) as well."""
    return _label_counts('amp_elementwise_lowerings_total', by)


@register_pass
class ConvEpilogueFusion(Pass):
    """Merge conv2d/depthwise_conv2d -> batch_norm -> activation /
    residual-add chains into single ``fused_conv`` ops.

    Chain rule mirrors ElementwiseFusion (each link's output has
    exactly one reader anywhere in the program, that reader is a later
    epilogue-absorbable op in the global block, intermediates are
    neither protected nor persistable, no interloper writes a name the
    region touches), with the head restricted to convs. A train-mode
    batch_norm rides along once, directly behind the conv, its
    moving-average/saved-stats outputs re-declared on the fused op
    ('Stats' slot); a test-mode batch_norm's extra outputs must be dead
    or persistable-backed, since they vanish with the op. The fused op
    sits at the LAST member's position.

    Not semantics-preserving in the bit-exact sense: when the Pallas
    epilogue engages (TPU, or the test force-hook) the kernel
    accumulates in f32 and applies the whole epilogue before one final
    cast — <= 1e-5 drift on f32 (policy as ``bn_fold``, pinned by
    tests/test_conv_fuse.py); with Pallas disengaged the lowering
    replays the captured ops bit-identically."""

    name = 'conv_epilogue_fuse'
    preserves_semantics = False

    _HEADS = ('conv2d', 'depthwise_conv2d')

    @staticmethod
    def _hazard(ops, cur, j, hazard):
        for k in range(cur + 1, j):
            if set(_op_writes(ops[k])) & hazard:
                return True
        return False

    def run(self, program, ctx):
        res = PassResult(self.name)
        block = program.global_block()
        ops = block.ops
        read_count = {}
        for b in program.blocks:
            for op in b.ops:
                for nm in list(op.input_arg_names) + _hidden_reads(op):
                    read_count[nm] = read_count.get(nm, 0) + 1
        global_reader = {}
        for j, op in enumerate(ops):
            for nm in op.input_arg_names:
                global_reader.setdefault(nm, []).append(j)

        def _dead_or_param(names):
            for nm in names:
                var = block._find_var_recursive(nm)
                if var is not None and var.persistable:
                    continue
                if read_count.get(nm, 0) or nm in ctx.protected:
                    return False
            return True

        used = set()
        regions = []          # (chain indices, stats names, final out)
        for i, op in enumerate(ops):
            if op.type not in self._HEADS or i in used \
                    or _has_sub_block(op) \
                    or not _attrs_fusable(op.attrs) \
                    or len(op.outputs.get('Output', ())) != 1:
                continue
            chain = [i]
            hazard = set(_op_reads(op)) | set(_op_writes(op))
            cur = i
            cur_out = op.outputs['Output'][0]
            stats = None
            while True:
                if read_count.get(cur_out, 0) != 1 \
                        or cur_out in ctx.protected:
                    break
                var = block._find_var_recursive(cur_out)
                if var is not None and var.persistable:
                    break
                readers = global_reader.get(cur_out, [])
                if len(readers) != 1 or readers[0] <= cur:
                    break
                j = readers[0]
                nxt = ops[j]
                if nxt.type not in _EPILOGUE_OPS or j in used \
                        or _has_sub_block(nxt) \
                        or not _attrs_fusable(nxt.attrs):
                    break
                if nxt.type == 'batch_norm':
                    if nxt.inputs.get('X', [None])[0] != cur_out \
                            or len(nxt.outputs.get('Y', ())) != 1:
                        break
                    extra = [nxt.outputs[s][0]
                             for s in ('MeanOut', 'VarianceOut',
                                       'SavedMean', 'SavedVariance')
                             if nxt.outputs.get(s)]
                    if nxt.attrs.get('is_test', False):
                        if not _dead_or_param(extra):
                            break
                    else:
                        if stats is not None or len(extra) != 4:
                            break
                        stats = extra
                    nxt_out = nxt.outputs['Y'][0]
                else:
                    if list(nxt.outputs) != ['Out'] \
                            or len(nxt.outputs['Out']) != 1:
                        break
                    if cur_out not in (
                            nxt.inputs.get('X', [None])[0],
                            nxt.inputs.get('Y', [None])[0]):
                        break
                    nxt_out = nxt.outputs['Out'][0]
                if self._hazard(ops, cur, j, hazard):
                    break
                hazard |= set(_op_reads(nxt)) | set(_op_writes(nxt))
                chain.append(j)
                cur = j
                cur_out = nxt_out
            if len(chain) >= 2:
                used.update(chain)
                regions.append((chain, stats, cur_out))

        if not regions:
            return res
        counter = _obs.default_registry().counter(
            'conv_fuse_ops_fused_total',
            help='ops absorbed into fused_conv regions by '
                 'conv_epilogue_fuse')
        drop, insert_at = set(), {}
        for chain, stats, final_out in regions:
            members = [ops[k] for k in chain]
            ext_inputs, sub_ops = _capture_region(members)
            outputs = {'Out': [final_out]}
            if stats:
                outputs['Stats'] = stats
            fused = Operator(
                block, FUSED_CONV_OP, inputs={'X': ext_inputs},
                outputs=outputs,
                attrs={'sub_ops': sub_ops,
                       'fused_types': [m.type for m in members],
                       'fused_count': len(members)})
            insert_at[chain[-1]] = fused
            drop.update(chain)
            res.ops_fused += len(members)
            counter.inc(len(members))
        new_ops = []
        for k, op in enumerate(ops):
            if k in insert_at:
                new_ops.append(insert_at[k])
            elif k not in drop:
                new_ops.append(op)
        block.ops = new_ops
        program._bump_version()
        res.changed = True
        res.ops_removed = len(ops) - len(new_ops)
        return res


@register_pass
class BufferReuse(Pass):
    """Liveness-based buffer-release annotations lowering honors.

    For every non-persistable name, find its LAST reader in the global
    block and annotate that op with ``__release__`` so
    ``BlockRunner.run_ops`` drops the environment reference once the op
    completes — the value's buffer becomes reusable instead of living
    to the end of the block (the TPU-meaningful successor of the
    reference ``memory_optimization_transpiler``'s in-place var reuse;
    in eager/dynamic mode this is a direct peak-memory win, under jit
    it shortens XLA's computed live ranges for donated temporaries).
    Fetch and persistable-state names are additionally guarded at
    lowering time (``BlockRunner.keep``), so an annotation can never
    starve a fetch the pass didn't know about."""

    name = 'buffer_reuse'

    def __init__(self, skip=None):
        self.skip = frozenset(skip or ())

    def run(self, program, ctx):
        res = PassResult(self.name)
        if _program_has_sub_blocks(program):
            # control-flow bodies re-read parent names per iteration;
            # a static last-read index over the flat op list would lie
            res.note = 'sub-blocks present; skipped'
            return res
        if any(op.type == 'gradient_marker'
               for op in program.global_block().ops):
            # calc_gradient's marker snapshots the environment and
            # replays earlier ops from it — names a static liveness
            # would call dead are still read through the snapshot
            res.note = 'gradient_marker present; skipped'
            return res
        block = program.global_block()
        ops = block.ops
        last_read = {}
        for i, op in enumerate(ops):
            for nm in list(_op_reads(op)) + _hidden_reads(op):
                last_read[nm] = i
        skip = set(ctx.protected) | self.skip | {RNG_KEY}
        releases = {}
        for nm, i in last_read.items():
            if nm in skip or nm in _op_writes(ops[i]):
                continue
            var = block._find_var_recursive(nm)
            if var is not None and var.persistable:
                continue
            releases.setdefault(i, []).append(nm)
        changed = 0
        for i, op in enumerate(ops):
            want = tuple(sorted(releases.get(i, ())))
            have = tuple(op.attrs.get('__release__', ()))
            if want != have:
                if want:
                    op.attrs['__release__'] = want
                else:
                    op.attrs.pop('__release__', None)
                changed += 1
            res.vars_released += len(want)
        if changed:
            program._bump_version()
        res.changed = bool(changed)
        return res


@register_pass
class BatchNormFolding(Pass):
    """Inference BN folding into the preceding conv/fc weights.

    Parity: inference_transpiler.py::_fuse_conv_bn / _fuse_param. For
    every ``conv2d``/``depthwise_conv2d``/``mul`` whose single consumer
    is a ``batch_norm`` and whose weights are resident in the scope::

        w' = w * scale / sqrt(var + eps)          (per output channel)
        b' = bias - mean * scale / sqrt(var + eps)

    the BN op is REMOVED and an ``elementwise_add(axis=1)`` with the
    folded bias takes over BN's output name. Remaining BN/dropout ops
    flip to test mode. Not semantics-preserving in the bit-exact sense:
    the re-associated affine drifts <= 1e-5 (tolerance policy pinned in
    tests/test_compiler.py)."""

    name = 'bn_fold'
    preserves_semantics = False

    def run(self, program, ctx):
        res = PassResult(self.name)
        scope = ctx.scope
        if scope is None:
            from ..executor import global_scope
            scope = global_scope()
        res.ops_folded = self._fuse_bn(program, scope)
        res.changed = bool(res.ops_folded)
        if self._mark_test_mode(program):
            res.changed = True
        return res

    @staticmethod
    def _consumers(program, name):
        return [op for b in program.blocks for op in b.ops
                if name in op.input_arg_names]

    def _fuse_bn(self, program, scope):
        block = program.global_block()
        # a weight with ANY other consumer cannot be rewritten in
        # place: each use would need its own scaled copy
        weight_uses = {}
        for b in program.blocks:
            for op in b.ops:
                for name in op.input_arg_names:
                    weight_uses[name] = weight_uses.get(name, 0) + 1
        folded = 0
        i = 0
        while i < len(block.ops):
            op = block.ops[i]
            if op.type in ('conv2d', 'depthwise_conv2d'):
                out_slot, w_slot = 'Output', 'Filter'
            elif op.type == 'mul':
                out_slot, w_slot = 'Out', 'Y'
            else:
                i += 1
                continue
            out_name = op.outputs[out_slot][0]
            consumers = self._consumers(program, out_name)
            if len(consumers) != 1 or consumers[0].type != 'batch_norm':
                i += 1
                continue
            bn = consumers[0]
            w_name = op.inputs[w_slot][0]
            w_var = block._find_var_recursive(w_name)
            if weight_uses.get(w_name, 0) > 1 or w_var is None \
                    or not getattr(w_var, 'persistable', False):
                i += 1
                continue
            vals, ok = {}, True
            for slot in ('Scale', 'Bias', 'Mean', 'Variance'):
                v = scope.raw(bn.inputs[slot][0])
                if v is None:
                    ok = False
                    break
                vals[slot] = np.asarray(v, np.float32)
            w_val = scope.raw(w_name)
            if not ok or w_val is None:
                i += 1
                continue
            w_val = np.asarray(w_val, np.float32)
            eps = float(bn.attrs.get('epsilon', 1e-5))
            alpha = vals['Scale'] / np.sqrt(vals['Variance'] + eps)
            if op.type == 'mul':
                if w_val.ndim != 2 or w_val.shape[1] != alpha.shape[0]:
                    i += 1
                    continue
                new_w = w_val * alpha[None, :]
            else:
                new_w = w_val * alpha[:, None, None, None]
            new_b = vals['Bias'] - vals['Mean'] * alpha

            bias_var = block.create_var(
                name=w_name + '.bn_fold_bias', shape=list(new_b.shape),
                dtype='float32', persistable=True)
            scope.set_var(w_name, new_w.astype(w_val.dtype))
            scope.set_var(bias_var.name, new_b.astype(np.float32))

            bn_idx = block.ops.index(bn)
            bn_out = bn.outputs['Y'][0]
            block.remove_op(bn_idx)
            block.insert_op(bn_idx, type='elementwise_add',
                            inputs={'X': [out_name],
                                    'Y': [bias_var.name]},
                            outputs={'Out': [bn_out]},
                            attrs={'axis': 1})
            folded += 1
            i += 1
        if folded:
            program._bump_version()
        return folded

    @staticmethod
    def _mark_test_mode(program):
        changed = False
        for block in program.blocks:
            for op in block.ops:
                if op.type in ('batch_norm', 'dropout') and \
                        op.attrs.get('is_test') is not True:
                    op.attrs['is_test'] = True
                    changed = True
        if changed:
            program._bump_version()
        return changed


# Canonical pipelines (see __init__.py for the config surface).
# conv_epilogue_fuse runs right before elementwise_fuse so the latter
# can absorb leftover elementwise chains into the conv epilogues.
DEFAULT_PASSES = ('constant_fold', 'dead_op_elim', 'conv_epilogue_fuse',
                  'elementwise_fuse', 'buffer_reuse')
INFERENCE_PASSES = ('bn_fold',) + DEFAULT_PASSES
