"""AMP's activation rule in the binary elementwise kernels
(ops/math_ops.py::_amp_flow): under bf16 activation flow a bf16 X against
a broadcast f32 Y (an fc's bias) computes in float32 and returns to bf16,
so the gradient of the hidden stays bf16 too; everything else keeps the
dtype type promotion gives it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import compiler
from paddle_tpu.compiler.passes import amp_elementwise_counts
from paddle_tpu.core.registry import get_kernel

BF16, F32 = jnp.bfloat16, jnp.float32
_FNS = {'add': jnp.add, 'sub': jnp.subtract, 'mul': jnp.multiply}


class _Ctx:
    """Just enough of OpCtx to drive an elementwise kernel directly."""

    def __init__(self, x, y, attrs):
        self._i, self._a, self.out = {'X': x, 'Y': y}, attrs, None

    def input(self, slot, idx=0):
        return self._i[slot]

    def attr(self, name, default=None):
        return self._a.get(name, default)

    def set_output(self, slot, val, idx=0):
        self.out = val


def _op(name, x, y, **attrs):
    ctx = _Ctx(x, y, attrs)
    get_kernel('elementwise_' + name)(ctx)
    return ctx.out


def _operands(xdtype, ydtype, yshape, xshape=(2, 4, 8), seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(*xshape), F32).astype(xdtype)
    y = jnp.asarray(rng.randn(*yshape), F32).astype(ydtype)
    return x, y


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def _same(a, b):
    assert a.dtype == b.dtype and a.shape == b.shape
    np.testing.assert_array_equal(_bits(a), _bits(b))


def _value_and_grads(fn, x, y):
    """fn(x, y), and the gradients of sum(fn * w) at a fixed w."""
    out = fn(x, y)
    w = jnp.asarray(np.random.RandomState(9).randn(*out.shape), out.dtype)
    return (out,) + jax.grad(
        lambda a, b: jnp.sum((fn(a, b) * w).astype(F32)), (0, 1))(x, y)


@pytest.mark.parametrize('name', sorted(_FNS))
def test_bf16_x_broadcast_f32_y_returns_to_bf16(name, amp):
    """The rule: float32 math, one rounding to bf16; the bias's gradient
    is the float32 sum of the bf16 cotangent."""
    amp.set_amp(True)
    x, y = _operands(BF16, F32, (8,))
    got = _value_and_grads(lambda a, b: _op(name, a, b), x, y)
    want = _value_and_grads(
        lambda a, b: _FNS[name](a.astype(F32), b).astype(BF16), x, y)
    assert [g.dtype for g in got] == [BF16, BF16, F32]
    for g, w in zip(got, want):
        _same(g, w)
    if name == 'add':
        ct = jnp.asarray(np.random.RandomState(9).randn(2, 4, 8), BF16)
        _same(got[2], jnp.sum(ct.astype(F32), axis=(0, 1)))


@pytest.mark.parametrize('switch', ['amp_off', 'act_f32'])
@pytest.mark.parametrize('name', sorted(_FNS))
def test_rule_off_is_the_plain_promotion(name, switch, amp):
    """AMP off, or activations flowing float32: output and gradients are
    bit for bit jnp's own promotion, the kernel's body before the rule,
    and nothing is counted."""
    amp.set_amp(switch == 'act_f32')
    amp.set_amp_act(False)
    x, y = _operands(BF16, F32, (8,))
    before = amp_elementwise_counts()
    got = _value_and_grads(lambda a, b: _op(name, a, b), x, y)
    want = _value_and_grads(
        lambda a, b: _FNS[name](a, b.reshape(1, 1, 8)), x, y)
    assert got[0].dtype == F32
    for g, w in zip(got, want):
        _same(g, w)
    assert amp_elementwise_counts() == before


@pytest.mark.parametrize('xdtype,ydtype,yshape,xshape,out,counted', [
    (BF16, BF16, (8,), (2, 4, 8), BF16, None),
    (BF16, BF16, (2, 4, 8), (2, 4, 8), BF16, None),
    (F32, F32, (8,), (2, 4, 8), F32, None),
    (F32, BF16, (8,), (2, 4, 8), F32, 'widened_f32'),
    (F32, BF16, (2, 4, 8), (2, 4, 8), F32, 'widened_f32'),
    (BF16, F32, (2, 4, 8), (2, 4, 8), F32, 'widened_f32'),
    (BF16, F32, (4, 8), (2, 4, 8), BF16, 'kept_bf16'),
    (BF16, F32, (8,), (2, 4, 8), BF16, 'kept_bf16'),
    (BF16, F32, (8,), (1, 8), BF16, 'kept_bf16')])
def test_result_dtype_by_operands(xdtype, ydtype, yshape, xshape, out,
                                  counted, amp):
    """Only a bf16 X against a broadcast f32 Y returns to bf16: equal
    dtypes are untouched, an f32 X stays f32 whatever Y is, and a bf16
    X against an f32 Y of its own shape (the residual stream) widens as
    before; a batch of one against its bias ([1, 8] + [8], as many
    elements on both sides) is a broadcast like any other batch. Each
    mixed pair counts once under its result."""
    amp.set_amp(True)
    x, y = _operands(xdtype, ydtype, yshape, xshape)
    before = amp_elementwise_counts(by=('op', 'result'))
    got = _op('add', x, y)
    after = amp_elementwise_counts(by=('op', 'result'))
    assert got.dtype == out
    _same(got, jnp.add(x, y.reshape((1,) * (x.ndim - y.ndim) + y.shape))
          .astype(out))
    moved = {k: n - before.get(k, 0) for k, n in after.items()
             if n != before.get(k, 0)}
    assert moved == ({('elementwise_add', counted): 1} if counted else {})


def test_scale_attribute_applies_before_the_cast(amp):
    amp.set_amp(True)
    x, y = _operands(BF16, F32, (8,))
    got = _op('add', x, y, scale=0.3)
    _same(got, ((x.astype(F32) + y) * 0.3).astype(BF16))
    assert not np.array_equal(
        _bits(got), _bits((x.astype(F32) + y).astype(BF16) * 0.3))


# ---- through the fc layers of a program -------------------------------------
_B, _T, _H, _F = 2, 4, 16, 32


def _ffn_program():
    """x + fc(fc(layer_norm(x), relu)): the OPT cell's FFN block, tiny;
    the layer norm's parameters make the first fc's data gradient
    needed."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 3
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name='x', shape=[_T, _H], dtype='float32')
        ln = fluid.layers.layer_norm(x, begin_norm_axis=2)
        h = fluid.layers.fc(input=ln, size=_F, num_flatten_dims=2,
                            act='relu')
        o = fluid.layers.fc(input=h, size=_H, num_flatten_dims=2)
        loss = fluid.layers.mean(fluid.layers.square(x + o))
        grads = fluid.backward.append_backward(loss)
    fetch = [loss, h, o] + [g for _, g in grads]
    return main, startup, fetch


def _ffn_feed():
    return {'x': np.random.RandomState(1).randn(_B, _T, _H)
            .astype('float32')}


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def _step_jaxpr(exe, main, feed, fetch, scope):
    """The jaxpr of the step Executor.run compiles (as Executor._lower
    builds it)."""
    from paddle_tpu.core.lowering import lower_block
    names, feed, s_in, s_out, static_env = exe._prep_lowering(
        main, feed, fetch, scope, consume_readers=False)
    prog = exe._optimized_program(main, names, scope=scope)
    fn = lower_block(prog, prog.global_block(), sorted(feed), names,
                     s_in, s_out, static_env=static_env)
    return prog, jax.make_jaxpr(fn)(feed, {n: scope.raw(n) for n in s_in})


def test_fc_block_fused_equals_unfused_and_hidden_gradient_stays_bf16(amp):
    """fc(act='relu') lowers as one fused_elementwise op that replays
    the very kernels: loss, activations and gradients equal the unfused
    program's to the bit under AMP, the biased fc outputs are bf16, and
    a trace counts the two bias adds and the residual once each. In the
    step's jaxpr every dot takes bf16 operands and no relu, select or
    multiply runs on a float32 value of the hidden's size: the float32
    values of that size are the bias add's own (the widened matmul
    output and the sum) and, in the backward, the one convert that feeds
    the bias gradient's reduce_sum."""
    amp.set_amp(True)
    main, startup, fetch = _ffn_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        before = amp_elementwise_counts()
        fused = exe.run(main, feed=_ffn_feed(), fetch_list=fetch,
                        return_numpy=False)
        after = amp_elementwise_counts()
        with compiler.disabled():
            plain = exe.run(main, feed=_ffn_feed(), fetch_list=fetch,
                            return_numpy=False)
        prog, jaxpr = _step_jaxpr(exe, main, _ffn_feed(), fetch, scope)
    assert {k: n - before.get(k, 0) for k, n in after.items()} == {
        ('kept_bf16',): 2, ('widened_f32',): 1}
    assert 'fused_elementwise' in [op.type
                                   for op in prog.global_block().ops]
    fused, plain = [[np.asarray(a) for a in r] for r in (fused, plain)]
    assert [a.dtype for a in fused[:3]] == [np.float32, BF16, BF16]
    assert all(a.dtype == np.float32 for a in fused[3:])
    for a, b in zip(fused, plain):
        _same(a, b)

    eqns = list(_eqns(jaxpr.jaxpr))
    dots = [e for e in eqns if e.primitive.name == 'dot_general']
    assert len(dots) == 6       # two forward, two gradients each
    assert all(v.aval.dtype == BF16 for e in dots for v in e.invars)
    wide = [e for e in eqns if e.primitive.name != 'reshape' and any(
        v.aval.dtype == F32 and v.aval.size == _B * _T * _F
        for v in e.outvars)]
    assert sorted(e.primitive.name for e in wide) == [
        'add', 'convert_element_type', 'convert_element_type']
    ct = wide[-1]               # the backward's: cotangent of the cast
    assert ct.primitive.name == 'convert_element_type'
    assert ct.invars[0].aval.dtype == BF16
    readers = sorted(e.primitive.name for e in eqns
                     if ct.outvars[0] in e.invars)
    assert readers == ['convert_element_type', 'reduce_sum']


# ---- a bf16 projection into the recurrent kernels ---------------------------
def _rnn_step(kind, H=4):
    """fc -> dynamic_<kind> on a ragged batch, as the stacked-LSTM model
    builds it: [hidden, d loss / d fc weight]."""
    from paddle_tpu.lod import create_lod_tensor
    mult = {'lstm': 4, 'lstmp': 4, 'gru': 3}[kind]
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 5
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name='x', shape=[6], dtype='float32',
                              lod_level=1)
        proj = fluid.layers.fc(input=x, size=mult * H)
        if kind == 'lstm':
            h, _ = fluid.layers.dynamic_lstm(input=proj, size=4 * H)
        elif kind == 'lstmp':
            h, _ = fluid.layers.dynamic_lstmp(input=proj, size=4 * H,
                                              proj_size=3)
        else:
            h = fluid.layers.dynamic_gru(input=proj, size=H)
        loss = fluid.layers.mean(fluid.layers.sequence_pool(h, 'sum'))
        grads = fluid.backward.append_backward(loss)
    lens = [3, 2]
    rows = np.random.RandomState(2).randn(sum(lens), 6).astype('float32')
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        return exe.run(main, feed={'x': create_lod_tensor(rows, [lens])},
                       fetch_list=[h, grads[0][1]], return_numpy=False)


@pytest.mark.parametrize('kind', ['lstm', 'lstmp', 'gru'])
def test_recurrent_kernels_take_a_bf16_projection(kind, amp):
    """Under AMP the fc in front of a recurrent kernel now hands it bf16;
    the recurrence runs in its weights' float32 (one carry dtype), and
    agrees with the AMP-off step to bf16's rounding of the projection."""
    amp.set_amp(False)
    want = [np.asarray(getattr(a, 'data', a)) for a in _rnn_step(kind)]
    amp.set_amp(True)
    got = [np.asarray(getattr(a, 'data', a)) for a in _rnn_step(kind)]
    for g, w in zip(got, want):
        assert g.dtype == np.float32 and np.isfinite(g).all()
        np.testing.assert_allclose(g, w, atol=3e-2 * np.abs(w).max())


# ---- state that outlives one bf16 update ------------------------------------
def _while_memory(layers, x):
    """A While loop whose float32 memory is assigned a biased fc."""
    mem = layers.fill_constant_batch_size_like(x, shape=[-1, 8],
                                               dtype='float32', value=0.0)
    i = layers.fill_constant(shape=[1], dtype='int64', value=0)
    n = layers.fill_constant(shape=[1], dtype='int64', value=3)
    cond = layers.less_than(x=i, y=n)
    loop = layers.While(cond=cond)
    with loop.block():
        layers.assign(layers.fc(input=[x, mem], size=8, act='tanh'), mem)
        layers.increment(x=i, in_place=True)
        layers.less_than(x=i, y=n, cond=cond)
    return mem


def _static_rnn_memory(layers, x):
    """A StaticRNN (one step over the whole batch) whose float32 boot
    memory is updated by a biased fc."""
    steps = layers.reshape(x, shape=[1, -1, 8])
    rnn = layers.StaticRNN()
    with rnn.step():
        xt = rnn.step_input(steps)
        prev = rnn.memory(shape=[-1, 8], batch_ref=steps, init_value=0.0)
        h = layers.fc(input=[xt, prev], size=8, act='tanh')
        rnn.update_memory(prev, h)
        rnn.step_output(h)
    return rnn()


def _array_of_both(layers, x):
    """A tensor array first written float32, then a biased fc."""
    arr = layers.create_array('float32')
    at = [layers.fill_constant(shape=[1], dtype='int64', value=k)
          for k in (0, 1)]
    layers.array_write(x, i=at[0], array=arr)
    layers.array_write(layers.fc(input=x, size=8), i=at[1], array=arr)
    return layers.array_read(arr, i=at[0]) + layers.array_read(arr, i=at[1])


@pytest.mark.parametrize('build', [_while_memory, _static_rnn_memory,
                                   _array_of_both])
def test_float32_state_takes_a_bf16_update(build, amp):
    """State that is float32 before a bf16 activation reaches it stays
    float32: a While's carried variable, a StaticRNN's memory, a tensor
    array's buffer. The step lowers and runs under AMP (lax loops and
    dynamic_update_slice want one dtype) and agrees with the AMP-off
    step to bf16's rounding."""
    def run():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 11
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x = fluid.layers.data(name='x', shape=[8], dtype='float32')
            out = build(fluid.layers, x)
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            feed = {'x': np.random.RandomState(4).rand(3, 8)
                    .astype('float32')}
            got, = exe.run(main, feed=feed, fetch_list=[out],
                           return_numpy=False)
        return np.asarray(getattr(got, 'data', got), np.float32)

    amp.set_amp(False)
    want = run()
    amp.set_amp(True)
    got = run()
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=3e-2 * np.abs(want).max())
