"""The hard-label rule of softmax_with_cross_entropy
(ops/nn_ops.py::_lse_loss): the logits stay in the dtype they came in,
a row keeps its float32 lse, and the logits' gradient is one elementwise
pass. Against ``jax.nn.log_softmax`` + ``take_along_axis`` in float32,
which is what the op lowered to before and what autodiff turns into a
scatter into float32 zeros the size of the logits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.compiler.passes import loss_counts
from paddle_tpu.core.registry import get_kernel
from paddle_tpu.ops import nn_ops

BF16, F32 = jnp.bfloat16, jnp.float32


class _Ctx:
    """Just enough of OpCtx to drive the loss kernel directly."""

    def __init__(self, logits, label, attrs):
        self._i, self._a = {'Logits': logits, 'Label': label}, attrs
        self.out = {}

    def input(self, slot, idx=0):
        return self._i[slot]

    def attr(self, name, default=None):
        return self._a.get(name, default)

    def set_output(self, slot, val, idx=0):
        self.out[slot] = val


def _op(logits, label, **attrs):
    ctx = _Ctx(logits, label, attrs)
    get_kernel('softmax_with_cross_entropy')(ctx)
    return ctx.out['Loss'], ctx.out['Softmax']


def _reference(logits, label, soft_label=False):
    """The float32 definition, on the values the op was given."""
    logp = jax.nn.log_softmax(logits.astype(F32), axis=-1)
    if soft_label:
        loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
    else:
        idx = label.astype('int32').reshape(logits.shape[:-1])
        loss = -jnp.take_along_axis(logp, idx[..., None], axis=-1)
    return loss, jnp.exp(logp)


def _inputs(shape, dtype, label_tail, soft, seed=0):
    rng = np.random.RandomState(seed)
    logits = jnp.asarray(3.0 * rng.randn(*shape), F32).astype(dtype)
    if soft:
        lab = rng.random_sample(shape).astype('float32')
        return logits, jnp.asarray(lab / lab.sum(-1, keepdims=True))
    lab = rng.randint(0, shape[-1], shape[:-1] + label_tail)
    return logits, jnp.asarray(lab.astype('int64'), 'int32')


def _grads(fn, logits, label):
    """d/dlogits of sum(loss * w) and of sum(softmax * v) at fixed w, v."""
    rng = np.random.RandomState(7)
    w = jnp.asarray(rng.randn(*logits.shape[:-1], 1), F32)
    v = jnp.asarray(rng.randn(*logits.shape), F32)
    by_loss = jax.grad(lambda x: jnp.sum(fn(x, label)[0] * w))(logits)
    by_softmax = jax.grad(lambda x: jnp.sum(fn(x, label)[1] * v))(logits)
    return by_loss, by_softmax


@pytest.mark.parametrize('label_tail,soft',
                         [((1,), False), ((), False), ((), True)],
                         ids=['hard_n1', 'hard_n', 'soft'])
@pytest.mark.parametrize('dtype', [F32, BF16], ids=['f32', 'bf16'])
@pytest.mark.parametrize('shape', [(6, 37), (2, 5, 37)], ids=['2d', '3d'])
def test_rule_against_log_softmax(shape, dtype, label_tail, soft):
    """Loss, Softmax and both gradients are the float32 definition's:
    the same exp, sum and subtractions in float32, so they agree to
    float32 rounding; the logits' gradient is rounded once, to the
    logits' own dtype."""
    logits, label = _inputs(shape, dtype, label_tail, soft)
    got = _op(logits, label, soft_label=soft)
    want = _reference(logits, label, soft)
    for g, w in zip(got, want):
        assert g.dtype == F32 and g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=2e-6, atol=2e-6)
    got_g = _grads(lambda x, lab: _op(x, lab, soft_label=soft),
                   logits, label)
    want_g = _grads(lambda x, lab: _reference(x, lab, soft), logits, label)
    # bf16: a float32 ulp may flip the one rounding (2**-8 relative)
    tol = 2e-6 if dtype == F32 else 2 ** -7
    for g, w in zip(got_g, want_g):
        assert g.dtype == dtype and g.shape == logits.shape
        np.testing.assert_allclose(g.astype(F32), w.astype(F32),
                                   rtol=tol, atol=tol * 1e-2)


@pytest.mark.parametrize('shape', [(64, 37), (4, 16, 37)], ids=['2d', '3d'])
def test_bf16_gradient_through_both_outputs_rounds_once(shape):
    """A scalar of Loss and Softmax together: the two cotangents of the
    bf16 logits meet in float32 and are rounded once, at the widening's
    transpose, as ordinary autodiff of the float32 definition rounds
    them. Rounded apart and added in bf16 they differ from it in a
    third of the elements."""
    logits, label = _inputs(shape, BF16, (1,), False)
    rng = np.random.RandomState(5)
    w = jnp.asarray(rng.randn(*shape[:-1], 1), F32)
    v = jnp.asarray(rng.randn(*shape), F32)

    def both(fn):
        def scalar(x):
            loss, softmax = fn(x, label)
            return jnp.sum(loss * w) + jnp.sum(softmax * v)
        return jax.grad(scalar)(logits)

    got, want = both(_op), both(_reference)
    assert got.dtype == BF16
    got, want = np.asarray(got, 'float32'), np.asarray(want, 'float32')
    # a float32 ulp in x - lse may flip the rounding of a few
    assert np.mean(got == want) > 0.97
    np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=1e-6)


def test_negative_label_counts_from_the_end():
    """take_along_axis reads a negative index from the end; the loss's
    pick and the gradient's one-hot read it alike."""
    logits, label = _inputs((6, 37), F32, (1,), False)
    wrapped = jnp.where(label % 2 == 0, label - 37, label)
    assert np.all(np.isfinite(_op(logits, wrapped)[0]))
    np.testing.assert_array_equal(_op(logits, wrapped)[0],
                                  _op(logits, label)[0])
    np.testing.assert_array_equal(
        jax.grad(lambda x: jnp.sum(_op(x, wrapped)[0]))(logits),
        jax.grad(lambda x: jnp.sum(_op(x, label)[0]))(logits))


# what one elementwise pass over the logits is made of: XLA fuses these
# into one loop, so a float32 value of the logits' size inside them is
# never an array in memory
_ELEMENTWISE = {'convert_element_type', 'sub', 'exp', 'mul', 'eq', 'iota',
                'broadcast_in_dim'}


def _eqns(jaxpr):
    """Every equation, those of nested jaxprs too; a ``jit`` wrapper is
    read through, not counted."""
    for eqn in jaxpr.eqns:
        for p in eqn.params.values():
            sub = getattr(p, 'jaxpr', p)
            if hasattr(sub, 'eqns'):
                for e in _eqns(sub):
                    yield e
        if eqn.primitive.name not in ('jit', 'pjit'):
            yield eqn


def test_backward_holds_no_float32_logits_and_no_scatter():
    """Over bf16 [rows, vocab] logits nothing saved for the backward is
    a float32 array of rows x vocab elements, the backward has no
    scatter, and every equation of it that touches a float32 value of
    that size is elementwise (no reduce, reshape, transpose or gather
    of one: those are arrays in memory)."""
    rows, vocab = 16, 384
    logits, label = _inputs((rows, vocab), BF16, (1,), False)
    loss, pull = jax.vjp(lambda x: _op(x, label)[0], logits)

    def wide(aval):
        return (getattr(aval, 'size', 0) >= rows * vocab
                and aval.dtype == F32)

    saved = jax.tree_util.tree_leaves(pull)
    assert saved and not [r.shape for r in saved if wide(r)]
    assert any(r.dtype == BF16 and r.shape == (rows, vocab) for r in saved)
    assert any(r.dtype == F32 and r.shape == (rows, 1) for r in saved)
    back = jax.make_jaxpr(pull)(jnp.ones_like(loss))
    assert not [v.aval.shape for v in
                back.jaxpr.invars + back.jaxpr.constvars if wide(v.aval)]
    names = set()
    for eqn in _eqns(back.jaxpr):
        names.add(eqn.primitive.name)
        if any(wide(v.aval) for v in eqn.invars + eqn.outvars
               if hasattr(v, 'aval')):
            assert eqn.primitive.name in _ELEMENTWISE, eqn
    assert not [n for n in names if 'scatter' in n or 'reduce' in n]
    # the old lowering, as a check that the test can see the difference
    _, old = jax.vjp(lambda x: _reference(x, label)[0], logits)
    old_names = {e.primitive.name
                 for e in _eqns(jax.make_jaxpr(old)(jnp.ones_like(loss)).jaxpr)}
    assert any(wide(r) for r in jax.tree_util.tree_leaves(old))
    assert any('scatter' in n for n in old_names)


def _lowerings(build, feed, params):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        fetch = build()
    before = dict(loss_counts())
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        for name, value in params.items():
            fluid.global_scope().find_var(name).set(value)
        out = exe.run(main, feed=feed, fetch_list=fetch)
        exe.run(main, feed=feed, fetch_list=fetch)      # cached: no trace
    after = loss_counts()
    return out, {k: n - before.get(k, 0) for k, n in after.items()
                 if n != before.get(k, 0)}


@pytest.mark.parametrize('soft', [False, True], ids=['hard', 'soft'])
def test_loss_counts_through_the_fluid_path(soft):
    """A traced step program counts one lowering in loss_counts(); a
    soft-label lowering counts nothing. The trained
    program's loss and gradient are the float32 definition's, at 3-D
    logits as the language models give them."""
    rng = np.random.RandomState(11)
    x = rng.randn(2, 5, 8).astype('float32')
    if soft:
        lab = rng.random_sample((2, 5, 13)).astype('float32')
        lab /= lab.sum(-1, keepdims=True)
    else:
        lab = rng.randint(0, 13, (2, 5, 1)).astype('int64')
    w0 = (0.3 * rng.randn(8, 13)).astype('float32')

    def build():
        xv = fluid.layers.data(name='x', shape=[5, 8], dtype='float32')
        lv = fluid.layers.data(name='lab', shape=list(lab.shape[1:]),
                               dtype=str(lab.dtype))
        logits = fluid.layers.fc(
            input=xv, size=13, num_flatten_dims=2, bias_attr=False,
            param_attr='head_w')
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            logits=logits, label=lv, soft_label=soft))
        fluid.backward.append_backward(loss)
        return [loss, 'head_w@GRAD']

    (loss, grad), counted = _lowerings(build, {'x': x, 'lab': lab},
                                       {'head_w': w0})
    assert counted == ({} if soft else {(): 1})

    def ref(w):
        return jnp.mean(_reference(jnp.asarray(x) @ w, jnp.asarray(lab),
                                   soft)[0])
    np.testing.assert_allclose(np.ravel(loss)[0], ref(jnp.asarray(w0)),
                               rtol=1e-5)
    np.testing.assert_allclose(grad, jax.grad(ref)(jnp.asarray(w0)),
                               rtol=1e-4, atol=1e-6)


def test_row_lse_is_float32_whatever_comes():
    for dtype in (BF16, F32):
        x = jnp.asarray(np.random.RandomState(2).randn(4, 9), dtype)
        lse = nn_ops._row_lse(x)
        assert lse.dtype == F32 and lse.shape == (4, 1)
        np.testing.assert_allclose(
            lse[:, 0], jax.nn.logsumexp(x.astype(F32), axis=-1), rtol=1e-6)
