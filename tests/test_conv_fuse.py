"""conv_epilogue_fuse + the Pallas fused-conv epilogue kernel.

Pins the ISSUE 20 acceptance contract (COMPILER.md "Conv epilogue
fusion", PERF.md "Conv bandwidth"):

- fused-vs-unfused parity <= 1e-5 on every covered shape: conv+BN+ReLU,
  residual elementwise_add, depthwise conv, the SE-block excitation
  scale — with the Pallas kernel actually engaged (interpret mode on
  CPU), not just the exact replay;
- train-mode gradient parity through ``append_backward`` (the fused op
  differentiates via its custom_vjp against the jnp reference);
- pass idempotence: run(run(p)) == run(p);
- unsupported shapes (grouped non-depthwise convs) fall back COUNTED
  (``conv_fuse_fallbacks_total`` + a ``conv_fuse_fallback`` journal
  event naming the reason) and stay bit-exact — never silent, never
  wrong.
"""
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
import paddle_tpu.compiler as compiler
from paddle_tpu import observability as obs
from paddle_tpu.compiler.passes import FUSED_CONV_OP
from paddle_tpu.ops import pallas_kernels as pk

pytestmark = pytest.mark.compiler

TOL = 1e-5


@pytest.fixture(autouse=True)
def _compiler_defaults():
    """Default pass configuration, same contract as test_compiler."""
    compiler.set_enabled(True)
    compiler.set_default_passes(None)
    yield
    compiler.set_enabled(True)
    compiler.set_default_passes(None)


def _op_types(program):
    return [op.type for op in program.global_block().ops]


def _counter(name):
    """Total over every label set of a counter (fallbacks are counted
    by reason)."""
    fam = obs.default_registry().snapshot().get(name)
    return sum(s['value'] for s in fam['series']) if fam else 0


def _randomize_bn_stats(program, scope, rng):
    """Non-trivial BN stats/affine so folding errors can't hide behind
    identity parameters."""
    for op in program.global_block().ops:
        if op.type != 'batch_norm':
            continue
        c = scope.raw(op.inputs['Scale'][0]).shape[0]
        scope.set_var(op.inputs['Mean'][0],
                      rng.randn(c).astype('float32') * 0.3)
        scope.set_var(op.inputs['Variance'][0],
                      (rng.rand(c) + 0.5).astype('float32'))
        scope.set_var(op.inputs['Scale'][0],
                      (rng.rand(c) + 0.5).astype('float32'))
        scope.set_var(op.inputs['Bias'][0],
                      rng.randn(c).astype('float32') * 0.1)


def _parity_legs(build, feed, fetch_names, expect_fused=True):
    """Run the raw (compiler disabled) and fused (Pallas interpret)
    legs of one program in ONE scope with ONE startup run.

    The engagement hook is not part of the executor's jit cache key,
    so the force context must wrap the FIRST default-passes compile;
    the raw leg compiles under a different cache token
    (``compiler.disabled()``), so leg order is free. Returns
    (raw_outs, fused_outs, fused_delta, fallback_delta)."""
    main, startup, _ = build()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(7)
    with fluid.scope_guard(scope):
        exe.run(startup)
        _randomize_bn_stats(main, scope, rng)
        with compiler.disabled():
            raw = exe.run(main, feed=dict(feed), fetch_list=fetch_names)
        f0, b0 = (_counter('conv_fuse_ops_fused_total'),
                  _counter('conv_fuse_fallbacks_total'))
        with pk.force_conv_epilogue('interpret'):
            fused = exe.run(main, feed=dict(feed),
                            fetch_list=fetch_names)
    fused_d = _counter('conv_fuse_ops_fused_total') - f0
    if expect_fused:
        assert fused_d > 0, 'conv_epilogue_fuse fused nothing'
    return ([np.asarray(v) for v in raw],
            [np.asarray(v) for v in fused],
            fused_d, _counter('conv_fuse_fallbacks_total') - b0)


# ---- covered-shape exactness ----------------------------------------------

def _build_conv_bn_relu():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name='x', shape=[3, 8, 8],
                                  dtype='float32')
            c = fluid.layers.conv2d(input=x, num_filters=4, filter_size=3,
                                    padding=1, bias_attr=False)
            b = fluid.layers.batch_norm(input=c, is_test=True)
            out = fluid.layers.relu(b)
    return main, startup, out


def test_conv_bn_relu_pallas_parity():
    feed = {'x': np.random.RandomState(0).randn(
        2, 3, 8, 8).astype('float32')}
    main, _, out = _build_conv_bn_relu()
    raw, fused, _, falls = _parity_legs(_build_conv_bn_relu, feed,
                                        [out.name])
    assert falls == 0, 'Pallas lowering rejected a supported shape'
    err = np.max(np.abs(raw[0] - fused[0]))
    assert err <= TOL, 'fused conv+BN+ReLU drifted %g > %g' % (err, TOL)
    # the optimized program really carries a fused_conv op
    optimized, _ = compiler.optimize(main, fetch_names=[out.name])
    assert FUSED_CONV_OP in _op_types(optimized)
    assert 'batch_norm' not in _op_types(optimized)


@pytest.mark.parametrize('hw,k,s,p,dw', [
    ((8, 8), 1, 2, 0, False), ((7, 9), 1, 2, 0, False),
    ((8, 8), 3, 2, 1, False), ((7, 9), 3, 2, 1, False),
    ((9, 7), 3, 2, 0, False), ((12, 12), 5, 3, 2, False),
    ((8, 8), 3, 2, 1, True)])
def test_strided_kernel_matches_plain_conv(hw, k, s, p, dw):
    """Strides are taken outside the kernel, as stride phases of the
    padded input: every tap of every phase must land where a plain
    strided conv puts it — odd extents, k > s and k < s included — for
    the output and for the train-BN moment partials."""
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(3)
    n, cin, cout = 2, 4, (4 if dw else 8)
    x = jnp.asarray(rng.randn(n, hw[0], hw[1], cin), jnp.float32)
    w = jnp.asarray(rng.randn(*((k, k, cin) if dw
                                else (k, k, cin, cout))), jnp.float32)
    want = jax.lax.conv_general_dilated(
        x, w[:, :, None, :] if dw else w, (s, s), [(p, p), (p, p)],
        feature_group_count=cin if dw else 1,
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'),
        precision=jax.lax.Precision.HIGHEST)
    got, why = pk.fused_conv_epilogue(x, w, (), (), (s, s), (p, p), dw,
                                      (), emit_stats=True, interpret=True)
    assert why is None
    y, psum, psumsq = got
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(psum.sum((0, 1)), want.sum((0, 1, 2)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(psumsq.sum((0, 1)),
                               (want * want).sum((0, 1, 2)),
                               rtol=1e-4, atol=1e-4)


def test_tiled_vmem_bytes_count_lanes_and_sublanes():
    """The VMEM predicate counts what Mosaic allocates: 128 lanes and
    whole sublane tiles, so narrow channel counts are not cheap."""
    assert pk._tiled_bytes((58, 58, 3), 'float32') == 58 * 64 * 128 * 4
    assert pk._tiled_bytes((58, 58, 3), 'bfloat16') == 58 * 64 * 128 * 2
    assert pk._tiled_bytes((1, 256), 'float32') == 8 * 256 * 4


def test_hardware_predicates_refuse_by_reason():
    """What Mosaic refuses is declined up front, by name (PERF.md
    "Bring-up on the chip"): bf16 row merges with an odd width and
    partial lanes, and blocks past the scoped-VMEM limit."""
    import jax.numpy as jnp

    def why(x_shape, w_shape, dtype):
        return pk.fused_conv_epilogue(
            jnp.zeros(x_shape, dtype), jnp.zeros(w_shape, dtype), (), (),
            (1, 1), (w_shape[0] // 2,) * 2, False, ())[1]

    assert why((2, 7, 7, 64), (1, 1, 64, 128), jnp.bfloat16) == \
        'packed-row-merge'
    assert why((2, 7, 7, 64), (1, 1, 64, 64), jnp.float32) == \
        'channel-align'
    assert why((2, 112, 112, 256), (3, 3, 256, 256), jnp.float32) == 'vmem'


def test_residual_add_parity():
    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 9
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                x = fluid.layers.data(name='x', shape=[4, 8, 8],
                                      dtype='float32')
                c = fluid.layers.conv2d(input=x, num_filters=4,
                                        filter_size=3, padding=1,
                                        bias_attr=False)
                b = fluid.layers.batch_norm(input=c, is_test=True)
                s = fluid.layers.elementwise_add(b, x)   # residual tensor
                out = fluid.layers.relu(s)
        return main, startup, out

    feed = {'x': np.random.RandomState(1).randn(
        2, 4, 8, 8).astype('float32')}
    _, _, out = build()
    raw, fused, _, falls = _parity_legs(build, feed, [out.name])
    assert falls == 0
    err = np.max(np.abs(raw[0] - fused[0]))
    assert err <= TOL, 'fused residual-add drifted %g' % err


def test_depthwise_conv_parity():
    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 11
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                x = fluid.layers.data(name='x', shape=[4, 8, 8],
                                      dtype='float32')
                c = fluid.layers.conv2d(input=x, num_filters=4,
                                        filter_size=3, padding=1,
                                        groups=4, bias_attr=False)
                b = fluid.layers.batch_norm(input=c, is_test=True)
                out = fluid.layers.relu(b)
        return main, startup, out

    feed = {'x': np.random.RandomState(2).randn(
        2, 4, 8, 8).astype('float32')}
    _, _, out = build()
    raw, fused, _, falls = _parity_legs(build, feed, [out.name])
    assert falls == 0, 'depthwise path fell back instead of engaging'
    err = np.max(np.abs(raw[0] - fused[0]))
    assert err <= TOL, 'fused depthwise drifted %g' % err


def test_se_block_excitation_parity():
    """The se_resnext pattern: a [N, C] excitation scales the conv
    output per channel (elementwise_mul axis=0 -> 'nc' aux)."""
    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 13
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                x = fluid.layers.data(name='x', shape=[3, 8, 8],
                                      dtype='float32')
                se = fluid.layers.data(name='se', shape=[4],
                                       dtype='float32')
                c = fluid.layers.conv2d(input=x, num_filters=4,
                                        filter_size=3, padding=1,
                                        bias_attr=False)
                b = fluid.layers.batch_norm(input=c, is_test=True)
                s = fluid.layers.elementwise_mul(b, se, axis=0)
                out = fluid.layers.relu(s)
        return main, startup, out

    rng = np.random.RandomState(3)
    feed = {'x': rng.randn(2, 3, 8, 8).astype('float32'),
            'se': (rng.rand(2, 4) + 0.25).astype('float32')}
    _, _, out = build()
    raw, fused, _, falls = _parity_legs(build, feed, [out.name])
    assert falls == 0
    err = np.max(np.abs(raw[0] - fused[0]))
    assert err <= TOL, 'fused SE excitation drifted %g' % err


# ---- train mode -----------------------------------------------------------

def test_train_mode_bn_loss_and_grad_parity():
    """Train-mode BN rides the fused op (moment partials emitted by
    the kernel) and gradients flow through the custom_vjp: loss AND
    conv-weight grads match the unfused program via append_backward."""
    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 17
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                x = fluid.layers.data(name='x', shape=[3, 8, 8],
                                      dtype='float32')
                c = fluid.layers.conv2d(input=x, num_filters=4,
                                        filter_size=3, padding=1,
                                        bias_attr=False)
                b = fluid.layers.batch_norm(input=c)    # train mode
                r = fluid.layers.relu(b)
                loss = fluid.layers.mean(r)
                grads = fluid.backward.append_backward(loss)
        return main, startup, (loss, grads)

    main, _, (loss, grads) = build()
    gnames = [g.name for _, g in grads]
    feed = {'x': np.random.RandomState(4).randn(
        2, 3, 8, 8).astype('float32')}
    raw, fused, _, falls = _parity_legs(
        build, feed, [loss.name] + gnames)
    assert falls == 0
    for name, rv, fv in zip(['loss'] + gnames, raw, fused):
        err = np.max(np.abs(rv - fv))
        assert err <= TOL, '%s drifted %g in train mode' % (name, err)


# ---- idempotence ----------------------------------------------------------

def test_conv_epilogue_fuse_idempotent():
    main, _, out = _build_conv_bn_relu()
    once, _ = compiler.optimize(main, fetch_names=[out.name])
    twice, _ = compiler.optimize(once, fetch_names=[out.name])
    assert _op_types(once) == _op_types(twice)
    assert _op_types(once).count(FUSED_CONV_OP) == 1


# ---- fallback accounting --------------------------------------------------

def test_grouped_conv_falls_back_counted_and_exact(tmp_path):
    """A grouped non-depthwise conv is fused by the pass but rejected
    by the lowering: the replay must be bit-exact AND visible — one
    counter tick plus a journal event naming reason='groups'."""
    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 19
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                x = fluid.layers.data(name='x', shape=[4, 8, 8],
                                      dtype='float32')
                c = fluid.layers.conv2d(input=x, num_filters=8,
                                        filter_size=3, padding=1,
                                        groups=2, bias_attr=False)
                b = fluid.layers.batch_norm(input=c, is_test=True)
                out = fluid.layers.relu(b)
        return main, startup, out

    feed = {'x': np.random.RandomState(5).randn(
        2, 4, 8, 8).astype('float32')}
    _, _, out = build()
    journal = str(tmp_path / 'fallback.jsonl')
    with obs.journal(journal):
        raw, fused, _, falls = _parity_legs(build, feed, [out.name])
    assert falls == 1, 'expected exactly one counted fallback'
    assert np.array_equal(raw[0], fused[0]), \
        'fallback replay must be bit-exact'
    records, malformed = obs.read_journal(journal)
    assert malformed == 0
    events = [r for r in records if r['ev'] == 'conv_fuse_fallback']
    assert len(events) == 1
    assert events[0]['reason'] == 'groups'
    assert 'conv2d' in events[0]['types']
