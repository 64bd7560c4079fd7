"""paddle_tpu.compiler — the program-level optimizing pass pipeline.

Pins the PR-6 acceptance contract (COMPILER.md):

- semantic equivalence on three book-style programs (MLP fit-a-line,
  conv+BN recognize_digits-style, elementwise chains): bit-identical
  where passes are exact; <= 1e-5 drift for BN folding;
- the canonical pipeline demonstrably rewrites programs (op counts
  drop, BN ops vanish, >= 1 elementwise chain lowers as ONE fused
  kernel, asserted via program introspection);
- pass idempotence: run(run(p)) == run(p) for every registered pass;
- Executor cache keying includes the compiler config: a toggle forces
  exactly one recompile and toggling back reuses the original program.
"""
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
import paddle_tpu.compiler as compiler
from paddle_tpu.compiler.pass_base import PassContext
from paddle_tpu.compiler.passes import FUSED_ELEMENTWISE_OP

pytestmark = pytest.mark.compiler


@pytest.fixture(autouse=True)
def _compiler_defaults():
    """Every test starts from the default config."""
    compiler.set_enabled(True)
    compiler.set_default_passes(None)
    yield
    compiler.set_enabled(True)
    compiler.set_default_passes(None)


def _op_types(program):
    return [op.type for op in program.global_block().ops]


# ---- program builders (the equivalence suite) -----------------------------------

def _build_mlp():
    """fit-a-line-style MLP with a training step."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[13], dtype='float32')
        h = fluid.layers.fc(input=x, size=16, act='relu')
        y_predict = fluid.layers.fc(input=h, size=1, act=None)
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        cost = fluid.layers.square_error_cost(input=y_predict, label=y)
        avg_cost = fluid.layers.mean(cost)
        fluid.optimizer.SGD(learning_rate=0.01).minimize(avg_cost)
    return main, startup, avg_cost


def _build_conv_bn(layers=2):
    """recognize_digits-conv-style inference net: conv+BN+relu blocks."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[3, 8, 8], dtype='float32')
        t = x
        for _ in range(layers):
            c = fluid.layers.conv2d(input=t, num_filters=4,
                                    filter_size=3, padding=1,
                                    bias_attr=False)
            b = fluid.layers.batch_norm(input=c, is_test=True)
            t = fluid.layers.relu(b)
        out = fluid.layers.reduce_mean(t) if hasattr(
            fluid.layers, 'reduce_mean') else fluid.layers.mean(t)
    return main, startup, out


def _randomize_bn_stats(program, scope, rng):
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


def _build_chain():
    """Elementwise chain + constant subgraph + dead branch."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[16], dtype='float32')
        c1 = fluid.layers.fill_constant(shape=[16], dtype='float32',
                                        value=2.0)
        c2 = fluid.layers.fill_constant(shape=[16], dtype='float32',
                                        value=3.0)
        c3 = fluid.layers.elementwise_mul(c1, c2)
        t = fluid.layers.scale(x, scale=2.0)
        t = fluid.layers.relu(t)
        t = fluid.layers.elementwise_add(t, c3)
        out = fluid.layers.tanh(t)
        fluid.layers.scale(x, scale=5.0)       # dead: never fetched
    return main, startup, out


# ---- semantic equivalence -------------------------------------------------------

def test_mlp_training_bit_identical_optimized_vs_raw():
    rng = np.random.RandomState(0)
    xv = rng.randn(16, 13).astype('float32')
    yv = rng.randn(16, 1).astype('float32')
    main, startup, avg_cost = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    losses = {}
    for enabled in (True, False):
        compiler.set_enabled(enabled)
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            run = []
            for _ in range(5):
                l, = exe.run(main, feed={'x': xv, 'y': yv},
                             fetch_list=[avg_cost.name])
                run.append(np.asarray(l).item())
        losses[enabled] = run
    assert losses[True] == losses[False]          # bit-identical
    assert losses[True][-1] < losses[True][0]     # still trains


def test_chain_program_bit_identical_and_op_count_drops():
    main, startup, out = _build_chain()
    xs = np.random.RandomState(1).randn(4, 16).astype('float32')
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        with compiler.disabled():
            raw, = exe.run(main, feed={'x': xs}, fetch_list=[out.name])
        opt, = exe.run(main, feed={'x': xs}, fetch_list=[out.name])
    assert np.array_equal(np.asarray(raw), np.asarray(opt))

    optimized, results = compiler.optimize(main,
                                           fetch_names=[out.name])
    n_before = len(main.global_block().ops)
    n_after = len(optimized.global_block().ops)
    assert n_after < n_before
    by_name = {r.pass_name: r for r in results}
    assert by_name['constant_fold'].ops_folded >= 1
    assert by_name['dead_op_elim'].ops_removed >= 1


def test_conv_bn_fold_removes_all_bn_within_tolerance():
    rng = np.random.RandomState(0)
    xs = rng.randn(2, 3, 8, 8).astype('float32')
    main, startup, out = _build_conv_bn(layers=2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        _randomize_bn_stats(main, scope, rng)
        with compiler.disabled():
            before, = exe.run(main, feed={'x': xs},
                              fetch_list=[out.name])
        n_bn = _op_types(main).count('batch_norm')
        assert n_bn == 2
        # in place (clone=False): bn_fold rewrites the scope weights,
        # so the program must lose its BN ops in the same stroke
        optimized, _ = compiler.optimize_inference(
            main, scope=scope, fetch_names=[out.name])
        assert optimized is main
        assert 'batch_norm' not in _op_types(main)
        with compiler.disabled():
            after, = exe.run(main, feed={'x': xs},
                             fetch_list=[out.name])
    np.testing.assert_allclose(np.asarray(before), np.asarray(after),
                               rtol=1e-4, atol=1e-5)


def test_elementwise_chain_lowers_as_single_fused_kernel():
    main, startup, out = _build_chain()
    optimized, results = compiler.optimize(main,
                                           fetch_names=[out.name])
    types = _op_types(optimized)
    assert types.count(FUSED_ELEMENTWISE_OP) == 1
    fused = [op for op in optimized.global_block().ops
             if op.type == FUSED_ELEMENTWISE_OP][0]
    # the whole scale->relu->add->tanh chain is ONE kernel
    assert fused.attrs['fused_count'] >= 4
    assert fused.attrs['fused_types'] == ['scale', 'relu',
                                          'elementwise_add', 'tanh']
    xs = np.random.RandomState(2).randn(3, 16).astype('float32')
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        with compiler.disabled():
            raw, = exe.run(main, feed={'x': xs}, fetch_list=[out.name])
            opt, = exe.run(optimized, feed={'x': xs},
                           fetch_list=[out.name])
    assert np.array_equal(np.asarray(raw), np.asarray(opt))


def test_buffer_reuse_annotations_and_training_unchanged():
    main, startup, avg_cost = _build_mlp()
    from paddle_tpu.transpiler import memory_optimize
    optimized = main.clone()
    memory_optimize(optimized)
    released = [op.attrs['__release__']
                for op in optimized.global_block().ops
                if '__release__' in op.attrs]
    assert released, 'liveness pass annotated nothing'
    # fetch name must be releasable-guarded at LOWERING, not the pass:
    # training through the annotated program matches the original
    rng = np.random.RandomState(0)
    xv = rng.randn(8, 13).astype('float32')
    yv = rng.randn(8, 1).astype('float32')
    exe = fluid.Executor(fluid.CPUPlace())
    losses = {}
    for prog in (main, optimized):
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            losses[prog is optimized] = [
                np.asarray(exe.run(prog, feed={'x': xv, 'y': yv},
                                   fetch_list=[avg_cost.name])[0]).item()
                for _ in range(3)]
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-6)


# ---- pass idempotence -----------------------------------------------------------

def _program_for_pass(name):
    if name == 'bn_fold':
        main, startup, out = _build_conv_bn()
    else:
        main, startup, out = _build_chain()
    return main, startup, out


@pytest.mark.parametrize('pass_name', compiler.registered_passes())
def test_pass_idempotence(pass_name):
    main, startup, out = _program_for_pass(pass_name)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        if pass_name == 'bn_fold':
            _randomize_bn_stats(main, scope,
                                np.random.RandomState(0))
        p = compiler.get_pass(pass_name)
        assert p.idempotent
        p.run(main, PassContext(scope=scope,
                                protected=frozenset([out.name])))
        fp1 = main.fingerprint()
        second = p.run(main, PassContext(scope=scope,
                                         protected=frozenset([out.name])))
        assert not second.changed
        assert main.fingerprint() == fp1


# ---- cache keying ---------------------------------------------------------------

def test_toggle_forces_exactly_one_recompile():
    main, startup, out = _build_chain()
    xs = np.random.RandomState(3).randn(2, 16).astype('float32')
    exe = fluid.Executor(fluid.CPUPlace())
    exe.reset_cache_info()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(main, feed={'x': xs}, fetch_list=[out.name])
        base = exe.cache_info()
        exe.run(main, feed={'x': xs}, fetch_list=[out.name])
        assert exe.cache_info().misses == base.misses      # steady: hit

        compiler.set_enabled(False)
        exe.run(main, feed={'x': xs}, fetch_list=[out.name])
        after_toggle = exe.cache_info()
        assert after_toggle.misses == base.misses + 1      # exactly one
        exe.run(main, feed={'x': xs}, fetch_list=[out.name])
        assert exe.cache_info().misses == after_toggle.misses

        # toggling BACK must reuse the originally compiled program
        compiler.set_enabled(True)
        exe.run(main, feed={'x': xs}, fetch_list=[out.name])
        assert exe.cache_info().misses == after_toggle.misses


def test_pass_list_change_is_a_cache_dimension():
    main, startup, out = _build_chain()
    xs = np.random.RandomState(4).randn(2, 16).astype('float32')
    exe = fluid.Executor(fluid.CPUPlace())
    exe.reset_cache_info()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(main, feed={'x': xs}, fetch_list=[out.name])
        m0 = exe.cache_info().misses
        compiler.set_default_passes(['dead_op_elim'])
        exe.run(main, feed={'x': xs}, fetch_list=[out.name])
        assert exe.cache_info().misses == m0 + 1
        # the pass names are the compiler's whole share of the key:
        # the default list again is the first program again
        compiler.set_default_passes(None)
        exe.run(main, feed={'x': xs}, fetch_list=[out.name])
        assert exe.cache_info().misses == m0 + 1
