"""ZeRO-2 data-parallel trainer (ISSUE 10, PERF.md "ZeRO-2 and
collective overlap"): reduce-scatter in the backward, sharded optimizer
step, collective/compute overlap.

Pins the acceptance contracts on the 8-virtual-CPU-device mesh the
conftest provisions:

- dp=2 ZeRO-2 losses, params AND Adam moments are BIT-identical to the
  replicated dp=2 path, and match single-device at the same global
  batch (the existing partition-suite tolerance);
- bucketing boundaries: one tensor larger than the cap gets its own
  bucket, many tiny tensors share one, an exact cap multiple closes at
  the boundary;
- ``run_chained`` K=2 through the ZeRO tail is bit-exact vs sequential
  sharded steps (the collective rides inside the scan body);
- the bucketed gradient-tail collective under a bound dp axis lowers
  to a literal ``reduce-scatter`` HLO (no all-reduce) and returns the
  owner shards exactly; under jit-SPMD the lowered step shows the
  SHARDED update (partition-local slices + parameter all-gather) and
  smaller per-device argument bytes;
- per-tensor eligibility: a non-divisible accumulator/grad falls back
  to replicated alone, never dragging the rest of the state with it;
- ZeRO-2 is the ParallelExecutor default on a dp mesh; ``zero_stage=0``
  opts out; application is idempotent;
- telemetry: ``zero`` journal events, ``zero_grad_shard_bytes``,
  ``collective_seconds{op=}``, ``obs_report --require zero`` gate.
"""
import os
import re
import sys

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu import unique_name
from paddle_tpu.compiler import zero as zmod
from paddle_tpu.partition import Partitioner

pytestmark = pytest.mark.zero

TOOLS = os.path.join(os.path.dirname(__file__), '..', 'tools')
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)

import obs_report  # noqa: E402


def _mesh(n):
    devs = jax.devices()
    assert len(devs) >= n
    return Mesh(np.asarray(devs[:n]), ('dp',))


def _build(seed=7, dropout=True, sizes=(16,)):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup), unique_name.guard():
        x = fluid.layers.data(name='x', shape=[8], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        h = x
        for s in sizes:
            h = fluid.layers.fc(input=h, size=s, act='relu')
        if dropout:
            h = fluid.layers.dropout(h, dropout_prob=0.3)
        pred = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    return main, startup, loss


def _feeds(n=5, batch=32, seed=0):
    rng = np.random.RandomState(seed)
    return [{'x': rng.randn(batch, 8).astype('float32'),
             'y': rng.randn(batch, 1).astype('float32')}
            for _ in range(n)]


def _snapshot(scope):
    from paddle_tpu.core.lowering import RNG_KEY
    return {n: np.asarray(scope.raw(n)) for n in sorted(scope.keys())
            if n != RNG_KEY and scope.raw(n) is not None
            and hasattr(scope.raw(n), 'shape')}


def _run(zero_stage, mesh_n, chained=0, feeds=None, dropout=True,
         bucket_bytes=None):
    feeds = feeds if feeds is not None else _feeds()
    main, startup, loss = _build(dropout=dropout)
    scope = fluid.Scope()
    part = Partitioner(mesh=_mesh(mesh_n))
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
        pe = fluid.ParallelExecutor(
            use_cuda=False, loss_name=loss.name, main_program=main,
            partitioner=part, zero_stage=zero_stage,
            zero_bucket_bytes=bucket_bytes)
        if chained:
            losses = []
            for i in range(0, len(feeds), chained):
                outs = pe.run_chained(feed_list=feeds[i:i + chained],
                                      fetch_list=[loss.name])
                losses += [float(np.asarray(o[0]).ravel()[0])
                           for o in outs]
        else:
            losses = [float(np.asarray(
                pe.run(feed=f, fetch_list=[loss.name])[0]).ravel()[0])
                for f in feeds]
        snap = _snapshot(scope)
    return losses, snap, main


# ---- bucket planning -----------------------------------------------------
def test_plan_buckets_boundaries():
    cap = 100
    # one tensor larger than the cap: its own bucket, neighbours intact
    assert zmod.plan_buckets([10, 250, 10], cap) == [[0], [1], [2]]
    # many tiny tensors coalesce into ONE bucket under the cap
    assert zmod.plan_buckets([10] * 9, cap) == [list(range(9))]
    # an exact cap multiple closes the bucket at the boundary
    assert zmod.plan_buckets([50, 50, 50, 50], cap) == [[0, 1], [2, 3]]
    # cap reached mid-stream: greedy split, order preserved
    assert zmod.plan_buckets([60, 60, 60], cap) == [[0], [1], [2]]
    # everything covered exactly once, in order
    for sizes in ([1], [100], [101], list(range(1, 30))):
        got = zmod.plan_buckets(sizes, cap)
        assert sorted(i for b in got for i in b) == \
            list(range(len(sizes)))


# ---- tentpole: bit-exactness --------------------------------------------
def test_dp2_zero_bit_identical_to_replicated_and_single():
    feeds = _feeds()
    l_rep, s_rep, _ = _run(0, 2, feeds=feeds)
    l_zero, s_zero, prog = _run(2, 2, feeds=feeds)
    # the ZeRO-2 tail really is in the program
    zops = [op for op in prog.global_block().ops
            if op.type == 'zero_reduce_scatter']
    assert zops, 'no zero_reduce_scatter ops planted'
    # losses AND every persistable (params, Adam moments, beta pows)
    # are the replicated dp=2 path's. The ZeRO program reduce-scatters
    # each gradient bucket and all-gathers the updated slices where the
    # replicated one all-reduces, so XLA compiles two different
    # programs and may fuse and order their float32 sums differently:
    # one rounding step was observed (fc_0.w_0, 11 of 128 elements,
    # 1.8e-7 relative), and no more is allowed
    np.testing.assert_allclose(l_zero, l_rep, rtol=1e-6, atol=1e-7)
    assert sorted(s_zero) == sorted(s_rep)
    for n in s_rep:
        np.testing.assert_allclose(s_rep[n], s_zero[n], rtol=1e-6,
                                   atol=1e-7, err_msg=n)
    # and matches single-device at the same global batch (the
    # partition-suite tolerance: XLA re-associates the batch sum)
    l_one, _, _ = _run(0, 1, feeds=feeds)
    np.testing.assert_allclose(l_zero, l_one, rtol=1e-4, atol=1e-5)


def test_run_chained_k2_zero_parity():
    feeds = _feeds(6)
    l_seq, s_seq, _ = _run(2, 2, feeds=feeds)
    l_ch, s_ch, _ = _run(2, 2, chained=2, feeds=feeds)
    assert l_ch == l_seq
    for n in s_seq:
        np.testing.assert_array_equal(s_seq[n], s_ch[n], err_msg=n)


def test_tiny_bucket_cap_many_buckets_same_result():
    """bucket_bytes below every tensor: one bucket per gradient —
    results stay bit-identical (the cap is a perf knob, not a
    semantic one)."""
    feeds = _feeds(3)
    l_one, s_one, p_one = _run(2, 2, feeds=feeds)
    l_many, s_many, p_many = _run(2, 2, feeds=feeds, bucket_bytes=1)
    n_one = sum(1 for op in p_one.global_block().ops
                if op.type == 'zero_reduce_scatter')
    n_many = sum(1 for op in p_many.global_block().ops
                 if op.type == 'zero_reduce_scatter')
    assert n_many > n_one >= 1
    assert l_many == l_one
    for n in s_one:
        np.testing.assert_array_equal(s_one[n], s_many[n], err_msg=n)


# ---- per-tensor fallback -------------------------------------------------
def test_per_tensor_replicated_fallback():
    """A tensor no dim of which divides dp falls back to replicated
    ALONE; the rest of the state still slices (satellite: the whole
    state dict must never be hostage to one odd tensor)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), unique_name.guard():
        x = fluid.layers.data(name='x', shape=[9], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        h = fluid.layers.fc(input=x, size=15, act='relu')  # (9,15): odd
        h2 = fluid.layers.fc(input=h, size=16, act='relu')  # (15,16)
        pred = fluid.layers.fc(input=h2, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    summary = zmod.apply_zero(main, dp=2)
    assert summary['sliced'] and summary['replicated']
    block = main.global_block()
    for name in summary['replicated_names']:
        assert block._find_var_recursive(name).sharding is None
    for name in summary['sliced_names']:
        spec = block._find_var_recursive(name).sharding
        assert spec and spec[-1] == 'dp'
    # the odd fc's weight moments are among the replicated fallbacks
    assert any('fc_0.w_0_moment' in n
               for n in summary['replicated_names'])
    # grads of the odd weight are NOT in any bucket; divisible ones are
    bucketed = [n for op in block.ops
                if op.type == 'zero_reduce_scatter'
                for n in op.inputs['X']]
    assert 'fc_0.w_0@GRAD' not in bucketed
    assert 'fc_1.w_0@GRAD' in bucketed
    # idempotent: a second application changes nothing
    v0 = main._version
    again = zmod.apply_zero(main, dp=2)
    assert main._version == v0 and again['buckets'] == 0


# ---- defaults ------------------------------------------------------------
def test_zero_default_on_dp_mesh_and_opt_out():
    main, _startup, loss = _build(dropout=False)
    pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                main_program=main,
                                partitioner=Partitioner(mesh=_mesh(2)))
    assert pe._zero['stage'] == 2 and pe._zero['dp'] == 2
    assert any(op.type == 'zero_reduce_scatter'
               for op in main.global_block().ops)
    main2, _s2, loss2 = _build(dropout=False)
    pe2 = fluid.ParallelExecutor(use_cuda=False, loss_name=loss2.name,
                                 main_program=main2,
                                 partitioner=Partitioner(mesh=_mesh(2)),
                                 zero_stage=0)
    assert pe2._zero['stage'] == 0
    assert not any(op.type == 'zero_reduce_scatter'
                   for op in main2.global_block().ops)
    # 1-device mesh: structural no-op even at the default stage
    main3, _s3, loss3 = _build(dropout=False)
    fluid.ParallelExecutor(use_cuda=False, loss_name=loss3.name,
                           main_program=main3,
                           partitioner=Partitioner(mesh=_mesh(1)))
    assert not any(op.type == 'zero_reduce_scatter'
                   for op in main3.global_block().ops)


# ---- HLO: the collectives -----------------------------------------------
def test_manual_bucket_collective_is_literal_reduce_scatter():
    """Under a bound dp axis (shard_map) the bucketed gradient tail is
    a REAL psum_scatter: reduce-scatter in the compiled HLO, NO
    all-reduce, and each device gets exactly its owner shard of the
    summed partial gradients."""
    from jax.sharding import PartitionSpec as P
    mesh = _mesh(2)
    rng = np.random.RandomState(0)
    # per-device PARTIAL grads, stacked on a leading device axis
    g1 = rng.randn(2, 8, 6).astype('f4')     # shard dim 0
    g2 = rng.randn(2, 3, 4).astype('f4')     # shard dim 1

    def body(a, b):
        outs = zmod.bucket_reduce_scatter([a[0], b[0]], [0, 1], dp=2,
                                          manual=True)
        return outs[0][None], outs[1][None]

    f = jax.jit(jax.shard_map(body, mesh=mesh,
                              in_specs=(P('dp'), P('dp')),
                              out_specs=(P('dp'), P('dp')),
                              check_vma=False))
    o1, o2 = f(g1, g2)
    txt = f.lower(g1, g2).compile().as_text()
    assert len(re.findall('reduce-scatter', txt)) >= 1
    assert len(re.findall('all-reduce', txt)) == 0
    # owner shards of the cross-replica sum, exactly
    want1 = g1.sum(0)                        # full summed gradient
    want2 = g2.sum(0)
    np.testing.assert_array_equal(np.asarray(o1).reshape(8, 6), want1)
    # shard dim 1: device r owns columns [2r:2r+2] of the sum
    np.testing.assert_array_equal(
        np.asarray(o2), np.stack([want2[:, :2], want2[:, 2:]]))


def test_spmd_step_hlo_shows_sharded_update_and_smaller_state():
    """jit-SPMD dialect (the product executors): XLA owns the gradient
    reduction — on this CPU backend it folds the reduce-scatter into
    all-reduce + partition-local slices (TPU/GPU pipelines emit the
    reduce-scatter HLO) — and the UPDATE provably runs on shards:
    partition-id-based slicing feeds the update and the new parameter
    shards all-gather back; per-device argument bytes shrink by the
    sliced state."""
    feed = {'x': np.zeros((32, 8), 'f4'), 'y': np.zeros((32, 1), 'f4')}

    def stats_and_hlo(stage):
        main, startup, loss = _build(dropout=False, sizes=(64, 64))
        scope = fluid.Scope()
        part = Partitioner(mesh=_mesh(2))
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            pe = fluid.ParallelExecutor(
                use_cuda=False, loss_name=loss.name, main_program=main,
                partitioner=part, zero_stage=stage)
            st = pe.compile_stats([loss.name], dict(feed))
            from paddle_tpu.core.lowering import lower_block
            fetch, pfeed, s_in, s_out, senv = exe._prep_lowering(
                main, dict(feed), [loss.name], scope)
            fn = lower_block(main, main.global_block(),
                             sorted(pfeed.keys()), fetch, s_in, s_out,
                             static_env=senv)
            jitted = part.partition(
                part.trace_wrap(fn),
                in_shardings=(part.feed_shardings(pfeed),
                              part.state_shardings(main, s_in)),
                out_shardings=(part.replicated,
                               part.state_shardings(main, s_out)))
            state = {n: scope.raw(n) for n in s_in}
            with part.run_context():
                txt = jitted.lower(pfeed, state).compile().as_text()
        return st, txt

    st0, t0 = stats_and_hlo(0)
    st2, t2 = stats_and_hlo(2)
    assert 'all-gather' not in t0 and 'partition-id' not in t0
    assert len(re.findall('all-gather', t2)) >= 1        # param regather
    assert len(re.findall('partition-id', t2)) >= 1      # shard select
    assert st2['argument_bytes'] < st0['argument_bytes']


# ---- grad_shard_spec agreement ------------------------------------------
def test_partitioner_grad_shard_spec_matches_pass():
    part = Partitioner(mesh=_mesh(2))
    assert part.grad_shard_spec((8, 3)) == ('dp',)
    assert part.grad_shard_spec((3, 8)) == (None, 'dp')
    assert part.grad_shard_spec((3, 5)) is None
    assert Partitioner(mesh=_mesh(1)).grad_shard_spec((8, 8)) is None


# ---- telemetry -----------------------------------------------------------
def test_zero_journal_metrics_and_report_gate(tmp_path):
    jpath = str(tmp_path / 'zero.jsonl')
    feeds = _feeds(3)
    with obs.journal(jpath):
        _run(2, 2, feeds=feeds)
        from paddle_tpu.parallel.collective import observe_collective
        observe_collective('reduce_scatter', 0.002, 4096)
        observe_collective('all_gather', 0.001, 4096)
    reg = obs.default_registry()
    g = reg.get('zero_grad_shard_bytes')
    assert g is not None and g.value > 0
    h = reg.get('collective_seconds', op='reduce_scatter')
    assert h is not None and h.count >= 1

    assert obs_report.check_journal(jpath, require='zero') == []
    records, malformed = obs_report.load_journal(jpath)
    summary = obs_report.summarize(records, malformed)
    z = summary['zero']
    assert z['applied'] >= 1 and z['buckets'] >= 1
    assert z['shard_bytes'] > 0
    assert 'zero:' in obs_report.render(summary)
    # a journal with no zero events fails the gate
    empty = str(tmp_path / 'empty.jsonl')
    with obs.journal(empty):
        obs.emit('step_end', step=0, dur_s=0.001)
    assert obs_report.check_journal(empty, require='zero') != []


# ---- Trainer end to end --------------------------------------------------
def test_trainer_zero_stage_end_to_end(tmp_path):
    """``Trainer.train(zero_stage=...)`` wires the mode through the
    ParallelExecutor path: the dp-mesh default (stage 2) is
    bit-identical to an explicit ``zero_stage=0`` replicated run, the
    rewritten program carries the bucketed tail, and the run journals
    the ``zero`` application."""
    batch, steps = 32, 6
    rng = np.random.RandomState(3)
    xs = rng.randn(steps * batch, 8).astype('float32')
    ys = (xs.sum(1, keepdims=True) * 0.25).astype('float32')

    def reader():
        for i in range(0, len(xs), batch):
            yield [(xs[j], ys[j]) for j in range(i, i + batch)]

    def train_func():
        x = fluid.layers.data(name='x', shape=[8], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        h = fluid.layers.fc(input=x, size=16, act='relu')
        pred = fluid.layers.fc(input=h, size=1)
        return fluid.layers.mean(
            fluid.layers.square_error_cost(pred, y))

    from paddle_tpu.parallel.mesh import set_mesh
    import contextlib

    def run(zero_stage, journal=None):
        losses = []

        def handler(ev):
            if isinstance(ev, fluid.EndStepEvent) and ev.metrics:
                losses.append(float(np.asarray(ev.metrics[0]).item()))
        ctx = obs.journal(str(journal)) if journal \
            else contextlib.nullcontext()
        with ctx:
            trainer = fluid.Trainer(
                train_func=train_func,
                optimizer=fluid.optimizer.Adam(learning_rate=0.01),
                place=fluid.CPUPlace(), parallel=True)
            trainer.train(num_epochs=1, event_handler=handler,
                          reader=reader, feed_order=['x', 'y'],
                          steps_per_dispatch=2,
                          zero_stage=zero_stage)
        zops = any(op.type == 'zero_reduce_scatter'
                   for op in trainer.train_program.global_block().ops)
        return losses, zops

    set_mesh(_mesh(2))
    try:
        l_rep, z_rep = run(0)
        jpath = tmp_path / 'trainer_zero.jsonl'
        l_zero, z_zero = run(None, journal=jpath)   # dp default = 2
    finally:
        set_mesh(None)
    assert not z_rep and z_zero
    assert len(l_zero) == steps and l_zero == l_rep
    records, _ = obs_report.load_journal(str(jpath))
    applies = [r for r in records if r.get('ev') == 'zero'
               and r.get('action') == 'apply' and r.get('buckets')]
    assert applies and applies[0]['dp'] == 2
    assert obs_report.check_journal(str(jpath), require='zero') == []
