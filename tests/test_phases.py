"""The Executor's phases and the Fluid scopes (OBSERVABILITY.md
"Distributed tracing", "Performance observatory").

- ``Executor.run`` is ``exe/run`` around ``exe/prep``, ``exe/launch``,
  ``exe/commit``; ``run_chained`` is ``exe/chain`` around the same.
  One helper (``observability.phase``) puts each name into the
  profiler's trace and, under a parent span, into the journal.
- With neither a profiler session nor a journal, ``run`` writes no
  record, lowers nothing beyond the jit's own compile, and returns
  what it returned before.
- ``perf.scope_map()`` maps the compiled step's instructions to the
  scopes the lowering left: ``forward`` / ``transpose(jvp(forward))``
  / ``optimizer``, then ``<op.type>:<output>``.
- ``perf.compile_log()`` holds what jax said of every trace, lowering
  and compile, filed under the phase it fell in, and the Executor's
  account of each miss (OBSERVABILITY.md "The compile path").
"""
import contextlib
import glob
import os
import time

import numpy as np
import pytest

import jax
import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu.observability import perf

pytestmark = pytest.mark.observability

PHASES = ('exe/prep', 'exe/launch', 'exe/commit')


@pytest.fixture(autouse=True)
def _no_ambient_tracing_env(monkeypatch):
    monkeypatch.delenv(obs.TRACE_SAMPLE_ENV, raising=False)
    monkeypatch.delenv(obs.TRACE_PARENT_ENV, raising=False)
    monkeypatch.delenv(obs.JOURNAL_ENV, raising=False)


def _tiny(optimizer='momentum', seed=3):
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = main.random_seed = seed
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name='x', shape=[3, 8, 8],
                                  dtype='float32')
            y = fluid.layers.data(name='y', shape=[1], dtype='int64')
            # no bias, no activation: nothing for conv_epilogue_fuse to
            # fold, so the op stays a conv2d
            h = fluid.layers.conv2d(x, num_filters=4, filter_size=3,
                                    bias_attr=False)
            h = fluid.layers.pool2d(h, pool_size=2, pool_stride=2)
            p = fluid.layers.fc(h, size=5, act='softmax')
            loss = fluid.layers.mean(
                fluid.layers.cross_entropy(input=p, label=y))
            if optimizer == 'adam':
                opt = fluid.optimizer.Adam(learning_rate=1e-3)
            else:
                opt = fluid.optimizer.Momentum(learning_rate=1e-3,
                                               momentum=0.9)
            opt.minimize(loss)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(seed)
    feeds = [{'x': rng.rand(4, 3, 8, 8).astype('float32'),
              'y': rng.randint(0, 5, (4, 1)).astype('int64')}
             for _ in range(4)]
    return exe, scope, main, loss, feeds


def _host_events(trace_dir):
    """``[(name, start, end, stats)]`` of the profiler trace's host
    events whose name starts with ``exe/``, in start order."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'),
                      recursive=True)
    evs = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith('exe/'):
                    evs.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return sorted(evs, key=lambda e: (e[1], -e[2]))


def _children(evs, parent):
    return [e for e in evs if e is not parent
            and parent[1] <= e[1] and e[2] <= parent[2]]


def test_profiler_trace_holds_the_phases_of_every_run(tmp_path):
    exe, scope, main, loss, feeds = _tiny()
    exe.run(main, feed=feeds[0], fetch_list=[loss], scope=scope)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for f in feeds[:3]:
            exe.run(main, feed=f, fetch_list=[loss], scope=scope)
    finally:
        jax.profiler.stop_trace()
    evs = _host_events(str(tmp_path))
    runs = [e for e in evs if e[0] == 'exe/run']
    assert len(runs) == 3
    # a StepTraceAnnotation: consecutive step numbers of this Executor
    nums = [r[3]['step_num'] for r in runs]
    assert nums == list(range(nums[0], nums[0] + 3))
    for r in runs:
        kids = [e[0] for e in _children(evs, r) if e[0] in PHASES]
        assert kids == list(PHASES)
        commit = next(e for e in _children(evs, r)
                      if e[0] == 'exe/commit')
        assert [e[0] for e in _children(evs, commit)] == ['exe/fetch']
    assert not [e for e in evs if e[0] in ('exe/verify', 'exe/compile')]


def test_profiler_trace_holds_the_phases_of_a_chain(tmp_path):
    exe, scope, main, loss, feeds = _tiny()
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = exe.run_chained(main, feed_list=feeds[:2],
                              fetch_list=[loss], scope=scope)
    finally:
        jax.profiler.stop_trace()
    assert len(out) == 2
    evs = _host_events(str(tmp_path))
    chain, = [e for e in evs if e[0] == 'exe/chain']
    assert [e[0] for e in _children(evs, chain) if e[0] in PHASES] \
        == list(PHASES)
    # the chunk's program was a cache miss: verify and compile lie
    # inside its prep
    prep = next(e for e in evs if e[0] == 'exe/prep')
    assert [e[0] for e in _children(evs, prep)] == ['exe/verify',
                                                    'exe/compile']
    assert not [e for e in evs if e[0] == 'exe/run']


def test_journal_carries_the_same_names_under_a_parent_span(tmp_path):
    exe, scope, main, loss, feeds = _tiny()
    p = str(tmp_path / 'j.jsonl')
    with obs.journal(p):
        # no parent span: no span record at all
        exe.run(main, feed=feeds[0], fetch_list=[loss], scope=scope)
        with obs.span('test/step'):
            exe.run(main, feed=feeds[1], fetch_list=[loss], scope=scope)
            exe.run_chained(main, feed_list=feeds[:2], fetch_list=[loss],
                            scope=scope)
    recs, malformed = obs.read_journal(p)
    assert malformed == 0
    ends = [r for r in recs if r['ev'] == 'span_end']
    names = [r['name'] for r in ends]
    # children end before their root; the miss of the first (bare) run
    # left no span, the hit of the second has neither verify nor compile;
    # the chunk's miss holds what jax said of its first launch
    assert names == ['exe/prep', 'exe/launch', 'exe/fetch', 'exe/commit',
                     'exe/run',
                     'exe/verify', 'exe/compile', 'exe/prep', 'jax/trace',
                     'jax/mlir', 'jax/xla_compile', 'exe/launch',
                     'exe/fetch', 'exe/commit', 'exe/chain', 'test/step']
    by = {r['name']: r for r in ends[:5]}
    root = by['exe/run']
    assert all(by[n]['parent'] == root['span'] for n in
               ('exe/prep', 'exe/launch', 'exe/commit', 'exe/fetch'))
    assert by['exe/launch']['cache'] == 'hit' and root['fp']
    step = ends[-1]
    assert root['parent'] == step['span'] == ends[-2]['parent']
    begins = [r for r in recs if r['ev'] == 'span_begin']
    assert [r['name'] for r in begins] == ['test/step', 'exe/run',
                                           'exe/chain']
    assert begins[-1]['steps'] == 2 and ends[-2]['fp']
    runs = [r for r in recs if r['ev'] == 'exe_run']
    assert [r['cache'] for r in runs] == ['miss', 'hit', 'miss']


def test_untraced_run_writes_nothing_lowers_nothing_and_agrees(
        tmp_path, monkeypatch):
    """No profiler session, no journal: the phases are inert. ``run``
    calls no ``.lower(`` (the jit's own first call compiles, as
    before), writes no journal record, and two Executors driven with
    and without tracing return bit-identical fetches."""
    import jax._src.stages as stages
    calls = []
    for cls in (stages.Wrapped, stages.Traced):
        real = cls.lower
        monkeypatch.setattr(
            cls, 'lower', lambda self, *a, _real=real, **k:
            calls.append(1) or _real(self, *a, **k))
    assert obs.get_journal() is None and obs.current_context() is None
    exe, scope, main, loss, feeds = _tiny()
    plain = [exe.run(main, feed=f, fetch_list=[loss], scope=scope)[0]
             for f in feeds]
    assert calls == []
    assert exe.cache_info().misses == 2      # startup and the step
    # the same steps, traced on both sinks
    exe2, scope2, main2, loss2, _ = _tiny()
    p = str(tmp_path / 'j.jsonl')
    jax.profiler.start_trace(str(tmp_path / 'trace'))
    try:
        with obs.journal(p), obs.span('test/step'):
            traced = [exe2.run(main2, feed=f, fetch_list=[loss2],
                               scope=scope2)[0] for f in feeds]
    finally:
        jax.profiler.stop_trace()
    for a, b in zip(plain, traced):
        assert a.tobytes() == b.tobytes()
    # scope_map() is what lowers again, and only when asked
    assert perf.scope_map(executors=[exe]) and calls
    # by default every live Executor is asked (the weak registry)
    assert any(main.fingerprint() in key
               for key in perf.scope_map(min_runs=len(feeds)))


@pytest.mark.parametrize('optimizer', ['momentum', 'adam'])
def test_scope_map_names_phase_and_fluid_op(optimizer):
    exe, scope, main, loss, feeds = _tiny(optimizer)
    for f in feeds[:2]:
        exe.run(main, feed=f, fetch_list=[loss], scope=scope)
    # the step; not the startup, nor another test's Executor
    maps = perf.scope_map(min_runs=2, executors=[exe])
    assert len(maps) == 1
    (key, scopes), = maps.items()
    assert key.startswith('jit_fn|') and 'error' not in scopes
    assert len(perf.scope_map(executors=[exe])) == 2
    seen = {}
    for inst, op_name in scopes.items():
        phase, op = perf.split_scope(op_name)
        assert phase in perf.PHASES
        if op:
            seen.setdefault(phase, set()).add(op.split(':')[0])
    assert 'conv2d' in seen['forward'] and 'conv2d' in seen['backward']
    assert 'pool2d' in seen['forward']
    # adam's beta-power accumulators advance through scale ops
    assert seen['optimizer'] - {'scale'} == {optimizer}
    # two ops of one type are two names
    convs = {perf.split_scope(n)[1] for n in scopes.values()
             if '/conv2d:' in n}
    assert convs == {'conv2d:conv2d_0.tmp_0'}
    assert perf.split_scope(
        'jit(fn)/transpose(jvp(forward))/mul:fc_0.tmp_0/dot_general') \
        == ('backward', 'mul:fc_0.tmp_0')
    assert perf.split_scope('jit(fn)/optimizer/adam:fc_0.w_0/mul') \
        == ('optimizer', 'adam:fc_0.w_0')
    assert perf.split_scope('jit(fn)/forward/reduce_sum') \
        == ('forward', None)


def test_parse_scopes_gives_a_fusion_one_scope():
    text = '''HloModule jit_fn, is_scheduled=true

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %m = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(fn)/jvp(forward)/relu:a/mul"}
  ROOT %s = f32[4]{0} subtract(%m, %p), metadata={op_name="jit(fn)/transpose(jvp(forward))/relu:a/sub"}
}

%fused_computation.2 (p: f32[4]) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0)
  %c = f32[4]{0} convert(%p.1), metadata={op_name="jit(fn)/transpose(jvp(forward))/mul:fc_0.tmp_0/convert"}
  %d = f32[4]{0} divide(%c, %c), metadata={op_name="jit(fn)/optimizer/adam:fc_0.w_0/div"}
  ROOT %u = f32[4]{0} subtract(%p.1, %d), metadata={op_name="jit(fn)/optimizer/adam:fc_0.w_0/sub"}
}

%fused_computation.3 (p: f32[4,4]) -> f32[4,4] {
  %p.2 = f32[4,4]{1,0} parameter(0)
  %g = f32[4,4]{1,0} convolution(%p.2, %p.2), dim_labels=bf_io->bf, metadata={op_name="jit(fn)/transpose(jvp(forward))/conv2d:c.tmp_0/conv_general_dilated"}
  %v = f32[4,4]{1,0} multiply(%g, %g), metadata={op_name="jit(fn)/optimizer/momentum:c.w_0/mul"}
  ROOT %w = f32[4,4]{1,0} subtract(%p.2, %v), metadata={op_name="jit(fn)/optimizer/momentum:c.w_0/sub"}
}

ENTRY %main.3 (x: f32[4]) -> (f32[4], f32[4]) {
  %x = f32[4]{0} parameter(0)
  %y = f32[4,4]{1,0} parameter(1)
  %fusion.9 = f32[4,4]{1,0} fusion(%y), kind=kOutput, calls=%fused_computation.3, metadata={op_name="jit(fn)/optimizer/momentum:c.w_0/sub"}
  %fusion.7 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %divide_subtract_fusion = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(fn)/transpose(jvp(forward))/mul:fc_0.tmp_0/convert"}
  %copy-done.2 = f32[4]{0} copy-done(%x)
  %_flash_kernel.1 = (f32[4]{0}, f32[4]{0}) custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(fn)/jvp(forward)/flash_attention:o/pallas_call"}
  ROOT %t = (f32[4]{0}, f32[4]{0}) tuple(%fusion.7, %copy-done.2)
}
'''
    module, scopes = perf.parse_scopes(text)
    assert module == 'jit_fn'
    # a forward op recomputed inside a backward fusion: backward
    assert perf.split_scope(scopes['fusion.7']) == ('backward', 'relu:a')
    # XLA names the update after the gradient's cast it swallowed
    assert perf.split_scope(scopes['divide_subtract_fusion']) \
        == ('optimizer', 'adam:fc_0.w_0')
    # a weight gradient's conv with the update as its epilogue: the
    # conv's phase, both names
    assert perf.split_scope(scopes['fusion.9']) \
        == ('backward', 'conv2d:c.tmp_0+momentum:c.w_0')
    assert perf.split_scope(scopes['_flash_kernel.1']) \
        == ('forward', 'flash_attention:o')
    assert 'copy-done.2' not in scopes and 'x' not in scopes


@contextlib.contextmanager
def _persistent_cache(path):
    """jax's persistent cache on, at ``path``, keeping every entry."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = ('jax_enable_compilation_cache', 'jax_compilation_cache_dir',
             'jax_persistent_cache_min_compile_time_secs',
             'jax_persistent_cache_min_entry_size_bytes')
    keep = {n: getattr(jax.config, n) for n in names}
    for n, v in zip(names, (True, str(path), 0, 0)):
        jax.config.update(n, v)
    cc.reset_cache()
    try:
        yield
    finally:
        for n, v in keep.items():
            jax.config.update(n, v)
        cc.reset_cache()


def test_compiled_text_reads_past_a_cache_entry_with_older_scopes(tmp_path):
    """JAX leaves metadata out of the persistent cache's key, so the
    cache hands a program the executable an older version compiled,
    with that version's scopes. ``scope_map`` then compiles afresh, and
    writes nothing back."""
    import jax.numpy as jnp
    with _persistent_cache(tmp_path):
        def step(scope):
            def f(x):
                with jax.named_scope(scope):
                    return jnp.tanh(x @ x).sum()
            return jax.jit(f)
        x = jnp.ones((64, 64))
        step('zz_before')(x)
        entries = sorted(os.listdir(str(tmp_path)))
        new = step('forward')
        new(x)
        avals = (jax.ShapeDtypeStruct(x.shape, x.dtype),)
        stale = new.trace(*avals).lower().compile().as_text()
        assert 'zz_before' in stale and '/forward/' not in stale
        text = perf._compiled_text(new, avals)
        assert '/forward/' in text and 'zz_before' not in text
        assert sorted(os.listdir(str(tmp_path))) == entries
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


# ---- the compile path, told by jax -----------------------------------------
JAX_KINDS = ('trace', 'mlir', 'backend')


def _since(t0, kind=None):
    return [e for e in perf.compile_log() if e['t'] >= t0
            and (kind is None or e['kind'] == kind)]


def test_a_miss_accounts_for_itself_part_by_part():
    t0 = time.perf_counter()
    exe, scope, main, loss, feeds = _tiny()
    exe.run(main, feed=feeds[0], fetch_list=[loss], scope=scope)
    startup, step = _since(t0, 'miss')       # one entry a miss
    assert step['fp'] == main.fingerprint() != startup['fp']
    assert step['phase'] == 'exe/run' and step['modules'] >= 1
    for miss in (startup, step):
        parts = [miss[k] for k in ('verify_s', 'lower_s', 'trace_s',
                                   'mlir_s', 'backend_s', 'first_run_s')]
        assert all(p >= 0.0 for p in parts)
        assert 0.0 < sum(parts) <= miss['wall_s']
        assert miss['cache'] == 'off' and miss['retrieval_s'] == 0.0
    # the parts are what jax said inside the call
    mine = [e for e in _since(t0) if e['fp'] == step['fp']
            and e['kind'] in JAX_KINDS]
    for kind in JAX_KINDS:
        assert step[kind + '_s'] == pytest.approx(
            sum(e['dur_s'] for e in mine if e['kind'] == kind))
    assert step['modules'] == len([e for e in mine
                                   if e['kind'] == 'backend'])


def test_nested_traces_fold_into_the_steps_one_trace():
    t0 = time.perf_counter()
    exe, scope, main, loss, feeds = _tiny()
    exe.run(main, feed=feeds[0], fetch_list=[loss], scope=scope)
    fp = main.fingerprint()
    # every jnp function inside the step's trace fires a trace event of
    # its own; the log holds the outermost: one trace, one lowering and
    # one compile of the step's module
    for kind in JAX_KINDS:
        e, = [e for e in _since(t0, kind)
              if e['fp'] == fp and e['phase'] == 'exe/launch']
        assert e['fun'] in ('fn', 'jit(fn)') and e['dur_s'] > 0
        assert e['thread'] and ('cache' in e) == (kind == 'backend')


def test_a_second_run_of_the_same_program_adds_no_entry():
    exe, scope, main, loss, feeds = _tiny()
    exe.run(main, feed=feeds[0], fetch_list=[loss], scope=scope)
    t0 = time.perf_counter()
    reg = obs.default_registry()
    seen = reg.counter('jax_compile_events_total', kind='trace',
                       owner='executor').value
    for f in feeds[1:]:
        exe.run(main, feed=f, fetch_list=[loss], scope=scope)
    assert _since(t0) == []
    assert reg.counter('jax_compile_events_total', kind='trace',
                       owner='executor').value == seen


def test_clear_caches_gives_a_retrace_under_a_held_key(tmp_path):
    exe, scope, main, loss, feeds = _tiny()
    exe.run(main, feed=feeds[0], fetch_list=[loss], scope=scope)
    misses = exe.cache_info().misses
    jax.clear_caches()
    t0 = time.perf_counter()
    p = str(tmp_path / 'j.jsonl')
    with obs.journal(p), obs.span('test/step'):
        exe.run(main, feed=feeds[1], fetch_list=[loss], scope=scope)
        exe.run(main, feed=feeds[2], fetch_list=[loss], scope=scope)
    # the Executor held the key: no miss of its own, no miss entry
    assert exe.cache_info().misses == misses
    assert _since(t0, 'miss') == []
    for kind in ('trace', 'backend'):
        e, = _since(t0, kind)
        assert e['phase'] == 'exe/launch' and e['fp'] == main.fingerprint()
    recs, _ = obs.read_journal(p)
    first, second = [r for r in recs if r['ev'] == 'span_end'
                     and r['name'] == 'exe/run']
    assert first['retraced'] is True and 'retraced' not in second
    assert [r['cache'] for r in recs if r['ev'] == 'exe_run'] \
        == ['hit', 'hit']


def test_a_compile_outside_any_phase_is_nobodys_of_ours():
    import jax.numpy as jnp
    reg = obs.default_registry()
    series = [reg.counter('jax_compile_events_total', kind=k, owner='other')
              for k in JAX_KINDS]
    x = jnp.ones((8, 8))
    seen = [c.value for c in series]
    secs = reg.counter('jax_compile_seconds_total', kind='backend',
                       owner='other').value
    t0 = time.perf_counter()
    jax.jit(lambda x: jnp.tanh(x) @ x)(x)
    assert [e['kind'] for e in _since(t0)] == list(JAX_KINDS)
    assert all(e['phase'] is None and e['fp'] is None for e in _since(t0))
    assert [c.value for c in series] == [n + 1 for n in seen]
    assert reg.counter('jax_compile_seconds_total', kind='backend',
                       owner='other').value > secs


def test_jax_spans_are_children_of_the_phase_they_fell_in(tmp_path):
    exe, scope, main, loss, feeds = _tiny()
    p = str(tmp_path / 'j.jsonl')
    with obs.journal(p), obs.span('test/step'):
        exe.run(main, feed=feeds[0], fetch_list=[loss], scope=scope)
    recs, _ = obs.read_journal(p)
    ends = {r['name']: r for r in recs if r['ev'] == 'span_end'}
    launch, run = ends['exe/launch'], ends['exe/run']
    assert launch['parent'] == run['span']
    for name in ('jax/trace', 'jax/mlir', 'jax/xla_compile'):
        assert ends[name]['parent'] == launch['span']
        assert ends[name]['trace'] == run['trace']
        assert ends[name]['fp'] == run['fp'] and ends[name]['fun']
        assert ends[name]['dur_s'] <= launch['dur_s']
    # the miss's own account rides on compile_end
    end, = [r for r in recs if r['ev'] == 'compile_end']
    assert end['fp'] == run['fp'] and end['modules'] >= 1
    assert end['trace_s'] + end['mlir_s'] + end['backend_s'] \
        + end['first_run_s'] <= end['dur_s'] + 1e-5


def test_a_second_process_like_run_logs_the_cache_hit(tmp_path):
    """With a persistent cache directory, ``jax.clear_caches()`` stands
    in for a second process: the step's module is found, not compiled."""
    reg = obs.default_registry()
    hits = reg.counter('jax_persistent_cache_total', result='hit').value
    with _persistent_cache(tmp_path):
        exe, scope, main, loss, feeds = _tiny()
        t0 = time.perf_counter()
        exe.run(main, feed=feeds[0], fetch_list=[loss], scope=scope)
        cold, = _since(t0, 'miss')
        assert cold['cache'] == 'miss' and cold['retrieval_s'] == 0.0
        jax.clear_caches()
        t1 = time.perf_counter()
        exe.run(main, feed=feeds[1], fetch_list=[loss], scope=scope)
        warm, = _since(t1, 'backend')
        assert warm['cache'] == 'hit' and warm['retrieval_s'] > 0
        assert warm['phase'] == 'exe/launch' and 'saved_s' in warm
        assert reg.counter('jax_persistent_cache_total',
                           result='hit').value == hits + 1
