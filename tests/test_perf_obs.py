"""Performance observatory (ISSUE 15, OBSERVABILITY.md "Performance
observatory").

Acceptance pins:
- a ProgramLedger is captured EXACTLY once per (program, shape, mesh)
  — cache_info miss parity — on the Executor's compile-miss path, and
  never when capture is off (the default);
- MFU/roofline math is pinned against hand-computed matmul arithmetic,
  and a captured fc program's XLA-counted flops match the hand count;
- a dp=2 sharded variant ledgers separately from the single-device
  compile of the SAME program, with per-device argument bytes about
  half the replicated run (batch sharded, params replicated);
- PerfBaseline round-trips through its on-disk JSON, and the diff
  sentinel names the program on seeded flops/step-time/MFU
  regressions (tools/perf_report.py --smoke --baseline exits nonzero);
- perf_ledger journal events carry the tracing trace-id exemplar and
  satisfy the obs_report --require perf gate; serving warmup ledgers
  its per-bucket compiles;
- the direct-cost-analysis lint rule fires outside observability/perf.
"""
import json
import os
import sys

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu import unique_name
from paddle_tpu.observability import perf

pytestmark = pytest.mark.perfobs

TOOLS = os.path.join(os.path.dirname(__file__), '..', 'tools')
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)

import lint_repo     # noqa: E402
import obs_report    # noqa: E402
import perf_report   # noqa: E402


@pytest.fixture(autouse=True)
def _perf_isolation(monkeypatch):
    """Tests own the capture gate and the ledger book; nothing leaks
    between tests or out to the rest of the suite."""
    monkeypatch.delenv(perf.PERF_ENV, raising=False)
    prev = perf.enable_capture(None)
    perf.clear()
    yield
    perf._CAPTURE[0] = prev
    perf.clear()


def _mlp(seed=7, batch=16):
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = seed
    with fluid.program_guard(main, startup), unique_name.guard():
        img = fluid.layers.data(name='img', shape=[32],
                                dtype='float32')
        label = fluid.layers.data(name='label', shape=[1],
                                  dtype='int64')
        h = fluid.layers.fc(input=img, size=24, act='relu')
        pred = fluid.layers.fc(input=h, size=10, act='softmax')
        loss = fluid.layers.mean(fluid.layers.cross_entropy(
            input=pred, label=label))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    rng = np.random.RandomState(seed)
    feed = {'img': rng.randn(batch, 32).astype('float32'),
            'label': rng.randint(0, 10, (batch, 1)).astype('int64')}
    return main, startup, loss, feed


# ---- capture gate + once-per-compile parity -------------------------------
def test_capture_off_by_default():
    assert not perf.capture_enabled()
    main, startup, loss, feed = _mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
    assert len(perf.book()) == 0
    assert perf.get_ledger(main.fingerprint()) is None


def test_ledger_once_per_program_shape_mesh():
    main, startup, loss, feed = _mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with perf.capture_scope(True):
            before = exe.cache_info()
            for _ in range(3):
                exe.run(main, feed=feed, fetch_list=[loss])
            mid = exe.cache_info()
            # ledger count tracks compile misses exactly: 3 runs, one
            # compile, one ledger
            assert mid.misses - before.misses == 1
            assert len(perf.book()) == 1
            # a new shape bucket is a new compile -> a second ledger
            _, _, _, feed24 = _mlp(batch=24)
            exe.run(main, feed=feed24, fetch_list=[loss])
            after = exe.cache_info()
            assert after.misses - before.misses == 2
            assert len(perf.book()) == 2
    ledger = perf.get_ledger(main.fingerprint())
    assert ledger is not None
    assert ledger.backend == 'cpu' and ledger.mesh == 'single'
    assert ledger.flops > 0 and ledger.bytes_accessed > 0
    assert ledger.live_bytes > 0 and ledger.compile_wall_s > 0
    assert len(ledger.shape_sig) == 16
    # every recorded entry is retrievable through the book
    keys = {perf.LedgerBook.key(l) for l in perf.ledgers()}
    assert len(keys) == 2


# ---- MFU / roofline math ---------------------------------------------------
def test_mfu_math_pinned_vs_hand_matmul():
    M, K, N = 32, 128, 64
    flops = 2.0 * M * K * N
    bytes_moved = 4.0 * (M * K + K * N + M * N)
    led = perf.ProgramLedger('fp1', device_kind='', flops=flops,
                             bytes_accessed=bytes_moved)
    # 1 ms against a 1 GFLOP/s peak: utilization is flops/1e6/1e9
    assert led.mfu(measured_ms=1.0, peak=1e9) == \
        pytest.approx(flops / 1e-3 / 1e9)
    # bound legs and the roofline pick are the literal quotients
    assert led.compute_bound_s(peak=1e9) == pytest.approx(flops / 1e9)
    assert led.bandwidth_bound_s(hbm_gbps=1.0) == \
        pytest.approx(bytes_moved / 1e9)
    # the device table (unknown kinds: tests/test_bringup.py)
    assert perf.peak_flops_for('TPU v4') == 275e12
    assert perf.peak_flops_for('TPU v5 lite') == 197e12
    assert perf.hbm_gbps_for('TPU v5 lite') == 819.0


def test_captured_fc_flops_match_hand_count():
    M, K, N = 32, 128, 64
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 3
    with fluid.program_guard(main, startup), unique_name.guard():
        x = fluid.layers.data(name='x', shape=[K], dtype='float32')
        y = fluid.layers.fc(input=x, size=N, bias_attr=False)
    xs = np.random.RandomState(0).randn(M, K).astype('float32')
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with perf.capture_scope(True):
            exe.run(main, feed={'x': xs}, fetch_list=[y])
    led = perf.get_ledger(main.fingerprint())
    assert led is not None
    # XLA counts the bare matmul: 2*M*K*N fused-multiply-add flops
    assert led.flops == pytest.approx(2.0 * M * K * N, rel=0.05)
    # the CPU backend has no roofline: a measured step publishes no
    # MFU against some other chip's peak
    assert led.device_kind == 'cpu' and led.roofline_bound is None
    assert perf.publish_step(main.fingerprint(), 0.002) is None
    # on a device from the table the same publish derives the gauges
    led.device_kind = 'TPU v5 lite'
    mfu = perf.publish_step(main.fingerprint(), 0.002)
    assert mfu == pytest.approx(led.flops / 0.002 / 197e12)
    from paddle_tpu.observability import metrics
    reg = metrics.default_registry()
    g = reg.get('perf_mfu', program=main.fingerprint())
    assert g is not None and g.value == pytest.approx(mfu)
    rb = reg.get('perf_roofline_bound', program=main.fingerprint())
    assert rb is not None and rb.value in (0.0, 1.0)


# ---- dp=2 variants ledger separately, per-device bytes halve ---------------
def test_dp2_per_device_bytes_about_half_of_replicated():
    devs = jax.devices()
    assert len(devs) >= 2
    mesh2 = Mesh(np.asarray(devs[:2]), ('dp',))
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 5
    with fluid.program_guard(main, startup), unique_name.guard():
        x = fluid.layers.data(name='x', shape=[1024], dtype='float32')
        h = fluid.layers.fc(input=x, size=4)
        loss = fluid.layers.mean(h)
    xs = np.random.RandomState(0).randn(64, 1024).astype('float32')
    exe = fluid.Executor(fluid.CPUPlace())
    with perf.capture_scope(True), fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed={'x': xs}, fetch_list=[loss])
        single = perf.get_ledger(main.fingerprint())
        assert single is not None and single.mesh == 'single'
        pexe = fluid.ParallelExecutor(use_cuda=False,
                                      main_program=main, mesh=mesh2)
        pexe.run([loss], feed={'x': xs})
        sharded = perf.get_ledger(main.fingerprint())
    assert sharded.mesh == 'dp=2' and sharded.devices == 2
    # the two variants coexist in the book under distinct keys
    meshes = {l.mesh for l in perf.ledgers()
              if l.fingerprint == main.fingerprint()}
    assert meshes == {'single', 'dp=2'}
    # the feed dominates the argument bytes; batch-sharding over dp=2
    # halves the per-device share while params stay replicated
    ratio = sharded.argument_bytes / float(single.argument_bytes)
    assert 0.35 < ratio < 0.75


# ---- baseline sentinel -----------------------------------------------------
def test_baseline_roundtrip_and_seeded_regressions(tmp_path):
    led = perf.ProgramLedger('fp0', shape_sig='abcd', backend='cpu',
                             device_kind='TPU v5e', mesh='dp=2',
                             flops=1e9, bytes_accessed=5e8,
                             output_bytes=1000.0, temp_bytes=2048,
                             argument_bytes=4096, label='prog')
    led.measured_ms = 2.0
    base = perf.PerfBaseline(str(tmp_path / 'b.json'))
    key = perf.PerfBaseline.key('fp0', 'abcd', 'cpu', 'dp=2')
    base.put(key,
             perf.PerfBaseline.entry_from_ledger(led, with_timings=True))
    base.save()
    again = perf.PerfBaseline(base.path).load()
    assert again.entries == base.entries
    entry = dict(base.entries[key])
    assert entry['step_ms'] == 2.0 and entry['mfu'] > 0
    # clean run: no problems
    assert again.diff({key: dict(entry)}) == []
    # deterministic drift names the program and the field
    probs = again.diff({key: dict(entry, flops=1.2e9)})
    assert any('prog' in p and 'flops' in p for p in probs)
    # timing regressions gate at the caller's tolerance
    probs = again.diff({key: dict(entry, step_ms=entry['step_ms'] * 2)},
                       tol=0.10)
    assert any('step time regressed' in p for p in probs)
    probs = again.diff({key: dict(entry, mfu=entry['mfu'] * 0.5)},
                       tol=0.10)
    assert any('MFU regressed' in p for p in probs)
    # a program vanishing from the run is itself a regression
    assert any('missing from run' in p for p in again.diff({}))
    # run-only programs ratchet in silently (never flagged)
    cur = {key: dict(entry),
           'new|x|cpu|single': {'program': 'new', 'flops': 1.0}}
    assert again.diff(cur) == []


def test_perf_report_smoke_sentinel_end_to_end(tmp_path, capsys):
    base = str(tmp_path / 'base.json')
    assert perf_report.main(['--smoke', '--steps', '2',
                             '--update-baseline', base]) == 0
    perf.clear()
    # same box, same XLA: the fresh run diffs clean
    assert perf_report.main(['--smoke', '--steps', '2',
                             '--baseline', base]) == 0
    perf.clear()
    capsys.readouterr()
    # seed a regression: double one program's baselined flops
    with open(base) as f:
        data = json.load(f)
    key = sorted(data['entries'])[0]
    name = data['entries'][key]['program']
    data['entries'][key]['flops'] *= 2.0
    with open(base, 'w') as f:
        json.dump(data, f)
    rc = perf_report.main(['--smoke', '--steps', '2',
                           '--baseline', base])
    err = capsys.readouterr().err
    assert rc == 1
    assert 'PERF REGRESSION' in err
    assert name in err and 'flops drifted' in err


# ---- journal events, trace exemplar, report gates --------------------------
def test_journal_event_trace_exemplar_and_gate(tmp_path):
    p = str(tmp_path / 'run.jsonl')
    main, startup, loss, feed = _mlp(seed=13)
    with obs.journal(p), perf.capture_scope(True):
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            with obs.span('perf/root') as root:
                exe.run(main, feed=feed, fetch_list=[loss])
            perf.publish_step(main.fingerprint(), 0.004)
    recs, malformed = obs.read_journal(p)
    assert malformed == 0
    evs = [r for r in recs if r['ev'] == 'perf_ledger']
    seal = next(r for r in evs if r.get('fp') == main.fingerprint()
                and r.get('phase') != 'measured')
    assert seal['flops'] > 0 and seal['mesh'] == 'single'
    assert seal['live_bytes'] > 0 and seal['compile_wall_s'] > 0
    assert 'roofline' not in seal    # captured on the CPU backend
    # the compile ran under the sampled root span: the ledger carries
    # its trace id, so a regressed program resolves to a span tree
    assert seal['trace'] == root.context.trace_id
    measured = next(r for r in evs if r.get('phase') == 'measured')
    assert measured['fp'] == main.fingerprint()
    assert measured['measured_ms'] == pytest.approx(4.0)
    assert measured['mfu'] is None   # no roofline on the CPU backend
    # the obs_report gate accepts this journal and renders a perf line
    assert obs_report.check_journal(p, require='perf') == []
    summary = obs_report.summarize(recs)
    assert summary['perf']['programs'] >= 1
    assert 'perf:' in obs_report.render(summary)
    # a journal without perf events fails the gate
    bare = str(tmp_path / 'bare.jsonl')
    with obs.journal(bare):
        obs.emit('step_end', dur_s=0.1)
    problems = obs_report.check_journal(bare, require='perf')
    assert any('perf_ledger' in pr for pr in problems)


def test_serving_warmup_ledgers_buckets(tmp_path):
    from paddle_tpu.serving import ModelServer
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 9
    with fluid.program_guard(main, startup), unique_name.guard():
        x = fluid.layers.data(name='x', shape=[6], dtype='float32')
        h = fluid.layers.fc(input=x, size=8, act='relu')
        y = fluid.layers.fc(input=h, size=3, act=None)
    d = str(tmp_path / 'm0')
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(d, ['x'], [y], exe,
                                      main_program=main)
    p = str(tmp_path / 'serve.jsonl')
    with obs.journal(p):
        with ModelServer(place=fluid.CPUPlace(),
                         max_batch_size=8) as srv:
            srv.load_model('m0', d)
            warmed = srv.warmup()
    assert warmed['m0']
    recs, _ = obs.read_journal(p)
    w = next(r for r in recs if r['ev'] == 'serving_warmup')
    # journal active -> warmup auto-enables capture; every per-bucket
    # pre-compile ledgered
    assert w['perf_ledgers'] >= len(warmed['m0'])
    assert sum(1 for r in recs if r['ev'] == 'perf_ledger') >= \
        w['perf_ledgers']
    assert obs_report.check_journal(p, require='perf') == []


# ---- lint rule -------------------------------------------------------------
def test_lint_forbids_new_direct_cost_analysis(tmp_path):
    src = 'def f(comp):\n    return comp.cost_analysis()\n'
    f = tmp_path / 'x.py'
    f.write_text(src)
    found, _ = lint_repo.lint_file(
        str(f), os.path.join('paddle_tpu', 'x.py'))
    hits = [v for v in found if v.rule == 'direct-cost-analysis']
    assert len(hits) == 1
    assert hits[0].detail == 'comp.cost_analysis()'
    # the observatory itself is the one exempt call site
    found, _ = lint_repo.lint_file(
        str(f), os.path.join('paddle_tpu', 'observability', 'perf.py'))
    assert not any(v.rule == 'direct-cost-analysis' for v in found)
    # the executor's pinned legacy entry is allowlisted, not deleted
    assert ('direct-cost-analysis:paddle_tpu/executor.py:'
            'comp.cost_analysis()') in lint_repo.ALLOWLIST
