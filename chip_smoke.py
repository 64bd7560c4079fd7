"""chip_smoke.py — does the main path still start on the chip?

    python3 chip_smoke.py

drives the Fluid training path once on the TPU this machine holds and
checks what comes out. It is the quickest proof that the system runs
where its users run it; it measures nothing (every time it prints is a
smoke observation, not a benchmark).

Two processes, one after the other, because a chip belongs to one
process at a time; this parent never touches JAX.

1. ``--phase main`` — in ONE process: ResNet-50 (3x224x224, batch 128,
   the BASELINE.json model) through ``Executor(TPUPlace(0))`` for eight
   steps with the loss fetched each step; the same program through
   ``benchmark/fluid/fluid_benchmark.py``; every Pallas family
   compiled non-interpret and checked against its jnp reference; one
   step each of the transformer, the stacked LSTM (as benchmarked, and
   without peepholes so that the fused cell engages) and SE-ResNeXt-50
   through the Executor, with the Mosaic custom calls counted in the
   lowered step; six Adam steps of the tiny hybrid stack (Mamba-2
   mixer, routed experts, grouped-query attention) under AMP, its
   scan state and router scores float32 in the lowered step, and one
   routed-experts layer wide enough for the Pallas grouped matmul
   against the ``lax.ragged_dot`` route on the same operands; six Adam
   steps of a tiny window / full attention stack with rotary positions,
   gated attention and gated experts under AMP, a windowed lowering and
   the gated products on the Pallas route; and, when four devices are
   visible, ResNet-50 through ``ParallelExecutor``.
2. ``--phase cache`` — a second process compiles the same ResNet-50
   step and must get it from the persistent compile cache.

Both refuse to start unless ``jax.default_backend() == 'tpu'``, before
any model is built. Every exception propagates. The last line of
stdout is ``{"ok": true, "device": {...}}`` only if every leg ran and
passed; otherwise the exit code is not 0 and there is no such line.

``--rehearse-cpu`` runs the main phase at toy sizes on the CPU backend
with the kernels interpreted, to debug this script. It cannot pass: it
prints no result line and exits 3.
"""
import argparse
import gc
import json
import math
import os
import subprocess
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_OUT = os.path.join(_HERE, 'chiprun_out', 'chip_smoke')

# seconds; the whole script must end within 1200
_PHASE_TIMEOUT = {'main': 900, 'cache': 240}

# Stated tolerances, relative to the reference's largest magnitude. The
# MXU rounds matmul/conv inputs to bf16 (verify skill, "TPU numerics"),
# and the kernels' operands here are bf16 or go through that rounding.
_TOL_KERNEL = 3e-2
_TOL_FOUR_CHIP_LOSS = 3e-2


def say(msg):
    print(msg, flush=True)


def check(ok, what):
    """A failed check fails the run — by raising, like everything
    else here."""
    if not ok:
        raise AssertionError('chip_smoke: FAILED: ' + what)
    say('  ok: ' + what)


# ---- the gate ------------------------------------------------------------
def require_backend(rehearse):
    import jax
    found = jax.default_backend()
    want = 'cpu' if rehearse else 'tpu'
    if found != want:
        sys.stderr.write(
            'chip_smoke: needs the %s backend; jax.default_backend() '
            'here is %r (devices: %s). Refusing to start.\n'
            % (want, found, jax.devices()))
        sys.exit(2)
    dev = jax.devices()[0]
    device = {'platform': dev.platform, 'kind': dev.device_kind,
              'count': len(jax.devices())}
    say('device: platform=%(platform)s kind=%(kind)s count=%(count)d'
        % device + ' jax=%s' % jax.__version__)
    return device


def sizes(rehearse):
    """Full width on the chip; toy sizes only under --rehearse-cpu."""
    if not rehearse:
        return dict(
            resnet_batch=128, resnet_steps=8, bench_iters=5,
            flash=(16, 2048, 8, 128), flash_ref_rows=2,
            lstm_cell=(256, 512), lstm_batch=256,
            transformer_batch=16,
            transformer=dict(n_heads=8, n_layers=2),  # depth cut 6 -> 2
            conv_n=8, conv_scale=1, interpret=False)
    return dict(
        resnet_batch=2, resnet_steps=3, bench_iters=1,
        flash=(2, 256, 2, 128), flash_ref_rows=2,
        lstm_cell=(8, 128), lstm_batch=4,
        transformer_batch=2,
        transformer=dict(n_heads=2, n_layers=1, vocab=256, d_model=256,
                         d_ff=256, seq=256),
        conv_n=2, conv_scale=8, interpret=True)


def _models():
    bench_dir = os.path.join(_HERE, 'benchmark', 'fluid')
    if bench_dir not in sys.path:
        sys.path.insert(0, bench_dir)
    from models import MODELS
    return MODELS


def _cache_counts():
    """What JAX's persistent compile cache did in this process so far,
    from the program's own listener (``observability.tracing``: the one
    ``jax.monitoring`` registration of the tree): hits and entries
    written from the registry, compile seconds saved from the log."""
    from paddle_tpu import observability as obs
    reg = obs.default_registry()
    return {
        'hits': reg.counter('jax_persistent_cache_total',
                            result='hit').value,
        'writes': reg.counter('jax_persistent_cache_total',
                              result='miss').value,
        'saved_s': sum(e.get('saved_s', 0.0)
                       for e in obs.perf.compile_log())}


def _build(fluid, name, **kwargs):
    """benchmark/fluid/models.py::<name> -> Momentum.minimize, as
    fluid_benchmark.py builds it. Built under a fresh name
    scope, so every build in every process is the same program."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        loss, feed_fn, _unit = _models()[name](None, **kwargs)
        fluid.optimizer.Momentum(learning_rate=0.01,
                                 momentum=0.9).minimize(loss)
    return main, startup, loss, feed_fn


def _place(fluid, rehearse):
    return fluid.CPUPlace(0) if rehearse else fluid.TPUPlace(0)


def _fuse_delta(before, after):
    """conv_fuse_counts() of what was lowered between two readings."""
    falls = {r: n - before['fallbacks'].get(r, 0)
             for r, n in after['fallbacks'].items()}
    return {'engaged': after['engaged'] - before['engaged'],
            'fallbacks': {r: n for r, n in falls.items() if n}}


def _report_fuse(counts):
    say('  fused conv on this step: engaged=%d fallbacks=%s'
        % (counts['engaged'], json.dumps(counts['fallbacks'],
                                         sort_keys=True)))
    check(not any(r.startswith('error') for r in counts['fallbacks']),
          'no error:* fallback reason')


def _all_finite(xs):
    return all(math.isfinite(float(x)) for x in xs)


# ---- leg 1: ResNet-50 through the Executor -------------------------------
def leg_resnet_executor(cfg, rehearse):
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.compiler.passes import conv_fuse_counts
    say('[resnet50/executor] batch %d, %d steps'
        % (cfg['resnet_batch'], cfg['resnet_steps']))
    place = _place(fluid, rehearse)
    dev = place.jax_device()
    main, startup, loss, feed_fn = _build(fluid, 'resnet')
    feed = feed_fn(cfg['resnet_batch'])      # one fixed synthetic batch
    scope = fluid.Scope()
    losses, walls = [], []
    fuse0 = conv_fuse_counts()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(place)
        t0 = time.perf_counter()
        exe.run(startup)
        startup_s = time.perf_counter() - t0
        for _ in range(cfg['resnet_steps']):
            t0 = time.perf_counter()
            out, = exe.run(main, feed=feed, fetch_list=[loss])
            walls.append(time.perf_counter() - t0)
            losses.append(float(np.ravel(out)[0]))   # on the host
        info = exe.cache_info()
        state = {v.name: scope.raw(v.name)
                 for v in main.global_block().vars.values()
                 if v.persistable and scope.raw(v.name) is not None}
    say('  losses: ' + ' '.join('%.4f' % v for v in losses))
    say('  first step (compile + run) %.1f s; startup %.1f s; later '
        'steps %s s (smoke observations, not measurements)'
        % (walls[0], startup_s,
           ' '.join('%.3f' % w for w in walls[1:])))
    check(_all_finite(losses), 'losses finite')
    check(losses[-1] < losses[0],
          'loss falls on the fixed batch (%.4f -> %.4f)'
          % (losses[0], losses[-1]))
    check(info.misses == 2 and info.hits == cfg['resnet_steps'] - 1,
          'one compile of the step after warm-up (cache_info: %d '
          'misses = startup + step, %d hits)' % (info.misses, info.hits))
    off = [n for n, v in state.items()
           if hasattr(v, 'devices') and v.devices() != {dev}]
    check(state and not off,
          '%d state arrays resident on %s' % (len(state), dev))
    counts = _fuse_delta(fuse0, conv_fuse_counts())
    _report_fuse(counts)
    stats = dev.memory_stats() or {}
    if stats:
        say('  peak_bytes_in_use %.2f GiB'
            % (stats.get('peak_bytes_in_use', 0) / 2.0 ** 30))
    return {'losses': losses, 'first_step_s': walls[0],
            'step_s': walls[1:], 'conv_fuse': counts,
            'peak_bytes': stats.get('peak_bytes_in_use')}


# ---- leg 2: the same through the benchmark harness -----------------------
def leg_fluid_benchmark(cfg, rehearse):
    import paddle_tpu.fluid as fluid
    say('[resnet50/fluid_benchmark.py] --model resnet --batch_size %d '
        '--device %s' % (cfg['resnet_batch'],
                         'CPU' if rehearse else 'TPU'))
    _models()
    import fluid_benchmark
    hits0 = _cache_counts()['hits']
    with fluid.scope_guard(fluid.Scope()), fluid.unique_name.guard():
        rec = fluid_benchmark.main([
            '--model', 'resnet',
            '--batch_size', str(cfg['resnet_batch']),
            '--device', 'CPU' if rehearse else 'TPU',
            '--skip_batch_num', '1',
            '--iterations', str(cfg['bench_iters'])])
    check(math.isfinite(rec['last_loss']), 'harness loss finite (%.4f)'
          % rec['last_loss'])
    say('  same program, second compile in this process: %d persistent-'
        'cache hit(s)' % (_cache_counts()['hits'] - hits0))
    return rec


# ---- leg 3: each Pallas family against its reference ---------------------
def _rel_err(got, want):
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want))
                 / (np.max(np.abs(want)) + 1e-6))


def _kernel_check(name, got, want):
    err = _rel_err(got, want)
    check(err < _TOL_KERNEL,
          '%s within %.0e of reference (max rel err %.2e)'
          % (name, _TOL_KERNEL, err))
    return err


def leg_flash(cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import pallas_kernels as pk
    B, T, H, D = cfg['flash']
    rows = cfg['flash_ref_rows']
    interpret = cfg['interpret']
    bq = bk = min(1024, T)
    say('[pallas/flash] B%d S%d H%d D%d bf16, blocks %dx%d (reference '
        'on the first %d batch rows)' % (B, T, H, D, bq, bk, rows))
    rng = np.random.RandomState(0)
    q, k, v, g = (jnp.asarray(rng.randn(B, T, H, D) * 0.5, jnp.bfloat16)
                  for _ in range(4))

    def loss(fn):
        return lambda q, k, v, g: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * g.astype(jnp.float32))

    def kernel(q, k, v):
        return pk._flash_lse(q, k, v, True, bq, bk, interpret)[0]

    want = pk.attention_reference(q[:rows], k[:rows], v[:rows])
    want_g = jax.jit(jax.grad(loss(pk.attention_reference),
                              argnums=(0, 1, 2)))(
        q[:rows], k[:rows], v[:rows], g[:rows])
    errs = {'fwd': _kernel_check('flash forward',
                                 jax.jit(kernel)(q, k, v)[:rows], want)}
    # the two passes are reached as a long sequence reaches them: by a
    # dq slab over the cap
    cap = pk._MERGED_BWD_MAX_SLAB_BYTES
    for label, slab_cap in (('merged', cap), ('two-pass', 0)):
        pk._MERGED_BWD_MAX_SLAB_BYTES = slab_cap
        try:
            got_g = jax.jit(jax.grad(loss(kernel), argnums=(0, 1, 2)))(
                q, k, v, g)
        finally:
            pk._MERGED_BWD_MAX_SLAB_BYTES = cap
        for nm, a, b in zip(('dq', 'dk', 'dv'), got_g, want_g):
            errs['%s %s' % (label, nm)] = _kernel_check(
                'flash %s backward %s' % (label, nm), a[:rows], b)
    return errs


def leg_lstm_cell(cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import pallas_kernels as pk
    B, H = cfg['lstm_cell']
    say('[pallas/lstm_cell] B%d H%d' % (B, H))
    rng = np.random.RandomState(1)
    errs = {}
    for dt in (jnp.float32, jnp.bfloat16):
        xg = jnp.asarray(rng.randn(B, 4 * H), jnp.float32)
        r = jnp.asarray(rng.randn(B, H) * 0.5, dt)
        c = jnp.asarray(rng.randn(B, H) * 0.5, jnp.float32)
        w = jnp.asarray(rng.randn(H, 4 * H) / math.sqrt(H), dt)
        got = jax.jit(lambda *a: pk._lstm_cell(*a, cfg['interpret']))(
            xg, r, c, w)
        want = pk._lstm_cell_reference(
            xg, r.astype(jnp.float32), c, w.astype(jnp.float32))
        for nm, a, b in zip(('h', 'c'), got, want):
            errs['%s %s' % (dt.__name__, nm)] = _kernel_check(
                'lstm cell %s (%s weights)' % (nm, dt.__name__), a, b)
    return errs


# (label, H=W, cin, cout, k, stride, pad, depthwise): ResNet-50 and
# SE-ResNeXt-50 conv shapes with >= 128 output channels
_CONV_SHAPES = (
    ('1x1 s1', 56, 64, 256, 1, 1, 0, False),
    ('1x1 s1', 14, 1024, 256, 1, 1, 0, False),
    ('1x1 s2', 56, 256, 512, 1, 2, 0, False),
    ('3x3 s1', 28, 128, 128, 3, 1, 1, False),
    ('3x3 s1', 14, 256, 256, 3, 1, 1, False),
    ('3x3 s2', 56, 128, 128, 3, 2, 1, False),
    ('3x3 s2', 14, 512, 512, 3, 2, 1, False),
    ('depthwise 3x3 s1', 28, 256, 256, 3, 1, 1, True),
    ('depthwise 3x3 s2', 28, 256, 256, 3, 2, 1, True),
)


def leg_fused_conv(cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import pallas_kernels as pk
    N, scale = cfg['conv_n'], cfg['conv_scale']
    say('[pallas/fused_conv] N=%d, conv + BN-affine + residual + relu '
        '+ SE scale; and emit_stats' % N)
    rng = np.random.RandomState(2)
    errs, refused = {}, {}
    for label, hw, cin, cout, k, s, p, dw in _CONV_SHAPES:
        hw, cin, cout = hw // scale or 1, cin // scale, cout // scale
        hw = max(hw, k)
        ho = (hw + 2 * p - k) // s + 1
        for dt, stats in ((jnp.float32, False), (jnp.bfloat16, False),
                          (jnp.float32, True)):
            tag = '%s %dx%d %d->%d %s%s' % (
                label, hw, hw, cin, cout, dt.__name__,
                ' emit_stats' if stats else '')
            x = jnp.asarray(rng.randn(N, hw, hw, cin), dt)
            w = jnp.asarray(
                rng.randn(*((k, k, cin) if dw else (k, k, cin, cout)))
                / math.sqrt(k * k * (1 if dw else cin)), dt)
            if stats:
                aux, kinds, stages = (), (), ()
            else:
                aux = (jnp.asarray(rng.rand(1, cout) + 0.5, jnp.float32),
                       jnp.asarray(rng.randn(1, cout), jnp.float32),
                       jnp.asarray(rng.randn(N, ho, ho, cout), dt),
                       jnp.asarray(rng.rand(N, cout), jnp.float32))
                kinds = ('c', 'c', 't', 'nc')
                stages = (('affine', 0, 1),
                          ('bin', 'elementwise_add', 2, True),
                          ('act', 'relu'),
                          ('bin', 'elementwise_mul', 3, False))
            why = []

            def fused(x, w, *aux):
                got, reason = pk.fused_conv_epilogue(
                    x, w, aux, kinds, (s, s), (p, p), dw, stages,
                    emit_stats=stats, interpret=cfg['interpret'])
                why.append(reason)
                return got

            if jax.eval_shape(fused, x, w, *aux) is None:
                # refused by predicate: the program would replay the
                # unfused ops and count the reason
                refused[tag] = why[0]
                say('  refused by predicate (%s): %s' % (why[0], tag))
                continue
            got = jax.jit(fused)(x, w, *aux)
            conv = jax.lax.conv_general_dilated(
                x.astype(jnp.float32),
                (w[:, :, None, :] if dw else w).astype(jnp.float32),
                (s, s), [(p, p), (p, p)],
                feature_group_count=cin if dw else 1,
                dimension_numbers=('NHWC', 'HWIO', 'NHWC'),
                precision=jax.lax.Precision.HIGHEST)
            if stats:
                y, psum, psumsq = got
                errs[tag] = max(
                    _kernel_check(tag + ' y', y, conv),
                    _kernel_check(tag + ' sum', jnp.sum(psum, (0, 1)),
                                  jnp.sum(conv, (0, 1, 2))),
                    _kernel_check(tag + ' sumsq',
                                  jnp.sum(psumsq, (0, 1)),
                                  jnp.sum(conv * conv, (0, 1, 2))))
            else:
                want = conv * aux[0].reshape(1, 1, 1, -1) \
                    + aux[1].reshape(1, 1, 1, -1)
                want = jax.nn.relu(aux[2].astype(jnp.float32) + want) \
                    * aux[3][:, None, None, :]
                errs[tag] = _kernel_check(tag, got, want)
    check(errs, 'at least one fused conv compiled and ran')
    return {'errs': errs, 'refused': refused}


# ---- legs 4, 5: model steps whose lowering must hold Mosaic calls --------
def leg_model_step(rehearse, name, batch, kwargs, engages):
    """One training step of a benchmark model through the Executor.
    ``engages`` names the Pallas kernel its lowering must hold, or is
    None where none is expected (the count is printed either way)."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.compiler.passes import conv_fuse_counts
    say('[%s/executor] batch %d %s' % (name, batch, kwargs or ''))
    main, startup, loss, feed_fn = _build(fluid, name, **kwargs)
    feed = feed_fn(batch)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(_place(fluid, rehearse))
        exe.run(startup)
        before = conv_fuse_counts()
        mosaic = exe.lowered(main, feed, [loss]).as_text().count(
            'tpu_custom_call')
        fuse = _fuse_delta(before, conv_fuse_counts())
        t0 = time.perf_counter()
        out, = exe.run(main, feed=feed, fetch_list=[loss])
        wall = time.perf_counter() - t0
    value = float(np.ravel(out)[0])
    say('  loss %.4f; compile + one step %.1f s (smoke observation)'
        % (value, wall))
    check(math.isfinite(value), 'loss finite')
    if fuse['engaged'] or fuse['fallbacks']:
        _report_fuse(fuse)
    if rehearse or engages is None:
        say('  %d Mosaic custom call(s) in the lowered step' % mosaic)
    else:
        check(mosaic >= 1, 'the lowered step holds %d Mosaic custom '
              'call(s): %s engaged' % (mosaic, engages))
    return {'loss': value, 'mosaic_calls': mosaic, 'conv_fuse': fuse}


# ---- leg 5b: the tiny hybrid (Mamba-2 / experts / GQA) under AMP ----------
def leg_hybrid(rehearse, steps=6):
    """A few Adam steps of the tiny hybrid stack the benchmark's tests
    use (benchmark/chip/tests/tiny_nemotron: pattern ME*E, 2 groups, 4
    query heads on 2 KV heads, 8 of 16 experts held) through the
    Executor under the backend's own AMP: the loss falls, and in the
    lowered step the scan's carried state and the router's scores are
    float32 whatever the stream's dtype."""
    import importlib.util
    import re
    import jax
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.core import amp
    chip = os.path.join(_HERE, 'benchmark', 'chip')
    spec = importlib.util.spec_from_file_location(
        'chip_models_nemotron_h',
        os.path.join(chip, 'models', 'nemotron_h.py'))
    model = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(model)
    with open(os.path.join(chip, 'tests', 'tiny_nemotron', 'configs',
                           'nemotron3-super-120b-a12b.json')) as f:
        cfg = json.load(f)
    cfg['optimizer'] = dict(cfg['optimizer'], learning_rate=3e-3)
    traffic = {'batch': 2, 'seq_len': 200}
    say('[hybrid/executor] pattern %s, %d x %d tokens, AMP %s'
        % (cfg['hybrid_override_pattern'], traffic['batch'],
           traffic['seq_len'], 'on' if amp.amp_enabled() else 'off'))
    built = model.build(cfg, traffic)
    feed = {k: np.asarray(v) for k, v in model.draw_batch(
        cfg, traffic, jax.random.PRNGKey(0)).items()}
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(_place(fluid, rehearse))
        exe.run(built['startup'])
        text = exe.lowered(built['main'], feed, [built['loss']]).as_text()
        losses = [float(np.ravel(exe.run(
            built['main'], feed=feed, fetch_list=[built['loss']])[0])[0])
            for _ in range(steps)]
    say('  losses: ' + ' '.join('%.4f' % v for v in losses))
    check(_all_finite(losses) and losses[-1] < losses[0],
          'losses finite and falling over %d steps' % steps)
    # [.., P, N] = [.., 8, 16]: the state a scan's loop carries
    states = re.findall(r'tensor<[0-9x]*x8x16x(\w+)>', ' '.join(
        re.findall(r'stablehlo\.while.*', text)))
    check(states and set(states) == {'f32'},
          'the scan\'s carried state is float32 (%d carries)' % len(states))
    scores = re.findall(r'chlo\.top_k.*: tensor<[0-9x]*x(\w+)>', text)
    check(scores and set(scores) == {'f32'},
          'the router chooses over float32 scores')
    mosaic = text.count('tpu_custom_call')
    say('  %d Mosaic custom call(s) in the lowered step' % mosaic)
    return {'losses': losses, 'mosaic_calls': mosaic,
            'routed_layer': _routed_layer_on_both_routes(rehearse)}


def _routed_layer_on_both_routes(rehearse):
    """The tiny stack is too narrow for the Pallas grouped matmul to
    engage (latent 12, experts 20 wide). One ``routed_experts`` layer at
    the smallest widths that rule takes (128 -> 128, 256 tokens, 8 of
    16 experts held, top 2, a 512-row chunk), under the backend's AMP:
    the counter says 'pallas', the output and both weight gradients are
    those of the ``lax.ragged_dot`` route on the same operands, and an
    expert the bias keeps every token from gets exactly zero."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.compiler.passes import moe_counts
    from paddle_tpu.ops import pallas_kernels as pk
    B, T, L, F, E, held, top_k, idle = 2, 128, 128, 128, 16, 8, 2, 3
    rng = np.random.RandomState(0)
    feed = {'x': rng.randn(B, T, L).astype('float32'),
            'scores': rng.rand(B, T, E).astype('float32'),
            'g': rng.randn(B, T, L).astype('float32')}
    weights = [rng.randn(held, L, F).astype('float32') * 0.1,
               rng.randn(held, F, L).astype('float32') * 0.1,
               np.where(np.arange(E) == idle, -100.0, 0.0).astype('float32')]

    def run():
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            x, scores, g = (fluid.layers.data(
                name=n, shape=list(feed[n].shape[1:]), dtype='float32')
                for n in ('x', 'scores', 'g'))
            out, tokens = fluid.layers.routed_experts(
                x, scores, hidden_size=F, num_experts=E, top_k=top_k,
                experts_held=(0, held))
            params = main.global_block().all_parameters()
            loss = fluid.layers.reduce_sum(
                fluid.layers.elementwise_mul(out, g))
            grads = fluid.gradients(loss, params[:2])
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(_place(fluid, rehearse))
            exe.run(startup)
            for p, w in zip(params, weights):
                scope.set_var(p.name, np.array(w))
            before = moe_counts(by=('route',))
            got = exe.run(main, feed=feed,
                          fetch_list=[out, tokens] + grads)
        routes = {k[0] for k, n in moe_counts(by=('route',)).items()
                  if n != before.get(k, 0)}
        return [np.asarray(a) for a in got], routes

    (out, tokens, dw1, dw2), routes = run()
    say('[hybrid/routed layer] %d x %d tokens, %d -> %d, route %s, '
        'tokens an expert %s' % (B, T, L, F, sorted(routes), tokens.tolist()))
    if not rehearse:
        check(routes == {'pallas'},
              'moe_lowerings_total says the Pallas grouped matmul engaged')
    plan = pk.grouped_plan
    pk.grouped_plan = lambda rows, w, interpret=None: None
    try:
        (ref_out, ref_tokens, ref_dw1, ref_dw2), routes = run()
    finally:
        pk.grouped_plan = plan
    check(routes == {'ragged_dot'} and tokens.tolist() == ref_tokens.tolist()
          and tokens[idle] == 0 and tokens.sum() > 0,
          'the same routing through lax.ragged_dot, expert %d idle' % idle)
    worst = {}
    for name, a, b in (('out', out, ref_out), ('dW1', dw1, ref_dw1),
                       ('dW2', dw2, ref_dw2)):
        worst[name] = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
        check(np.isfinite(a).all() and worst[name] <= _TOL_KERNEL,
              '%s within %.0e of the ragged_dot route (%.2e)'
              % (name, _TOL_KERNEL, worst[name]))
    check(not dw1[idle].any() and not dw2[idle].any(),
          'the idle expert\'s weight gradients are exactly zero')
    return worst


# ---- leg 5c: window and full attention, rotary, gated experts, under AMP ---
def leg_window_stack(rehearse, steps=6):
    """A few Adam steps of a tiny stack as benchmark/chip/models/afmoe.py
    builds it (layers S S F S after... the first is the dense one; 4
    query heads on 2 KV heads at head size 64, a window of a quarter of
    the sequence, rotary on the window layers, gated attention, 8 of
    16 gated experts held, one shared) through the Executor under the
    backend's own AMP: the loss falls; the windowed lowerings took the
    Pallas kernels, which skip the tiles below the band, and the gated
    experts' products the Pallas grouped matmul; the rotary angles are
    float32 in the lowered step."""
    import importlib.util
    import re
    import jax
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.compiler.passes import (flash_counts, moe_counts,
                                            window_flash_counts)
    from paddle_tpu.core import amp
    spec = importlib.util.spec_from_file_location(
        'chip_models_afmoe', os.path.join(
            _HERE, 'benchmark', 'chip', 'models', 'afmoe.py'))
    model = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(model)
    T = 256 if rehearse else 4096
    cfg = {
        'layer_types': [model.SLIDING, model.SLIDING, model.FULL,
                        model.SLIDING],
        'num_hidden_layers': 4, 'num_dense_layers': 1, 'hidden_size': 128,
        'vocab_size': 256, 'rms_norm_eps': 1e-5, 'num_attention_heads': 4,
        'num_key_value_heads': 2, 'head_dim': 64, 'sliding_window': T // 4,
        'rope_theta': 10000.0, 'intermediate_size': 256,
        'moe_intermediate_size': 128, 'num_shared_experts': 1,
        'router_num_experts': 16, 'num_experts': 8, 'experts_first': 4,
        'num_experts_per_tok': 2, 'route_scale': 2.826, 'route_norm': True,
        'score_func': 'sigmoid', 'hidden_act': 'silu', 'mup_enabled': True,
        'tie_word_embeddings': False,
        'optimizer': {'learning_rate': 3e-3, 'beta1': 0.9, 'beta2': 0.95,
                      'epsilon': 1e-8}}
    traffic = {'batch': 1, 'seq_len': T}
    say('[window stack/executor] layers S S F S, 1 x %d tokens, window %d, '
        'AMP %s' % (T, cfg['sliding_window'],
                    'on' if amp.amp_enabled() else 'off'))
    built = model.build(cfg, traffic)
    feed = {k: np.asarray(v) for k, v in model.draw_batch(
        cfg, traffic, jax.random.PRNGKey(0)).items()}

    def counts():
        return (flash_counts(by=('route', 'window')), window_flash_counts(),
                moe_counts(by=('route', 'act')))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(_place(fluid, rehearse))
        exe.run(built['startup'])
        before = counts()
        text = exe.lowered(built['main'], feed, [built['loss']]).as_text()
        after = counts()
        losses = [float(np.ravel(exe.run(
            built['main'], feed=feed, fetch_list=[built['loss']])[0])[0])
            for _ in range(steps)]
    moved = [{k: n - was.get(k, 0) for k, n in now.items()
              if n != was.get(k, 0)} for was, now in zip(before, after)]
    say('  losses: ' + ' '.join('%.4f' % v for v in losses))
    say('  lowerings (route, window): %s; windowed on the kernels: %s; '
        'experts (route, act): %s' % tuple(moved))
    check(_all_finite(losses) and losses[-1] < losses[0],
          'losses finite and falling over %d steps' % steps)
    trig = re.findall(r'stablehlo\.(?:cosine|sine) .*tensor<[0-9x]*x(\w+)>',
                      text)
    check(trig and set(trig) == {'f32'},
          'the rotary angles are float32 (%d cos / sin)' % len(trig))
    if not rehearse:
        window = str(cfg['sliding_window'])
        check(moved[0] == {('pallas', window): 3, ('pallas', '0'): 1},
              'three windowed lowerings and the full one took the kernels')
        check(moved[1] == {(window,): 3},
              'window_flash_counts() reads the three')
        check(moved[2] == {('pallas', 'swiglu'): 3},
              'the gated experts\' products took the Pallas grouped matmul')
    mosaic = text.count('tpu_custom_call')
    say('  %d Mosaic custom call(s) in the lowered step' % mosaic)
    return {'losses': losses, 'mosaic_calls': mosaic}


# ---- leg 6: four chips, one process --------------------------------------
def leg_four_chips(cfg, one_chip_losses):
    import jax
    import numpy as np
    import paddle_tpu.fluid as fluid
    n = len(jax.devices())
    if n < 4:
        say('[resnet50/parallel_executor] skipped: %d device(s)' % n)
        return None
    say('[resnet50/parallel_executor] 4 devices, global batch %d'
        % cfg['resnet_batch'])
    main, startup, loss, feed_fn = _build(fluid, 'resnet')
    feed = feed_fn(cfg['resnet_batch'])
    steps = min(4, len(one_chip_losses))

    def in_use():
        return {d.id: (d.memory_stats() or {}).get('bytes_in_use', 0)
                for d in jax.devices()[:4]}

    gc.collect()        # the earlier legs ran on device 0 alone
    before = in_use()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.TPUPlace(0)).run(startup)
        pe = fluid.ParallelExecutor(loss_name=loss.name,
                                    main_program=main, num_devices=4)
        part = pe.partitioner
        staged = part.stage(feed)
        losses = [float(np.ravel(pe.run(fetch_list=[loss],
                                        feed=staged)[0])[0])
                  for _ in range(steps)]
        say('  losses: ' + ' '.join('%.4f' % v for v in losses)
            + '   one chip: '
            + ' '.join('%.4f' % v for v in one_chip_losses[:steps]))
        check(_all_finite(losses), 'losses finite')
        worst = max(abs(a - b) / abs(b)
                    for a, b in zip(losses, one_chip_losses))
        check(worst < _TOL_FOUR_CHIP_LOSS,
              'per-step loss within %.0e of the one-chip run (worst '
              'rel diff %.2e)' % (_TOL_FOUR_CHIP_LOSS, worst))

        def shards(arr):
            return sorted((s.device.id, tuple(s.data.shape))
                          for s in arr.addressable_shards)

        zero = {k: v for k, v in pe._zero.items()
                if not k.endswith('_names')}
        evidence = {'zero': zero}
        img = staged['data']
        evidence['feed'] = shards(img)
        say('  feed %s shards: %s' % (img.shape, evidence['feed']))
        check(len({d for d, _ in evidence['feed']}) == 4
              and all(s[0] * 4 == img.shape[0]
                      for _, s in evidence['feed']),
              'feed batch split over 4 distinct devices')
        block = main.global_block()
        params = [v.name for v in block.all_parameters()]
        pname = max(params, key=lambda p: scope.raw(p).size)
        evidence['param'] = shards(scope.raw(pname))
        say('  param %s %s replicas: %s'
            % (pname, scope.raw(pname).shape, evidence['param']))
        check(len({d for d, _ in evidence['param']}) == 4
              and all(s == tuple(scope.raw(pname).shape)
                      for _, s in evidence['param']),
              'parameters replicated on 4 distinct devices')
        sharded = [v.name for v in block.vars.values()
                   if v.persistable and getattr(v, 'sharding', None)
                   and scope.raw(v.name) is not None]
        check(sharded, 'ZeRO annotated %d optimizer-state var(s) (%s)'
              % (len(sharded), zero))
        oname = max(sharded, key=lambda p: scope.raw(p).size)
        oval = scope.raw(oname)
        evidence['opt'] = shards(oval)
        say('  optimizer state %s %s shards: %s'
            % (oname, oval.shape, evidence['opt']))
        check(len({d for d, _ in evidence['opt']}) == 4
              and all(int(np.prod(s)) * 4 == oval.size
                      for _, s in evidence['opt']),
              'ZeRO optimizer shards: a quarter each on 4 distinct '
              'devices')
        after = in_use()
        mem = {d: after[d] - before[d] for d in after}
        evidence['bytes_in_use'] = {'before': before, 'after': after}
        say('  bytes_in_use per device, before this leg: %s' % before)
        say('  bytes_in_use per device, with its state live: %s' % after)
        check(min(mem.values()) > 0 and
              max(mem.values()) < 2 * min(mem.values()),
              'this leg\'s memory is on all four devices, none holding '
              'twice another (nothing stacked on device 0)')
    evidence['losses'] = losses
    return evidence


# ---- phases --------------------------------------------------------------
def phase_main(rehearse):
    device = require_backend(rehearse)
    cfg = sizes(rehearse)
    from paddle_tpu.core.compile_cache import compile_cache_dir
    say('compile cache: %s' % compile_cache_dir())
    result = {'device': device}
    result['resnet'] = leg_resnet_executor(cfg, rehearse)
    result['fluid_benchmark'] = leg_fluid_benchmark(cfg, rehearse)
    result['flash'] = leg_flash(cfg)
    result['lstm_cell'] = leg_lstm_cell(cfg)
    result['fused_conv'] = leg_fused_conv(cfg)
    result['transformer'] = leg_model_step(
        rehearse, 'transformer', cfg['transformer_batch'],
        cfg['transformer'], 'flash attention forward and backward')
    # as benchmarked: dynamic_lstm's default peepholes keep the fused
    # cell out of this model (PERF.md, "Bring-up on the chip")
    result['stacked_lstm'] = leg_model_step(
        rehearse, 'stacked_dynamic_lstm', cfg['lstm_batch'], {}, None)
    result['stacked_lstm_no_peepholes'] = leg_model_step(
        rehearse, 'stacked_dynamic_lstm', cfg['lstm_batch'],
        {'use_peepholes': False}, 'the fused LSTM cell')
    # no Pallas conv is expected here: until PR 30 the excitation's
    # elementwise_mul widened the stream to float32 and 18 convs engaged
    # on it; under AMP's rule the stream stays bf16 and every conv
    # declines with reason dtype, as in ResNet-50 (leg_fused_conv above
    # checks the kernel itself)
    result['se_resnext'] = leg_model_step(
        rehearse, 'se_resnext', cfg['resnet_batch'], {}, None)
    result['hybrid'] = leg_hybrid(rehearse)
    result['window_stack'] = leg_window_stack(rehearse)
    if rehearse:
        say('[resnet50/parallel_executor] not rehearsed')
    else:
        result['four_chips'] = leg_four_chips(
            cfg, result['resnet']['losses'])
    events = result['cache_events'] = _cache_counts()
    say('persistent compile cache in this process: %d hit(s), %d '
        'entr(ies) written' % (events['hits'], events['writes']))
    return result


def phase_cache():
    """A second process: the ResNet-50 step must come from the
    persistent compile cache the main phase filled."""
    device = require_backend(False)
    cfg = sizes(False)
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.core.compile_cache import compile_cache_dir
    say('[resnet50/second process] compile cache: %s'
        % compile_cache_dir())
    main, startup, loss, feed_fn = _build(fluid, 'resnet')
    feed = feed_fn(cfg['resnet_batch'])
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.TPUPlace(0))
        exe.run(startup)
        t0 = time.perf_counter()
        out, = exe.run(main, feed=feed, fetch_list=[loss])
        first = time.perf_counter() - t0
    events = _cache_counts()
    say('  first step %.1f s, loss %.4f; cache hits %d, entries '
        'written %d, compile seconds saved %.1f'
        % (first, float(np.ravel(out)[0]), events['hits'],
           events['writes'], events['saved_s']))
    check(events['hits'] >= 1 and events['saved_s'] >= 5.0,
          'the step came from the persistent cache (an entry whose '
          'first compile took seconds was read, not rebuilt)')
    return {'device': device, 'first_step_s': first,
            'loss': float(np.ravel(out)[0]), 'events': events}


def run_phase(phase, rehearse=False):
    result = phase_main(rehearse) if phase == 'main' else phase_cache()
    if rehearse:
        return
    os.makedirs(_OUT, exist_ok=True)
    with open(os.path.join(_OUT, phase + '.json'), 'w') as f:
        json.dump(result, f, indent=1, sort_keys=True, default=str)


def orchestrate():
    """The parent: never imports JAX, so each child has the chip to
    itself; stops the child it started when its time is up."""
    results = {}
    for phase in ('main', 'cache'):
        path = os.path.join(_OUT, phase + '.json')
        if os.path.exists(path):
            os.remove(path)
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), '--phase',
             phase])
        try:
            rc = proc.wait(timeout=_PHASE_TIMEOUT[phase])
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            sys.stderr.write('chip_smoke: phase %r exceeded %d s; '
                             'killed\n' % (phase, _PHASE_TIMEOUT[phase]))
            return 124
        if rc != 0:
            sys.stderr.write('chip_smoke: phase %r exited %d\n'
                             % (phase, rc))
            return rc or 1
        with open(path) as f:
            results[phase] = json.load(f)
    cold = results['main']['resnet']['first_step_s']
    warm = results['cache']['first_step_s']
    say('ResNet-50 first step: %.1f s compiling, %.1f s from the '
        'persistent cache in a second process (smoke observations)'
        % (cold, warm))
    say(json.dumps({'ok': True, 'device': results['main']['device']}))
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--phase', choices=('main', 'cache'),
                    help='internal: run one phase in this process')
    ap.add_argument('--rehearse-cpu', action='store_true',
                    help='toy-size CPU run to debug this script; '
                         'prints no result and exits 3')
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        run_phase('main', rehearse=True)
        say('REHEARSAL ONLY (cpu backend, toy sizes, interpreted '
            'kernels): this is not a result.')
        return 3
    if args.phase:
        run_phase(args.phase)
        return 0
    return orchestrate()


if __name__ == '__main__':
    sys.exit(main())
