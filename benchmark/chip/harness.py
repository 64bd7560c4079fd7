"""The run of one cell: build the program, drive it from the seed, time
a window, trace a few steps, and compare what the timed path produced
with the plain reference. Everything that belongs to one cell, one
configuration, one traffic mix or one metric is data, found by the name
``BENCHMARK.json`` gives (``manifest.py``).
"""
import collections
import contextlib
import functools
import gc
import importlib
import json
import os
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SPANS = ('feed', 'dispatch', 'fetch')


def log(msg):
    sys.stderr.write('[chip-bench] %s\n' % msg)
    sys.stderr.flush()


def model_module(cfg):
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    return importlib.import_module('models.' + cfg['model_module'])


def key_of(seed):
    """A PRNG key from any whole number (the driver's seeds pass 2**31,
    which a 32-bit PRNGKey argument would refuse)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7fffffff),
                              seed >> 31)


# ---- the traffic: one general generator ------------------------------------
class Feeder(object):
    """A pool of ``pool`` batches drawn from the seed by the model's
    ``draw_batch``, rotated one a step. ``placement`` ``resident`` keeps
    them on the device(s), staged as the executor shards a feed;
    ``host`` hands numpy to every step. Every seed gives the same sizes;
    only the values and their order differ."""

    def __init__(self, model, cfg, traffic, seed, stage):
        import jax
        key = jax.random.fold_in(key_of(seed), 1)
        draw = jax.jit(lambda k: model.draw_batch(cfg, traffic, k))
        self.batches = []
        for i in range(traffic['pool']):
            b = draw(jax.random.fold_in(key, i))
            if traffic['placement'] == 'resident':
                b = stage(b)
            elif traffic['placement'] == 'host':
                b = {k: np.asarray(v) for k, v in b.items()}
            else:
                raise ValueError('unknown placement %r'
                                 % traffic['placement'])
            self.batches.append(b)

    def feed(self, step):
        return self.batches[step % len(self.batches)]

    def first(self, n):
        """The first ``n`` steps' batches, for the reference."""
        return [self.batches[i % len(self.batches)] for i in range(n)]


# ---- the system under test -------------------------------------------------
class Session(object):
    """The compiled step with its state: ``Executor`` on one chip,
    ``ParallelExecutor`` over several. One object serves the first
    (compared) steps and the window."""

    def __init__(self, model, cfg, traffic, devices):
        import paddle_tpu.fluid as fluid
        self.fluid = fluid
        self.built = model.build(cfg, traffic)
        self.main, self.startup = self.built['main'], self.built['startup']
        self.loss = self.built['loss']
        self.scope = fluid.Scope()
        self.devices = devices
        place = self._place(devices[0])
        self.exe0 = fluid.Executor(place)
        self.pe = None
        self.executor = traffic.get('executor', 'executor')

    def _place(self, dev):
        fluid = self.fluid
        if dev.platform == 'tpu':
            return fluid.TPUPlace(dev.id)
        return fluid.CPUPlace(0)

    def start(self):
        """Run the startup program (creates every persistable: weights,
        optimizer state, statistics), then build the parallel executor
        where the traffic asks for one."""
        with self.fluid.scope_guard(self.scope):
            self.exe0.run(self.startup)
            if self.executor == 'parallel':
                self.pe = self.fluid.ParallelExecutor(
                    loss_name=self.loss.name, main_program=self.main,
                    num_devices=len(self.devices))
            elif self.executor != 'executor':
                raise ValueError('unknown executor %r' % self.executor)

    def restart(self):
        """Fresh optimizer state and statistics for another seed in the
        same process: the startup program again, from the cache."""
        with self.fluid.scope_guard(self.scope):
            self.exe0.run(self.startup)

    def stage(self, batch):
        import jax
        if self.pe is not None:
            return self.pe.partitioner.stage(batch)
        return {k: jax.device_put(v, self.devices[0])
                for k, v in batch.items()}

    def set_params(self, values):
        """Hand the benchmark's weights to the program, by creation
        order; shapes must agree leaf for leaf."""
        names = self.built['param_names']
        if len(names) != len(values):
            raise ValueError('program has %d parameters, reference %d'
                             % (len(names), len(values)))
        for name, (ref_name, arr) in zip(names, values):
            have = tuple(np.shape(self.scope.raw(name)))
            if have != tuple(arr.shape):
                raise ValueError('%s %s is not %s %s' % (
                    name, have, ref_name, tuple(arr.shape)))
            self.scope.set_var(name, arr)

    def dispatch(self, feed):
        if self.pe is not None:
            with self.fluid.scope_guard(self.scope):
                return self.pe.run(fetch_list=[self.loss.name], feed=feed,
                                   return_numpy=False)[0]
        return self.exe0.run(self.main, feed=feed, fetch_list=[self.loss],
                             scope=self.scope, return_numpy=False)[0]

    @staticmethod
    def fetch(handle):
        """Bring the step's loss to the host: the step is complete when
        this returns."""
        data = getattr(handle, 'data', handle)
        return float(np.asarray(data).reshape(-1)[0])

    def step(self, feed):
        return self.fetch(self.dispatch(feed))

    def cache_info(self):
        return (self.pe or self.exe0).cache_info()

    def state(self, name):
        return self.scope.raw(name)

    def close(self):
        self.scope = self.pe = self.exe0 = self.built = None
        self.main = self.startup = self.loss = None


# ---- readings of the timed path's first steps ------------------------------
@functools.lru_cache(maxsize=None)
def _leaf_norms_fn():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda xs: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs]))


def leaf_norms(arrays):
    """Per-leaf Euclidean norms, one device program for all leaves."""
    return np.asarray(_leaf_norms_fn()(list(arrays)), np.float64)


@functools.lru_cache(maxsize=None)
def _delta_norms_fn(ref):
    import jax
    import jax.numpy as jnp
    names = [n for n, _, _ in ref.leaves()]

    def fn(xs, key):
        init = ref.init(key)
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - init[n]))) for x, n in zip(xs, names)])
    return jax.jit(fn)


def delta_norms(now, ref, key):
    """Per-leaf norm of (parameter now - initial parameter), the initial
    one made again from the key inside the program so that no second
    copy of the weights is kept."""
    return np.asarray(_delta_norms_fn(ref)(list(now), key), np.float64)


def drive_first_steps(sess, ref, feeder, wkey, n_steps=3):
    """Steps 1..n through the window's own call and feed. Returns what
    the comparison needs: each loss, per-leaf norm of the first gradient
    as the optimizer got it (from its state after one step), per-leaf
    norm of the parameters' change after the n steps."""
    built = sess.built
    names = built['param_names']
    leaves = ref.leaves()
    trainable = [i for i, (_, _, t) in enumerate(leaves) if t]
    losses = [sess.step(feeder.feed(0))]
    gstate = [sess.state(built['grad_state'](names[i])) for i in trainable]
    grad = leaf_norms(gstate) * built['grad_scale']
    # the whole first gradient, on the host: the reference's is compared
    # with it element by element once the program's state is freed
    grad_full = [np.asarray(g, np.float32) * np.float32(built['grad_scale'])
                 for g in gstate]
    del gstate
    for s in range(1, n_steps):
        losses.append(sess.step(feeder.feed(s)))
    delta_all = delta_norms([sess.state(n) for n in names], ref, wkey)
    return {'loss': losses, 'grad': grad, 'grad_full': grad_full,
            'delta': delta_all[trainable],
            'names': [leaves[i][0] for i in trainable]}


# ---- the plain reference, run once the window has closed -------------------
@functools.lru_cache(maxsize=None)
def _reference_step_fn(ref, dot, devices):
    """The jitted reference step, its weight maker and its batch placer
    for these devices; built once per (reference, dots, devices)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    names = ref.trainable()

    def step(params, opt_state, batch, stepno):
        loss, grads = jax.value_and_grad(
            lambda p: ref.loss(p, batch, dot))(params)
        new_p, new_s = ref.update(params, grads, opt_state, stepno)
        return loss, [grads[n] for n in names], new_p, new_s

    if len(devices) > 1:
        mesh = Mesh(np.array(devices), ('dp',))
        rep = NamedSharding(mesh, P())
        where = NamedSharding(mesh, P('dp'))
    else:
        rep = where = devices[0]
    make = jax.jit(ref.init)

    def init(key):
        """Weights and optimizer state, committed where the step leaves
        them: ``jit`` keys on whether an argument is committed, so the
        step's later calls then find the program of its first."""
        params = jax.device_put(make(key), rep)
        return params, jax.device_put(ref.new_opt_state(params), rep)

    def put(batch):
        return {k: jax.device_put(np.asarray(v), where)
                for k, v in batch.items()}
    return jax.jit(step, donate_argnums=(0, 1)), init, put


def reference_steps(ref, wkey, batches, devices, dot=None, n_steps=3):
    """The same readings from the plain reference: float32, its own
    weights from the same key, the same first batches. On several
    devices the batch is split by rows under plain ``jit`` (the
    partitioner inserts the sums; batch norm stays over the whole
    batch)."""
    import jax
    import jax.numpy as jnp
    jstep, init, put = _reference_step_fn(ref, dot, tuple(devices))
    with jax.default_device(devices[0]):
        params, opt_state = init(wkey)
        losses, grad_full = [], None
        for s in range(n_steps):
            loss, grads, params, opt_state = jstep(
                params, opt_state, put(batches[s]), jnp.float32(s + 1))
            losses.append(float(loss))
            if s == 0:
                grad_full = [np.asarray(g, np.float32) for g in grads]
            del grads
        del opt_state
        delta_all = delta_norms(
            [params[n] for n, _, _ in ref.leaves()], ref, wkey)
    keep = [i for i, (_, _, t) in enumerate(ref.leaves()) if t]
    grad = np.array([np.sqrt(np.sum(np.square(g, dtype=np.float64)))
                     for g in grad_full])
    return {'loss': losses, 'grad': grad, 'grad_full': grad_full,
            'delta': delta_all[keep], 'names': ref.trainable()}


def compare(got, want):
    """Every number a cell's limits may name; each a gap between the
    program's reading and the reference's. Norms are compared as norms
    (the gap between the program's and the reference's, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger), by the worst leaf and by the median leaf.

    ``loss``, ``loss_step1``   |loss - ref| / |ref|: widest over the
                               compared steps, and the first step's.
    ``grad``, ``grad_median``  norm of the first gradient as the
                               optimizer got it.
    ``delta``, ``delta_median`` norm of the parameters' change after the
                               steps, over leaves whose reference
                               gradient is at least a thousandth of the
                               median leaf's (the others move under Adam
                               by round-off alone).
    ``grad_err``               median leaf's norm of (gradient -
                               reference gradient) over the same
                               yardstick: what rounding every operand
                               does, which the norms average away.
    """
    rel = [abs(a - b) / abs(b) for a, b in zip(got['loss'], want['loss'])]
    if not all(np.isfinite(got['loss'])):
        rel = [float('inf')] * len(rel)

    def gaps_of(a, b):
        gaps = np.abs(a - b) / np.maximum(b, float(np.median(b)))
        return np.where(np.isfinite(gaps), gaps, np.inf)

    names = want['names']
    gg = gaps_of(got['grad'], want['grad'])
    moved = want['grad'] >= 1e-3 * np.median(want['grad'])
    gd = np.where(moved, gaps_of(got['delta'], want['delta']), -1.0)
    if got.get('grad_full') is None:        # calibrate.py, later seeds
        diff = np.full(len(names), np.nan)
    else:
        diff = np.array([
            np.sqrt(np.sum(np.square(a - b, dtype=np.float64)))
            for a, b in zip(got['grad_full'], want['grad_full'])])
    ge = diff / np.maximum(want['grad'], float(np.median(want['grad'])))
    ge = np.where(np.isnan(ge) | np.isfinite(ge), ge, np.inf)
    numbers = {'loss': max(rel), 'loss_step1': rel[0],
               'grad': float(gg.max()), 'grad_median': float(np.median(gg)),
               'delta': float(gd.max()),
               'delta_median': float(np.median(gd[moved])),
               'grad_err': float(np.median(ge)),
               'grad_err_worst': float(ge.max())}
    where = {'grad_leaf': names[int(gg.argmax())],
             'delta_leaf': names[int(gd.argmax())],
             'leaves_left_out': int((~moved).sum())}
    return numbers, where


# ---- the timed window ------------------------------------------------------
def percentile(values, q):
    """The q-th percentile as the sample at rank ceil(q n / 100)."""
    v = sorted(values)
    rank = max(1, -(-q * len(v) // 100))
    return v[int(rank) - 1]


# a step's time over the window's median step: the bucket edges of
# ``step_histogram``; 1.02 and up are the slow buckets
HIST_EDGES = (0.98, 0.995, 1.005, 1.02, 1.05, 1.25)


def step_histogram(gaps):
    """The window's step times in fixed buckets around their median,
    as one line: the median, the medians of the window's two halves (a
    window slower in every step moves all three, a drift only the
    second), the count in each bucket, and the first step of each slow
    bucket (a load that grows shows late and stays)."""
    gaps = np.asarray(gaps, np.float64)
    med = float(np.median(gaps))
    half = len(gaps) // 2
    which = np.searchsorted(HIST_EDGES, gaps / med, side='right')
    names = (['<%g' % HIST_EDGES[0]]
             + ['%g-%g' % e for e in zip(HIST_EDGES, HIST_EDGES[1:])]
             + ['>%g' % HIST_EDGES[-1]])
    parts = []
    for b, name in enumerate(names):
        steps = np.flatnonzero(which == b)
        part = '%s: %d' % (name, len(steps))
        if len(steps) and b and HIST_EDGES[b - 1] >= 1.02:
            part += ' from step %d' % steps[0]
        parts.append(part)
    return 'median step %.2f ms (halves %.2f / %.2f); by share of it %s' % (
        1e3 * med, 1e3 * np.median(gaps[:max(half, 1)]),
        1e3 * np.median(gaps[half:]), ', '.join(parts))


def run_window(sess, feeder, seconds, first_step, ahead=0, annotate=False,
               max_steps=None):
    """Steps until ``seconds`` have passed (or ``max_steps`` are sent):
    each step dispatched, then the loss of the step ``ahead`` steps
    before it fetched, so that ``ahead`` steps stay queued on the device
    while the host stands still. Once the time is up nothing more is
    sent, and every step sent is waited for: all of them count, over
    all of that time. A step is complete when its loss is on the host.
    Returns completion times, dispatch and fetch spans (host clock, by
    step) and the losses."""
    import jax
    spans = {'dispatch': [], 'fetch': []}
    done, losses = [], []
    queue = collections.deque()
    ann = (jax.profiler.TraceAnnotation if annotate
           else lambda name: contextlib.nullcontext())

    def complete():
        tb = time.perf_counter()
        with ann('fetch'):
            losses.append(sess.fetch(queue.popleft()))
        tc = time.perf_counter()
        spans['fetch'].append(tc - tb)
        done.append(tc)

    i = first_step
    t0 = time.perf_counter()
    while True:
        with ann('feed'):
            feed = feeder.feed(i)
        ta = time.perf_counter()
        with ann('dispatch'):
            queue.append(sess.dispatch(feed))
        spans['dispatch'].append(time.perf_counter() - ta)
        i += 1
        if len(queue) > ahead:
            complete()
        if max_steps is not None:
            if i - first_step >= max_steps:
                break
        elif time.perf_counter() - t0 >= seconds:
            break
    while queue:
        complete()
    return {'t0': t0, 'done': done, 'spans': spans, 'losses': losses,
            'next_step': i}


def memory_peak(devices):
    """Peak bytes on the fullest chip. The TPU runtime counts live
    buffers (``peak_bytes_in_use``) apart from what it reserves for the
    compiled programs' temporaries (``peak_bytes_reserved``): a step's
    8.9 GB of temp shows only in the second (PERF.md, Findings). The
    peak is their sum."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get('peak_bytes_in_use', 0))
                     + int(stats.get('peak_bytes_reserved', 0)))
    return max(peaks)


def run_cell(man, workload, seed, seconds, trace, devices, out_dir,
             break_path=None, keep_trace=None):
    """One run of one cell on ``devices``. Returns the result object of
    the last line. ``break_path`` is for the benchmark's own tests: a
    callable that gets the session and breaks the timed path."""
    import jax
    t_proc = man.t_start
    cell = man.workload(workload)
    cfg = man.config(cell['config'])
    traffic = man.traffic(cell['traffic'])
    limits = man.limits(workload)
    model = model_module(cfg)
    ref = model.Reference(cfg)
    wkey = jax.random.fold_in(key_of(seed), 0)

    # -- set-up: build, start, weights, the first (compared) steps ----------
    sess = Session(model, cfg, traffic, devices)
    t = time.perf_counter()
    sess.start()
    startup_s = time.perf_counter() - t
    with jax.default_device(devices[0]):
        init = jax.jit(ref.init)(wkey)
        sess.set_params([(n, init[n]) for n, _, _ in ref.leaves()])
        del init
        feeder = Feeder(model, cfg, traffic, seed, sess.stage)
    if break_path is not None:
        break_path(sess)
    t = time.perf_counter()
    got = drive_first_steps(sess, ref, feeder, wkey)
    first_steps_s = time.perf_counter() - t
    step = 3
    for _ in range(traffic.get('warm_steps', 3)):
        sess.step(feeder.feed(step))
        step += 1
    info0 = sess.cache_info()
    setup_s = time.perf_counter() - t_proc

    # -- the window -----------------------------------------------------------
    gc0 = [g['collections'] for g in gc.get_stats()]
    ahead = traffic.get('ahead', 0)
    win = run_window(sess, feeder, seconds, step, ahead)
    gc1 = [g['collections'] for g in gc.get_stats()]
    step = win['next_step']
    info1 = sess.cache_info()
    t_end = win['done'][-1]
    window_s = t_end - win['t0']
    gaps = np.diff([win['t0']] + win['done'])
    n_steps = len(win['done'])
    items = model.items_per_step(cfg, traffic)
    failed = sum(1 for v in win['losses'] if not np.isfinite(v))
    # for whoever looks for the cause of a slow run: a stall, a drift or
    # a window slower in every step shows here. In the gap before step
    # j completes the host sent step j + ahead (none once time was up)
    sp = win['spans']
    sent = sp['dispatch'][ahead:] + [0.0] * ahead
    log('window: %d steps in %.4f s, %d ahead; %s; longest gaps (ms at '
        'step: dispatch + fetch) %s; collections by generation %s' % (
            n_steps, window_s, ahead, step_histogram(gaps),
            ', '.join('%.1f at %d: %.1f + %.1f' % (
                1e3 * gaps[j], j, 1e3 * sent[j],
                1e3 * sp['fetch'][j]) for j in np.argsort(-gaps)[:3]),
            [b - a for a, b in zip(gc0, gc1)]))

    ctx = {
        'man': man, 'cell': cell, 'cfg': cfg, 'traffic': traffic,
        'model': model, 'chips': len(devices),
        'device_kind': devices[0].device_kind,
        'n_steps': n_steps, 'window_s': window_s,
        'step_gaps_s': gaps, 'items_per_step': items,
        'spans': win['spans'], 'setup_s': setup_s,
        'compile_s': startup_s + first_steps_s,
        'recompiles': info1.misses - info0.misses,
        'counters': man.counters(),
        'trace': None, 'trace_window': None,
    }

    # -- a traced stretch of the same loop, after the window ------------------
    device = {'platform': devices[0].platform,
              'kind': devices[0].device_kind, 'count': len(devices)}
    breakdown = None
    if trace:
        import reduce_trace
        tdir = os.path.join(out_dir, 'trace')
        jax.profiler.start_trace(tdir)
        try:
            tw = run_window(sess, feeder, 0, step, ahead, annotate=True,
                            max_steps=traffic.get('trace_steps', 12))
        finally:
            jax.profiler.stop_trace()
        step = tw['next_step']
        xplane = reduce_trace.find_xplane(tdir)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(xplane, keep_trace)
        tr = reduce_trace.load(xplane)
        if not tr['devices']:
            raise RuntimeError('the trace holds no device operation')
        lo, _ = reduce_trace.window_of(tr, 'feed')
        _, hi = reduce_trace.window_of(tr, 'fetch')
        ctx['trace'], ctx['trace_window'] = tr, (lo, hi)
        ctx['trace_steps'] = len(tw['done'])
        b = reduce_trace.busy(tr, lo, hi)
        device['busy_s'] = sum(b.values()) / len(b)
        device['window_s'] = hi - lo
        breakdown = {
            'device_ops': [[k, v] for k, v in reduce_trace.top_ops(
                tr, lo, hi, label=man.op_label)],
            'idle_gaps': [[k, v] for k, v in reduce_trace.idle_gaps(
                tr, lo, hi, SPANS)]}

    device['memory_peak_bytes'] = memory_peak(devices)
    log('memory_stats of device 0: %s' % json.dumps(
        devices[0].memory_stats() or {}))
    ctx['memory_peak_bytes'] = device['memory_peak_bytes']

    # -- metrics --------------------------------------------------------------
    if trace:
        metrics = man.read_layer_metrics(workload, ctx)
    else:
        metrics = man.read_end_to_end(workload, ctx)

    # -- free the program, then the reference ---------------------------------
    batches = [{k: np.asarray(v) for k, v in b.items()}
               for b in feeder.first(3)]
    ctx = None
    feeder = None
    sess.close()
    sess = None
    gc.collect()
    t = time.perf_counter()
    want = reference_steps(ref, wkey, batches, devices)
    ref_s = time.perf_counter() - t
    numbers, where = compare(got, want)
    compared = {k: {'value': numbers[k], 'limit': limits[k]}
                for k in sorted(limits)}
    correct = all(np.isfinite(numbers[k]) and numbers[k] <= limits[k]
                  for k in limits) and failed == 0
    log('reference took %.1f s; compared at %s' % (ref_s, json.dumps(where)))
    log('losses program %s reference %s' % (
        ' '.join('%.6f' % v for v in got['loss']),
        ' '.join('%.6f' % v for v in want['loss'])))
    log('every number read: %s' % json.dumps(numbers))
    for k, v in compared.items():
        log('compared %s = %.6g (limit %.6g)' % (k, v['value'], v['limit']))
    result = {'correct': bool(correct), 'attempted': n_steps,
              'failed': failed, 'metrics': metrics, 'device': device}
    if breakdown is not None:
        result['breakdown'] = breakdown
    result['reference_s'] = ref_s
    result['compared'] = compared
    return result
