"""Deterministic fault injection: the test harness for the resilience
runtime.

Every recovery path (retry, corruption fallback, NaN policies,
auto-resume) must be exercisable in tier-1 on CPU — so faults are
injected deterministically, keyed by named SITES and hit counts, never
by wall clock or randomness:

- :func:`fault_plan` installs a :class:`FaultPlan`; production code
  calls :func:`maybe_fault(site)` at its injection points (checkpoint
  payload write/commit/read, reader pulls). With no plan installed the
  call is a near-free truthiness check.
- :func:`corrupt_checkpoint` / :func:`truncate_checkpoint` damage an
  on-disk checkpoint payload the way real bitrot/preemption does.
- :func:`nan_reader` / :func:`flaky_reader` wrap data readers to emit
  poisoned batches / transient I/O errors at chosen step indices.
- :class:`KillSwitch` raises :class:`SimulatedKill` at a chosen global
  step, modelling a preemption mid-training for auto-resume tests.
"""
import collections
import glob
import os
import re
import time

import numpy as np

__all__ = ['FaultInjected', 'FaultPlan', 'fault_plan', 'maybe_fault',
           'corrupt_checkpoint', 'truncate_checkpoint', 'nan_reader',
           'flaky_reader', 'SimulatedKill', 'KillSwitch']

# injection sites wired into the runtime
SITE_CKPT_WRITE = 'checkpoint.write'      # payload serialization
SITE_CKPT_COMMIT = 'checkpoint.commit'    # between payload and rename
SITE_CKPT_READ = 'checkpoint.read'        # payload deserialization
SITE_READER_NEXT = 'reader.next'          # program-reader batch pull
SITE_TRAINER_STEP = 'trainer.step'        # top of each train-loop step
#   ^ the preemption-delivery site: a plan with ``action=`` fires a
#   side effect (e.g. os.kill(os.getpid(), SIGTERM)) at an exact step,
#   so SIGTERM-mid-chunk recovery is deterministically testable
# serving runtime sites (SERVING.md "Failure domains & SLO guardrails")
SITE_SERVING_RUN = 'serving/run_batch'    # inside the per-attempt run
SITE_SERVING_LOAD = 'serving/load_model'  # model load / hot swap
SITE_SERVING_PAD = 'serving/pad'          # bucket padding stage
# remote-cell RPC sites (RESILIENCE.md "Cross-host elasticity"):
# delay= models a slow/partitioned link, error= a dropped frame or
# reset, and an error at send never touches the wire (retryable)
SITE_REMOTE_SEND = 'remote/send'          # client frame send
SITE_REMOTE_RECV = 'remote/recv'          # client reader pull
SITE_REMOTE_SPAWN = 'remote/spawn'        # spawn_cell provisioning


class FaultInjected(IOError):
    """The error type injected by default — an IOError subclass so the
    retry/fallback machinery treats it exactly like a real I/O fault,
    while tests can still assert it was synthetic."""

    def __init__(self, site, hit):
        super(FaultInjected, self).__init__(
            'injected fault at %s (hit %d)' % (site, hit))
        self.site = site
        self.hit = hit


class FaultPlan(object):
    """Which hits of which sites fault. ``at`` names 0-based hit
    indices; ``times`` faults the first N hits; ``every`` faults every
    Nth hit. Each matched hit raises ``error`` (a class instantiated
    with (site, hit) for FaultInjected, else called with no args; an
    instance is raised as-is). ``delay`` sleeps that many seconds at
    the injection point before raising — and with ``error=None`` it
    raises nothing at all, modelling a *wedged* (not failed) stage:
    the hang the serving watchdog and ``close(timeout=)`` escalation
    exist to bound."""

    def __init__(self):
        self._rules = collections.defaultdict(list)
        self.hits = collections.Counter()
        self.faults = collections.Counter()

    def inject(self, site, error=FaultInjected, at=None, times=None,
               every=None, delay=None, action=None):
        """``action`` is a zero-arg callable fired at the injection
        point (after ``delay``, before ``error``) — the side-effect
        channel: deliver a real signal, flip a flag, damage a file.
        With ``error=None`` the matched hit performs only the
        delay/action (a wedge, or a pure preemption delivery)."""
        if at is None and times is None and every is None:
            times = 1
        if error is None and delay is None and action is None:
            raise ValueError(
                'error=None requires delay= (a pure hang) or action= '
                '(a pure side effect)')
        self._rules[site].append({'error': error,
                                  'at': None if at is None
                                  else frozenset(at),
                                  'times': times, 'every': every,
                                  'delay': delay, 'action': action})
        return self

    def check(self, site):
        """Record a hit; return the error to raise, or None."""
        hit = self.hits[site]
        self.hits[site] += 1
        for rule in self._rules.get(site, ()):
            matched = (
                (rule['at'] is not None and hit in rule['at']) or
                (rule['times'] is not None and hit < rule['times']) or
                (rule['every'] is not None and
                 (hit + 1) % rule['every'] == 0))
            if not matched:
                continue
            self.faults[site] += 1
            if rule['delay']:
                time.sleep(rule['delay'])
            if rule.get('action') is not None:
                rule['action']()
            err = rule['error']
            if err is None:
                continue          # pure hang: no error to raise
            if isinstance(err, BaseException):
                return err
            if err is FaultInjected or (isinstance(err, type) and
                                        issubclass(err, FaultInjected)):
                return err(site, hit)
            return err()
        return None


_PLANS = []


class _PlanContext(object):
    def __init__(self, plan):
        self.plan = plan

    def __enter__(self):
        _PLANS.append(self.plan)
        return self.plan

    def __exit__(self, *exc):
        _PLANS.remove(self.plan)
        return False


def fault_plan(plan=None):
    """``with fault_plan() as plan: plan.inject(...)`` — installs the
    plan for the dynamic extent of the block."""
    return _PlanContext(plan or FaultPlan())


def maybe_fault(site):
    """Called at runtime injection points; raises per the active plans.
    No-op (one list truthiness check) when no plan is installed."""
    if not _PLANS:
        return
    for plan in tuple(_PLANS):
        err = plan.check(site)
        if err is not None:
            raise err


# ---- on-disk checkpoint damage -------------------------------------------
_SERIAL_RE = re.compile(r'^checkpoint_(\d+)$')


def _pick_serial_dir(checkpoint_dir, serial=None):
    if serial is not None:
        d = os.path.join(checkpoint_dir, 'checkpoint_%d' % serial)
        if not os.path.isdir(d):
            raise IOError('no checkpoint serial %d under %s'
                          % (serial, checkpoint_dir))
        return d
    serials = []
    for name in os.listdir(checkpoint_dir):
        m = _SERIAL_RE.match(name)
        if m and os.path.isdir(os.path.join(checkpoint_dir, name)):
            serials.append(int(m.group(1)))
    if not serials:
        raise IOError('no checkpoints under %s' % checkpoint_dir)
    return os.path.join(checkpoint_dir, 'checkpoint_%d' % max(serials))


def _payload_paths(serial_dir):
    paths = [p for p in glob.glob(os.path.join(serial_dir, '**', '*'),
                                  recursive=True)
             if os.path.isfile(p) and not p.endswith(
                 ('_MANIFEST.json', '_SUCCESS'))]
    if not paths:
        raise IOError('no payload files in %s' % serial_dir)
    # largest file == the tensor payload, the realistic bitrot target
    return sorted(paths, key=os.path.getsize, reverse=True)


def corrupt_checkpoint(checkpoint_dir, serial=None, nbytes=8,
                       path_contains=None):
    """Flip ``nbytes`` bytes in the middle of the (newest, unless
    ``serial`` given) checkpoint's largest payload file WITHOUT
    touching the manifest — exactly what bitrot/torn writes look like.
    ``path_contains`` picks a specific payload file by substring
    instead (e.g. one SHARD of a sharded checkpoint: the validator
    must then name exactly that shard). Returns the damaged file's
    path."""
    paths = _payload_paths(_pick_serial_dir(checkpoint_dir, serial))
    if path_contains is not None:
        paths = [p for p in paths if path_contains in p]
        if not paths:
            raise IOError('no payload file matching %r' % path_contains)
    target = paths[0]
    size = os.path.getsize(target)
    offset = max(0, size // 2 - nbytes // 2)
    with open(target, 'r+b') as f:
        f.seek(offset)
        block = f.read(nbytes)
        f.seek(offset)
        f.write(bytes(b ^ 0xFF for b in block))
        f.flush()
        os.fsync(f.fileno())
    return target


def truncate_checkpoint(checkpoint_dir, serial=None, keep_fraction=0.5):
    """Truncate the largest payload file (torn write / preempted
    writer). Returns the damaged file's path."""
    target = _payload_paths(_pick_serial_dir(checkpoint_dir, serial))[0]
    size = os.path.getsize(target)
    with open(target, 'r+b') as f:
        f.truncate(int(size * keep_fraction))
    return target


# ---- poisoned data -------------------------------------------------------
def _poison(value):
    arr = np.asarray(value)
    if arr.dtype.kind == 'f':
        return np.full_like(arr, np.nan)
    return value


def nan_reader(reader, at_steps, poison=_poison):
    """Wrap a (batched or per-sample) reader so the batches at 0-based
    indices in ``at_steps`` have every float payload replaced with NaN
    — the deterministic poisoned-batch source for anomaly-policy
    tests. Total batch count is unchanged."""
    at_steps = frozenset(at_steps)

    def poisoned_reader():
        for i, item in enumerate(reader()):
            if i not in at_steps:
                yield item
                continue
            if isinstance(item, list):  # a batch of samples
                yield [tuple(poison(v) for v in s) if isinstance(
                    s, tuple) else poison(s) for s in item]
            elif isinstance(item, tuple):
                yield tuple(poison(v) for v in item)
            else:
                yield poison(item)
    return poisoned_reader


def flaky_reader(reader, fail_at, error=FaultInjected):
    """Wrap a reader so pulling the item at each 0-based index in
    ``fail_at`` raises once — the NEXT pass over the reader succeeds at
    that index (a transient fault, which is what retry_reader must
    absorb). Error construction follows FaultPlan rules."""
    remaining = set(fail_at)

    def flaky():
        for i, item in enumerate(reader()):
            if i in remaining:
                remaining.discard(i)
                if isinstance(error, BaseException):
                    raise error
                if error is FaultInjected or (
                        isinstance(error, type) and
                        issubclass(error, FaultInjected)):
                    raise error(SITE_READER_NEXT, i)
                raise error()
            yield item
    return flaky


# ---- simulated preemption ------------------------------------------------
class SimulatedKill(BaseException):
    """Raised by KillSwitch. Derives from BaseException so no
    well-meaning ``except Exception`` recovery path inside the trainer
    can swallow a preemption — exactly like a real SIGKILL wouldn't
    be catchable."""

    def __init__(self, step):
        super(SimulatedKill, self).__init__(
            'simulated kill at global step %d' % step)
        self.step = step


class KillSwitch(object):
    """Event-handler wrapper that raises SimulatedKill once ``at_step``
    steps have completed (counted across epochs):

        trainer.train(..., event_handler=KillSwitch(5, my_handler))

    kills the run right after the 5th EndStepEvent.
    """

    def __init__(self, at_step, handler=None):
        self.at_step = at_step
        self.handler = handler
        self.steps_seen = 0

    def __call__(self, event):
        if self.handler is not None:
            self.handler(event)
        if type(event).__name__ == 'EndStepEvent':
            self.steps_seen += 1
            if self.steps_seen >= self.at_step:
                raise SimulatedKill(self.steps_seen)
