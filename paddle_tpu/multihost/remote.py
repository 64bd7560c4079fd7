"""Remote-process serving cells: a ModelServer living in ANOTHER
process, proxied over a local socket so ``fleet.Router`` /
``ReplicaSupervisor`` manage it unchanged (SERVING.md "Fleet tier").

In-process replicas die with their thread; a HOST dies with all of its
replicas at once. :func:`spawn_cell` starts a worker process running
:func:`serve` (a plain ModelServer behind a length-prefixed pickle
protocol on 127.0.0.1) and returns a :class:`RemoteCell` — an object
with the cell surface the Router already speaks: ``submit`` returning
a future-like request, ``health``, ``load_score``, ``load_model``,
``warmup``, ``drain``, ``swap_model``, ``close``.

Failure mapping is the point: when the worker process dies (kill -9 of
a "host"), the proxy's reader thread sees the socket reset and fails
every in-flight future with the typed ``ServerClosed`` — exactly the
REQUEUEABLE error the fleet's requeue path expects — and ``health()``
raises, so the supervisor marks the replica DEAD and rebuilds it
through the factory (a fresh process). ``tools/chaos_bench.py
--kill-host`` drives this end to end.

The protocol is pickle over a loopback socket between processes of the
SAME user on the SAME machine (the launcher owns both ends) — it is an
IPC transport, not a network service; the listener binds 127.0.0.1 and
accepts exactly one connection.
"""
import os
import pickle
import socket
import struct
import subprocess
import sys
import tempfile
import threading
import time

from .. import observability as _obs
from ..resilience.faultinject import (FaultInjected, SITE_REMOTE_RECV,
                                      SITE_REMOTE_SEND,
                                      SITE_REMOTE_SPAWN, maybe_fault)
from ..resilience.retry import RetryError, retry_call
from ..serving.errors import (DeadlineExceeded, ServerClosed,
                              ServingError)
from .events import mh_emit
from .heartbeat import start_heartbeat, stop_heartbeat

__all__ = ['RemoteCell', 'RemoteRequest', 'spawn_cell', 'serve',
           'DEFAULT_IDLE_TIMEOUT']

_LEN = struct.Struct('>I')

# client-side reader wake-up bound (seconds): how long a recv may idle
# before the reader checks the peer process is still alive. Overridden
# per cell via spawn_cell(idle_timeout=) or PTPU_REMOTE_IDLE_TIMEOUT.
DEFAULT_IDLE_TIMEOUT = 5.0


def _idle_timeout(value=None):
    if value is not None:
        return float(value)
    return float(os.environ.get('PTPU_REMOTE_IDLE_TIMEOUT',
                                DEFAULT_IDLE_TIMEOUT))


def _send_msg(sock, obj, lock, fault_site=None):
    if fault_site is not None:
        # before serialization and the wire: an injected send fault
        # never emits bytes, so the framing stays intact (retryable)
        maybe_fault(fault_site)
    blob = pickle.dumps(obj, protocol=4)
    with lock:
        sock.sendall(_LEN.pack(len(blob)) + blob)


def _recv_exact(sock, n, started=False):
    """Read exactly ``n`` bytes. A socket timeout is only benign while
    NOTHING of the frame has arrived and the caller says no frame is in
    progress (``started=False``) — then it propagates as an idle tick
    for the caller's liveness check. A timeout (or EOF) after partial
    bytes means the peer died mid-frame: the stream can never re-sync,
    so it raises a typed torn-frame ConnectionError."""
    buf = b''
    while len(buf) < n:
        try:
            chunk = sock.recv(n - len(buf))
        except socket.timeout:
            if started or buf:
                raise ConnectionError(
                    'torn frame: peer went quiet after %d of %d '
                    'byte(s)' % (len(buf), n))
            raise
        if not chunk:
            if started or buf:
                raise ConnectionError(
                    'torn frame: connection closed after %d of %d '
                    'byte(s)' % (len(buf), n))
            raise ConnectionError('remote cell connection closed')
        buf += chunk
    return buf


def _recv_msg(sock, fault_site=None):
    if fault_site is not None:
        maybe_fault(fault_site)
    header = _recv_exact(sock, _LEN.size)
    (n,) = _LEN.unpack(header)
    # the length prefix arrived: from here on the frame is in progress
    # and any stall/EOF is torn, never an idle tick
    return pickle.loads(_recv_exact(sock, n, started=True))


# ---- worker side ---------------------------------------------------------
def serve(port_file, place=None, kind='serve'):
    """Worker-process main loop: one server cell, one connection.

    ``kind`` picks the cell behind the protocol: ``'serve'`` is a
    plain ModelServer; ``'prefill'`` a
    :class:`~paddle_tpu.kvcache.prefill.PrefillServer` (prompt
    ingestion for disaggregated decode — the generic ``getattr``
    dispatch below covers its ``register_prefill`` op unchanged).

    Binds 127.0.0.1:0, publishes the port atomically through
    ``port_file``, serves requests until ``close`` or EOF. ``submit``
    is asynchronous server-side too — a waiter thread replies when the
    batch resolves, so one slow request never blocks control ops.

    When ``PTPU_JOURNAL`` names a path, the worker installs a
    RunJournal there for its lifetime: TraceContexts arriving on
    ``submit`` (pickled through the protocol) continue their tree in
    this process's own journal, flushed per message so a ``kill -9``
    leaves the in-flight ``span_begin`` on disk — the unclosed span
    trace_report reports for work that died with the host.

    When ``PTPU_TELEMETRY`` is truthy the worker also serves its own
    scrape endpoint (``/metrics`` / ``/health`` / ``/ledgers``),
    publishing the port through ``PTPU_TELEMETRY_DIR`` when set; the
    parent can also fetch it in-band with the ``telemetry_port`` op."""
    jpath = os.environ.get(_obs.JOURNAL_ENV)
    jnl = None
    if jpath:
        jnl = _obs.RunJournal(jpath)
        _obs.set_journal(jnl)
    # fleet liveness contract: a cell spawned with a heartbeat dir
    # (PTPU_HB_DIR / PTPU_PROC_ID / PTPU_HB_INTERVAL) beats into it
    # from the very top — BEFORE the slow cell construction below — so
    # the prober sees the host live as early as possible
    start_heartbeat()
    tel = _obs.install_env_telemetry(name='cell-%d' % os.getpid())
    if kind == 'prefill':
        from ..kvcache.prefill import PrefillServer
        srv = PrefillServer(place=place)
    elif kind == 'serve':
        from ..serving import ModelServer
        # batch envelope contract: a cell standing in for a local
        # replica must accept the same request sizes the router's
        # local servers do, so the spawner exports the envelope into
        # the child env (RemoteBackend(env=...)) instead of the cell
        # guessing ModelServer defaults
        kw = {}
        if os.environ.get('PTPU_CELL_MAX_BATCH'):
            kw['max_batch_size'] = int(os.environ['PTPU_CELL_MAX_BATCH'])
        if os.environ.get('PTPU_CELL_MAX_QUEUE'):
            kw['max_queue_depth'] = int(os.environ['PTPU_CELL_MAX_QUEUE'])
        srv = ModelServer(place=place, **kw)
    else:
        raise ValueError("cell kind must be 'serve' or 'prefill', "
                         'got %r' % (kind,))
    lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lsock.bind(('127.0.0.1', 0))
    lsock.listen(1)
    port = lsock.getsockname()[1]
    tmp = port_file + '.tmp'
    with open(tmp, 'w') as f:
        f.write('%d\n' % port)
    os.rename(tmp, port_file)
    conn, _ = lsock.accept()
    lsock.close()
    send_lock = threading.Lock()

    def _reply(mid, ok, value):
        try:
            _send_msg(conn, {'id': mid, 'ok': ok, 'value': value},
                      send_lock)
        except (pickle.PicklingError, TypeError):
            _send_msg(conn, {'id': mid, 'ok': False,
                             'value': ServingError(repr(value))},
                      send_lock)
        except OSError:
            pass  # client went away; nothing left to tell

    def _wait_and_reply(mid, req, timeout):
        try:
            _reply(mid, True, req.result(timeout=timeout))
        except Exception as e:  # noqa: BLE001 — forwarded typed
            _reply(mid, False, e)

    try:
        while True:
            try:
                msg = _recv_msg(conn)
            except (ConnectionError, OSError):
                break
            mid, op = msg['id'], msg['op']
            args = msg.get('args', ())
            kwargs = msg.get('kwargs', {})
            if op == 'submit':
                try:
                    req = srv.submit(*args, **kwargs)
                except Exception as e:  # noqa: BLE001 — typed refusal
                    _reply(mid, False, e)
                    continue
                finally:
                    if jnl is not None:
                        jnl.flush()
                timeout = kwargs.get('deadline') or 60.0
                threading.Thread(
                    target=_wait_and_reply, args=(mid, req, timeout),
                    daemon=True).start()
                continue
            if op == 'ping':
                _reply(mid, True, os.getpid())
                continue
            if op == 'telemetry_port':
                _reply(mid, True,
                       tel.port if tel is not None else None)
                continue
            try:
                value = getattr(srv, op)(*args, **kwargs)
            except Exception as e:  # noqa: BLE001 — forwarded typed
                _reply(mid, False, e)
                if op == 'close':
                    break
                continue
            _reply(mid, True, value)
            if op == 'close':
                break
    finally:
        try:
            srv.close(timeout=5.0)
        except Exception:  # noqa: BLE001 — already closed
            pass
        conn.close()
        stop_heartbeat()
        if tel is not None:
            tel.close()
        if jnl is not None:
            _obs.set_journal(None)
            jnl.close()


# ---- client side ---------------------------------------------------------
class RemoteRequest(object):
    """Future over a submit running in the remote cell. Raises the
    forwarded typed error — a dead cell process fails it with
    ``ServerClosed``, the fleet's requeueable error."""

    __slots__ = ('_event', '_value', '_error')

    def __init__(self):
        self._event = threading.Event()
        self._value = None
        self._error = None

    def done(self):
        return self._event.is_set()

    def _complete(self, ok, value):
        if ok:
            self._value = value
        else:
            self._error = value
        self._event.set()

    def result(self, timeout=None):
        if not self._event.wait(timeout):
            raise DeadlineExceeded(
                'remote cell request timed out after %ss' % timeout)
        if self._error is not None:
            raise self._error
        return self._value


class RemoteCell(object):
    """Client proxy with the replica-cell surface the Router speaks.
    One reader thread demultiplexes replies; process death fails every
    pending future with ServerClosed and makes ``health()`` raise."""

    def __init__(self, proc, sock, name='remote-cell'):
        self.proc = proc
        self.name = name
        self.role = 'serve'        # spawn_cell sets 'prefill' for a
        # kind='prefill' worker; the Router's role-aware placement
        # reads it off the cell like any in-process server
        self.journal_path = None   # set by spawn_cell when tracing
        self._sock = sock
        self._send_lock = threading.Lock()
        self._lock = threading.Lock()
        self._pending = {}
        self._next_id = 0
        self._dead = None
        self._reader = threading.Thread(target=self._read_loop,
                                        daemon=True,
                                        name='ptpu-remote-cell')
        self._reader.start()

    @property
    def pid(self):
        return self.proc.pid

    def _read_loop(self):
        try:
            while True:
                try:
                    msg = _recv_msg(self._sock,
                                    fault_site=SITE_REMOTE_RECV)
                except socket.timeout:
                    # bounded idle tick (socket.timeout subclasses
                    # OSError, so it MUST be caught before the fatal
                    # clause below): nothing arrived inside the idle
                    # window — fine for a living idle peer, fatal for
                    # one whose process is gone with the socket
                    # half-open
                    if self.proc is not None \
                            and self.proc.poll() is not None:
                        raise ConnectionError(
                            'peer process exited rc=%s with the '
                            'socket half-open'
                            % self.proc.returncode)
                    continue
                with self._lock:
                    req = self._pending.pop(msg['id'], None)
                if req is not None:
                    req._complete(msg['ok'], msg['value'])
        except (ConnectionError, OSError, pickle.UnpicklingError,
                EOFError) as e:
            self._fail_all(ServerClosed(
                'remote cell %r process died: %r' % (self.name, e)))

    def _fail_all(self, error):
        with self._lock:
            if self._dead is None:
                self._dead = error
            pending, self._pending = self._pending, {}
        for req in pending.values():
            req._complete(False, error)

    def _post(self, op, args, kwargs):
        with self._lock:
            if self._dead is not None:
                raise self._dead
            self._next_id += 1
            mid = self._next_id
            req = RemoteRequest()
            self._pending[mid] = req
        try:
            _send_msg(self._sock, {'id': mid, 'op': op, 'args': args,
                                   'kwargs': kwargs}, self._send_lock,
                      fault_site=SITE_REMOTE_SEND)
        except FaultInjected:
            # an injected send fault fires before any bytes hit the
            # wire (see _send_msg), so the connection is still framed
            # and healthy: drop the orphaned pending slot and let the
            # caller (or _call_idempotent's retry) decide — FaultInjected
            # is an IOError, so this clause must precede OSError below
            with self._lock:
                self._pending.pop(mid, None)
            raise
        except (OSError, ConnectionError) as e:
            err = ServerClosed('remote cell %r unreachable: %r'
                               % (self.name, e))
            self._fail_all(err)
            raise err
        return req

    def _call(self, op, *args, **kwargs):
        timeout = kwargs.pop('_timeout', 120.0)
        return self._post(op, args, kwargs).result(timeout=timeout)

    def _call_idempotent(self, op, *args, **kwargs):
        """Read-only control ops (health, load_score, ...) retried
        with bounded backoff on transient transport faults.

        Only faults that provably never touched the wire are safely
        retryable on this protocol — anything that emitted partial
        bytes desyncs the length-prefixed framing and is terminal
        (ServerClosed via ``_fail_all``). In practice that means the
        ``remote/send`` injected faults plus pre-send errors; the
        retry is what keeps a control probe alive through a blip the
        fault plan (or a flaky loopback) models."""
        timeout = kwargs.pop('_timeout', 10.0)
        retries = _obs.default_registry().counter(
            'remote_rpc_retries_total',
            'idempotent remote-cell control ops retried after a '
            'transient transport fault')

        def _attempt():
            return self._post(op, args, kwargs).result(timeout=timeout)

        try:
            return retry_call(_attempt, max_attempts=3, backoff=0.05,
                              jitter=0.0, retry_on=(FaultInjected,),
                              on_retry=lambda a, e: retries.inc())
        except RetryError as e:
            raise ServerClosed(
                'remote cell %r control op %r kept faulting: %r'
                % (self.name, op, e.last_error)) from e

    # ---- the cell surface the Router drives ----------------------------
    def submit(self, name, feeds, deadline=None, **kwargs):
        return self._post('submit', (name, feeds),
                          dict(kwargs, deadline=deadline))

    def infer(self, name, feeds, deadline=None, timeout=30.0):
        return self.submit(name, feeds,
                           deadline=deadline).result(timeout=timeout)

    def ping(self):
        """Round-trip liveness probe; returns the worker's pid."""
        return self._call_idempotent('ping', _timeout=10.0)

    def health(self):
        return self._call_idempotent('health', _timeout=10.0)

    def telemetry_port(self):
        """The worker's scrape-endpoint port, or None when the cell
        was spawned without ``PTPU_TELEMETRY`` — feed it to
        :meth:`TelemetryAggregator.add_endpoint` for fleet rollups."""
        return self._call_idempotent('telemetry_port', _timeout=10.0)

    def load_score(self, model_name=None):
        try:
            return self._call_idempotent('load_score', model_name,
                                         _timeout=10.0)
        except ServerClosed:
            return float('inf')  # unroutable, not an exception path

    def load_model(self, name, dirname, model_filename=None,
                   params_filename=None):
        return self._call('load_model', name, dirname,
                          model_filename=model_filename,
                          params_filename=params_filename)

    def swap_model(self, name, dirname, model_filename=None,
                   params_filename=None):
        return self._call('swap_model', name, dirname,
                          model_filename=model_filename,
                          params_filename=params_filename)

    def register_prefill(self, name, spec):
        """Prefill-cell op: build the engine for ``name`` from its
        declarative spec dict in the worker process (the spec is plain
        data, so it pickles through the protocol untouched)."""
        return self._call('register_prefill', name, spec)

    def unload_model(self, name, timeout=None):
        return self._call('unload_model', name, timeout=timeout)

    def drain(self, name, timeout=None):
        return self._call('drain', name, timeout=timeout)

    def warmup(self, model_name=None, upto=None, timeout=300.0):
        return self._call('warmup', model_name, upto=upto,
                          timeout=timeout, _timeout=timeout + 10.0)

    def pause(self, model_name=None):
        return self._call('pause', model_name, _timeout=10.0)

    def resume(self, model_name=None):
        return self._call('resume', model_name, _timeout=10.0)

    def queue_depth(self, model_name):
        return self._call_idempotent('queue_depth', model_name,
                                     _timeout=10.0)

    def models(self):
        return self._call_idempotent('models', _timeout=10.0)

    def close(self, timeout=30.0):
        try:
            self._call('close', timeout=timeout,
                       _timeout=max(1.0, timeout) + 5.0)
        except (ServerClosed, DeadlineExceeded, FaultInjected):
            pass  # already gone — close converges either way
        try:
            self.proc.wait(timeout=max(1.0, timeout))
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()
        self._fail_all(ServerClosed('remote cell %r closed'
                                    % self.name))
        try:
            self._sock.close()
        except OSError:
            pass
        # the reader wakes within one idle window (sock.close makes
        # its recv raise) — join so close() leaves zero stuck threads
        self._reader.join(timeout=_idle_timeout() + 5.0)

    def kill(self):
        """Chaos hook: SIGKILL the whole cell process — the remote
        analogue of killing a host."""
        self.proc.kill()
        self.proc.wait()


def _reap(proc):
    """Kill + wait: a ``kill()`` without the ``wait()`` leaves a
    zombie the parent carries until exit."""
    try:
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=10.0)
    except (OSError, subprocess.TimeoutExpired):
        pass  # already reaped elsewhere, or unkillable — give up


def spawn_cell(name='remote-cell', devices=1, env=None,
               startup_timeout=180.0, kind='serve',
               heartbeat_dir=None, host_id=None,
               heartbeat_interval=None, idle_timeout=None,
               platform='cpu'):
    """Start a cell worker process and connect to it; the parent
    blocks until the port file appears. The child runs on the JAX
    ``platform`` the CALLER names — written into its environment over
    whatever this process inherited — with ``devices`` virtual host
    devices when that is the CPU. A chip belongs to one process: a
    parent that holds one must not pass ``platform='tpu'``, or the
    child waits on the chip until ``startup_timeout``.
    ``kind='prefill'`` runs a prefill cell (prompt ingestion) instead
    of a ModelServer — the returned proxy carries ``role='prefill'``
    so the Router pins prefill placements to it.

    Elastic-fleet contracts (RESILIENCE.md "Cross-host elasticity"):
    ``heartbeat_dir``/``host_id``/``heartbeat_interval`` export the
    PTPU_HB_* env so the worker beats into the fleet heartbeat dir;
    the parent's active AOT cache dir (env OR ``coldstart.cache_scope``
    — the scope is a process-local override the child can't otherwise
    see) is exported as ``PTPU_AOT_CACHE`` so the remote ``warmup()``
    deserializes sealed executables instead of recompiling; the client
    socket gets a bounded ``idle_timeout`` (default
    PTPU_REMOTE_IDLE_TIMEOUT / 5s) so the reader can never block
    forever on a partitioned peer. Every failed spawn reaps the child
    (kill + wait) and journals a ``spawn_failed`` multihost event."""
    maybe_fault(SITE_REMOTE_SPAWN)
    t0 = time.monotonic()
    workdir = tempfile.mkdtemp(prefix='ptpu_cell_')
    port_file = os.path.join(workdir, 'port')
    child_env = dict(os.environ)
    child_env.update(env or {})
    child_env['JAX_PLATFORMS'] = platform
    # a journaling parent gets a journaling worker: each process writes
    # its OWN file; trace_report/timeline merge them by trace id.
    # PTPU_TRACE_SAMPLE rides the inherited environ unchanged, so the
    # worker agrees with the parent's sampling decisions.
    journal_path = child_env.get(_obs.JOURNAL_ENV)
    if not journal_path and _obs.journal_active():
        journal_path = os.path.join(workdir, 'journal.jsonl')
        child_env[_obs.JOURNAL_ENV] = journal_path
    if heartbeat_dir is not None:
        child_env['PTPU_HB_DIR'] = str(heartbeat_dir)
        child_env['PTPU_PROC_ID'] = str(int(host_id or 0))
        if heartbeat_interval is not None:
            child_env['PTPU_HB_INTERVAL'] = str(heartbeat_interval)
    from ..fleet import coldstart as _coldstart  # lazy: fleet is heavy
    aot_dir = _coldstart.cache_dir()
    _coldstart.export_env(child_env)
    flags = child_env.get('XLA_FLAGS', '')
    if 'xla_force_host_platform_device_count' not in flags:
        child_env['XLA_FLAGS'] = (
            flags + ' --xla_force_host_platform_device_count=%d'
            % devices).strip()
    root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    child_env['PYTHONPATH'] = os.pathsep.join(
        [root] + [p for p in
                  child_env.get('PYTHONPATH', '').split(os.pathsep)
                  if p])
    proc = subprocess.Popen(
        [sys.executable, '-m', 'paddle_tpu.multihost.remote',
         '--port-file', port_file, '--cell-kind', kind],
        env=child_env)
    try:
        deadline = time.monotonic() + startup_timeout
        while not os.path.exists(port_file):
            if proc.poll() is not None:
                raise ServerClosed(
                    'remote cell %r exited rc=%s before publishing '
                    'its port' % (name, proc.returncode))
            if time.monotonic() > deadline:
                raise ServerClosed(
                    'remote cell %r did not come up within %.0fs'
                    % (name, startup_timeout))
            time.sleep(0.05)
        with open(port_file) as f:
            port = int(f.read().strip())
        sock = socket.create_connection(('127.0.0.1', port),
                                        timeout=30.0)
    except BaseException as e:
        # EVERY failed spawn reaps the child: the old code left a
        # zombie on startup timeout and leaked the process entirely
        # when create_connection failed after the port file appeared
        _reap(proc)
        mh_emit('spawn_failed', name=name, kind=kind, pid=proc.pid,
                reason=repr(e),
                dur_s=round(time.monotonic() - t0, 6))
        raise
    # bounded idle timeout: the reader wakes at least this often to
    # verify the peer process is alive instead of blocking forever
    sock.settimeout(_idle_timeout(idle_timeout))
    cell = RemoteCell(proc, sock, name=name)
    cell.role = kind
    cell.journal_path = journal_path
    dur_s = time.monotonic() - t0
    _obs.default_registry().histogram(
        'remote_spawn_seconds',
        'wall seconds from spawn_cell() to a connected remote cell'
    ).observe(dur_s)
    mh_emit('spawn', name=name, kind=kind, pid=proc.pid,
            host_id=host_id, aot_warm=bool(aot_dir),
            dur_s=round(dur_s, 6))
    return cell


def _main(argv=None):
    import argparse
    parser = argparse.ArgumentParser(
        description='paddle_tpu remote serving cell worker')
    parser.add_argument('--port-file', required=True)
    parser.add_argument('--cell-kind', default='serve',
                        choices=('serve', 'prefill'))
    args = parser.parse_args(argv)
    serve(args.port_file, kind=args.cell_kind)
    return 0


if __name__ == '__main__':
    # the platform is spawn_cell()'s decision, passed as JAX_PLATFORMS
    sys.exit(_main())
