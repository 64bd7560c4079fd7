"""tools/lint_repo.py: the repo-specific AST lint stays green against
its pinned allowlist, and each rule actually fires on the defect it
encodes (ANALYSIS.md "Repo lint")."""
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.lint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, 'tools'))

import lint_repo  # noqa: E402


def _lint_source(tmp_path, source):
    p = tmp_path / 'mod.py'
    p.write_text(source)
    violations, metrics = lint_repo.lint_file(str(p), 'mod.py')
    return violations, metrics


def test_tree_is_clean_against_allowlist():
    """The ratchet: zero NEW violations across paddle_tpu/ + tools/,
    zero stale allowlist pins."""
    violations = lint_repo.lint_tree()
    new = [v for v in violations if v.key() not in lint_repo.ALLOWLIST]
    assert not new, '\n'.join(v.render() for v in new)
    seen = {v.key() for v in violations}
    assert not (lint_repo.ALLOWLIST - seen), 'stale allowlist entries'


def test_cli_exit_zero_and_json(tmp_path):
    out = tmp_path / 'lint.json'
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'lint_repo.py'),
         '--json', str(out)],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    report = json.loads(out.read_text())
    assert report['violations'] == []
    assert report['stale_allowlist'] == []


def test_rule_bare_except(tmp_path):
    v, _ = _lint_source(tmp_path, '''
try:
    x = 1
except:
    pass
''')
    assert [x for x in v if x.rule == 'bare-except']
    v, _ = _lint_source(tmp_path, '''
try:
    x = 1
except Exception:
    pass
''')
    assert not v


def test_rule_lock_outside_with(tmp_path):
    v, _ = _lint_source(tmp_path, '''
def f(self):
    self._lock.acquire()
    self._lock.release()
''')
    assert [x for x in v if x.rule == 'lock-outside-with']
    v, _ = _lint_source(tmp_path, '''
def f(self):
    with self._lock:
        pass
''')
    assert not v
    # non-lock acquire (e.g. a semaphore pool named otherwise) is out
    # of scope for the rule
    v, _ = _lint_source(tmp_path, 'conn.acquire()\n')
    assert not v


def test_rule_unguarded_emit(tmp_path):
    v, _ = _lint_source(tmp_path, '''
def f(self):
    self.journal.emit('ev', x=1)
''')
    assert [x for x in v if x.rule == 'unguarded-emit']
    v, _ = _lint_source(tmp_path, '''
def f(self):
    if journal_active():
        self.journal.emit('ev', x=1)
    j = get_journal()
    if j is not None:
        j.emit('ev', x=2)
''')
    assert not [x for x in v if x.rule == 'unguarded-emit']
    # the module-level None-safe helper is always allowed
    v, _ = _lint_source(tmp_path, "_obs.emit('ev', x=1)\n")
    assert not v


def test_rule_dup_metric_name(tmp_path):
    for pkg in ('serving', 'fleet'):
        d = tmp_path / 'paddle_tpu' / pkg
        d.mkdir(parents=True)
        (d / 'm.py').write_text(
            "reg.counter('shared_total', 'help')\n")
    (tmp_path / 'tools').mkdir()
    violations = lint_repo.lint_tree(root=str(tmp_path))
    dups = [v for v in violations if v.rule == 'dup-metric-name']
    assert dups and 'shared_total' in dups[0].detail
    assert {v.path.split(os.sep)[1] for v in dups} == \
        {'serving', 'fleet'}


def test_rule_jit_on_warmup_path(tmp_path):
    """ISSUE 16 satellite: a direct jax.jit/pjit in serving/ or
    fleet/ bypasses the PTPU_AOT_CACHE store; only fleet/coldstart.py
    may compile."""
    src = 'import jax\nf = jax.jit(lambda x: x)\n'
    p = tmp_path / 'mod.py'
    p.write_text(src)
    for rel, expect in [
            (os.path.join('paddle_tpu', 'serving', 'server.py'), 1),
            (os.path.join('paddle_tpu', 'fleet', 'router.py'), 1),
            (os.path.join('paddle_tpu', 'fleet', 'coldstart.py'), 0),
            (os.path.join('paddle_tpu', 'executor.py'), 0),
            ('tools/bench.py', 0)]:
        v, _ = lint_repo.lint_file(str(p), rel)
        hits = [x for x in v if x.rule == 'jit-on-warmup-path']
        assert len(hits) == expect, (rel, hits)
    # pjit too, and bare-name jit calls
    p.write_text('from jax.experimental.pjit import pjit\n'
                 'g = pjit(lambda x: x)\n')
    v, _ = lint_repo.lint_file(
        str(p), os.path.join('paddle_tpu', 'fleet', 'autoscaler.py'))
    assert any(x.rule == 'jit-on-warmup-path' for x in v)


def test_rule_http_outside_telemetry(tmp_path):
    """ISSUE 18 satellite: http.server stand-ups outside
    observability/telemetry.py fork the scrape-endpoint surface; the
    telemetry plane is the one sanctioned listener. The remote-cell
    pickle protocol (raw sockets) stays out of scope."""
    src = ('from http.server import ThreadingHTTPServer\n'
           'import http.server\n')
    p = tmp_path / 'mod.py'
    p.write_text(src)
    for rel, expect in [
            (os.path.join('paddle_tpu', 'serving', 'server.py'), 2),
            ('tools/fleet_top.py', 2),
            (os.path.join('paddle_tpu', 'observability',
                          'telemetry.py'), 0)]:
        v, _ = lint_repo.lint_file(str(p), rel)
        hits = [x for x in v if x.rule == 'http-outside-telemetry']
        assert len(hits) == expect, (rel, hits)
    # raw sockets (the multihost remote protocol) don't trip the rule
    p.write_text('import socket\ns = socket.socket()\n'
                 's.bind(("127.0.0.1", 0))\ns.listen(1)\n')
    v, _ = lint_repo.lint_file(
        str(p), os.path.join('paddle_tpu', 'multihost', 'remote.py'))
    assert not [x for x in v if x.rule == 'http-outside-telemetry']


def test_rule_blocking_socket_recv(tmp_path):
    """ISSUE 19 satellite: a timeout-less socket read outside
    multihost/remote.py's guarded frame reader can hang a fleet thread
    forever on a partitioned peer; settimeout(None) re-arms blocking
    mode anywhere."""
    src = 'chunk = sock.recv(4096)\n'
    p = tmp_path / 'mod.py'
    p.write_text(src)
    for rel, expect in [
            (os.path.join('paddle_tpu', 'serving', 'server.py'), 1),
            ('tools/fleet_top.py', 1),
            (os.path.join('paddle_tpu', 'multihost', 'remote.py'), 0)]:
        v, _ = lint_repo.lint_file(str(p), rel)
        hits = [x for x in v if x.rule == 'blocking-socket-recv']
        assert len(hits) == expect, (rel, hits)
    # settimeout(None) is flagged even inside the sanctioned reader;
    # zero-arg .recv() (pipes/queues) is out of scope by construction
    p.write_text('sock.settimeout(None)\nok = channel.recv()\n')
    v, _ = lint_repo.lint_file(
        str(p), os.path.join('paddle_tpu', 'multihost', 'remote.py'))
    hits = [x for x in v if x.rule == 'blocking-socket-recv']
    assert len(hits) == 1 and 'settimeout' in hits[0].detail
    # a deadline-armed settimeout anywhere is fine
    p.write_text('sock.settimeout(5.0)\n')
    v, _ = lint_repo.lint_file(str(p), 'tools/x.py')
    assert not [x for x in v if x.rule == 'blocking-socket-recv']


def test_rule_kv_alloc_outside_pool(tmp_path):
    """ISSUE 17 satellite: raw numpy KV buffers in serving/ or fleet/
    dodge the PagePool's kv_bytes accounting; only the kvcache package
    (and non-KV buffers anywhere) may allocate directly."""
    src = 'import numpy as np\nkv_cache = np.zeros((4, 8))\n'
    p = tmp_path / 'mod.py'
    p.write_text(src)
    for rel, expect in [
            (os.path.join('paddle_tpu', 'fleet', 'decode.py'), 1),
            (os.path.join('paddle_tpu', 'serving', 'server.py'), 1),
            (os.path.join('paddle_tpu', 'kvcache', 'pool.py'), 0),
            (os.path.join('paddle_tpu', 'executor.py'), 0)]:
        v, _ = lint_repo.lint_file(str(p), rel)
        hits = [x for x in v if x.rule == 'kv-alloc-outside-pool']
        assert len(hits) == expect, (rel, hits)
    # non-KV-named buffers in fleet/ are fine; np.empty on a KV name
    # is not
    p.write_text('import numpy as np\nscratch = np.zeros((4, 8))\n'
                 'page_kv = np.empty((2, 2))\n')
    v, _ = lint_repo.lint_file(
        str(p), os.path.join('paddle_tpu', 'fleet', 'decode.py'))
    hits = [x.detail for x in v if x.rule == 'kv-alloc-outside-pool']
    assert len(hits) == 1 and 'page_kv' in hits[0]
