#!/usr/bin/env python
"""Repo-specific static lint over the paddle_tpu sources (ANALYSIS.md
"Repo lint"). Stdlib ``ast`` only — no third-party linter, runs
anywhere the tree is checked out.

    python tools/lint_repo.py              # human report
    python tools/lint_repo.py --json -     # machine output
    python tools/lint_repo.py --list       # rules + scope

Rules (each encodes a convention the codebase actually relies on):

- ``bare-except``: ``except:`` swallows KeyboardInterrupt/SystemExit;
  every intentional broad handler here spells ``except Exception``.
- ``lock-outside-with``: ``<lock>.acquire()`` called outside a ``with``
  item — an exception between acquire and release deadlocks the
  executor cache / journal writer; the codebase takes locks only via
  context managers.
- ``unguarded-emit``: calling ``.emit`` on a journal OBJECT
  (``get_journal().emit``, ``self.journal.emit``) without a
  ``journal_active()`` / ``is not None`` guard — the module-level
  ``observability.emit`` / ``_obs.emit`` helper is the None-safe entry
  point and is always allowed.
- ``dup-metric-name``: the same raw metric-name literal passed to
  ``counter()``/``histogram()``/``gauge()`` from more than one of the
  ``serving/``, ``fleet/``, ``multihost/``, ``observability/``
  packages (the last covers the tracing series) — cross-subsystem
  metric names must live in ONE place or the schemas drift apart.
- ``span-not-ended``: a ``start_span()`` call that is not a ``with``
  item, not returned, not passed on, and not bound to a name that the
  enclosing scope later ``.end()``s, aliases, or hands off — a span
  begun and dropped journals a ``span_begin`` with no ``span_end``,
  which trace_report/obs_report then report as a crashed-looking
  unclosed span. The ``x = start_span(...) if cond else None`` idiom
  and cross-method handoffs (``slot.span = x``) are recognized.
- ``direct-cost-analysis``: a ``.cost_analysis()`` call outside
  ``paddle_tpu/observability/perf.py`` — XLA's cost model is read in
  ONE place (the perf observatory, OBSERVABILITY.md "Performance
  observatory") so key-spelling quirks (``'bytes accessed'``,
  list-wrapped results) and roofline constants never fork. New callers
  go through ``observability.perf`` (``capture_compiled``);
  ``Executor.cost_analysis`` is the one pinned
  legacy entry point.
- ``jit-on-warmup-path``: a direct ``jax.jit()``/``pjit()`` call in
  ``paddle_tpu/serving/`` or ``paddle_tpu/fleet/`` outside
  ``fleet/coldstart.py`` — replica warmup compiles must flow through
  ``Executor.run`` so the ``PTPU_AOT_CACHE`` cold-start store
  (SERVING.md "Self-driving fleet") can serve them; a bypassing jit
  silently turns millisecond warm starts back into recompiles.
- ``http-outside-telemetry``: an ``http.server`` import (or an
  ``HTTPServer``/``ThreadingHTTPServer`` stand-up) outside
  ``paddle_tpu/observability/telemetry.py`` — the telemetry plane is
  the ONE sanctioned HTTP surface (OBSERVABILITY.md "Telemetry
  plane"), so exposition format, handler timeouts and port-file
  publication cannot fork; the multihost remote protocol is a raw
  loopback socket on purpose and stays out of this rule's scope.
- ``blocking-socket-recv``: a ``.settimeout(None)`` call (re-arming a
  socket into blocking mode), or a ``sock.recv(n)``-style read outside
  ``paddle_tpu/multihost/remote.py``'s guarded frame reader — the
  remote RPC plane is partition-tolerant only because every socket
  read sits under a deadline with torn-frame detection
  (RESILIENCE.md "Cross-host elasticity"); a timeout-less recv loop
  anywhere else can hang a fleet thread forever on a silent peer.
  Zero-argument ``.recv()`` (pipes/queues) is out of scope by
  construction.
- ``kv-alloc-outside-pool``: a raw numpy buffer allocation
  (``np.zeros``/``empty``/``full``/``ones``) bound to a KV-named
  target in ``paddle_tpu/serving/`` or ``paddle_tpu/fleet/`` — KV
  cache storage is owned by ``paddle_tpu/kvcache/`` (the PagePool),
  so the placement budget's ``kv_bytes`` axis and the
  ``kvcache_pool_*`` gauges account every resident KV byte; a
  side-channel KV buffer is memory the fleet schedules blind to
  (SERVING.md "Paged KV-cache & disaggregated prefill").

The embedded ``ALLOWLIST`` pins known, accepted occurrences (ratchet
style): the tool exits nonzero only on violations NOT in the allowlist,
and reports stale allowlist entries so the pin shrinks over time.
tests/test_lint.py runs this over the tree and asserts zero new
violations.
"""
import argparse
import ast
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPE = ('paddle_tpu', 'tools')
METRIC_PACKAGES = ('serving', 'fleet', 'multihost', 'observability')
METRIC_FACTORIES = ('counter', 'histogram', 'gauge')
# packages on the serving warmup path: compiles here must flow through
# the Executor (whose miss path consults the AOT cold-start store) —
# a direct jax.jit/pjit would silently bypass PTPU_AOT_CACHE and turn
# millisecond warm starts back into full recompiles. fleet/coldstart.py
# is the one sanctioned compile site (the seal path itself).
JIT_FORBIDDEN_PACKAGES = ('serving', 'fleet')
JIT_SANCTIONED = os.path.join('paddle_tpu', 'fleet', 'coldstart.py')
# packages where KV-cache bytes must come from the kvcache.PagePool
# (so kv_bytes placement budgeting and the pool gauges see them) —
# a raw numpy KV buffer here is memory the fleet schedules blind to
KV_FORBIDDEN_PACKAGES = ('serving', 'fleet')
KV_ALLOC_FNS = ('zeros', 'empty', 'full', 'ones', 'zeros_like',
                'empty_like', 'full_like', 'ones_like')
# the one sanctioned http.server stand-up: the telemetry plane owns
# every scrape endpoint so exposition/handler behavior never forks.
# (The remote-cell pickle protocol is a raw socket, not http — scoping
# this rule to http.server keeps it out of scope by construction.)
TELEMETRY_SANCTIONED = os.path.join('paddle_tpu', 'observability',
                                    'telemetry.py')
# the one sanctioned byte-level socket reader: remote.py's _recv_exact
# runs every recv under the connection deadline with torn-frame
# accounting — a raw sized recv anywhere else is a thread that can
# block forever on a partitioned peer
RECV_SANCTIONED = os.path.join('paddle_tpu', 'multihost', 'remote.py')
HTTP_SERVER_CLASSES = ('HTTPServer', 'ThreadingHTTPServer',
                       'BaseHTTPRequestHandler')

# rule:path:detail -> accepted occurrences. Add entries ONLY with a
# review note; the lint test pins this set.
ALLOWLIST = frozenset({
    # Executor.cost_analysis is the public pre-observatory API; its
    # body is the single pinned direct reader outside perf.py
    'direct-cost-analysis:paddle_tpu/executor.py:'
    'comp.cost_analysis()',
})


def _src(node):
    try:
        return ast.unparse(node)
    except Exception:
        return ast.dump(node)


class Violation(object):
    def __init__(self, rule, path, line, detail):
        self.rule, self.path, self.line, self.detail = \
            rule, path, line, detail

    def key(self):
        return '%s:%s:%s' % (self.rule, self.path, self.detail)

    def render(self):
        return '%s:%d: [%s] %s' % (self.path, self.line, self.rule,
                                   self.detail)

    def as_dict(self):
        return {'rule': self.rule, 'path': self.path,
                'line': self.line, 'detail': self.detail}


def _parents(tree):
    par = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            par[child] = node
    return par


def _with_item_calls(tree):
    """Call nodes used as ``with`` context expressions (directly or via
    contextlib helpers wrapping them)."""
    calls = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.With, getattr(ast, 'AsyncWith',
                                               ast.With))):
            for item in node.items:
                for sub in ast.walk(item.context_expr):
                    if isinstance(sub, ast.Call):
                        calls.add(id(sub))
    return calls


def _guarded(node, parents):
    """Is ``node`` under an ``if`` whose test mentions the journal
    guard idiom (``journal_active()`` / an ``is not None`` check)?"""
    cur = node
    while cur in parents:
        cur = parents[cur]
        if isinstance(cur, ast.If):
            test = _src(cur.test)
            if 'journal_active' in test or 'is not None' in test:
                return True
    return False


def _enclosing_scope(node, parents):
    """Nearest enclosing function (or the module) — the region scanned
    for what happens to a span after start_span()."""
    cur = node
    while cur in parents:
        cur = parents[cur]
        if isinstance(cur, (ast.FunctionDef,
                            getattr(ast, 'AsyncFunctionDef',
                                    ast.FunctionDef), ast.Lambda,
                            ast.Module)):
            return cur
    return cur


def _names_in(node):
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _span_disposition(call, parents):
    """How a start_span() call's result leaves the call site: 'with',
    'returned', 'escaped' (argument of another call / stored on an
    attribute or subscript), ('named', name) for a plain name binding
    (possibly through ``... if cond else None``), or 'dropped'."""
    cur = call
    while cur in parents:
        parent = parents[cur]
        if isinstance(parent, ast.withitem):
            return 'with'
        if isinstance(parent, ast.Return):
            return 'returned'
        if isinstance(parent, ast.Call) and cur is not parent.func:
            return 'escaped'        # callee owns it now
        if isinstance(parent, ast.keyword):
            return 'escaped'
        if isinstance(parent, (ast.Assign, ast.AnnAssign)):
            targets = parent.targets \
                if isinstance(parent, ast.Assign) else [parent.target]
            if all(isinstance(t, ast.Name) for t in targets):
                return ('named', targets[0].id)
            return 'escaped'        # self.x = / slot[i] = handoff
        if isinstance(parent, ast.Expr):
            return 'dropped'
        if isinstance(parent, (ast.stmt, ast.FunctionDef, ast.Module)):
            return 'dropped'
        cur = parent            # IfExp / BoolOp / ternary wrappers
    return 'dropped'


def _span_name_consumed(scope, name, defining_call):
    """Does ``scope`` end, return, alias, or hand off the span bound to
    ``name``? ``.end()`` and ``__exit__`` count as closing; a return,
    a re-assignment of the value elsewhere (``slot.span = x``), or
    passing the name into another call counts as ownership transfer."""
    for node in ast.walk(scope):
        if isinstance(node, ast.Call):
            func = node.func
            if isinstance(func, ast.Attribute) \
                    and func.attr in ('end', '__exit__') \
                    and isinstance(func.value, ast.Name) \
                    and func.value.id == name:
                return True
            args = list(node.args) + [k.value for k in node.keywords]
            for a in args:
                if any(sub is defining_call
                       for sub in ast.walk(a)):
                    continue        # the defining site itself
                if name in _names_in(a):
                    return True
        elif isinstance(node, ast.Return) and node.value is not None:
            if name in _names_in(node.value):
                return True
        elif isinstance(node, (ast.Assign, ast.AnnAssign)):
            value = node.value
            if value is None or any(sub is defining_call
                                    for sub in ast.walk(value)):
                continue
            if name in _names_in(value):
                return True         # aliased / stored for later close
    return False


def lint_file(path, relpath):
    with open(path) as f:
        source = f.read()
    try:
        tree = ast.parse(source, filename=relpath)
    except SyntaxError as e:
        return [Violation('parse-error', relpath, e.lineno or 0,
                          str(e))], {}
    parents = _parents(tree)
    with_calls = _with_item_calls(tree)
    out = []
    metrics = {}
    for node in ast.walk(tree):
        if relpath != TELEMETRY_SANCTIONED:
            if isinstance(node, ast.Import) and any(
                    a.name == 'http.server' or
                    a.name.startswith('http.server.')
                    for a in node.names):
                out.append(Violation(
                    'http-outside-telemetry', relpath, node.lineno,
                    'import http.server: scrape endpoints live in '
                    'observability/telemetry.py only (serve_telemetry)'
                ))
            elif isinstance(node, ast.ImportFrom) \
                    and node.module == 'http.server':
                out.append(Violation(
                    'http-outside-telemetry', relpath, node.lineno,
                    'from http.server import %s: scrape endpoints '
                    'live in observability/telemetry.py only '
                    '(serve_telemetry)'
                    % ', '.join(a.name for a in node.names)))
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            out.append(Violation('bare-except', relpath, node.lineno,
                                 'bare except: catches SystemExit/'
                                 'KeyboardInterrupt; use except '
                                 'Exception'))
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute):
            recv = _src(node.func.value)
            if node.func.attr == 'acquire' \
                    and 'lock' in recv.lower() \
                    and id(node) not in with_calls:
                out.append(Violation(
                    'lock-outside-with', relpath, node.lineno,
                    '%s.acquire() outside a with item' % recv))
            if node.func.attr == 'emit' and 'journal' in recv.lower() \
                    and not _guarded(node, parents):
                out.append(Violation(
                    'unguarded-emit', relpath, node.lineno,
                    '%s.emit() with no journal_active()/None guard '
                    '(use observability.emit)' % recv))
            if node.func.attr == 'settimeout' and len(node.args) == 1 \
                    and isinstance(node.args[0], ast.Constant) \
                    and node.args[0].value is None:
                out.append(Violation(
                    'blocking-socket-recv', relpath, node.lineno,
                    '%s.settimeout(None) re-arms a blocking socket: '
                    'every fleet socket read keeps a deadline so a '
                    'partitioned peer times out typed instead of '
                    'hanging the thread' % recv))
            if node.func.attr == 'recv' and node.args \
                    and relpath != RECV_SANCTIONED:
                out.append(Violation(
                    'blocking-socket-recv', relpath, node.lineno,
                    '%s.recv(...) outside multihost/remote.py\'s '
                    'guarded reader: sized socket reads go through '
                    'the deadline-bounded RPC frame reader '
                    '(_recv_exact) or they can block forever on a '
                    'silent peer' % recv))
            if node.func.attr == 'cost_analysis' \
                    and relpath != os.path.join('paddle_tpu',
                                                'observability',
                                                'perf.py'):
                out.append(Violation(
                    'direct-cost-analysis', relpath, node.lineno,
                    '%s.cost_analysis()' % recv))
            if node.func.attr in METRIC_FACTORIES and node.args \
                    and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                metrics.setdefault(node.args[0].value, []).append(
                    (relpath, node.args[0].lineno))
        if isinstance(node, ast.Call):
            func = node.func
            callee = func.attr if isinstance(func, ast.Attribute) \
                else (func.id if isinstance(func, ast.Name) else None)
            if callee in ('jit', 'pjit') \
                    and _package_of(relpath) in JIT_FORBIDDEN_PACKAGES \
                    and relpath != JIT_SANCTIONED:
                out.append(Violation(
                    'jit-on-warmup-path', relpath, node.lineno,
                    '%s() compiles outside the Executor: the warmup '
                    'path must go through Executor.run so the '
                    'PTPU_AOT_CACHE store (fleet/coldstart.py) can '
                    'serve it' % _src(func)))
        if isinstance(node, ast.Assign) \
                and isinstance(node.value, ast.Call) \
                and isinstance(node.value.func, ast.Attribute) \
                and node.value.func.attr in KV_ALLOC_FNS \
                and isinstance(node.value.func.value, ast.Name) \
                and node.value.func.value.id in ('np', 'numpy') \
                and _package_of(relpath) in KV_FORBIDDEN_PACKAGES:
            for target in node.targets:
                if 'kv' in _src(target).lower():
                    out.append(Violation(
                        'kv-alloc-outside-pool', relpath, node.lineno,
                        '%s = np.%s(...): KV buffers come from '
                        'kvcache.PagePool.alloc() so kv_bytes '
                        'budgeting and the pool gauges account them'
                        % (_src(target), node.value.func.attr)))
                    break
        if isinstance(node, ast.Call):
            func = node.func
            callee = func.attr if isinstance(func, ast.Attribute) \
                else (func.id if isinstance(func, ast.Name) else None)
            if callee == 'start_span' \
                    and relpath != os.path.join('paddle_tpu',
                                                'observability',
                                                'tracing.py'):
                disp = _span_disposition(node, parents)
                problem = None
                if disp == 'dropped':
                    problem = ('start_span() result dropped — the '
                               'span can never be end()ed; use '
                               'with span(...) or bind and close it')
                elif isinstance(disp, tuple):
                    scope = _enclosing_scope(node, parents)
                    if not _span_name_consumed(scope, disp[1], node):
                        problem = ('span %r is started but never '
                                   'end()ed, returned, or handed '
                                   'off in this scope' % disp[1])
                if problem:
                    out.append(Violation('span-not-ended', relpath,
                                         node.lineno, problem))
    return out, metrics


def _package_of(relpath):
    parts = relpath.split(os.sep)
    if len(parts) >= 2 and parts[0] == 'paddle_tpu' \
            and parts[1] in METRIC_PACKAGES:
        return parts[1]
    return None


def lint_tree(root=REPO):
    violations = []
    metric_sites = {}        # literal -> {package: [(path, line)]}
    for top in SCOPE:
        for dirpath, dirnames, filenames in os.walk(
                os.path.join(root, top)):
            dirnames[:] = [d for d in dirnames
                           if d != '__pycache__']
            for fn in sorted(filenames):
                if not fn.endswith('.py'):
                    continue
                path = os.path.join(dirpath, fn)
                relpath = os.path.relpath(path, root)
                found, metrics = lint_file(path, relpath)
                violations.extend(found)
                pkg = _package_of(relpath)
                if pkg:
                    for name, sites in metrics.items():
                        metric_sites.setdefault(
                            name, {}).setdefault(pkg, []).extend(sites)
    for name, by_pkg in sorted(metric_sites.items()):
        if len(by_pkg) < 2:
            continue
        for pkg, sites in sorted(by_pkg.items()):
            path, line = sites[0]
            violations.append(Violation(
                'dup-metric-name', path, line,
                'metric literal %r defined in %d packages (%s); hoist '
                'the name to one shared module'
                % (name, len(by_pkg), ', '.join(sorted(by_pkg)))))
    return violations


def main(argv=None):
    ap = argparse.ArgumentParser(description='paddle_tpu repo lint')
    ap.add_argument('--json', nargs='?', const='-', default=None,
                    help='write report as JSON (path or - for stdout)')
    ap.add_argument('--list', action='store_true',
                    help='print the rules and scope, then exit')
    args = ap.parse_args(argv)
    if args.list:
        print('scope: %s' % ', '.join(SCOPE))
        print('rules: bare-except, lock-outside-with, unguarded-emit, '
              'span-not-ended, direct-cost-analysis, '
              'jit-on-warmup-path, kv-alloc-outside-pool, '
              'http-outside-telemetry, blocking-socket-recv, '
              'dup-metric-name (across %s)'
              % '/'.join(METRIC_PACKAGES))
        return 0
    violations = lint_tree()
    new = [v for v in violations if v.key() not in ALLOWLIST]
    seen = {v.key() for v in violations}
    stale = sorted(ALLOWLIST - seen)
    report = {'violations': [v.as_dict() for v in new],
              'allowlisted': len(violations) - len(new),
              'stale_allowlist': stale}
    if args.json:
        text = json.dumps(report, indent=2, sort_keys=True)
        if args.json == '-':
            print(text)
        else:
            with open(args.json, 'w') as f:
                f.write(text + '\n')
    else:
        for v in new:
            print(v.render())
        if stale:
            print('stale allowlist entries (remove them):')
            for k in stale:
                print('  ' + k)
        print('%d violation(s), %d allowlisted, %d stale pin(s)'
              % (len(new), len(violations) - len(new), len(stale)))
    return 1 if new else 0


if __name__ == '__main__':
    sys.exit(main())
