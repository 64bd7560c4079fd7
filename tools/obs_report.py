#!/usr/bin/env python
"""Render a paddle_tpu observability run journal into a human report.

The input is the JSONL file written by
``paddle_tpu.observability.RunJournal`` (schema: OBSERVABILITY.md).
Standalone on purpose — only stdlib imports, so it runs anywhere the
journal file landed, with no jax/paddle_tpu install.

    python tools/obs_report.py run.jsonl            # human report
    python tools/obs_report.py run.jsonl --top 20   # more slow spans
    python tools/obs_report.py run.jsonl --json -   # summary as JSON
    python tools/obs_report.py run.jsonl --smoke    # CI gate

``--smoke`` exits nonzero when the journal is empty, contains malformed
lines, or lacks the required records (``--require step`` by default —
a training journal must hold step records; ``--require serving`` for a
serving soak; ``--require pipeline`` for a pipelined-trainer run —
step records must carry the ``feed_wait`` host-wait field; ``--require
compiler`` for a run that must have gone through the compiler pass
pipeline (``compile_pass`` records); ``--require partition`` for a run
that must have placed work through the Partitioner (``partition``
records, PARTITIONING.md); ``--require resilience`` for a run that
must have exercised preemption saves or topology resharding
(``preempt_save`` / ``reshard`` records, RESILIENCE.md); ``--require
fleet`` for a run through the replica router / continuous-batching
decode engine (``fleet`` / ``decode`` records, SERVING.md);
``--require analysis`` for a run that must have exercised the static
program verifier (``analysis`` records, ANALYSIS.md); ``--require
tracing`` for a run that must hold completed distributed-tracing spans
(``span_end`` records, OBSERVABILITY.md — unclosed spans never fail
the gate; fault injection legitimately leaves them); ``--require
perf`` for a run that must have captured per-program performance
ledgers (``perf_ledger`` records, OBSERVABILITY.md "Performance
observatory"); ``--require autoscale`` for a self-driving fleet run —
``autoscale`` records must include at least one acted scale_up /
scale_down decision (SERVING.md "Self-driving fleet"); ``--require
coldstart`` for an AOT-warmed run — ``coldstart`` records must show
both a store save and a warm hit; ``--require kvcache`` for a
paged-KV / disaggregated-prefill run — ``kvcache`` records must show
both page-pool allocs and at least one prefilled prompt (SERVING.md
"Paged KV-cache & disaggregated prefill"); ``--require slo`` for a
run under declared service-level objectives — ``slo`` records must
show a burn-rate breach AND a recovery (OBSERVABILITY.md "SLO burn
rates"); ``--require telemetry`` for a run scraped through the live
telemetry plane — ``telemetry`` records must show an aggregator
scrape (OBSERVABILITY.md "Telemetry plane"); ``--require
remote_elastic`` for a cross-host elastic run — ``fleet`` records
must cover the whole remote replica lifecycle: a ``spawn_remote``,
a ``host_lost`` detected inside its heartbeat window, an in-flight
``requeue`` and a scale-in ``retire`` (RESILIENCE.md "Cross-host
elasticity"); ``--require any`` for presence only). Run
``--list-requires`` for the full machine-derived catalog — the argparse
choices come straight from ``REQUIRED_EV``, so the list above can lag
but the tool cannot.
``tools/serve_bench.py --smoke`` runs this gate over the journal its
load run writes.
"""
import argparse
import json
import sys

REQUIRED_EV = {'step': 'step_end', 'serving': 'serving_batch',
               'pipeline': 'step_end', 'compiler': 'compile_pass',
               'partition': 'partition',
               # a resilience run must show at least one preemption
               # save OR one topology reshard (RESILIENCE.md)
               'resilience': ('preempt_save', 'reshard'),
               # a fleet run must show router/replica lifecycle events
               # OR continuous-batching decode steps (SERVING.md
               # "Fleet tier & continuous batching")
               'fleet': ('fleet', 'decode'),
               # a ZeRO-2 run must show the mode being applied
               # (bucketed grad tail / sliced state — PERF.md "ZeRO-2
               # and collective overlap") or a measured collective
               'zero': ('zero', 'collective'),
               # a multi-host pod must show bootstrap/barrier/host_lost
               # /relaunch lifecycle events (RESILIENCE.md "Surviving
               # host loss"); the gate also checks every host_lost was
               # detected inside its heartbeat window
               'multihost': 'multihost',
               # a run that must have gone through the static program
               # verifier (Executor miss-path verify / feed checks /
               # pass sanitizer — ANALYSIS.md) shows 'analysis' records
               'analysis': 'analysis',
               # a traced run must hold completed spans (span_end —
               # OBSERVABILITY.md "Distributed tracing"). Unclosed
               # spans are NOT gated: fault injection legitimately
               # leaves them (a killed replica's in-flight work)
               'tracing': 'span_end',
               # a perf-observed run must have ledgered at least one
               # compiled program (cost/memory accounting captured on
               # the Executor's compile-miss path — OBSERVABILITY.md
               # "Performance observatory")
               'perf': 'perf_ledger',
               # a self-driving fleet run must show autoscale decisions
               # (SERVING.md "Self-driving fleet"); the gate further
               # insists at least one decision actually resized the
               # fleet (scale_up / scale_down), not just holds
               'autoscale': 'autoscale',
               # an AOT-warmed run must show cold-start store traffic
               # (save on the compiling replica, hit on the warmed one)
               'coldstart': 'coldstart',
               # a paged-KV / disaggregated-prefill run must show
               # page-pool lifecycle events (SERVING.md "Paged
               # KV-cache & disaggregated prefill"); the gate further
               # insists at least one prompt was actually prefilled
               # (action='prefill'), not just pages cycled
               'kvcache': 'kvcache',
               # a run under declared SLOs must show the burn-rate
               # engine both breaching and recovering (the gate checks
               # the state transitions, not mere presence)
               'slo': 'slo',
               # a run on the live telemetry plane must show endpoint
               # lifecycle + at least one aggregator scrape that saw a
               # live endpoint
               'telemetry': 'telemetry',
               # a cross-host elastic run must show the full remote
               # replica lifecycle (RESILIENCE.md "Cross-host
               # elasticity"): a remote spawn, a heartbeat-detected
               # host loss inside its window, the in-flight requeue,
               # and the scale-in retire back to the floor
               'remote_elastic': 'fleet',
               'any': None}

# one-line purpose per family, keyed like REQUIRED_EV — rendered by
# --list-requires so the CLI self-documents without re-reading this file
REQUIRE_DOC = {
    'step': 'training journal holds step_end records',
    'serving': 'serving soak holds serving_batch records',
    'pipeline': 'step_end records carry feed_wait (pipelined trainer)',
    'compiler': 'compile_pass records (compiler pass pipeline ran)',
    'partition': 'partition records (Partitioner placed work)',
    'resilience': 'preempt_save / reshard records',
    'fleet': 'fleet / decode records (router or decode engine ran)',
    'zero': 'zero / collective records (ZeRO-2 applied or measured)',
    'multihost': 'multihost lifecycle; host losses inside the window',
    'analysis': 'analysis records (static verifier ran)',
    'tracing': 'completed span_end records',
    'perf': 'perf_ledger records (cost/memory capture ran)',
    'autoscale': 'autoscale records incl. an acted scale decision',
    'coldstart': 'coldstart records incl. a store save and a warm hit',
    'kvcache': 'kvcache records incl. page allocs and a prefill',
    'slo': 'slo records incl. a burn-rate breach and a recovery',
    'telemetry': 'telemetry records incl. an aggregator scrape',
    'remote_elastic': 'fleet spawn_remote + in-window host_lost + '
                      'requeue + retire',
    'any': 'presence only (any well-formed journal passes)',
}


def load_journal(path):
    """(records, malformed_line_count) — same contract as
    ``observability.read_journal`` without importing paddle_tpu."""
    records, malformed = [], 0
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                malformed += 1
                continue
            if not isinstance(rec, dict) or 'ev' not in rec:
                malformed += 1
                continue
            records.append(rec)
    return records, malformed


def _mean(xs):
    return sum(xs) / len(xs) if xs else 0.0


def _pipeline_summary(steps, duration):
    """Input-pipeline SLI (PERF.md "Dispatch pipelining"): how much of
    the run the trainer spent BLOCKED on host feed work (feed_wait) vs
    dispatching compute, and how well chaining amortized dispatches."""
    waits = [r['feed_wait'] for r in steps if 'feed_wait' in r]
    dispatches = [r['dispatch_s'] for r in steps if 'dispatch_s' in r]
    chained = [r for r in steps if r.get('chain', 0) > 1]
    return {
        'steps_with_feed_wait': len(waits),
        'host_wait_total_s': sum(waits),
        'host_wait_mean_s': _mean(waits),
        'host_wait_fraction': (sum(waits) / duration) if duration
        else 0.0,
        'dispatch_total_s': sum(dispatches),
        'chained_steps': len(chained),
        'mean_chain': _mean([r['chain'] for r in chained]),
    }


def _compiler_summary(by_ev):
    """Compiler SLI (COMPILER.md): per-pass wall + rewrite counts from
    ``compile_pass`` events."""
    passes = {}
    for r in by_ev.get('compile_pass', ()):
        p = passes.setdefault(r.get('pass', '?'), {
            'runs': 0, 'total_s': 0.0, 'removed': 0, 'fused': 0,
            'released': 0})
        p['runs'] += 1
        p['total_s'] += r.get('dur_s', 0.0)
        p['removed'] += r.get('removed', 0)
        p['fused'] += r.get('fused', 0)
        p['released'] += r.get('released', 0)
    return {
        'passes': passes,
        'pass_wall_s': sum(p['total_s'] for p in passes.values()),
        'ops_eliminated': sum(p['removed'] for p in passes.values()),
        'ops_fused': sum(p['fused'] for p in passes.values()),
    }


def _resilience_summary(by_ev):
    """Resilience SLI (RESILIENCE.md "Sharded checkpoints & topology
    portability"): preemption saves (SIGTERM/SIGINT chunk-boundary
    commits) and restore-time topology reshards (from-mesh -> to-mesh,
    vars placed, wall)."""
    preempts = by_ev.get('preempt_save', ())
    reshards = by_ev.get('reshard', ())
    topologies = {}
    for r in reshards:
        key = '%s -> %s' % (r.get('from_mesh') or '?',
                            r.get('to_mesh') or '?')
        t = topologies.setdefault(key, {'count': 0, 'vars': 0,
                                        'wall_s': 0.0})
        t['count'] += 1
        t['vars'] += r.get('vars', 0)
        t['wall_s'] += r.get('dur_s', 0.0)
    return {
        'preempt_saves': len(preempts),
        'preempt_signals': sorted({r.get('signal') for r in preempts
                                   if r.get('signal') is not None}),
        'reshards': len(reshards),
        'reshard_vars': sum(r.get('vars', 0) for r in reshards),
        'reshard_wall_s': sum(r.get('dur_s', 0.0) for r in reshards),
        'topologies': topologies,
    }


def _partition_summary(by_ev):
    """Partition SLI (PARTITIONING.md): what mesh(es) the run placed
    work on and how much wall went into resharding-class work
    (shard_scope journal events carry dur_s; per-batch staging is
    metric-only by design)."""
    events = by_ev.get('partition', ())
    meshes = {}
    for r in events:
        m = meshes.setdefault(r.get('mesh', '?'), {
            'devices': r.get('devices'), 'creates': 0,
            'scopes_sharded': 0, 'vars_placed': 0, 'reshard_s': 0.0})
        if r.get('devices'):
            m['devices'] = r['devices']
        if r.get('action') == 'create':
            m['creates'] += 1
        elif r.get('action') == 'shard_scope':
            m['scopes_sharded'] += 1
            m['vars_placed'] += r.get('vars', 0)
        m['reshard_s'] += r.get('dur_s', 0.0)
    return {
        'events': len(events),
        'meshes': meshes,
        'scopes_sharded': sum(m['scopes_sharded']
                              for m in meshes.values()),
        'vars_placed': sum(m['vars_placed'] for m in meshes.values()),
        'reshard_wall_s': sum(m['reshard_s'] for m in meshes.values()),
    }


def _zero_summary(by_ev):
    """ZeRO-2 SLI (PERF.md "ZeRO-2 and collective overlap"): mode
    applications from ``zero`` events (buckets, sliced/replicated state
    tensors, per-device grad-shard bytes) and measured collective walls
    from ``collective`` events — ``overlap_fraction`` is the share of
    the standalone collective wall HIDDEN under compute (1.0 = the
    sharded step pays nothing visible over the replicated step)."""
    events = by_ev.get('zero', ())
    applies = [r for r in events if r.get('action') == 'apply']
    colls = by_ev.get('collective', ())
    total_coll_s = sum(r.get('standalone_s', 0.0) for r in colls)
    visible_s = sum(r.get('visible_s', 0.0) for r in colls)
    overlap = None
    if total_coll_s > 0:
        overlap = max(0.0, min(1.0, 1.0 - visible_s / total_coll_s))
    return {
        'events': len(events),
        'applied': len(applies),
        'buckets': sum(r.get('buckets', 0) for r in applies),
        'grads': sum(r.get('grads', 0) for r in applies),
        'sliced_state': sum(r.get('sliced', 0) for r in applies),
        'replicated_state': sum(r.get('replicated', 0)
                                for r in applies),
        'shard_bytes': max((r.get('shard_bytes', 0) for r in applies),
                           default=0),
        'collectives': {
            'measured': len(colls),
            'standalone_wall_s': total_coll_s,
            'visible_wall_s': visible_s,
            'overlap_fraction': overlap,
            'by_op': {
                op: sum(r.get('standalone_s', 0.0) for r in colls
                        if r.get('op') == op)
                for op in sorted({r.get('op', '?') for r in colls})},
        },
    }


def _analysis_summary(by_ev):
    """Static-verifier SLI (ANALYSIS.md): applications of the program
    verifier / feed checks / pass sanitizer from ``analysis`` events —
    diagnostics found per phase, verify wall, and which compiler
    passes ran under the sanitizer."""
    events = by_ev.get('analysis', ())
    phases = {}
    for r in events:
        p = phases.setdefault(r.get('phase', '?'), {
            'runs': 0, 'errors': 0, 'warnings': 0, 'wall_s': 0.0})
        p['runs'] += 1
        p['errors'] += r.get('errors', 0)
        p['warnings'] += r.get('warnings', 0)
        p['wall_s'] += r.get('dur_s', 0.0)
    return {
        'events': len(events),
        'errors': sum(p['errors'] for p in phases.values()),
        'warnings': sum(p['warnings'] for p in phases.values()),
        'wall_s': sum(p['wall_s'] for p in phases.values()),
        'phases': phases,
        'sanitized_passes': sorted({
            r['pass'] for r in events
            if r.get('phase') == 'sanitize' and r.get('pass')}),
    }


def _multihost_summary(by_ev):
    """Multi-host SLI (RESILIENCE.md "Surviving host loss"): pod
    lifecycle from ``multihost`` events — bootstraps per host,
    barriers/agreement checks, whole-host losses with their detection
    latency against the heartbeat window, degraded relaunches."""
    events = by_ev.get('multihost', ())
    actions = {}
    for r in events:
        actions[r.get('action', '?')] = \
            actions.get(r.get('action', '?'), 0) + 1
    losses = [r for r in events if r.get('action') == 'host_lost']
    detects = [r['detect_s'] for r in losses if 'detect_s' in r]
    relaunches = [r for r in events if r.get('action') == 'relaunch']
    boots = [r for r in events if r.get('action') == 'bootstrap']
    return {
        'events': len(events),
        'actions': actions,
        'bootstraps': len(boots),
        'world': max((r.get('world', 0) for r in boots), default=0),
        'barriers': actions.get('barrier', 0),
        'agreement_failures': actions.get('agreement_fail', 0),
        'hosts_lost': len(losses),
        'loss_reasons': sorted({str(r.get('reason', '?'))
                                for r in losses}),
        'detect_max_s': max(detects) if detects else None,
        'detect_mean_s': _mean(detects) if detects else None,
        'losses_outside_window': sum(
            1 for r in losses
            if 'detect_s' in r and 'window_s' in r
            and r['detect_s'] > r['window_s']),
        'relaunches': len(relaunches),
        'final_world': relaunches[-1].get('world') if relaunches
        else (max((r.get('world', 0) for r in boots), default=None)),
    }


def _fleet_summary(by_ev):
    """Fleet SLI (SERVING.md "Fleet tier & continuous batching"):
    replica lifecycle (quarantines, kills, restarts, swaps) from
    ``fleet`` events, continuous-batching decode behavior (steps,
    occupancy, admissions/retirements) from ``decode`` events."""
    events = by_ev.get('fleet', ())
    actions = {}
    for r in events:
        actions[r.get('action', '?')] = \
            actions.get(r.get('action', '?'), 0) + 1
    decode = by_ev.get('decode', ())
    occ = [r['occupancy'] for r in decode if 'occupancy' in r]
    return {
        'events': len(events),
        'actions': actions,
        'requeues': actions.get('requeue', 0),
        'restarts': actions.get('restart', 0),
        'swaps': actions.get('swap', 0),
        'decode': {
            'steps': len(decode),
            'mean_occupancy': _mean(occ),
            'min_occupancy': min(occ) if occ else 0.0,
            'admitted': sum(r.get('admitted', 0) for r in decode),
            'retired': sum(r.get('retired', 0) for r in decode),
            'slot_steps': sum(r.get('live', 0) for r in decode),
        },
    }


def _tracing_summary(by_ev):
    """Tracing SLI (OBSERVABILITY.md "Distributed tracing"): span
    counts per kind, distinct traces, link records, UNCLOSED spans
    (span_begin with no span_end in THIS journal — work that died with
    the process, or continued in another journal: tools/trace_report.py
    merges files before judging), and the top critical paths (largest
    roots with their dominant child chains)."""
    begins = by_ev.get('span_begin', ())
    ends = by_ev.get('span_end', ())
    ended = {r.get('span') for r in ends}
    unclosed = [r for r in begins if r.get('span') not in ended]
    kinds = {}
    children = {}
    for r in ends:
        k = kinds.setdefault(r.get('name', '?'), {
            'count': 0, 'total_s': 0.0, 'max_s': 0.0})
        k['count'] += 1
        k['total_s'] += r.get('dur_s', 0.0)
        k['max_s'] = max(k['max_s'], r.get('dur_s', 0.0))
        children.setdefault(r.get('parent'), []).append(r)
    ends_by_id = {r.get('span'): r for r in ends}
    roots = [r for r in ends
             if r.get('parent') is None
             or r.get('parent') not in ends_by_id]
    roots.sort(key=lambda r: -r.get('dur_s', 0.0))
    paths = []
    for root in roots[:5]:
        path, rec = [], root
        for _ in range(8):
            path.append('%s(%.1fms)' % (rec.get('name', '?'),
                                        rec.get('dur_s', 0.0) * 1e3))
            kids = children.get(rec.get('span'))
            if not kids:
                break
            rec = max(kids, key=lambda r: r.get('dur_s', 0.0))
        paths.append(' > '.join(path))
    return {
        'spans': len(ends),
        'traces': len({r.get('trace') for r in ends
                       if r.get('trace')}),
        'links': len(by_ev.get('span_link', ())),
        'unclosed': len(unclosed),
        'unclosed_names': sorted({r.get('name', '?')
                                  for r in unclosed}),
        'kinds': kinds,
        'critical_paths': paths,
    }


def _perf_summary(by_ev):
    """Perf SLI (OBSERVABILITY.md "Performance observatory"):
    per-program cost/memory ledgers from ``perf_ledger`` events. Seal
    rows (compile-miss capture) and measured rows (phase=measured,
    folded in once a step time lands) are merged per fingerprint."""
    progs = {}
    for r in by_ev.get('perf_ledger', ()):
        cur = progs.setdefault(r.get('fp'), {})
        cur.update({k: v for k, v in r.items()
                    if k not in ('ev', 'run', 't', 'phase')
                    and v is not None})
    bounds = {}
    for d in progs.values():
        b = d.get('roofline')
        if b:
            bounds[b] = bounds.get(b, 0) + 1
    return {
        'programs': len(progs),
        'live_bytes_total': sum(d.get('live_bytes') or 0
                                for d in progs.values()),
        'compile_wall_s': sum(d.get('compile_wall_s') or 0.0
                              for d in progs.values()),
        'roofline_bounds': bounds,
        'by_program': {
            (d.get('program') or (fp or '?')[:12]): {
                'flops': d.get('flops'),
                'bytes_accessed': d.get('bytes_accessed'),
                'live_bytes': d.get('live_bytes'),
                'mfu': d.get('mfu'),
                'roofline': d.get('roofline'),
                'measured_ms': d.get('measured_ms'),
                'compile_wall_s': d.get('compile_wall_s'),
                'mesh': d.get('mesh'),
            } for fp, d in progs.items()},
    }


def _conv_fuse_summary(by_ev):
    """The fused-conv fallback ledger (COMPILER.md "Conv epilogue
    fusion"): every op the compiler fused but the lowering replayed
    unfused, with the rejection reason."""
    fallbacks = by_ev.get('conv_fuse_fallback', ())
    reasons = {}
    for r in fallbacks:
        reasons[r.get('reason', '?')] = \
            reasons.get(r.get('reason', '?'), 0) + 1
    return {'fallbacks': len(fallbacks), 'fallback_reasons': reasons}


def summarize(records, malformed=0):
    """Aggregate a record list into a JSON-ready summary dict."""
    by_ev = {}
    for r in records:
        by_ev.setdefault(r['ev'], []).append(r)
    header = (by_ev.get('run_begin') or [{}])[0]
    steps = [r for r in by_ev.get('step_end', ())
             if 'skipped' not in r]
    step_walls = [r['dur_s'] for r in steps if 'dur_s' in r]
    losses = [r['loss'] for r in steps if 'loss' in r]
    compiles = by_ev.get('compile_end', [])
    exe_runs = by_ev.get('exe_run', [])
    batches = by_ev.get('serving_batch', [])
    spans = sorted((r for r in records if 'dur_s' in r),
                   key=lambda r: -r['dur_s'])
    duration = max((r.get('t', 0.0) for r in records), default=0.0)
    summary = {
        'run_id': header.get('run') or (records[0].get('run')
                                        if records else None),
        'started_wall': header.get('wall'),
        'schema': header.get('schema'),
        'duration_s': duration,
        'malformed_lines': malformed,
        'event_counts': {ev: len(rs) for ev, rs in sorted(by_ev.items())},
        'steps': {
            'count': len(steps),
            'skipped': len(by_ev.get('step_end', ())) - len(steps),
            'examples': sum(r.get('examples', 0) for r in steps),
            'mean_step_s': _mean(step_walls),
            'max_step_s': max(step_walls) if step_walls else 0.0,
            'steps_per_s': len(steps) / duration if duration else 0.0,
            'examples_per_s': (sum(r.get('examples', 0) for r in steps)
                               / duration if duration else 0.0),
            'first_loss': losses[0] if losses else None,
            'last_loss': losses[-1] if losses else None,
        },
        'compiles': {
            'count': len(compiles),
            'total_s': sum(r.get('dur_s', 0.0) for r in compiles),
            'max_s': max((r.get('dur_s', 0.0) for r in compiles),
                         default=0.0),
        },
        'executor': {
            'runs': len(exe_runs),
            'cache_hits': sum(1 for r in exe_runs
                              if r.get('cache') == 'hit'),
            'cache_misses': sum(1 for r in exe_runs
                                if r.get('cache') == 'miss'),
        },
        'serving': {
            'batches': len(batches),
            'rows': sum(r.get('rows', 0) for r in batches),
            'padded_rows': sum(r.get('bucket', 0) - r.get('rows', 0)
                               for r in batches),
            'admitted': sum(r.get('n', 1)
                            for r in by_ev.get('serving_admit', ())),
            'shed': sum(r.get('n', 1)
                        for r in by_ev.get('serving_shed', ())),
            'retries': sum(r.get('n', 1)
                           for r in by_ev.get('serving_retry', ())),
        },
        'checkpoints': {
            'saves': len(by_ev.get('checkpoint_save', ())),
            'loads': len(by_ev.get('checkpoint_load', ())),
            'fallbacks': len(by_ev.get('checkpoint_fallback', ())),
        },
        'anomalies': len(by_ev.get('anomaly', ())),
        'pipeline': _pipeline_summary(steps, duration),
        'compiler': _compiler_summary(by_ev),
        'partition': _partition_summary(by_ev),
        'resilience': _resilience_summary(by_ev),
        'fleet': _fleet_summary(by_ev),
        'multihost': _multihost_summary(by_ev),
        'zero': _zero_summary(by_ev),
        'analysis': _analysis_summary(by_ev),
        'tracing': _tracing_summary(by_ev),
        'perf': _perf_summary(by_ev),
        'conv_fuse': _conv_fuse_summary(by_ev),
        'slowest_spans': [
            {'ev': r['ev'], 't': r.get('t'), 'dur_s': r['dur_s'],
             'detail': {k: v for k, v in r.items()
                        if k not in ('ev', 'run', 't', 'dur_s')}}
            for r in spans],
    }
    return summary


def render(summary, top=10):
    s = summary
    lines = [
        '----------------->   Run Journal Report   <-----------------',
        'run %s  (%.2fs journalled, schema %s)'
        % (s['run_id'], s['duration_s'], s['schema']),
    ]
    if s['malformed_lines']:
        lines.append('!! %d malformed line(s)' % s['malformed_lines'])
    st = s['steps']
    if st['count']:
        lines.append(
            'training: %d steps (%d skipped), %d examples | %.1f '
            'steps/s, %.1f examples/s | step mean %.1fms max %.1fms'
            % (st['count'], st['skipped'], st['examples'],
               st['steps_per_s'], st['examples_per_s'],
               st['mean_step_s'] * 1e3, st['max_step_s'] * 1e3))
        if st['first_loss'] is not None:
            lines.append('loss:     %.6g -> %.6g'
                         % (st['first_loss'], st['last_loss']))
    pl = s.get('pipeline') or {}
    if pl.get('steps_with_feed_wait'):
        line = ('pipeline: host wait %.3fs total (%.1f%% of wall, '
                'mean %.2fms/step)'
                % (pl['host_wait_total_s'],
                   100.0 * pl['host_wait_fraction'],
                   pl['host_wait_mean_s'] * 1e3))
        if pl['chained_steps']:
            line += (' | %d steps chained (avg %.1f steps/dispatch)'
                     % (pl['chained_steps'], pl['mean_chain']))
        lines.append(line)
    co = s.get('compiler') or {}
    if co.get('passes'):
        lines.append(
            'compiler: %d pass runs, %.3fs total | %d ops eliminated, '
            '%d fused' % (
                sum(p['runs'] for p in co['passes'].values()),
                co['pass_wall_s'], co['ops_eliminated'],
                co['ops_fused']))
        for name, p in sorted(co['passes'].items(),
                              key=lambda kv: -kv[1]['total_s']):
            lines.append(
                '  %-18s %3d runs  %8.3fms  removed=%d fused=%d '
                'released=%d' % (name, p['runs'], p['total_s'] * 1e3,
                                 p['removed'], p['fused'],
                                 p['released']))
    pa = s.get('partition') or {}
    if pa.get('events'):
        lines.append(
            'partition: %d events | %d scope(s) sharded (%d vars), '
            '%.3fs resharding wall'
            % (pa['events'], pa['scopes_sharded'], pa['vars_placed'],
               pa['reshard_wall_s']))
        for mesh, m in sorted(pa['meshes'].items()):
            lines.append('  mesh %-14s devices=%s creates=%d '
                         'shard_scope=%d' % (mesh, m['devices'],
                                             m['creates'],
                                             m['scopes_sharded']))
    ex = s['executor']
    if ex['runs']:
        lookups = ex['cache_hits'] + ex['cache_misses']
        lines.append(
            'executor: %d runs | cache %d hits / %d misses (%.1f%% hit '
            'rate)' % (ex['runs'], ex['cache_hits'], ex['cache_misses'],
                       100.0 * ex['cache_hits'] / lookups
                       if lookups else 0.0))
    c = s['compiles']
    if c['count']:
        lines.append('compiles: %d, %.2fs total (max %.2fs)'
                     % (c['count'], c['total_s'], c['max_s']))
    sv = s['serving']
    if sv['batches'] or sv['admitted'] or sv['shed']:
        lines.append(
            'serving:  %d admitted, %d shed, %d retries | %d batches, '
            '%d rows (+%d pad)'
            % (sv['admitted'], sv['shed'], sv['retries'], sv['batches'],
               sv['rows'], sv['padded_rows']))
    ck = s['checkpoints']
    if ck['saves'] or ck['loads'] or ck['fallbacks']:
        lines.append('ckpts:    %d saves, %d loads, %d corruption '
                     'fallbacks' % (ck['saves'], ck['loads'],
                                    ck['fallbacks']))
    rz = s.get('resilience') or {}
    if rz.get('preempt_saves') or rz.get('reshards'):
        lines.append(
            'resilience: %d preemption save(s), %d reshard(s) '
            '(%d vars, %.3fs wall)'
            % (rz['preempt_saves'], rz['reshards'],
               rz['reshard_vars'], rz['reshard_wall_s']))
        for topo, t in sorted(rz.get('topologies', {}).items()):
            lines.append('  reshard %-22s x%d  vars=%d  %.3fs'
                         % (topo, t['count'], t['vars'], t['wall_s']))
    zr = s.get('zero') or {}
    if zr.get('applied') or zr.get('collectives', {}).get('measured'):
        lines.append(
            'zero:     %d application(s) | %d grads -> %d bucket(s) | '
            'state sliced=%d replicated=%d | shard bytes/device %d'
            % (zr['applied'], zr['grads'], zr['buckets'],
               zr['sliced_state'], zr['replicated_state'],
               zr['shard_bytes']))
        zc = zr['collectives']
        if zc['measured']:
            line = ('collective: %d measured, %.3fs standalone wall'
                    % (zc['measured'], zc['standalone_wall_s']))
            if zc['overlap_fraction'] is not None:
                line += (' | %.0f%% hidden under compute'
                         % (100.0 * zc['overlap_fraction']))
            lines.append(line)
            for op, wall in sorted(zc['by_op'].items()):
                lines.append('  %-16s %8.3fms' % (op, wall * 1e3))
    fl = s.get('fleet') or {}
    if fl.get('events') or fl.get('decode', {}).get('steps'):
        if fl.get('events'):
            lines.append(
                'fleet:    %d events | %d requeues, %d restarts, '
                '%d swaps | %s'
                % (fl['events'], fl['requeues'], fl['restarts'],
                   fl['swaps'],
                   ', '.join('%s=%d' % kv for kv in sorted(
                       fl['actions'].items())) or '-'))
        dc = fl.get('decode') or {}
        if dc.get('steps'):
            lines.append(
                'decode:   %d steps, %d slot-steps | occupancy mean '
                '%.1f%% min %.1f%% | %d admitted, %d retired'
                % (dc['steps'], dc['slot_steps'],
                   100.0 * dc['mean_occupancy'],
                   100.0 * dc['min_occupancy'], dc['admitted'],
                   dc['retired']))
    mh = s.get('multihost') or {}
    if mh.get('events'):
        line = ('multihost: %d hosts bootstrapped | %d barriers, '
                '%d agreement failure(s) | %d host(s) lost, '
                '%d relaunch(es)'
                % (mh['bootstraps'], mh['barriers'],
                   mh['agreement_failures'], mh['hosts_lost'],
                   mh['relaunches']))
        lines.append(line)
        if mh['hosts_lost']:
            lines.append(
                '  loss detection: mean %.3fs max %.3fs (%d outside '
                'the heartbeat window) | reasons: %s'
                % (mh['detect_mean_s'] or 0.0, mh['detect_max_s']
                   or 0.0, mh['losses_outside_window'],
                   ', '.join(mh['loss_reasons']) or '-'))
        if mh['relaunches']:
            lines.append('  degraded to world=%s after relaunch'
                         % mh['final_world'])
    an = s.get('analysis') or {}
    if an.get('events'):
        line = ('analysis: %d verifier run(s), %.3fs wall | %d '
                'error(s), %d warning(s)'
                % (an['events'], an['wall_s'], an['errors'],
                   an['warnings']))
        if an['sanitized_passes']:
            line += (' | sanitized passes: %s'
                     % ', '.join(an['sanitized_passes']))
        lines.append(line)
        for ph, p in sorted(an['phases'].items()):
            lines.append('  %-10s %3d runs  %8.3fms  errors=%d '
                         'warnings=%d' % (ph, p['runs'],
                                          p['wall_s'] * 1e3,
                                          p['errors'], p['warnings']))
    tr = s.get('tracing') or {}
    if tr.get('spans') or tr.get('unclosed'):
        line = ('tracing:  %d span(s) over %d trace(s), %d link(s)'
                % (tr['spans'], tr['traces'], tr['links']))
        if tr['unclosed']:
            line += (' | %d UNCLOSED (%s)'
                     % (tr['unclosed'],
                        ', '.join(tr['unclosed_names']) or '-'))
        lines.append(line)
        for name, k in sorted(tr.get('kinds', {}).items(),
                              key=lambda kv: -kv[1]['total_s'])[:top]:
            lines.append('  %-24s %5d spans  %9.3fms total  max '
                         '%8.3fms' % (name, k['count'],
                                      k['total_s'] * 1e3,
                                      k['max_s'] * 1e3))
        for p in tr.get('critical_paths', ())[:3]:
            lines.append('  path: %s' % p)
    pf = s.get('perf') or {}
    if pf.get('programs'):
        bounds = ', '.join('%d %s-bound' % (n, b) for b, n in
                           sorted(pf['roofline_bounds'].items()))
        lines.append(
            'perf:     %d program ledger(s) | live %.2f MB | compile '
            '%.2fs%s' % (pf['programs'],
                         pf['live_bytes_total'] / 1e6,
                         pf['compile_wall_s'],
                         (' | %s' % bounds) if bounds else ''))
        for name, d in sorted(pf['by_program'].items(),
                              key=lambda kv: -(kv[1]['flops'] or 0)):
            mfu = d.get('mfu')
            lines.append(
                '  %-20s %10.3f MFLOP %8.2f MB  mfu=%s  %s'
                % (name[:20], (d['flops'] or 0) / 1e6,
                   (d['bytes_accessed'] or 0) / 1e6,
                   '%.4f' % mfu if mfu is not None else '-',
                   d.get('roofline') or '-'))
    cf = s.get('conv_fuse') or {}
    if cf.get('fallbacks'):
        lines.append(
            'conv fallbacks: %d fused op(s) replayed unfused (%s)'
            % (cf['fallbacks'],
               ', '.join('%s=%d' % kv for kv in sorted(
                   cf['fallback_reasons'].items()))))
    if s['anomalies']:
        lines.append('anomaly:  %d guard trips' % s['anomalies'])
    lines.append('events:   %s' % ', '.join(
        '%s=%d' % kv for kv in sorted(s['event_counts'].items())))
    if s['slowest_spans']:
        lines.append('top %d slowest spans:' % min(
            top, len(s['slowest_spans'])))
        for r in s['slowest_spans'][:top]:
            detail = ' '.join('%s=%s' % kv
                              for kv in sorted(r['detail'].items()))
            lines.append('  %10.3fms  t=%-10.3f %-16s %s'
                         % (r['dur_s'] * 1e3, r.get('t') or 0.0,
                            r['ev'], detail))
    return '\n'.join(lines)


def check_journal(path, require='step'):
    """Smoke validation -> list of problems (empty == healthy)."""
    if require not in REQUIRED_EV:
        raise ValueError('require must be one of %s'
                         % sorted(REQUIRED_EV))
    try:
        records, malformed = load_journal(path)
    except OSError as e:
        return ['journal unreadable: %r' % (e,)]
    problems = []
    if malformed:
        problems.append('%d malformed journal line(s)' % malformed)
    if not records:
        problems.append('journal contains no records')
        return problems
    if records[0].get('ev') != 'run_begin':
        problems.append('journal does not start with run_begin')
    need = REQUIRED_EV[require]
    if need is not None:
        wanted = need if isinstance(need, tuple) else (need,)
        n = sum(1 for r in records
                if r['ev'] in wanted and 'skipped' not in r)
        if n == 0:
            problems.append('journal contains zero %s records'
                            % ' / '.join(wanted))
        elif require == 'pipeline':
            n = sum(1 for r in records if r['ev'] == need
                    and 'skipped' not in r and 'feed_wait' in r)
            if n == 0:
                problems.append(
                    'journal contains zero step_end records with '
                    'pipeline fields (feed_wait) — was the run made '
                    'with a pre-pipelining trainer?')
    if require == 'autoscale':
        acted = sum(1 for r in records if r['ev'] == 'autoscale'
                    and r.get('action') in ('scale_up', 'scale_down'))
        if not acted:
            problems.append(
                'journal holds autoscale records but no scale_up / '
                'scale_down decision — the control loop never acted')
    if require == 'coldstart':
        actions = {r.get('action') for r in records
                   if r['ev'] == 'coldstart'}
        if 'save' not in actions:
            problems.append('coldstart journal shows no AOT save — '
                            'nothing was ever sealed to the store')
        if 'hit' not in actions:
            problems.append('coldstart journal shows no AOT hit — '
                            'no warmup ever deserialized')
    if require == 'kvcache':
        actions = {r.get('action') for r in records
                   if r['ev'] == 'kvcache'}
        if 'prefill' not in actions:
            problems.append(
                'kvcache journal shows page traffic but no prefill — '
                'no prompt was ever disaggregated')
        if 'alloc' not in actions:
            problems.append(
                'kvcache journal shows no page alloc — the pool was '
                'never exercised')
    if require == 'slo':
        states = {r.get('state') for r in records if r['ev'] == 'slo'}
        if 'breach' not in states:
            problems.append(
                'slo journal shows no burn-rate breach — the error '
                'budget was never pressured')
        if 'recovered' not in states:
            problems.append(
                'slo journal shows no recovery — every breached '
                'objective stayed breached to the end of the run')
    if require == 'telemetry':
        actions = {r.get('action') for r in records
                   if r['ev'] == 'telemetry'}
        if 'scrape' not in actions:
            problems.append(
                'telemetry journal shows no aggregator scrape — '
                'endpoints may have served but nothing merged them')
    if require == 'remote_elastic':
        actions = {r.get('action') for r in records
                   if r['ev'] == 'fleet'}
        for action, why in (
                ('spawn_remote', 'no remote replica was ever '
                                 'provisioned'),
                ('host_lost', 'no heartbeat-detected host loss — the '
                              'chaos kill never registered'),
                ('requeue', 'no in-flight request was requeued off '
                            'the lost host'),
                ('retire', 'the fleet never scaled back in')):
            if action not in actions:
                problems.append(
                    'remote_elastic journal shows no fleet %s '
                    'record — %s' % (action, why))
        # detection must come from the heartbeat monitor, not from an
        # eventual RPC failure: the journalled detect_s is the file
        # age at detection, which lags a silent death by at most one
        # beat interval + one supervisor poll — 2x window + 1s is the
        # generous ceiling that still catches RPC-deadline detection
        for r in records:
            if (r['ev'] == 'fleet' and r.get('action') == 'host_lost'
                    and 'detect_s' in r and 'window_s' in r
                    and float(r['detect_s'])
                    > 2.0 * float(r['window_s']) + 1.0):
                problems.append(
                    'remote host %s loss detected after %.2fs — '
                    'outside its %.2fs heartbeat window (+slack); '
                    'detection leaned on an RPC failure, not the '
                    'monitor' % (r.get('host'), float(r['detect_s']),
                                 float(r['window_s'])))
    if require == 'multihost':
        # a host loss the monitor only noticed after its own heartbeat
        # window means detection is broken even if recovery worked
        for r in records:
            if (r['ev'] == 'multihost'
                    and r.get('action') == 'host_lost'
                    and 'detect_s' in r and 'window_s' in r
                    and float(r['detect_s']) > float(r['window_s'])):
                problems.append(
                    'host %s loss detected after %.2fs — outside its '
                    '%.2fs heartbeat window'
                    % (r.get('host'), float(r['detect_s']),
                       float(r['window_s'])))
    return problems


def list_requires():
    """The --list-requires catalog: every --require family with the
    journal events it insists on, straight from REQUIRED_EV."""
    lines = []
    for fam in sorted(REQUIRED_EV):
        need = REQUIRED_EV[fam]
        evs = ('-' if need is None else
               ' | '.join(need if isinstance(need, tuple) else (need,)))
        lines.append('%-11s %-24s %s'
                     % (fam, evs, REQUIRE_DOC.get(fam, '')))
    return '\n'.join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('journal', nargs='?', default=None,
                    help='path to a RunJournal .jsonl file')
    ap.add_argument('--top', type=int, default=10,
                    help='slowest spans to list')
    ap.add_argument('--json', default=None, metavar='PATH',
                    help="write the summary dict as JSON ('-' = stdout)")
    ap.add_argument('--smoke', action='store_true',
                    help='validate instead of report; nonzero exit on '
                         'an empty/malformed/step-less journal')
    ap.add_argument('--require', default='step',
                    choices=sorted(REQUIRED_EV),
                    help='record family --smoke insists on (default: '
                         'step; see --list-requires for the catalog)')
    ap.add_argument('--list-requires', action='store_true',
                    help='print every --require family with the '
                         'journal events it gates on, then exit')
    args = ap.parse_args(argv)

    if args.list_requires:
        print(list_requires())
        return 0
    if args.journal is None:
        ap.error('journal path required (or use --list-requires)')

    if args.smoke:
        problems = check_journal(args.journal, require=args.require)
        if problems:
            print('JOURNAL SMOKE FAILED (%s):' % args.journal,
                  file=sys.stderr)
            for p in problems:
                print('  - %s' % p, file=sys.stderr)
            return 1
        print('journal smoke OK (%s)' % args.journal)
        return 0

    records, malformed = load_journal(args.journal)
    summary = summarize(records, malformed)
    if args.json == '-':
        json.dump(summary, sys.stdout, indent=2, sort_keys=True)
        print()
    else:
        if args.json:
            with open(args.json, 'w') as f:
                json.dump(summary, f, indent=2, sort_keys=True)
        print(render(summary, top=args.top))
    return 0


if __name__ == '__main__':
    sys.exit(main())
