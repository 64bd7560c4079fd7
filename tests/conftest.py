"""Test config: the CPU backend with 8 virtual devices, so multi-chip
sharding paths are exercised without TPU hardware (SURVEY.md §4). The
chip is reached only through chip_smoke.py and benchmark/chip/run.py.
"""
import os
import re

import pytest

os.environ['JAX_PLATFORMS'] = 'cpu'
# Tests compile what they test: a persistent-cache hit from an earlier
# run would hide a program that no longer compiles. Set in the
# environment so worker processes the tests start inherit it.
os.environ['JAX_ENABLE_COMPILATION_CACHE'] = 'false'

# Honor an externally chosen device count (either convention) for debugging
# smaller meshes; default to 8.
_m = re.search(r'xla_force_host_platform_device_count=(\d+)',
               os.environ.get('XLA_FLAGS', ''))
_n = int(_m.group(1)) if _m else int(
    os.environ.get('PADDLE_TPU_TEST_DEVICES', 8))

import jax  # noqa: E402

if _m is None:
    jax.config.update('jax_num_cpu_devices', _n)


@pytest.fixture
def amp():
    """core.amp with its state handed back as it was found, so no other
    test sees AMP on."""
    from paddle_tpu.core import amp as _amp
    saved = dict(_amp._STATE)
    yield _amp
    _amp._STATE.clear()
    _amp._STATE.update(saved)


def pytest_configure(config):
    config.addinivalue_line(
        'markers', 'slow: long-running tests excluded from tier-1')
    config.addinivalue_line(
        'markers',
        'faultinject: tests that drive the resilience fault-injection '
        'harness (tier-1; filter with -m "not faultinject")')
    config.addinivalue_line(
        'markers',
        'serving: tests of the paddle_tpu.serving runtime (tier-1, '
        'CPU-safe; filter with -m "not serving")')
    config.addinivalue_line(
        'markers',
        'observability: tests of the metrics registry / run journal / '
        'telemetry tools (tier-1; filter with -m "not observability")')
    config.addinivalue_line(
        'markers',
        'chaos: deterministic chaos-harness tests of the serving SLO '
        'guardrails — breaker/watchdog/drain/close escalation (tier-1; '
        'filter with -m "not chaos")')
    config.addinivalue_line(
        'markers',
        'pipeline: tests of the pipelined training hot loop — async '
        'prefetch, K-step chained dispatch, non-blocking fetch '
        '(tier-1; filter with -m "not pipeline")')
    config.addinivalue_line(
        'markers',
        'compiler: tests of the paddle_tpu.compiler pass pipeline — '
        'semantic equivalence, pass idempotence, cache keying '
        '(tier-1; filter with -m "not compiler")')
    config.addinivalue_line(
        'markers',
        'partition: tests of the paddle_tpu.partition subsystem — '
        'CPU-fallback bit-exactness, multi-device CPU-mesh training '
        'parity, per-(program, sharding, mesh) compile caching, '
        'sharded serving load (tier-1; filter with -m "not partition")')
    config.addinivalue_line(
        'markers',
        'fleet: tests of the paddle_tpu.fleet serving tier — replica '
        'router (load-aware routing, quarantine, requeue, rolling '
        'swap, supervised restart) and continuous-batching decode '
        '(tier-1; filter with -m "not fleet")')
    config.addinivalue_line(
        'markers',
        'elastic: tests of partition-aware resilience — sharded '
        'checkpoints, topology-portable restore (N-device save -> '
        'M-device resume), SIGTERM preemption safety, mesh-degraded '
        'autoresume, concurrent-saver locking (tier-1; filter with '
        '-m "not elastic")')
    config.addinivalue_line(
        'markers',
        'zero: tests of the ZeRO-2 data-parallel trainer — bucketed '
        'reduce-scatter gradient tail, sharded optimizer update, '
        'replicated-path bit-exactness, chained-dispatch overlap '
        '(tier-1; filter with -m "not zero")')
    config.addinivalue_line(
        'markers',
        'multihost: tests of the multi-host elastic runtime — pod '
        'launcher, bounded bootstrap handshake, cross-host agreement, '
        'heartbeat host-loss detection, degraded relaunch + bit-exact '
        'resume (tier-1; filter with -m "not multihost")')
    config.addinivalue_line(
        'markers',
        'analysis: tests of the paddle_tpu.analysis static verifier — '
        'dataflow/shape/sharding inference, executor-path '
        'ProgramInvalid/FeedInvalid, the pass-pipeline sanitizer, the '
        'analyze_program CLI (tier-1; filter with -m "not analysis")')
    config.addinivalue_line(
        'markers',
        'lint: tests running tools/lint_repo.py over the tree against '
        'its pinned allowlist (tier-1; filter with -m "not lint")')
    config.addinivalue_line(
        'markers',
        'perfobs: tests of the performance observatory — per-program '
        'cost/memory ledgers on the compile-miss path, MFU/roofline '
        'math, the PerfBaseline regression sentinel, tools/'
        'perf_report.py (tier-1; filter with -m "not perfobs")')
    config.addinivalue_line(
        'markers',
        'kvcache: tests of the paged KV-cache subsystem — PagePool '
        'allocator, paged-attention bit-identity, admission '
        'backpressure, prefill engine/server, disaggregated '
        'prefill->decode (tier-1; filter with -m "not kvcache")')
    config.addinivalue_line(
        'markers',
        'telemetry: tests of the fleet telemetry plane — scrape '
        'endpoint, exposition parser round-trip, cross-host '
        'aggregation/retire, SLO burn-rate engine, crash flight '
        'recorder (tier-1; filter with -m "not telemetry")')
