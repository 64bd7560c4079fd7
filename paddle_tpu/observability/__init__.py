"""Unified telemetry for the paddle_tpu stack (OBSERVABILITY.md).

Two complementary surfaces, both stdlib-only and import-cycle-free:

- :mod:`~paddle_tpu.observability.metrics` — a thread-safe metrics
  registry (counters, gauges, log2-bucket histograms) with Prometheus
  text exposition and a JSON snapshot. The Executor, Trainer, serving
  runtime and resilience layer all publish into
  :func:`default_registry`.
- :mod:`~paddle_tpu.observability.journal` — a structured JSONL run
  journal (:class:`RunJournal`) of typed events with monotonic
  timestamps and a run id: steps, XLA compiles, executor cache
  hits/misses, checkpoints, serving batches, anomaly trips. Off by
  default; install one with :func:`journal` / :func:`set_journal` and
  render it with ``tools/obs_report.py`` or merge it into a
  chrome://tracing view with ``tools/timeline.py --journal_path``.
- :mod:`~paddle_tpu.observability.tracing` — distributed tracing over
  the journal: propagated :class:`TraceContext` ids, ``span_begin`` /
  ``span_end`` / ``span_link`` events, a ``PTPU_TRACE_SAMPLE``
  sampling knob. Reconstruct trees with ``tools/trace_report.py``,
  merge per-process journals with repeated ``--journal_path`` flags.
- :mod:`~paddle_tpu.observability.perf` — the performance
  observatory: per-program :class:`ProgramLedger` (XLA cost/memory
  analysis) captured on the Executor's compile-miss path when enabled
  (``PTPU_PERF=1`` / :func:`perf.enable_capture`), live
  ``perf_mfu{program=}`` / roofline gauges joined from measured step
  walls, and the :class:`PerfBaseline` regression sentinel behind
  ``tools/perf_report.py``.
- :mod:`~paddle_tpu.observability.telemetry` — the live telemetry
  plane: a per-process HTTP scrape endpoint (``/metrics`` /
  ``/health`` / ``/ledgers``), the ``PTPU_TELEMETRY`` env contract,
  and the :class:`TelemetryAggregator` merging every endpoint into
  fleet-wide rollups under ``host=``/``replica=`` labels
  (``tools/fleet_top.py`` renders it live).
- :mod:`~paddle_tpu.observability.slo` — declared objectives (p99
  latency, shed/error rate) evaluated as multi-window burn rates,
  published as ``slo_burn_rate{slo=}`` gauges and consumable by the
  fleet autoscaler.
- :mod:`~paddle_tpu.observability.flight` — the crash flight
  recorder: an always-on bounded ring of recent journal-grade events
  that dumps an atomic postmortem bundle (ring + metrics + unclosed
  spans + health + ledgers) on watchdog/breaker/anomaly trips, kills
  and SIGTERM, rendered by ``tools/postmortem.py``.
"""
from .metrics import (Counter, Gauge, Histogram,  # noqa: F401
                      MetricsRegistry, default_registry,
                      DEFAULT_SECONDS_EDGES)
from .journal import (SCHEMA_VERSION, JOURNAL_ENV, RunJournal,  # noqa
                      set_journal, get_journal, journal,
                      journal_active, emit, read_journal,
                      install_env_journal)
from .tracing import (TraceContext, Span, NULL_SPAN,  # noqa: F401
                      start_span, span, current_span, current_context,
                      link, emit_span, phase, sample_rate,
                      parent_from_env, TRACE_PARENT_ENV,
                      TRACE_SAMPLE_ENV)
from . import perf  # noqa: F401
from .perf import (ProgramLedger, LedgerBook, PerfBaseline,  # noqa
                   PERF_ENV)
from . import flight  # noqa: F401
from .flight import FLIGHT_ENV  # noqa: F401
from . import telemetry  # noqa: F401
from .telemetry import (TelemetryAggregator,  # noqa: F401
                        TelemetryServer, serve_telemetry,
                        install_env_telemetry, parse_exposition,
                        register_health_provider,
                        unregister_health_provider, collect_health,
                        TELEMETRY_ENV, TELEMETRY_DIR_ENV)
from . import slo as slo  # noqa: F401
from .slo import SLO, SLOEngine  # noqa: F401

__all__ = [
    'Counter', 'Gauge', 'Histogram', 'MetricsRegistry',
    'default_registry', 'DEFAULT_SECONDS_EDGES',
    'SCHEMA_VERSION', 'JOURNAL_ENV', 'RunJournal', 'set_journal',
    'get_journal',
    'journal', 'journal_active', 'emit', 'read_journal',
    'install_env_journal',
    'TraceContext', 'Span', 'NULL_SPAN', 'start_span', 'span',
    'current_span', 'current_context', 'link', 'emit_span', 'phase',
    'sample_rate', 'parent_from_env', 'TRACE_PARENT_ENV',
    'TRACE_SAMPLE_ENV',
    'perf', 'ProgramLedger', 'LedgerBook', 'PerfBaseline', 'PERF_ENV',
    'flight', 'FLIGHT_ENV',
    'telemetry', 'TelemetryAggregator', 'TelemetryServer',
    'serve_telemetry', 'install_env_telemetry', 'parse_exposition',
    'register_health_provider', 'unregister_health_provider',
    'collect_health', 'TELEMETRY_ENV', 'TELEMETRY_DIR_ENV',
    'slo', 'SLO', 'SLOEngine',
]
