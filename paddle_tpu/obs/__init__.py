"""``paddle_tpu.obs`` — the unified telemetry subsystem.

One instrument panel for every tier (docs/observability.md): the
process-wide metrics registry (counters/gauges/histograms, Prometheus +
JSON exposition, ``--metrics_port`` HTTP endpoint), the trainer's
step-time breakdown with a live MFU gauge (the analytic-FLOPs walker
``analysis.flops`` on the trainer's step), the process's set-up record
(``obs/timeline.py``: import, init, the trainer's build and the first
iteration as phases, JAX's own trace / lower / compile events as their
parts), the rank-tagged structured event
journal (``--obs_journal`` + ``python -m paddle_tpu obs merge``),
request-level distributed tracing (``obs/trace.py``: span-based
tail-latency attribution across serving, the decode slot table, and the
gang — ``python -m paddle_tpu obs trace`` / ``--format=perfetto``), and
on-demand ``jax.profiler`` capture windows (``--profile_steps`` /
SIGUSR2).

Consumed by the trainer (phases + journal + profiler), serving
(``ServerMetrics`` is a registry view), the gang supervisor (resize /
death / hang journal records), and the pserver tier (snapshot commits).
Telemetry never adds host transfers inside jit — gated by ``lint --obs``.
"""

from paddle_tpu.obs.journal import (EventJournal, close_journal, get_journal,
                                    journal_event, journal_files,
                                    journal_path, merge_journals,
                                    read_journal, set_journal_context)
from paddle_tpu.obs.profiler import ProfilerCapture
from paddle_tpu.obs.registry import (Counter, Gauge, Histogram,
                                     MetricsRegistry, ensure_metrics_server,
                                     get_registry, reset_registry,
                                     start_metrics_server)
from paddle_tpu.obs.timeline import (PHASES, StepTimeline, close_setup,
                                     reset_setup, setup_phase, setup_record)
from paddle_tpu.obs.trace import (Span, Tracer, collect_traces,
                                  format_trace_tree, get_tracer,
                                  perfetto_trace, reset_tracer,
                                  trace_summaries)

__all__ = [
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "get_registry",
    "reset_registry",
    "start_metrics_server",
    "ensure_metrics_server",
    "StepTimeline",
    "PHASES",
    "setup_phase",
    "setup_record",
    "close_setup",
    "reset_setup",
    "EventJournal",
    "journal_path",
    "journal_files",
    "read_journal",
    "merge_journals",
    "get_journal",
    "journal_event",
    "set_journal_context",
    "close_journal",
    "ProfilerCapture",
    "Span",
    "Tracer",
    "get_tracer",
    "reset_tracer",
    "collect_traces",
    "trace_summaries",
    "format_trace_tree",
    "perfetto_trace",
]
