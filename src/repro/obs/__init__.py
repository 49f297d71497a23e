"""Observability: structured tracing, metrics, and trace exporters.

The measurement layer under everything else in the repo: the paper's
claims are about *measured* period and energy, so the runtime, governor,
simulator and serve engine all need a cheap way to say what happened and
when. This package provides it without importing anything above it —
call sites receive a :class:`Tracer` / :class:`MetricsRegistry` by
argument (duck-typed, optional, default off), so the layering in
``docs/architecture.md`` is unchanged.

  - :mod:`repro.obs.trace`   — :class:`Tracer`: monotonic-clock spans,
    instants and counter samples recorded into per-thread ring buffers
    (no locks on the hot path, bounded memory, explicit :meth:`drain`),
    and :class:`Span`, the one span helper: a block timed into the
    ``jax.profiler`` trace and, given an enabled tracer, into its ring;
  - :mod:`repro.obs.metrics` — :class:`MetricsRegistry`: plain-dict
    counters, gauges and windowed histograms (p50/p95/p99);
  - :mod:`repro.obs.export`  — Chrome/Perfetto ``trace.json`` writer
    (thread-per-replica rows, counter tracks) + loader;
  - :mod:`repro.obs.report`  — trace analysis (per-stage utilization,
    replica imbalance, rebuild stall, over-cap intervals) behind the
    ``tools/trace_report.py`` CLI, plus measured-energy attribution
    (:func:`attribute_energy`) against a power capture;
  - :mod:`repro.obs.power`   — measured-power ingestion: RAPL
    ``energy_uj`` logs and macOS ``powermetrics`` captures parsed into
    a normalized :class:`PowerCapture` timeline, synthetic capture
    generators for CI, and trace/schedule alignment into
    :class:`CaptureWindow` calibration rows.

See docs/observability.md for the event/metric catalog and the overhead
gate (``benchmarks/sched_perf.py --check`` holds the tracer under 5%
period inflation on the threaded runtime hot path, within one run).
"""
from .export import load_trace, to_chrome_events, write_perfetto  # noqa: F401
from .metrics import MetricsRegistry  # noqa: F401
from .power import (  # noqa: F401
    CaptureWindow,
    PowerCapture,
    PowerSample,
    UtilizationWindow,
    capture_windows_from_trace,
    parse_powermetrics,
    parse_rapl_log,
    synthesize_powermetrics,
    synthesize_rapl_log,
    windows_from_schedule,
)
from .report import (  # noqa: F401
    EnergyAttribution,
    StageAttribution,
    TraceReport,
    WindowAttribution,
    analyze_trace,
    attribute_energy,
)
from .trace import NULL_TRACER, Span, TraceEvent, Tracer  # noqa: F401
