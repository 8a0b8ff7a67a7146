"""repro.obs — measured runtime tracing, drift analysis, and metrics.

The observability substrate the paper's timeline claims are checked
against.  Three layers, one import:

- :mod:`repro.obs.trace` — :class:`TraceRecorder` ring buffer; pass one
  as ``OOCSolver.factor(a, trace=rec)`` and every executor records one
  measured :class:`Span` per schedule op (``block_until_ready``-fenced).
  The :data:`NULL` recorder is the zero-cost default.
- :mod:`repro.obs.export` / :mod:`repro.obs.drift` — render measured
  traces as chrome://tracing JSON in the simulator's lane vocabulary,
  and align them op-by-op against ``simulate``/``simulate_multi`` into
  a :class:`DriftReport` (per-kind ratios, top mispredictions, overlap
  efficiency).  ``repro.tune.calibrate(refine_from=trace)`` closes the
  loop by refitting the :class:`~repro.core.analytics.HardwareModel`.
- :mod:`repro.obs.metrics` — the process-wide :data:`REGISTRY`
  absorbing plan-cache stats, executor counters, and serve metrics
  under one :func:`snapshot` / :func:`render_text`.
- :mod:`repro.obs.timed` — ``jax.profiler`` spans with their bytes on
  the untraced path (:func:`span`, which also bumps the executed-bytes
  counters) and the ``jax.named_scope`` names of the executor's tile ops
  (:func:`scope`).
"""
from .drift import MODELED_KINDS, DriftReport, drift_report, total_abs_error
from .export import chrome_trace_measured, trace_view, write_jsonl
from .metrics import REGISTRY, MetricsRegistry, render_text, snapshot
from .timed import parse_scope, scope, span
from .trace import NULL, NullRecorder, Span, TraceRecorder, is_active, resolve

__all__ = [
    "TraceRecorder", "NullRecorder", "Span", "NULL", "resolve", "is_active",
    "chrome_trace_measured", "trace_view", "write_jsonl",
    "DriftReport", "drift_report", "total_abs_error", "MODELED_KINDS",
    "MetricsRegistry", "REGISTRY", "snapshot", "render_text",
    "span", "scope", "parse_scope",
]
