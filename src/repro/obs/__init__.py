"""Observability: span tracing, metrics, and §2.6 cost accounting.

The flight recorder for the solve path (DESIGN.md §12). Pass a
:class:`Tracer` to ``rank_list_with_stats(..., tracer=...)`` (or the
graphalg/treealg front doors) and every stage execution, retry,
checkpoint, and capacity-estimation pre-pass is recorded as a span with
its measured wall time, statically counted collective footprint, and
the §2.6 predicted time. Each span is also a host annotation in the
``jax.profiler`` trace, beside the device's ops; the residual table is
:func:`~repro.obs.export.format_residual_table`.

Instrumentation is host-side only and never perturbs a traced program —
the no-perturbation rule is pinned by ``tests/test_obs.py``.
"""
from repro.obs.trace import (ANNOTATION_PREFIX, NULL_TRACER, NullTracer,
                             Span, Tracer, ensure, profile_spans,
                             span_tree_lines)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               Text, ingest_host_stats, json_safe,
                               json_safe_stats)
from repro.obs.cost import (footprint_summary, format_skew_table,
                            predict_footprint, predict_solve, predict_stage,
                            skew_rows, total_collectives)
from repro.obs.export import (format_residual_table, residual_rows,
                              residual_summary)
from repro.obs.telemetry import (StageRecord, TELEMETRY_HELP, dkw_backtest,
                                 format_headroom_table, headroom_rows,
                                 utilization)

__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER", "Span", "ensure",
    "ANNOTATION_PREFIX", "profile_spans", "span_tree_lines",
    "Counter", "Gauge", "Histogram", "Text", "MetricsRegistry",
    "ingest_host_stats", "json_safe", "json_safe_stats",
    "predict_footprint", "predict_stage", "predict_solve",
    "footprint_summary", "total_collectives",
    "skew_rows", "format_skew_table",
    "residual_rows", "format_residual_table", "residual_summary",
    "StageRecord", "TELEMETRY_HELP", "dkw_backtest",
    "format_headroom_table", "headroom_rows", "utilization",
]
