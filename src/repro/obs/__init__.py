"""repro.obs: metrics registry, phase tracing, Perfetto export, perf gate.

The observability layer the paper's argument is made of: phase-level
visibility into where halo-exchange time goes, and a regression gate on
the measured trajectory.

* :mod:`repro.obs.registry` — typed counters/gauges/histograms with
  per-block snapshots and JSONL export; every existing stats surface
  (``halo_stats``/``overlap_stats``/``pair_stats``, ledger summaries,
  ``sched_history``, the overflow monitor) publishes here.
* :mod:`repro.obs.tracing` — ``jax.named_scope`` phase annotations,
  on-device per-step ledger counters (barrier-neutral: bitwise-identical
  trajectories with tracing on), and the host-side ``span``/``time_fn``
  timing API shared by ``benchmarks/`` and ``launch/dryrun.py``; spans
  are also ``obs.<name>`` profiler annotations.
* :mod:`repro.obs.perfetto` — metrics JSONL -> Chrome/Perfetto
  ``trace.json`` of the measured host spans and counters
  (``python -m repro.obs metrics.jsonl --out trace.json``).
* :mod:`repro.obs.gate` — drift check of a fresh
  ``BENCH_pipeline.json`` against the checked-in baseline (the CI
  ``perf-smoke`` job).
"""
from repro.obs.gate import (
    DEFAULT_GATE,
    KEY_FIELDS,
    SCHEMA_VERSION,
    cell_key,
    compare_bench,
    gate_files,
)
from repro.obs.perfetto import export_trace, to_trace
from repro.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
    iter_kind,
    jsonsafe,
    load_jsonl,
)
from repro.obs.tracing import (
    HOST_SPANS,
    NULL_TRACER,
    PHASES,
    PhaseTracer,
    Span,
    TimingResult,
    is_obs_metric,
    span,
    strip_obs_metrics,
    time_fn,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "default_registry", "iter_kind", "jsonsafe", "load_jsonl",
    "HOST_SPANS", "NULL_TRACER", "PHASES", "PhaseTracer", "Span",
    "TimingResult", "is_obs_metric", "span", "strip_obs_metrics", "time_fn",
    "export_trace", "to_trace",
    "DEFAULT_GATE", "KEY_FIELDS", "SCHEMA_VERSION", "cell_key",
    "compare_bench", "gate_files",
]
