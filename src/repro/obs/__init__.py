"""Unified telemetry subsystem (DESIGN.md §14).

Three layers, one nervous system for every production subsystem in the
repo (``TrainRunner``, ``FoldEngine``/``ContinuousScheduler``,
``DataPipeline``, ``CheckpointManager``):

* :mod:`repro.obs.registry` — a **metric registry** (counters, gauges,
  histograms and named time series, tagged by subsystem/bucket/plan) with
  pluggable sinks (:mod:`repro.obs.sinks`: in-memory for tests, JSONL file
  writer for runs, periodic console summary).  Subsystems route their
  reporting through a registry instead of private dicts; the historical
  attributes (``TrainRunner.history``, ``FoldEngine.stats``,
  ``DataPipeline.report``) remain as thin views over registry contents.
* :mod:`repro.obs.tracing` — a **host-side span tracer**: nestable
  ``with trace_span("featurize", step=...)`` spans across
  featurize→queue→device-put→step→eval→checkpoint (train) and
  admit→recycle-step→heads→cache (serve), exported as
  ``jax.profiler`` annotations on the device trace's clock and optionally
  as Chrome-trace/Perfetto JSON, plus an opt-in ``jax.profiler.trace``
  capture window aligned to the same step ids.
* :mod:`repro.obs.attribution` — the **roofline-vs-measured report**:
  measured per-step time confronted with
  ``analysis.roofline.predict_step_time`` for the active ``ParallelPlan``,
  achieved model-FLOP/s, MFU against ``HW`` peak, and goodput (the
  non-stall, non-eval/checkpoint fraction) — the cost model that picks
  plans (``auto_plan``) becomes a continuously validated observable.
"""
from repro.obs.attribution import attribution_report, describe_attribution
from repro.obs.registry import Counter, Gauge, Histogram, MetricRegistry
from repro.obs.sinks import ConsoleSink, JsonlSink, MemorySink
from repro.obs.tracing import (ProfileWindow, SpanTracer, get_tracer,
                               parse_profile_steps, set_tracer, step_span,
                               trace_span)

__all__ = [
    "MetricRegistry", "Counter", "Gauge", "Histogram",
    "MemorySink", "JsonlSink", "ConsoleSink",
    "SpanTracer", "trace_span", "step_span", "set_tracer", "get_tracer",
    "ProfileWindow", "parse_profile_steps",
    "attribution_report", "describe_attribution",
]
