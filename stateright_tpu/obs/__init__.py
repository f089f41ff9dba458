"""Unified observability for the checking engines.

* :class:`~stateright_tpu.obs.metrics.Metrics` — the per-run metrics
  registry (counters, phase timers, observed maxima) behind every
  engine's ``profile()``, with the canonical key glossary
  :data:`~stateright_tpu.obs.metrics.GLOSSARY`.
* :class:`~stateright_tpu.obs.trace.RunTrace` — the structured JSONL
  run-trace event stream enabled via ``tpu_options(trace=...)``
  (zero-cost :data:`~stateright_tpu.obs.trace.NULL_TRACE` when fully
  off), with per-event requirements pinned by
  :data:`~stateright_tpu.obs.trace.EVENT_SCHEMA`.
* :class:`~stateright_tpu.obs.spans.SpanRecorder` — each host phase as
  one interval: the span ring, the ``span_s.<phase>`` timer, and an
  ``sr.<phase>`` annotation in any ``jax.profiler`` trace.
* :class:`~stateright_tpu.obs.recorder.FlightRecorder` — the always-on
  bounded ring of recent trace events behind every checker, dumped as
  a JSONL postmortem artifact when a run dies (README.md
  § Observability, "Flight recorder").

* :mod:`~stateright_tpu.obs.aggregate` — the fleet timeline: merge any
  set of engine/job/service/fleet JSONL streams into one wall-anchored,
  identity-resolved event list (``tools/trace_report.py --fleet``).
* :mod:`~stateright_tpu.obs.prom` — Prometheus text exposition of
  ``Metrics`` registries (the service's ``GET /metrics`` scrape
  endpoint).

See README.md § Observability for the trace format and how to read a
stall; ``tools/trace_report.py`` renders a trace as a per-phase table.
(``aggregate`` and ``prom`` are imported lazily by their consumers —
not re-exported here — so ``import stateright_tpu.obs`` stays light.)
"""

from .artifacts import (ARTIFACT_NAMES, apply_artifact_dir,
                        artifact_paths)
from .metrics import (GAUGES, GLOSSARY, MAXIMA, Metrics, MetricsRing,
                      gc_totals)
from .recorder import FlightRecorder, default_flight_path
from .spans import (DEVICE_SPANS, SpanRecorder, analyze, annotate,
                    attach_attribution, loop_spans, ranked,
                    shard_imbalance, spans_from_events, top_stalls)
from .trace import (EVENT_SCHEMA, NULL_TRACE, NullTrace, RunTrace,
                    emit_trace_header, fault_info, identity_fields,
                    make_trace, new_run_id, validate_event)

__all__ = [
    "ARTIFACT_NAMES",
    "DEVICE_SPANS",
    "EVENT_SCHEMA",
    "FlightRecorder",
    "GAUGES",
    "GLOSSARY",
    "MAXIMA",
    "Metrics",
    "MetricsRing",
    "NULL_TRACE",
    "NullTrace",
    "RunTrace",
    "SpanRecorder",
    "analyze",
    "annotate",
    "apply_artifact_dir",
    "artifact_paths",
    "attach_attribution",
    "default_flight_path",
    "emit_trace_header",
    "fault_info",
    "gc_totals",
    "identity_fields",
    "loop_spans",
    "make_trace",
    "new_run_id",
    "ranked",
    "shard_imbalance",
    "spans_from_events",
    "top_stalls",
    "validate_event",
]
