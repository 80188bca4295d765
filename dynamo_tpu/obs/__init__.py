"""Observability: always-on request tracing, flight recorder, and the
span-to-metrics bridge.

Fills the role of the reference's tracing layer
(reference: lib/runtime/src/logging.rs traceparent propagation plus the
per-phase serving metrics the SLA planner consumes): a dependency-free
Dapper-style tracer keyed off ``TraceContext``, a bounded in-process
flight recorder dumpable as JSONL or Chrome trace-event JSON
(Perfetto-loadable), and a bridge deriving ``dynamo_request_*``
Prometheus histograms from closed spans so operators get per-phase
aggregates without an external trace backend.
"""

from dynamo_tpu.obs.bridge import SpanMetricsBridge
from dynamo_tpu.obs.fleet import (
    DEFAULT_SLO_SPECS,
    EwmaAnomaly,
    FleetAggregator,
    SloEngine,
    SloSpec,
    parse_slo_specs,
)
from dynamo_tpu.obs.costmodel import (
    HardwareSpec,
    KernelCost,
    hw_spec_for,
)
from dynamo_tpu.obs.profiler import (
    PerfMetrics,
    StepPerfProfiler,
    get_perf_metrics,
    install_perf_metrics,
    phase,
)
from dynamo_tpu.obs.recorder import FlightRecorder, StepProfiler
from dynamo_tpu.obs.tracer import (
    TRACE_KEY,
    Span,
    Tracer,
    get_tracer,
    trace_context_of,
)

__all__ = [
    "DEFAULT_SLO_SPECS",
    "TRACE_KEY",
    "EwmaAnomaly",
    "FleetAggregator",
    "FlightRecorder",
    "SloEngine",
    "SloSpec",
    "parse_slo_specs",
    "HardwareSpec",
    "KernelCost",
    "PerfMetrics",
    "Span",
    "SpanMetricsBridge",
    "StepPerfProfiler",
    "StepProfiler",
    "Tracer",
    "get_perf_metrics",
    "get_tracer",
    "hw_spec_for",
    "install_perf_metrics",
    "phase",
    "trace_context_of",
]
