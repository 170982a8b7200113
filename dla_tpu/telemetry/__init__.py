"""Unified telemetry: the observability spine every subsystem reports
through (docs/OBSERVABILITY.md).

- registry        — metric instruments, the name CATALOG, snapshot +
                    Prometheus renderings
- stepclock       — step-time decomposition and goodput accounting
- collector       — in-graph scalar collection (zero extra compiles)
- mfu             — MFU math + per-chip peak FLOPs / HBM tables
- flight_recorder — crash postmortems from a bounded event ring
- exporter        — stdlib HTTP ``/metrics`` + readiness ``/healthz``
- trace           — thread-aware spans exported as Chrome-trace JSON
- trace_context   — cross-process trace propagation (traceparent ids,
                    per-process span spools for tools/trace_merge.py)
- aggregate       — pod-wide per-host step-time/goodput + straggler,
                    and gossip-fed fleet-wide metrics federation
- slo             — rolling-window SLOs with burn-rate alerting
- xla_introspect  — retrace attribution + compiled-fn cost/memory gauges,
                    the lower / compile split, process-wide compile
                    accounting (persistent-cache hits and misses)
- anomaly         — rolling median/MAD triage with one-shot capture
"""
from dla_tpu.telemetry.registry import (
    CATALOG,
    Counter,
    FuncGauge,
    Gauge,
    Histogram,
    MetricRegistry,
    MetricSpec,
    catalog_names,
    is_catalog_name,
    parse_prometheus_text,
    prometheus_name,
)
from dla_tpu.telemetry.stepclock import StepClock
from dla_tpu.telemetry.collector import (
    CollectorConfig,
    capture,
    collect_train_scalars,
    stash_rms,
    stash_scalar,
)
from dla_tpu.telemetry.mfu import (
    MFUCalculator,
    PEAK_BF16_FLOPS,
    PEAK_HBM_BW,
    flops_per_token,
    hbm_bw_for,
    peak_flops_for,
)
from dla_tpu.telemetry.flight_recorder import FlightRecorder
from dla_tpu.telemetry.exporter import MetricsHTTPServer, ReadinessProbe
from dla_tpu.telemetry.trace import Tracer, get_tracer, install_tracer
from dla_tpu.telemetry.trace_context import (
    TRACEPARENT_HEADER,
    SpanSpool,
    TraceContext,
    open_spool,
    read_spool,
    spool_paths,
)
from dla_tpu.telemetry.aggregate import (
    FleetMetricsAggregator,
    PodAggregator,
    SkewSimulator,
)
from dla_tpu.telemetry.slo import SLO, SLOWatch
from dla_tpu.telemetry.xla_introspect import (
    IntrospectedFunction,
    live_array_bytes,
    register_live_bytes_gauge,
)
from dla_tpu.telemetry.anomaly import (
    AnomalyConfig,
    AnomalyMonitor,
    RollingDetector,
)

__all__ = [
    "AnomalyConfig", "AnomalyMonitor", "CATALOG", "CollectorConfig",
    "Counter", "FleetMetricsAggregator", "FlightRecorder", "FuncGauge",
    "Gauge", "Histogram", "IntrospectedFunction", "MFUCalculator",
    "MetricRegistry", "MetricSpec", "MetricsHTTPServer",
    "PEAK_BF16_FLOPS", "PEAK_HBM_BW", "PodAggregator", "ReadinessProbe",
    "RollingDetector", "SLO", "SLOWatch", "SkewSimulator", "SpanSpool",
    "StepClock", "TRACEPARENT_HEADER", "TraceContext", "Tracer",
    "capture", "catalog_names", "collect_train_scalars",
    "flops_per_token", "get_tracer", "hbm_bw_for", "install_tracer",
    "is_catalog_name", "live_array_bytes", "open_spool",
    "parse_prometheus_text", "peak_flops_for", "prometheus_name",
    "read_spool", "register_live_bytes_gauge", "spool_paths",
    "stash_rms", "stash_scalar",
]
