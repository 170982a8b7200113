"""Continuous-batching inference core (dla_tpu/serving).

The serving layer decouples REQUEST admission from STEP execution — the
property that lets a static-shape, never-recompiled decode loop serve
requests that arrive, finish, and get evicted at arbitrary times
(Podracer-style decoupling, arxiv 2104.06272; vLLM-style paged KV).

Modules:
  kv_blocks  block-paged KV cache: fixed-size page pool + host-side
             allocator + the in-graph block-table gather/scatter
  scheduler  request lifecycle state machine (WAITING -> PREFILL ->
             DECODE -> FINISHED/EVICTED), strict-FCFS chunked
             admission, eviction-on-OOM
  server     the host engine loop driving jitted prefill/decode steps
  metrics    queue depth, TTFT, inter-token latency, page occupancy,
             preemption counters
  resilience admission control + load shedding, degradation ladder,
             engine Supervisor (watchdog/rebuild/deterministic replay),
             circuit breaker
  fleet      multi-engine FleetRouter (cache-aware placement via
             PrefixCache.peek, sticky-prefix affinity, per-member
             supervisors) + SLO-driven Autoscaler with zero-loss
             scale-down (docs/SERVING.md "Fleet")
  migration  KVMigrator: prefill/decode disaggregation — export a
             mid-decode request's committed KV pages as a
             MigrationTicket, install on another engine, resume
             bit-identically (docs/SERVING.md "Disaggregated
             prefill/decode")
  gateway    ServingGateway: stdlib HTTP front door — POST /v1/generate
             with per-token SSE streaming, disconnect -> cancel,
             shed -> 429 / deadline -> 408 / draining -> 503
             (docs/SERVING.md "Gateway & federation")
  federation GossipBeater + FederatedRouter: cross-host placement over
             N gateway-fronted fleets with the FleetRouter score,
             replay-on-failure zero loss, MigrationTicket wire handoff
"""
from dla_tpu.serving.federation import (
    FederatedRouter,
    FederationConfig,
    FederationError,
    FederationMetrics,
    GossipBeater,
)
from dla_tpu.serving.fleet import (
    Autoscaler,
    FleetConfig,
    FleetMetrics,
    FleetRouter,
)
from dla_tpu.serving.gateway import (
    GatewayConfig,
    GatewayMetrics,
    ServingGateway,
)
from dla_tpu.serving.kv_blocks import (
    PageAllocator,
    PagedKVCache,
    PageGeometry,
    PrefixCache,
)
from dla_tpu.serving.metrics import ServingMetrics
from dla_tpu.serving.migration import (
    KVMigrator,
    MigrationConfig,
    MigrationError,
    MigrationTicket,
)
from dla_tpu.serving.resilience import (
    AdmissionController,
    CircuitBreaker,
    DegradationLadder,
    DeviceStepError,
    NaNLogitsError,
    ShedConfig,
    Supervisor,
    SupervisorConfig,
)
from dla_tpu.serving.scheduler import (
    TERMINAL_STATES,
    Request,
    RequestState,
    Scheduler,
    SchedulerConfig,
)
from dla_tpu.serving.server import ServingConfig, ServingEngine
# per-request sampling contract lives in ops.sampling (shared with the
# batch generate fn); re-exported here because submit() speaks it
from dla_tpu.ops.sampling import SamplingParams

__all__ = [
    "SamplingParams",
    "AdmissionController",
    "Autoscaler",
    "CircuitBreaker",
    "DegradationLadder",
    "DeviceStepError",
    "FederatedRouter",
    "FederationConfig",
    "FederationError",
    "FederationMetrics",
    "FleetConfig",
    "FleetMetrics",
    "FleetRouter",
    "GatewayConfig",
    "GatewayMetrics",
    "GossipBeater",
    "KVMigrator",
    "MigrationConfig",
    "MigrationError",
    "MigrationTicket",
    "NaNLogitsError",
    "PageAllocator",
    "PagedKVCache",
    "PageGeometry",
    "PrefixCache",
    "Request",
    "RequestState",
    "Scheduler",
    "SchedulerConfig",
    "ServingConfig",
    "ServingEngine",
    "ServingGateway",
    "ServingMetrics",
    "ShedConfig",
    "Supervisor",
    "SupervisorConfig",
    "TERMINAL_STATES",
]
