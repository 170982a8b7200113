"""Shared metric registry: the one place metric NAMES are declared and
the one rendering path every exporter goes through.

Every subsystem (trainer, serving engine, resilience counters) creates
plain instruments — :class:`Counter`, :class:`Gauge`, :class:`Histogram`,
or a :class:`FuncGauge` bridging an existing attribute — and registers
them under a canonical ``area/name`` string. The registry then serves:

- ``snapshot()``  — the flat float dict a ``MetricsLogger`` writes as one
  JSONL row (same keys as before this layer existed; dashboards keep
  working),
- ``prometheus_text()`` — Prometheus text exposition (0.0.4) for the
  stdlib HTTP ``/metrics`` endpoint (telemetry/exporter.py).

Renames are a production hazard (a dashboard silently flatlines), so
registration validates names against :data:`CATALOG` — the metric
catalog documented in docs/OBSERVABILITY.md — and
``tools/check_metric_names.py`` greps emission sites for literals that
drifted from it. Instruments stay plain mutable objects on purpose: the
hot paths (serving decode loop, trainer step loop) mutate fields
directly with zero indirection; the registry only matters at
snapshot/scrape time.
"""
from __future__ import annotations

import dataclasses
import math
import re
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from dla_tpu.utils.logging import latency_summary

# --------------------------------------------------------------- instruments


class Counter:
    """Monotonic event count."""

    def __init__(self):
        self.value = 0

    def inc(self, n: int = 1) -> None:
        self.value += n


class Gauge:
    """Last-set value plus the observed peak (peak matters for capacity
    questions like "did the page pool ever fill?"). The peak seeds from
    the FIRST observed value — a gauge that only ever holds negative
    values reports that value as its peak, not a phantom 0.0."""

    def __init__(self):
        self.value = 0.0
        self._peak: Optional[float] = None

    def set(self, v: float) -> None:
        # dla: disable=host-sync-in-hot-loop -- Gauge.set receives host scalars; float() is type coercion, not a device fetch
        self.value = float(v)
        self._peak = (self.value if self._peak is None
                      else max(self._peak, self.value))

    @property
    def peak(self) -> float:
        return self.value if self._peak is None else self._peak


class FuncGauge:
    """Read-through gauge over an existing counter/attribute — how
    subsystems that already track a number (``AsyncCheckpointer.
    retries_total``, ``GuardState.bad_steps_total``) join the registry
    without double bookkeeping. ``fn`` is called at snapshot/scrape."""

    def __init__(self, fn: Callable[[], float]):
        self.fn = fn

    @property
    def value(self) -> float:
        return float(self.fn())


class Histogram:
    """Windowed latency sample store (last ``window`` observations) with
    p50/p95/mean via the shared percentile helper. A serving process
    runs indefinitely; the bound keeps the store O(1) while the window
    is wide enough that percentiles track current behavior.
    ``total_count``/``total_sum`` are unbounded (Prometheus summary
    semantics: _count/_sum are monotonic even though quantiles are
    windowed)."""

    def __init__(self, window: int = 4096):
        self.samples: deque = deque(maxlen=window)
        self.total_count = 0
        self.total_sum = 0.0

    def record(self, v: float) -> None:
        v = float(v)
        self.samples.append(v)
        self.total_count += 1
        self.total_sum += v

    def summary(self, prefix: str = "") -> Dict[str, float]:
        return latency_summary(self.samples, prefix)


# ------------------------------------------------------------------ catalog


@dataclasses.dataclass(frozen=True)
class MetricSpec:
    """One catalog row: canonical name, instrument kind, unit, cadence."""
    name: str
    kind: str          # "counter" | "gauge" | "histogram"
    unit: str = ""
    help: str = ""
    cadence: str = ""  # when it updates: "step" | "log_every" | "scrape"


def _s(name, kind, unit="", help="", cadence="log_every"):
    return MetricSpec(name, kind, unit, help, cadence)


#: The metric catalog — docs/OBSERVABILITY.md renders this table and
#: tools/check_metric_names.py fails the build on emission-site literals
#: not declared here. Dynamic families (``train/<loss_fn metric>``,
#: ``eval/<metric>``, per-layer collector keys ``train/rms/<path>``)
#: are declared as their documented members plus the PREFIXES entry.
CATALOG: Tuple[MetricSpec, ...] = (
    # -- training JSONL (trainer.fit log interval)
    _s("train/loss", "gauge", "nll", "windowed mean training loss"),
    _s("train/loss_instant", "gauge", "nll", "last step's loss"),
    _s("train/lr", "gauge", "1", "learning-rate schedule value"),
    _s("train/grad_norm", "gauge", "1", "global gradient norm (in-graph)"),
    _s("train/param_norm", "gauge", "1",
       "global parameter norm (in-graph collector)"),
    _s("train/update_norm", "gauge", "1",
       "global optimizer-update norm (in-graph collector)"),
    _s("train/guard_ok", "gauge", "bool", "finite-step guard verdict"),
    _s("train/guard_bad_steps", "counter", "steps",
       "non-finite steps seen by the guard"),
    _s("train/kl", "gauge", "nats", "policy/ref KL (RLHF)"),
    _s("train/kl_coef", "gauge", "1", "adaptive KL coefficient (RLHF)"),
    _s("train/reward_mean", "gauge", "1", "mean rollout reward (RLHF)"),
    _s("train/rm_score_mean", "gauge", "1", "mean raw RM score (RLHF)"),
    _s("train/response_len", "gauge", "tokens", "mean rollout length"),
    _s("train/zero_len_responses", "gauge", "1",
       "fraction of empty rollouts"),
    _s("train/preference_rate", "gauge", "1",
       "chosen>rejected rate (reward/DPO)"),
    _s("tokens_per_sec", "gauge", "tok/s", "global training throughput"),
    _s("tokens_per_sec_per_chip", "gauge", "tok/s/chip",
       "per-chip training throughput (the north-star rate)"),
    _s("ms_per_step", "gauge", "ms", "mean optimizer-step wall time"),
    _s("eval/loss", "gauge", "nll", "eval loss", "eval_every"),
    _s("eval/acc", "gauge", "1", "eval accuracy", "eval_every"),
    # -- step-time / goodput accounting (telemetry.stepclock)
    _s("telemetry/step_ms", "gauge", "ms", "mean wall time per step"),
    _s("telemetry/data_wait_ms", "gauge", "ms",
       "host wait on the data iterator"),
    _s("telemetry/h2d_ms", "gauge", "ms",
       "batch reshape + host-to-device placement"),
    _s("telemetry/compute_ms", "gauge", "ms",
       "jitted step dispatch-to-sync (device compute)"),
    _s("telemetry/metrics_fetch_ms", "gauge", "ms",
       "step metrics crossing device-to-host (externally driven steps)"),
    _s("telemetry/checkpoint_stall_ms", "gauge", "ms",
       "step loop blocked on checkpointing"),
    _s("telemetry/logging_ms", "gauge", "ms", "metric emission"),
    _s("telemetry/eval_ms", "gauge", "ms", "in-loop eval"),
    _s("telemetry/other_ms", "gauge", "ms",
       "unattributed step wall time"),
    _s("telemetry/goodput", "gauge", "fraction",
       "useful device compute / total wall clock (cumulative)"),
    _s("telemetry/badput_compile", "gauge", "fraction",
       "wall fraction lost to XLA compiles"),
    _s("telemetry/badput_fault", "gauge", "fraction",
       "wall fraction lost to failed/retried steps"),
    _s("telemetry/badput_checkpoint", "gauge", "fraction",
       "wall fraction lost to checkpoint stalls"),
    _s("telemetry/badput_elastic", "gauge", "fraction",
       "wall fraction lost to host-loss outages (lease expiry through "
       "topology-shift resume)"),
    _s("telemetry/mfu", "gauge", "fraction",
       "model FLOPs utilization vs chip peak"),
    # -- pod-wide aggregation (telemetry.aggregate; host 0 only)
    _s("telemetry/pod_step_ms_max", "gauge", "ms",
       "slowest host's interval step time (the pod's pace)"),
    _s("telemetry/pod_step_ms_mean", "gauge", "ms",
       "pod-mean interval step time"),
    _s("telemetry/pod_step_ms_min", "gauge", "ms",
       "fastest host's interval step time"),
    _s("telemetry/pod_goodput_min", "gauge", "fraction",
       "worst host's cumulative goodput"),
    _s("telemetry/pod_goodput_mean", "gauge", "fraction",
       "pod-mean cumulative goodput"),
    _s("telemetry/straggler_host", "gauge", "host",
       "process index of the slowest host this interval"),
    _s("telemetry/step_skew", "gauge", "ratio",
       "slowest / pod-mean step time (1.0 = balanced pod)"),
    # -- host tracing (telemetry.trace)
    _s("telemetry/trace_events", "counter", "events",
       "trace events emitted since start"),
    _s("telemetry/trace_dropped", "counter", "events",
       "trace events evicted from the ring buffer"),
    # -- distributed tracing (telemetry.trace_context): the process-
    #    local tracer's health mirrored into every registry that fronts
    #    a /metrics endpoint (gateway, fleet members, federated router)
    #    — the trainer contract (``telemetry/trace_events`` FuncGauge)
    #    extended to the serving side. Scrape-cadence FuncGauges over
    #    the installed tracer.
    _s("telemetry/trace/emitted", "counter", "events",
       "trace events emitted by this process's tracer", "scrape"),
    _s("telemetry/trace/dropped", "counter", "events",
       "trace events evicted from this process's ring buffer",
       "scrape"),
    _s("telemetry/trace/spooled", "counter", "records",
       "span records appended to this process's cross-process spool "
       "file (tools/trace_merge.py input)", "scrape"),
    _s("telemetry/trace/spool_errors", "counter", "errors",
       "spool write failures (counted, never raised — the spool sits "
       "behind serving hot paths)", "scrape"),
    # -- serving instrument panel (serving.metrics)
    _s("serving/queue_depth", "gauge", "requests",
       "waiting requests", "step"),
    _s("serving/active_requests", "gauge", "requests",
       "requests holding decode slots", "step"),
    _s("serving/page_occupancy", "gauge", "fraction",
       "KV page pool occupancy", "step"),
    _s("serving/requests_submitted", "counter", "requests", "", "step"),
    _s("serving/requests_finished", "counter", "requests", "", "step"),
    _s("serving/requests_timed_out", "counter", "requests", "", "step"),
    _s("serving/requests_cancelled", "counter", "requests", "", "step"),
    _s("serving/preemptions", "counter", "evictions",
       "page-pool OOM evictions", "step"),
    _s("serving/decode_steps", "counter", "steps", "", "step"),
    _s("serving/decode_steps_sampled", "counter", "steps",
       "decode steps with a running slot of temperature > 0 (the "
       "sampler's filter-and-draw branch)", "step"),
    _s("serving/decode_steps_paged_kernel", "counter", "steps",
       "decode steps dispatched to a program that holds the paged "
       "attention kernel (reads the running slots' live pages, not "
       "every slot's whole window)", "step"),
    _s("serving/step_arg_puts", "counter", "puts",
       "host-to-device puts of per-step arguments by the decode and "
       "prefill-chunk dispatches (one packed array each)", "step"),
    _s("serving/step_arg_bytes", "counter", "bytes",
       "bytes those puts sent", "step"),
    _s("serving/moe/experts_hit", "counter", "experts",
       "held experts that received a token, summed over layers and "
       "decode steps (dropless routing)", "step"),
    _s("serving/moe/expert_assignments", "counter", "pairs",
       "(token, choice) pairs that landed on a held expert, summed over "
       "layers and decode steps", "step"),
    _s("serving/kv_bytes_per_token", "gauge", "bytes",
       "bytes one cached token takes in the paged pool over every layer "
       "(keys and values, or one latent row)", "step"),
    _s("serving/kv_paged_layers", "gauge", "layers",
       "layers that keep request-long pages of their own (every layer of "
       "a model of one kind; the `paged` layers of a per-layer spec)",
       "step"),
    _s("serving/window_bytes_per_token", "gauge", "bytes",
       "bytes a token takes in the window pool while inside the window, "
       "over its layers (0 without window layers)", "step"),
    _s("serving/state_bytes_per_slot", "gauge", "bytes",
       "bytes of recurrent state a slot holds whatever its length (0 "
       "without state-space layers)", "step"),
    _s("serving/kv_shared_readers", "gauge", "layers",
       "layers that read a shared paged attention layer's rows, itself "
       "included (1 where every paged layer reads its own alone)", "step"),
    _s("serving/window_page_occupancy", "gauge", "fraction",
       "window pool pages owned over pages it has", "step"),
    _s("serving/window_pages_released", "counter", "pages",
       "window pages given back to the allocator from behind the "
       "window", "step"),
    _s("serving/tokens_generated", "counter", "tokens", "", "step"),
    _s("serving/ttft_ms", "histogram", "ms",
       "time to first token (arrival -> first emit)", "step"),
    _s("serving/itl_ms", "histogram", "ms",
       "inter-token latency between consecutive decodes", "step"),
    _s("serving/queue_wait_ms", "histogram", "ms",
       "arrival -> first prefill admission", "step"),
    _s("serving/prefix_cache/lookups", "counter", "lookups",
       "prefix-cache probes at admission", "step"),
    _s("serving/prefix_cache/hit_tokens", "counter", "tokens",
       "prompt tokens covered by cached prefix pages", "step"),
    _s("serving/prefix_cache/evictions", "counter", "pages",
       "cached pages reclaimed by the allocator (LRU)", "step"),
    _s("serving/prefill/chunks", "counter", "chunks",
       "chunked-prefill forward passes", "step"),
    _s("serving/prefill/scan_tokens", "counter", "tokens",
       "real tokens x state-space layers the prefill chunks ran (what "
       "the chunked selective scan worked through; 0 without such "
       "layers)", "step"),
    _s("serving/prefill/scan_kernel_chunks", "counter", "chunks",
       "prefill chunks dispatched to a program that holds the "
       "selective-scan kernel (the state-space layers' state stays in "
       "VMEM through the chunk)", "step"),
    _s("serving/prefill/attn_read_tokens", "counter", "tokens",
       "cached columns the prefill chunks' attention block walk read: "
       "ceil(context / block columns) x block columns x the layers "
       "that walk (0 where the chunk program gathers whole windows)",
       "step"),
    _s("serving/prefill/attn_window_tokens", "counter", "tokens",
       "slot window x the layers that walk, a chunk: what a whole-"
       "window gather would have read; attn_read_tokens over this is "
       "the share of the window read", "step"),
    _s("serving/prefill/tokens_saved", "counter", "tokens",
       "prefill tokens skipped via cached prefixes", "step"),
    # -- serving resilience (serving.resilience): admission control,
    #    degradation ladder, engine supervision
    _s("serving/requests_shed", "counter", "requests",
       "requests dropped by admission control / load shedding", "step"),
    _s("serving/queue_timeouts", "counter", "requests",
       "deadline expiries resolved straight from the wait queue "
       "(never admitted)", "step"),
    _s("serving/degradation_level", "gauge", "level",
       "graceful-degradation ladder rung (0=none .. 4=shedding)",
       "step"),
    _s("serving/supervisor/restarts", "counter", "restarts",
       "engine teardown+rebuild cycles (wedge/device error/NaN logits)"),
    _s("serving/supervisor/replayed_requests", "counter", "requests",
       "in-flight requests replayed after an engine rebuild"),
    _s("serving/supervisor/breaker_open", "gauge", "bool",
       "1 while the restart circuit breaker is tripped (draining)"),
    # -- speculative decoding on the paged engine (serving.server):
    #    draft-propose / target-verify rounds, delta-mirrored from
    #    engine-side counters so totals survive supervisor rebuilds
    _s("serving/spec/rounds", "counter", "rounds",
       "speculative draft/verify rounds (one per active slot per "
       "engine step)", "step"),
    _s("serving/spec/proposed_tokens", "counter", "tokens",
       "draft tokens proposed for verification (K per slot-round)",
       "step"),
    _s("serving/spec/accepted_tokens", "counter", "tokens",
       "draft tokens accepted by target verification", "step"),
    _s("serving/spec/acceptance_rate", "gauge", "fraction",
       "accepted / proposed draft tokens, cumulative", "step"),
    _s("serving/spec/rollbacks", "counter", "rounds",
       "rounds that rejected at least one draft token (rolled-back "
       "columns are never marked valid)", "step"),
    # -- serving fleet (serving.fleet): router + autoscaler panel; lives
    #    in the ROUTER's own registry (not a member engine's), so totals
    #    are monotone across member rebuilds by construction. Per-member
    #    occupancy FuncGauges ride the serving/fleet/engine/ dynamic
    #    prefix below.
    _s("serving/fleet/engines_active", "gauge", "engines",
       "fleet members currently accepting placements (draining and "
       "reclaimed members excluded)", "step"),
    _s("serving/fleet/routed_by_prefix", "counter", "requests",
       "placements won on prefix-cache affinity (peek hit or sticky "
       "family match)", "step"),
    _s("serving/fleet/routed_by_load", "counter", "requests",
       "placements decided by load alone (no member held cached "
       "prefix state for the prompt)", "step"),
    _s("serving/fleet/scale_ups", "counter", "engines",
       "autoscaler member spawns (SLO burn or occupancy over the "
       "scale-up threshold)", "step"),
    _s("serving/fleet/scale_downs", "counter", "engines",
       "autoscaler member reclaims (drained via the draining contract; "
       "queued work redistributed first)", "step"),
    _s("serving/fleet/rebalanced_requests", "counter", "requests",
       "queued requests moved to a peer member during scale-down "
       "(rid/sampling/streamed state preserved)", "step"),
    # -- KV page migration (serving.migration): the prefill/decode
    #    disaggregation handoff. Counters are engine-side, delta-
    #    mirrored (speculative-counter idiom) so totals stay monotone
    #    across supervisor rebuilds; export failures land on the source
    #    engine, everything else on the target.
    _s("serving/migration/migrations", "counter", "requests",
       "requests installed via KV page migration (import_request)",
       "step"),
    _s("serving/migration/migrated_pages", "counter", "pages",
       "committed KV pages scattered into target pools", "step"),
    _s("serving/migration/host_bounce_bytes", "counter", "bytes",
       "migration payload bytes that took the host-bounce transport "
       "(0 on device-to-device handoffs)", "step"),
    _s("serving/migration/failed_migrations", "counter", "requests",
       "refused/failed exports and imports (eviction holes, geometry "
       "mismatches, slot/page exhaustion); the request keeps running "
       "on its source engine", "step"),
    _s("serving/migration/failed_handoffs", "counter", "requests",
       "decode handoffs abandoned after max_handoff_retries refusals: "
       "the request finishes decoding on its prefill member (mixed-"
       "capable) or is shed", "step"),
    _s("serving/migration/handoff_wait_ms", "histogram", "ms",
       "source's last emitted token -> target install (the stream gap "
       "a migrated request's first post-handoff ITL sample includes)",
       "step"),
    # -- multi-tenant adapter pool (serving.tenancy): device-resident
    #    stacked LoRA A/B pools serving N tenants through one decode
    #    step. Counters are store-side plain ints, delta-mirrored by the
    #    engine (speculative-counter idiom) so totals survive supervisor
    #    rebuilds; per-tenant series ride the serving/tenant/ dynamic
    #    prefix below.
    _s("serving/adapter_pool/resident", "gauge", "adapters",
       "tenant adapters currently resident in the device pool "
       "(slot 0, the all-zeros base identity, excluded)", "step"),
    _s("serving/adapter_pool/publishes", "counter", "publishes",
       "publish_adapter hot-swaps installed into the pool "
       "(treedef-validated, recompile-free)", "step"),
    _s("serving/adapter_pool/loads", "counter", "loads",
       "cold adapters re-admitted to the device pool from their "
       "host-side copies (load-on-admission)", "step"),
    _s("serving/adapter_pool/spills", "counter", "spills",
       "resident adapters evicted to host-only (LRU over refcount-0 "
       "residents when the pool is full)", "step"),
    # -- serving gateway (serving.gateway): the HTTP front door. Handler
    #    threads bump plain-int stats; the gateway's engine loop delta-
    #    mirrors them into the gateway-owned registry (speculative-
    #    counter idiom), so totals stay monotone across engine swaps
    #    and supervisor rebuilds behind the same gateway.
    _s("serving/gateway/connections", "counter", "requests",
       "HTTP requests accepted by the gateway (all routes)", "step"),
    _s("serving/gateway/streamed_tokens", "counter", "tokens",
       "tokens written to clients as SSE stream events", "step"),
    _s("serving/gateway/disconnect_cancels", "counter", "requests",
       "in-flight requests cancelled because the client hung up "
       "mid-stream (broken pipe on an event write)", "step"),
    _s("serving/gateway/http_429", "counter", "responses",
       "generate calls refused by admission control (shed at the "
       "gate or displaced from a full queue) -> 429 + Retry-After",
       "step"),
    _s("serving/gateway/http_408", "counter", "responses",
       "generate calls whose per-request deadline expired before the "
       "first token -> 408", "step"),
    # -- fleet federation (serving.federation): cross-host placement
    #    over gossiped peer beats; counters live on the FederatedRouter's
    #    own registry, which outlives every remote fleet.
    _s("serving/federation/gossip_beats", "counter", "beats",
       "fresh peer heartbeat sequence numbers observed in the gossip "
       "directory", "step"),
    _s("serving/federation/routed_remote", "counter", "requests",
       "requests placed onto a remote fleet (cache-aware score over "
       "peeked hit-frac and gossiped pressure)", "step"),
    _s("serving/federation/handoff_bytes", "counter", "bytes",
       "serialized MigrationTicket bytes shipped between fleets "
       "(cross-host mid-decode handoffs)", "step"),
    _s("serving/federation/stale_peers", "counter", "peers",
       "placement passes that skipped a peer whose gossip lease had "
       "gone stale (no beat within the TTL)", "step"),
    _s("serving/federation/peek_rtt_ms", "histogram", "ms",
       "wire RTT of prefix-peek probes during placement (fleet-wide; "
       "per-peer series ride the serving/federation/peer/ prefix)",
       "step"),
    _s("serving/federation/place_rtt_ms", "histogram", "ms",
       "submit-to-placement-decision wall time per federated request",
       "step"),
    _s("serving/federation/stream_rtt_ms", "histogram", "ms",
       "POST /v1/generate to first SSE event (wire TTFB) per placed "
       "request", "step"),
    # -- fleet-wide metrics federation (telemetry.aggregate.
    #    FleetMetricsAggregator): per-peer digests gossiped on beats,
    #    rolled up on the federated router's registry — the pod
    #    aggregation idiom lifted from hosts to processes. Per-peer
    #    series ride the fleet/peer/ dynamic prefix below.
    _s("fleet/peers", "gauge", "peers",
       "live (non-stale) peers whose digests fed the last rollup"),
    _s("fleet/draining", "gauge", "peers",
       "live peers currently refusing new placements"),
    _s("fleet/pressure_max", "gauge", "fraction",
       "most-loaded peer's admission pressure (the placement-refusal "
       "horizon)"),
    _s("fleet/pressure_mean", "gauge", "fraction",
       "fleet-mean admission pressure"),
    _s("fleet/queue_depth_max", "gauge", "requests",
       "deepest per-peer in-flight stream count"),
    _s("fleet/queue_depth_sum", "gauge", "requests",
       "fleet-total in-flight stream count"),
    _s("fleet/goodput_tok_s_min", "gauge", "tok/s",
       "slowest peer's streamed-token rate over its last digest "
       "interval"),
    _s("fleet/goodput_tok_s_sum", "gauge", "tok/s",
       "fleet-total streamed-token rate"),
    _s("fleet/trace_dropped", "gauge", "events",
       "fleet-total trace-ring evictions (any nonzero peer means its "
       "merged timeline has holes)"),
    _s("fleet/straggler_peer", "gauge", "peer",
       "index (sorted live-peer-name order) of the most-pressured "
       "peer — the process-level telemetry/straggler_host"),
    # -- RLHF rollout subsystem (dla_tpu/rollout): serving-backed
    #    generation for train_rlhf (docs/RLHF.md)
    _s("rollout/rollouts", "counter", "rollouts",
       "completed serving-backed rollout batches"),
    _s("rollout/gen_tokens_per_s", "gauge", "tok/s",
       "generated tokens per wall-second over the last rollout"),
    _s("rollout/slot_steps_per_token", "gauge", "slot-steps/token",
       "decode slot-steps spent per generated token over the last "
       "rollout (1.0 = zero padding waste)"),
    _s("rollout/padding_waste_recovered", "gauge", "fraction",
       "1 - continuous/batch slot-steps-per-token on the same request "
       "mix (bench.py rollout A/B)"),
    _s("rollout/refits", "counter", "refits",
       "in-place weight publications into the live engine"),
    _s("rollout/refit_ms", "gauge", "ms",
       "wall time of the last weight refit (param build + publish)"),
    _s("rollout/staleness_updates", "gauge", "updates",
       "learner updates applied since the consumed rollout's weights "
       "were published (async mode; 0 in sync mode)"),
    _s("rollout/stale_rollouts", "counter", "rollouts",
       "rollouts consumed with staleness > 0 (importance-corrected)"),
    _s("rollout/discarded_rollouts", "counter", "rollouts",
       "async rollouts discarded for exceeding max_staleness_updates "
       "and regenerated fresh"),
    # -- elastic sampler fleet (rollout.actor_fleet): fleet-level panel,
    #    delta-mirrored on the SamplerFleet's own registry so totals
    #    survive member retirement and respawn
    _s("rollout/fleet/samplers_active", "gauge", "samplers",
       "fleet members currently accepting rollout work (target size "
       "minus retired, plus regrown)"),
    _s("rollout/fleet/refit_fanout_ms", "gauge", "ms",
       "wall time of the last broadcast-tree refit fanout across all "
       "active members (bounded by tree depth, not N)"),
    _s("rollout/fleet/retired_samplers", "counter", "samplers",
       "members removed from the fleet (lease expiry, repeated refit "
       "failure, drive crash, or injected sampler=lost)"),
    _s("rollout/fleet/reassigned_rollouts", "counter", "groups",
       "trajectory groups reassigned from a lost member to survivors "
       "and regenerated bit-identically from journaled (prompt, seed) "
       "pairs"),
    _s("rollout/fleet/trajectory_queue_depth", "gauge", "groups",
       "staleness-tagged trajectory groups waiting in the bounded "
       "multi-producer queue at last observation"),
    # -- XLA introspection (telemetry.xla_introspect); per-fn series
    #    (telemetry/xla/<fn>/flops, .../recompiles, ...) ride the
    #    telemetry/xla/ dynamic prefix below
    _s("telemetry/xla/recompiles", "counter", "compiles",
       "re-traces observed across all introspected jitted fns"),
    _s("telemetry/xla/live_bytes", "gauge", "bytes",
       "total bytes of live jax arrays in this process (live-HBM proxy)",
       "scrape"),
    # -- anomaly auto-triage (telemetry.anomaly); per-metric series ride
    #    the telemetry/anomaly/ dynamic prefix below
    _s("telemetry/anomaly/triggers", "counter", "events",
       "anomaly detector trips (z breach or unattributed recompile)"),
    _s("telemetry/anomaly/captures", "counter", "captures",
       "completed one-shot evidence captures (postmortem_anomaly.json)"),
    # -- resilience counters bridged into the registry (FuncGauge)
    _s("resilience/ckpt_saves_started", "counter", "saves"),
    _s("resilience/ckpt_saves_completed", "counter", "saves"),
    _s("resilience/ckpt_io_retries", "counter", "retries",
       "background-writer retry attempts"),
    _s("resilience/ckpt_retries", "counter", "retries",
       "checkpoint write retry attempts (alias feed of ckpt_io_retries "
       "for the flaky-FS triage pair)"),
    _s("resilience/ckpt_last_error_age_s", "gauge", "s",
       "seconds since the newest checkpoint write OSError; -1 when the "
       "writer never failed"),
    _s("resilience/ckpt_stall_ms_total", "counter", "ms",
       "cumulative step-loop checkpoint stall"),
    _s("resilience/guard_bad_steps", "counter", "steps"),
    _s("resilience/guard_rollbacks", "counter", "rollbacks"),
    _s("resilience/preemptions_requested", "counter", "signals"),
    _s("resilience/elastic_epoch", "gauge", "epoch",
       "gang membership epoch (bumps once per agreed shrink)"),
)

#: Dynamic-name families a static check cannot enumerate: any name under
#: these prefixes is catalog-legal (loss_fn auxiliary metrics surface as
#: ``train/<k>`` / ``eval/<k>``; the per-layer collector emits
#: ``train/rms/<param path>``).
DYNAMIC_PREFIXES: Tuple[str, ...] = ("train/rms/", "train/aux/", "eval/",
                                     "slo/", "telemetry/xla/",
                                     "telemetry/anomaly/",
                                     "serving/fleet/engine/",
                                     "serving/federation/peer/",
                                     "serving/tenant/",
                                     "fleet/peer/")

#: Derived suffixes ``latency_summary`` appends to histogram base names.
HISTOGRAM_SUFFIXES: Tuple[str, ...] = ("p50", "p95", "p99", "mean",
                                       "count")

_CATALOG_BY_NAME: Dict[str, MetricSpec] = {s.name: s for s in CATALOG}


def catalog_names() -> Tuple[str, ...]:
    return tuple(_CATALOG_BY_NAME)


def is_catalog_name(name: str) -> bool:
    """True when ``name`` is a declared metric: exact catalog hit, a
    histogram-derived name (``serving/ttft_ms_p95``), a gauge peak
    (``serving/queue_depth_peak``), or under a dynamic-family prefix."""
    name = name.rstrip("_")          # "serving/ttft_ms_" prefix literals
    if name in _CATALOG_BY_NAME:
        return True
    if any(name.startswith(p) for p in DYNAMIC_PREFIXES):
        return True
    base, _, suffix = name.rpartition("_")
    if base in _CATALOG_BY_NAME:
        spec = _CATALOG_BY_NAME[base]
        if spec.kind == "histogram" and suffix in HISTOGRAM_SUFFIXES:
            return True
        if spec.kind == "gauge" and suffix == "peak":
            return True
    return False


# ----------------------------------------------------------------- registry


def prometheus_name(name: str) -> str:
    """Canonical ``area/name`` -> Prometheus ``dla_area_name``."""
    return "dla_" + re.sub(r"[^a-zA-Z0-9_]", "_", name)


def _finite(v: float) -> float:
    return v if math.isfinite(v) else 0.0


class MetricRegistry:
    """Name -> instrument map with catalog validation and the two export
    renderings (flat snapshot dict, Prometheus text)."""

    def __init__(self, strict: bool = True):
        self.strict = strict
        self._instruments: Dict[str, Any] = {}

    def register(self, name: str, instrument: Any) -> Any:
        if self.strict and not is_catalog_name(name):
            raise ValueError(
                f"metric {name!r} is not declared in telemetry.registry."
                f"CATALOG — add a MetricSpec (and docs/OBSERVABILITY.md "
                f"row) instead of inventing names at the emission site")
        self._instruments[name] = instrument
        return instrument

    def counter(self, name: str) -> Counter:
        return self.register(name, Counter())

    def gauge(self, name: str) -> Gauge:
        return self.register(name, Gauge())

    def histogram(self, name: str, window: int = 4096) -> Histogram:
        return self.register(name, Histogram(window))

    def func_gauge(self, name: str, fn: Callable[[], float]) -> FuncGauge:
        return self.register(name, FuncGauge(fn))

    def get(self, name: str) -> Any:
        return self._instruments[name]

    def names(self) -> List[str]:
        return sorted(self._instruments)

    # ------------------------------------------------------------- exports

    def snapshot(self) -> Dict[str, float]:
        """Flat float dict, one key per exported series — the JSONL row."""
        out: Dict[str, float] = {}
        for name in sorted(self._instruments):
            inst = self._instruments[name]
            if isinstance(inst, Histogram):
                out.update(inst.summary(f"{name}_"))
            elif isinstance(inst, Gauge):
                out[name] = inst.value
                out[f"{name}_peak"] = inst.peak
            else:
                out[name] = float(inst.value)
        return out

    def prometheus_text(self) -> str:
        """Prometheus text exposition format 0.0.4. Counters render with
        the conventional ``_total`` suffix; histograms render as
        summaries (windowed quantiles + monotonic _sum/_count); gauges
        also export their ``_peak``. Non-finite values export as 0 —
        scrapers must never choke on a NaN."""
        lines: List[str] = []
        for name in sorted(self._instruments):
            inst = self._instruments[name]
            pname = prometheus_name(name)
            spec = _CATALOG_BY_NAME.get(name)
            help_text = (spec.help or spec.unit) if spec else ""
            if isinstance(inst, Histogram):
                s = inst.summary()
                if help_text:
                    lines.append(f"# HELP {pname} {help_text}")
                lines.append(f"# TYPE {pname} summary")
                lines.append(
                    f'{pname}{{quantile="0.5"}} {_finite(s["p50"])}')
                lines.append(
                    f'{pname}{{quantile="0.95"}} {_finite(s["p95"])}')
                lines.append(
                    f'{pname}{{quantile="0.99"}} {_finite(s["p99"])}')
                lines.append(f"{pname}_sum {_finite(inst.total_sum)}")
                lines.append(f"{pname}_count {inst.total_count}")
            elif isinstance(inst, Gauge):
                if help_text:
                    lines.append(f"# HELP {pname} {help_text}")
                lines.append(f"# TYPE {pname} gauge")
                lines.append(f"{pname} {_finite(inst.value)}")
                lines.append(f"# TYPE {pname}_peak gauge")
                lines.append(f"{pname}_peak {_finite(inst.peak)}")
            else:
                kind = spec.kind if spec else "gauge"
                if kind == "counter":
                    if help_text:
                        lines.append(f"# HELP {pname}_total {help_text}")
                    lines.append(f"# TYPE {pname}_total counter")
                    lines.append(f"{pname}_total {_finite(inst.value)}")
                else:
                    if help_text:
                        lines.append(f"# HELP {pname} {help_text}")
                    lines.append(f"# TYPE {pname} gauge")
                    lines.append(f"{pname} {_finite(inst.value)}")
        return "\n".join(lines) + "\n"


_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})?\s+(?P<value>\S+)$")


def parse_prometheus_text(text: str) -> Dict[Tuple[str, Tuple], float]:
    """Minimal strict parser for the exposition format this module
    emits: {(name, sorted (label, value) tuple): float}. Raises
    ValueError on any line that is neither a comment nor a well-formed
    sample — the round-trip test runs every exported line through it."""
    out: Dict[Tuple[str, Tuple], float] = {}
    for lineno, line in enumerate(text.splitlines(), 1):
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            raise ValueError(f"line {lineno}: not a prometheus sample: "
                             f"{line!r}")
        labels = []
        if m.group("labels"):
            for part in m.group("labels").split(","):
                k, _, v = part.partition("=")
                if not (v.startswith('"') and v.endswith('"')):
                    raise ValueError(
                        f"line {lineno}: unquoted label value in {line!r}")
                labels.append((k.strip(), v[1:-1]))
        out[(m.group("name"), tuple(sorted(labels)))] = float(
            m.group("value"))
    return out
