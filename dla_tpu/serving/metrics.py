"""Serving metrics surface: counters, gauges, and bounded histograms for
the quantities that tell you whether a serving deployment is healthy —
queue depth, time-to-first-token, inter-token latency, queue wait,
page-pool occupancy, preemption count.

The instrument classes live in ``dla_tpu.telemetry.registry`` (re-
exported here for back-compat) and every instrument registers into a
shared :class:`~dla_tpu.telemetry.MetricRegistry`, so the same numbers
export two ways: ``snapshot()`` returns the flat dict a
``MetricsLogger`` writes as one JSONL row, and the registry's
``prometheus_text()`` backs the engine's HTTP ``/metrics`` endpoint.
Percentiles come from ``utils.logging.percentile`` so serving and
eval_latency report the same statistic.
"""
from __future__ import annotations

from typing import Dict, Optional

from dla_tpu.telemetry.registry import (  # noqa: F401 — re-exported
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
)
from dla_tpu.utils.logging import MetricsLogger


class ServingMetrics:
    """The serving engine's instrument panel. The engine records; anyone
    (CLI harness, bench, tests, a Prometheus scraper) reads
    ``snapshot()``, streams rows through ``report()``, or scrapes the
    registry."""

    def __init__(self, registry: Optional[MetricRegistry] = None):
        r = self.registry = registry or MetricRegistry()
        self.queue_depth = r.gauge("serving/queue_depth")
        self.active_requests = r.gauge("serving/active_requests")
        self.page_occupancy = r.gauge("serving/page_occupancy")
        self.ttft_ms = r.histogram("serving/ttft_ms")
        self.itl_ms = r.histogram("serving/itl_ms")
        self.queue_wait_ms = r.histogram("serving/queue_wait_ms")
        self.requests_submitted = r.counter("serving/requests_submitted")
        self.requests_finished = r.counter("serving/requests_finished")
        self.requests_timed_out = r.counter("serving/requests_timed_out")
        self.requests_cancelled = r.counter("serving/requests_cancelled")
        self.preemptions = r.counter("serving/preemptions")
        self.decode_steps = r.counter("serving/decode_steps")
        # decode steps with a running slot of temperature > 0: the steps
        # whose sampler filtered and drew instead of taking the arg-max
        self.decode_steps_sampled = r.counter(
            "serving/decode_steps_sampled")
        # decode steps dispatched to a program that holds the paged
        # attention kernel (ops/paged_attention.py; dense rows, or a
        # layer-spec model's flat plain-attention rows): the steps that
        # read the running slots' live pages, not every slot's window
        self.decode_steps_paged_kernel = r.counter(
            "serving/decode_steps_paged_kernel")
        # what the decode and chunk dispatches sent to the device: one
        # packed array each (serving/step_args.py), and its bytes
        self.step_arg_puts = r.counter("serving/step_arg_puts")
        self.step_arg_bytes = r.counter("serving/step_arg_bytes")
        # dropless routing, summed over layers and decode steps: held
        # experts that received a token, (token, choice) pairs that landed
        # on a held expert (0 for a model without routed experts)
        self.moe_experts_hit = r.counter("serving/moe/experts_hit")
        self.moe_expert_assignments = r.counter(
            "serving/moe/expert_assignments")
        # bytes one cached token takes in the paged pool, every layer
        # (dense: keys and values; latent attention: one latent row)
        self.kv_bytes_per_token = r.gauge("serving/kv_bytes_per_token")
        # layers that keep request-long pages of their own (every layer of
        # a dense model; the ``paged`` layers of a per-layer spec)
        self.kv_paged_layers = r.gauge("serving/kv_paged_layers")
        # a model with a per-layer cache spec (0 / 1 for the others):
        # bytes a token takes in the window pool while inside the window;
        # bytes of recurrent state a slot holds; the layers that read a
        # shared ``paged`` attention layer's rows (itself included); the
        # window pool's pages owned over pages it has; window pages given
        # back to its allocator from behind the window
        self.window_bytes_per_token = r.gauge(
            "serving/window_bytes_per_token")
        self.state_bytes_per_slot = r.gauge("serving/state_bytes_per_slot")
        self.kv_shared_readers = r.gauge("serving/kv_shared_readers")
        self.window_page_occupancy = r.gauge(
            "serving/window_page_occupancy")
        self.window_pages_released = r.counter(
            "serving/window_pages_released")
        self.tokens_generated = r.counter("serving/tokens_generated")
        # prompt tokens of the submitted requests: what the prefix cache's
        # hit tokens are a share of
        self.prompt_tokens = r.counter("serving/prompt_tokens")
        # learned sparse attention, per query (decode steps and chunks),
        # not multiplied by layers: the cached columns its indexer scored,
        # and the latent rows it read of them (0 without an indexer)
        self.dsa_live_tokens = r.counter("serving/dsa/live_tokens")
        self.dsa_selected_tokens = r.counter("serving/dsa/selected_tokens")
        self.prefix_lookups = r.counter("serving/prefix_cache/lookups")
        self.prefix_hit_tokens = r.counter(
            "serving/prefix_cache/hit_tokens")
        self.prefix_evictions = r.counter(
            "serving/prefix_cache/evictions")
        self.prefill_chunks = r.counter("serving/prefill/chunks")
        # real tokens x state-space layers the chunks ran: what the
        # chunked selective scan worked through (0 without such layers)
        self.prefill_scan_tokens = r.counter("serving/prefill/scan_tokens")
        # chunks dispatched to a program that holds the selective-scan
        # kernel (ops/selective_scan_kernel.py)
        self.prefill_scan_kernel_chunks = r.counter(
            "serving/prefill/scan_kernel_chunks")
        # cached columns the chunks' attention block walk read, and the
        # whole windows a gather would have read, both x walking layers
        # (ops/attention.py blockwise_paged_attention; 0 / 0 where the
        # chunk program gathers)
        self.prefill_attn_read_tokens = r.counter(
            "serving/prefill/attn_read_tokens")
        self.prefill_attn_window_tokens = r.counter(
            "serving/prefill/attn_window_tokens")
        self.prefill_tokens_saved = r.counter(
            "serving/prefill/tokens_saved")
        self.requests_shed = r.counter("serving/requests_shed")
        self.queue_timeouts = r.counter("serving/queue_timeouts")
        self.degradation_level = r.gauge("serving/degradation_level")
        self.supervisor_restarts = r.counter(
            "serving/supervisor/restarts")
        self.replayed_requests = r.counter(
            "serving/supervisor/replayed_requests")
        self.breaker_open = r.gauge("serving/supervisor/breaker_open")
        self.spec_rounds = r.counter("serving/spec/rounds")
        self.spec_proposed = r.counter("serving/spec/proposed_tokens")
        self.spec_accepted = r.counter("serving/spec/accepted_tokens")
        self.spec_rollbacks = r.counter("serving/spec/rollbacks")
        self.spec_acceptance_rate = r.gauge("serving/spec/acceptance_rate")
        self.migrations = r.counter("serving/migration/migrations")
        self.migrated_pages = r.counter(
            "serving/migration/migrated_pages")
        self.host_bounce_bytes = r.counter(
            "serving/migration/host_bounce_bytes")
        self.failed_migrations = r.counter(
            "serving/migration/failed_migrations")
        self.handoff_wait_ms = r.histogram(
            "serving/migration/handoff_wait_ms")
        self.adapter_resident = r.gauge("serving/adapter_pool/resident")
        self.adapter_publishes = r.counter(
            "serving/adapter_pool/publishes")
        self.adapter_loads = r.counter("serving/adapter_pool/loads")
        self.adapter_spills = r.counter("serving/adapter_pool/spills")

    def snapshot(self) -> Dict[str, float]:
        out: Dict[str, float] = {
            "serving/queue_depth": self.queue_depth.value,
            "serving/queue_depth_peak": self.queue_depth.peak,
            "serving/active_requests": self.active_requests.value,
            "serving/page_occupancy": self.page_occupancy.value,
            "serving/page_occupancy_peak": self.page_occupancy.peak,
            "serving/requests_submitted": float(
                self.requests_submitted.value),
            "serving/requests_finished": float(self.requests_finished.value),
            "serving/requests_timed_out": float(
                self.requests_timed_out.value),
            "serving/requests_cancelled": float(
                self.requests_cancelled.value),
            "serving/preemptions": float(self.preemptions.value),
            "serving/decode_steps": float(self.decode_steps.value),
            "serving/decode_steps_sampled": float(
                self.decode_steps_sampled.value),
            "serving/decode_steps_paged_kernel": float(
                self.decode_steps_paged_kernel.value),
            "serving/step_arg_puts": float(self.step_arg_puts.value),
            "serving/step_arg_bytes": float(self.step_arg_bytes.value),
            "serving/moe/experts_hit": float(self.moe_experts_hit.value),
            "serving/moe/expert_assignments": float(
                self.moe_expert_assignments.value),
            "serving/kv_bytes_per_token": self.kv_bytes_per_token.value,
            "serving/kv_paged_layers": self.kv_paged_layers.value,
            "serving/window_bytes_per_token":
                self.window_bytes_per_token.value,
            "serving/state_bytes_per_slot": self.state_bytes_per_slot.value,
            "serving/kv_shared_readers": self.kv_shared_readers.value,
            "serving/window_page_occupancy":
                self.window_page_occupancy.value,
            "serving/window_page_occupancy_peak":
                self.window_page_occupancy.peak,
            "serving/window_pages_released": float(
                self.window_pages_released.value),
            "serving/tokens_generated": float(self.tokens_generated.value),
            "serving/prompt_tokens": float(self.prompt_tokens.value),
            "serving/dsa/live_tokens": float(self.dsa_live_tokens.value),
            "serving/dsa/selected_tokens": float(
                self.dsa_selected_tokens.value),
            "serving/prefix_cache/lookups": float(
                self.prefix_lookups.value),
            "serving/prefix_cache/hit_tokens": float(
                self.prefix_hit_tokens.value),
            "serving/prefix_cache/evictions": float(
                self.prefix_evictions.value),
            "serving/prefill/chunks": float(self.prefill_chunks.value),
            "serving/prefill/scan_tokens": float(
                self.prefill_scan_tokens.value),
            "serving/prefill/scan_kernel_chunks": float(
                self.prefill_scan_kernel_chunks.value),
            "serving/prefill/attn_read_tokens": float(
                self.prefill_attn_read_tokens.value),
            "serving/prefill/attn_window_tokens": float(
                self.prefill_attn_window_tokens.value),
            "serving/prefill/tokens_saved": float(
                self.prefill_tokens_saved.value),
            "serving/requests_shed": float(self.requests_shed.value),
            "serving/queue_timeouts": float(self.queue_timeouts.value),
            "serving/degradation_level": self.degradation_level.value,
            "serving/supervisor/restarts": float(
                self.supervisor_restarts.value),
            "serving/supervisor/replayed_requests": float(
                self.replayed_requests.value),
            "serving/supervisor/breaker_open": self.breaker_open.value,
            "serving/spec/rounds": float(self.spec_rounds.value),
            "serving/spec/proposed_tokens": float(
                self.spec_proposed.value),
            "serving/spec/accepted_tokens": float(
                self.spec_accepted.value),
            "serving/spec/rollbacks": float(self.spec_rollbacks.value),
            "serving/spec/acceptance_rate":
                self.spec_acceptance_rate.value,
            "serving/migration/migrations": float(self.migrations.value),
            "serving/migration/migrated_pages": float(
                self.migrated_pages.value),
            "serving/migration/host_bounce_bytes": float(
                self.host_bounce_bytes.value),
            "serving/migration/failed_migrations": float(
                self.failed_migrations.value),
            "serving/adapter_pool/resident": self.adapter_resident.value,
            "serving/adapter_pool/publishes": float(
                self.adapter_publishes.value),
            "serving/adapter_pool/loads": float(self.adapter_loads.value),
            "serving/adapter_pool/spills": float(
                self.adapter_spills.value),
        }
        out.update(self.ttft_ms.summary("serving/ttft_ms_"))
        out.update(self.itl_ms.summary("serving/itl_ms_"))
        out.update(self.queue_wait_ms.summary("serving/queue_wait_ms_"))
        out.update(self.handoff_wait_ms.summary(
            "serving/migration/handoff_wait_ms_"))
        return out

    def report(self, logger: Optional[MetricsLogger], step: int) -> None:
        if logger is not None:
            logger.log(self.snapshot(), step)
