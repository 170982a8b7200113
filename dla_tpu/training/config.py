"""Config system: the reference's YAML block shapes, plus the pieces it
lacks (SURVEY.md sec 5 config row): overlay merging for the ablation
fragments (reference README says "merge manually", config/ablations/),
dotted CLI overrides, and validation warnings — while tolerating GPU-era
keys (hardware.deepspeed_config / fsdp / mixed_precision / num_processes)
so reference configs keep launching runs.

Block shapes kept verbatim: experiment_name / seed / model / data /
optimization / logging / hardware (/ ppo / reward_model / sampling /
distill / benchmarks / latency / generation).
"""
from __future__ import annotations

import argparse
import copy
import dataclasses
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

import yaml

GPU_ERA_HARDWARE_KEYS = {
    "deepspeed_config": "parameter sharding comes from hardware.mesh.fsdp",
    "fsdp": "parameter sharding comes from hardware.mesh.fsdp",
    "mixed_precision": "bf16 activations are the default on TPU",
    "num_processes": "host count comes from jax.process_count()",
}


def load_yaml(path) -> Dict[str, Any]:
    with Path(path).open("r", encoding="utf-8") as fh:
        out = yaml.safe_load(fh)
    return out or {}


def deep_merge(base: Dict[str, Any], overlay: Dict[str, Any]) -> Dict[str, Any]:
    """Recursive dict merge; overlay wins; lists replace wholesale."""
    out = copy.deepcopy(base)
    for k, v in overlay.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = deep_merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def set_dotted(cfg: Dict[str, Any], dotted: str, value: Any) -> None:
    keys = dotted.split(".")
    node = cfg
    for k in keys[:-1]:
        node = node.setdefault(k, {})
        if not isinstance(node, dict):
            raise ValueError(f"Cannot set '{dotted}': '{k}' is not a mapping")
    node[keys[-1]] = value


def get_dotted(cfg: Dict[str, Any], dotted: str, default: Any = None) -> Any:
    node: Any = cfg
    for k in dotted.split("."):
        if not isinstance(node, dict) or k not in node:
            return default
        node = node[k]
    return node


def apply_overrides(cfg: Dict[str, Any], overrides: Sequence[str]) -> Dict[str, Any]:
    """``a.b.c=value`` overrides; values parsed as YAML (so 1e-5, true, [1,2])."""
    out = copy.deepcopy(cfg)
    for ov in overrides:
        if "=" not in ov:
            raise ValueError(f"Override '{ov}' is not of the form key=value")
        key, raw = ov.split("=", 1)
        set_dotted(out, key.strip(), yaml.safe_load(raw))
    return out


def warn_legacy_keys(cfg: Dict[str, Any]) -> List[str]:
    warnings = []
    hw = cfg.get("hardware", {}) or {}
    for key, why in GPU_ERA_HARDWARE_KEYS.items():
        if key in hw:
            warnings.append(
                f"hardware.{key} is a GPU-era key and is ignored on TPU ({why})")
    if cfg.get("backend") == "accelerate":
        warnings.append("backend: accelerate is ignored (TPU-native runtime)")
    return warnings


def load_config(path, overlays: Sequence[str] = (),
                overrides: Sequence[str] = (), quiet: bool = False
                ) -> Dict[str, Any]:
    cfg = load_yaml(path)
    for ov_path in overlays:
        cfg = deep_merge(cfg, load_yaml(ov_path))
    cfg = apply_overrides(cfg, overrides)
    # interleaved-PP storage coupling: block-major layer storage needs
    # the stage count at model-build time (transformer.py
    # _interleaved_storage). Copied, not required — an explicit
    # model.pipeline_stages (or a wildcard/absent stage axis) wins.
    model = cfg.get("model") or {}
    stage = ((cfg.get("hardware") or {}).get("mesh") or {}).get("stage", 1)
    if (int(model.get("pipeline_interleave", 1) or 1) > 1
            and "pipeline_stages" not in model
            and isinstance(stage, int) and stage > 1):
        model["pipeline_stages"] = stage
        cfg["model"] = model
    if not quiet:
        for w in warn_legacy_keys(cfg):
            print(f"[dla_tpu][config] {w}", flush=True)
    return cfg


def make_arg_parser(description: str) -> argparse.ArgumentParser:
    """The shared CLI shape: ``train_X --config cfg.yaml [--overlay o.yaml]
    [--set key=value] [--resume]`` — superset of the reference's single
    --config flag (train_sft.py:27-30)."""
    p = argparse.ArgumentParser(description=description)
    p.add_argument("--config", required=True, help="YAML config path")
    p.add_argument("--overlay", action="append", default=[],
                   help="overlay YAML fragment(s), e.g. config/ablations/low_lr.yaml")
    p.add_argument("--set", dest="overrides", action="append", default=[],
                   metavar="KEY=VALUE", help="dotted config override")
    p.add_argument("--resume", action="store_true",
                   help="resume from the latest checkpoint in logging.output_dir")
    return p


def config_from_args(args: argparse.Namespace) -> Dict[str, Any]:
    return load_config(args.config, args.overlay, args.overrides)


# ----------------------------------------------------------------- schema
# Declared YAML schema: one frozen dataclass per config block. The runtime
# stays dict-based (overlay merging and dotted overrides want plain
# dicts), but the dataclasses are the single source of truth for which
# keys exist — dla-lint's ``config-schema-drift`` rule introspects them
# via ``dataclasses.fields`` and flags any ``config/*.yaml`` key they do
# not declare, so a typo'd key is a lint failure instead of a silently
# ignored default three minutes into a pod run.
#
# Field *types* encode structure, not value validation: a dataclass or
# ``Dict[str, <dataclass>]`` / ``List[<dataclass>]`` annotation tells the
# rule to recurse; ``Any`` marks a validated-elsewhere leaf. Keep new keys
# in sync with the block they are read from (grep ``cfg.get("<key>")``).

@dataclasses.dataclass(frozen=True)
class MeshSchema:
    data: Any = None
    fsdp: Any = None
    model: Any = None
    sequence: Any = None
    stage: Any = None


@dataclasses.dataclass(frozen=True)
class HardwareSchema:
    mesh: Optional[MeshSchema] = None
    gradient_accumulation_steps: Any = None
    auto_initialize: Any = None
    coordinator_address: Any = None
    # GPU-era keys: tolerated by load_config with a warning (see
    # GPU_ERA_HARDWARE_KEYS) so reference configs keep launching
    deepspeed_config: Any = None
    fsdp: Any = None
    mixed_precision: Any = None
    num_processes: Any = None


@dataclasses.dataclass(frozen=True)
class CollectorSchema:
    param_norm: Any = None
    update_norm: Any = None
    per_layer: Any = None


@dataclasses.dataclass(frozen=True)
class TraceSchema:
    enabled: Any = None
    capacity: Any = None
    path: Any = None


@dataclasses.dataclass(frozen=True)
class AggregateSchema:
    enabled: Any = None


@dataclasses.dataclass(frozen=True)
class XlaIntrospectSchema:
    """``logging.telemetry.xla_introspect``: retrace attribution +
    compiled-fn cost/memory gauges (telemetry.xla_introspect)."""
    enabled: Any = None
    max_entries: Any = None


@dataclasses.dataclass(frozen=True)
class AnomalySchema:
    """``logging.telemetry.anomaly``: rolling median/MAD auto-triage
    with one-shot capture (telemetry.anomaly.AnomalyConfig)."""
    enabled: Any = None
    window: Any = None
    warmup_steps: Any = None
    z_threshold: Any = None
    capture_steps: Any = None
    cooldown_steps: Any = None
    max_captures: Any = None
    xplane_dir: Any = None


@dataclasses.dataclass(frozen=True)
class TelemetrySchema:
    enabled: Any = None
    metrics_port: Any = None
    flight_recorder_capacity: Any = None
    readiness_timeout_s: Any = None
    collector: Optional[CollectorSchema] = None
    trace: Optional[TraceSchema] = None
    aggregate: Optional[AggregateSchema] = None
    xla_introspect: Optional[XlaIntrospectSchema] = None
    anomaly: Optional[AnomalySchema] = None


@dataclasses.dataclass(frozen=True)
class ProfileSchema:
    trace_dir: Any = None
    start_step: Any = None
    num_steps: Any = None


@dataclasses.dataclass(frozen=True)
class LoggingSchema:
    output_dir: Any = None
    output_path: Any = None
    log_dir: Any = None
    table_path: Any = None
    log_every_steps: Any = None
    eval_every_steps: Any = None
    save_every_steps: Any = None
    keep_last_n: Any = None
    use_wandb: Any = None
    profile: Optional[ProfileSchema] = None
    telemetry: Optional[TelemetrySchema] = None


@dataclasses.dataclass(frozen=True)
class ColumnsSchema:
    prompt: Any = None
    response: Any = None
    chosen: Any = None
    rejected: Any = None


@dataclasses.dataclass(frozen=True)
class DataSourceSchema:
    """One data source: the ``data:`` block's per-source keys, also the
    shape of ``config/data_sources/*.yaml`` fragments and
    ``data.mixture`` entries."""
    source: Any = None
    hf_path: Any = None
    split: Any = None
    train_split: Any = None
    eval_split: Any = None
    train_path: Any = None
    eval_path: Any = None
    limit: Any = None
    template: Any = None
    prompt_key: Any = None
    weight: Any = None
    columns: Optional[ColumnsSchema] = None


@dataclasses.dataclass(frozen=True)
class DataSchema(DataSourceSchema):
    packing: Any = None
    mixture: Optional[List[DataSourceSchema]] = None
    mixture_seed: Any = None
    mixture_size: Any = None
    preference_path: Any = None
    teacher_samples_path: Any = None
    max_seq_length: Any = None


@dataclasses.dataclass(frozen=True)
class OptimizationSchema:
    learning_rate: Any = None
    lr_scheduler: Any = None
    warmup_steps: Any = None
    weight_decay: Any = None
    max_grad_norm: Any = None
    max_train_steps: Any = None
    micro_batch_size: Any = None
    total_batch_size: Any = None
    grad_accum: Any = None
    grad_accum_dtype: Any = None
    gradient_accumulation_steps: Any = None
    adam_beta1: Any = None
    adam_beta2: Any = None
    adam_eps: Any = None
    adam_moment_dtype: Any = None
    optimizer: Any = None
    temperature: Any = None


@dataclasses.dataclass(frozen=True)
class ModelSchema:
    model_name_or_path: Any = None
    base_model_name_or_path: Any = None
    policy_model_name_or_path: Any = None
    reference_model_name_or_path: Any = None
    student_model_name_or_path: Any = None
    teacher_path: Any = None
    tokenizer: Any = None
    beta: Any = None
    dropout: Any = None
    gradient_checkpointing: Any = None
    label_smoothing: Any = None
    max_seq_length: Any = None
    num_layers: Any = None
    pooling: Any = None
    lora: Any = None
    kv_cache_dtype: Any = None
    context_parallel: Any = None
    rope_scaling: Any = None
    use_flash_attention: Any = None
    pipeline_microbatches: Any = None
    pipeline_stages: Any = None
    pipeline_interleave: Any = None


@dataclasses.dataclass(frozen=True)
class GenerationSchema:
    batch_size: Any = None
    do_sample: Any = None
    max_new_tokens: Any = None
    max_prompt_length: Any = None
    temperature: Any = None
    top_p: Any = None
    draft_model: Any = None
    speculative_gamma: Any = None
    speculative_alloc_factor: Any = None


@dataclasses.dataclass(frozen=True)
class RolloutServingSchema:
    """ppo.rollout.serving: ServingConfig overrides for the rollout
    engine (anything omitted is derived from the rollout shape by
    rollout.pipeline.build_rollout_pipeline)."""
    page_size: Any = None
    num_pages: Any = None
    num_slots: Any = None
    max_model_len: Any = None
    prefill_chunk: Any = None
    prefill_token_budget: Any = None
    prefix_cache: Any = None
    fault_plan: Any = None
    speculative: Any = None


@dataclasses.dataclass(frozen=True)
class RolloutFleetSchema:
    """ppo.rollout.fleet: elastic sampler fleet
    (rollout.actor_fleet.SamplerFleetConfig; docs/RLHF.md
    "Disaggregated sampler fleet"). N supervised rollout engines with
    broadcast-tree refit fanout, lease-based member loss detection,
    and journaled-seed reassignment."""
    samplers: Any = None
    fanout_branch: Any = None
    refit_timeout_s: Any = None
    refit_retries: Any = None
    retire_after_failures: Any = None
    lease_ttl_s: Any = None
    step_wedge_s: Any = None
    collect_poll_s: Any = None
    traj_queue_cap: Any = None
    regrow: Any = None
    min_samplers: Any = None
    refit_delay_s: Any = None


@dataclasses.dataclass(frozen=True)
class RolloutSchema:
    """ppo.rollout: disaggregated rollouts through the serving engine
    (dla_tpu.rollout; docs/RLHF.md). donate_refit frees the previous
    rollout tree's device buffers at each refit — only enable with
    LoRA-merge or rollout_quantize_weights (a fresh tree per refit),
    never when rollout params ARE the live trainer params."""
    backend: Any = None            # batch (default) | serving
    mode: Any = None               # sync (default) | async
    max_staleness_updates: Any = None
    is_clip: Any = None
    supervised: Any = None
    donate_refit: Any = None
    serving: Optional[RolloutServingSchema] = None
    fleet: Optional[RolloutFleetSchema] = None


@dataclasses.dataclass(frozen=True)
class PpoSchema:
    algo: Any = None
    steps: Any = None
    batch_size: Any = None
    mini_batch_size: Any = None
    epochs: Any = None
    learning_rate: Any = None
    clip_ratio: Any = None
    kl_coef: Any = None
    target_kl: Any = None
    gae_lambda: Any = None
    gamma: Any = None
    value_clip: Any = None
    value_coef: Any = None
    rollout_quantize_weights: Any = None
    samples_per_prompt: Any = None
    max_prompt_length: Any = None
    generation_params: Optional[GenerationSchema] = None
    rollout: Optional[RolloutSchema] = None


@dataclasses.dataclass(frozen=True)
class SamplingSchema:
    source: Any = None
    hf_path: Any = None
    split: Any = None
    prompt_key: Any = None
    prompt_path: Any = None


@dataclasses.dataclass(frozen=True)
class RewardModelSchema:
    path: Any = None


@dataclasses.dataclass(frozen=True)
class DistillSchema:
    on_policy: Any = None
    teacher_model_name_or_path: Any = None
    teacher_model_names_or_paths: Any = None
    use_kl: Any = None
    temperature: Any = None


@dataclasses.dataclass(frozen=True)
class BenchmarkSchema:
    type: Any = None
    path: Any = None
    hf_path: Any = None
    split: Any = None
    prompt_key: Any = None
    prompts_path: Any = None
    max_samples: Any = None


@dataclasses.dataclass(frozen=True)
class DecodeLatencySchema:
    enabled: Any = None
    batch_size: Any = None
    prompt_length: Any = None
    new_tokens: Any = None


@dataclasses.dataclass(frozen=True)
class PrefixCacheSchema:
    enabled: Any = None
    cached_logits_capacity: Any = None


@dataclasses.dataclass(frozen=True)
class ChunkedPrefillSchema:
    chunk: Any = None
    token_budget: Any = None


@dataclasses.dataclass(frozen=True)
class SharedPrefixSchema:
    enabled: Any = None
    families: Any = None
    requests_per_family: Any = None
    prefix_len: Any = None
    suffix_len: Any = None


@dataclasses.dataclass(frozen=True)
class ShedSchema:
    """serving.resilience.ShedConfig: admission control + load
    shedding + degradation-ladder thresholds."""
    enabled: Any = None
    max_queue_depth: Any = None
    rate: Any = None
    burst: Any = None
    slo_burn_threshold: Any = None
    degrade_high: Any = None
    degrade_low: Any = None
    degrade_patience: Any = None


@dataclasses.dataclass(frozen=True)
class SupervisorSchema:
    """serving.resilience.SupervisorConfig: watchdog + restart budget
    for the supervised serving engine."""
    enabled: Any = None
    watchdog_timeout_s: Any = None
    watchdog_poll_s: Any = None
    max_restarts: Any = None
    restart_window_s: Any = None


@dataclasses.dataclass(frozen=True)
class OverloadSchema:
    """eval_latency --overload: burst size injected mid-trace for the
    shed-on vs shed-off A/B."""
    enabled: Any = None
    burst: Any = None
    new_tokens: Any = None


@dataclasses.dataclass(frozen=True)
class SpeculativeSchema:
    """ServingConfig.speculative: blockwise draft/verify speculative
    decoding on the paged engine (k draft tokens per round; draft is
    'int8' weight-only self-draft or 'self' full precision). Also the
    eval_latency --speculative A/B switch."""
    enabled: Any = None
    k: Any = None
    draft: Any = None


@dataclasses.dataclass(frozen=True)
class FleetSchema:
    """serving.fleet.FleetConfig: multi-engine router (cache-aware /
    random / round_robin placement) + SLO-driven autoscaler bounds.
    Also the eval_latency --fleet A/B/C switch."""
    enabled: Any = None
    engines: Any = None
    min_engines: Any = None
    max_engines: Any = None
    placement: Any = None
    prefix_weight: Any = None
    load_weight: Any = None
    sticky_bonus: Any = None
    adapter_weight: Any = None
    autoscale: Any = None
    scale_up_burn: Any = None
    scale_up_pressure: Any = None
    scale_down_pressure: Any = None
    patience: Any = None
    check_every: Any = None
    seed: Any = None
    roles: Any = None
    migration_transport: Any = None


@dataclasses.dataclass(frozen=True)
class DisaggSchema:
    """eval_latency --disagg A/B/C: single chunked engine vs a mixed
    co-scheduled fleet vs a role-split prefill/decode fleet of the same
    size, all replaying the SAME long-prompt Poisson trace."""
    enabled: Any = None
    prefill_engines: Any = None
    decode_engines: Any = None
    num_requests: Any = None
    arrival_rate: Any = None
    prompt_len: Any = None
    new_tokens: Any = None


@dataclasses.dataclass(frozen=True)
class MigrationSchema:
    """serving.migration.MigrationConfig: KV-page handoff transport for
    the disaggregated fleet (auto / device / host)."""
    enabled: Any = None
    transport: Any = None


@dataclasses.dataclass(frozen=True)
class AdapterPoolSchema:
    """serving.tenancy.AdapterPoolConfig: the device-resident LoRA
    adapter pool behind multi-tenant serving (capacity, rank padding,
    target projections)."""
    max_adapters: Any = None
    max_rank: Any = None
    targets: Any = None


@dataclasses.dataclass(frozen=True)
class TenancySchema:
    """serving.tenancy.TenancyConfig: multi-tenant serving — the
    adapter pool plus per-tenant quota buckets and SLO objectives
    (docs/SERVING.md "Multi-tenant serving")."""
    enabled: Any = None
    adapter_pool: Optional[AdapterPoolSchema] = None
    quotas: Any = None
    slo: Any = None


@dataclasses.dataclass(frozen=True)
class GatewaySchema:
    """eval_latency --gateway: wire-vs-in-process serving A/B through
    the HTTP streaming gateway (serving.gateway)."""
    enabled: Any = None
    num_requests: Any = None
    arrival_rate: Any = None
    new_tokens: Any = None


@dataclasses.dataclass(frozen=True)
class ServingLatencySchema:
    enabled: Any = None
    arrival_rate: Any = None
    num_requests: Any = None
    prompt_len_min: Any = None
    prompt_len_max: Any = None
    new_tokens: Any = None
    page_size: Any = None
    num_pages: Any = None
    num_slots: Any = None
    max_model_len: Any = None
    decode_reserve_pages: Any = None
    prefix_cache: Optional[PrefixCacheSchema] = None
    chunked_prefill: Optional[ChunkedPrefillSchema] = None
    shared_prefix: Optional[SharedPrefixSchema] = None
    shed: Optional[ShedSchema] = None
    supervisor: Optional[SupervisorSchema] = None
    overload: Optional[OverloadSchema] = None
    speculative: Optional[SpeculativeSchema] = None
    fleet: Optional[FleetSchema] = None
    disagg: Optional[DisaggSchema] = None
    migration: Optional[MigrationSchema] = None
    gateway: Optional[GatewaySchema] = None
    tenancy: Optional[TenancySchema] = None


@dataclasses.dataclass(frozen=True)
class LatencySchema:
    batch_sizes: Any = None
    seq_lengths: Any = None
    measure_steps: Any = None
    warmup_steps: Any = None
    decode: Optional[DecodeLatencySchema] = None
    serving: Optional[ServingLatencySchema] = None


@dataclasses.dataclass(frozen=True)
class GuardSchema:
    enabled: Any = None
    rollback: Any = None
    spike_factor: Any = None
    max_consecutive_bad: Any = None


@dataclasses.dataclass(frozen=True)
class WatchdogSchema:
    enabled: Any = None
    timeout_s: Any = None


@dataclasses.dataclass(frozen=True)
class ElasticSchema:
    enabled: Any = None
    lease_ttl_s: Any = None
    lease_ttl_steps: Any = None
    gang_dir: Any = None
    sim_world: Any = None
    collective_deadline_s: Any = None


@dataclasses.dataclass(frozen=True)
class ResilienceSchema:
    async_checkpointing: Any = None
    save_retries: Any = None
    retry_backoff_s: Any = None
    preemption: Any = None
    preemption_sync_every: Any = None
    fault_plan: Any = None
    guard: Optional[GuardSchema] = None
    watchdog: Optional[WatchdogSchema] = None
    elastic: Optional[ElasticSchema] = None


@dataclasses.dataclass(frozen=True)
class ObjectiveSchema:
    name: Any = None
    metric: Any = None
    objective: Any = None
    kind: Any = None
    budget: Any = None


@dataclasses.dataclass(frozen=True)
class SloSchema:
    objectives: Optional[List[ObjectiveSchema]] = None
    window_s: Any = None
    budget: Any = None
    check_every: Any = None


@dataclasses.dataclass(frozen=True)
class RootConfigSchema:
    """Top level of every full config under ``config/``; overlay
    fragments (``config/ablations/``) are partial instances of it."""
    experiment_name: Any = None
    seed: Any = None
    backend: Any = None
    model: Optional[ModelSchema] = None
    data: Optional[DataSchema] = None
    optimization: Optional[OptimizationSchema] = None
    logging: Optional[LoggingSchema] = None
    hardware: Optional[HardwareSchema] = None
    ppo: Optional[PpoSchema] = None
    reward_model: Optional[RewardModelSchema] = None
    sampling: Optional[SamplingSchema] = None
    distill: Optional[DistillSchema] = None
    benchmarks: Optional[Dict[str, BenchmarkSchema]] = None
    latency: Optional[LatencySchema] = None
    generation: Optional[GenerationSchema] = None
    resilience: Optional[ResilienceSchema] = None
    slo: Optional[SloSchema] = None
    models: Optional[Dict[str, Any]] = None
