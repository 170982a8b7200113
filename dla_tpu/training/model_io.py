"""Model resolution: config name / HF id / checkpoint path -> ModelBundle.

TPU-native counterpart of the reference's loaders
(src/models/base_model.py:17-42 ``load_causal_lm`` and
src/models/reward_model.py:20-35 ``build_reward_model``): the same config
keys (``model_name_or_path`` etc.) accept

1. a dla_tpu checkpoint directory (or its ``latest`` pointer) — the chain
   the reference uses between phases (checkpoints/sft/latest -> DPO, ...);
2. a registry preset / HF repo id (dla_tpu.models.config) — fresh init, or
   HF safetensors import when local weight files exist
   (dla_tpu.models.hf_import).
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

import jax

from dla_tpu.checkpoint.checkpointer import (
    is_checkpoint_path,
    load_tree_numpy,
)
from dla_tpu.data.tokenizers import ByteTokenizer, Tokenizer, load_tokenizer
from dla_tpu.models.config import ModelConfig, get_model_config
from dla_tpu.models.reward import RewardModel
from dla_tpu.models.transformer import Transformer
from dla_tpu.utils.profiling import startup_span


@dataclasses.dataclass
class ModelBundle:
    """(reference base_model.py:11-14 ModelBundle carried tokenizer+model)"""
    model: Any                 # Transformer | RewardModel
    params: Any
    specs: Any
    tokenizer: Tokenizer
    config: ModelConfig


def _tokenizer_for(name_or_path: str, model_cfg: Dict[str, Any],
                   aux: Optional[Dict] = None) -> Tokenizer:
    tok_name = model_cfg.get("tokenizer")
    if tok_name:
        return load_tokenizer(tok_name)
    if aux and aux.get("tokenizer"):
        return load_tokenizer(aux["tokenizer"])
    if is_checkpoint_path(name_or_path):
        return ByteTokenizer()
    return load_tokenizer(name_or_path)


def _arch_overrides(model_cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Config keys that override preset architecture fields."""
    out: Dict[str, Any] = {}
    if "max_seq_length" in model_cfg:
        out["max_seq_length"] = int(model_cfg["max_seq_length"])
    if model_cfg.get("gradient_checkpointing") is False:
        out["remat"] = "none"
    elif model_cfg.get("gradient_checkpointing") is True:
        out["remat"] = "full"
    if "use_flash_attention" in model_cfg:
        out["attention"] = ("flash" if model_cfg["use_flash_attention"]
                            else "xla")
    for key in ("dtype", "param_dtype", "remat", "vocab_size", "num_layers",
                "attention",
                "kv_cache_dtype", "decode_kernel",
                "context_parallel", "arch", "rotary_pct", "attention_bias",
                "sliding_window", "sliding_window_pattern",
                "attn_logit_softcap", "final_logit_softcap",
                "query_pre_attn_scalar",
                "pipeline_microbatches", "pipeline_interleave",
                "pipeline_stages",
                "num_experts", "num_experts_per_token",
                "moe_capacity_factor", "moe_group_size", "moe_aux_weight",
                "moe_z_weight", "moe_intermediate_size",
                "num_shared_experts", "moe_routed_scale",
                "moe_first_expert", "moe_experts_held",
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "rope_interleave"):
        if key in model_cfg:
            out[key] = model_cfg[key]
    # reference model.lora block (config/distill_config.yaml:10-14; dead
    # there, functional here — Transformer.init_lora)
    lora = model_cfg.get("lora") or {}
    if lora.get("enabled"):
        out["lora_r"] = int(lora.get("r", 8))
        out["lora_alpha"] = float(lora.get("alpha", 32.0))
        out["lora_dropout"] = float(lora.get("dropout", 0.0))
        if lora.get("target_modules"):
            out["lora_targets"] = tuple(lora["target_modules"])
    return out


def load_causal_lm(name_or_path: str, model_cfg: Dict[str, Any],
                   rng: jax.Array) -> ModelBundle:
    """Resolve a causal LM (policy/teacher/student):
    dla_tpu checkpoint > local HF weight dir > registry preset."""
    with startup_span("startup_weights", source="") as span:
        source, bundle = _load_causal_lm(name_or_path, model_cfg, rng)
        span.set(source=source)
    return bundle


def _load_causal_lm(name_or_path: str, model_cfg: Dict[str, Any],
                    rng: jax.Array) -> Tuple[str, ModelBundle]:
    overrides = _arch_overrides(model_cfg)
    if is_checkpoint_path(name_or_path):
        params, aux = load_tree_numpy(name_or_path, prefix="params")
        mc = aux.get("model_config")
        if mc is None:
            raise ValueError(
                f"checkpoint {name_or_path} lacks model_config aux; "
                "cannot rebuild the architecture")
        cfg = ModelConfig.from_dict({**mc, **overrides})
        model = Transformer(cfg)
        # a checkpoint written by a matching run is already in storage
        # layout (idempotent); one written canonically (e.g. converted
        # cross-topology via to_canonical_layout) reshapes here
        params = model.to_storage_layout(params)
        tok = _tokenizer_for(name_or_path, model_cfg, aux)
        return "checkpoint", ModelBundle(
            model, params, model.partition_specs(), tok, cfg)

    hf = _try_hf_dir(name_or_path, overrides)
    if hf is not None:
        cfg, params = hf
        model = Transformer(cfg)
        # HF import builds the canonical [L] stack; interleaved-PP
        # models store block-major (free reshape, no-op otherwise)
        params = model.to_storage_layout(params)
        tok = _tokenizer_for(name_or_path, model_cfg)
        return "hf_dir", ModelBundle(
            model, params, model.partition_specs(), tok, cfg)

    cfg = get_model_config(name_or_path, **overrides)
    model = Transformer(cfg)
    tok = _tokenizer_for(name_or_path, model_cfg)
    if getattr(tok, "vocab_size", cfg.vocab_size) > cfg.vocab_size:
        cfg = dataclasses.replace(cfg, vocab_size=int(tok.vocab_size))
        model = Transformer(cfg)
    params = model.init(rng)
    return "preset", ModelBundle(
        model, params, model.partition_specs(), tok, cfg)


def build_reward_model(model_cfg: Dict[str, Any], rng: jax.Array) -> ModelBundle:
    """Reward model from ``model.base_model_name_or_path`` + pooling/dropout
    (reference reward_model.py:20-35, config/reward_config.yaml)."""
    name = (model_cfg.get("base_model_name_or_path")
            or model_cfg.get("model_name_or_path"))
    pooling = model_cfg.get("pooling", "last_token")
    dropout = float(model_cfg.get("dropout", 0.0))
    overrides = _arch_overrides(model_cfg)
    if is_checkpoint_path(name):
        params, aux = load_tree_numpy(name, prefix="params")
        mc = aux.get("model_config")
        if mc is None:
            raise ValueError(f"checkpoint {name} lacks model_config aux")
        cfg = ModelConfig.from_dict({**mc, **overrides})
        rm = RewardModel(cfg, pooling=pooling, dropout=dropout)
        if "reward_head" not in params:
            # warm-starting a reward model from a causal-LM checkpoint:
            # fresh head, drop the unembedding
            params.pop("lm_head", None)
            fresh = rm.init(rng)
            params["reward_head"] = fresh["reward_head"]
        tok = _tokenizer_for(name, model_cfg, aux)
        return ModelBundle(rm, params, rm.partition_specs(), tok, cfg)

    cfg = get_model_config(name, **overrides)
    tok = _tokenizer_for(name, model_cfg)
    if getattr(tok, "vocab_size", cfg.vocab_size) > cfg.vocab_size:
        cfg = dataclasses.replace(cfg, vocab_size=int(tok.vocab_size))
    rm = RewardModel(cfg, pooling=pooling, dropout=dropout)
    params = rm.init(rng)
    return ModelBundle(rm, params, rm.partition_specs(), tok, cfg)


def _try_hub_snapshot(repo_id: str) -> Optional[Path]:
    """Optional hub fetch (reference parity: base_model.py:30-35 loads any
    hub id via from_pretrained). Opt-in via DLA_HF_HUB_DOWNLOAD=1 because
    the primary deployment is zero-egress — without the flag, hub-looking
    names fall through to the preset registry (random init) exactly as
    before. With it, weights download once into the HF cache and import
    through the same local-dir path."""
    import os
    if "/" not in repo_id or not os.environ.get("DLA_HF_HUB_DOWNLOAD"):
        return None
    try:
        from huggingface_hub import snapshot_download
        return Path(snapshot_download(
            repo_id,
            allow_patterns=["*.safetensors", "*.json", "*.model",
                            "tokenizer*"]))
    except Exception as e:  # noqa: BLE001 — fall back to preset init, loudly
        from dla_tpu.utils.logging import log_rank_zero
        log_rank_zero(f"[dla_tpu] hub fetch of '{repo_id}' failed "
                      f"({type(e).__name__}: {e}); using preset init")
        return None


def _try_hf_dir(name_or_path: str, overrides: Dict[str, Any]):
    """(ModelConfig, params) from a local HF weight directory (or an
    opt-in hub snapshot, see _try_hub_snapshot), else None."""
    p = Path(name_or_path)
    if not p.is_dir():
        p = _try_hub_snapshot(name_or_path)
        if p is None:
            return None
    from dla_tpu.models.hf_import import (
        hf_config_to_model_config,
        import_hf_weights,
        read_hf_config,
    )
    hf_cfg = read_hf_config(p)
    if hf_cfg is None:
        return None
    cfg = hf_config_to_model_config(hf_cfg, **{
        k: v for k, v in overrides.items() if k != "vocab_size"})
    return cfg, import_hf_weights(p, cfg)


def model_aux(bundle: ModelBundle, tokenizer_name: Optional[str] = None
              ) -> Dict[str, Any]:
    """aux dict to store with checkpoints so they are self-describing."""
    out: Dict[str, Any] = {"model_config": bundle.config.to_dict()}
    if tokenizer_name:
        out["tokenizer"] = tokenizer_name
    return out


def init_lora_adapters(bundle: ModelBundle, rng: jax.Array):
    """(adapters, specs) for a LoRA run, with a rank-0 size report."""
    from dla_tpu.utils.logging import log_rank_zero
    adapters = bundle.model.init_lora(rng)
    n_adapt = sum(int(l.size) for l in jax.tree.leaves(adapters))
    n_base = sum(int(l.size) for l in jax.tree.leaves(bundle.params))
    log_rank_zero(
        f"[dla_tpu] LoRA r={bundle.config.lora_r}: "
        f"{n_adapt:,} trainable / {n_base:,} frozen params")
    return adapters, bundle.model.lora_partition_specs()


def save_merged_lora_final(trainer, bundle: ModelBundle, base_params,
                           tokenizer_name: Optional[str] = None,
                           adapters=None) -> None:
    """Write a `merged` checkpoint with adapters folded into the base
    weights so downstream phases (configs chain via checkpoints/X/latest —
    save() repoints `latest` here) load a plain model. The adapter `final`
    and step checkpoints remain intact for resume; Trainer.try_resume
    falls back to them when `latest` names this export artifact."""
    from dla_tpu.utils.logging import log_rank_zero
    merged = bundle.model.merge_lora(
        base_params, adapters if adapters is not None else trainer.params)
    aux = {"step": trainer.step, **model_aux(bundle, tokenizer_name)}
    aux["model_config"] = dataclasses.replace(
        bundle.config, lora_r=0).to_dict()
    trainer.checkpointer.save(
        trainer.step, {"params": merged}, aux, tag="merged")
    log_rank_zero("[dla_tpu] wrote merged (LoRA-folded) checkpoint "
                  "(`latest` -> merged; training state kept in `final`)")
    # alongside the fold, export the RAW adapter tree in the
    # AdapterStore servable format (manifest.json + adapter.npz): the
    # multi-tenant serving path loads this via tenancy.load_adapter_tree
    # and serves it unmerged — one base-weight engine, N such adapters
    cfg = bundle.config
    tree = adapters if adapters is not None else trainer.params
    layers = tree.get("layers") if isinstance(tree, dict) else None
    # only the causal-LM adapter layout is servable: reward-model
    # adapter trees (no target-keyed ``layers`` block) merge fine above
    # but have no multi-tenant decode path to export for
    servable = isinstance(layers, dict) and all(
        f"{t}_lora_{s}" in layers
        for t in cfg.lora_targets for s in ("a", "b"))
    if servable and getattr(trainer.checkpointer, "is_main", True):
        from dla_tpu.serving.tenancy import export_adapter_tree
        out = export_adapter_tree(
            str(Path(trainer.checkpointer.dir) / "adapter_servable"),
            tree,
            targets=tuple(cfg.lora_targets), rank=int(cfg.lora_r),
            alpha=float(cfg.lora_alpha), num_layers=int(cfg.num_layers))
        log_rank_zero(f"[dla_tpu] wrote servable adapter export at {out} "
                      "(publish_adapter-loadable; see docs/SERVING.md "
                      "\"Multi-tenant serving\")")
