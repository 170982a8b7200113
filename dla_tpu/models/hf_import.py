"""Import HuggingFace Llama/Mistral-family weights from a local directory.

Replaces the weight-loading half of the reference's
``AutoModelForCausalLM.from_pretrained`` (src/models/base_model.py:30-35):
reads ``config.json`` + ``*.safetensors`` (or ``pytorch_model.bin``) and
produces this framework's stacked-layer param pytree:

  HF [out, in] Linear weights are transposed to [in, out] (we compute
  ``x @ w``), and per-layer tensors are stacked along a leading [L] dim to
  match the scan-over-layers layout (dla_tpu.models.transformer).

Zero-egress: only local files are read; there is no hub download here.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict, Optional

import numpy as np

from dla_tpu.models.config import ModelConfig
from dla_tpu.ops.rotary import validate_rope_scaling


def _validated_rope_scaling(hf_cfg):
    """rope_scaling from a config.json, normalized/refused by the one
    whitelist ops/rotary.py implements (None for default-type dicts).
    YaRN dicts omitting original_max_position_embeddings get the
    checkpoint's max_position_embeddings injected — HF's own fallback,
    which ops/rotary cannot see from inside the op."""
    # newer configs (mistral4) carry the dict as ``rope_parameters``,
    # with the base frequency inside it
    rs = validate_rope_scaling(hf_cfg.get("rope_scaling")
                               or hf_cfg.get("rope_parameters"))
    rope_type = rs and rs["rope_type"]  # normalized by validate
    if (rope_type == "yarn"
            and "original_max_position_embeddings" not in rs
            and "max_position_embeddings" in hf_cfg):
        rs["original_max_position_embeddings"] = int(
            hf_cfg["max_position_embeddings"])
    if (rope_type == "dynamic"
            and "max_position_embeddings" not in rs
            and "max_position_embeddings" in hf_cfg):
        # dynamic NTK stretches relative to the TRAINED context, which
        # lives at the top level of config.json
        rs["max_position_embeddings"] = int(
            hf_cfg["max_position_embeddings"])
    if rope_type == "longrope":
        # phi-3 keeps the pretraining context at the TOP level of
        # config.json and derives the attention factor from the
        # extension ratio (HF _compute_longrope_parameters); fold both
        # into the dict so ops/rotary needs no config back-reference.
        # The TOP-LEVEL value wins over a dict-level one — HF reads the
        # config attribute for both the switch point and the factor.
        max_pos = hf_cfg.get("max_position_embeddings")
        orig = hf_cfg.get("original_max_position_embeddings")
        if orig:
            rs["original_max_position_embeddings"] = int(orig)
            if max_pos:
                rs["factor"] = float(max_pos) / float(orig)
        elif ("original_max_position_embeddings" not in rs and max_pos):
            rs["original_max_position_embeddings"] = int(max_pos)
    return rs


def _mamba_fields(hf_cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The Mamba-1 sizes of a config.json, with the defaults the HF
    config classes that carry them share (``mamba_d_state`` 16,
    ``mamba_d_conv`` 4, ``mamba_expand`` 2, ``mamba_dt_rank`` "auto" =
    ceil(hidden / 16))."""
    dt_rank = hf_cfg.get("mamba_dt_rank", "auto")
    return dict(
        ssm_state_size=int(hf_cfg.get("mamba_d_state", 16)),
        ssm_conv_width=int(hf_cfg.get("mamba_d_conv", 4)),
        ssm_expand=int(hf_cfg.get("mamba_expand", 2)),
        ssm_dt_rank=0 if dt_rank == "auto" else int(dt_rank))


def _phi4flash_config(hf_cfg: Dict[str, Any], overrides) -> ModelConfig:
    """Phi-4-mini-flash (SambaY, arXiv:2507.06607): the per-layer spec
    from ``num_hidden_layers``, ``mb_per_layer`` and ``sliding_window``
    (config.sambay_layers), LayerNorm with bias, a tied head, no rotary
    embedding. The Mamba sizes are HF ``Phi4FlashConfig``'s defaults
    where the file leaves them out (``mamba_d_state`` 16, ``mamba_d_conv``
    4, ``mamba_expand`` 2, ``mamba_dt_rank`` ceil(hidden / 16))."""
    from dla_tpu.models.config import sambay_layers
    n_heads = int(hf_cfg["num_attention_heads"])
    n_layers = int(hf_cfg["num_hidden_layers"])
    fields = dict(
        vocab_size=int(hf_cfg["vocab_size"]),
        hidden_size=int(hf_cfg["hidden_size"]),
        intermediate_size=int(hf_cfg["intermediate_size"]),
        num_layers=n_layers, num_heads=n_heads,
        num_kv_heads=int(hf_cfg.get("num_key_value_heads", n_heads)),
        rms_norm_eps=float(hf_cfg.get("layer_norm_eps", 1e-5)),
        tie_embeddings=bool(hf_cfg.get("tie_word_embeddings", True)),
        max_seq_length=int(hf_cfg.get("max_position_embeddings", 4096)),
        norm="layer",
        layers=sambay_layers(n_layers, int(hf_cfg["sliding_window"]),
                             int(hf_cfg.get("mb_per_layer", 2))),
        **_mamba_fields(hf_cfg))
    fields.update(overrides)
    return ModelConfig(**fields)


def _jamba_config(hf_cfg: Dict[str, Any], overrides) -> ModelConfig:
    """Jamba (HF ``JambaConfig``): layer l is plain attention iff ``l %
    attn_layer_period == attn_layer_offset`` (``layers_block_type``),
    else Mamba-1 with RMSNorms on dt, B and C; RMSNorm, no rotary
    embedding, a dense gated-SiLU MLP in every layer at ``num_experts``
    1. The routed variant would put an expert MLP in the layers
    ``expert_layer_period`` / ``_offset`` choose and a dense one in the
    others: a per-layer FFN kind the spec'd block does not have, so it
    refuses by name."""
    from dla_tpu.models.config import jamba_layers
    experts = int(hf_cfg.get("num_experts", 1))
    if experts > 1:
        raise ValueError(
            f"model_type 'jamba' with num_experts={experts}: expert MLPs "
            "in some layers and dense ones in the others is a per-layer "
            "FFN kind the per-layer spec does not state; only "
            "num_experts=1 (a dense MLP in every layer) is mapped")
    if hf_cfg.get("mamba_proj_bias", False):
        raise ValueError("model_type 'jamba' with mamba_proj_bias=true: "
                         "the Mamba mixer's in / out projections have no "
                         "bias here")
    if hf_cfg.get("sliding_window"):
        raise ValueError("model_type 'jamba' with a sliding_window: its "
                         "attention layers are mapped as full attention")
    n_heads = int(hf_cfg["num_attention_heads"])
    n_layers = int(hf_cfg["num_hidden_layers"])
    period = int(hf_cfg.get("attn_layer_period", 8))
    offset = int(hf_cfg.get("attn_layer_offset", 4))
    fields = dict(
        vocab_size=int(hf_cfg["vocab_size"]),
        hidden_size=int(hf_cfg["hidden_size"]),
        intermediate_size=int(hf_cfg["intermediate_size"]),
        num_layers=n_layers, num_heads=n_heads,
        num_kv_heads=int(hf_cfg.get("num_key_value_heads", n_heads)),
        rms_norm_eps=float(hf_cfg.get("rms_norm_eps", 1e-6)),
        tie_embeddings=bool(hf_cfg.get("tie_word_embeddings", False)),
        max_seq_length=int(hf_cfg.get("max_position_embeddings", 4096)),
        layers=jamba_layers(n_layers, period, offset),
        ssm_inner_norms=True, **_mamba_fields(hf_cfg))
    fields.update(overrides)
    return ModelConfig(**fields)


def hf_config_to_model_config(hf_cfg: Dict[str, Any], **overrides) -> ModelConfig:
    """Map a Llama/Mistral/Qwen2- or Phi-style HF config.json to
    ModelConfig."""
    model_type = str(hf_cfg.get("model_type", "")).lower()
    if model_type == "phi":
        return _phi_config(hf_cfg, overrides)
    if model_type == "phi4flash":
        return _phi4flash_config(hf_cfg, overrides)
    if model_type == "jamba":
        return _jamba_config(hf_cfg, overrides)
    n_heads = int(hf_cfg["num_attention_heads"])
    rope_theta = hf_cfg.get("rope_theta") or (
        hf_cfg.get("rope_parameters") or {}).get("rope_theta", 10000.0)
    fields = dict(
        vocab_size=int(hf_cfg["vocab_size"]),
        hidden_size=int(hf_cfg["hidden_size"]),
        intermediate_size=int(hf_cfg["intermediate_size"]),
        num_layers=int(hf_cfg["num_hidden_layers"]),
        num_heads=n_heads,
        num_kv_heads=int(hf_cfg.get("num_key_value_heads", n_heads)),
        head_dim=hf_cfg.get("head_dim"),
        rope_theta=float(rope_theta),
        rms_norm_eps=float(hf_cfg.get("rms_norm_eps", 1e-5)),
        tie_embeddings=bool(hf_cfg.get("tie_word_embeddings", False)),
        max_seq_length=int(hf_cfg.get("max_position_embeddings", 4096)),
        # qwen2 carries q/k/v biases; llama configs may also set
        # attention_bias explicitly
        attention_bias=bool(hf_cfg.get("attention_bias",
                                       model_type == "qwen2")),
    )
    rs = _validated_rope_scaling(hf_cfg)
    if rs:
        fields["rope_scaling"] = rs
    if model_type == "gemma":
        # gated GELU MLP, sqrt(hidden)-scaled embeddings, (1+w) norms
        # (folded into the stored weights at import), tied unembedding
        # (GemmaConfig defaults tie_word_embeddings=True)
        fields["arch"] = "gemma"
        fields["tie_embeddings"] = bool(
            hf_cfg.get("tie_word_embeddings", True))
    if model_type == "gemma2":
        # gemma plus: post-attn/post-ffw norms (4 RMSNorms per block),
        # attention + final logit softcapping, query_pre_attn_scalar
        # softmax scale, and alternating-layer SWA (even layers slide —
        # HF Gemma2's is_sliding = not layer_idx % 2 == pattern 2 with
        # the (l+1) % pattern != 0 rule). Gemma2Config has no
        # use_sliding_window knob: a set sliding_window always applies.
        fields["arch"] = "gemma2"
        fields["tie_embeddings"] = bool(
            hf_cfg.get("tie_word_embeddings", True))
        fields["attn_logit_softcap"] = float(
            hf_cfg.get("attn_logit_softcapping") or 0.0)
        fields["final_logit_softcap"] = float(
            hf_cfg.get("final_logit_softcapping") or 0.0)
        qpas = hf_cfg.get("query_pre_attn_scalar")
        if qpas:
            fields["query_pre_attn_scalar"] = int(qpas)
        if hf_cfg.get("sliding_window"):
            fields["sliding_window"] = int(hf_cfg["sliding_window"])
            fields["sliding_window_pattern"] = 2
    if hf_cfg.get("kv_lora_rank"):
        # latent attention (mistral4; the DeepSeek-V2 key names)
        fields.update(
            q_lora_rank=int(hf_cfg.get("q_lora_rank") or 0),
            kv_lora_rank=int(hf_cfg["kv_lora_rank"]),
            qk_nope_head_dim=int(hf_cfg["qk_nope_head_dim"]),
            qk_rope_head_dim=int(hf_cfg["qk_rope_head_dim"]),
            v_head_dim=int(hf_cfg["v_head_dim"]),
            rope_interleave=bool(hf_cfg.get("rope_interleave", False)))
    if hf_cfg.get("n_routed_experts"):
        fields.update(_routed_experts(hf_cfg))
    if model_type == "mixtral" or "num_local_experts" in hf_cfg:
        fields["num_experts"] = int(hf_cfg.get("num_local_experts", 8))
        fields["num_experts_per_token"] = int(
            hf_cfg.get("num_experts_per_tok", 2))
    # mistral sliding-window attention; qwen2 ships sliding_window but
    # HF Qwen2Config defaults use_sliding_window to FALSE — an absent
    # key must follow the per-model-type transformers default (round-3
    # advisor finding). Whitelist the families whose HF configs apply a
    # set sliding_window unconditionally (no use_sliding_window knob);
    # any other type with the key absent stays full-causal rather than
    # silently windowing.
    sw = hf_cfg.get("sliding_window")
    # phi3 (like mistral/mixtral) has no use_sliding_window knob: a set
    # sliding_window always applies
    sw_default_on = model_type in ("mistral", "mixtral", "phi3")
    if sw and hf_cfg.get("use_sliding_window", sw_default_on):
        # qwen2's max_window_layers: the FIRST mwl layers run full
        # attention, SWA applies to layers i >= mwl (transformers
        # configuration_qwen2.py layer_types derivation). This
        # architecture's window is all-layers, so only mwl == 0 (SWA
        # everywhere) is representable; mwl >= L means SWA is disabled
        # entirely; anything between is per-layer — refuse rather than
        # silently windowing the full-attention layers. An absent key
        # means the HF default (28), not 0.
        mwl = hf_cfg.get("max_window_layers")
        if mwl is None and model_type == "qwen2":
            mwl = 28
        n_layers = int(hf_cfg["num_hidden_layers"])
        if mwl is None or int(mwl) == 0:
            fields["sliding_window"] = int(sw)
        elif int(mwl) >= n_layers:
            pass  # every layer full-attention: window never applies
        else:
            raise ValueError(
                f"partial sliding-window scheme (max_window_layers={mwl} "
                f"of {n_layers} layers full-attention) is not supported; "
                "sliding_window here is all-layers")
    fields.update(overrides)
    return ModelConfig(**fields)


def _routed_experts(hf_cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The DeepSeek-lineage expert keys (mistral4): ``n_routed_experts``
    counts the experts whose weights THIS process holds; a file that
    stands for one chip of an expert-parallel deployment states the
    router's width as ``experts_published`` and the held ids as
    ``experts_held`` = [first, last]. What ops/moe.py does not compute is
    refused, not approximated."""
    if int(hf_cfg.get("first_k_dense_replace") or 0):
        raise NotImplementedError(
            "leading dense layers (first_k_dense_replace > 0) need a "
            "per-layer layer spec; every layer here is an expert layer")
    if int(hf_cfg.get("n_group") or 1) != 1 or \
            int(hf_cfg.get("topk_group") or 1) != 1:
        raise NotImplementedError("group-limited routing (n_group > 1)")
    if str(hf_cfg.get("scoring_func") or "softmax") != "softmax":
        raise NotImplementedError(
            "only the softmax router is implemented (no sigmoid scores, "
            "no correction bias)")
    if not hf_cfg.get("norm_topk_prob", True):
        raise NotImplementedError(
            "router weights are renormalised over the chosen experts "
            "(norm_topk_prob false is not implemented)")
    held = int(hf_cfg["n_routed_experts"])
    published = int(hf_cfg.get("experts_published") or held)
    first = int((hf_cfg.get("experts_held") or [0])[0])
    return dict(
        num_experts=published,
        moe_experts_held=0 if held == published else held,
        moe_first_expert=first,
        num_experts_per_token=int(hf_cfg["num_experts_per_tok"]),
        moe_intermediate_size=int(hf_cfg["moe_intermediate_size"]),
        num_shared_experts=int(hf_cfg.get("n_shared_experts") or 0),
        moe_routed_scale=float(hf_cfg.get("routed_scaling_factor") or 1.0))


def _phi_config(hf_cfg: Dict[str, Any], overrides) -> ModelConfig:
    """microsoft/phi-2-style config.json: parallel block, partial rotary,
    LayerNorm (layer_norm_eps, not rms_norm_eps), biased projections."""
    n_heads = int(hf_cfg["num_attention_heads"])
    fields = dict(
        vocab_size=int(hf_cfg["vocab_size"]),
        hidden_size=int(hf_cfg["hidden_size"]),
        intermediate_size=int(hf_cfg["intermediate_size"]),
        num_layers=int(hf_cfg["num_hidden_layers"]),
        num_heads=n_heads,
        num_kv_heads=int(hf_cfg.get("num_key_value_heads") or n_heads),
        rope_theta=float(hf_cfg.get("rope_theta", 10000.0)),
        rms_norm_eps=float(hf_cfg.get("layer_norm_eps", 1e-5)),
        tie_embeddings=bool(hf_cfg.get("tie_word_embeddings", False)),
        max_seq_length=int(hf_cfg.get("max_position_embeddings", 2048)),
        arch="phi",
        rotary_pct=float(hf_cfg.get("partial_rotary_factor", 0.5)),
    )
    rs = _validated_rope_scaling(hf_cfg)
    if rs:
        fields["rope_scaling"] = rs
    fields.update(overrides)
    return ModelConfig(**fields)


def read_hf_config(model_dir) -> Optional[Dict[str, Any]]:
    p = Path(model_dir) / "config.json"
    if not p.is_file():
        return None
    with p.open() as fh:
        return json.load(fh)


def _load_state_dict(model_dir: Path) -> Dict[str, np.ndarray]:
    """All tensors from safetensors shards (preferred) or a torch bin."""
    st_files = sorted(model_dir.glob("*.safetensors"))
    if st_files:
        from safetensors import safe_open
        out: Dict[str, np.ndarray] = {}
        for f in st_files:
            with safe_open(str(f), framework="np") as sf:
                for key in sf.keys():
                    out[key] = sf.get_tensor(key)
        return out
    bin_files = sorted(model_dir.glob("pytorch_model*.bin"))
    if bin_files:
        import torch
        out = {}
        for f in bin_files:
            sd = torch.load(str(f), map_location="cpu", weights_only=True)
            for k, v in sd.items():
                out[k] = v.float().numpy() if v.dtype == torch.bfloat16 \
                    else v.numpy()
        return out
    raise FileNotFoundError(
        f"No *.safetensors or pytorch_model*.bin under {model_dir}")


def import_hf_weights(model_dir, cfg: ModelConfig,
                      dtype: Optional[str] = None) -> Dict[str, Any]:
    """Local HF checkpoint dir -> dla_tpu param pytree (host numpy)."""
    model_dir = Path(model_dir)
    sd = _load_state_dict(model_dir)
    pdtype = np.dtype(dtype or cfg.param_dtype)
    pre = "model." if any(k.startswith("model.") for k in sd) else ""

    def take(name: str) -> np.ndarray:
        key = pre + name
        if key not in sd:
            raise KeyError(f"HF checkpoint missing tensor '{key}'")
        return np.asarray(sd[key])

    def linear(name: str) -> np.ndarray:
        return take(name).T.astype(pdtype)  # [out,in] -> [in,out]

    if cfg.arch == "phi":
        return _import_phi(sd, cfg, pdtype, take, linear)

    L = cfg.num_layers
    moe = cfg.num_experts > 0
    gemma2 = cfg.arch == "gemma2"
    stacked: Dict[str, list] = {k: [] for k in (
        "attn_norm", "wq", "wk", "wv", "wo",
        "mlp_norm", "w_gate", "w_up", "w_down")}
    if gemma2:
        stacked["attn_post_norm"] = []
        stacked["mlp_post_norm"] = []
    if moe:
        stacked["router"] = []
    if cfg.attention_bias:
        for k in ("wq_bias", "wk_bias", "wv_bias"):
            stacked[k] = []
    # phi-3 fuses q/k/v into qkv_proj and gate/up into gate_up_proj;
    # detect by key (the config maps to the plain llama block otherwise)
    fused_qkv = (pre + "layers.0.self_attn.qkv_proj.weight") in sd
    qd = cfg.num_heads * cfg.head_dim_
    kvd = cfg.num_kv_heads * cfg.head_dim_
    for i in range(L):
        p = f"layers.{i}."
        stacked["attn_norm"].append(take(p + "input_layernorm.weight").astype(pdtype))
        if fused_qkv:
            qkv = take(p + "self_attn.qkv_proj.weight")  # [(H+2K)dh, D]
            stacked["wq"].append(qkv[:qd].T.astype(pdtype))
            stacked["wk"].append(qkv[qd:qd + kvd].T.astype(pdtype))
            stacked["wv"].append(qkv[qd + kvd:].T.astype(pdtype))
        else:
            stacked["wq"].append(linear(p + "self_attn.q_proj.weight"))
            stacked["wk"].append(linear(p + "self_attn.k_proj.weight"))
            stacked["wv"].append(linear(p + "self_attn.v_proj.weight"))
        if cfg.attention_bias:
            stacked["wq_bias"].append(
                take(p + "self_attn.q_proj.bias").astype(pdtype))
            stacked["wk_bias"].append(
                take(p + "self_attn.k_proj.bias").astype(pdtype))
            stacked["wv_bias"].append(
                take(p + "self_attn.v_proj.bias").astype(pdtype))
        stacked["wo"].append(linear(p + "self_attn.o_proj.weight"))
        if gemma2:
            # gemma-2 norm names: post_attention_layernorm normalizes the
            # attention OUTPUT (pre-residual); the MLP's pre-norm is
            # pre_feedforward_layernorm
            stacked["attn_post_norm"].append(
                take(p + "post_attention_layernorm.weight").astype(pdtype))
            stacked["mlp_norm"].append(
                take(p + "pre_feedforward_layernorm.weight").astype(pdtype))
            stacked["mlp_post_norm"].append(
                take(p + "post_feedforward_layernorm.weight").astype(pdtype))
        else:
            stacked["mlp_norm"].append(
                take(p + "post_attention_layernorm.weight").astype(pdtype))
        if moe:
            # Mixtral MoE layout: block_sparse_moe.gate -> router,
            # experts.j.{w1,w3,w2} -> per-expert gate/up/down, stacked
            # along a leading [E] dim
            m = p + "block_sparse_moe."
            stacked["router"].append(linear(m + "gate.weight"))
            stacked["w_gate"].append(np.stack(
                [linear(m + f"experts.{j}.w1.weight")
                 for j in range(cfg.num_experts)]))
            stacked["w_up"].append(np.stack(
                [linear(m + f"experts.{j}.w3.weight")
                 for j in range(cfg.num_experts)]))
            stacked["w_down"].append(np.stack(
                [linear(m + f"experts.{j}.w2.weight")
                 for j in range(cfg.num_experts)]))
        elif fused_qkv:
            gu = take(p + "mlp.gate_up_proj.weight")      # [2F, D]
            f_dim = cfg.intermediate_size
            stacked["w_gate"].append(gu[:f_dim].T.astype(pdtype))
            stacked["w_up"].append(gu[f_dim:].T.astype(pdtype))
            stacked["w_down"].append(linear(p + "mlp.down_proj.weight"))
        else:
            stacked["w_gate"].append(linear(p + "mlp.gate_proj.weight"))
            stacked["w_up"].append(linear(p + "mlp.up_proj.weight"))
            stacked["w_down"].append(linear(p + "mlp.down_proj.weight"))

    params: Dict[str, Any] = {
        "embed": {"embedding": take("embed_tokens.weight").astype(pdtype)},
        "layers": {k: np.stack(v) for k, v in stacked.items()},
        "final_norm": take("norm.weight").astype(pdtype),
    }
    if cfg.arch in ("gemma", "gemma2"):
        # HF gemma RMSNorm computes x * (1 + w); fold the +1 here so the
        # model's shared rms_norm path needs no arch branch
        norm_keys = ("attn_norm", "mlp_norm") if cfg.arch == "gemma" else (
            "attn_norm", "mlp_norm", "attn_post_norm", "mlp_post_norm")
        for k in norm_keys:
            params["layers"][k] = params["layers"][k] + np.asarray(1, pdtype)
        params["final_norm"] = params["final_norm"] + np.asarray(1, pdtype)
    if not cfg.tie_embeddings:
        if "lm_head.weight" in sd:
            params["lm_head"] = np.asarray(sd["lm_head.weight"]).T.astype(pdtype)
        else:
            params["lm_head"] = params["embed"]["embedding"].T.copy()
    return params


def _import_phi(sd, cfg: ModelConfig, pdtype, take, linear
                ) -> Dict[str, Any]:
    """Phi weight layout (HF PhiForCausalLM): shared input_layernorm
    (weight+bias), q/k/v_proj + dense with biases, mlp.fc1/fc2 with
    biases, final_layernorm, biased lm_head."""
    L = cfg.num_layers
    names = {
        "ln": "input_layernorm.weight", "ln_bias": "input_layernorm.bias",
        "wq": "self_attn.q_proj.weight", "wq_bias": "self_attn.q_proj.bias",
        "wk": "self_attn.k_proj.weight", "wk_bias": "self_attn.k_proj.bias",
        "wv": "self_attn.v_proj.weight", "wv_bias": "self_attn.v_proj.bias",
        "wo": "self_attn.dense.weight", "wo_bias": "self_attn.dense.bias",
        "fc1": "mlp.fc1.weight", "fc1_bias": "mlp.fc1.bias",
        "fc2": "mlp.fc2.weight", "fc2_bias": "mlp.fc2.bias",
    }
    matrices = ("wq", "wk", "wv", "wo", "fc1", "fc2")
    stacked: Dict[str, list] = {k: [] for k in names}
    for i in range(L):
        p = f"layers.{i}."
        for ours, theirs in names.items():
            stacked[ours].append(
                linear(p + theirs) if ours in matrices
                else take(p + theirs).astype(pdtype))
    params: Dict[str, Any] = {
        "embed": {"embedding": take("embed_tokens.weight").astype(pdtype)},
        "layers": {k: np.stack(v) for k, v in stacked.items()},
        "final_norm": take("final_layernorm.weight").astype(pdtype),
        "final_norm_bias": take("final_layernorm.bias").astype(pdtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = np.asarray(sd["lm_head.weight"]).T.astype(pdtype)
        bias = sd.get("lm_head.bias")
        params["lm_head_bias"] = (
            np.asarray(bias).astype(pdtype) if bias is not None
            else np.zeros((cfg.vocab_size,), pdtype))
    return params
