"""Operations and bytes the algorithm needs for a decoder of latent
attention (MLA) and routed experts, computed from the shapes in a
configuration file (Hugging Face key names: ``kv_lora_rank``,
``n_routed_experts`` ...). Kept with the benchmark, beside ``costs.py``:
a PR that claims a gain may not change what its work is divided by."""
from __future__ import annotations

from typing import Dict


def attention_params(cfg: Dict) -> int:
    """Matmul weights of one block's latent attention: q_a, q_b, kv_a,
    kv_b and the output projection."""
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    vd, r = int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"])
    ql = int(cfg.get("q_lora_rank") or 0)
    q = d * ql + ql * h * (nope + rope) if ql else d * h * (nope + rope)
    return q + d * (r + rope) + r * h * (nope + vd) + h * vd * d


def expert_params(cfg: Dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["moe_intermediate_size"])


def shared_params(cfg: Dict) -> int:
    return int(cfg.get("n_shared_experts") or 0) * expert_params(cfg)


def router_params(cfg: Dict) -> int:
    """The router scores every published expert, held here or not."""
    return int(cfg["hidden_size"]) * int(
        cfg.get("experts_published") or cfg["n_routed_experts"])


def layer_fixed_params(cfg: Dict) -> int:
    """What every decode step reads of one block whatever its routing."""
    return attention_params(cfg) + shared_params(cfg) + router_params(cfg)


def total_params(cfg: Dict) -> int:
    """Every parameter this chip holds: blocks with their held experts
    and norms, embedding, final norm and the untied head."""
    d, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    norms = 2 * d + int(cfg["kv_lora_rank"]) + int(cfg.get("q_lora_rank") or 0)
    layer = (layer_fixed_params(cfg) + norms
             + int(cfg["n_routed_experts"]) * expert_params(cfg))
    head = 0 if cfg.get("tie_word_embeddings") else d * v
    return int(cfg["num_hidden_layers"]) * layer + d * v + d + head


def latent_row_bytes(cfg: Dict, dtype_bytes: int = 2) -> int:
    """Bytes one cached token takes in one layer: the normalised latent
    and the one rotated key all heads share."""
    return (int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
            ) * dtype_bytes


def kv_bytes_per_token(cfg: Dict, dtype_bytes: int = 2) -> int:
    return latent_row_bytes(cfg, dtype_bytes) * int(cfg["num_hidden_layers"])


def decode_step_bytes(cfg: Dict, live_context_tokens: float,
                      experts_hit: float, weight_dtype_bytes: int = 2
                      ) -> float:
    """Least bytes one decode step must read: attention, shared-expert
    and router weights of every block and the output head once (the batch
    shares them), one expert's weights for each held expert that received
    a token (``experts_hit``: summed over the blocks, from the program's
    counter), and the latent rows of the tokens the running slots hold,
    in every block. The embedding is a lookup of a row a slot. Bandwidth
    bounds the step: at 32 slots a weight is used 32 times at most, a
    seventh of what a v5e needs to be compute-bound."""
    layers = int(cfg["num_hidden_layers"])
    head = int(cfg["hidden_size"]) * int(cfg["vocab_size"])
    weights = (layers * layer_fixed_params(cfg) + head
               + experts_hit * expert_params(cfg)) * weight_dtype_bytes
    return weights + live_context_tokens * kv_bytes_per_token(cfg)
