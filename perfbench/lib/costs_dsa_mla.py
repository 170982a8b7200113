"""Operations and bytes the algorithm needs for a decoder of latent
attention with a learned sparse (DSA) indexer, leading dense layers and
routed experts (``model_type: glm_moe_dsa``), computed from the shapes in
a configuration file (Hugging Face key names). Kept with the benchmark,
beside ``costs_mla_moe.py``, whose latent-attention and expert counts it
shares: a PR that claims a gain may not change what its work is divided
by."""
from __future__ import annotations

from typing import Dict

from perfbench.lib.costs_mla_moe import (attention_params, expert_params,
                                         router_params, shared_params)


def indexer_params(cfg: Dict) -> int:
    """The indexer of one block: its query from the query latent, its key
    and its head weights from the block input."""
    d, hi = int(cfg["hidden_size"]), int(cfg["index_n_heads"])
    di = int(cfg["index_head_dim"])
    return int(cfg["q_lora_rank"]) * hi * di + d * di + d * hi


def dense_mlp_params(cfg: Dict) -> int:
    return 3 * int(cfg["hidden_size"]) * int(cfg["intermediate_size"])


def layer_counts(cfg: Dict):
    """(leading dense layers, expert layers)."""
    dense = int(cfg.get("first_k_dense_replace") or 0)
    return dense, int(cfg["num_hidden_layers"]) - dense


def fixed_params(cfg: Dict) -> int:
    """What every step reads of the blocks whatever its routing: every
    layer's attention and indexer, the dense layers' MLP, the expert
    layers' shared expert and router."""
    dense, moe = layer_counts(cfg)
    return ((dense + moe) * (attention_params(cfg) + indexer_params(cfg))
            + dense * dense_mlp_params(cfg)
            + moe * (shared_params(cfg) + router_params(cfg)))


def head_params(cfg: Dict) -> int:
    return int(cfg["hidden_size"]) * int(cfg["vocab_size"])


def row_bytes(cfg: Dict, dtype_bytes: int = 2) -> int:
    """A latent row: the normalised latent and the shared rotated key."""
    return (int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
            ) * dtype_bytes


def key_bytes(cfg: Dict, dtype_bytes: int = 2) -> int:
    """An index key."""
    return int(cfg["index_head_dim"]) * dtype_bytes


def decode_step_bytes(cfg: Dict, scored_tokens: float, read_rows: float,
                      experts_hit: float, weight_dtype_bytes: int = 2
                      ) -> float:
    """Least bytes one decode step must read: the fixed weights and the
    head once (the batch shares them), one expert's weights for each held
    expert that received a token (``experts_hit``, summed over the expert
    layers, from the program's counter), every index key the running
    slots' queries score (``scored_tokens``, summed over the slots) and
    the latent rows they read (``read_rows``, at most index_topk a slot),
    in every layer."""
    layers = int(cfg["num_hidden_layers"])
    weights = (fixed_params(cfg) + head_params(cfg)
               + experts_hit * expert_params(cfg)) * weight_dtype_bytes
    return weights + layers * (scored_tokens * key_bytes(cfg)
                               + read_rows * row_bytes(cfg))


def chunk_flops(cfg: Dict, tokens: float, context: float) -> float:
    """Matmul operations of one prefill chunk of ``tokens`` real tokens at
    a mean position of ``context``: every token through the fixed weights,
    the experts it chose that are held here (its top k times the share of
    experts held), its indexer's scores against every column up to it,
    the absorbed query and output of its latent heads and their scores
    and weighted sum over the index_topk rows it reads; the head for one
    row."""
    dense, moe = layer_counts(cfg)
    layers = dense + moe
    h, r = int(cfg["num_attention_heads"]), int(cfg["kv_lora_rank"])
    nope, vd = int(cfg["qk_nope_head_dim"]), int(cfg["v_head_dim"])
    rope = int(cfg["qk_rope_head_dim"])
    held = int(cfg["n_routed_experts"]) / int(
        cfg.get("experts_published") or cfg["n_routed_experts"])
    routed = moe * int(cfg["num_experts_per_tok"]) * held * expert_params(cfg)
    rows = min(float(int(cfg["index_topk"])), context)
    per_token = (fixed_params(cfg) + routed + layers * (
        int(cfg["index_n_heads"]) * int(cfg["index_head_dim"]) * context
        + h * (nope * r + r * vd) + h * rows * (2 * r + rope)))
    return 2.0 * (tokens * per_token + head_params(cfg))


def chunk_bytes(cfg: Dict, tokens: float, context: float,
                weight_dtype_bytes: int = 2) -> float:
    """Least bytes of one chunk: every weight held here once (a chunk's
    pairs reach every held expert), the index keys of its context and
    the latent rows its queries read, at most the context's, in every
    layer."""
    dense, moe = layer_counts(cfg)
    weights = (fixed_params(cfg) + head_params(cfg) + moe * int(
        cfg["n_routed_experts"]) * expert_params(cfg)) * weight_dtype_bytes
    rows = min(context, tokens * int(cfg["index_topk"]))
    return weights + (dense + moe) * (context * key_bytes(cfg)
                                      + rows * row_bytes(cfg))
