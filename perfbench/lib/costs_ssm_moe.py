"""Parameters, operations and bytes of a decoder of Mamba-2 layers beside
grouped-query attention layers, with routed experts and a shared expert in
every layer (Hugging Face ``granitemoehybrid``: ``layer_types``,
``mamba_n_heads``, ``num_local_experts`` ...), computed from the shapes in
a configuration file. Kept with the benchmark, beside ``costs.py``: a PR
that claims a gain may not change what its work is divided by."""
from __future__ import annotations

from typing import Dict


def _sizes(cfg: Dict):
    """(d, d_inner, heads, head_dim, N, conv channels, conv width)."""
    d = int(cfg["hidden_size"])
    di = int(cfg["mamba_expand"]) * d
    nh = int(cfg["mamba_n_heads"])
    n = int(cfg["mamba_d_state"])
    groups = int(cfg.get("mamba_n_groups", 1))
    return d, di, nh, di // nh, n, di + 2 * groups * n, int(cfg["mamba_d_conv"])


def layer_counts(cfg: Dict) -> Dict[str, int]:
    kinds = list(cfg["layer_types"])
    return {"mamba": kinds.count("mamba"),
            "attention": kinds.count("attention")}


def _head_dim(cfg: Dict) -> int:
    return int(cfg["hidden_size"]) // int(cfg["num_attention_heads"])


def mixer_params(cfg: Dict) -> Dict[str, int]:
    """Every weight of one mixer of each kind: for Mamba-2 the in / out
    projections, the convolution's taps and bias, dt's bias, A and D a
    head, and the gated norm's weight."""
    d, di, nh, _, _, conv, k = _sizes(cfg)
    q = int(cfg["num_attention_heads"]) * _head_dim(cfg)
    kv = int(cfg["num_key_value_heads"]) * _head_dim(cfg)
    return {"mamba": (d * (di + conv + nh) + k * conv + conv + 3 * nh + di
                      + di * d),
            "attention": d * (q + 2 * kv) + q * d}


def expert_params(cfg: Dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * int(cfg["hidden_size"]) * int(cfg["intermediate_size"])


def shared_params(cfg: Dict) -> int:
    return 3 * int(cfg["hidden_size"]) * int(
        cfg.get("shared_intermediate_size") or 0)


def router_params(cfg: Dict) -> int:
    """The router scores every published expert, held here or not."""
    return int(cfg["hidden_size"]) * int(
        cfg.get("experts_published") or cfg["num_local_experts"])


def ffn_params(cfg: Dict) -> int:
    """One layer's FFN as this chip holds it: router, held experts,
    shared expert."""
    return (router_params(cfg) + int(cfg["num_local_experts"])
            * expert_params(cfg) + shared_params(cfg))


def total_params(cfg: Dict) -> int:
    """Every parameter this chip holds: blocks (mixer, FFN, two norms),
    the final norm and the embedding (the tied head)."""
    d, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    mixers, counts = mixer_params(cfg), layer_counts(cfg)
    blocks = sum(counts[kind] * (mixers[kind] + ffn_params(cfg) + 2 * d)
                 for kind in counts)
    head = 0 if cfg.get("tie_word_embeddings", True) else d * v
    return blocks + d + d * v + head


def kv_row_bytes(cfg: Dict, dtype_bytes: int = 2) -> int:
    """Keys and values one token takes in one attention layer."""
    return 2 * int(cfg["num_key_value_heads"]) * _head_dim(cfg) * dtype_bytes


def state_bytes_per_slot(cfg: Dict, dtype_bytes: int = 2) -> int:
    """The recurrent state one sequence keeps over every Mamba-2 layer:
    float32 [heads, head_dim, N] and the convolution's last inputs."""
    _, _, nh, hp, n, conv, k = _sizes(cfg)
    return layer_counts(cfg)["mamba"] * (
        nh * hp * n * 4 + (k - 1) * conv * dtype_bytes)


def decode_step_bytes(cfg: Dict, live_context_tokens: float, slots: float,
                      experts_hit: float, dtype_bytes: int = 2) -> float:
    """Least bytes one decode step must move: every weight but the routed
    experts' once (the batch shares them; the tied embedding is the head),
    one expert's weights for each held expert that received a token
    (``experts_hit``: summed over the layers, from the program's counter),
    the attention layers' rows of the tokens the running slots hold, and
    the running slots' recurrent state read and written."""
    layers = sum(layer_counts(cfg).values())
    held = layers * int(cfg["num_local_experts"]) * expert_params(cfg)
    weights = total_params(cfg) - held + experts_hit * expert_params(cfg)
    return (weights * dtype_bytes
            + live_context_tokens * kv_row_bytes(cfg, dtype_bytes)
            * layer_counts(cfg)["attention"]
            + 2 * slots * state_bytes_per_slot(cfg, dtype_bytes))


def ssd_chunk_flops(cfg: Dict, tokens: float) -> float:
    """Matmul operations the chunked (SSD) scans of one prefill chunk
    need, every Mamba-2 layer, in blocks of ``mamba_chunk_size`` tokens
    (one group): a block of L tokens costs C B^T (2 L^2 N), its masked
    product with dt x (2 L^2 H P), the state from before it read through C
    (2 L H P N) and the state after it (2 L H P N)."""
    _, _, nh, hp, n, _, _ = _sizes(cfg)
    size = min(float(cfg["mamba_chunk_size"]), tokens)
    per_block = 2 * size * size * (n + nh * hp) + 4 * size * nh * hp * n
    return layer_counts(cfg)["mamba"] * tokens / size * per_block


def ssd_chunk_bytes(cfg: Dict, tokens: float, dtype_bytes: int = 2) -> float:
    """Least bytes the SSD scans of one chunk must move, every Mamba-2
    layer: x, B, C and dt in and y out at the activation dtype, and the
    layer's state read and written once."""
    _, di, nh, hp, n, _, _ = _sizes(cfg)
    return layer_counts(cfg)["mamba"] * (
        tokens * (2 * di + 2 * n + nh) * dtype_bytes + 2 * nh * hp * n * 4)


def ssd_chunk_least_seconds(cfg: Dict, tokens: float, peaks: Dict,
                            dtype_bytes: int = 2) -> Dict[str, float]:
    """The two bounds on the SSD scans of one chunk, in seconds: ``mxu``
    and ``hbm``; the larger holds."""
    return {"mxu": ssd_chunk_flops(cfg, tokens) / peaks["bf16_flops_per_s"],
            "hbm": ssd_chunk_bytes(cfg, tokens, dtype_bytes)
            / peaks["hbm_bytes_per_s"]}
