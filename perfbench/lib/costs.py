"""Operations and bytes the algorithm needs, computed from the shapes in
a configuration file (Hugging Face key names). Kept with the benchmark:
a PR that claims a gain may not change what its work is divided by."""
from __future__ import annotations

from typing import Dict, Iterable


def head_dim(cfg: Dict) -> int:
    return int(cfg.get("head_dim") or
               cfg["hidden_size"] // cfg["num_attention_heads"])


def layer_matmul_params(cfg: Dict) -> int:
    """Weights of one decoder block that a token is multiplied through:
    q, k, v, o projections and the gated MLP's three matrices."""
    d, f = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    dh = head_dim(cfg)
    q = int(cfg["num_attention_heads"]) * dh
    kv = int(cfg["num_key_value_heads"]) * dh
    return d * q + 2 * d * kv + q * d + 3 * d * f


def matmul_params(cfg: Dict) -> int:
    """Every weight a token is multiplied through: the blocks and the
    output head. The embedding table is a lookup, not a multiplication,
    so an untied table is left out (a tied one is the head)."""
    head = int(cfg["hidden_size"]) * int(cfg["vocab_size"])
    return int(cfg["num_hidden_layers"]) * layer_matmul_params(cfg) + head


def total_params(cfg: Dict) -> int:
    d, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    norms = int(cfg["num_hidden_layers"]) * 2 * d + d
    embed = 0 if cfg.get("tie_word_embeddings") else d * v
    return matmul_params(cfg) + embed + norms


def attended_pairs(doc_len: int, window: int | None) -> int:
    """(query, key) pairs causal attention needs inside one document of
    ``doc_len`` tokens: query p sees min(p + 1, window) keys."""
    n = int(doc_len)
    w = int(window) if window else n
    if n <= w:
        return n * (n + 1) // 2
    return w * (w + 1) // 2 + (n - w) * w


def train_flops_per_token(cfg: Dict, doc_lengths: Iterable[int]) -> float:
    """Forward plus backward operations per document token: 6 per matmul
    weight (2 forward, 4 backward) and, for attention, QK^T and PV at
    2 * heads * head_dim operations per (query, key) pair each, forward
    once and backward twice, averaged over the documents' tokens. No
    recomputation, no embedding lookup, no work on padding."""
    lengths = [int(n) for n in doc_lengths]
    pairs = sum(attended_pairs(n, cfg.get("sliding_window")) for n in lengths)
    per_pair = (12 * int(cfg["num_attention_heads"]) * head_dim(cfg)
                * int(cfg["num_hidden_layers"]))
    return 6.0 * matmul_params(cfg) + per_pair * pairs / max(sum(lengths), 1)


def kv_bytes_per_token(cfg: Dict, kv_dtype_bytes: int = 2) -> int:
    """Bytes of keys and values one cached token holds over all layers."""
    return (2 * int(cfg["num_key_value_heads"]) * head_dim(cfg)
            * kv_dtype_bytes * int(cfg["num_hidden_layers"]))


def decode_step_bytes(cfg: Dict, live_context_tokens: float,
                      weight_dtype_bytes: int = 2) -> float:
    """Least bytes one decode step must read: every matmul weight once
    (the batch shares them) plus the keys and values of the tokens the
    running slots hold. Activations and the one new column written are
    negligible beside them. Bandwidth bounds a decode step: at 16 slots
    its operations (2 per weight per slot) take a fifteenth of the time
    its bytes take on a v5e."""
    weights = matmul_params(cfg) * weight_dtype_bytes
    return weights + live_context_tokens * kv_bytes_per_token(cfg)
