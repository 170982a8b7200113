"""Parameters and bytes of a SambaY decoder (Hugging Face ``phi4flash``:
Mamba-1 layers, window and full differential attention, gated memory
units, cross-attention onto one layer's cache), computed from the shapes
in a configuration file. Kept with the benchmark, beside ``costs.py``: a
PR that claims a gain may not change what its work is divided by."""
from __future__ import annotations

from typing import Dict

#: the state-space sizes a ``phi4flash`` file leaves to its config
#: class's defaults (the configuration file lists them under ``assumed``)
MAMBA_DEFAULTS = {"mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2}


def _sizes(cfg: Dict):
    d = int(cfg["hidden_size"])
    n = int(cfg.get("mamba_d_state", MAMBA_DEFAULTS["mamba_d_state"]))
    k = int(cfg.get("mamba_d_conv", MAMBA_DEFAULTS["mamba_d_conv"]))
    di = int(cfg.get("mamba_expand", MAMBA_DEFAULTS["mamba_expand"])) * d
    rank = cfg.get("mamba_dt_rank", "auto")
    r = -(-d // 16) if rank == "auto" else int(rank)
    return d, n, k, di, r


def layer_counts(cfg: Dict) -> Dict[str, int]:
    """How many layers of each kind: ``ssm``, ``window``, ``full``,
    ``gmu``, ``cross`` (the layout of ``reference/phi4flash_block.py``)."""
    n = int(cfg["num_hidden_layers"])
    every = int(cfg.get("mb_per_layer", 2))
    half = n // 2
    out = {"ssm": 0, "window": 0, "full": 0, "gmu": 0, "cross": 0}
    for l in range(n):
        ssm_pos = l % every == 0
        if l <= half:
            out["ssm" if ssm_pos else "window"] += 1
        elif l == half + 1:
            out["full"] += 1
        else:
            out["gmu" if ssm_pos else "cross"] += 1
    return out


def mlp_params(cfg: Dict) -> int:
    return 3 * int(cfg["hidden_size"]) * int(cfg["intermediate_size"])


def mixer_params(cfg: Dict) -> Dict[str, int]:
    """Weights of one mixer of each kind, biases, lambdas and the
    sub-norm included; the block's two LayerNorms are apart."""
    d, n, k, di, r = _sizes(cfg)
    heads, kheads = (int(cfg["num_attention_heads"]),
                     int(cfg["num_key_value_heads"]))
    dh = d // heads
    q, kv = heads * dh, kheads * dh
    diff = 4 * dh + 2 * dh                    # four lambdas, the sub-norm
    attn = d * (q + 2 * kv) + (q + 2 * kv) + q * d + d + diff
    return {
        "ssm": (d * 2 * di + k * di + di + di * (r + 2 * n) + r * di + di
                + di * n + di + di * d),
        "window": attn, "full": attn,
        "gmu": 2 * d * di,
        "cross": d * q + q + q * d + d + diff}


def total_params(cfg: Dict) -> int:
    """Every parameter: the blocks, the final norm and the embedding
    (which is the head too when tied)."""
    d, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    mixers, counts = mixer_params(cfg), layer_counts(cfg)
    blocks = sum(counts[kind] * (mixers[kind] + mlp_params(cfg) + 4 * d)
                 for kind in counts)
    head = 0 if cfg.get("tie_word_embeddings", True) else d * v
    return blocks + 2 * d + d * v + head


def kv_row_bytes(cfg: Dict, dtype_bytes: int = 2) -> int:
    """Keys and values one token takes in one attention layer."""
    dh = int(cfg["hidden_size"]) // int(cfg["num_attention_heads"])
    return 2 * int(cfg["num_key_value_heads"]) * dh * dtype_bytes


def state_bytes_per_slot(cfg: Dict, dtype_bytes: int = 2) -> int:
    """The recurrent state one sequence keeps over every state-space
    layer: float32 [d_inner, N] and the convolution's last inputs."""
    _, n, k, di, _ = _sizes(cfg)
    return layer_counts(cfg)["ssm"] * (di * n * 4 + (k - 1) * di * dtype_bytes)


def shared_readers(cfg: Dict) -> int:
    """Layers that read the full layer's keys and values: itself and the
    cross layers."""
    counts = layer_counts(cfg)
    return counts["full"] + counts["cross"]


def decode_step_bytes(cfg: Dict, live_context_tokens: float,
                      window_tokens: float, slots: float,
                      dtype_bytes: int = 2) -> float:
    """Least bytes one decode step must move: every weight once (the
    batch shares them; the tied embedding is the head), the full layer's
    rows of the tokens the running slots hold, once for each layer that
    reads them, each window layer's rows inside the window
    (``window_tokens``: sum over the running slots of min(length,
    window)), and the running slots' recurrent state read and written.
    Bandwidth bounds the step: at 64 slots a weight is used 64 times, a
    quarter of what a v5e needs to be compute-bound."""
    row = kv_row_bytes(cfg, dtype_bytes)
    return (total_params(cfg) * dtype_bytes
            + live_context_tokens * row * shared_readers(cfg)
            + window_tokens * row * layer_counts(cfg)["window"]
            + 2 * slots * state_bytes_per_slot(cfg, dtype_bytes))
