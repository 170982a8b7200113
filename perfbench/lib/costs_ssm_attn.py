"""Parameters, operations and bytes of a decoder of Mamba-1 layers (dt, B
and C RMS-normed) beside plain grouped- / multi-query attention layers that
each keep rows of their own (Hugging Face ``jamba`` at ``num_experts`` 1),
computed from the shapes in a configuration file. Kept with the benchmark,
beside ``costs.py``: a PR that claims a gain may not change what its work
is divided by."""
from __future__ import annotations

from typing import Dict

#: Elementwise float32 operations a v5e TensorCore's vector unit can issue
#: a second. NOT a published figure: 8 sublanes x 128 lanes x 4 vector ALU
#: slots a cycle at the clock the published matmul peak implies (197e12 /
#: (4 MXUs x 128 x 128 x 2) = 1.503 GHz). Taken high on purpose: a higher
#: peak gives a shorter least time and a lower share, never one over 100%.
VPU_F32_OPS_PER_S = 8 * 128 * 4 * 197e12 / (4 * 128 * 128 * 2)

#: elementwise operations one token costs the recurrence per (channel,
#: state) element: dt * A, exp, dt * x * B (2), decay * S + add (2),
#: S * C and its sum over the state axis (2)
SCAN_OPS_PER_ELEMENT = 8


def _sizes(cfg: Dict):
    d = int(cfg["hidden_size"])
    n = int(cfg["mamba_d_state"])
    k = int(cfg["mamba_d_conv"])
    di = int(cfg["mamba_expand"]) * d
    rank = cfg.get("mamba_dt_rank", "auto")
    r = -(-d // 16) if rank == "auto" else int(rank)
    return d, n, k, di, r


def layer_counts(cfg: Dict) -> Dict[str, int]:
    """How many layers of each kind: ``ssm``, ``attention`` (the layout of
    ``reference/jamba_block.py``)."""
    period = int(cfg["attn_layer_period"])
    offset = int(cfg["attn_layer_offset"])
    attn = sum(1 for l in range(int(cfg["num_hidden_layers"]))
               if l % period == offset)
    return {"ssm": int(cfg["num_hidden_layers"]) - attn, "attention": attn}


def mlp_params(cfg: Dict) -> int:
    return 3 * int(cfg["hidden_size"]) * int(cfg["intermediate_size"])


def _head_dim(cfg: Dict) -> int:
    return int(cfg["hidden_size"]) // int(cfg["num_attention_heads"])


def mixer_matmul_params(cfg: Dict) -> Dict[str, int]:
    """Weights of one mixer of each kind that a token is multiplied by."""
    d, n, _, di, r = _sizes(cfg)
    q = int(cfg["num_attention_heads"]) * _head_dim(cfg)
    kv = int(cfg["num_key_value_heads"]) * _head_dim(cfg)
    return {"ssm": d * 2 * di + di * (r + 2 * n) + r * di + di * d,
            "attention": d * (q + 2 * kv) + q * d}


def mixer_params(cfg: Dict) -> Dict[str, int]:
    """Every weight of one mixer of each kind: for the state-space mixer
    the convolution's taps and bias, dt's bias, A, D and the three inner
    norms beside its four matrices; the block's two RMSNorms are apart."""
    _, n, k, di, r = _sizes(cfg)
    matmul = mixer_matmul_params(cfg)
    return {"ssm": (matmul["ssm"] + k * di + di + di + di * n + di
                    + r + 2 * n),
            "attention": matmul["attention"]}


def total_params(cfg: Dict) -> int:
    """Every parameter: the blocks, the final norm and the embedding
    (which is the head too when tied)."""
    d, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    mixers, counts = mixer_params(cfg), layer_counts(cfg)
    blocks = sum(counts[kind] * (mixers[kind] + mlp_params(cfg) + 2 * d)
                 for kind in counts)
    head = 0 if cfg.get("tie_word_embeddings", True) else d * v
    return blocks + d + d * v + head


def kv_row_bytes(cfg: Dict, dtype_bytes: int = 2) -> int:
    """Keys and values one token takes in one attention layer."""
    return (2 * int(cfg["num_key_value_heads"]) * _head_dim(cfg)
            * dtype_bytes)


def state_bytes_per_slot(cfg: Dict, dtype_bytes: int = 2) -> int:
    """The recurrent state one sequence keeps over every state-space
    layer: float32 [d_inner, N] and the convolution's last inputs."""
    _, n, k, di, _ = _sizes(cfg)
    return layer_counts(cfg)["ssm"] * (di * n * 4 + (k - 1) * di * dtype_bytes)


def decode_step_bytes(cfg: Dict, live_context_tokens: float, slots: float,
                      dtype_bytes: int = 2) -> float:
    """Least bytes one decode step must move: every weight once (the
    batch shares them; the tied embedding is the head), each attention
    layer's rows of the tokens the running slots hold, and the running
    slots' recurrent state read and written. Bandwidth bounds the step:
    at 16 slots a weight is used 16 times."""
    return (total_params(cfg) * dtype_bytes
            + live_context_tokens * kv_row_bytes(cfg, dtype_bytes)
            * layer_counts(cfg)["attention"]
            + 2 * slots * state_bytes_per_slot(cfg, dtype_bytes))


def chunk_flops(cfg: Dict, tokens: float, context: float) -> float:
    """Matmul operations one prefill chunk needs: ``tokens`` real tokens
    through every layer's matrices, their attention against the
    ``context`` tokens cached before the chunk and causally against each
    other, and the head for the one row whose logits leave."""
    counts, matmul = layer_counts(cfg), mixer_matmul_params(cfg)
    per_token = sum(counts[kind] * (matmul[kind] + mlp_params(cfg))
                    for kind in counts)
    q = int(cfg["num_attention_heads"]) * _head_dim(cfg)
    attended = context + (tokens + 1) / 2.0
    return (2.0 * tokens * per_token
            + counts["attention"] * 4.0 * tokens * q * attended
            + 2.0 * int(cfg["hidden_size"]) * int(cfg["vocab_size"]))


def chunk_bytes(cfg: Dict, context: float, dtype_bytes: int = 2) -> float:
    """Least bytes one prefill chunk must move: every weight once, the
    slot's cached rows in each attention layer, and the slot's recurrent
    state read and written."""
    return (total_params(cfg) * dtype_bytes
            + context * kv_row_bytes(cfg, dtype_bytes)
            * layer_counts(cfg)["attention"]
            + 2 * state_bytes_per_slot(cfg, dtype_bytes))


def scan_chunk_bytes(cfg: Dict, tokens: float, dtype_bytes: int = 2) -> float:
    """Least bytes the selective scans of one chunk must move, every
    state-space layer: x, dt, B and C in and y out at the activation
    dtype, and the layer's state read and written once."""
    _, n, _, di, _ = _sizes(cfg)
    return layer_counts(cfg)["ssm"] * (
        tokens * (3 * di + 2 * n) * dtype_bytes + 2 * di * n * 4)


def scan_chunk_ops(cfg: Dict, tokens: float) -> float:
    """Elementwise float32 operations the recurrences of one chunk need
    (``SCAN_OPS_PER_ELEMENT`` a token a state element)."""
    _, n, _, di, _ = _sizes(cfg)
    return layer_counts(cfg)["ssm"] * tokens * di * n * SCAN_OPS_PER_ELEMENT


def scan_chunk_least_seconds(cfg: Dict, tokens: float, peaks: Dict,
                             dtype_bytes: int = 2) -> Dict[str, float]:
    """The two bounds on the scans of one chunk, in seconds: ``hbm`` and
    ``vpu``; the larger holds (at d_inner 5,120 and N 16 it is ``vpu``:
    each number read feeds 16 state elements)."""
    return {"hbm": scan_chunk_bytes(cfg, tokens, dtype_bytes)
            / peaks["hbm_bytes_per_s"],
            "vpu": scan_chunk_ops(cfg, tokens) / VPU_F32_OPS_PER_S}
