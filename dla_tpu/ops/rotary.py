"""Rotary position embeddings (RoPE), LLaMA convention.

Angles are computed on the fly from integer positions — no precomputed
[max_len, dim] table to keep in HBM, and decode-step positions can be
dynamic values inside a jitted loop.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp


def validate_rope_scaling(scaling: Optional[Dict[str, Any]]
                          ) -> Optional[Dict[str, Any]]:
    """Normalize an HF ``rope_scaling`` dict: None/default-type -> None,
    supported types pass through, anything else raises. The single
    source of truth for what _scale_inv_freq implements — importers call
    this instead of keeping their own whitelist."""
    if not scaling:
        return None
    rope_type = str(scaling.get("rope_type")
                    or scaling.get("type") or "default").lower()
    if rope_type in ("default", "none"):
        return None
    if rope_type == "su":  # phi-3's pre-release name for longrope
        rope_type = "longrope"
    if rope_type not in ("llama3", "linear", "yarn", "longrope",
                         "dynamic"):
        raise NotImplementedError(
            f"rope_scaling type '{rope_type}' is not supported "
            "(implemented: llama3, linear, yarn, longrope, dynamic — "
            "the full HF ROPE_INIT_FUNCTIONS family)")
    out = dict(scaling)
    out["rope_type"] = rope_type   # normalized: consumers read ONE key
    out.pop("type", None)
    return out


def yarn_mscale(factor: float, m: float = 1.0) -> float:
    """YaRN's attention temperature 0.1 * m * ln(factor) + 1 (1 at or
    under factor 1): cos / sin carry mscale(m = mscale) / mscale(m =
    mscale_all_dim); latent attention's softmax scale carries the square
    of the latter (models/transformer.py)."""
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def _scale_inv_freq(inv_freq: jnp.ndarray, scaling: Dict[str, Any],
                    head_dim: int, theta: float
                    ) -> Tuple[jnp.ndarray, float]:
    """Frequency remapping for extended-context checkpoints. Returns
    (scaled inv_freq, attention scale multiplier for cos/sin).

    ``llama3`` (llama-3.1/3.2, HF modeling_rope_utils
    _compute_llama3_parameters): wavelengths shorter than the
    high-frequency cutoff keep their frequency, longer than the
    low-frequency cutoff divide by ``factor``, and the band between
    interpolates smoothly. ``linear`` divides every frequency by
    ``factor`` (position-interpolation scaling). ``yarn`` (qwen2.5-1M
    and friends, HF _compute_yarn_parameters): NTK-by-parts — dims
    whose full rotations at the ORIGINAL context exceed ``beta_fast``
    extrapolate (unchanged), dims below ``beta_slow`` interpolate
    (divide by factor), a linear ramp blends the band between; cos/sin
    additionally scale by ``attention_factor`` (default
    0.1*ln(factor)+1), the YaRN temperature on attention entropy.
    """
    rope_type = scaling["rope_type"]  # normalized by validate_rope_scaling
    factor = float(scaling.get("factor", 1.0))
    if rope_type == "linear":
        return inv_freq / factor, 1.0
    if rope_type == "yarn":
        # mirrors HF modeling_rope_utils._compute_yarn_parameters
        # key for key (incl. mscale/mscale_all_dim, truncate, and the
        # `or`-style beta defaults); parity pinned against
        # ROPE_INIT_FUNCTIONS["yarn"] in tests/test_qwen2_import.py
        beta_fast = float(scaling.get("beta_fast") or 32.0)
        beta_slow = float(scaling.get("beta_slow") or 1.0)
        if "original_max_position_embeddings" not in scaling:
            # HF falls back to the MODEL's max_position_embeddings,
            # which this op cannot see — the HF importer injects it
            # (hf_import._validated_rope_scaling); a hand-built config
            # must carry it explicitly rather than get a silent guess
            raise ValueError(
                "yarn rope_scaling needs original_max_position_"
                "embeddings (the HF importer injects the checkpoint's "
                "max_position_embeddings when the dict omits it)")
        old_ctx = float(scaling["original_max_position_embeddings"])

        attn = scaling.get("attention_factor")
        if attn is None:
            mscale = scaling.get("mscale")
            mscale_all = scaling.get("mscale_all_dim")
            if mscale and mscale_all:
                attn = float(yarn_mscale(factor, mscale)
                             / yarn_mscale(factor, mscale_all))
            else:
                attn = yarn_mscale(factor)
        else:
            attn = float(attn)

        def correction_dim(n_rot: float) -> float:
            # the (fractional) dim index whose wavelength completes
            # n_rot rotations over the original context
            return (head_dim
                    * math.log(old_ctx / (n_rot * 2.0 * math.pi))
                    / (2.0 * math.log(theta)))

        low = correction_dim(beta_fast)
        high = correction_dim(beta_slow)
        if scaling.get("truncate", True):
            low, high = math.floor(low), math.ceil(high)
        low, high = max(low, 0), min(high, head_dim - 1)
        if low == high:
            high += 0.001  # HF's degenerate-ramp guard
        ramp = (jnp.arange(head_dim // 2, dtype=jnp.float32) - low) \
            / (high - low)
        extrap_mask = 1.0 - jnp.clip(ramp, 0.0, 1.0)
        scaled = (inv_freq / factor * (1.0 - extrap_mask)
                  + inv_freq * extrap_mask)
        return scaled, attn
    # validate_rope_scaling is the one whitelist; anything else reaching
    # here is a programming error, not a user-config error
    assert rope_type == "llama3", rope_type
    low = float(scaling.get("low_freq_factor", 1.0))
    high = float(scaling.get("high_freq_factor", 4.0))
    old_ctx = float(scaling.get("original_max_position_embeddings", 8192))
    wavelen = 2.0 * math.pi / inv_freq
    smooth = (old_ctx / wavelen - low) / (high - low)
    interpolated = ((1.0 - smooth) * inv_freq / factor
                    + smooth * inv_freq)
    out = jnp.where(wavelen > old_ctx / low, inv_freq / factor,
                    interpolated)
    return jnp.where(wavelen < old_ctx / high, inv_freq, out), 1.0


def _longrope_inv_freq(inv_freq: jnp.ndarray, scaling: Dict[str, Any],
                       positions: jnp.ndarray
                       ) -> Tuple[jnp.ndarray, float]:
    """LongRoPE (phi-3 128k, HF _compute_longrope_parameters +
    longrope_frequency_update): per-dim rescale factor LISTS, the short
    list while max(position)+1 <= original context and the long list
    beyond — a TRACED select, matching HF's dynamic frequency update
    (their switch mid-generation and ours agree). cos/sin scale by
    attention_factor (default sqrt(1 + ln(factor)/ln(original_ctx)))."""
    if "original_max_position_embeddings" not in scaling:
        raise ValueError(
            "longrope rope_scaling needs original_max_position_"
            "embeddings (the HF importer injects it from the "
            "checkpoint's top-level config)")
    orig = int(scaling["original_max_position_embeddings"])
    half = inv_freq.shape[0]
    if "short_factor" not in scaling or "long_factor" not in scaling:
        raise ValueError("longrope rope_scaling needs short_factor and "
                         "long_factor per-dim rescale lists")
    short = jnp.asarray(scaling["short_factor"], jnp.float32)
    long = jnp.asarray(scaling["long_factor"], jnp.float32)
    if short.shape != (half,) or long.shape != (half,):
        raise ValueError(
            f"longrope factor lists must have rotary_dim/2 = {half} "
            f"entries, got short {short.shape} long {long.shape}")
    factor = float(scaling.get("factor") or 1.0)
    attn = scaling.get("attention_factor")
    if attn is None:
        attn = 1.0 if factor <= 1.0 else \
            math.sqrt(1.0 + math.log(factor) / math.log(orig))
    seq_len = jnp.max(positions) + 1
    ext = jnp.where(seq_len > orig, long, short)
    return inv_freq / ext, float(attn)


def _dynamic_ntk_inv_freq(scaling: Dict[str, Any],
                          positions: jnp.ndarray, head_dim: int,
                          theta: float) -> jnp.ndarray:
    """Dynamic NTK scaling (HF _compute_dynamic_ntk_parameters +
    dynamic_rope_update): the wavelength base stretches continuously
    once the current sequence exceeds the trained context —
    base' = base * ((factor * seq / max_pos) - (factor - 1))^(d/(d-2)),
    with seq = max(max(position)+1, max_pos), a TRACED quantity (below
    the trained context the multiplier is exactly 1). attention scale
    is unused for this type."""
    if "max_position_embeddings" not in scaling:
        raise ValueError(
            "dynamic rope_scaling needs max_position_embeddings (the "
            "HF importer injects it from the checkpoint config)")
    max_pos = float(scaling["max_position_embeddings"])
    factor = float(scaling["factor"])
    seq = jnp.maximum(jnp.max(positions).astype(jnp.float32) + 1.0,
                      max_pos)
    base = theta * ((factor * seq / max_pos) - (factor - 1.0)) \
        ** (head_dim / (head_dim - 2.0))
    return 1.0 / (base ** (jnp.arange(0, head_dim, 2,
                                      dtype=jnp.float32) / head_dim))


def rotary_angles(positions: jnp.ndarray, head_dim: int,
                  theta: float = 10000.0,
                  scaling: Optional[Dict[str, Any]] = None,
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """positions [..., T] int -> (cos, sin) each [..., T, head_dim//2], fp32.
    ``scaling``: HF ``rope_scaling`` dict (llama3 / linear / yarn /
    longrope / dynamic — the full HF family), see _scale_inv_freq /
    _longrope_inv_freq / _dynamic_ntk_inv_freq."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    scaling = validate_rope_scaling(scaling)  # the ONE whitelist
    attn_scale = 1.0
    if scaling:
        if scaling["rope_type"] == "longrope":
            inv_freq, attn_scale = _longrope_inv_freq(
                inv_freq, scaling, positions)
        elif scaling["rope_type"] == "dynamic":
            inv_freq = _dynamic_ntk_inv_freq(scaling, positions,
                                             head_dim, theta)
        else:
            inv_freq, attn_scale = _scale_inv_freq(inv_freq, scaling,
                                                   head_dim, theta)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., T, D/2]
    if attn_scale != 1.0:
        return jnp.cos(ang) * attn_scale, jnp.sin(ang) * attn_scale
    return jnp.cos(ang), jnp.sin(ang)


def position_query_scale(positions: jnp.ndarray,
                         scaling: Optional[Dict[str, Any]]
                         ) -> Optional[jnp.ndarray]:
    """The position-dependent query scale of ``llama_4_scaling_beta``
    (mistral4's ``rope_parameters``): the query at position p is
    multiplied by 1 + beta * ln(1 + floor(p / original context)), the
    identity below the original context. positions [..., T] -> fp32
    [..., T], or None where the dict has no such key."""
    beta = float((scaling or {}).get("llama_4_scaling_beta") or 0.0)
    if not beta:
        return None
    orig = float(scaling["original_max_position_embeddings"])
    return 1.0 + beta * jnp.log1p(
        jnp.floor(positions.astype(jnp.float32) / orig))


def apply_rotary(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
                 rotary_dim: int = 0, interleave: bool = False
                 ) -> jnp.ndarray:
    """x [B, T, H, D] with (cos, sin) [B, T, rd/2] (or broadcastable).

    ``interleave``: the rotated pairs are adjacent dims (x[2i], x[2i+1])
    (HF ``rope_interleave``, the DeepSeek / mistral4 checkpoints' layout)
    instead of (x[i], x[i + rd/2]). The result comes out in the
    split-halves order either way — queries and keys share the
    permutation, so every score is unchanged and nothing re-interleaves.

    Uses the split-halves convention (rotate_half), matching LLaMA /
    HF transformers so imported weights are numerically compatible.

    ``rotary_dim``: rotate only the first rd dims, pass the rest through —
    partial RoPE, the phi-family convention (HF partial_rotary_factor;
    cos/sin must then be built with rotary_angles(positions, rd, theta)).
    0 means full rotation.
    """
    d = x.shape[-1]
    if rotary_dim < 0 or rotary_dim > d:
        raise ValueError(f"rotary_dim {rotary_dim} out of range for head "
                         f"dim {d}")
    rd = rotary_dim or d
    rot, rest = x[..., :rd], x[..., rd:]
    d_half = rd // 2
    if interleave:
        x1, x2 = rot[..., 0::2], rot[..., 1::2]
    else:
        x1, x2 = rot[..., :d_half], rot[..., d_half:]
    cos = cos[..., None, :].astype(x.dtype)  # [B, T, 1, rd/2]
    sin = sin[..., None, :].astype(x.dtype)
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    if rd == d:
        return jnp.concatenate([out1, out2], axis=-1)
    return jnp.concatenate([out1, out2, rest], axis=-1)
