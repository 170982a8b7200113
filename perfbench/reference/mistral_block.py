"""Plain reference of the Mistral decoder (arXiv 2310.06825; Hugging Face
``MistralForCausalLM``): pre-RMSNorm blocks, rotary embeddings in the
rotate-half convention, grouped-query attention under a causal sliding
window, SwiGLU, an untied output head. Straightforward ``jax.numpy`` in
float32 with ``matmul_precision "highest"``: no kernel, no cache, no
batching, one sequence at a time, and independent of ``dla_tpu``.

Weights come a layer at a time through a callable, in whatever type they
are stored, and are upcast here: the reference then fits beside a serving
engine, and on several chips the caller gathers one layer at a time.

Departure from the published model, stated: a packed row holds several
documents; attention is confined to a token's own document and positions
restart at each document, which is what training on the documents one by
one would compute.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, weight, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def _rope(x, positions, theta):
    """x [T, H, dh]; rotate-half: the first and second half of a head are
    the real and imaginary parts."""
    dh = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
    ang = positions.astype(F32)[:, None] * inv_freq[None, :]   # [T, dh/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "window", "eps", "theta"))
def _block(x, w: Dict, positions, segments, *, heads, kv_heads, window,
           eps, theta):
    with jax.default_matmul_precision("highest"):
        w = {k: v.astype(F32) for k, v in w.items()}
        t, _ = x.shape
        dh = w["wq"].shape[1] // heads
        h = _rms_norm(x, w["attn_norm"], eps)
        q = _rope((h @ w["wq"]).reshape(t, heads, dh), positions, theta)
        k = _rope((h @ w["wk"]).reshape(t, kv_heads, dh), positions, theta)
        v = (h @ w["wv"]).reshape(t, kv_heads, dh)
        group = heads // kv_heads
        q = q.reshape(t, kv_heads, group, dh)
        scores = jnp.einsum("qkgd,skd->kgqs", q, k) / jnp.sqrt(F32(dh))
        idx = jnp.arange(t)
        seen = idx[None, :] <= idx[:, None]                 # causal
        seen &= segments[None, :] == segments[:, None]      # own document
        if window:
            seen &= positions[:, None] - positions[None, :] < window
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("kgqs,skd->qkgd", probs, v).reshape(t, heads * dh)
        x = x + attn @ w["wo"]
        h = _rms_norm(x, w["mlp_norm"], eps)
        return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def document_positions(segments):
    """Positions that restart at each change of segment id."""
    t = segments.shape[0]
    idx = jnp.arange(t)
    start = jnp.concatenate(
        [jnp.ones((1,), bool), segments[1:] != segments[:-1]])
    return idx - jax.lax.cummax(jnp.where(start, idx, 0))


def hidden_states(tokens, embedding, layer: Callable[[int], Dict],
                  final_norm, cfg: Dict, segments=None):
    """[T] token ids -> [T, D] float32 after the final norm. ``layer(l)``
    gives block l's weights; ``cfg`` uses the Hugging Face key names."""
    tokens = jnp.asarray(tokens, jnp.int32)
    t = tokens.shape[0]
    segments = (jnp.ones((t,), jnp.int32) if segments is None
                else jnp.asarray(segments, jnp.int32))
    positions = document_positions(segments)
    x = jnp.take(embedding, tokens, axis=0).astype(F32)
    for l in range(int(cfg["num_hidden_layers"])):
        x = _block(x, layer(l), positions, segments,
                   heads=int(cfg["num_attention_heads"]),
                   kv_heads=int(cfg["num_key_value_heads"]),
                   window=int(cfg.get("sliding_window") or 0),
                   eps=float(cfg["rms_norm_eps"]),
                   theta=float(cfg["rope_theta"]))
    return _rms_norm(x, final_norm.astype(F32), float(cfg["rms_norm_eps"]))


@jax.jit
def logits(hidden_rows, lm_head):
    """[N, D] float32 rows -> [N, V] float32 logits."""
    with jax.default_matmul_precision("highest"):
        return hidden_rows @ lm_head.astype(F32)


def next_token_nll(hidden, lm_head, labels, ignore_index: int = -100):
    """(sum of -log p(label[t+1] | ..t), count) over the positions whose
    next label is not ``ignore_index``: the SFT objective's numerator and
    denominator for one row."""
    labels = jnp.asarray(labels, jnp.int32)
    logp = jax.nn.log_softmax(logits(hidden[:-1], lm_head), axis=-1)
    target = labels[1:]
    valid = target != ignore_index
    picked = jnp.take_along_axis(
        logp, jnp.where(valid, target, 0)[:, None], axis=-1)[:, 0]
    return -jnp.sum(jnp.where(valid, picked, 0.0)), jnp.sum(valid)
