"""In-graph token sampling for the decode loop.

The reference samples through HF ``generate(temperature, top_p)``
(train_rlhf.py:123-124, generate_teacher_data.py:72-79,
eval_alignment.py:71-77). Here sampling is a pure jittable function of
(logits, rng) so the whole rollout stays on device.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs carried through ``ServingEngine.submit``.

    ``seed`` names the request's private PRNG stream: generated token k is
    drawn with ``fold_in(PRNGKey(seed), k)``, so the stream depends only on
    (seed, token index) — not on batch placement, slot assignment, or how
    many other requests are in flight. Eviction/recompute and supervisor
    replay therefore reproduce the identical continuation even for sampled
    requests.

    ``do_sample=False`` (or ``temperature == 0``) means greedy; both fold
    into an effective temperature of 0.0, which is the in-graph greedy
    switch in ``sample_token_per_row``.
    """

    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0
    seed: int = 0
    do_sample: bool = True

    @property
    def effective_temperature(self) -> float:
        if not self.do_sample:
            return 0.0
        return float(self.temperature)

    @classmethod
    def from_gen(cls, gen, seed: int) -> "SamplingParams":
        """Engine defaults for a request with no explicit override."""
        return cls(temperature=float(gen.temperature), top_p=float(gen.top_p),
                   top_k=int(gen.top_k), seed=int(seed) & 0xFFFFFFFF,
                   do_sample=bool(gen.do_sample))


def derive_request_seed(base_seed: int, rid: int) -> int:
    """Deterministic default seed for a request without an explicit
    ``SamplingParams``. Depends only on (engine seed, rid); rids are
    preserved across supervisor restarts (``restore(rid=...)``), so the
    default stream also survives replay."""
    return (int(base_seed) * 1000003 + int(rid) * 2654435761) & 0xFFFFFFFF


def derive_rollout_seeds(rollout_seed: int, n: int) -> np.ndarray:
    """Host-side per-row seeds for one rollout batch — shared by the
    serving-backed RolloutEngine and the seeded ``build_generate_fn`` path
    (identical inputs => identical streams => bit-identical rollouts)."""
    idx = np.arange(n, dtype=np.uint64)
    base = np.uint64(int(rollout_seed) & 0xFFFFFFFF)
    vals = (base * np.uint64(0x9E3779B1) + idx * np.uint64(0x85EBCA6B)
            ) & np.uint64(0xFFFFFFFF)
    return vals.astype(np.uint32)


def apply_temperature(logits: jnp.ndarray, temperature: float) -> jnp.ndarray:
    return logits / jnp.maximum(temperature, 1e-6)


def top_k_mask(logits: jnp.ndarray, k: int) -> jnp.ndarray:
    """Keep the k largest logits per row, NEG_INF elsewhere. Static k."""
    if k <= 0 or k >= logits.shape[-1]:
        return logits
    vals, _ = jax.lax.top_k(logits, k)
    cutoff = vals[..., -1:]
    return jnp.where(logits >= cutoff, logits, NEG_INF)


def top_p_mask(logits: jnp.ndarray, p: float) -> jnp.ndarray:
    """Nucleus filtering: keep the smallest prefix of the sorted distribution
    with cumulative probability >= p. Tokens outside get NEG_INF.

    Sort-based; [*, V] -> [*, V]. The token that crosses the threshold is
    kept (matching the usual HF semantics).
    """
    if p >= 1.0:
        return logits
    sort_idx = jnp.argsort(logits, axis=-1)[..., ::-1]
    sorted_logits = jnp.take_along_axis(logits, sort_idx, axis=-1)
    sorted_probs = jax.nn.softmax(sorted_logits.astype(jnp.float32), axis=-1)
    cum = jnp.cumsum(sorted_probs, axis=-1)
    # drop tokens whose *preceding* cumulative mass already reached p
    drop_sorted = (cum - sorted_probs) >= p
    keep_sorted = ~drop_sorted
    inv = jnp.argsort(sort_idx, axis=-1)
    keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
    return jnp.where(keep, logits, NEG_INF)


def filtered_probs(
    logits: jnp.ndarray,  # [..., V]
    *,
    temperature: float = 1.0,
    top_p: float = 1.0,
    top_k: int = 0,
    do_sample: bool = True,
) -> jnp.ndarray:
    """The probability vector ``sample_token`` draws from, materialized:
    softmax of the temperature/top-k/top-p-filtered logits — or a
    one-hot at the argmax for greedy decoding (so speculative
    decoding's accept ratio p/q and residual max(p-q, 0) cover greedy
    and sampling with ONE rule). fp32 [..., V], rows sum to 1."""
    logits = logits.astype(jnp.float32)
    if not do_sample or temperature == 0.0:
        return jax.nn.one_hot(jnp.argmax(logits, axis=-1),
                              logits.shape[-1], dtype=jnp.float32)
    logits = apply_temperature(logits, temperature)
    logits = top_k_mask(logits, top_k)
    logits = top_p_mask(logits, top_p)
    return jax.nn.softmax(logits, axis=-1)


def sample_token(
    rng: jax.Array,
    logits: jnp.ndarray,  # [B, V]
    *,
    temperature: float = 1.0,
    top_p: float = 1.0,
    top_k: int = 0,
    do_sample: bool = True,
) -> jnp.ndarray:
    """One sampling step -> [B] int32 token ids. All filters static."""
    logits = logits.astype(jnp.float32)
    if not do_sample or temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = apply_temperature(logits, temperature)
    logits = top_k_mask(logits, top_k)
    logits = top_p_mask(logits, top_p)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def filter_logits_per_row(
    logits: jnp.ndarray,   # [B, V]
    temps: jnp.ndarray,    # [B] f32, <= 0 rows are greedy (filter unused)
    top_ps: jnp.ndarray,   # [B] f32
    top_ks: jnp.ndarray,   # [B] i32, <= 0 disables top-k for the row
) -> jnp.ndarray:
    """Temperature/top-k/top-p filtering with PER-ROW traced parameters.

    One descending sort serves both filters: top-k keeps sorted rank
    < k, top-p then keeps the smallest prefix of the top-k-renormalized
    distribution reaching p (the same ``(cum - probs) < p`` rule — and the
    same k-then-p composition — as the static ``top_k_mask``/``top_p_mask``
    pipeline). Traced k and p mean every request in a decode batch can
    carry its own knobs without retracing — the decode compile count stays
    pinned at 1.

    Both sorts carry their payload: the first yields the sorted values
    with the permutation (a stable ascending sort, reversed — the order
    of ``argsort(x)[..., ::-1]``, ties included), the second, keyed on
    that permutation, brings the keep mask back to vocabulary order. A
    ``take_along_axis`` over [B, V] instead is a scalar-indexed gather
    of B*V elements, several times a sort's cost on the TPU.
    """
    x = logits.astype(jnp.float32) / jnp.maximum(temps, 1e-6)[:, None]
    v = x.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    sorted_x, sort_idx = jax.lax.sort((x, iota), dimension=1,
                                      is_stable=True, num_keys=1)
    sorted_x, sort_idx = sorted_x[:, ::-1], sort_idx[:, ::-1]
    ranks = jnp.arange(v, dtype=jnp.int32)[None, :]
    keep_k = (ranks < top_ks[:, None]) | (top_ks[:, None] <= 0)
    sorted_probs = jax.nn.softmax(jnp.where(keep_k, sorted_x, NEG_INF),
                                  axis=-1)
    cum = jnp.cumsum(sorted_probs, axis=-1)
    keep_p = (cum - sorted_probs) < top_ps[:, None]
    keep_sorted = keep_p & keep_k
    _, keep = jax.lax.sort((sort_idx, keep_sorted), dimension=1,
                           is_stable=False, num_keys=1)
    return jnp.where(keep, x, NEG_INF)


def sample_token_per_row(
    seeds: jnp.ndarray,      # [B] uint32 per-request seeds
    positions: jnp.ndarray,  # [B] i32 generated-token index (0 = first)
    logits: jnp.ndarray,     # [B, V]
    temps: jnp.ndarray,      # [B] f32 effective temperature (<= 0 = greedy)
    top_ps: jnp.ndarray,     # [B] f32
    top_ks: jnp.ndarray,     # [B] i32
):
    """Per-row sampled/greedy next token + chosen-token logprob.

    Row i draws with ``fold_in(PRNGKey(seeds[i]), positions[i])`` where the
    position is the generated-token index, so the stream is a pure function
    of (seed, k): independent of batch placement, restarts and evictions.
    The returned logprob is ``log_softmax`` of the RAW fp32 logits at the
    chosen token — the model's actual distribution, not the
    filtered/tempered one — so greedy logps match a recomputed forward
    pass and the values are usable as behavior-policy logps downstream.

    The vocabulary-wide filter and draw run only for a batch that holds
    a sampling row: one ``lax.cond`` on ``any(temps > 0)``, a traced
    predicate (no host sync, one program), whose other branch is the
    arg-max alone. Callers zero the temperature of rows that are not
    running, keep this call out of ``vmap`` (there a cond lowers to a
    select and both branches run) and inside a ``jit`` (bound eagerly,
    the cond compiles at every call).

    Returns ``(tokens [B] int32, logps [B] float32)``.
    """
    raw = logits.astype(jnp.float32)
    logp_all = jax.nn.log_softmax(raw, axis=-1)

    greedy = jnp.argmax(raw, axis=-1).astype(jnp.int32)

    def draw_rows():
        filt = filter_logits_per_row(raw, temps, top_ps, top_ks)

        def draw(seed, position, row):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), position)
            return jax.random.categorical(key, row)

        sampled = jax.vmap(draw)(seeds, positions, filt)
        return jnp.where(temps <= 0.0, greedy, sampled.astype(jnp.int32))

    tok = jax.lax.cond(jnp.any(temps > 0.0), draw_rows, lambda: greedy)
    logp = jnp.take_along_axis(logp_all, tok[:, None], axis=-1)[:, 0]
    return tok, logp


def sample_token_block(
    seeds: jnp.ndarray,       # [B] uint32 per-request seeds
    positions0: jnp.ndarray,  # [B] i32 generated-token index of column 0
    logits: jnp.ndarray,      # [B, G, V] one distribution per block column
    temps: jnp.ndarray,       # [B] f32 effective temperature (<= 0 = greedy)
    top_ps: jnp.ndarray,      # [B] f32
    top_ks: jnp.ndarray,      # [B] i32
):
    """Block form of ``sample_token_per_row``: column g of row i draws at
    generated-token index ``positions0[i] + g`` with row i's seed and
    filter knobs. Every op in the per-row sampler is row-wise, so
    flattening [B, G] -> [B*G] and delegating produces bit-identical
    draws to G successive single-token calls — the property that lets a
    speculative verify step emit the exact tokens the non-speculative
    engine would have, regardless of how many tokens each round accepts.

    Returns ``(tokens [B, G] int32, logps [B, G] float32)``.
    """
    b, g, v = logits.shape
    offs = jnp.arange(g, dtype=jnp.int32)[None, :]
    flat_pos = (positions0[:, None] + offs).reshape(b * g)
    rep = lambda x: jnp.repeat(x, g, axis=0)  # noqa: E731 — row broadcast
    tok, logp = sample_token_per_row(
        rep(seeds), flat_pos, logits.reshape(b * g, v),
        rep(temps), rep(top_ps), rep(top_ks))
    return tok.reshape(b, g), logp.reshape(b, g)
