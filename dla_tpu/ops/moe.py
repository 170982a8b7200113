"""Mixture-of-Experts MLP with expert parallelism over the ``expert``
mesh axis.

Beyond-reference capability: the reference is dense-only (SURVEY.md sec
2.3 EP row) and this framework had reserved the mesh axis without using
it. This is the GShard/Mixtral TPU recipe — everything is einsum, so
GSPMD shards the expert dim and inserts the all-to-alls:

- router: logits [B, T, E] from a [D, E] projection; top-k softmax over
  the selected experts' logits (Mixtral normalization);
- GShard token grouping: the sequence folds into groups of at most
  ``group_size`` tokens (groups ride the batch dim), so the dispatch
  tensor is [rows, G, E, Cg] with Cg = ceil(k * G / E * cf) — O(T) total
  memory and dispatch FLOPs instead of the O(T^2) a whole-sequence
  capacity would cost at 32k context;
- capacity dispatch: within each group, each expert takes at most Cg
  tokens; a one-hot dispatch tensor built from a cumulative position
  count routes token -> (expert, slot). Tokens over capacity are DROPPED
  (standard GShard behavior): they contribute nothing here and ride the
  residual connection. Padding tokens (``valid`` = 0) never claim a
  slot and are excluded from the router statistics;
- expert FFN: gated-SiLU like the dense block, batched over experts with
  weights [E, D, F] whose expert dim is sharded over the mesh's
  ``expert`` axis — the dispatch/return einsums become all-to-alls on
  TPU;
- combine: weighted sum of expert outputs back to [B, T, D] with the
  top-k router weights;
- aux losses: switch-style load-balance loss (mean fraction x mean
  router prob per expert, scaled by E) and router z-loss, returned for
  the trainer to weight in.

Static shapes throughout (C is computed from static T/E/k), scan/remat
friendly, composes with fsdp/model sharding on the non-expert dims.

That is the TRAINING path (``moe_mlp``). Serving routes droplessly
(``moe_mlp_dropless``): a dropped token is a wrong answer there, and no
reference can match a capacity that depends on the batch. Both take a
share of the experts the router scores (``first`` .. ``first + E_held -
1``): the per-shard body of expert parallelism, which on one chip runs
without its exchange; choices landing on experts held elsewhere add
nothing here. Shared experts are the caller's (a dense gated MLP beside
the routed sum, ``Transformer._mlp``).
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Params = Dict[str, jnp.ndarray]


class MoEAux(NamedTuple):
    load_balance: jnp.ndarray   # scalar, switch-style balance loss
    router_z: jnp.ndarray       # scalar, router logit z-loss
    dropped_frac: jnp.ndarray   # scalar, fraction of token-slots dropped


def expert_capacity(t: int, n_experts: int, k: int,
                    capacity_factor: float) -> int:
    return max(1, math.ceil(t * k / n_experts * capacity_factor))


def _constrain(x, spec):
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x


def _fit_group(t: int, group_size: int) -> int:
    """Largest divisor of t that is <= group_size (t itself when small)."""
    g = min(t, group_size)
    while g > 1 and t % g:
        g -= 1
    return max(g, 1)


def moe_mlp(
    h: jnp.ndarray,              # [B, T, D] block input (post-norm)
    router_w: jnp.ndarray,       # [D, E]
    w_gate: jnp.ndarray,         # [E, D, F]
    w_up: jnp.ndarray,           # [E, D, F]
    w_down: jnp.ndarray,         # [E, F, D]
    *,
    k: int,
    capacity_factor: float = 1.25,
    valid: Optional[jnp.ndarray] = None,   # [B, T] 1 = real token
    group_size: int = 512,
    first: int = 0,
    routed_scale: float = 1.0,
) -> Tuple[jnp.ndarray, MoEAux]:
    """Routed gated-SiLU MLP with GShard capacity dispatch (the training
    path). Returns ([B, T, D] output, aux losses). The weights may hold a
    share of the experts the router scores: ids ``first .. first + E_held
    - 1``; a choice landing elsewhere adds nothing here."""
    b, t, d = h.shape
    g = _fit_group(t, group_size)
    rows = b * (t // g)
    h2 = h.reshape(rows, g, d)
    v2 = None if valid is None else valid.reshape(rows, g)
    out, aux = _moe_rows(h2, router_w, w_gate, w_up, w_down, k=k,
                         capacity_factor=capacity_factor, valid=v2,
                         first=first, routed_scale=routed_scale)
    return out.reshape(b, t, d), aux


def route_top_k(h: jnp.ndarray, router_w: jnp.ndarray, k: int,
                routed_scale: float = 1.0):
    """Router logits [..., E] in fp32 (fp32 accumulation of the
    activation-dtype product), the k chosen experts and their weights:
    softmax over the chosen logits, which IS the softmax over all E
    renormalised over the chosen (Mixtral's and mistral4's
    ``norm_topk_prob`` convention), times ``routed_scale``."""
    with jax.named_scope("moe_router"):
        logits = jnp.einsum("...d,de->...e", h, router_w.astype(h.dtype),
                            preferred_element_type=jnp.float32)
        top_l, top_e = jax.lax.top_k(logits, min(k, router_w.shape[1]))
        top_w = jax.nn.softmax(top_l, axis=-1)
        if routed_scale != 1.0:
            top_w = top_w * routed_scale
    return logits, top_e, top_w


def moe_mlp_dropless(
    h: jnp.ndarray,              # [B, T, D] block input (post-norm)
    router_w: jnp.ndarray,       # [D, E]    every expert the router scores
    w_gate: jnp.ndarray,         # [E_held, D, F]
    w_up: jnp.ndarray,           # [E_held, D, F]
    w_down: jnp.ndarray,         # [E_held, F, D]
    *,
    k: int,
    first: int = 0,
    routed_scale: float = 1.0,
    valid: Optional[jnp.ndarray] = None,   # [B, T] 1 = real token
    layer: Optional[jnp.ndarray] = None,   # traced index into stacked w_*
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Routed gated-SiLU MLP with no capacity: every (token, choice) that
    lands on a held expert is computed, whatever the imbalance — the
    serving paths' routine (a dropped token is a wrong answer there, and
    at a 512-token chunk over 128 experts GShard's capacity is 20).

    The (token, choice) pairs are sorted by expert (pairs of experts held
    elsewhere, and of pad tokens, sort last and belong to no group) and
    go through ``jax.lax.ragged_dot``, XLA:TPU's grouped matmul: it walks
    only the row tiles that hold a pair, so a decode step reads the
    weights of the experts its rows chose and no others. Rows past the
    last group come back undefined and are zeroed.

    ``layer``: the weights are every layer's, stacked [L, E_held, D, F],
    and this is block ``layer`` of a scan over them. The grouped matmul
    then takes the whole stack as L * E_held groups of which only this
    block's are not empty: a custom call cannot read a slice in place,
    and slicing a block's experts out first copies all of their weights
    every step (on a v5e, half of a decode step at 32 experts of 50 MB).

    Returns ([B, T, D], int32 [2] = (held experts that received a pair,
    pairs that landed here)): the counters behind ``experts_hit`` /
    ``expert_assignments``."""
    b, t, d = h.shape
    e_held = w_gate.shape[-3]
    x = h.reshape(b * t, d)
    _, top_e, top_w = route_top_k(x, router_w, k, routed_scale)
    kk = top_e.shape[-1]
    with jax.named_scope("moe_experts"):
        local = top_e - first
        here = (local >= 0) & (local < e_held)
        if valid is not None:
            here = here & (valid.reshape(b * t, 1) > 0)
        key = jnp.where(here, local, e_held).reshape(-1)     # [N*k]
        order = jnp.argsort(key, stable=True)
        group_sizes = jnp.sum(
            key[:, None] == jnp.arange(e_held, dtype=key.dtype)[None, :],
            axis=0, dtype=jnp.int32)                          # [E_held]
        landed = jnp.sum(group_sizes)
        in_group = (jnp.arange(key.shape[0]) < landed)[:, None]
        xs = jnp.take(x, order // kk, axis=0)                 # [N*k, D]
        sizes = group_sizes
        if layer is not None:
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((w_gate.shape[0] * e_held,), jnp.int32),
                group_sizes, (layer * e_held,))

        def grouped(rows, w):
            w = w.reshape((-1,) + w.shape[-2:]).astype(rows.dtype)
            return jax.lax.ragged_dot(rows, w, sizes)

        act = jnp.where(in_group, jax.nn.silu(grouped(xs, w_gate))
                        * grouped(xs, w_up), 0)
        ys = grouped(act, w_down)
        w_sorted = jnp.take(top_w.reshape(-1), order)[:, None]
        ys = jnp.where(in_group, ys * w_sorted.astype(ys.dtype), 0)
        # back to (token, choice) order, then sum a token's k choices
        out = jnp.take(ys, jnp.argsort(order), axis=0
                       ).reshape(b * t, kk, d).sum(axis=1)
        stats = jnp.stack([jnp.sum(group_sizes > 0, dtype=jnp.int32),
                           landed])
    return out.reshape(b, t, d), stats


def _moe_rows(h, router_w, w_gate, w_up, w_down, *, k, capacity_factor,
              valid, first=0, routed_scale=1.0):
    rows, g, d = h.shape
    e_all = router_w.shape[1]            # experts the router scores
    e = w_gate.shape[0]                  # experts held here (usually all)
    k = min(k, e_all)
    cap = expert_capacity(g, e_all, k, capacity_factor)
    v = (jnp.ones((rows, g), jnp.float32) if valid is None
         else valid.astype(jnp.float32))

    logits, top_e, top_w = route_top_k(h, router_w, k, routed_scale)
    probs = jax.nn.softmax(logits, axis=-1)                # [R, G, E]

    # slot assignment: position of this token among all (token, choice)
    # pairs routed to the same expert, counted in (choice-major, then
    # token) order so primary routes win capacity over secondary ones.
    # Padding tokens claim no slot at all (their one-hot is zeroed), so
    # they can never evict real tokens from an expert's capacity.
    # (an expert id outside the held range one-hots to all zeros)
    choice_onehot = (jax.nn.one_hot(top_e - first, e, dtype=jnp.int32)
                     * v[:, :, None, None].astype(jnp.int32))  # [R,G,k,E]
    flat = choice_onehot.transpose(0, 2, 1, 3).reshape(rows, k * g, e)
    pos_flat = jnp.cumsum(flat, axis=1) - flat                 # [R, k*G, E]
    pos = pos_flat.reshape(rows, k, g, e).transpose(0, 2, 1, 3)
    slot = jnp.sum(pos * choice_onehot, axis=-1)               # [R, G, k]
    keep = slot < cap

    # dispatch [R, G, E, C]: 1 where token (r, g) occupies expert slot
    disp = (choice_onehot[..., None].astype(h.dtype) *
            jax.nn.one_hot(slot, cap, dtype=h.dtype)[..., None, :]
            * keep[..., None, None].astype(h.dtype))           # [R,G,k,E,C]
    combine = jnp.sum(disp * top_w[..., None, None].astype(h.dtype), axis=2)
    disp = jnp.sum(disp, axis=2)                               # [R,G,E,C]

    # route tokens to expert buffers; expert dim sharded over `expert`
    expert_in = jnp.einsum("rgec,rgd->ercd", disp, h)          # [E,R,C,D]
    expert_in = _constrain(expert_in, P("expert", ("data", "fsdp"),
                                        None, None))
    gate = jax.nn.silu(jnp.einsum(
        "ercd,edf->ercf", expert_in, w_gate.astype(h.dtype)))
    up = jnp.einsum("ercd,edf->ercf", expert_in, w_up.astype(h.dtype))
    act = _constrain(gate * up, P("expert", ("data", "fsdp"), None,
                                  "model"))
    expert_out = jnp.einsum("ercf,efd->ercd", act,
                            w_down.astype(h.dtype))            # [E,R,C,D]
    expert_out = _constrain(expert_out, P("expert", ("data", "fsdp"),
                                          None, None))
    out = jnp.einsum("rgec,ercd->rgd", combine, expert_out)

    # aux over REAL tokens only: switch load-balance (fraction routed to
    # e * mean router prob of e, summed, scaled by E — minimized at
    # uniform) and z-loss on router logits
    n_real = jnp.maximum(jnp.sum(v), 1.0)
    primary = jax.nn.one_hot(top_e[..., 0], e_all, dtype=jnp.float32)
    frac = jnp.sum(primary * v[..., None], axis=(0, 1)) / n_real
    mean_prob = jnp.sum(probs * v[..., None], axis=(0, 1)) / n_real
    load_balance = e_all * jnp.sum(frac * mean_prob)
    router_z = jnp.sum(
        jax.nn.logsumexp(logits, axis=-1) ** 2 * v) / n_real
    dropped = 1.0 - jnp.sum(disp) / (k * n_real)
    return out, MoEAux(load_balance, router_z, dropped)
