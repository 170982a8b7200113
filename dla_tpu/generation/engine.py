"""Jitted autoregressive generation: prefill + fixed-length scan decode
over a preallocated KV cache, with in-graph temperature/top-p/top-k
sampling.

This is the TPU-native replacement for HF ``model.generate`` in all three
reference call sites: PPO rollouts (train_rlhf.py:123-124), teacher
sampling (generate_teacher_data.py:72-79), and evaluation
(eval_alignment.py:71-77). The whole rollout stays on device: no decode to
strings, no re-tokenization round-trip (the reference's host bounce,
SURVEY.md sec 3.3).

Design: prompts arrive right-padded to a static width P; decode is
static-shape throughout. With a real EOS id (the default for
RLHF/eval/teacher-gen) it runs a ``lax.while_loop`` that EXITS EARLY
once every row has finished — finished rows keep writing pad into
preallocated [N] buffers, so the outputs are bit-identical to the
fixed-length schedule (pinned by test). With ``eos_token_id < 0``
(bench/fixed-length paths) it runs a plain ``lax.scan`` of exactly
``max_new_tokens`` steps. Per-row true positions are tracked so rotary
phases match contiguous sequences; ``left_align`` compacts
[prompt pad gap response] rows into contiguous right-padded sequences for
downstream in-graph consumers (logprob, reward scoring).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from dla_tpu.models.transformer import Transformer
from dla_tpu.ops.sampling import sample_token, sample_token_per_row


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    """Mirrors the reference's generation_params / sampling blocks
    (config/rlhf_config.yaml:19-22, config/eval_config.yaml generation).

    ``early_exit_chunk``: 0 keeps the per-step early-exit while_loop;
    C > 0 runs a while_loop over CHUNKS of C scan steps instead —
    the inner loop gets lax.scan's tighter codegen (profile_decode
    measured the per-step while_loop ~14% slower per step on-chip)
    while early exit keeps a C-token granularity. Outputs are
    bit-identical to both other schedules (same pre-split rng keys
    indexed by absolute step; finished rows emit pad with a zero
    mask)."""
    max_new_tokens: int = 128
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0
    do_sample: bool = True
    eos_token_id: int = 2
    pad_token_id: int = 0
    early_exit_chunk: int = 0

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]], **defaults) -> "GenerationConfig":
        d = dict(d or {})
        fields = {f.name for f in dataclasses.fields(cls)}
        merged = {**defaults, **{k: v for k, v in d.items() if k in fields}}
        return cls(**merged)


def left_align(ids: jnp.ndarray, mask: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compact masked-out gaps: real tokens slide left, pads to the right.
    Stable order among real tokens is preserved."""
    order = jnp.argsort(~mask.astype(bool), axis=1, stable=True)
    return (jnp.take_along_axis(ids, order, axis=1),
            jnp.take_along_axis(mask, order, axis=1))


def encode_prompt_batch(tokenizer, prompts, width: int):
    """Host-side prompt encoding to fixed-width right-padded arrays —
    the single implementation shared by the engine, the RLHF rollout loop,
    and the teacher-gen/eval chunk paths."""
    import numpy as np
    ids = np.full((len(prompts), width), tokenizer.pad_token_id, np.int32)
    mask = np.zeros((len(prompts), width), np.int32)
    for i, p in enumerate(prompts):
        enc = tokenizer.encode(p)[:width]
        ids[i, :len(enc)] = enc
        mask[i, :len(enc)] = 1
    return ids, mask


def build_prefill_step(model: Transformer, max_new_tokens: int):
    """Public single-step prefill: ``fn(params, input_ids,
    attention_mask) -> (logits [B, V], cache)`` — ``start_decode`` with
    the decode budget bound statically so the result jits per prompt
    shape. Shared by the fixed-batch generate loop and any caller that
    drives decode one step at a time (eval harness, serving engine)."""
    def prefill_step(params, input_ids, attention_mask):
        return model.start_decode(
            params, input_ids, attention_mask, max_new_tokens)
    return prefill_step


def build_decode_step(model: Transformer, gen: GenerationConfig):
    """Public single-step sampled decode: ``fn(rng, params, logits,
    cache, done) -> (tok, emit_mask, logits, cache, done)``.

    This is THE step of autoregressive generation — sample from the
    incoming logits, hold finished rows at pad, advance the KV cache —
    factored out of ``build_generate_fn`` so the fixed-batch scan/while
    schedules and step-at-a-time drivers (latency percentile harness,
    serving scheduler) run the exact same math. ``build_generate_fn``
    composes its loops from this function, so the factoring is
    bit-identical by construction (pinned by the existing generation
    tests)."""
    def decode_step(rng, params, logits, cache, done):
        tok = sample_token(
            rng, logits,
            temperature=gen.temperature, top_p=gen.top_p,
            top_k=gen.top_k, do_sample=gen.do_sample)
        tok = jnp.where(done, gen.pad_token_id, tok)
        emit_mask = ~done
        done = done | (tok == gen.eos_token_id)
        logits, cache = model.decode_step(params, cache, tok)
        return tok, emit_mask, logits, cache, done
    return decode_step


def build_generate_fn(model: Transformer, gen: GenerationConfig,
                      group_size: int = 1,
                      per_request_seeds: bool = False):
    """Returns a jittable ``fn(params, input_ids, attention_mask, rng)`` ->
    dict of device arrays:

      sequences/sequence_mask  [B, P+N]  prompt + response, left-aligned
      response_tokens/response_mask [B, N]
      response_logps [B, N] chosen-token logprobs under the RAW model
        distribution (zero where the mask is zero)
      lengths [B] total real tokens (prompt + generated, incl. eos)

    ``group_size`` G > 1 is the GRPO/best-of-N rollout shape: the caller
    passes B UNIQUE prompts, each prompt is prefilled ONCE, and the
    prefill outputs (logits + KV cache) are expanded G-fold before
    decode — G samples per prompt for one prompt's prefill FLOPs (the
    serving engine's prefix cache, done in-graph). Outputs are laid out
    grouped ([p0 s0..sG-1, p1 s0..sG-1, ...]) and bit-identical to
    submitting each prompt G times in that same [B*G] batch order: the
    per-row decode math is batch-independent and the rng stream is keyed
    by absolute step, so only the (deduplicated) prefill differs.

    ``per_request_seeds=True`` swaps the final argument: ``fn(params,
    input_ids, attention_mask, seeds)`` where ``seeds`` is a [B*G] uint32
    array of per-row sampling seeds. Generated token k of row i is drawn
    with ``fold_in(PRNGKey(seeds[i]), k)`` — the exact keying the serving
    engine uses per request — so a serving-backed rollout with the same
    seeds reproduces this path's tokens and logps bit-for-bit (the
    sync-mode parity contract, pinned by test). The default mode keeps
    the historical absolute-step rng stream byte-for-byte."""
    single_step = build_decode_step(model, gen)
    eos = gen.eos_token_id if gen.eos_token_id is not None else -1

    def _expand(leaf):
        # cache leaves: pooled KV [L, B, S, KH, D] / int8 scales
        # [L, B, KH, S] carry batch at axis 1; per-row metadata
        # (valid/pos [B, S], lengths [B]) at axis 0; scalars
        # (step, prompt_width) are batch-free
        if leaf.ndim >= 4:
            return jnp.repeat(leaf, group_size, axis=1)
        if leaf.ndim >= 1:
            return jnp.repeat(leaf, group_size, axis=0)
        return leaf

    def generate(params, input_ids, attention_mask, rng):
        b, p_width = input_ids.shape
        n = gen.max_new_tokens
        logits, cache = model.start_decode(
            params, input_ids, attention_mask, n)
        if group_size > 1:
            logits = jnp.repeat(logits, group_size, axis=0)
            cache = jax.tree_util.tree_map(_expand, cache)
            input_ids = jnp.repeat(input_ids, group_size, axis=0)
            attention_mask = jnp.repeat(attention_mask, group_size,
                                        axis=0)
            b = b * group_size

        done0 = jnp.zeros((b,), bool)
        if per_request_seeds:
            seeds = rng.astype(jnp.uint32)           # [B*G] row seeds
            temps = jnp.full(
                (b,), gen.temperature if gen.do_sample else 0.0,
                jnp.float32)
            top_ps = jnp.full((b,), gen.top_p, jnp.float32)
            top_ks = jnp.full((b,), gen.top_k, jnp.int32)
        else:
            rngs = jax.random.split(rng, n)

        def step_fn(step, logits, cache, done):
            prev = logits.astype(jnp.float32)
            if per_request_seeds:
                tok, logp = sample_token_per_row(
                    seeds, jnp.full((b,), step, jnp.int32), prev,
                    temps, top_ps, top_ks)
                tok = jnp.where(done, gen.pad_token_id, tok)
                emit_mask = ~done
                done = done | (tok == eos)
                logits, cache = model.decode_step(params, cache, tok)
            else:
                tok, emit_mask, logits, cache, done = single_step(
                    rngs[step], params, logits, cache, done)
                logp = jnp.take_along_axis(
                    jax.nn.log_softmax(prev, axis=-1),
                    tok[:, None].astype(jnp.int32), axis=-1)[:, 0]
            return tok, logp, emit_mask, logits, cache, done

        if (gen.eos_token_id is not None and gen.eos_token_id >= 0
                and gen.early_exit_chunk > 0 and n > 0):
            # chunked early exit: while_loop over chunks, lax.scan of C
            # steps inside. Inner steps get scan's codegen; the done
            # check runs between chunks. Steps past n in the final
            # ragged chunk compute into clamped/padded slots that are
            # sliced away (their emit mask is zero; the cache is dead
            # after generation), so outputs match the per-step paths.
            c = min(int(gen.early_exit_chunk), n)
            nc = -(-n // c)
            toks0 = jnp.full((nc * c, b), gen.pad_token_id, jnp.int32)
            emits0 = jnp.zeros((nc * c, b), bool)
            lps0 = jnp.zeros((nc * c, b), jnp.float32)

            def chunk_cond(state):
                chunk, _, _, done, _, _, _ = state
                return (chunk < nc) & ~jnp.all(done)

            def chunk_body(state):
                chunk, logits, cache, done, toks, emits, lps = state

                def inner(carry, i):
                    logits, cache, done = carry
                    step = chunk * c + i
                    # absolute step indexes the same pre-split keys;
                    # ragged-tail steps (>= n) reuse the last key (n-1)
                    # (their output is pad with a zero mask either way)
                    tok, logp, emit_mask, logits, cache, done = step_fn(
                        jnp.minimum(step, n - 1), logits, cache, done)
                    emit_mask = emit_mask & (step < n)
                    tok = jnp.where(step < n, tok, gen.pad_token_id)
                    return (logits, cache, done), (tok, emit_mask, logp)

                (logits, cache, done), (ctoks, cemits, clps) = jax.lax.scan(
                    inner, (logits, cache, done), jnp.arange(c))
                toks = jax.lax.dynamic_update_slice(
                    toks, ctoks, (chunk * c, 0))
                emits = jax.lax.dynamic_update_slice(
                    emits, cemits, (chunk * c, 0))
                lps = jax.lax.dynamic_update_slice(
                    lps, clps, (chunk * c, 0))
                return chunk + 1, logits, cache, done, toks, emits, lps

            *_, toks, emits, lps = jax.lax.while_loop(
                chunk_cond, chunk_body,
                (jnp.int32(0), logits, cache, done0, toks0, emits0, lps0))
            toks, emits, lps = toks[:n], emits[:n], lps[:n]
        elif gen.eos_token_id is not None and gen.eos_token_id >= 0:
            # early exit: a while_loop that stops once every row has hit
            # EOS — real savings for eval/teacher-gen/rollout batches
            # whose sequences finish before max_new_tokens. Identical
            # math/rng stream to the scan path (same pre-split keys
            # indexed by step; unreached steps leave pad/0 rows).
            toks0 = jnp.full((n, b), gen.pad_token_id, jnp.int32)
            emits0 = jnp.zeros((n, b), bool)
            lps0 = jnp.zeros((n, b), jnp.float32)

            def cond(state):
                step, _, _, done, _, _, _ = state
                return (step < n) & ~jnp.all(done)

            def body(state):
                step, logits, cache, done, toks, emits, lps = state
                tok, logp, emit_mask, logits, cache, done = step_fn(
                    step, logits, cache, done)
                toks = jax.lax.dynamic_update_slice(
                    toks, tok[None, :], (step, 0))
                emits = jax.lax.dynamic_update_slice(
                    emits, emit_mask[None, :], (step, 0))
                lps = jax.lax.dynamic_update_slice(
                    lps, logp[None, :], (step, 0))
                return step + 1, logits, cache, done, toks, emits, lps

            *_, toks, emits, lps = jax.lax.while_loop(
                cond, body,
                (jnp.int32(0), logits, cache, done0, toks0, emits0, lps0))
        else:
            # no EOS (bench/fixed-length paths): plain scan over n steps
            def scan_body(carry, step):
                logits, cache, done = carry
                tok, logp, emit_mask, logits, cache, done = step_fn(
                    step, logits, cache, done)
                return (logits, cache, done), (tok, emit_mask, logp)

            (_, _, _), (toks, emits, lps) = jax.lax.scan(
                scan_body, (logits, cache, done0), jnp.arange(n))
        response_tokens = toks.T                      # [B, N]
        response_mask = emits.T.astype(jnp.int32)     # [B, N]
        response_logps = jnp.where(                   # [B, N]
            response_mask > 0, lps.T, 0.0)

        raw_ids = jnp.concatenate([input_ids, response_tokens], axis=1)
        raw_mask = jnp.concatenate(
            [attention_mask.astype(jnp.int32), response_mask], axis=1)
        sequences, sequence_mask = left_align(raw_ids, raw_mask)
        return {
            "sequences": sequences,
            "sequence_mask": sequence_mask,
            "response_tokens": response_tokens,
            "response_mask": response_mask,
            "response_logps": response_logps,
            "lengths": jnp.sum(raw_mask, axis=1),
        }

    return generate


class GenerationEngine:
    """Convenience wrapper that jits per (batch, prompt_width) shape and
    tokenizes/detokenizes at the host boundary."""

    def __init__(self, model: Transformer, tokenizer, gen: GenerationConfig):
        model.refuse_contiguous_cache()
        self.model = model
        self.tokenizer = tokenizer
        self.gen = dataclasses.replace(
            gen,
            eos_token_id=tokenizer.eos_token_id,
            pad_token_id=tokenizer.pad_token_id)
        self._fn = jax.jit(build_generate_fn(model, self.gen))
        # public single-step surface: the same prefill/decode step the
        # fused generate loop runs, jitted for step-at-a-time drivers
        self.prefill_step = jax.jit(
            build_prefill_step(model, self.gen.max_new_tokens))
        self.decode_step = jax.jit(build_decode_step(model, self.gen))

    def encode_prompts(self, prompts, max_prompt_len: int):
        return encode_prompt_batch(self.tokenizer, prompts, max_prompt_len)

    def generate_text(self, params, prompts, max_prompt_len: int,
                      rng) -> Tuple[list, Dict[str, Any]]:
        import numpy as np
        ids, mask = self.encode_prompts(prompts, max_prompt_len)
        out = self._fn(params, jnp.asarray(ids), jnp.asarray(mask), rng)
        texts = []
        resp = np.asarray(out["response_tokens"])
        rmask = np.asarray(out["response_mask"])
        for i in range(len(prompts)):
            toks = [int(t) for t, m in zip(resp[i], rmask[i])
                    if m and t != self.tokenizer.eos_token_id]
            texts.append(self.tokenizer.decode(toks))
        return texts, out
