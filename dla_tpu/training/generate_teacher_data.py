"""Teacher rollout generation for distillation (phase 4 input).

CLI parity: argparse flags, not YAML, like the reference
(src/training/generate_teacher_data.py:17-27):

  python -m dla_tpu.training.generate_teacher_data \
      --model_name_or_path checkpoints/dpo/latest \
      --prompts_path data/prompts.jsonl --output_path rollouts.jsonl \
      [--reward_model_path checkpoints/reward/latest]

Behavior parity: batch sampling with temperature/top-p, prompt stripped
from the response, optional reward scoring of each (prompt, response),
streamed JSONL ``{prompt, teacher_response, reward?}``
(reference :72-107).

TPU-native improvements: decode is the jitted KV-cache scan (not HF
generate), and reward scoring is batched in-graph on token ids (the
reference scored one sample at a time through a re-tokenize round trip,
:87-100).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from dla_tpu.data.jsonl import append_jsonl, read_jsonl
from dla_tpu.generation.engine import GenerationConfig, GenerationEngine
from dla_tpu.training.model_io import build_reward_model, load_causal_lm
from dla_tpu.training.utils import seed_everything
from dla_tpu.utils.compile_cache import enable_compile_cache
from dla_tpu.utils.logging import log_rank_zero

PROMPT_TEMPLATE = "{prompt}\n\n"


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description="Generate teacher rollouts")
    p.add_argument("--model_name_or_path", required=True)
    p.add_argument("--prompts_path", required=True)
    p.add_argument("--output_path", required=True)
    p.add_argument("--reward_model_path", default=None)
    p.add_argument("--tokenizer", default=None)
    p.add_argument("--batch_size", type=int, default=8)
    p.add_argument("--max_prompt_length", type=int, default=256)
    p.add_argument("--max_new_tokens", type=int, default=256)
    p.add_argument("--temperature", type=float, default=0.7)
    p.add_argument("--top_p", type=float, default=0.9)
    p.add_argument("--limit", type=int, default=None)
    p.add_argument("--seed", type=int, default=0)
    # partition the prompt file across independent rollout jobs: shard k
    # of n parses and generates only records k::n (native byte-range
    # reads, dla_tpu/data/jsonl.py) and should write a per-shard
    # --output_path
    p.add_argument("--shard_index", type=int, default=0)
    p.add_argument("--shard_count", type=int, default=1)
    # speculative decoding: a small same-tokenizer checkpoint proposes,
    # the teacher verifies blockwise — exact (outputs distributed as
    # plain teacher sampling), dla_tpu/generation/speculative.py
    p.add_argument("--draft_model_name_or_path", default=None)
    p.add_argument("--speculative_gamma", type=int, default=4)
    return p.parse_args(argv)


def main(argv=None) -> None:
    args = parse_args(argv)
    enable_compile_cache()
    rng = seed_everything(args.seed)
    model_cfg = {"tokenizer": args.tokenizer} if args.tokenizer else {}
    bundle = load_causal_lm(args.model_name_or_path, model_cfg, rng)
    gen = GenerationConfig(max_new_tokens=args.max_new_tokens,
                           temperature=args.temperature, top_p=args.top_p,
                           do_sample=args.temperature > 0)
    if args.draft_model_name_or_path:
        from dla_tpu.generation.speculative import SpeculativeEngine
        draft = load_causal_lm(args.draft_model_name_or_path, model_cfg,
                               jax.random.fold_in(rng, 17))
        engine = SpeculativeEngine(
            bundle.model, draft.model, draft.params, bundle.tokenizer,
            gen, gamma=args.speculative_gamma)
    else:
        engine = GenerationEngine(bundle.model, bundle.tokenizer, gen)

    rm_bundle = None
    score_fn = None
    if args.reward_model_path:
        rm_bundle = build_reward_model(
            {"base_model_name_or_path": args.reward_model_path,
             **model_cfg}, jax.random.fold_in(rng, 1))
        score_fn = jax.jit(rm_bundle.model.apply)

    records = read_jsonl(args.prompts_path, shard_index=args.shard_index,
                         shard_count=args.shard_count)
    prompts = [r["prompt"] for r in records if r.get("prompt")]
    if args.limit:
        prompts = prompts[: args.limit]
    shard = (f" (shard {args.shard_index}/{args.shard_count})"
             if args.shard_count > 1 else "")
    log_rank_zero(
        f"[dla_tpu] generating rollouts for {len(prompts)} prompts{shard}")

    # truncate a possibly pre-existing output
    open(args.output_path, "w").close()
    n_done = 0
    for start in range(0, len(prompts), args.batch_size):
        chunk = prompts[start:start + args.batch_size]
        # pad the tail chunk to a full batch (static shapes = one compile);
        # the padded rows' outputs are dropped below
        padded = chunk + [chunk[-1]] * (args.batch_size - len(chunk))
        templated = [PROMPT_TEMPLATE.format(prompt=p) for p in padded]
        texts, out = engine.generate_text(
            bundle.params, templated, args.max_prompt_length,
            jax.random.fold_in(rng, 100 + start))
        rewards = None
        if score_fn is not None:
            rewards = np.asarray(score_fn(
                rm_bundle.params, out["sequences"], out["sequence_mask"]))
        for i, (prompt, response) in enumerate(zip(chunk, texts)):
            rec = {"prompt": prompt, "teacher_response": response}
            if rewards is not None:
                rec["reward"] = float(rewards[i])
            append_jsonl(args.output_path, rec)
        n_done += len(chunk)
        log_rank_zero(f"[dla_tpu] {n_done}/{len(prompts)} rollouts written")


if __name__ == "__main__":
    main()
