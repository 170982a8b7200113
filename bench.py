"""Headline benchmark: SFT training throughput, tokens/sec/chip.

Prints ONE JSON line:
  {"metric": "sft_tokens_per_sec_per_chip", "value": N, "unit": "tok/s/chip",
   "vs_baseline": R}

``vs_baseline`` normalizes against the north-star target (BASELINE.json:
>= 0.8x the per-device throughput of the 8xH100 NCCL reference stack).
Neither repo publishes absolute H100 numbers (SURVEY.md sec 6), so the
comparison is made in hardware-normalized terms: a well-tuned
DeepSpeed-ZeRO3 run sustains ~40% MFU on H100-class hardware, so the
baseline per-chip token rate on *this* chip class is
0.8 * 0.40 * peak_flops / (6 * n_params) and

  vs_baseline = measured_MFU / (0.8 * 0.40)

i.e. vs_baseline >= 1.0 means this framework beats 0.8x the H100 baseline
after normalizing for per-chip peak FLOPs.

One process, on the chip: the headline and every ``--extra`` phase run
in this process (a chip belongs to one process at a time, and a parent
that had touched jax would hold it). Without a TPU the command exits
non-zero and prints no line — a CPU timing is never written under a
device metric's name — and a failing ``--extra`` phase fails the run.
The named CPU count-demos below (``python bench.py rollout`` etc.) force
the CPU platform themselves and report counts, not speeds.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.abspath(__file__))

# Per-chip peak FLOPs / HBM-bandwidth tables live in
# dla_tpu.telemetry.mfu: ONE set of peak numbers for bench, the trainer's
# MFU gauge and the sweep tools. A device that is not in them is an error.
BASELINE_MFU = 0.8 * 0.40  # 0.8x of a 40%-MFU H100-class DeepSpeed baseline
# PPO baseline efficiency factors: an
# H100-class trl/DeepSpeed rollout+update loop modeled at 40% MFU on the
# compute-bound phases (scoring forwards, update fwd+bwd) and 60% of HBM
# bandwidth on the decode phase — generous to the baseline: the
# reference's actual loop host-bounces between decode and scoring
# (src/training/train_rlhf.py:123-147) and uses HF generate.
PPO_BASELINE_MFU = 0.40
PPO_BASELINE_BW_EFF = 0.60


def hbm_bw(device) -> float:
    from dla_tpu.telemetry.mfu import hbm_bw_for
    return hbm_bw_for(device.device_kind, device.platform)


def ppo_baseline_samples_per_sec(n_params: int, batch: int, prompt: int,
                                 new_tokens: int, peak: float, bw: float,
                                 lora: bool, epochs: int = 1) -> float:
    """Hardware-normalized PPO rollout+update baseline, samples/s/chip.

    Per-sample cost model of the reference loop's phases on THIS chip
    with H100-class efficiency (the PPO analog of the SFT MFU bar):
      decode  — bandwidth-bound: new_tokens param reads amortized over
                the rollout batch,
      score   — 3 forwards (policy logp, ref logp, RM) at 2*N FLOPs/tok,
      update  — fwd+bwd at 6*N FLOPs/tok (4*N with LoRA: no base dW).
    """
    total_len = prompt + new_tokens
    p_bytes = 2.0 * n_params  # bf16 weights
    decode_s = new_tokens * p_bytes / (PPO_BASELINE_BW_EFF * bw * batch)
    score_s = 3 * 2.0 * n_params * total_len / (PPO_BASELINE_MFU * peak)
    upd_factor = 4.0 if lora else 6.0
    update_s = (upd_factor * n_params * total_len * epochs
                / (PPO_BASELINE_MFU * peak))
    return 1.0 / (decode_s + score_s + update_s)


def peak_flops(device) -> float:
    from dla_tpu.telemetry.mfu import peak_flops_for
    return peak_flops_for(device.device_kind, device.platform)


def count_params(params) -> int:
    import jax
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(params)))


def run_bench() -> dict:
    """The measurement itself. Assumes a live jax backend."""
    import jax
    from dla_tpu.models.config import ModelConfig
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.ops.fused_ce import model_fused_ce
    from dla_tpu.parallel.mesh import MeshConfig, build_mesh
    from dla_tpu.training.trainer import Trainer

    # ~350M-param Mistral-style decoder (GQA 8q/4kv like Mistral-7B's
    # 32q/8kv ratio, head_dim 128): big enough to exercise the MXU,
    # small enough that params + Adam state fit one v5e chip.
    # Measured-fastest single-chip configuration (round-5 on-chip
    # sweep, tools/sweep_bench.py): Pallas flash attention with
    # 1024x1024 blocks, remat="dots", micro=8, fused CE at
    # chunk=4096, bf16 Adam first moment — 33.0k tok/s (35.0% MFU,
    # 1.094x the H100-normalized bar). head_dim 64 -> 128 was the
    # big rock (round 3): it fills the MXU's 128-deep contraction in
    # the attention kernel AND stops the saved flash activations
    # from 2x lane-padding. Round 5 added the block-size bump
    # (1024-blocks cut the causal diagonal waste and per-block
    # bookkeeping vs 512: +3.9% step) and the larger CE chunk
    # (fewer [chunk, V] logit tiles: +3.1%); combined +6%.
    cfg = ModelConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        num_layers=24, num_heads=8, num_kv_heads=4,
        max_seq_length=2048, remat="dots", attention="flash",
        flash_block_q=1024, flash_block_k=1024)
    micro = int(os.environ.get("DLA_BENCH_MICRO", "8"))
    seq, steps, warmup = 2048, 6, 2

    print(f"[bench] devices up: {jax.devices()[0].device_kind} "
          f"x{jax.device_count()}", file=sys.stderr)
    mesh = build_mesh(MeshConfig(data=1, fsdp=-1, model=1, sequence=1))
    model = Transformer(cfg)
    params = model.init(jax.random.key(0))
    jax.block_until_ready(params)
    n_params = count_params(params)
    print(f"[bench] params initialized: {n_params / 1e6:.0f}M",
          file=sys.stderr)

    def loss_fn(p, frozen, batch, rng):
        del frozen, rng
        loss, _ = model_fused_ce(model, p, batch, chunk=4096)
        return loss, {}

    config = {
        "experiment_name": "bench",
        "optimization": {
            "total_batch_size": micro * mesh.devices.size,
            "micro_batch_size": micro, "learning_rate": 1e-4,
            "max_train_steps": steps, "lr_scheduler": "constant",
            "max_grad_norm": 1.0,
            # bf16 first moment frees ~0.7G for the micro=8 batch
            "adam_moment_dtype": "bfloat16",
        },
        "logging": {"output_dir": "/tmp/dla_bench_ckpt", "log_dir": None},
        "hardware": {"gradient_accumulation_steps": 1},
    }
    with jax.sharding.set_mesh(mesh):
        trainer = Trainer(config=config, mesh=mesh, loss_fn=loss_fn,
                          params=params, param_specs=model.partition_specs())
        rs = np.random.RandomState(0)
        local_bs = micro * mesh.devices.size
        batch = {
            "input_ids": rs.randint(1, cfg.vocab_size, (local_bs, seq)
                                    ).astype(np.int32),
            "attention_mask": np.ones((local_bs, seq), np.int32),
            "labels": rs.randint(1, cfg.vocab_size, (local_bs, seq)
                                 ).astype(np.int32),
        }
        t_c = time.perf_counter()
        for i in range(warmup):
            trainer.step_on_batch(batch, jax.random.key(i))
        print(f"[bench] warmup ({warmup} steps incl. compile): "
              f"{time.perf_counter() - t_c:.1f}s", file=sys.stderr)
        t0 = time.perf_counter()
        for i in range(steps):
            trainer.step_on_batch(batch, jax.random.key(100 + i))
        dt = time.perf_counter() - t0

    n_chips = jax.device_count()
    tokens = local_bs * seq * steps
    tok_s_chip = tokens / dt / n_chips
    mfu = tok_s_chip * 6 * n_params / peak_flops(jax.devices()[0])
    vs_baseline = mfu / BASELINE_MFU
    return {
        "metric": "sft_tokens_per_sec_per_chip",
        "value": round(tok_s_chip, 2),
        "unit": "tok/s/chip",
        "vs_baseline": round(vs_baseline, 4),
        "detail": {"micro": micro, "seq": seq,
                   "params_m": round(n_params / 1e6),
                   "mfu": round(mfu, 4),
                   "platform": jax.devices()[0].platform,
                   "device_kind": jax.devices()[0].device_kind,
                   "device_count": jax.device_count()},
    }


def run_ppo_bench() -> dict:
    """PPO rollout+update throughput, samples/sec — the second north-star
    metric BASELINE.json names ('PPO rollout+update samples/sec @7B'),
    measured at representative scale: a ~1.3B-param policy with LoRA
    adapters (the HBM-fitting RLHF setup: frozen bf16 base ALIASED as
    the reference model — one tree serves both — plus a 1.3B reward
    model), jitted scan-decode rollout over merged weights, on-device
    reinforce update of the adapters. vs_baseline normalizes against an
    H100-class trl/DeepSpeed loop modeled on this chip's peak specs
    (ppo_baseline_samples_per_sec)."""
    import jax
    import jax.numpy as jnp
    from dla_tpu.generation.engine import GenerationConfig, build_generate_fn
    from dla_tpu.models.config import ModelConfig
    from dla_tpu.models.reward import RewardModel
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.parallel.mesh import MeshConfig, build_mesh
    from dla_tpu.parallel.sharding import sharding_tree
    from dla_tpu.training.train_rlhf import (
        make_policy_gradient_loss,
        make_score_fn,
    )
    from dla_tpu.training.trainer import Trainer

    # ~1.3B llama-shaped policy (2048 x 24L, GQA 16q/8kv, hd 128).
    # bf16 base (frozen, shared policy/ref) + bf16 RM + one merged
    # rollout copy + KV cache ~ 9.5G of a v5e's 16G HBM.
    cfg = ModelConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_layers=24, num_heads=16, num_kv_heads=8,
        max_seq_length=512, remat="dots", attention="flash",
        param_dtype="bfloat16", lora_r=16,
        # int8 KV cache halves the rollout's cache HBM traffic
        # (~38% of decode bytes at this batch/seq)
        kv_cache_dtype="int8")
    # rollout batch 64 = the reference's own scale
    # (config/rlhf_config.yaml rollout_batch_size)
    batch, prompt_w, new_tokens, rollouts, warmup = 64, 128, 128, 3, 1
    # the UPDATE phase grad-accumulates 4 x 16 rows: at micro=64 the
    # "dots" remat stash is [24L, 64, 256, 5632] bf16 x2 (~8.2G) and
    # the step OOMs a 15.75G v5e (measured r5); micro=16 bounds the
    # stash at ~2.1G with the same samples/sec semantics
    update_micro, update_accum = 16, 4

    mesh = build_mesh(MeshConfig(data=1, fsdp=-1, model=1, sequence=1))
    policy = Transformer(cfg)
    rm = RewardModel(cfg)
    with jax.sharding.set_mesh(mesh):
        specs = policy.partition_specs()
        base = jax.device_put(policy.init(jax.random.key(0)),
                              sharding_tree(specs, mesh))
        adapters = policy.init_lora(jax.random.key(1))
        rm_params = jax.device_put(
            rm.init(jax.random.key(2)),
            sharding_tree(rm.partition_specs(), mesh))
        from dla_tpu.parallel.mesh import data_parallel_size
        dp = data_parallel_size(mesh)
        config = {
            "experiment_name": "bench_ppo",
            "optimization": {
                "total_batch_size": batch,
                "micro_batch_size": max(1, update_micro // dp),
                "learning_rate": 1e-6, "max_train_steps": rollouts + warmup,
                "lr_scheduler": "constant", "max_grad_norm": 1.0,
            },
            "logging": {"output_dir": "/tmp/dla_bench_ppo", "log_dir": None},
            "hardware": {"gradient_accumulation_steps": update_accum},
        }
        trainer = Trainer(
            config=config, mesh=mesh,
            loss_fn=make_policy_gradient_loss(policy, "reinforce", 0.2,
                                              lora=True),
            params=adapters, param_specs=policy.lora_partition_specs(),
            frozen={"base": base}, frozen_specs={"base": specs})
        gen = GenerationConfig(max_new_tokens=new_tokens, do_sample=True,
                               temperature=1.0, top_p=1.0,
                               eos_token_id=-1, pad_token_id=0)
        generate_fn = jax.jit(build_generate_fn(policy, gen))
        # ref == frozen base (LoRA aliasing, train_rlhf.py:283-285)
        score_fn = make_score_fn(policy, policy, rm)
        merge_fn = jax.jit(policy.merge_lora)
        # int8 weight-only rollouts: halves the decode loop's dominant
        # HBM traffic (ppo.rollout_quantize_weights in the trainer)
        quant_fn = jax.jit(policy.quantize_weights)

        rs = np.random.RandomState(0)
        ids = rs.randint(1, cfg.vocab_size, (batch, prompt_w)).astype(np.int32)
        mask = np.ones((batch, prompt_w), np.int32)
        ids_d = jax.device_put(jnp.asarray(ids))
        mask_d = jax.device_put(jnp.asarray(mask))

        def one_rollout(i):
            merged = quant_fn(merge_fn(base, trainer.params))
            out = generate_fn(merged, ids_d, mask_d, jax.random.key(i))
            scores = score_fn(merged, base, rm_params,
                              out["sequences"], out["sequence_mask"],
                              jnp.float32(0.1))
            up = {"sequences": out["sequences"],
                  "sequence_mask": out["sequence_mask"],
                  "advantages": scores["advantages"],
                  "behavior_logp": scores["behavior_logp"]}
            trainer.step_on_device_batch(up, jax.random.key(100 + i))

        for i in range(warmup):
            one_rollout(i)
        t0 = time.perf_counter()
        for i in range(rollouts):
            one_rollout(10 + i)
        dt = time.perf_counter() - t0

    n_params = count_params(base)
    samples_s = batch * rollouts / dt / jax.device_count()
    dev = jax.devices()[0]
    baseline = ppo_baseline_samples_per_sec(
        n_params, batch, prompt_w, new_tokens,
        peak_flops(dev), hbm_bw(dev), lora=True)
    return {
        "metric": "ppo_rollout_update_samples_per_sec_per_chip",
        "value": round(samples_s, 3),
        "unit": "samples/s/chip",
        "vs_baseline": round(samples_s / (0.8 * baseline), 4),
        "detail": {"batch": batch, "prompt_len": prompt_w,
                   "new_tokens": new_tokens, "lora_r": cfg.lora_r,
                   "rollout_weights": "int8", "kv_cache": cfg.kv_cache_dtype,
                   "params_m": round(n_params / 1e6),
                   "baseline_samples_s_chip": round(baseline, 2),
                   "platform": dev.platform,
                   "device_kind": dev.device_kind},
    }


def run_decode_bench() -> dict:
    """Autoregressive decode ms/token through the KV-cache engine (the
    PPO rollout hot path; reference only measured forward passes,
    src/eval/eval_latency.py:22-63)."""
    import jax
    from dla_tpu.eval.eval_latency import measure_decode
    from dla_tpu.models.config import ModelConfig
    from dla_tpu.models.transformer import Transformer

    # bf16 KV: the r5 on-chip sweep measured int8 KV ALONE as a
    # regression at this scale (1.655 vs 1.45 ms/token — dequant
    # work outweighs bandwidth savings while the cache is small
    # next to the weights; it pays only combined with int8 weights,
    # tools/sweep_decode.py b8_w8kv8 = 1.23 ms)
    # bf16 params: the inference/rollout storage dtype (fp32
    # masters would double the per-step weight read — same
    # rationale as tools/sweep_decode.py, review r4)
    cfg = ModelConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        num_layers=24, num_heads=8, num_kv_heads=4,
        max_seq_length=2048, attention="flash", remat="none",
        dtype="bfloat16", param_dtype="bfloat16")
    b, prompt, new = 8, 128, 256
    model = Transformer(cfg)
    params = model.init(jax.random.key(0))
    row = measure_decode(model, params, b, prompt, new)
    return {
        "metric": "decode_ms_per_token",
        "value": round(row["ms_per_token"], 3),
        "unit": "ms/token",
        "detail": {"batch": b, "prompt_len": prompt, "new_tokens": new,
                   "decode_tok_s_chip": round(
                       row["decode_tokens_per_second_per_chip"], 1),
                   "params_m": round(count_params(params) / 1e6)},
    }


def run_serving_bench() -> dict:
    """Continuous-batching serving throughput: requests/s and TTFT/ITL
    percentiles under a Poisson arrival trace through the paged-KV
    engine (dla_tpu/serving) — the rollout-side counterpart of the
    decode bench's fixed-batch ms/token."""
    import jax
    from dla_tpu.eval.eval_latency import measure_serving
    from dla_tpu.models.config import ModelConfig
    from dla_tpu.models.transformer import Transformer

    cfg = ModelConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        num_layers=24, num_heads=8, num_kv_heads=4,
        max_seq_length=2048, attention="flash", remat="none",
        dtype="bfloat16", param_dtype="bfloat16")
    srv = {"num_requests": 32, "arrival_rate": 32.0, "new_tokens": 64,
           "prompt_len_min": 32, "prompt_len_max": 128,
           "page_size": 16, "num_pages": 512, "num_slots": 8,
           "max_model_len": 256}
    model = Transformer(cfg)
    params = model.init(jax.random.key(0))
    row = measure_serving(model, params, srv)
    return {
        "metric": "serving_requests_per_s",
        "value": round(row["requests_per_second"], 3),
        "unit": "req/s",
        "detail": {"requests_per_s": round(row["requests_per_second"], 3),
                   "ttft_ms_p50": round(row["ttft_ms_p50"], 2),
                   "ttft_ms_p95": round(row["ttft_ms_p95"], 2),
                   "itl_ms_p50": round(row["itl_ms_p50"], 3),
                   "page_occupancy": round(row["page_occupancy_peak"], 4),
                   "serve_tok_s": round(row["serve_tokens_per_second"], 1),
                   "preemptions": int(row["preemptions"]),
                   "num_slots": row["num_slots"],
                   "num_requests": row["num_requests"],
                   "arrival_rate": row["arrival_rate"],
                   "params_m": round(count_params(params) / 1e6)},
    }


def run_serving_prefix_bench() -> dict:
    """Shared-prefix serving A/B: the same K-families x N-requests trace
    through the chunked-prefill engine with the prefix cache on vs off.
    The headline is the fraction of prefill tokens the cache saved
    (higher is better); detail carries both arms' ITL p95 and the greedy
    bit-identity check — a caching regression shows up as a saved-frac
    drop or an outputs_identical flip, both gateable."""
    import jax
    from dla_tpu.eval.eval_latency import measure_shared_prefix
    from dla_tpu.models.config import ModelConfig
    from dla_tpu.models.transformer import Transformer

    cfg = ModelConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        num_layers=24, num_heads=8, num_kv_heads=4,
        max_seq_length=2048, attention="flash", remat="none",
        dtype="bfloat16", param_dtype="bfloat16")
    srv = {"arrival_rate": 64.0, "new_tokens": 32,
           "page_size": 16, "num_pages": 1024, "num_slots": 8,
           "max_model_len": 256,
           "chunked_prefill": {"chunk": 32},
           "shared_prefix": {"families": 8, "requests_per_family": 16,
                             "prefix_len": 96, "suffix_len": 16}}
    model = Transformer(cfg)
    params = model.init(jax.random.key(0))
    row = measure_shared_prefix(model, params, srv)
    return {
        "metric": "serving_prefill_tokens_saved_frac",
        "value": round(row["prefill_tokens_saved_frac"], 4),
        "unit": "frac",
        "detail": {
            "cache_hit_rate": round(row["cache_hit_rate"], 4),
            "outputs_identical": bool(row["outputs_identical"]),
            "itl_ms_p95_cache_on": round(row["itl_ms_p95_cache_on"], 3),
            "itl_ms_p95_cache_off": round(row["itl_ms_p95_cache_off"], 3),
            "ttft_ms_p95_cache_on": round(row["ttft_ms_p95_cache_on"], 2),
            "ttft_ms_p95_cache_off": round(
                row["ttft_ms_p95_cache_off"], 2),
            "cache_evictions": int(row["cache_evictions"]),
            "families": row["families"],
            "requests_per_family": row["requests_per_family"],
            "prefix_len": row["prefix_len"],
            "suffix_len": row["suffix_len"],
            "prefill_chunk": row["prefill_chunk"],
            "params_m": round(count_params(params) / 1e6)},
    }


def run_rollout_bench() -> dict:
    """Disaggregated-rollout A/B on a long-tail response-length mix:
    slot-steps per generated token through the serving-engine rollout
    path (dla_tpu/rollout — continuous batching retires short rows
    early and refills their slots) vs the fixed-shape batch generate
    path (every row pays decode steps until the LONGEST row finishes).
    The headline is the padding waste recovered, ``1 - serving/batch``
    (higher is better); the batch arm's cost is exact by construction
    (rows x longest row — eos is disabled so every row runs its full
    per-row budget), the serving arm's decode steps are measured.
    Deterministic, CPU-sized, in-process."""
    import jax
    import numpy as np
    from dla_tpu.generation.engine import GenerationConfig
    from dla_tpu.models.config import ModelConfig
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.ops.sampling import derive_rollout_seeds
    from dla_tpu.rollout import RolloutEngine
    from dla_tpu.serving import ServingConfig

    cfg = ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=192,
        num_layers=2, num_heads=4, num_kv_heads=4,
        max_seq_length=128, remat="none", dtype="float32",
        param_dtype="float32")
    model = Transformer(cfg)
    params = model.init(jax.random.key(0))
    # long-tail budgets: most rows are short, one dominates — the shape
    # that makes fixed-batch padding waste worst
    max_new = [3, 3, 3, 4, 4, 6, 8, 24]
    rows, longest = len(max_new), max(max_new)
    gen = GenerationConfig(max_new_tokens=longest, do_sample=True,
                           temperature=1.0, eos_token_id=-1,
                           pad_token_id=0)
    rs = np.random.RandomState(7)
    lens = rs.randint(4, 11, (rows,))
    width = int(lens.max())
    ids = np.zeros((rows, width), np.int32)
    mask = np.zeros_like(ids)
    for i, n in enumerate(lens):
        ids[i, :n] = rs.randint(3, 500, (n,))
        mask[i, :n] = 1
    num_slots = 4
    eng = RolloutEngine(
        model, params, gen,
        ServingConfig(page_size=4, num_pages=96, num_slots=num_slots,
                      max_model_len=48))
    out = eng.generate(ids, mask, derive_rollout_seeds(11, rows),
                       max_new=max_new)
    snap = eng.metrics.snapshot()
    decode_steps = eng._decode_steps_total()
    eng.close()
    tokens = int(np.asarray(out["response_mask"]).sum())
    assert tokens == sum(max_new), "eos disabled: budgets run in full"
    serving_spt = decode_steps * num_slots / tokens
    batch_spt = rows * longest / tokens
    recovered = 1.0 - serving_spt / batch_spt
    return {
        "metric": "rollout_padding_waste_recovered",
        "value": round(recovered, 4),
        "unit": "frac",
        "detail": {
            "padding_waste_recovered": round(recovered, 4),
            "serving_slot_steps_per_token": round(serving_spt, 4),
            "batch_slot_steps_per_token": round(batch_spt, 4),
            "serving_decode_steps": decode_steps,
            "gen_tokens_per_s": round(snap["rollout/gen_tokens_per_s"], 1),
            "tokens": tokens,
            "rows": rows,
            "num_slots": num_slots,
            "longest_row": longest,
            "params_m": round(count_params(params) / 1e6)},
    }


def run_rollout_fleet_bench() -> dict:
    """Elastic sampler-fleet A/B (dla_tpu/rollout/actor_fleet), three
    measurements in one row: (1) refit fanout at N=4 — every member
    publish costs a fixed ``refit_delay_s``, so the serial baseline
    pays ~N delays while the broadcast tree pays ~wave-count (2 at
    branch 2); the headline is that wall-time ratio (higher is
    better). (2) Rollout throughput N=1 vs N=4 on the same prompts —
    trajectories/s per fleet size, outputs pinned bit-identical across
    fleet sizes. (3) Chaos: ``sampler=1:rollout_step=1:lost`` kills a
    member mid-run over 3 rollouts; ``steps_lost_to_sampler_death``
    must be 0 (lose a sampler, not the run — orphaned groups are
    reassigned and regenerate bit-identically from the journal).
    Deterministic, CPU-sized, in-process."""
    import time
    import jax
    import numpy as np
    from dla_tpu.generation.engine import GenerationConfig
    from dla_tpu.models.config import ModelConfig
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.ops.sampling import derive_rollout_seeds
    from dla_tpu.rollout import (SamplerFleet, SamplerFleetConfig,
                                 ensure_cpu_sync_dispatch)
    from dla_tpu.serving import ServingConfig

    # must precede the first jax computation below — the CPU client
    # bakes the dispatch mode in at creation (see actor_fleet)
    ensure_cpu_sync_dispatch()
    cfg = ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=192,
        num_layers=2, num_heads=4, num_kv_heads=4,
        max_seq_length=128, remat="none", dtype="float32",
        param_dtype="float32")
    model = Transformer(cfg)
    params = model.init(jax.random.key(0))
    gen = GenerationConfig(max_new_tokens=6, do_sample=True,
                           temperature=1.0, eos_token_id=-1,
                           pad_token_id=0)
    rows = 8
    rs = np.random.RandomState(7)
    lens = rs.randint(4, 11, (rows,))
    width = int(lens.max())
    ids = np.zeros((rows, width), np.int32)
    mask = np.zeros_like(ids)
    for i, n in enumerate(lens):
        ids[i, :n] = rs.randint(3, 500, (n,))
        mask[i, :n] = 1
    seeds = derive_rollout_seeds(11, rows)
    scfg = ServingConfig(page_size=4, num_pages=96, num_slots=4,
                         max_model_len=48,
                         fault_plan="")
    delay_s, branch = 0.05, 2

    # --- (1) refit fanout serial vs broadcast at N=4, (2) N=4 rollout
    fleet4 = SamplerFleet(
        model, params, gen, scfg,
        SamplerFleetConfig(samplers=4, fanout_branch=branch,
                           refit_delay_s=delay_s))
    t0 = time.perf_counter()
    fleet4.publish_params_serial(params, version=1)
    serial_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    fleet4.publish_params(params, version=2)
    bcast_s = time.perf_counter() - t0
    fanout_speedup = serial_s / bcast_s
    fleet4.generate(ids, mask, seeds)          # warm-up: compiles
    t0 = time.perf_counter()
    out4 = fleet4.generate(ids, mask, seeds)
    n4_s = time.perf_counter() - t0
    fleet4.close()

    fleet1 = SamplerFleet(model, params, gen, scfg,
                          SamplerFleetConfig(samplers=1))
    fleet1.generate(ids, mask, seeds)          # warm-up
    t0 = time.perf_counter()
    out1 = fleet1.generate(ids, mask, seeds)
    n1_s = time.perf_counter() - t0
    fleet1.close()
    identical = bool(np.array_equal(np.asarray(out1["response_tokens"]),
                                    np.asarray(out4["response_tokens"])))

    # --- (3) lose a sampler mid-run: zero learner steps lost
    chaos = SamplerFleet(
        model, params, gen,
        ServingConfig(page_size=4, num_pages=96, num_slots=4,
                      max_model_len=48,
                      fault_plan="sampler=1:rollout_step=1:lost"),
        SamplerFleetConfig(samplers=2, lease_ttl_s=0.3))
    steps_lost = 0
    for _ in range(3):
        try:
            ck = chaos.generate(ids, mask, seeds)
            if np.asarray(ck["response_tokens"]).shape[0] != rows:
                steps_lost += 1
        except Exception:  # noqa: BLE001 — a lost run IS the metric
            steps_lost += 1
    snap = chaos.fleet_metrics.snapshot()
    chaos.close()

    return {
        "metric": "rollout_fleet_fanout_speedup",
        "value": round(fanout_speedup, 2),
        "unit": "x",
        "detail": {
            "fanout_speedup": round(fanout_speedup, 2),
            "serial_refit_ms": round(serial_s * 1e3, 1),
            "broadcast_refit_ms": round(bcast_s * 1e3, 1),
            "refit_delay_ms": delay_s * 1e3,
            "samplers": 4,
            "fanout_branch": branch,
            "fanout_waves": 2,
            "trajectories_per_s_n1": round(rows / n1_s, 2),
            "trajectories_per_s_n4": round(rows / n4_s, 2),
            "fleet_scaling": round(n1_s / n4_s, 2),
            "outputs_identical_n1_n4": identical,
            "steps_lost_to_sampler_death": steps_lost,
            "retired_samplers": int(
                snap["rollout/fleet/retired_samplers"]),
            "reassigned_rollouts": int(
                snap["rollout/fleet/reassigned_rollouts"]),
            "params_m": round(count_params(params) / 1e6)},
    }


def run_serving_spec_bench() -> dict:
    """Speculative-serving A/B on the long-tail response-length mix:
    the SAME prompts and per-row budgets through two serving engines —
    blockwise draft/verify speculation ON (int8 self-draft) vs OFF.
    The headline is the decode-throughput speedup (tokens/s spec-on /
    spec-off, higher is better); detail carries the measured draft
    acceptance rate, per-arm tokens/s and slot-steps per token (a
    speculative round retires up to K+1 tokens per slot-step, so the
    spec arm's slot-steps/token drops with acceptance). Deterministic,
    CPU-sized, in-process."""
    import time
    import jax
    import numpy as np
    from dla_tpu.generation.engine import GenerationConfig
    from dla_tpu.models.config import ModelConfig
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.serving import ServingConfig, ServingEngine

    # deliberately latency-bound: per-step FLOPs are tiny so the fixed
    # per-dispatch cost dominates the decode step, the CPU stand-in for
    # the TPU's memory-bandwidth-bound decode — the regime where a
    # verify over K+1 columns costs about the same as one column and
    # speculation pays
    cfg = ModelConfig(
        vocab_size=256, hidden_size=32, intermediate_size=96,
        num_layers=2, num_heads=2, num_kv_heads=2,
        max_seq_length=128, remat="none", dtype="float32",
        param_dtype="float32")
    model = Transformer(cfg)
    params = model.init(jax.random.key(0))
    max_new = [9, 9, 9, 12, 12, 18, 24, 72]
    rows, longest = len(max_new), max(max_new)
    gen = GenerationConfig(max_new_tokens=longest, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    rs = np.random.RandomState(7)
    lens = rs.randint(4, 11, (rows,))
    prompts = [list(rs.randint(3, 250, (n,)).astype(int)) for n in lens]
    num_slots = 4
    # k=8: a speculative round is dominated by its two fixed dispatch
    # costs (draft scan + verify), so a deeper block amortizes them
    # over more committed tokens — the CPU analogue of the TPU's
    # memory-bound decode step
    spec = {"enabled": True, "k": 8, "draft": "int8"}
    reps = 5

    def run_arm(spec_on: bool):
        eng = ServingEngine(model, params, gen, ServingConfig(
            page_size=4, num_pages=128, num_slots=num_slots,
            max_model_len=96,
            speculative=spec if spec_on else None))
        # compile warmup off the clock: the one chunk program plus one
        # decode round — the 2-token budget is what forces the
        # draft+verify pair (or plain decode) to trace
        eng.submit([3 + (i % 251) for i in range(min(
            eng.cfg.prefill_chunk, eng.cache.geom.slot_window - 2))], 2)
        eng.run_until_drained()
        # the measured window is small (~100 ms on CPU), so wall-clock
        # noise swamps a single pass: repeat the identical mix and take
        # the fastest pass — scheduling is deterministic, so every rep
        # does the same work and the min is the least-perturbed timing
        dts = []
        for _ in range(reps):
            steps0 = eng.engine_steps
            t0 = time.perf_counter()
            for p, m in zip(prompts, max_new):
                eng.submit(p, m)
            eng.run_until_drained(max_steps=5000)
            dts.append(time.perf_counter() - t0)
            steps = eng.engine_steps - steps0
        snap = eng.metrics.snapshot()
        eng.close()
        return min(dts), steps, snap

    dt_on, steps_on, snap_on = run_arm(True)
    dt_off, steps_off, snap_off = run_arm(False)
    tokens = sum(max_new)
    tps_on = tokens / dt_on
    tps_off = tokens / dt_off
    prop = snap_on["serving/spec/proposed_tokens"]
    acceptance = snap_on["serving/spec/accepted_tokens"] / max(prop, 1)
    return {
        "metric": "serving_spec_decode_speedup",
        "value": round(tps_on / tps_off, 4),
        "unit": "x",
        "detail": {
            "decode_tokens_per_s_spec_on": round(tps_on, 1),
            "decode_tokens_per_s_spec_off": round(tps_off, 1),
            "acceptance_rate": round(acceptance, 4),
            "slot_steps_per_token_spec_on":
                round(steps_on * num_slots / tokens, 4),
            "slot_steps_per_token_spec_off":
                round(steps_off * num_slots / tokens, 4),
            "spec_rounds": snap_on["serving/spec/rounds"] / reps,
            "spec_rollbacks": snap_on["serving/spec/rollbacks"] / reps,
            "reps": reps,
            "k": spec["k"],
            "draft": spec["draft"],
            "tokens": tokens,
            "rows": rows,
            "num_slots": num_slots,
            "longest_row": longest,
            "params_m": round(count_params(params) / 1e6)},
    }


def run_serving_tenant_bench() -> dict:
    """Multi-tenant LoRA serving A/B (dla_tpu/serving/tenancy): N=4
    tenants' adapters batched into ONE engine (per-slot adapter gather,
    one decode compile across the whole tenant mix) vs serving the same
    tenants' interleaved arrival trace on a single-tenant engine that
    pays a merge-and-republish weight swap at every tenant switch. The
    headline is the batched arm's tokens/s speedup over the serial-swap
    arm (higher is better) — the model is sized so a swap costs real
    merge + republish time, not just a pointer flip, since that is the
    cost the adapter pool removes;
    detail pins per-tenant greedy outputs identical across arms,
    decode_step_compiles == 1 on the batched engine, and the
    noisy-tenant quota probe (a flooding tenant's sheds must land on
    itself only, every other tenant's requests finishing untouched).
    Deterministic, CPU-sized, in-process."""
    import jax
    from dla_tpu.eval.eval_latency import measure_multi_tenant
    from dla_tpu.models.config import ModelConfig
    from dla_tpu.models.transformer import Transformer

    cfg = ModelConfig(
        vocab_size=2048, hidden_size=384, intermediate_size=768,
        num_layers=4, num_heads=6, num_kv_heads=6,
        max_seq_length=128, remat="none", dtype="float32",
        param_dtype="float32", lora_r=8, lora_alpha=16.0)
    model = Transformer(cfg)
    params = model.init(jax.random.key(0))
    srv = {"new_tokens": 8, "arrival_rate": 1000.0, "seed": 7,
           "page_size": 4, "num_pages": 96, "num_slots": 4,
           "max_model_len": 48,
           "chunked_prefill": {"chunk": 8},
           "tenancy": {"tenants": 4, "requests_per_tenant": 3}}
    row = measure_multi_tenant(model, params, srv)
    return {
        "metric": "serving_tenant_batched_speedup",
        "value": round(row["batched_speedup"], 3),
        "unit": "x",
        "detail": {
            "tokens_per_s_batched": round(row["tokens_per_s_batched"], 1),
            "tokens_per_s_serial": round(row["tokens_per_s_serial"], 1),
            "outputs_identical": bool(row["outputs_identical"]),
            "decode_step_compiles": int(row["decode_step_compiles"]),
            "adapter_publishes": int(row["adapter_publishes"]),
            "adapter_resident": int(row["adapter_resident"]),
            "noisy_isolated": bool(row["noisy_isolated"]),
            "noisy_shed": int(row["noisy_shed"]),
            "others_shed": int(row["others_shed"]),
            "others_finished": int(row["others_finished"]),
            "tenants": row["tenants"],
            "requests_per_tenant": row["requests_per_tenant"],
            "lora_rank": row["lora_rank"],
            "params_m": round(count_params(params) / 1e6)},
    }


def run_serving_fleet_bench() -> dict:
    """Fleet-routing A/B/C on a shared-prefix request mix: the SAME
    prompts through (1) a single engine, (2) an N=4 fleet with random
    placement, and (3) an N=4 fleet with cache-aware routing (peek +
    load + sticky-prefix affinity). The headline is the routed fleet's
    decode-throughput speedup over random placement (higher is better —
    random scatters each prompt family across members and destroys
    cross-request prefix reuse); detail carries per-arm decode tokens/s
    (N=1 vs N=4 scaling), per-arm prefix-cache hit rates and the
    routed fleet's hit-rate retention vs the single engine, the greedy
    bit-identity check across all three arms, and a scale-down drain
    exercise (queued work rebalanced to peers, zero lost requests).
    Deterministic placement and outputs, CPU-sized, in-process."""
    import time
    import jax
    import numpy as np
    from dla_tpu.generation.engine import GenerationConfig
    from dla_tpu.models.config import ModelConfig
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.serving import (
        TERMINAL_STATES,
        FleetConfig,
        FleetRouter,
        ServingConfig,
        ServingEngine,
        ServingMetrics,
    )

    cfg = ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=192,
        num_layers=2, num_heads=4, num_kv_heads=4,
        max_seq_length=128, remat="none", dtype="float32",
        param_dtype="float32")
    model = Transformer(cfg)
    params = model.init(jax.random.key(0))
    new_tokens, chunk = 8, 8
    gen = GenerationConfig(max_new_tokens=new_tokens, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    families, per_family = 4, 8
    rs = np.random.RandomState(7)
    prompts = []
    for _ in range(families):
        head = [int(t) for t in rs.randint(3, 500, (16,))]
        for _ in range(per_family):
            prompts.append(head + [int(t)
                                   for t in rs.randint(3, 500, (4,))])
    tokens = len(prompts) * new_tokens
    prompt_tokens = sum(len(p) for p in prompts)
    engines, reps = 4, 3

    def build_engine(slot=0):
        # two slots per engine: the single-engine arm is deliberately
        # slot-bound, so fleet scaling measures real added capacity;
        # fault_plan="" pins members fault-free under $DLA_FAULT_PLAN
        return ServingEngine(model, params, gen, ServingConfig(
            page_size=4, num_pages=96, num_slots=2, max_model_len=48,
            prefill_chunk=chunk, prefix_cache=True,
            fault_plan=""))

    def warm(eng):
        # compile warmup (chunk fn + decode) off the clock; random
        # tokens can't collide with a family prefix
        eng.submit([int(t) for t in rs.randint(3, 500, (chunk + 1,))], 1)
        eng.run_until_drained()
        eng.metrics = ServingMetrics()

    def drive(eng):
        # burst-submit the whole mix and take the fastest of `reps`
        # identical passes — scheduling and placement are
        # deterministic, so the min is the least-perturbed timing
        dts, outs = [], None
        for _ in range(reps):
            t0 = time.perf_counter()
            rids = [eng.submit(p, new_tokens) for p in prompts]
            results = eng.run_until_drained(max_steps=20000)
            dts.append(time.perf_counter() - t0)
            outs = [list(results[r].generated) for r in rids]
        return min(dts), outs

    def run_single():
        eng = build_engine()
        warm(eng)
        dt, outs = drive(eng)
        hit = eng.metrics.snapshot()["serving/prefix_cache/hit_tokens"]
        eng.close()
        return dt, outs, hit / (reps * prompt_tokens)

    def run_fleet(placement):
        router = FleetRouter(
            lambda slot: build_engine(slot),
            FleetConfig(engines=engines, min_engines=1,
                        max_engines=engines, placement=placement))
        for m in router.members():
            warm(m.engine)
        dt, outs = drive(router)
        hit = sum(s["serving/prefix_cache/hit_tokens"]
                  for s in router.engine_snapshots())
        return router, dt, outs, hit / (reps * prompt_tokens)

    dt_single, outs_single, hit_single = run_single()
    r_rand, dt_rand, outs_rand, hit_rand = run_fleet("random")
    r_rand.close()
    r_routed, dt_routed, outs_routed, hit_routed = run_fleet("cache_aware")

    # scale-down drain on the routed fleet: queued work must move to
    # peers and every request must still reach a terminal state
    rids = [r_routed.submit(p, new_tokens) for p in prompts]
    r_routed.scale_down()
    results = r_routed.run_until_drained(max_steps=20000)
    lost = sum(1 for r in rids
               if results[r].state not in TERMINAL_STATES)
    fleet_snap = r_routed.fleet_snapshot()
    r_routed.close()

    tps_routed = tokens / dt_routed
    tps_rand = tokens / dt_rand
    tps_single = tokens / dt_single
    return {
        "metric": "serving_fleet_routed_speedup",
        "value": round(tps_routed / tps_rand, 4),
        "unit": "x",
        "detail": {
            "decode_tokens_per_s_routed": round(tps_routed, 1),
            "decode_tokens_per_s_random": round(tps_rand, 1),
            "decode_tokens_per_s_single": round(tps_single, 1),
            "fleet_n4_tokens_per_s_scaling":
                round(tps_routed / tps_single, 4),
            "hit_rate_routed": round(hit_routed, 4),
            "hit_rate_random": round(hit_rand, 4),
            "hit_rate_single": round(hit_single, 4),
            "hit_rate_retention":
                round(hit_routed / max(hit_single, 1e-9), 4),
            "outputs_identical":
                bool(outs_single == outs_rand == outs_routed),
            "requests_lost_scale_down": lost,
            "rebalanced_requests":
                int(fleet_snap["serving/fleet/rebalanced_requests"]),
            "routed_by_prefix":
                int(fleet_snap["serving/fleet/routed_by_prefix"]),
            "routed_by_load":
                int(fleet_snap["serving/fleet/routed_by_load"]),
            "engines": engines,
            "reps": reps,
            "requests": len(prompts),
            "families": families,
            "params_m": round(count_params(params) / 1e6)},
    }


def run_serving_disagg_bench() -> dict:
    """Prefill/decode disaggregation A/B on a long-prompt burst: the
    SAME prompts through (1) a mixed co-scheduled fleet of 3 members and
    (2) a role-split fleet of 1 prefill + 2 decode members where every
    finished prefix ships to a decode member as a KV migration ticket
    (one jitted gather + one jitted scatter per handoff). The headline
    is the disaggregated fleet's ITL p99 speedup over the mixed fleet
    (higher is better — decode members never interleave prefill chunks,
    so the inter-token tail loses its head-of-line stalls); detail
    carries per-arm ITL p50/p99 and decode tokens/s, a single-engine
    reference arm, the migrated-page throughput, and the greedy
    bit-identity check across all arms (migration resumes from the
    exact committed KV columns). Deterministic, CPU-sized,
    in-process."""
    import time
    import jax
    import numpy as np
    from dla_tpu.generation.engine import GenerationConfig
    from dla_tpu.models.config import ModelConfig
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.serving import (
        FleetConfig,
        FleetRouter,
        ServingConfig,
        ServingEngine,
        ServingMetrics,
    )
    from dla_tpu.utils.logging import percentile

    cfg = ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=192,
        num_layers=2, num_heads=4, num_kv_heads=4,
        max_seq_length=128, remat="none", dtype="float32",
        param_dtype="float32")
    model = Transformer(cfg)
    params = model.init(jax.random.key(0))
    # long prompts + small chunk: the regime where co-scheduled prefill
    # chunks head-of-line-block decode steps and inflate the ITL tail
    new_tokens, chunk, prompt_len = 8, 8, 24
    gen = GenerationConfig(max_new_tokens=new_tokens, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    rs = np.random.RandomState(7)
    prompts = [[int(t) for t in rs.randint(3, 500, (prompt_len,))]
               for _ in range(24)]
    tokens = len(prompts) * new_tokens
    n_prefill, n_decode, reps = 1, 2, 3
    engines = n_prefill + n_decode
    roles = ("prefill",) * n_prefill + ("decode",) * n_decode

    def build_engine(role="mixed"):
        # fault_plan="" pins members fault-free under $DLA_FAULT_PLAN
        return ServingEngine(model, params, gen, ServingConfig(
            page_size=4, num_pages=96, num_slots=2, max_model_len=48,
            prefill_chunk=chunk, prefix_cache=True,
            fault_plan="", role=role))

    def warm(eng):
        # compile warmup off the clock; decode-role members gate
        # submit(), so warm those through restore() — the handoff-only
        # admission surface compiles the same chunk + decode fns
        prompt = [int(t) for t in rs.randint(3, 500, (chunk + 1,))]
        if eng.cfg.role == "decode":
            eng.restore(prompt, 1, generated=[], arrival_time=0.0)
        else:
            eng.submit(prompt, 1)
        eng.run_until_drained()

    def drive(eng, member_engines):
        # burst-submit the whole mix; per rep, reset the member metrics
        # and keep the least-perturbed (fastest) rep's ITL samples
        best = None
        for _ in range(reps):
            for e in member_engines:
                e.metrics = ServingMetrics()
            t0 = time.perf_counter()
            rids = [eng.submit(p, new_tokens) for p in prompts]
            results = eng.run_until_drained(max_steps=20000)
            dt = time.perf_counter() - t0
            outs = [list(results[r].generated) for r in rids]
            itl = [s for e in member_engines
                   for s in e.metrics.itl_ms.samples]
            pages = sum(
                e.metrics.snapshot()["serving/migration/migrated_pages"]
                for e in member_engines)
            if best is None or dt < best[0]:
                best = (dt, outs, itl, pages)
        return best

    def run_single():
        eng = build_engine()
        warm(eng)
        dt, outs, itl, _ = drive(eng, [eng])
        eng.close()
        return dt, outs, itl

    def run_fleet(role_split):
        router = FleetRouter(
            lambda slot: build_engine(
                roles[slot] if role_split else "mixed"),
            FleetConfig(engines=engines, min_engines=1,
                        max_engines=engines,
                        roles=roles if role_split else None))
        for m in router.members():
            warm(m.engine)
        dt, outs, itl, pages = drive(
            router, [m.engine for m in router.members()])
        router.close()
        return dt, outs, itl, pages

    dt_single, outs_single, itl_single = run_single()
    dt_mixed, outs_mixed, itl_mixed, _ = run_fleet(False)
    dt_disagg, outs_disagg, itl_disagg, pages = run_fleet(True)

    p99_mixed = percentile(itl_mixed, 99.0)
    p99_disagg = percentile(itl_disagg, 99.0)
    return {
        "metric": "serving_disagg_itl_p99_speedup",
        "value": round(p99_mixed / max(p99_disagg, 1e-9), 4),
        "unit": "x",
        "detail": {
            "itl_p99_ms_disagg": round(p99_disagg, 3),
            "itl_p99_ms_mixed": round(p99_mixed, 3),
            "itl_p99_ms_single": round(percentile(itl_single, 99.0), 3),
            "itl_p50_ms_disagg": round(percentile(itl_disagg, 50.0), 3),
            "itl_p50_ms_mixed": round(percentile(itl_mixed, 50.0), 3),
            "decode_tokens_per_s_disagg": round(tokens / dt_disagg, 1),
            "decode_tokens_per_s_mixed": round(tokens / dt_mixed, 1),
            "decode_tokens_per_s_single": round(tokens / dt_single, 1),
            "migrated_pages_per_s": round(pages / dt_disagg, 1),
            "migrated_pages": int(pages),
            "outputs_identical":
                bool(outs_single == outs_mixed == outs_disagg),
            "prefill_engines": n_prefill,
            "decode_engines": n_decode,
            "prompt_len": prompt_len,
            "reps": reps,
            "requests": len(prompts),
            "params_m": round(count_params(params) / 1e6)},
    }


def run_serving_resilience_bench() -> dict:
    """Serving-resilience chaos bench: a supervised engine
    (dla_tpu/serving/resilience) driven through the full serving fault
    plan — a wedged step, a device error, NaN logits, and a request
    burst — with admission control on. The headline is requests lost
    (MUST be 0: every submitted request reaches a terminal state, work
    is replayed across engine rebuilds, overload is shed explicitly);
    detail carries the shed rate, p99 TTFT under the burst, restart
    count and breaker state. Deterministic, CPU-sized, in-process."""
    import jax
    import numpy as np
    from dla_tpu.generation.engine import GenerationConfig
    from dla_tpu.models.config import ModelConfig
    from dla_tpu.serving import (
        RequestState,
        ServingConfig,
        ServingEngine,
        Supervisor,
        SupervisorConfig,
        TERMINAL_STATES,
    )
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.utils.logging import percentile

    cfg = ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=192,
        num_layers=2, num_heads=4, num_kv_heads=4,
        max_seq_length=128, remat="none", dtype="float32",
        param_dtype="float32")
    model = Transformer(cfg)
    params = model.init(jax.random.key(0))
    gen = GenerationConfig(max_new_tokens=10, do_sample=False,
                           eos_token_id=-1)
    plan = ("engine_step=2:wedge:0.3;engine_step=4:device_error;"
            "engine_step=6:nan_logits;engine_step=8:burst=8")
    engines = []

    def factory():
        eng = ServingEngine(model, params, gen, ServingConfig(
            page_size=4, num_pages=64, num_slots=2, max_model_len=32,
            fault_plan=plan,
            shed={"max_queue_depth": 6}))
        engines.append(eng)
        return eng

    sup = Supervisor(factory, SupervisorConfig(
        watchdog_timeout_s=0.05, watchdog_poll_s=0.01, max_restarts=3))
    rs = np.random.RandomState(0)
    # uniform prompt length: one prefill bucket, so the only compile-
    # exempt watchdog window is each engine's first step
    prompts = [list(rs.randint(3, 500, (6,)).astype(int))
               for _ in range(8)]
    for p in prompts:
        sup.submit(p, 10)
    results = sup.run()
    sup.close()
    reqs = list(results.values())
    lost = sum(1 for r in reqs if r.state not in TERMINAL_STATES)
    shed = sum(1 for r in reqs if r.state is RequestState.SHED)
    ttfts = [(r.first_token_time - r.arrival_time) * 1000.0
             for r in reqs if r.first_token_time is not None]
    return {
        "metric": "serving_requests_lost",
        "value": lost,
        "unit": "requests",
        "detail": {
            "requests_lost": lost,
            "requests_total": len(reqs),
            "shed_rate": round(shed / max(len(reqs), 1), 4),
            "ttft_ms_p99": round(percentile(ttfts, 99.0), 2)
            if ttfts else None,
            "restarts": sup.restarts,
            "failures": sup.failures,
            "breaker_tripped": bool(sup.tripped),
            "replayed_requests": sup.replayed,
            "decode_compiles_per_engine": [
                e.decode_compiles for e in engines],
            "params_m": round(count_params(params) / 1e6)},
    }


def run_serving_gateway_bench() -> dict:
    """Gateway wire-overhead + federation chaos bench (serving.gateway
    / serving.federation). Two passes on the same greedy trace:

      1. retention — the trace in-process vs over localhost HTTP
         through one streaming gateway (SSE per-token events); the
         headline is wire tokens/s as a fraction of in-process
         (>= 0.9 expected: serialization + loopback must not dominate
         a CPU-sized decode)
      2. chaos — the trace through a TWO-gateway federation with a
         ``net=`` fault plan (delay, drop, disconnect mid-stream);
         requests_lost MUST be 0 (dropped / disconnected streams are
         replayed bit-identically from the router journal) and outputs
         stay identical to in-process

    Deterministic, CPU-sized, in-process (sockets on loopback only)."""
    import http.client
    import tempfile
    import threading
    import time

    import jax
    import numpy as np
    from dla_tpu.generation.engine import GenerationConfig
    from dla_tpu.models.config import ModelConfig
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.resilience.faults import FaultPlan
    from dla_tpu.serving import (
        FederatedRouter,
        FederationConfig,
        GossipBeater,
        ServingConfig,
        ServingEngine,
        ServingGateway,
    )

    cfg = ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=192,
        num_layers=2, num_heads=4, num_kv_heads=4,
        max_seq_length=128, remat="none", dtype="float32",
        param_dtype="float32")
    model = Transformer(cfg)
    params = model.init(jax.random.key(0))
    new_tokens = 8
    gen = GenerationConfig(max_new_tokens=new_tokens, do_sample=False,
                           eos_token_id=-1)
    kw = dict(page_size=4, num_pages=64, num_slots=2, max_model_len=32,
              prefill_chunk=4, prefix_cache=True,
              fault_plan="")

    def make_engine():
        return ServingEngine(model, params, gen, ServingConfig(**kw))

    rs = np.random.RandomState(0)
    prompts = [[int(t) for t in rs.randint(3, 500, (6,))]
               for _ in range(8)]

    def http_generate(port, prompt):
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=300)
        try:
            conn.request("POST", "/v1/generate", json.dumps(
                {"prompt": prompt, "max_new_tokens": new_tokens}
            ).encode(), {"Content-Type": "application/json"})
            resp = conn.getresponse()
            toks = []
            while True:
                line = resp.readline()
                if not line:
                    break
                line = line.strip()
                if not line.startswith(b"data: "):
                    continue
                ev = json.loads(line[len(b"data: "):])
                if ev.get("done"):
                    break
                toks.append(int(ev["token"]))
            return toks
        finally:
            conn.close()

    # compile-warm prompts: same length/count as the measured trace
    # (covers the full prefill batch + both-slots decode shapes) but
    # disjoint tokens, so the prefix cache stays cold for the clock
    warm_prompts = [[1 + (i + j) % 2 for i in range(6)]
                    for j in range(len(prompts))]

    def drive_wire(port, batch):
        """The trace over the wire with one concurrent client per
        request — the engine batches exactly as the in-process arm."""
        out = [None] * len(batch)

        def client(i):
            out[i] = http_generate(port, batch[i])
        ts = [threading.Thread(target=client, args=(i,),
                               name=f"dla-bench-gwclient-{i}",
                               daemon=True)
              for i in range(len(batch))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300)
        return out

    # pass 1: retention ------------------------------------------------
    eng = make_engine()
    for p in warm_prompts:             # compile warm, off the clock
        eng.submit(p, new_tokens)
    eng.run_until_drained()
    t0 = time.perf_counter()
    rids = [eng.submit(p, new_tokens) for p in prompts]
    results = eng.run_until_drained(max_steps=5000)
    dt_in = time.perf_counter() - t0
    ref = [list(results[r].generated) for r in rids]
    tokens = sum(len(o) for o in ref)

    gw = ServingGateway(make_engine())
    drive_wire(gw.port, warm_prompts)      # wire + compile warm
    t0 = time.perf_counter()
    wire = [list(o) for o in drive_wire(gw.port, prompts)]
    dt_wire = time.perf_counter() - t0
    gw.close()
    retention = (tokens / dt_wire) / (tokens / dt_in)

    # pass 2: federation chaos ----------------------------------------
    gdir = tempfile.mkdtemp(prefix="dla-gw-bench-")
    gws = [ServingGateway(make_engine()) for _ in range(2)]
    beats = [GossipBeater(g, gdir, n) for g, n in zip(gws, "ab")]
    plan = FaultPlan.parse(
        "net=3:delay:0.01;net=5:drop;net=8:disconnect")
    fed = FederatedRouter(gdir, FederationConfig(),
                          fault_plan=plan)
    deadline = time.monotonic() + 10
    while len(fed.live_peers()) < 2 and time.monotonic() < deadline:
        time.sleep(0.05)
    fids = [fed.submit(p, new_tokens) for p in prompts]
    out = fed.results(timeout_s=300)
    chaos = [out[f].tokens for f in fids]
    lost = fed.requests_lost
    for b in beats:
        b.stop()
    for g in gws:
        g.close()

    return {
        "metric": "serving_gateway_wire_retention",
        "value": round(retention, 4),
        "unit": "x",
        "detail": {
            "tokens_per_s_in_process": round(tokens / dt_in, 1),
            "tokens_per_s_wire": round(tokens / dt_wire, 1),
            "wire_overhead_ms_per_token": round(
                1e3 * (dt_wire - dt_in) / max(tokens, 1), 3),
            "requests_lost": lost,
            "requests_total": len(prompts),
            "replayed_requests": fed.replayed,
            "faults_injected": 3,
            "outputs_identical_wire": bool(wire == ref),
            "outputs_identical_chaos": bool(chaos == ref),
            "new_tokens": new_tokens,
            "params_m": round(count_params(params) / 1e6)},
    }


def run_observability_bench() -> dict:
    """Distributed-tracing overhead target (telemetry.trace_context /
    tools/trace_merge.py): the same greedy wire trace through a
    streaming gateway twice — process tracing OFF (the disabled
    default: the zero-work-when-disabled pin) vs ON (enabled tracer +
    per-process span spool) — reporting the wire throughput fraction
    tracing costs. The detail pins the contract: measured-section
    engine compile counts identical across arms (tracing adds zero
    compiles), outputs bit-identical, zero ring drops and spool write
    errors in the traced arm, and the traced arm's spool must merge
    into a strictly valid Chrome trace via tools/trace_merge.py.

    Deterministic, CPU-sized, in-process (sockets on loopback only)."""
    import http.client
    import shutil
    import tempfile
    import threading
    import time
    from pathlib import Path

    import jax
    import numpy as np
    from dla_tpu.generation.engine import GenerationConfig
    from dla_tpu.models.config import ModelConfig
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.serving import ServingConfig, ServingEngine, \
        ServingGateway
    from dla_tpu.telemetry.trace import Tracer, get_tracer, \
        install_tracer

    cfg = ModelConfig(
        vocab_size=512, hidden_size=64, intermediate_size=192,
        num_layers=2, num_heads=4, num_kv_heads=4,
        max_seq_length=128, remat="none", dtype="float32",
        param_dtype="float32")
    model = Transformer(cfg)
    params = model.init(jax.random.key(0))
    new_tokens = 8
    gen = GenerationConfig(max_new_tokens=new_tokens, do_sample=False,
                           eos_token_id=-1)
    kw = dict(page_size=4, num_pages=64, num_slots=2, max_model_len=32,
              prefill_chunk=4, prefix_cache=True,
              fault_plan="")
    rs = np.random.RandomState(0)
    prompts = [[int(t) for t in rs.randint(3, 500, (6,))]
               for _ in range(8)]
    warm_prompts = [[1 + (i + j) % 2 for i in range(6)]
                    for j in range(len(prompts))]

    def http_generate(port, prompt):
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=300)
        try:
            conn.request("POST", "/v1/generate", json.dumps(
                {"prompt": prompt, "max_new_tokens": new_tokens}
            ).encode(), {"Content-Type": "application/json"})
            resp = conn.getresponse()
            toks = []
            while True:
                line = resp.readline()
                if not line:
                    break
                line = line.strip()
                if not line.startswith(b"data: "):
                    continue
                ev = json.loads(line[len(b"data: "):])
                if ev.get("done"):
                    break
                toks.append(int(ev["token"]))
            return toks
        finally:
            conn.close()

    def drive_wire(port, batch):
        out = [None] * len(batch)

        def client(i):
            out[i] = http_generate(port, batch[i])
        ts = [threading.Thread(target=client, args=(i,),
                               name=f"dla-bench-obsclient-{i}",
                               daemon=True)
              for i in range(len(batch))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300)
        return out

    # Interleaved best-of-N A/B against ONE gateway instance. The
    # 2-slot CPU wire drive is bimodal (an engine-loop idle-poll park
    # just as submits land serializes the tiny batch) and the mode is
    # sticky per process phase — separate per-arm gateways measure
    # scheduler luck, not tracing. Toggling the process tracer between
    # measured drives on the same gateway hits both arms with the same
    # artifact; max over reps is the steady-state throughput per arm.
    reps = 5
    spool = tempfile.mkdtemp(prefix="dla-obs-spool-")
    prev = get_tracer()
    traced = Tracer.from_config(
        {"enabled": True, "capacity": 1 << 17,
         "spool_dir": spool, "proc": "gateway"})
    eng = ServingEngine(model, params, gen, ServingConfig(**kw))
    gw = ServingGateway(eng)
    try:
        drive_wire(gw.port, warm_prompts)   # compile + wire warm
        install_tracer(traced)
        drive_wire(gw.port, warm_prompts)   # traced-path + spool warm
        install_tracer(prev)
        c0 = (eng.decode_compiles, eng.prefill_chunk_compiles)
        best = {False: 0.0, True: 0.0}
        outs = {False: None, True: None}
        for _ in range(reps):
            for arm in (False, True):
                install_tracer(traced if arm else prev)
                t0 = time.perf_counter()
                rep = [list(o)
                       for o in drive_wire(gw.port, prompts)]
                dt = time.perf_counter() - t0
                tps = sum(len(o) for o in rep) / dt
                if outs[arm] is None or tps > best[arm]:
                    best[arm], outs[arm] = tps, rep
        # summed over ALL measured drives of BOTH arms — tracing must
        # add zero compiles, so the pinned total is (0, 0)
        compiles = (eng.decode_compiles - c0[0],
                    eng.prefill_chunk_compiles - c0[1])
    finally:
        install_tracer(prev)
        gw.close()
    stats = {"spooled": traced.spooled, "dropped": traced.dropped,
             "spool_errors": traced.spool_errors}
    traced.detach_spool()
    off_tps, on_tps = best[False], best[True]
    off_out, on_out = outs[False], outs[True]
    off_compiles = on_compiles = compiles

    from tools.trace_merge import merge_dir, validate
    merged = merge_dir(Path(spool))
    problems = validate(merged)
    n_spans = sum(1 for e in merged["traceEvents"]
                  if e.get("ph") == "X")
    shutil.rmtree(spool, ignore_errors=True)

    return {
        "metric": "observability_wire_overhead_frac",
        "value": round(1.0 - on_tps / max(off_tps, 1e-9), 4),
        "unit": "fraction",
        "detail": {
            "tokens_per_s_traced_off": round(off_tps, 1),
            "tokens_per_s_traced_on": round(on_tps, 1),
            # must be equal across arms: tracing adds zero compiles to
            # the measured section (both expected (0, 0) post-warm)
            "compiles_measured_off": list(off_compiles),
            "compiles_measured_on": list(on_compiles),
            "outputs_identical": bool(on_out == off_out),
            "trace_spooled_records": int(stats.get("spooled", 0)),
            "trace_dropped": int(stats.get("dropped", 0)),
            "trace_spool_errors": int(stats.get("spool_errors", 0)),
            "merged_trace_valid": not problems,
            "merged_trace_spans": int(n_spans),
            "new_tokens": new_tokens,
            "params_m": round(count_params(params) / 1e6)},
    }


def run_resilience_bench() -> dict:
    """Recovery-overhead microbench for the fault-tolerance stack
    (dla_tpu/resilience): one tiny SFT run with an injected checkpoint
    io_error AND an injected NaN step, async checkpointing on. Reports
    what resilience costs when faults actually happen:

      - checkpoint stall ms — how long save() blocked the step loop
        (async: host-snapshot only), vs the same save through the
        synchronous Checkpointer
      - steps lost — extra step executions the NaN guard spent
        (retries); with a one-shot transient fault the retry succeeds,
        so the run still reaches max_steps with zero skipped data
      - io retries — backoff retries the background writer needed

    Deterministic, CPU-sized, in-process."""
    import shutil as _shutil
    import tempfile

    import jax
    from dla_tpu.checkpoint.checkpointer import Checkpointer
    from dla_tpu.models.config import ModelConfig
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.ops.fused_ce import model_fused_ce
    from dla_tpu.parallel.mesh import MeshConfig, build_mesh
    from dla_tpu.training.trainer import Trainer

    cfg = ModelConfig(
        vocab_size=256, hidden_size=64, intermediate_size=192,
        num_layers=2, num_heads=4, num_kv_heads=4,
        max_seq_length=64, remat="none", dtype="float32",
        param_dtype="float32")
    micro, seq, max_steps, save_every = 2, 64, 8, 2
    mesh = build_mesh(MeshConfig(data=1, fsdp=-1, model=1, sequence=1))
    model = Transformer(cfg)

    def loss_fn(p, frozen, batch, rng):
        del frozen, rng
        loss, _ = model_fused_ce(model, p, batch)
        return loss, {}

    rs = np.random.RandomState(0)

    def batches():
        local_bs = micro * mesh.devices.size
        while True:
            yield {
                "input_ids": rs.randint(1, cfg.vocab_size, (local_bs, seq)
                                        ).astype(np.int32),
                "attention_mask": np.ones((local_bs, seq), np.int32),
                "labels": rs.randint(1, cfg.vocab_size, (local_bs, seq)
                                     ).astype(np.int32),
            }

    out_dir = tempfile.mkdtemp(prefix="dla_bench_resil_")
    try:
        config = {
            "experiment_name": "bench_resilience",
            "optimization": {
                "total_batch_size": micro * mesh.devices.size,
                "micro_batch_size": micro, "learning_rate": 1e-4,
                "max_train_steps": max_steps, "lr_scheduler": "constant",
                "max_grad_norm": 1.0,
            },
            "logging": {"output_dir": out_dir, "log_dir": None,
                        "save_every_steps": save_every,
                        "log_every_steps": 10 ** 6},
            "hardware": {"gradient_accumulation_steps": 1},
            "resilience": {
                "async_checkpointing": True,
                "save_retries": 3, "retry_backoff_s": 0.05,
                # io_error hits the background writer of the step-2 save;
                # nan hits the forward of step 5 (one-shot -> the guard's
                # retry of the same batch recovers bit-exactly)
                "fault_plan": "step=2:io_error;step=5:nan",
            },
        }
        with jax.sharding.set_mesh(mesh):
            trainer = Trainer(config=config, mesh=mesh, loss_fn=loss_fn,
                              params=model.init(jax.random.key(0)),
                              param_specs=model.partition_specs())
            trainer.fit(batches(), rng=jax.random.key(1))
            trainer.checkpoint_wait()
            ck = trainer.checkpointer
            async_stall = (ck.total_stall_ms
                           / max(1, ck.saves_started))
            retries = ck.retries_total
            bad_steps = trainer.guard.bad_steps_total
            final_step = trainer.step

            # the comparison bar: the same state through the blocking
            # Checkpointer — what every save used to cost the step loop
            sync = Checkpointer(out_dir + "/sync", keep_last_n=1)
            t0 = time.perf_counter()
            sync.save(final_step, trainer._state_tree(), {"step": final_step})
            sync_stall = (time.perf_counter() - t0) * 1000.0
    finally:
        _shutil.rmtree(out_dir, ignore_errors=True)

    return {
        "metric": "resilience_checkpoint_stall_ms",
        "value": round(async_stall, 3),
        "unit": "ms",
        "vs_baseline": round(async_stall / max(sync_stall, 1e-9), 4),
        "detail": {
            # steps lost = retried executions; the run still reaches
            # max_steps (transient NaN retried on the same batch)
            "steps_lost_to_faults": int(bad_steps),
            "final_step": int(final_step),
            "target_steps": int(max_steps),
            "io_retries": int(retries),
            "async_stall_ms_per_save": round(async_stall, 3),
            "sync_save_ms": round(sync_stall, 3),
            "saves_completed": int(ck.saves_completed),
            "fault_plan": "step=2:io_error;step=5:nan",
        },
    }


def run_elastic_resilience_bench() -> dict:
    """Host-loss recovery bench for the elastic gang
    (dla_tpu/resilience/elastic): a simulated 8-host pod loses host 1
    mid-run (fault plan ``host=1:step=6:lost``), the gang detects the
    stale lease within ``lease_ttl_steps``, exits resumably, and the
    run resumes on a 4-device mesh from the latest checkpoint with the
    global batch preserved (grad accum recomputed). Reports:

      - steps replayed — detection step minus the resumed-from step
        (work re-done because the outage landed between saves)
      - detection lag — steps from the injected loss to the agreed
        shrink (bounded by lease_ttl_steps)
      - elastic badput — the detect -> restart -> resume gap as the
        resumed run's ``telemetry/badput_elastic`` fraction

    Deterministic, CPU-sized, in-process."""
    import shutil as _shutil
    import tempfile

    import jax
    from dla_tpu.models.config import ModelConfig
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.ops.fused_ce import model_fused_ce
    from dla_tpu.parallel.mesh import MeshConfig, build_mesh
    from dla_tpu.resilience import ElasticRestart
    from dla_tpu.training.trainer import Trainer

    cfg = ModelConfig(
        vocab_size=256, hidden_size=64, intermediate_size=192,
        num_layers=2, num_heads=4, num_kv_heads=4,
        max_seq_length=64, remat="none", dtype="float32",
        param_dtype="float32")
    seq, max_steps, save_every = 64, 12, 4
    lease_ttl_steps, fault_step, lost_host = 3, 5, 1
    devices = jax.devices()
    if len(devices) < 8:
        return {"metric": "elastic_steps_replayed",
                "error": f"needs 8 CPU devices, have {len(devices)}"}
    mesh8 = build_mesh(MeshConfig(data=1, fsdp=8, model=1, sequence=1),
                       devices=devices[:8])
    mesh4 = build_mesh(MeshConfig(data=1, fsdp=4, model=1, sequence=1),
                       devices=devices[:4])
    model = Transformer(cfg)

    def loss_fn(p, frozen, batch, rng):
        del frozen, rng
        loss, _ = model_fused_ce(model, p, batch)
        return loss, {}

    def batches():
        rs = np.random.RandomState(0)
        while True:
            yield {
                "input_ids": rs.randint(1, cfg.vocab_size, (8, seq)
                                        ).astype(np.int32),
                "attention_mask": np.ones((8, seq), np.int32),
                "labels": rs.randint(1, cfg.vocab_size, (8, seq)
                                     ).astype(np.int32),
            }

    def make_config(out_dir, world, fault_plan=""):
        return {
            "experiment_name": "bench_elastic",
            "optimization": {
                "total_batch_size": 8, "micro_batch_size": 1,
                "learning_rate": 1e-4, "max_train_steps": max_steps,
                "lr_scheduler": "constant", "max_grad_norm": 1.0,
            },
            "data": {"prefetch": 0},
            "logging": {"output_dir": out_dir, "log_dir": None,
                        "save_every_steps": save_every,
                        "log_every_steps": 10 ** 6},
            "hardware": {"gradient_accumulation_steps": 1},
            "resilience": {
                "fault_plan": fault_plan,
                "elastic": {"enabled": True, "lease_ttl_s": 0,
                            "lease_ttl_steps": lease_ttl_steps,
                            "sim_world": world},
            },
        }

    out_dir = tempfile.mkdtemp(prefix="dla_bench_elastic_")
    try:
        fault_plan = f"host={lost_host}:step={fault_step}:lost"
        with jax.sharding.set_mesh(mesh8):
            trainer = Trainer(
                config=make_config(out_dir, 8, fault_plan), mesh=mesh8,
                loss_fn=loss_fn, params=model.init(jax.random.key(0)),
                param_specs=model.partition_specs())
            detect_step = None
            try:
                trainer.fit(batches(), rng=jax.random.key(1))
            except ElasticRestart as exc:
                detect_step = exc.step
        if detect_step is None:
            return {"metric": "elastic_steps_replayed",
                    "error": "host loss was never detected"}
        with jax.sharding.set_mesh(mesh4):
            resumed = Trainer(
                config=make_config(out_dir, 4), mesh=mesh4,
                loss_fn=loss_fn, params=model.init(jax.random.key(0)),
                param_specs=model.partition_specs())
            resumed.fit(batches(), rng=jax.random.key(1), resume=True)
            resume_step = None
            for ev in resumed.recorder.events:
                if ev["kind"] == "elastic_resume":
                    resume_step = ev["step"]
            badput = resumed.clock.badput()["elastic"]
            final_step = resumed.step
    finally:
        _shutil.rmtree(out_dir, ignore_errors=True)

    replayed = detect_step - (resume_step or 0)
    return {
        "metric": "elastic_steps_replayed",
        "value": int(replayed),
        "unit": "steps",
        # a full save interval is the worst case for an outage landing
        # right before a save; <1.0 means detection beat the cadence
        "vs_baseline": round(replayed / save_every, 4),
        "detail": {
            "detect_step": int(detect_step),
            "resumed_from_step": int(resume_step or 0),
            "detection_lag_steps": int(detect_step - fault_step),
            "lease_ttl_steps": int(lease_ttl_steps),
            "badput_elastic": round(float(badput), 6),
            "final_step": int(final_step),
            "target_steps": int(max_steps),
            "train_step_compiles": int(resumed.train_step_compiles),
            "fault_plan": fault_plan,
        },
    }


def run_telemetry_bench() -> dict:
    """Telemetry-overhead microbench (dla_tpu/telemetry): the same tiny
    SFT run twice — telemetry on (step clock + in-graph collector +
    flight recorder + registry mirror) vs ``logging.telemetry.enabled:
    false`` — reporting ms/step overhead and the ratio. The collector
    rides the one jitted step (train_step_compiles stays 1, asserted),
    so the expected overhead is host-side accounting only: a few
    perf_counter calls per step.

    Deterministic, CPU-sized, in-process."""
    import shutil as _shutil
    import tempfile

    import jax
    from dla_tpu.models.config import ModelConfig
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.ops.fused_ce import model_fused_ce
    from dla_tpu.parallel.mesh import MeshConfig, build_mesh
    from dla_tpu.training.trainer import Trainer

    cfg = ModelConfig(
        vocab_size=256, hidden_size=64, intermediate_size=192,
        num_layers=2, num_heads=4, num_kv_heads=4,
        max_seq_length=64, remat="none", dtype="float32",
        param_dtype="float32")
    micro, seq, max_steps = 2, 64, 24
    mesh = build_mesh(MeshConfig(data=1, fsdp=-1, model=1, sequence=1))
    model = Transformer(cfg)

    def loss_fn(p, frozen, batch, rng):
        del frozen, rng
        loss, _ = model_fused_ce(model, p, batch)
        return loss, {}

    def batches(seed):
        rs = np.random.RandomState(seed)
        local_bs = micro * mesh.devices.size
        while True:
            yield {
                "input_ids": rs.randint(1, cfg.vocab_size, (local_bs, seq)
                                        ).astype(np.int32),
                "attention_mask": np.ones((local_bs, seq), np.int32),
                "labels": rs.randint(1, cfg.vocab_size, (local_bs, seq)
                                     ).astype(np.int32),
            }

    def one_run(enabled: bool) -> tuple:
        out_dir = tempfile.mkdtemp(prefix="dla_bench_tel_")
        try:
            config = {
                "experiment_name": "bench_telemetry",
                "optimization": {
                    "total_batch_size": micro * mesh.devices.size,
                    "micro_batch_size": micro, "learning_rate": 1e-4,
                    "max_train_steps": max_steps,
                    "lr_scheduler": "constant", "max_grad_norm": 1.0,
                },
                "logging": {"output_dir": out_dir, "log_dir": None,
                            "save_every_steps": 0,
                            "log_every_steps": 8,
                            "telemetry": {"enabled": enabled}},
                "hardware": {"gradient_accumulation_steps": 1},
                "resilience": {"watchdog": {"enabled": False}},
            }
            with jax.sharding.set_mesh(mesh):
                trainer = Trainer(config=config, mesh=mesh,
                                  loss_fn=loss_fn,
                                  params=model.init(jax.random.key(0)),
                                  param_specs=model.partition_specs())
                t0 = time.perf_counter()
                trainer.fit(batches(0), rng=jax.random.key(1))
                wall = time.perf_counter() - t0
                return (wall * 1000.0 / max_steps,
                        trainer.train_step_compiles,
                        trainer.clock.goodput())
        finally:
            _shutil.rmtree(out_dir, ignore_errors=True)

    base_ms, base_compiles, _ = one_run(enabled=False)
    tel_ms, tel_compiles, goodput = one_run(enabled=True)
    overhead_ms = tel_ms - base_ms

    return {
        "metric": "telemetry_overhead_ms_per_step",
        "value": round(overhead_ms, 3),
        "unit": "ms",
        # ratio of instrumented to bare step time: ~1.0 = free telemetry
        "vs_baseline": round(tel_ms / max(base_ms, 1e-9), 4),
        "detail": {
            "base_ms_per_step": round(base_ms, 3),
            "telemetry_ms_per_step": round(tel_ms, 3),
            "goodput": round(goodput, 4),
            # both must be 1: the collector adds ZERO extra compiles
            "train_step_compiles_base": int(base_compiles),
            "train_step_compiles_telemetry": int(tel_compiles),
            "steps": int(max_steps),
        },
    }


def run_introspect_bench() -> dict:
    """XLA-introspection overhead target (dla_tpu/telemetry/
    xla_introspect): the same tiny SFT run twice with telemetry on —
    ``xla_introspect.enabled: true`` (AOT-dispatching wrapper,
    per-call argument fingerprinting, cost/memory gauges) vs ``false``
    (plain jit dispatch) — reporting ms/step overhead. Also asserts the
    wrapper's zero-extra-compile contract (train_step_compiles == 1
    both ways) and surfaces the compiled-fn analytics the wrapper read.

    Deterministic, CPU-sized, in-process."""
    import shutil as _shutil
    import tempfile

    import jax
    from dla_tpu.models.config import ModelConfig
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.ops.fused_ce import model_fused_ce
    from dla_tpu.parallel.mesh import MeshConfig, build_mesh
    from dla_tpu.training.trainer import Trainer

    cfg = ModelConfig(
        vocab_size=256, hidden_size=64, intermediate_size=192,
        num_layers=2, num_heads=4, num_kv_heads=4,
        max_seq_length=64, remat="none", dtype="float32",
        param_dtype="float32")
    micro, seq, max_steps = 2, 64, 24
    mesh = build_mesh(MeshConfig(data=1, fsdp=-1, model=1, sequence=1))
    model = Transformer(cfg)

    def loss_fn(p, frozen, batch, rng):
        del frozen, rng
        loss, _ = model_fused_ce(model, p, batch)
        return loss, {}

    def batches(seed):
        rs = np.random.RandomState(seed)
        local_bs = micro * mesh.devices.size
        while True:
            yield {
                "input_ids": rs.randint(1, cfg.vocab_size, (local_bs, seq)
                                        ).astype(np.int32),
                "attention_mask": np.ones((local_bs, seq), np.int32),
                "labels": rs.randint(1, cfg.vocab_size, (local_bs, seq)
                                     ).astype(np.int32),
            }

    def one_run(introspect: bool) -> tuple:
        out_dir = tempfile.mkdtemp(prefix="dla_bench_xi_")
        try:
            config = {
                "experiment_name": "bench_introspect",
                "optimization": {
                    "total_batch_size": micro * mesh.devices.size,
                    "micro_batch_size": micro, "learning_rate": 1e-4,
                    "max_train_steps": max_steps,
                    "lr_scheduler": "constant", "max_grad_norm": 1.0,
                },
                "logging": {"output_dir": out_dir, "log_dir": None,
                            "save_every_steps": 0,
                            "log_every_steps": 8,
                            "telemetry": {"enabled": True,
                                          "xla_introspect": {
                                              "enabled": introspect}}},
                "hardware": {"gradient_accumulation_steps": 1},
                "resilience": {"watchdog": {"enabled": False}},
            }
            with jax.sharding.set_mesh(mesh):
                trainer = Trainer(config=config, mesh=mesh,
                                  loss_fn=loss_fn,
                                  params=model.init(jax.random.key(0)),
                                  param_specs=model.partition_specs())
                t0 = time.perf_counter()
                trainer.fit(batches(0), rng=jax.random.key(1))
                wall = time.perf_counter() - t0
                stats = dict(getattr(trainer._jit_train_step, "stats",
                                     None) or {})
                return (wall * 1000.0 / max_steps,
                        trainer.train_step_compiles, stats)
        finally:
            _shutil.rmtree(out_dir, ignore_errors=True)

    base_ms, base_compiles, _ = one_run(introspect=False)
    xi_ms, xi_compiles, stats = one_run(introspect=True)
    overhead_ms = xi_ms - base_ms

    return {
        "metric": "introspect_overhead_ms_per_step",
        "value": round(overhead_ms, 3),
        "unit": "ms",
        # ratio of introspected to plain-jit step time: ~1.0 = free
        "vs_baseline": round(xi_ms / max(base_ms, 1e-9), 4),
        "detail": {
            "base_ms_per_step": round(base_ms, 3),
            "introspect_ms_per_step": round(xi_ms, 3),
            # both must be 1: the AOT wrapper adds ZERO extra compiles
            "train_step_compiles_base": int(base_compiles),
            "train_step_compiles_introspect": int(xi_compiles),
            "xla_flops": stats.get("flops"),
            "xla_bytes_accessed": stats.get("bytes_accessed"),
            "roofline_compute_bound": stats.get("roofline_compute_bound"),
            "steps": int(max_steps),
        },
    }


def _emit(extra: bool) -> None:
    """Print the headline SFT line; with ``--extra`` also measure PPO
    rollout+update, decode, serving and shared-prefix serving, and write
    them all to BENCH_extra.json. A phase that raises fails the run."""
    headline = run_bench()
    print(json.dumps(headline))
    if not extra:
        return
    rows = [headline]
    # the phases sized for the chip; the CPU count-demos are reached by
    # name (see main) and report no speed
    for fn in (run_ppo_bench, run_decode_bench, run_serving_bench,
               run_serving_prefix_bench):
        res = fn()
        print(json.dumps(res), file=sys.stderr)
        rows.append(res)
    # each artifact carries its provenance (commit + wall time)
    import datetime
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=_REPO_ROOT,
            capture_output=True, text=True, timeout=10).stdout.strip()
    except OSError:         # the measuring machine may carry no git
        commit = ""
    rows.append({"provenance": {
        "commit": commit or "unknown",
        "utc": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds")}})
    with open(os.path.join(_REPO_ROOT, "BENCH_extra.json"), "w") as fh:
        json.dump(rows, fh, indent=1)


def main() -> int:
    if "resilience" in sys.argv[1:]:
        # fault-tolerance recovery-overhead target: deterministic and
        # CPU-sized, so it runs in-process on the forced-CPU platform
        from _cpuhost import force_cpu_platform
        force_cpu_platform()
        print(json.dumps(run_resilience_bench()))
        return 0
    if "elastic-resilience" in sys.argv[1:]:
        # host-loss chaos target: simulated 8-host gang loses a host and
        # resumes at 4 devices; needs the 8-device virtual CPU mesh
        from _cpuhost import force_cpu_platform
        force_cpu_platform(8)
        print(json.dumps(run_elastic_resilience_bench()))
        return 0
    if "rollout" in sys.argv[1:]:
        # disaggregated-rollout A/B target: same in-process forced-CPU
        # pattern; headline is padding waste recovered (higher better)
        from _cpuhost import force_cpu_platform
        force_cpu_platform()
        print(json.dumps(run_rollout_bench()))
        return 0
    if "rollout-fleet" in sys.argv[1:]:
        # elastic sampler-fleet target: serial-vs-broadcast refit
        # fanout at N=4 (headline, higher better), trajectories/s N=1
        # vs N=4, and steps-lost-to-sampler-death chaos (must be 0)
        from _cpuhost import force_cpu_platform
        force_cpu_platform()
        print(json.dumps(run_rollout_fleet_bench()))
        return 0
    if "serving-spec" in sys.argv[1:]:
        # speculative-serving A/B target: same in-process forced-CPU
        # pattern; headline is decode tokens/s speedup (higher better)
        from _cpuhost import force_cpu_platform
        force_cpu_platform()
        print(json.dumps(run_serving_spec_bench()))
        return 0
    if "serving-fleet" in sys.argv[1:]:
        # fleet-routing A/B/C target: same in-process forced-CPU
        # pattern; headline is routed-vs-random decode speedup
        from _cpuhost import force_cpu_platform
        force_cpu_platform()
        print(json.dumps(run_serving_fleet_bench()))
        return 0
    if "serving-disagg" in sys.argv[1:]:
        # prefill/decode disaggregation A/B target: same in-process
        # forced-CPU pattern; headline is ITL p99 speedup (higher
        # better)
        from _cpuhost import force_cpu_platform
        force_cpu_platform()
        print(json.dumps(run_serving_disagg_bench()))
        return 0
    if "serving-tenant" in sys.argv[1:]:
        # multi-tenant LoRA serving A/B target: same in-process
        # forced-CPU pattern; headline is batched-vs-serial-swap
        # tokens/s speedup, detail pins output identity, one decode
        # compile across the tenant mix, and noisy-tenant isolation
        from _cpuhost import force_cpu_platform
        force_cpu_platform()
        print(json.dumps(run_serving_tenant_bench()))
        return 0
    if "serving-gateway" in sys.argv[1:]:
        # gateway wire-overhead + federation chaos target: same
        # in-process forced-CPU pattern (loopback sockets only);
        # headline is wire tokens/s retention (higher better), detail
        # pins requests_lost to 0 under net= disconnect chaos
        from _cpuhost import force_cpu_platform
        force_cpu_platform()
        print(json.dumps(run_serving_gateway_bench()))
        return 0
    if "observability" in sys.argv[1:]:
        # distributed-tracing overhead target: wire + spool cost with
        # tracing on vs off, compile counts pinned identical across
        # arms and the spool merged via tools/trace_merge.py
        from _cpuhost import force_cpu_platform
        force_cpu_platform()
        print(json.dumps(run_observability_bench()))
        return 0
    if "serving-resilience" in sys.argv[1:]:
        # supervised-serving chaos target: same in-process forced-CPU
        # pattern; headline is requests lost (must be 0)
        from _cpuhost import force_cpu_platform
        force_cpu_platform()
        print(json.dumps(run_serving_resilience_bench()))
        return 0
    if "telemetry" in sys.argv[1:]:
        # telemetry-overhead target: same in-process forced-CPU pattern
        from _cpuhost import force_cpu_platform
        force_cpu_platform()
        print(json.dumps(run_telemetry_bench()))
        return 0
    if "introspect" in sys.argv[1:]:
        # XLA-introspection overhead target: same in-process forced-CPU
        # pattern; headline is ms/step added by the AOT wrapper
        from _cpuhost import force_cpu_platform
        force_cpu_platform()
        print(json.dumps(run_introspect_bench()))
        return 0
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[bench] no TPU: jax.devices()[0].platform is "
              f"{dev.platform!r}; a speed is only measured on the chip",
              file=sys.stderr)
        return 2
    from dla_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    _emit(extra="--extra" in sys.argv)
    return 0


if __name__ == "__main__":
    sys.exit(main())
