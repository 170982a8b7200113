"""On-chip config sweep for the headline SFT bench (round-3 perf work).

Runs several (model shape, remat, micro, flash blocks) variants in one
process on the live TPU and prints tok/s/chip + MFU for each, so bench.py
can ship the measured-fastest configuration. Usage:

    python tools/sweep_bench.py [variant ...]   # default: all

Each variant is timed exactly like bench.py (2 warmup incl. compile, 6
measured steps, synthetic batch, fused CE loss, real Trainer update).
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_variant(name: str, *, hidden=1024, inter=2816, layers=24, heads=16,
                kv_heads=None, micro=8, seq=2048, remat="dots",
                attention="flash", steps=6, warmup=2,
                moment_dtype=None, block_q=0, block_k=0,
                ce_chunk=None, packed=False) -> dict:
    import jax
    from dla_tpu.models.config import ModelConfig
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.ops.fused_ce import model_fused_ce
    from dla_tpu.parallel.mesh import MeshConfig, build_mesh
    from dla_tpu.training.trainer import Trainer
    from bench import BASELINE_MFU, count_params, peak_flops

    cfg = ModelConfig(
        vocab_size=32000, hidden_size=hidden, intermediate_size=inter,
        num_layers=layers, num_heads=heads,
        num_kv_heads=kv_heads if kv_heads is not None else heads,
        max_seq_length=seq, remat=remat, attention=attention,
        flash_block_q=block_q, flash_block_k=block_k)
    mesh = build_mesh(MeshConfig(data=1, fsdp=-1, model=1, sequence=1))
    model = Transformer(cfg)
    params = model.init(jax.random.key(0))
    jax.block_until_ready(params)
    n_params = count_params(params)

    def loss_fn(p, frozen, batch, rng):
        del frozen, rng
        loss, _ = model_fused_ce(
            model, p, batch,
            **({"chunk": ce_chunk} if ce_chunk else {}))
        return loss, {}

    config = {
        "experiment_name": f"sweep_{name}",
        "optimization": {
            "total_batch_size": micro * mesh.devices.size,
            "micro_batch_size": micro, "learning_rate": 1e-4,
            "max_train_steps": steps, "lr_scheduler": "constant",
            "max_grad_norm": 1.0,
            **({"adam_moment_dtype": moment_dtype} if moment_dtype else {}),
        },
        "logging": {"output_dir": "/tmp/dla_sweep_ckpt", "log_dir": None},
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
        if packed:
            # 4 synthetic segments per row: drives the segment-aware
            # flash path exactly like data.packing: true does
            bounds = sorted(rs.choice(np.arange(1, seq), 3, replace=False))
            seg = np.zeros((local_bs, seq), np.int32)
            prev = 0
            for si, bnd in enumerate(list(bounds) + [seq]):
                seg[:, prev:bnd] = si + 1
                prev = bnd
            batch["segment_ids"] = seg
        for i in range(warmup):
            trainer.step_on_batch(batch, jax.random.key(i))
        t0 = time.perf_counter()
        for i in range(steps):
            trainer.step_on_batch(batch, jax.random.key(100 + i))
        dt = time.perf_counter() - t0

    tokens = local_bs * seq * steps
    tok_s = tokens / dt / jax.device_count()
    mfu = tok_s * 6 * n_params / peak_flops(jax.devices()[0])
    row = {"variant": name, "tok_s_chip": round(tok_s, 1),
           "mfu_pct": round(mfu * 100, 2),
           "vs_baseline": round(mfu / BASELINE_MFU, 4),
           "params_m": round(n_params / 1e6),
           "step_ms": round(dt / steps * 1000, 1)}
    print(row, flush=True)
    return row


VARIANTS = {
    # round-2 shipped config: head_dim 64, micro 8 — OOMs on 15.75G HBM
    # (saved flash out [.,.,.,64] pads 2x to 128 lanes; see BENCH log)
    "base_hd64_micro6": dict(micro=6),
    # head_dim 128: same params, MXU-deep attention contractions, no
    # lane padding on saved activations
    "hd128_micro6": dict(heads=8, micro=6),
    # + bf16 Adam first moment frees ~0.75G for the bigger micro
    "hd128_micro8_bf16m": dict(heads=8, micro=8, moment_dtype="bfloat16"),
    "hd128_micro6_bf16m": dict(heads=8, micro=6, moment_dtype="bfloat16"),
    # head_dim 128 + GQA 4 kv heads (mistral-7b's 4x q:kv ratio) — the
    # shipped bench config (31.7k tok/s, 33.7% MFU, vs_baseline 1.05)
    "hd128_kv4_micro8_bf16m": dict(heads=8, kv_heads=4, micro=8,
                                   moment_dtype="bfloat16"),
    "hd128_kv4_micro6_bf16m": dict(heads=8, kv_heads=4, micro=6,
                                   moment_dtype="bfloat16"),
    "hd128_kv4_micro12_bf16m": dict(heads=8, kv_heads=4, micro=12,
                                    moment_dtype="bfloat16"),
    # no remat at small micro (backward skips all recompute)
    "hd128_noremat_micro4_bf16m": dict(heads=8, micro=4, remat="none",
                                       moment_dtype="bfloat16"),
    # flash tile-size sweep around the shipped kv4/micro8 config
    # (256x256 halves the causal diagonal-block waste: 12% vs 25% excess
    # pairs at T=2048 — net win iff per-block bookkeeping stays amortized)
    "kv4_micro8_b256": dict(heads=8, kv_heads=4, micro=8,
                            moment_dtype="bfloat16",
                            block_q=256, block_k=256),
    "kv4_micro8_bq256": dict(heads=8, kv_heads=4, micro=8,
                             moment_dtype="bfloat16", block_q=256),
    "kv4_micro8_bq1024": dict(heads=8, kv_heads=4, micro=8,
                              moment_dtype="bfloat16", block_q=1024),
    "kv4_micro8_b1024": dict(heads=8, kv_heads=4, micro=8,
                             moment_dtype="bfloat16",
                             block_q=1024, block_k=1024),
    "kv4_micro8_bq2048": dict(heads=8, kv_heads=4, micro=8,
                              moment_dtype="bfloat16", block_q=2048),
    # fused-CE chunk sweep (rows per [chunk, V] fp32 logit tile)
    "kv4_micro8_ce512": dict(heads=8, kv_heads=4, micro=8,
                             moment_dtype="bfloat16", ce_chunk=512),
    "kv4_micro8_ce2048": dict(heads=8, kv_heads=4, micro=8,
                              moment_dtype="bfloat16", ce_chunk=2048),
    "kv4_micro8_ce4096": dict(heads=8, kv_heads=4, micro=8,
                              moment_dtype="bfloat16", ce_chunk=4096),
    # odd micro between the 8-OOM-at-hd64 and 12-OOM-at-hd128 cliffs
    "kv4_micro10": dict(heads=8, kv_heads=4, micro=10,
                        moment_dtype="bfloat16"),
    # round-5: the two independent wins measured above (1024-blocks
    # 1.0714, ce4096 1.065) combined, plus one step further on each
    "kv4_micro8_b1024_ce4096": dict(heads=8, kv_heads=4, micro=8,
                                    moment_dtype="bfloat16",
                                    block_q=1024, block_k=1024,
                                    ce_chunk=4096),
    "kv4_micro8_b1024_ce8192": dict(heads=8, kv_heads=4, micro=8,
                                    moment_dtype="bfloat16",
                                    block_q=1024, block_k=1024,
                                    ce_chunk=8192),
    "kv4_micro8_b2048_ce4096": dict(heads=8, kv_heads=4, micro=8,
                                    moment_dtype="bfloat16",
                                    block_q=2048, block_k=1024,
                                    ce_chunk=4096),
    # the flagship packing:true path — segment ids through the
    # segment-aware flash kernel (fwd + bwd)
    "kv4_micro8_packed": dict(heads=8, kv_heads=4, micro=8,
                              moment_dtype="bfloat16", packed=True),
    # long context: 32k tokens in one sequence, O(T) flash memory,
    # full remat (activation stash at 32k doesn't fit "dots")
    "kv4_seq32k_micro1": dict(heads=8, kv_heads=4, micro=1, seq=32768,
                              remat="full", moment_dtype="bfloat16",
                              steps=3, warmup=1),
}


def main():
    names = sys.argv[1:] or list(VARIANTS)
    if len(names) == 1:
        # child mode: one variant in this process
        n = names[0]
        try:
            run_variant(n, **VARIANTS[n])
        except Exception as e:  # OOM etc
            print({"variant": n, "error": f"{type(e).__name__}: {e}"[:300]},
                  flush=True)
            sys.exit(1)
        return
    # parent mode: FRESH process per variant — a variant that OOMs (or
    # even completes) leaves buffers behind that poison later compiles in
    # the same TPU client (observed: every variant after the first fails
    # RESOURCE_EXHAUSTED in-process). This parent never imports jax: a
    # chip belongs to one process at a time.
    import subprocess
    failed = [
        n for n in names
        if subprocess.run([sys.executable, os.path.abspath(__file__), n],
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__)))).returncode]
    if failed:
        sys.exit(f"== sweep FAILED for {failed} ==")
    print("== sweep done ==")


if __name__ == "__main__":
    main()
