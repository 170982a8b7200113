"""On-chip decode sweep: ms/token through the KV-cache engine across
cache dtypes and batch sizes, with the HBM roofline printed next to each
row — the measurement tool for VERDICT r4 items 2 (decode-to-roofline
after the no-copy restructure) and the int8-cache win.

    python tools/sweep_decode.py [variant ...]   # default: all

Each variant runs in a FRESH child process (same OOM-poisoning rationale
as tools/sweep_bench.py). Roofline model per decode step:
params_bytes + kv_bytes_per_step, all at the chip's peak HBM bandwidth.
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_variant(name: str, *, batch=8, prompt=128, new=256,
                kv_dtype="bfloat16", weights="bfloat16",
                decode_kernel="auto", speculative=None, gamma=4,
                hidden=1024, inter=2816, layers=24,
                heads=8, kv_heads=4) -> dict:
    import jax

    from bench import count_params, hbm_bw
    from dla_tpu.eval.eval_latency import measure_decode
    from dla_tpu.models.config import ModelConfig
    from dla_tpu.models.transformer import Transformer

    # bf16 params: the inference/rollout storage dtype (fp32 masters
    # would double the per-step weight read and corrupt the roofline
    # comparison — review r4)
    cfg = ModelConfig(
        vocab_size=32000, hidden_size=hidden, intermediate_size=inter,
        num_layers=layers, num_heads=heads, num_kv_heads=kv_heads,
        max_seq_length=4096, attention="flash", remat="none",
        dtype="bfloat16", param_dtype="bfloat16",
        kv_cache_dtype=kv_dtype, decode_kernel=decode_kernel)
    model = Transformer(cfg)
    params = model.init(jax.random.key(0))
    if weights == "int8":   # the rollout_quantize_weights path
        params = model.quantize_weights(params)
    jax.block_until_ready(params)
    n_params = count_params(params)
    p_bytes = float(sum(l.size * l.dtype.itemsize
                        for l in jax.tree.leaves(params)))
    # a decode step GATHERS only `batch` embedding rows, not the whole
    # table — count the table out of the per-step weight read (the
    # untied lm_head matmul still reads fully and stays in)
    emb = params["embed"]["embedding"]
    p_bytes_step = (p_bytes - emb.size * emb.dtype.itemsize
                    + batch * emb.shape[1] * emb.dtype.itemsize)

    if speculative == "selfint8":
        # self-speculation: the target's own int8 weight-quantized tree
        # drafts, the bf16 target verifies blockwise — no second
        # checkpoint, distribution-exact. Prefill (both models) is
        # measured separately and subtracted so decode_ms_per_token is
        # comparable with the other variants' prefill-subtracted
        # numbers; accept_rate uses the engine's live-row
        # proposal_slots telemetry (stragglers don't bias it).
        from dla_tpu.eval.eval_latency import _sync
        from dla_tpu.generation.engine import GenerationConfig
        from dla_tpu.generation.speculative import (
            build_speculative_generate_fn,
        )
        dparams = model.quantize_weights(params)
        gen = GenerationConfig(max_new_tokens=new, do_sample=True,
                               temperature=1.0, eos_token_id=-1)
        fn = jax.jit(build_speculative_generate_fn(
            model, model, gen, gamma=gamma, alloc_factor=1.2))
        rs = np.random.RandomState(0)
        ids = jax.numpy.asarray(
            rs.randint(3, cfg.vocab_size - 1, (batch, prompt)),
            jax.numpy.int32)
        mask = jax.numpy.ones((batch, prompt), jax.numpy.int32)
        alloc = int(1.2 * new) + gamma

        @jax.jit
        def prefills(params, dparams, ids, mask):
            lt, _ = model.start_decode(params, ids, mask, alloc)
            ld, _ = model.start_decode(dparams, ids, mask, alloc)
            return lt[0, 0] + ld[0, 0]

        _sync(prefills(params, dparams, ids, mask))
        pre_best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            _sync(prefills(params, dparams, ids, mask))
            pre_best = min(pre_best, time.perf_counter() - t0)

        _sync(fn(params, dparams, ids, mask, jax.random.key(0)))
        best, emitted, acc, slots, rounds = float("inf"), 0, 0, 1, 0
        for r in range(3):
            t0 = time.perf_counter()
            out = fn(params, dparams, ids, mask, jax.random.key(r))
            _sync(out)
            dt = time.perf_counter() - t0
            if dt < best:
                best = dt
                emitted = int(jax.numpy.sum(out["response_mask"]))
                acc = int(out["accepted_tokens"])
                slots = int(out["proposal_slots"])
                rounds = int(out["verify_rounds"])
            out = None
        decode_s = max(best - pre_best, 1e-9)
        res = {"variant": name, "spec": "selfint8", "gamma": gamma,
               "ms_per_token": round(
                   decode_s / max(emitted / batch, 1) * 1000, 3),
               "decode_tok_s_chip": round(
                   emitted / decode_s / jax.device_count(), 1),
               "emitted": emitted, "verify_rounds": rounds,
               "accept_rate": round(acc / max(slots, 1), 3),
               "batch": batch, "prompt": prompt, "new": new,
               "params_m": round(n_params / 1e6)}
        print(res, flush=True)
        return res

    if new < 2:
        raise ValueError("sweep_decode needs new >= 2 (the prefill "
                         "subtraction divides by new - 1)")
    t0 = time.perf_counter()
    row = measure_decode(model, params, batch, prompt, new)
    wall = time.perf_counter() - t0
    # measure_decode times the whole generate fn (prefill + decode
    # scan); subtract a 1-new-token run (~pure prefill) so ms/token is
    # decode-only — at the PPO rollout shape prefill is a double-digit
    # share of the total. (Timed outside `wall` so wall_s keeps its
    # one-measurement meaning.)
    pre = measure_decode(model, params, batch, prompt, 1)
    total_ms = row["ms_per_token"] * new
    decode_ms = (total_ms - pre["ms_per_token"]) / (new - 1)

    # roofline: per decode step, every parameter byte is read once for
    # the whole batch; the KV cache (avg fill ~ prompt + new/2 columns)
    # is read once per step; writes are one column (negligible)
    dev = jax.devices()[0]
    kv_elem = 1 if kv_dtype == "int8" else 2
    avg_fill = prompt + new / 2
    kv_bytes = (2 * layers * batch * avg_fill
                * kv_heads * cfg.head_dim_ * kv_elem)
    bw = hbm_bw(dev)    # None on a CPU: a host has no roofline
    roofline_ms = bw and (p_bytes_step + kv_bytes) / bw * 1000
    out = {"variant": name, "ms_per_token": round(decode_ms, 3),
           "ms_per_token_incl_prefill": round(row["ms_per_token"], 3),
           "decode_tok_s_chip": round(
               1000.0 * batch / decode_ms / jax.device_count(), 1),
           "roofline_ms": roofline_ms and round(roofline_ms, 3),
           "x_roofline": roofline_ms and round(decode_ms / roofline_ms, 2),
           "batch": batch, "prompt": prompt, "new": new,
           "kv": kv_dtype, "weights": weights,
           "params_m": round(n_params / 1e6),
           "wall_s": round(wall, 1)}
    print(out, flush=True)
    return out


VARIANTS = {
    # the BASELINE.md r3 comparison point: 349M, batch 8 — r3 measured
    # 2.53 ms/token (~2x roofline) before the no-copy restructure
    "b8_bf16": dict(batch=8, kv_dtype="bfloat16"),
    "b8_int8": dict(batch=8, kv_dtype="int8"),
    # bigger batch amortizes the param reads; cache share grows
    "b32_bf16": dict(batch=32, kv_dtype="bfloat16"),
    "b32_int8": dict(batch=32, kv_dtype="int8"),
    # the PPO rollout shape (128 prompt + 128 new)
    "b64_n128_int8": dict(batch=64, prompt=128, new=128, kv_dtype="int8"),
    # the full rollout stack: int8 weights (rollout_quantize_weights)
    # + int8 cache — both halves of the decode HBM traffic
    "b8_w8kv8": dict(batch=8, kv_dtype="int8", weights="int8"),
    "b64_n128_w8kv8": dict(batch=64, prompt=128, new=128,
                           kv_dtype="int8", weights="int8"),
    # r5 ablations at the PPO rollout shape: int8 KV alone REGRESSED at
    # b8/b32 (dequant overhead > bandwidth savings while the cache is
    # small next to the weights) — isolate whether the rollout stack
    # should keep the int8 cache or only the int8 weights
    "b64_n128_bf16": dict(batch=64, prompt=128, new=128),
    "b64_n128_w8": dict(batch=64, prompt=128, new=128, weights="int8"),
    "b8_w8": dict(batch=8, weights="int8"),
    # bf16 cache THROUGH the pallas decode kernel (decode_kernel: on):
    # fill-bounded reads vs the XLA einsum's full-S reads — decides
    # whether "on" should become the bf16 default
    "b64_n128_bf16_kernel": dict(batch=64, prompt=128, new=128,
                                 decode_kernel="on"),
    "b8_bf16_kernel": dict(batch=8, decode_kernel="on"),
    # self-speculation: int8 tree drafts for its own bf16 target —
    # decode_tok_s_chip is prefill-subtracted, same basis as b8_bf16
    "b8_spec_selfint8": dict(batch=8, speculative="selfint8", gamma=4),
    "b8_spec_selfint8_g6": dict(batch=8, speculative="selfint8",
                                gamma=6),
}


def main():
    names = sys.argv[1:] or list(VARIANTS)
    if len(names) == 1:
        n = names[0]
        try:
            run_variant(n, **VARIANTS[n])
        except Exception as e:  # OOM etc
            print({"variant": n, "error": f"{type(e).__name__}: {e}"[:300]},
                  flush=True)
            sys.exit(1)
        return
    # one child per variant, one at a time; this parent never imports
    # jax (a chip belongs to one process at a time)
    import subprocess
    failed = [
        n for n in names
        if subprocess.run([sys.executable, os.path.abspath(__file__), n],
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__)))).returncode]
    if failed:
        sys.exit(f"== sweep FAILED for {failed} ==")
    print("== decode sweep done ==")


if __name__ == "__main__":
    main()
