#!/usr/bin/env python3
"""Bring-up proof on the chip: does dla_tpu start, compile and step on
the TPU this process can see?

One process, three phases, each through the entry points a user calls,
at Mistral-7B widths (hidden 4096, FFN 14336, 32q/8kv x 128, vocab 32000,
window 4096) with depth as the only cut, random weights from a seed and
data generated here from a seed:

  kernels  the Pallas kernels compiled (``interpret=False``) at Mistral
           head shapes (the chunk's selective scan at the layer-spec
           cells' T 512 / 256, d_inner 5,120, N 16) and compared with
           their XLA references
  trainer  ``dla_tpu.training.train_sft.main`` on a YAML written here:
           flash attention, fused CE, remat, T = 2048, full fine-tuning;
           a few steps, a checkpoint, one ``--resume`` step. On a
           multi-chip host the same phase runs over a
           ``{fsdp: 2, model: 2}`` mesh at a deeper cut
  server   a ``ServingEngine`` built the way ``eval_latency --serving``
           builds it, chunked prefill on, a dozen mixed-length requests
           answered to completion and checked against the contiguous
           ``generation/engine.py`` engine and a teacher-forced forward

The command fails (exit code != 0, no result line) unless
``jax.devices()[0].platform == "tpu"``: JAX itself falls back to the CPU
with only a warning when libtpu does not come up, and neither the
trainer nor the mesh builder looks. ``--rehearsal`` is the tiny-size CPU
dry run the on-chip-measurement guide asks for before spending chip
time; it is never chosen automatically and says REHEARSAL on every line.

The last stdout line of a chip run is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import shutil
import sys
import time
import traceback
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

REPO = Path(__file__).resolve().parent

#: per-phase sizes. Depth is the only cut from the preset on the chip;
#: the rehearsal swaps in the `tiny` preset and toy shapes.
CHIP = {
    "model": "mistral-7b",
    # one layer is 218.1M parameters, embedding + untied head 262.1M.
    # Full fine-tuning holds fp32 params, fp32 grads, a bf16 first and an
    # fp32 second Adam moment = 14 B/param: L=2 is 0.70B -> 9.8 GB of a
    # 16 GB chip (XLA's own plan for the step peaks at 11.9 GiB).
    # (lr: every weight feeds 4096-wide sums, so Adam's first steps move
    # the logits coherently — 3e-4 diverged on the chip within 3 steps,
    # 8e-5 wobbles, 2e-5 drops 7 nats in one step and keeps falling)
    "trainer": {"layers": 2, "seq": 2048, "micro": 2, "steps": 6,
                "lr": 1e-5, "mesh": {"fsdp": 1},
                "why": "0.70B x 14 B of training state = 9.8 GB of 16"},
    # four chips: FSDP gathers, tensor-parallel collectives and the
    # shard_map-wrapped flash call in one step; L=8 is 2.01B -> 28 GB of
    # state, 7 GB a chip (planned peak 9.4 GiB a chip)
    "trainer_multi": {"layers": 8, "seq": 2048, "micro": 2, "steps": 6,
                      "lr": 5e-6, "mesh": {"fsdp": 2, "model": 2},
                      "why": "2.01B x 14 B = 28 GB of state, 7 GB a chip"},
    # bf16 weights: L=16 is 3.75B -> 7.5 GB; the KV pool costs 64 KiB a
    # token at that depth, 1280 pages x 16 tokens = 1.25 GiB (the
    # undonated decode step holds two copies), 8 slots x 2048 tokens
    "server": {"layers": 16, "page_size": 16, "num_pages": 1280,
               "num_slots": 8, "max_model_len": 2048, "chunk": 256,
               "new_tokens": 24,
               "prompt_lens": [2000, 37, 512, 1200, 90, 1800, 300, 64,
                               1024, 700, 1500, 200],
               "why": "3.75B in bf16 = 7.5 GB beside a 1.25 GiB KV pool"},
    "kernels": {"heads": 32, "kv_heads": 8, "head_dim": 128,
                "seq": 2048, "window": 4096, "flash_batch": 2,
                "matmul": [(64, 4096, 14336), (64, 14336, 4096),
                           (2048, 14336, 4096)],
                "decode_batches": [8, 64], "decode_cache": 2048,
                "decode_fill": 1500,
                "scan_chunks": [512, 256], "scan_inner": 5120,
                "scan_state": 16},
}
REHEARSAL = {
    "model": "tiny",
    "trainer": {"layers": 2, "seq": 128, "micro": 2, "steps": 8,
                "lr": 2e-3, "mesh": {"fsdp": 1}, "why": "toy"},
    "trainer_multi": {"layers": 2, "seq": 128, "micro": 2, "steps": 8,
                      "lr": 2e-3, "mesh": {"fsdp": 2, "model": 2},
                      "why": "toy"},
    "server": {"layers": 2, "page_size": 4, "num_pages": 64,
               "num_slots": 3, "max_model_len": 128, "chunk": 8,
               "new_tokens": 6, "prompt_lens": [40, 5, 17, 33, 9],
               "why": "toy"},
    "kernels": {"heads": 4, "kv_heads": 2, "head_dim": 128,
                "seq": 256, "window": 64, "flash_batch": 1,
                "matmul": [(16, 256, 384)],
                "decode_batches": [2], "decode_cache": 256,
                "decode_fill": 150,
                "scan_chunks": [32], "scan_inner": 256, "scan_state": 4},
}


class Smoke:
    """Output and phase bookkeeping of one run. What compiled, how long
    it took and what the persistent cache answered is the program's own
    accounting (``telemetry.xla_introspect``, started by
    ``enable_compile_cache()``): the one source of compile counts."""

    def __init__(self, rehearsal: bool, out_dir: Path):
        self.tag = "REHEARSAL " if rehearsal else ""
        self.rehearsal = rehearsal
        self.sizes = REHEARSAL if rehearsal else CHIP
        self.out_dir = out_dir
        # checkpoints are GBs: they live beside the report only for the
        # length of the run (the tool's output directory is capped)
        self.work = out_dir / "work"
        self.results: Dict[str, bool] = {}

    def say(self, msg: str) -> None:
        print(f"{self.tag}[chip_smoke] {msg}", flush=True)

    def compiles(self, since_ns: int = 0) -> List[tuple]:
        """(fun_name, seconds) of every backend compile JAX reported
        since ``since_ns`` (``perf_counter_ns``); a persistent-cache
        retrieval counts as the compile it replaced."""
        from dla_tpu.telemetry import xla_introspect as xi
        return [(ev.fun_name, ev.seconds)
                for ev in xi.compile_events(since_ns)
                if ev.event == xi.BACKEND_COMPILE_EVENT]

    @staticmethod
    def spent(before: Dict[str, float]) -> str:
        """What the process compiled since ``before`` (an earlier
        ``compile_accounting()``), in words."""
        from dla_tpu.telemetry.xla_introspect import compile_accounting
        d = {k: v - before[k] for k, v in compile_accounting().items()}
        return (f"{d['backend_compiles']} compiles "
                f"{d['backend_compile_s']:.1f}s, lowering "
                f"{d['lower_s']:.1f}s; persistent cache "
                f"{d['cache_hits']} hits, {d['cache_misses']} misses")

    def compile_seconds(self, fun_name: str, since_ns: int = 0
                        ) -> List[float]:
        return [s for n, s in self.compiles(since_ns) if n == fun_name]

    def run(self, name: str, phase: Callable[["Smoke"], None]) -> None:
        import jax

        from dla_tpu.telemetry.xla_introspect import compile_accounting
        t0, mark = time.perf_counter(), time.perf_counter_ns()
        before = compile_accounting()
        try:
            phase(self)
            ok, why = True, ""
        except Exception as exc:            # noqa: BLE001 — phase boundary
            traceback.print_exc()
            ok, why = False, f" {type(exc).__name__}: {str(exc)[:500]}"
        self.results[name] = ok
        self.say(f"{'PASS' if ok else 'FAIL'} {name} "
                 f"({time.perf_counter() - t0:.1f}s; "
                 f"{self.spent(before)}){why}")
        slow = sorted((c for c in self.compiles(mark) if c[1] >= 1.0),
                      key=lambda c: -c[1])
        for fun, secs in slow[:12]:
            self.say(f"  compile {fun}: {secs:.1f}s")
        gc.collect()
        jax.clear_caches()


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-6))


# ------------------------------------------------------------------ kernels

def phase_kernels(s: Smoke) -> None:
    """Each Pallas kernel compiled by Mosaic and compared with the XLA
    path it replaces. A kernel the compiler refuses fails the phase with
    the compiler's message; nothing is routed round it."""
    import jax
    import jax.numpy as jnp

    from dla_tpu.ops.attention import causal_attention, decode_attention
    from dla_tpu.ops.decode_kernel import flash_decode_attention
    from dla_tpu.ops.flash_attention import (
        DEFAULT_BLOCK_K,
        DEFAULT_BLOCK_Q,
        flash_causal_attention,
    )
    from dla_tpu.ops.quant_matmul import int8_matmul

    k = s.sizes["kernels"]
    interpret = s.rehearsal
    h, kh, d, t = k["heads"], k["kv_heads"], k["head_dim"], k["seq"]
    rs = np.random.RandomState(0)
    failures: List[str] = []

    def verdict(name: str, fn: Callable[[], float], tol: float) -> None:
        try:
            err = fn()
        except Exception as exc:        # noqa: BLE001 — per-kernel verdict
            traceback.print_exc()
            failures.append(name)
            s.say(f"  kernel FAIL {name}: {type(exc).__name__}: "
                  f"{str(exc)[:800]}")
            return
        good = math.isfinite(err) and err <= tol
        if not good:
            failures.append(name)
        s.say(f"  kernel {'ok  ' if good else 'FAIL'} {name}: "
              f"max rel err {err:.2e} (tol {tol:.0e})")

    def normal(*shape):
        return jnp.asarray(rs.randn(*shape), jnp.bfloat16)

    # flash attention, forward and backward, at the trainer's blocks
    b = k["flash_batch"]
    q, kk, vv = normal(b, t, h, d), normal(b, t, kh, d), normal(b, t, kh, d)
    w_out = normal(b, t, h, d)

    def flash_loss(q, kk, vv):
        out = flash_causal_attention(
            q, kk, vv, window=k["window"], block_q=DEFAULT_BLOCK_Q,
            block_k=DEFAULT_BLOCK_K, interpret=interpret)
        return jnp.sum(out.astype(jnp.float32) * w_out), out

    def xla_loss(q, kk, vv):
        out = causal_attention(q, kk, vv, window=k["window"])
        return jnp.sum(out.astype(jnp.float32) * w_out), out

    def flash_vs_xla() -> float:
        grad = lambda f: jax.jit(jax.value_and_grad(  # noqa: E731
            f, argnums=(0, 1, 2), has_aux=True))
        (_, out), grads = grad(flash_loss)(q, kk, vv)
        (_, ref), ref_grads = grad(xla_loss)(q, kk, vv)
        return max(rel_err(out, ref),
                   *(rel_err(g, r) for g, r in zip(grads, ref_grads)))

    verdict(f"flash_causal_attention fwd+bwd [B{b} T{t} {h}q/{kh}kv x{d} "
            f"window {k['window']} blocks {DEFAULT_BLOCK_Q}x"
            f"{DEFAULT_BLOCK_K}]", flash_vs_xla, 3e-2)

    # int8 weight-only matmul
    for m, kdim, n in k["matmul"]:
        x = normal(m, kdim)
        w = jnp.asarray(rs.randint(-127, 128, (kdim, n)), jnp.int8)
        scale = jnp.asarray(rs.rand(1, n) * 0.01 + 1e-3, jnp.float32)

        def matmul_vs_xla(x=x, w=w, scale=scale) -> float:
            out = int8_matmul(x, w, scale, interpret=interpret)
            ref = jax.jit(lambda x, w, sc: jnp.dot(
                x, w.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32) * sc)(x, w, scale)
            return rel_err(out, ref)

        verdict(f"int8_matmul [M{m} K{kdim} N{n}]", matmul_vs_xla, 2e-2)

    # decode attention over a partly filled cache, bf16 and int8
    cache, fill = k["decode_cache"], k["decode_fill"]
    for b in k["decode_batches"]:
        q1 = normal(b, 1, h, d)
        kc, vc = normal(b, cache, kh, d), normal(b, cache, kh, d)
        kn, vn = normal(b, 1, kh, d), normal(b, 1, kh, d)
        pos = jnp.broadcast_to(jnp.arange(cache, dtype=jnp.int32)[None],
                               (b, cache))
        masks = dict(kv_valid=pos < fill,
                     q_positions=jnp.full((b, 1), fill, jnp.int32),
                     kv_positions=pos, window=k["window"])

        def quantize(x):
            absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
            sc = absmax / 127.0 + 1e-12
            xq = jnp.clip(jnp.round(x.astype(jnp.float32) / sc[..., None]),
                          -127, 127).astype(jnp.int8)
            return xq, sc

        def decode_vs_xla(int8: bool, q1=q1, kc=kc, vc=vc, kn=kn, vn=vn,
                          masks=masks) -> float:
            extra = {}
            if int8:
                (kq, ksc), (vq, vsc) = quantize(kc), quantize(vc)
                ref_k = (kq.astype(jnp.float32) * ksc[..., None]
                         ).astype(jnp.bfloat16)
                ref_v = (vq.astype(jnp.float32) * vsc[..., None]
                         ).astype(jnp.bfloat16)
                # the kernel takes K-major [B, K, S] scales
                extra = dict(k_scale=ksc.transpose(0, 2, 1),
                             v_scale=vsc.transpose(0, 2, 1))
                kc, vc = kq, vq
            else:
                ref_k, ref_v = kc, vc
            out = flash_decode_attention(
                q1, kc, vc, kn, vn, kv_fill=jnp.asarray(fill, jnp.int32),
                interpret=interpret, **masks, **extra)
            ref = decode_attention(q1, ref_k, ref_v, kn, vn, **masks)
            return rel_err(out, ref)

        for int8 in (False, True):
            verdict(f"flash_decode_attention [B{b} S{cache} fill {fill} "
                    f"{h}q/{kh}kv x{d} {'int8' if int8 else 'bf16'} cache]",
                    lambda int8=int8: decode_vs_xla(int8), 2e-2)

        # the same cache as pages of 16 scattered over a pool, slots at
        # ragged fills: the paged kernel walks the block table
        def paged_vs_xla(b=b, q1=q1, kc=kc, vc=vc, kn=kn, vn=vn) -> float:
            from dla_tpu.ops.paged_attention import paged_decode_attention
            page = 16
            pps = cache // page
            order = rs.permutation(b * pps) + 1      # page 0: the trash
            tables = jnp.asarray(order.reshape(b, pps), jnp.int32)
            lengths = jnp.asarray(
                rs.randint(0, fill + 1, size=b), jnp.int32).at[0].set(fill)

            def pool(x):
                pages = x.reshape(b * pps, page, kh, d)
                flat = jnp.zeros((b * pps + 1, page, kh, d), x.dtype)
                return flat.at[tables.reshape(-1)].set(pages)[None]
            out = paged_decode_attention(
                q1[:, 0], pool(kc), pool(vc), tables, lengths, kn[:, 0],
                vn[:, 0], layer=0, window=k["window"], interpret=interpret)
            ref = decode_attention(
                q1, kc, vc, kn, vn, kv_valid=pos < lengths[:, None],
                q_positions=lengths[:, None], kv_positions=pos,
                window=k["window"])
            return rel_err(out, ref[:, 0])

        verdict(f"paged_decode_attention [B{b} pages of 16, fills 0.."
                f"{fill} of {cache} {h}q/{kh}kv x{d}]", paged_vs_xla, 2e-2)

    # the chunk's selective scan at the two layer-spec serving cells'
    # shapes, from a carried state that is not zero
    def scan_vs_xla(t: int) -> float:
        from dla_tpu.ops.selective_scan import selective_scan_chunk
        from dla_tpu.ops.selective_scan_kernel import (
            selective_scan_chunk_kernel,
        )
        di, n = k["scan_inner"], k["scan_state"]
        args = (normal(1, t, di),
                jnp.asarray(np.log1p(np.exp(rs.randn(1, t, di) - 1.0)),
                            jnp.float32),
                -jnp.exp(jnp.asarray(0.5 * rs.randn(n, di), jnp.float32)),
                normal(1, t, n), normal(1, t, n),
                jnp.asarray(rs.randn(di), jnp.float32),
                jnp.asarray(rs.randn(1, n, di), jnp.float32))
        got = selective_scan_chunk_kernel(*args, interpret=interpret)
        want = selective_scan_chunk(*args)
        return max(rel_err(g, w) for g, w in zip(got, want))

    for t in k["scan_chunks"]:
        verdict(f"selective_scan_chunk_kernel [T{t} d_inner "
                f"{k['scan_inner']} N{k['scan_state']}]",
                lambda t=t: scan_vs_xla(t), 1e-3)

    check(not failures, f"{len(failures)} kernel check(s) failed: "
          + "; ".join(failures))


# ------------------------------------------------------------------ trainer

_WORDS = ("align reward policy token mesh shard layer cache page slot "
          "prompt answer model chip step loss adam norm head rope").split()


def _write_sft_records(path: Path, n: int, seq: int, seed: int) -> None:
    """Byte-tokenized rows that fill the sequence: a short prompt and a
    response drawn from a closed word list (learnable in a few steps)."""
    from dla_tpu.data.jsonl import write_jsonl
    rng = np.random.default_rng(seed)
    recs = []
    for _ in range(n):
        prompt = "continue: " + " ".join(rng.choice(_WORDS, 6))
        response = " ".join(rng.choice(_WORDS, max(4, seq // 5)))
        recs.append({"prompt": prompt, "response": response})
    write_jsonl(path, recs)


def _step_losses(metrics_path: Path) -> Dict[int, Optional[float]]:
    out: Dict[int, Optional[float]] = {}
    with metrics_path.open() as fh:
        for line in fh:
            rec = json.loads(line)
            if "train/loss_instant" in rec:
                out[int(rec["step"])] = rec["train/loss_instant"]
    return out


def phase_trainer(s: Smoke) -> None:
    import jax
    import yaml

    from dla_tpu.training import train_sft

    n_dev = jax.device_count()
    check(n_dev == 1 or n_dev % 4 == 0,
          f"trainer phase covers 1 chip or a multiple of 4, found {n_dev}")
    z = s.sizes["trainer" if n_dev == 1 else "trainer_multi"]
    mesh = dict(z["mesh"])
    if n_dev > 1:
        mesh["data"] = n_dev // 4
    dp = mesh.get("data", 1) * mesh["fsdp"]
    global_batch = z["micro"] * dp
    steps = z["steps"]
    run = s.work / "trainer"
    _write_sft_records(run / "sft_train.jsonl", global_batch * (steps + 2),
                       z["seq"], seed=0)
    config = {
        "experiment_name": "chip_smoke_sft",
        "seed": 0,
        "model": {"model_name_or_path": s.sizes["model"],
                  "tokenizer": "byte", "num_layers": z["layers"],
                  "max_seq_length": z["seq"], "attention": "flash",
                  "remat": "full"},
        "data": {"source": "local",
                 "train_path": str(run / "sft_train.jsonl")},
        "optimization": {
            "total_batch_size": global_batch,
            "micro_batch_size": z["micro"], "learning_rate": z["lr"],
            "warmup_steps": 0, "lr_scheduler": "constant",
            "max_train_steps": steps, "max_grad_norm": 1.0,
            "adam_moment_dtype": "bfloat16"},
        "logging": {"output_dir": str(run / "ckpt"),
                    "log_dir": str(s.out_dir / "trainer_logs"),
                    "log_every_steps": 1, "save_every_steps": 0},
        "hardware": {"gradient_accumulation_steps": 1, "mesh": mesh},
    }
    cfg_path = run / "sft.yaml"
    cfg_path.write_text(yaml.safe_dump(config))
    s.say(f"trainer: {s.sizes['model']} widths, depth {z['layers']} "
          f"(the only cut: {z['why']}), T={z['seq']}, global batch "
          f"{global_batch}, "
          f"mesh {mesh} over {n_dev} device(s), {steps} steps + 1 resumed")

    metrics = s.out_dir / "trainer_logs" / "metrics.jsonl"
    metrics.unlink(missing_ok=True)
    mark = time.perf_counter_ns()
    train_sft.main(["--config", str(cfg_path)])
    cold = s.compile_seconds("jit(_train_step)", mark)
    losses = _step_losses(metrics)
    series = [losses.get(i) for i in range(1, steps + 1)]
    s.say("  loss by step: " + " ".join(
        "nan" if v is None else f"{v:.3f}" for v in series))
    check(all(v is not None and math.isfinite(v) for v in series),
          f"non-finite or missing loss in {series}")
    check(series[-1] < series[0],
          f"loss did not fall: {series[0]:.4f} -> {series[-1]:.4f}")
    check(len(cold) == 1,
          f"expected exactly one train-step compile, saw {len(cold)}")
    ckpt = run / "ckpt"
    check((ckpt / "latest").is_file()
          and (ckpt / "final" / "index.json").is_file(),
          f"no final checkpoint under {ckpt}")

    # the jitted step must hold the Pallas flash call: _flash_eligible
    # falls to XLA attention without a word (an interpreted kernel
    # leaves no such marker, so the rehearsal only finds the dump)
    dumps = sorted((s.work / "ir").glob("*jit__train_step*"))
    check(bool(dumps), "no lowered train step was dumped")
    if not s.rehearsal:
        check("tpu_custom_call" in dumps[-1].read_text(),
              f"{dumps[-1].name} holds no tpu_custom_call: the train "
              "step did not engage the Pallas flash kernel")
        s.say(f"  flash kernel engaged: tpu_custom_call in "
              f"{dumps[-1].name}")
        stats = [d.memory_stats() for d in jax.devices()]
        peaks = [st["peak_bytes_in_use"] for st in stats]
        s.say("  peak HBM by device: " + " ".join(
            f"{p / 2**30:.2f}GiB" for p in peaks))
        # params and both moments live between steps, 10 B/param evenly
        # sharded (the step's own temporaries are not in this counter): a
        # device that held less means build_mesh's hand-reshaped device
        # order went wrong
        n_params = 262.1e6 + z["layers"] * 218.1e6
        check(min(peaks) >= 0.9 * 10 * n_params / n_dev,
              f"a device held less than its shard of the state: {peaks}")

    gc.collect()        # the first run's state must leave the chip first
    mark = time.perf_counter_ns()
    train_sft.main(["--config", str(cfg_path), "--resume", "--set",
                    f"optimization.max_train_steps={steps + 1}"])
    warm = s.compile_seconds("jit(_train_step)", mark)
    resumed = _step_losses(metrics).get(steps + 1)
    check(resumed is not None and math.isfinite(resumed),
          f"resumed step {steps + 1} logged no finite loss")
    # a restart from fresh weights would sit back at the first loss
    check(resumed < (series[0] + series[-1]) / 2,
          f"resumed loss {resumed:.4f} is not a continuation of "
          f"{series[-1]:.4f} (first step was {series[0]:.4f})")
    check(len(warm) == 1, f"resume compiled the step {len(warm)} times")
    s.say(f"  resumed at step {steps + 1}: loss {resumed:.3f}; train-step "
          f"compile {cold[0]:.1f}s first, {warm[0]:.1f}s on resume "
          "(same process, second lookup of the persistent cache)")
    shutil.rmtree(ckpt)


# ------------------------------------------------------------------- server

def phase_server(s: Smoke) -> None:
    import jax
    import jax.numpy as jnp

    from dla_tpu.eval.eval_latency import _drive_open_loop, _serving_config
    from dla_tpu.generation.engine import GenerationConfig, build_generate_fn
    from dla_tpu.serving import ServingEngine
    from dla_tpu.training.model_io import load_causal_lm

    z = s.sizes["server"]
    new = z["new_tokens"]
    model_cfg = {"tokenizer": "byte", "num_layers": z["layers"],
                 "param_dtype": "float32" if s.rehearsal else "bfloat16",
                 "attention": "flash", "max_seq_length": z["max_model_len"]}
    bundle = load_causal_lm(s.sizes["model"], model_cfg, jax.random.key(0))
    model, params = bundle.model, bundle.params
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    s.say(f"server: {s.sizes['model']} widths, depth {z['layers']} (the "
          f"only cut: {z['why']}), {n_params / 1e9:.2f}B params in "
          f"{model_cfg['param_dtype']}, {len(z['prompt_lens'])} requests "
          f"of {min(z['prompt_lens'])}..{max(z['prompt_lens'])} prompt "
          f"tokens, {new} new tokens each, prefill chunk {z['chunk']}")

    rs = np.random.RandomState(0)
    prompts = [[int(t) for t in rs.randint(3, 259, (n,))]
               for n in z["prompt_lens"]]
    gen = GenerationConfig(max_new_tokens=new, do_sample=False,
                           eos_token_id=-1)          # run to length
    srv = {"page_size": z["page_size"], "num_pages": z["num_pages"],
           "num_slots": z["num_slots"], "max_model_len": z["max_model_len"],
           "chunked_prefill": {"chunk": z["chunk"]}}
    eng = ServingEngine(model, params, gen, _serving_config(srv))
    try:
        # every request arrives at t=0: the engine batches, chunks and
        # preempts on its own schedule
        _, served = _drive_open_loop(
            eng, prompts, np.zeros(len(prompts)), new)
        snap = eng.metrics.snapshot()
        check(all(len(out) == new for out in served),
              f"requests finished short: {[len(o) for o in served]}")
        check(eng.decode_compiles == 1 and eng.prefill_chunk_compiles == 1,
              f"decode compiled {eng.decode_compiles}x, prefill chunk "
              f"{eng.prefill_chunk_compiles}x (want 1 each)")
        s.say(f"  served {len(served)} requests in {eng.engine_steps} "
              f"engine steps, {int(snap['serving/prefill/chunks'])} "
              f"prefill chunks, {int(snap['serving/preemptions'])} "
              "preemptions; decode and prefill-chunk steps compiled once")
    finally:
        eng.close()
    del eng
    gc.collect()

    # the contiguous engine on the same prompts, right-padded to one width
    width = z["max_model_len"]
    check(max(z["prompt_lens"]) + new <= width, "prompts overflow the window")
    ids = np.zeros((len(prompts), width), np.int32)
    mask = np.zeros_like(ids)
    for i, p in enumerate(prompts):
        ids[i, :len(p)] = p
        mask[i, :len(p)] = 1
    out = jax.jit(build_generate_fn(model, gen))(
        params, jnp.asarray(ids), jnp.asarray(mask), jax.random.key(0))
    contiguous = [[int(t) for t in row]
                  for row in np.asarray(out["response_tokens"])]
    del out
    identical = sum(a == b for a, b in zip(served, contiguous))

    # teacher-forced reference: one plain full-sequence forward over
    # prompt + answer. Greedy decoding in bf16 may legitimately leave
    # the contiguous engine's stream at a near-tie of two logits, so
    # every token either engine emitted must be an argmax of this
    # forward to within the activation dtype's resolution.
    lens = jnp.asarray([len(p) for p in prompts], jnp.int32)
    at = lens[:, None] - 1 + jnp.arange(new, dtype=jnp.int32)[None, :]

    @jax.jit
    def deficits(params, ids, mask, answers):
        hidden = model.hidden_states(params, ids, attention_mask=mask)
        picked = jnp.take_along_axis(hidden, at[:, :, None], axis=1)
        logits = model.unembed(params, picked).astype(jnp.float32)
        top = jnp.max(logits, axis=-1)
        chosen = jnp.take_along_axis(
            logits, answers[:, :, None], axis=-1)[..., 0]
        tol = 8 * float(jnp.finfo(model.adtype).eps) * jnp.maximum(
            jnp.abs(top), 1.0)
        return top - chosen, (top - chosen) / tol       # [R, N] each

    worst = 0.0
    for name, streams in (("paged", served), ("contiguous", contiguous)):
        full_ids, full_mask = ids.copy(), mask.copy()
        for i, (p, ans) in enumerate(zip(prompts, streams)):
            full_ids[i, len(p):len(p) + new] = ans
            full_mask[i, :len(p) + new] = 1
        gap, ratio = (np.asarray(x) for x in deficits(
            params, jnp.asarray(full_ids), jnp.asarray(full_mask),
            jnp.asarray(streams, jnp.int32)))
        s.say(f"  {name} engine vs teacher-forced forward: worst logit "
              f"deficit of an emitted token {gap.max():.4f} "
              f"({ratio.max():.2f} of the {model.cfg.dtype} tolerance)")
        if not ratio.max() <= 1.0:
            r, t = np.unravel_index(np.nanargmax(ratio), ratio.shape)
            s.say(f"    request {r} (prompt {len(prompts[r])}), token {t}: "
                  f"{name} emitted {streams[r][:8]}..., the other engine "
                  f"{(contiguous if streams is served else served)[r][:8]}"
                  "...")
        worst = max(worst, float(ratio.max()))
    s.say(f"  greedy streams identical to the contiguous engine: "
          f"{identical}/{len(prompts)} requests")
    check(math.isfinite(worst) and worst <= 1.0,
          "an emitted token is not an argmax of the reference forward")
    check(identical == len(prompts) or model.cfg.dtype != "float32",
          f"float32 streams diverged on "
          f"{len(prompts) - identical} request(s)")
    dumps = sorted((s.work / "ir").glob("*jit_generate*"))
    check(bool(dumps), "no lowered generate function was dumped")
    check(s.rehearsal or "tpu_custom_call" in dumps[-1].read_text(),
          "the contiguous engine's prefill did not engage the Pallas "
          "flash kernel")


# --------------------------------------------------------------------- main

PHASES = {"kernels": phase_kernels, "trainer": phase_trainer,
          "server": phase_server}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny-size CPU dry run; proves nothing about "
                         "the chip")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset of: " + ", ".join(PHASES))
    ap.add_argument("--out", default=str(REPO / "chiprun_out" / "chip_smoke"),
                    help="report directory (default: the chip tool's "
                         "output directory)")
    args = ap.parse_args(argv)
    phases = [p for p in args.phases.split(",") if p]
    unknown = [p for p in phases if p not in PHASES]
    if unknown:
        ap.error(f"unknown phase(s) {unknown}")

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearsal:
        print(f"[chip_smoke] no TPU: jax.devices()[0].platform is "
              f"{dev.platform!r} ({dev.device_kind}). This check only "
              "means something on the chip; run it through the chip "
              "tool.", file=sys.stderr)
        return 2

    import jaxlib

    from dla_tpu import native
    from dla_tpu.native.build import LIB
    from dla_tpu.utils.compile_cache import enable_compile_cache

    s = Smoke(args.rehearsal, Path(args.out))
    shutil.rmtree(s.work, ignore_errors=True)
    s.work.mkdir(parents=True)
    cache_dir = Path(enable_compile_cache())
    entries = len(list(cache_dir.glob("*"))) if cache_dir.is_dir() else 0
    jax.config.update("jax_dump_ir_to", str(s.work / "ir"))
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "absent"
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}
    s.say(f"platform {dev.platform}, device_kind {dev.device_kind}, "
          f"{jax.device_count()} device(s); jax {jax.__version__}, jaxlib "
          f"{jaxlib.__version__}, libtpu {libtpu_version}")
    s.say(f"compile cache {cache_dir} ({entries} entries at start: "
          f"{'warm' if entries else 'cold'} run)")
    # built from what git would commit: never a binary left in the
    # ignored _lib/ by an earlier run
    LIB.unlink(missing_ok=True)
    s.say("data plane: " + (
        "native, rebuilt from dla_tpu/native/src/dla_data.cpp"
        if native.available() else "pure Python (no native toolchain)"))

    from dla_tpu.telemetry.xla_introspect import compile_accounting
    t0, start_ns = time.perf_counter(), time.perf_counter_ns()
    before = compile_accounting()
    try:
        for name in phases:
            s.run(name, PHASES[name])
    finally:
        jax.config.update("jax_dump_ir_to", None)
        shutil.rmtree(s.work, ignore_errors=True)
    ok = all(s.results.values())
    acct = compile_accounting()
    s.say(f"in all: {s.spent(before)}; "
          f"wall {time.perf_counter() - t0:.1f}s")
    report = {"ok": ok, "device": device, "phases": s.results,
              "rehearsal": args.rehearsal,
              "compiles": [{"fn": n, "seconds": round(t, 3)}
                           for n, t in s.compiles(start_ns) if t >= 1.0],
              "cache": {"dir": str(cache_dir), "entries_at_start": entries,
                        "hits": acct["cache_hits"] - before["cache_hits"],
                        "misses": (acct["cache_misses"]
                                   - before["cache_misses"])}}
    (s.out_dir / "report.json").write_text(json.dumps(report, indent=1))
    print(s.tag + json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
