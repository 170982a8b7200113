"""Driver ``serve_closed_loop_hf``: the closed loop of ``serve_closed_loop``
(its :class:`ClosedLoop`, the same outputs, counters, samples,
``ANNOTATIONS`` and ``PROGRAMS``) for a configuration the program itself
reads: the model is built by the program's ``hf_config_to_model_config``
from the configuration file's Hugging Face keys, and the plain reference
named in the file takes its weights through its own ``take_layer``.

It refuses at once, before any weight is made, where the program does
not know a key the configuration needs (``kv_lora_rank`` on a program
without latent attention): exit code 3, a line on standard error.

``correct`` is the comparison of ``serve_closed_loop``: the last 64
chosen-token log-probabilities of two finished requests against the
float32 reference's teacher-forced forward over prompt + answer, with one
addition for routed experts, below.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
from typing import Dict

import numpy as np

from perfbench.drivers.serve_closed_loop import (  # noqa: F401  (re-exported)
    ANNOTATIONS, CHECK_LAST, PROGRAMS, ClosedLoop)
from perfbench.lib import stats
from perfbench.lib.traffic import ClosedLoopTraffic

#: Tolerances of ``correct``, in nats, for a 5-layer bf16 engine with 32
#: of 128 routed experts against the float32 reference on random weights.
#: Two kinds of error reach a chosen token's log-probability. (1) bf16
#: rounding in every matmul, norm and cached row: smooth, half of the
#: checked tokens within 0.015 and three quarters within 0.026 on the
#: chip. (2) A routing flip: where a token's 4th and 5th router scores lie
#: nearer than bf16's rounding of the router's input, the engine sends
#: the token to another expert than the float32 reference does, and that
#: layer's routed output changes by a whole expert's share. Both answers
#: are right to the precision the configuration states, but a flip is a
#: step, not a rounding: 0.1 to 1.5 nats on the chosen token, or on the
#: reference's best token instead (the chosen token's own log-probability
#: can come through a flip nearly unchanged while another token overtakes
#: it). So a checked token is an OUTLIER where its log-probability is off
#: by more than TOL_STEP, or where it trails the reference's best logit by
#: more than TOL_STEP; the share of outliers is counted, reported and held
#: to TOL_OUTLIER_SHARE, never waved through.
#:
#: Each limit lies between two readings on the chip (PERF.md section 6
#: has them, with seeds): the largest this engine gave over 4,000 pairs
#: of 64-token blocks from twelve requests of six seeds, and what the
#: reference gives against itself when every matmul operand and every
#: cached row is rounded to 8 bits (e4m3, a scale a row: the nearest
#: precision under the configuration's bf16):
#:   75th percentile of the errors   0.0326 < 0.045 < 0.37
#:   rms of the inliers' errors      0.058  < 0.09 < 0.135
#:   share of outliers               0.047  < 0.10 < 0.47
#: The 75th percentile is the tight one: flips (a few tokens in a hundred)
#: cannot move it and sampling 128 tokens moves it by 0.002 around 0.026.
#: With one part alone in e4m3 beside this engine's own bf16 error it
#: reads: the cached rows 0.055 to 0.073, the attention's inputs 0.070 to
#: 0.094 (both fail), the routed experts' weights 0.042 to 0.055 (fails in
#: the middle case, not in every one). The rms is the loose one because
#: the flips under TOL_STEP carry most of it (0.020 to 0.058 by block
#: pair). Rows of int8 with a scale a row keep 7 bits to bf16's 8 and
#: read within a fifth of bf16 itself: 128 tokens cannot tell them apart.
TOL_STEP = 0.25
TOL_LOGPROB_P75 = 0.045
TOL_LOGPROB_RMS = 0.09
TOL_OUTLIER_SHARE = 0.10


def _model_config(cfg: Dict, srv: Dict):
    """The program's ModelConfig from the configuration file, or exit 3
    where the program lacks what the file needs."""
    try:
        from dla_tpu.models.config import ModelConfig
        from dla_tpu.models.hf_import import hf_config_to_model_config
    except ImportError as exc:
        print(f"[perfbench] the program is not in this directory: {exc}",
              file=sys.stderr)
        raise SystemExit(3)
    known = {f.name for f in dataclasses.fields(ModelConfig)}
    wanted = [k for k in ("kv_lora_rank", "q_lora_rank", "moe_experts_held",
                          "num_shared_experts") if k not in known]
    if cfg.get("kv_lora_rank") and wanted:
        print("[perfbench] this program cannot run configuration "
              f"{cfg.get('model_type')!r}: its ModelConfig has no "
              f"{', '.join(wanted)}", file=sys.stderr)
        raise SystemExit(3)
    return hf_config_to_model_config(
        cfg, dtype=srv["dtype"], param_dtype=srv["param_dtype"],
        attention=srv["attention"], max_seq_length=int(srv["max_model_len"]))


def run(bench) -> Dict:
    cfg, srv, mix = bench.config, bench.config["serving"], bench.traffic
    model_cfg = _model_config(cfg, srv)      # before any weight is made

    import jax
    from dla_tpu.generation.engine import GenerationConfig
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.serving import ServingConfig, ServingEngine

    from perfbench.lib import sut

    model = Transformer(model_cfg)
    params = sut.init_params(model, bench.seed)
    jax.block_until_ready(params)
    bench.say("weights on the device")
    traffic = ClosedLoopTraffic(mix, bench.seed, int(cfg["vocab_size"]))
    gen = GenerationConfig(max_new_tokens=max(o for _, o in traffic.grid),
                           do_sample=False, eos_token_id=-1)  # to length
    engine = ServingEngine(model, params, gen, ServingConfig(
        page_size=int(srv["page_size"]), num_pages=int(srv["num_pages"]),
        num_slots=int(srv["num_slots"]),
        max_model_len=int(srv["max_model_len"]),
        prefill_chunk=int(srv["prefill_chunk"])))
    loop = ClosedLoop(engine, traffic)
    try:
        # warm-up: the cell's own traffic for a fixed count of engine
        # steps (part of set-up): compiles both step programs and takes
        # the opening burst of prefills out of the window
        loop.start()
        for _ in range(int(mix["warm_steps"])):
            loop.step()
        late = [c for c, s in enumerate(loop.first_prefill_step) if s is None]
        warm_steps, warm_finished = len(loop.steps), len(loop.finished)
        before = engine.metrics.snapshot()
        t0, setup_s = bench.open_window()
        bench.say(f"window open after {warm_steps} warm steps "
                  f"(set-up {setup_s:.1f}s)")
        while True:
            bench.tracer.tick(loop.now() - t0)
            loop.step()
            if loop.steps[-1][0] - t0 >= bench.seconds:
                break
        bench.close_window()
        t1 = loop.steps[-1][0]
        after = engine.metrics.snapshot()
        memory_peak = sut.memory_peak_bytes()
        finished = loop.finished[warm_finished:]
        # sampled for the reference before the engine goes
        pick = np.random.default_rng([int(bench.seed), 5]).permutation(
            len(finished))[:2]
        sampled = []
        for i in pick:
            rid, req = finished[int(i)]
            res = engine.result(rid)
            prompt, _ = traffic.request(req.client, req.k)
            sampled.append((prompt, list(res.generated),
                            list(res.generated_logprobs)))
        short = sum(1 for rid, req in finished
                    if len(engine.result(rid).generated) != req.out_len)
    finally:
        engine.close()
    window_s = t1 - t0
    steps = loop.steps[warm_steps:]
    bench.say(f"window closed: {len(steps)} engine steps, "
              f"{len(finished)} requests finished")

    # ---- client-side metrics, on the benchmark's clock
    requests = [r for _, r in loop.finished] + list(loop.open.values())
    gaps_ms, ttft_ms = [], []
    for req in requests:
        ts = req.times
        gaps_ms += [(b - a) * 1e3 for a, b in zip(ts, ts[1:]) if t0 < b <= t1]
        if ts and t0 < ts[0] <= t1:
            ttft_ms.append((ts[0] - req.t_submit) * 1e3)
    tokens = sum(n for _, n, _, _ in steps)
    parts = stats.subwindow_rates(
        [s[0] for s in steps], [s[1] for s in steps], t0, window_s,
        float(mix["part_seconds"]))
    end_to_end = {
        "serve_tok_s": tokens / window_s,
        "itl_p99_ms": stats.percentile(gaps_ms, 99.0),
        "ttft_p50_ms": stats.median(ttft_ms) if ttft_ms else float("nan"),
        "setup_s": setup_s,
    }

    def grew(key: str) -> float:
        return float(after.get(key, 0.0)) - float(before.get(key, 0.0))

    counters = {
        "engine_steps": len(steps),
        "tokens": tokens,
        "requests_finished": len(finished),
        "prefill_chunks": grew("serving/prefill/chunks"),
        "preemptions": grew("serving/preemptions"),
        "page_occupancy_peak": after["serving/page_occupancy_peak"],
        "num_slots": int(srv["num_slots"]),
        "warm_steps": warm_steps,
        "clients_not_prefilled_in_warmup": len(late),
        "itl_samples": len(gaps_ms),
        "ttft_samples": len(ttft_ms),
        # the window's dropless routing, summed over layers and steps
        "decode_steps": grew("serving/decode_steps"),
        "moe_experts_hit": grew("serving/moe/experts_hit"),
        "moe_expert_assignments": grew("serving/moe/expert_assignments"),
        "kv_bytes_per_token": float(
            after.get("serving/kv_bytes_per_token", 0.0)),
    }
    samples = {
        "ttft_ms": ttft_ms,
        "running_slots": [s[2] for s in steps],
        "live_context_tokens": [s[3] for s in steps],
        "part_tok_s": parts,
    }

    # ---- correct: outside the window, against the plain reference
    del engine, loop
    gc.collect()
    ok = not short and not late and bool(finished)
    if late:
        bench.say(f"NOT CORRECT: clients {late} had no first token when "
                  "the window opened; raise warm_steps")
    ref = _check_against_reference(bench, cfg, srv, params, sampled)
    ok = (ok and ref["p75"] <= TOL_LOGPROB_P75
          and ref["rms"] <= TOL_LOGPROB_RMS
          and ref["outlier_share"] <= TOL_OUTLIER_SHARE)
    bench.say(f"reference: |logprob - ref| 75th percentile {ref['p75']:.4f} "
              f"(tol {TOL_LOGPROB_P75}), median {ref['p50']:.4f}; "
              f"{ref['outliers']} of {ref['n']} tokens off by more than "
              f"{TOL_STEP} (largest error {ref['max']:.4f}, largest "
              f"top-logit deficit {ref['deficit']:.4f}), share "
              f"{ref['outlier_share']:.4f} (tol {TOL_OUTLIER_SHARE}); rms "
              f"over the others {ref['rms']:.4f} (tol {TOL_LOGPROB_RMS}), "
              f"over all {ref['rms_all']:.4f}; {len(sampled)} requests")
    counters["ref_logprob_rms"] = ref["rms"]
    counters["ref_logprob_p75"] = ref["p75"]
    counters["ref_outlier_share"] = ref["outlier_share"]
    return {"correct": ok, "attempted": len(finished), "failed": short,
            "end_to_end": end_to_end, "counters": counters,
            "samples": samples, "window_s": window_s,
            "memory_peak_bytes": memory_peak}


def reference_errors(bench, cfg, srv, params, sampled):
    """Per checked token: |engine log-probability - reference's| of the
    chosen token, and the gap by which the chosen token trails the
    reference's best logit. Teacher-forced float32 forward over prompt +
    answer, the reference given the experts the engine holds."""
    import jax
    import jax.numpy as jnp

    ref = bench.manifest.reference(cfg["reference"])
    layers = {k: params["layers"][k] for k in ref.LAYER_LEAVES}
    held = cfg.get("experts_held")
    experts = ((int(held[0]), int(cfg["n_routed_experts"]))
               if held else None)
    width = int(srv["max_model_len"])
    errs, deficits = [], []
    for prompt, answer, logprobs in sampled:
        seq = (prompt + answer)[:-1]
        ids = np.zeros((width,), np.int32)
        ids[:len(seq)] = seq            # padding sits after every query
        hidden = ref.hidden_states(
            ids, params["embed"]["embedding"],
            lambda l: ref.take_layer(layers, jnp.asarray(l, jnp.int32)),
            params["final_norm"], cfg, experts=experts)
        n = min(CHECK_LAST, len(answer))
        at = np.arange(len(seq) - n, len(seq))       # rows that chose them
        rows = ref.logits(hidden[jnp.asarray(at)], params["lm_head"])
        logp = np.asarray(jax.nn.log_softmax(rows, axis=-1))
        rows = np.asarray(rows)
        chosen = np.asarray(answer[-n:])
        errs.append(np.abs(logp[np.arange(n), chosen]
                           - np.asarray(logprobs[-n:], np.float32)))
        deficits.append(rows.max(axis=-1) - rows[np.arange(n), chosen])
    return np.concatenate(errs), np.concatenate(deficits)


def _check_against_reference(bench, cfg, srv, params, sampled) -> Dict:
    err, deficit = reference_errors(bench, cfg, srv, params, sampled)
    n = len(err)
    if not (np.all(np.isfinite(err)) and np.all(np.isfinite(deficit))):
        inf = float("inf")
        return {"p50": inf, "p75": inf, "rms": inf, "rms_all": inf,
                "max": inf, "deficit": inf, "outliers": n,
                "outlier_share": 1.0, "n": n}
    inlier = (err <= TOL_STEP) & (deficit <= TOL_STEP)
    outliers = int(n - inlier.sum())
    return {
        "p50": stats.median(err.tolist()),
        "p75": stats.percentile(err.tolist(), 75.0),
        "rms": (float(np.sqrt(np.mean(err[inlier] ** 2)))
                if inlier.any() else float("inf")),
        "rms_all": float(np.sqrt(np.mean(err ** 2))),
        "max": float(err.max()), "deficit": float(deficit.max()),
        "outliers": outliers, "outlier_share": outliers / max(n, 1), "n": n}
