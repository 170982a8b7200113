"""Driver ``serve_closed_loop_ssm_attn``: the closed loop of
``serve_closed_loop`` (its :class:`ClosedLoop`, the same outputs, counters,
samples, ``ANNOTATIONS`` and ``PROGRAMS``) for a configuration of
state-space layers beside plain attention layers that each keep rows of
their own (``model_type: jamba``: Mamba-1 with RMSNorms on dt, B and C,
multi-query full attention, no positional encoding). The model is built by
the program's ``hf_config_to_model_config`` from the configuration file's
Hugging Face keys, and the plain reference named in the file takes each
layer's weights out of the program's tree through its own ``take_layer``.

It refuses at once, before any weight is made, where the program cannot
state such a model (its ``ModelConfig`` has no fact for the normed dt / B /
C, or its importer gives no per-layer spec with attention in it for these
keys): exit code 3, a line on standard error.

``correct`` is the comparison of ``serve_closed_loop``: the last 64
chosen-token log-probabilities that the timed run itself produced for two
finished requests, against the float32 reference's teacher-forced forward
over that request's own prompt + answer at the published widths (its own
length rounded up to the reference's query block, not ``max_model_len``:
the padding sits after every query).
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time
from typing import Dict

import numpy as np

from perfbench.drivers.serve_closed_loop import (  # noqa: F401  (re-exported)
    ANNOTATIONS, CHECK_LAST, PROGRAMS, ClosedLoop)
from perfbench.drivers.serve_closed_loop_hybrid import summary
from perfbench.lib import stats
from perfbench.lib.traffic import ClosedLoopTraffic

#: Limits of ``correct``, in nats, for the 28-layer bf16 engine (bf16
#: weights, activations and pages, float32 recurrent state) against the
#: float32 reference on random weights. One kind of error reaches a chosen
#: token's log-probability: rounding, in every matmul, norm, cached row and
#: (in float32) state update; no router is there to flip. So the
#: statistics are the smooth ones of the hybrid driver: the 75th percentile
#: and the root mean square of |log-probability - reference's| over the 128
#: checked tokens, and the largest gap by which a chosen token trails the
#: reference's best (two logits nearer than their errors swap places).
#:
#: Each limit lies between readings on the chip (my chip runs, PR 36;
#: PERF.md section 6 has them, with seeds): the largest this engine gave
#: over the builder's seventeen runs; what the reference gives against
#: itself with every matmul operand and every key and value row rounded to
#: e4m3 (a scale a row) and the recurrent state rounded to bfloat16 after
#: every token, the nearest precisions under the configuration's
#: (``lowp=True`` of the reference); and what an engine gives that leaves
#: out the three inner RMSNorms of the Mamba mixer (a deliberately wrong
#: engine, same weights):
#:   75th percentile   0.060 < 0.10 < 1.08 (lowp) < 3.40 (no inner norms)
#:   rms               0.053 < 0.09 < 0.88        < 2.85
#:   top-logit deficit 0.181 < 0.40 < 2.05        < 5.47
#: The check costs 25 to 47 s a run (two requests of 3,700 to 20,900
#: tokens; the reference takes queries 512 at a time).
TOL_LOGPROB_P75 = 0.10
TOL_LOGPROB_RMS = 0.09
TOL_ARGMAX = 0.4


def _model_config(cfg: Dict, srv: Dict):
    """The program's ModelConfig from the configuration file, or exit 3
    where the program cannot state this model."""
    try:
        from dla_tpu.models.config import ModelConfig
        from dla_tpu.models.hf_import import hf_config_to_model_config
    except ImportError as exc:
        print(f"[perfbench] the program is not in this directory: {exc}",
              file=sys.stderr)
        raise SystemExit(3)

    def refuse(why: str):
        print("[perfbench] this program cannot run configuration "
              f"{cfg.get('model_type')!r}: {why}", file=sys.stderr)
        raise SystemExit(3)

    if "ssm_inner_norms" not in {
            f.name for f in dataclasses.fields(ModelConfig)}:
        refuse("its ModelConfig has no fact for RMSNorms on the Mamba "
               "mixer's dt, B and C (`ssm_inner_norms`)")
    try:
        model_cfg = hf_config_to_model_config(
            cfg, dtype=srv["dtype"], param_dtype=srv["param_dtype"],
            attention=srv["attention"],
            max_seq_length=int(srv["max_model_len"]))
    except (KeyError, ValueError, TypeError) as exc:
        refuse(f"hf_config_to_model_config: {exc}")
    if not model_cfg.layers or "attention" not in {
            s.mixer for s in model_cfg.layers}:
        refuse("its importer gives no per-layer spec with plain attention "
               "for these keys")
    return model_cfg


def step_anatomy(ends, first_token_times) -> Dict[str, float]:
    """What ``itl_p99_ms`` rests on in this cell, from the times at which
    the window's engine steps ended (``ends``, the step before the window
    first) and the times of the requests' first tokens: the median step of
    each kind (one that ends a prompt, another that carries a chunk, one
    that only decodes: the two modes of the step time are split halfway
    between its 5th and 95th percentile) and the steps that took over 1.25
    times their kind's median, with the time they took beyond it (in a
    traced run the profiler's start and stop are among them). A token gap
    is a step, so a hundredth of the gaps is a hundredth of the steps: the
    cell's ``itl_p99_ms`` is about the ninth slowest step of 850, which is
    a step that ends a prompt while fewer than nine others are stalled."""
    step_ms = np.diff(np.asarray(ends, np.float64)) * 1e3
    firsts = set(first_token_times)
    ends_prompt = np.array([t in firsts for t in ends[1:]], bool)
    lo, hi = np.percentile(step_ms, [5.0, 95.0])
    chunk = (step_ms > 0.5 * (lo + hi)) & ~ends_prompt
    kinds = {"prompt_end": ends_prompt, "chunk": chunk,
             "plain": ~chunk & ~ends_prompt}
    out, stalled, excess = {}, 0, 0.0
    for name, mask in kinds.items():
        mine = step_ms[mask]
        out[f"steps_{name}"] = int(mine.size)
        out[f"step_ms_{name}"] = float(np.median(mine)) if mine.size else 0.0
        slow = mine[mine > 1.25 * out[f"step_ms_{name}"]]
        stalled += int(slow.size)
        excess += float((slow - out[f"step_ms_{name}"]).sum())
    out["stalled_steps"], out["stalled_excess_ms"] = stalled, excess
    return out


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
    anatomy = step_anatomy(
        [s[0] for s in loop.steps[warm_steps - 1:]],
        [r.times[0] for r in requests if r.times])
    bench.say("steps by kind, median ms: "
              f"{anatomy['steps_prompt_end']} that end a prompt "
              f"{anatomy['step_ms_prompt_end']:.2f}, "
              f"{anatomy['steps_chunk']} others with a chunk "
              f"{anatomy['step_ms_chunk']:.2f}, {anatomy['steps_plain']} "
              f"without {anatomy['step_ms_plain']:.2f}; "
              f"{anatomy['stalled_steps']} took over 1.25 x their kind's "
              f"median, {anatomy['stalled_excess_ms']:.0f} ms beyond it in "
              "all (a hundredth of the gaps is "
              f"{len(steps) / 100.0:.1f} steps)")
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
        "decode_steps": grew("serving/decode_steps"),
        "kv_bytes_per_token": float(
            after.get("serving/kv_bytes_per_token", 0.0)),
        "kv_paged_layers": float(after.get("serving/kv_paged_layers", 0.0)),
        "state_bytes_per_slot": float(
            after.get("serving/state_bytes_per_slot", 0.0)),
        # real tokens x state-space layers the window's chunks ran
        "prefill_scan_tokens": grew("serving/prefill/scan_tokens"),
        **anatomy,
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
    t_ref = time.perf_counter()
    ref = check_against_reference(bench, cfg, srv, params, sampled)
    ok = (ok and ref["p75"] <= TOL_LOGPROB_P75
          and ref["rms"] <= TOL_LOGPROB_RMS and ref["argmax"] <= TOL_ARGMAX)
    bench.say(f"reference: |logprob - ref| 75th percentile {ref['p75']:.4f} "
              f"(tol {TOL_LOGPROB_P75}), median {ref['p50']:.4f}, rms "
              f"{ref['rms']:.4f} (tol {TOL_LOGPROB_RMS}), max "
              f"{ref['max']:.4f}; worst top-logit deficit "
              f"{ref['argmax']:.4f} (tol {TOL_ARGMAX}) over {ref['n']} "
              f"tokens of {len(sampled)} requests of "
              f"{[len(p) + len(a) for p, a, _ in sampled]} tokens, in "
              f"{time.perf_counter() - t_ref:.1f}s")
    counters["ref_logprob_rms"] = ref["rms"]
    counters["ref_logprob_p75"] = ref["p75"]
    return {"correct": ok, "attempted": len(finished), "failed": short,
            "end_to_end": end_to_end, "counters": counters,
            "samples": samples, "window_s": window_s,
            "memory_peak_bytes": memory_peak}


def reference_errors(bench, cfg, srv, params, sampled, lowp=False):
    """Per checked token: |engine log-probability - reference's| of the
    chosen token, and the gap by which the chosen token trails the
    reference's best logit. Teacher-forced float32 forward over prompt +
    answer. ``lowp``: the reference in the precisions under the
    configuration's (the reading that sets the limits)."""
    import jax
    import jax.numpy as jnp

    ref = bench.manifest.reference(cfg["reference"])
    embedding = params["embed"]["embedding"]
    errs, deficits = [], []
    for prompt, answer, logprobs in sampled:
        seq = (prompt + answer)[:-1]
        # the request's own length, rounded up to the reference's query
        # block so that two requests share few compiled shapes
        width = -(-len(seq) // ref.Q_BLOCK) * ref.Q_BLOCK
        ids = np.zeros((width,), np.int32)
        ids[:len(seq)] = seq            # padding sits after every query
        hidden = ref.hidden_states(
            ids, embedding, lambda l: ref.take_layer(params["layers"], l),
            params["final_norm"], cfg, lowp=lowp)
        n = min(CHECK_LAST, len(answer))
        at = np.arange(len(seq) - n, len(seq))       # rows that chose them
        rows = ref.logits(hidden[jnp.asarray(at)], embedding)
        del hidden
        logp = np.asarray(jax.nn.log_softmax(rows, axis=-1))
        rows = np.asarray(rows)
        chosen = np.asarray(answer[-n:])
        errs.append(np.abs(logp[np.arange(n), chosen]
                           - np.asarray(logprobs[-n:], np.float32)))
        deficits.append(rows.max(axis=-1) - rows[np.arange(n), chosen])
    return np.concatenate(errs), np.concatenate(deficits)


def check_against_reference(bench, cfg, srv, params, sampled) -> Dict:
    return summary(*reference_errors(bench, cfg, srv, params, sampled))
