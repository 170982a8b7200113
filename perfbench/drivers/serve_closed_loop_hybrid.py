"""Driver ``serve_closed_loop_hybrid``: the closed loop of
``serve_closed_loop`` (its :class:`ClosedLoop`, the same outputs,
counters, samples, ``ANNOTATIONS`` and ``PROGRAMS``) for a configuration
whose layers are of several kinds (``model_type: phi4flash``: state-space
layers, window and full differential attention, gated memory units,
cross-attention onto one layer's cache). The model is built by the
program's ``hf_config_to_model_config`` from the configuration file's
Hugging Face keys, and the plain reference named in the file takes each
layer's weights out of the program's tree through its own ``take_layer``.

It refuses at once, before any weight is made, where the program cannot
state such a model (its ``ModelConfig`` has no per-layer spec): exit code
3, a line on standard error.

``correct`` is the comparison of ``serve_closed_loop``: the last 64
chosen-token log-probabilities that the timed run itself produced for two
finished requests, against the float32 reference's teacher-forced forward
over prompt + answer at the published widths.
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

#: Limits of ``correct``, in nats, for the 32-layer bf16 engine (bf16
#: weights, activations and pages, float32 recurrent state) against the
#: float32 reference on random weights. One kind of error reaches a chosen
#: token's log-probability: rounding, in every matmul, norm, cached row
#: and (in float32) state update; no router is there to flip. So the
#: statistics are the smooth ones: the 75th percentile and the root mean
#: square of |log-probability - reference's| over the 128 checked tokens,
#: and the largest gap by which a chosen token trails the reference's best
#: (two logits nearer than their errors swap places).
#:
#: Each limit lies between readings on the chip (PERF.md section 6 has
#: them, with seeds): the largest this engine gave over the builder's
#: runs; what the engine gives when window pages go back to the allocator
#: one page early (rows a query still sees are dropped); and what the
#: reference gives against itself with every matmul operand and every key
#: and value row rounded to e4m3 (a scale a row) and the recurrent state
#: rounded to bfloat16 after every token, the nearest precisions under the
#: configuration's (``lowp=True`` of the reference):
#:   75th percentile   0.062 < 0.10 < 0.30 (a page early) < 1.00 (lowp)
#:   rms               0.054 < 0.09 < 0.26               < 0.89
#:   top-logit deficit 0.216 < 0.40 < 0.70               < 2.42
#: NOT told apart: an engine whose recurrent state alone is kept in
#: bfloat16 (rounded after every update) reads 0.053 / 0.045 / 0.137, inside
#: this engine's own range: bfloat16's rounding of the state is of the
#: size of its rounding of every activation beside it.
TOL_LOGPROB_P75 = 0.10
TOL_LOGPROB_RMS = 0.09
TOL_ARGMAX = 0.4


class HybridLoop(ClosedLoop):
    """The callers, also keeping for each engine step the tokens its
    running slots hold inside the attention window (sum of min(length,
    window)): what the window layers have to read."""

    def __init__(self, engine, traffic, window: int):
        super().__init__(engine, traffic)
        self.window = int(window)
        self.window_tokens = []

    def step(self) -> None:
        self.window_tokens.append(sum(
            min(r.prompt_len + len(r.times), self.window)
            for r in self.open.values() if r.times))
        super().step()


def _model_config(cfg: Dict, srv: Dict):
    """The program's ModelConfig from the configuration file, or exit 3
    where the program cannot state a model of several kinds of layer."""
    try:
        from dla_tpu.models.config import ModelConfig
        from dla_tpu.models.hf_import import hf_config_to_model_config
    except ImportError as exc:
        print(f"[perfbench] the program is not in this directory: {exc}",
              file=sys.stderr)
        raise SystemExit(3)
    if "layers" not in {f.name for f in dataclasses.fields(ModelConfig)}:
        print("[perfbench] this program cannot run configuration "
              f"{cfg.get('model_type')!r}: its ModelConfig has no per-layer "
              "spec (`layers`)", file=sys.stderr)
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
    loop = HybridLoop(engine, traffic, int(cfg["sliding_window"]))
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
        "decode_steps": grew("serving/decode_steps"),
        "kv_bytes_per_token": float(
            after.get("serving/kv_bytes_per_token", 0.0)),
        # the cache manager's other two kinds of array
        "window_bytes_per_token": float(
            after.get("serving/window_bytes_per_token", 0.0)),
        "state_bytes_per_slot": float(
            after.get("serving/state_bytes_per_slot", 0.0)),
        "kv_shared_readers": float(
            after.get("serving/kv_shared_readers", 0.0)),
        "window_page_occupancy_peak": float(
            after.get("serving/window_page_occupancy_peak", 0.0)),
        "window_pages_released": grew("serving/window_pages_released"),
    }
    samples = {
        "ttft_ms": ttft_ms,
        "running_slots": [s[2] for s in steps],
        "live_context_tokens": [s[3] for s in steps],
        "window_tokens": loop.window_tokens[warm_steps:],
        "part_tok_s": parts,
    }

    # ---- correct: outside the window, against the plain reference
    del engine, loop
    gc.collect()
    ok = not short and not late and bool(finished)
    if late:
        bench.say(f"NOT CORRECT: clients {late} had no first token when "
                  "the window opened; raise warm_steps")
    ref = check_against_reference(bench, cfg, srv, params, sampled)
    ok = (ok and ref["p75"] <= TOL_LOGPROB_P75
          and ref["rms"] <= TOL_LOGPROB_RMS and ref["argmax"] <= TOL_ARGMAX)
    bench.say(f"reference: |logprob - ref| 75th percentile {ref['p75']:.4f} "
              f"(tol {TOL_LOGPROB_P75}), median {ref['p50']:.4f}, rms "
              f"{ref['rms']:.4f} (tol {TOL_LOGPROB_RMS}), max "
              f"{ref['max']:.4f}; worst top-logit deficit "
              f"{ref['argmax']:.4f} (tol {TOL_ARGMAX}) over {ref['n']} "
              f"tokens of {len(sampled)} requests")
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
    final_norm = (params["final_norm"], params["final_norm_bias"])
    width = int(srv["max_model_len"])
    errs, deficits = [], []
    for prompt, answer, logprobs in sampled:
        seq = (prompt + answer)[:-1]
        ids = np.zeros((width,), np.int32)
        ids[:len(seq)] = seq            # padding sits after every query
        hidden = ref.hidden_states(
            ids, embedding, lambda l: ref.take_layer(params["layers"], l),
            final_norm, cfg, lowp=lowp)
        n = min(CHECK_LAST, len(answer))
        at = np.arange(len(seq) - n, len(seq))       # rows that chose them
        rows = ref.logits(hidden[jnp.asarray(at)], embedding)
        logp = np.asarray(jax.nn.log_softmax(rows, axis=-1))
        rows = np.asarray(rows)
        chosen = np.asarray(answer[-n:])
        errs.append(np.abs(logp[np.arange(n), chosen]
                           - np.asarray(logprobs[-n:], np.float32)))
        deficits.append(rows.max(axis=-1) - rows[np.arange(n), chosen])
    return np.concatenate(errs), np.concatenate(deficits)


def summary(err, deficit) -> Dict:
    n = len(err)
    if not (np.all(np.isfinite(err)) and np.all(np.isfinite(deficit))):
        inf = float("inf")
        return {"p50": inf, "p75": inf, "rms": inf, "max": inf,
                "argmax": inf, "n": n}
    return {"p50": stats.median(err.tolist()),
            "p75": stats.percentile(err.tolist(), 75.0),
            "rms": float(np.sqrt(np.mean(err ** 2))),
            "max": float(err.max()), "argmax": float(deficit.max()), "n": n}


def check_against_reference(bench, cfg, srv, params, sampled) -> Dict:
    return summary(*reference_errors(bench, cfg, srv, params, sampled))
