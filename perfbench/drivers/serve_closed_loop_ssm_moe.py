"""Driver ``serve_closed_loop_ssm_moe``: the closed loop of
``serve_closed_loop`` (its :class:`ClosedLoop`, the same outputs, counters,
samples, ``ANNOTATIONS`` and ``PROGRAMS``) for a configuration of Mamba-2
layers beside grouped-query attention, with routed experts and a shared
expert in every layer and muP scalars (``model_type: granitemoehybrid``).
The model is built by the program's ``hf_config_to_model_config`` from the
configuration file's Hugging Face keys; the plain reference named in the
file takes each layer's weights out of the program's tree through its own
``take_layer``, and is given the experts this chip holds.

It refuses at once, before any weight is made, where the program cannot
state such a model (its ``ModelConfig`` has no Mamba-2 or muP facts, or
its importer gives no per-layer spec with Mamba-2 layers for these keys):
exit code 3, a line on standard error.

``correct``: two comparisons with the float32 reference, both over what
the timed run itself left behind.

Log-probabilities: the last 64 chosen-token log-probabilities of
CHECK_REQUESTS finished requests, against the reference's teacher-forced
forward over that request's own prompt + answer (padded to one width, so
the reference compiles once). Two kinds of error reach them, as in
``serve_closed_loop_hf``: bf16 rounding, smooth; and a routing flip (a
token's 10th and 11th router logits nearer than bf16's rounding of the
router's input send it to another expert than the reference), a step. So
a checked token is an OUTLIER where its log-probability is off by more
than TOL_STEP or it trails the reference's best logit by more than
TOL_STEP; the share of outliers is held to TOL_OUTLIER_SHARE, the 75th
percentile of all the errors and the root mean square of the inliers' to
their limits.

The recurrent state: every Mamba-2 layer's state [heads, head_dim, N] of
one slot still decoding when the window closed, read back from the pool,
against the reference's state after the same tokens (the slot's prompt
and the answer fed so far); the largest relative (Frobenius) error over
the layers is held to TOL_STATE. The configuration keeps that state in
float32: the share of its elements that a bfloat16 cannot hold (a float32
whose low 16 bits are not all 0) is held to TOL_STATE_F32_SHARE from
below. A state rounded to bfloat16 reads 0 there, while on this model's
random weights its error against the reference stays inside the engine's
own (the other bfloat16 roundings upstream of the state dominate).

``PERFBENCH_LOWP_CONTROL=1`` in the environment runs the control: the
engine's side of both comparisons is taken from the reference in the
precisions under the configuration's (``lowp``: e4m3 operands and rows, a
bfloat16 state), which has to come out as not correct.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import sys
import time
from typing import Dict

import numpy as np

from perfbench.drivers.serve_closed_loop import (  # noqa: F401  (re-exported)
    ANNOTATIONS, CHECK_LAST, PROGRAMS, ClosedLoop)
from perfbench.drivers.serve_closed_loop_ssm_attn import step_anatomy
from perfbench.lib import stats
from perfbench.lib.traffic import ClosedLoopTraffic

#: Limits of ``correct`` (nats; a relative error and a share for the
#: state), set from readings on the chip (my chip runs, PR 40; PERF.md
#: section 6 has them with seeds). The engine's largest over seven seeds:
#: 0.0083 / 0.0059 and a state error of 0.0149 (float32 share 1.0). The
#: control: 0.0056 to 0.0093 / 0.0048 to 0.0077, which the engine
#: overlaps, and a state error of 0.112 to 0.115 (share 0.0), which fails.
#: Wrong engines: ``residual_multiplier`` 1 and the norm without its gate
#: fail the log-probability limits; the state rounded to bfloat16 (error
#: 0.0125) fails the share alone (0.0 on the CPU).
#:   75th percentile, rms of the inliers, share of outliers, state error,
#:   state's float32 share
TOL_STEP = 0.25
TOL_LOGPROB_P75 = 0.025
TOL_LOGPROB_RMS = 0.025
TOL_OUTLIER_SHARE = 0.10
TOL_STATE = 0.03
TOL_STATE_F32_SHARE = 0.5
#: finished requests whose last CHECK_LAST tokens are checked
CHECK_REQUESTS = 6
#: the control: the reference in lowp stands in for the engine
CONTROL = "PERFBENCH_LOWP_CONTROL"

#: ModelConfig facts the configuration needs of the program
FACTS = ("ssm_heads", "ssm_head_dim", "ssm_groups", "embedding_multiplier",
         "residual_multiplier", "attention_multiplier", "logits_scaling",
         "moe_experts_held")


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

    known = {f.name for f in dataclasses.fields(ModelConfig)}
    missing = [k for k in FACTS if k not in known]
    if missing:
        refuse(f"its ModelConfig has no {', '.join(missing)}")
    try:
        model_cfg = hf_config_to_model_config(
            cfg, dtype=srv["dtype"], param_dtype=srv["param_dtype"],
            attention=srv["attention"],
            max_seq_length=int(srv["max_model_len"]))
    except (KeyError, ValueError, TypeError) as exc:
        refuse(f"hf_config_to_model_config: {exc}")
    if not model_cfg.layers or "ssm2" not in {
            s.mixer for s in model_cfg.layers}:
        refuse("its importer gives no per-layer spec with Mamba-2 layers "
               "for these keys")
    return model_cfg


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
        rng = np.random.default_rng([int(bench.seed), 5])
        pick = rng.permutation(len(finished))[:CHECK_REQUESTS]
        sampled = []
        for i in pick:
            rid, req = finished[int(i)]
            res = engine.result(rid)
            prompt, _ = traffic.request(req.client, req.k)
            sampled.append((prompt, list(res.generated),
                            list(res.generated_logprobs)))
        held_state = slot_state(engine, rng)
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
        # the window's dropless routing, summed over layers and steps
        "decode_steps": grew("serving/decode_steps"),
        "moe_experts_hit": grew("serving/moe/experts_hit"),
        "moe_expert_assignments": grew("serving/moe/expert_assignments"),
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
    control = os.environ.get(CONTROL) == "1"
    if control:
        bench.say(f"CONTROL ({CONTROL}): the reference in lowp stands in "
                  "for the engine")
    ref = check_against_reference(bench, cfg, params, sampled, held_state,
                                  traffic, control)
    ok = (ok and ref["p75"] <= TOL_LOGPROB_P75
          and ref["rms"] <= TOL_LOGPROB_RMS
          and ref["outlier_share"] <= TOL_OUTLIER_SHARE
          and ref["state"] <= TOL_STATE
          and ref["state_f32_share"] >= TOL_STATE_F32_SHARE)
    bench.say(f"reference: |logprob - ref| 75th percentile {ref['p75']:.4f} "
              f"(tol {TOL_LOGPROB_P75}), median {ref['p50']:.4f}; "
              f"{ref['outliers']} of {ref['n']} tokens off by more than "
              f"{TOL_STEP} (largest error {ref['max']:.4f}, largest "
              f"top-logit deficit {ref['deficit']:.4f}), share "
              f"{ref['outlier_share']:.4f} (tol {TOL_OUTLIER_SHARE}); rms "
              f"over the others {ref['rms']:.4f} (tol {TOL_LOGPROB_RMS}), "
              f"over all {ref['rms_all']:.4f}; {len(sampled)} requests of "
              f"{[len(p) + len(a) for p, a, _ in sampled]} tokens; state "
              f"after {ref['state_tokens']} tokens, relative error by layer "
              f"{[round(e, 5) for e in ref['state_layers']]}, largest "
              f"{ref['state']:.5f} (tol {TOL_STATE}), largest of one head "
              f"{ref['state_head']:.5f}; share of its elements in float32 "
              f"precision {ref['state_f32_share']:.5f} (at least "
              f"{TOL_STATE_F32_SHARE}); in "
              f"{time.perf_counter() - t_ref:.1f}s")
    counters["ref_logprob_rms"] = ref["rms"]
    counters["ref_logprob_p75"] = ref["p75"]
    counters["ref_outlier_share"] = ref["outlier_share"]
    counters["ref_state_rel_err"] = ref["state"]
    counters["ref_state_f32_share"] = ref["state_f32_share"]
    return {"correct": ok, "attempted": len(finished), "failed": short,
            "end_to_end": end_to_end, "counters": counters,
            "samples": samples, "window_s": window_s,
            "memory_peak_bytes": memory_peak}


def slot_state(engine, rng):
    """(tokens fed, every Mamba-2 layer's state [layers, H, P, N] on the
    host) of one slot that is decoding, picked by ``rng``; None if no slot
    is. A slot's state holds its prompt and every answer token but the
    newest, which the next step feeds."""
    running = sorted(s for s, req in engine.scheduler.running.items()
                     if req.generated)
    if not running:
        return None
    slot = running[int(rng.integers(len(running)))]
    req = engine.scheduler.running[slot]
    fed = int(engine.cache.lengths[slot])
    si, _ = engine.model.hybrid._slots["state"]
    return ((list(req.prompt_tokens) + list(req.generated))[:fed],
            np.asarray(engine.cache.pools[si][:, slot], np.float32))


def _reference_pass(bench, cfg, params, ids, n_valid, lowp):
    """(hidden [T, D], the Mamba-2 layers' states after ``n_valid``
    tokens) of the reference over ``ids``, the held experts given."""
    ref = bench.manifest.reference(cfg["reference"])
    held = cfg.get("experts_held")
    experts = ((int(held[0]), int(cfg["num_local_experts"]))
               if held else None)
    return ref.hidden_states(
        ids, params["embed"]["embedding"],
        lambda l: ref.take_layer(params["layers"], l), params["final_norm"],
        cfg, experts=experts, lowp=lowp, n_valid=n_valid, states=True)


def reference_errors(bench, cfg, params, sampled, width, control=False):
    """Per checked token: |engine log-probability - reference's| of the
    chosen token, and the gap by which the chosen token trails the
    reference's best logit. Teacher-forced float32 forward over prompt +
    answer, padded to ``width``. ``control``: the reference in lowp's
    log-probability in place of the engine's."""
    import jax
    import jax.numpy as jnp

    ref = bench.manifest.reference(cfg["reference"])
    embedding = params["embed"]["embedding"]
    scaling = float(cfg.get("logits_scaling", 1.0))
    errs, deficits = [], []
    for prompt, answer, logprobs in sampled:
        seq = (prompt + answer)[:-1]
        ids = np.zeros((width,), np.int32)
        ids[:len(seq)] = seq            # padding sits after every query
        n = min(CHECK_LAST, len(answer))
        at = jnp.asarray(np.arange(len(seq) - n, len(seq)))  # chose them
        chosen = np.asarray(answer[-n:])
        hidden, _ = _reference_pass(bench, cfg, params, ids, len(seq), False)
        rows = ref.logits(hidden[at], embedding, scaling)
        del hidden
        logp = np.asarray(jax.nn.log_softmax(rows, axis=-1))
        rows = np.asarray(rows)
        got = np.asarray(logprobs[-n:], np.float32)
        if control:
            low, _ = _reference_pass(bench, cfg, params, ids, len(seq), True)
            got = np.asarray(jax.nn.log_softmax(ref.logits(
                low[at], embedding, scaling, lowp=True), axis=-1))[
                    np.arange(n), chosen]
            del low
        errs.append(np.abs(logp[np.arange(n), chosen] - got))
        deficits.append(rows.max(axis=-1) - rows[np.arange(n), chosen])
    return np.concatenate(errs), np.concatenate(deficits)


def state_errors(bench, cfg, params, held_state, width, control=False):
    """Relative (Frobenius) error of each Mamba-2 layer's state, and of
    each head's [P, N] block, against the reference's after the same
    tokens, and the share of the state's elements that carry float32
    precision: ([layers], [layers, heads], share). ``control``: the
    reference in lowp's state in place of the engine's."""
    fed, got = held_state
    ids = np.zeros((max(width, len(fed)),), np.int32)
    ids[:len(fed)] = fed
    _, want = _reference_pass(bench, cfg, params, ids, len(fed), False)
    want = np.stack([np.asarray(s) for s in want])
    if control:
        _, low = _reference_pass(bench, cfg, params, ids, len(fed), True)
        got = np.stack([np.asarray(s) for s in low])
    got = got.reshape(want.shape)
    sq = (got - want) ** 2
    layer = np.sqrt(sq.sum((1, 2, 3)) / (want ** 2).sum((1, 2, 3)))
    head = np.sqrt(sq.sum((2, 3)) / (want ** 2).sum((2, 3)))
    return layer, head, f32_share(got)


def f32_share(state) -> float:
    """Share of the elements of a float32 array that a bfloat16 cannot
    hold: their low 16 bits are not all 0."""
    bits = np.ascontiguousarray(state, np.float32).view(np.uint32)
    return float(np.mean((bits & 0xFFFF) != 0))


def summary(err, deficit) -> Dict:
    """The statistics ``correct`` holds to its limits."""
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


def check_against_reference(bench, cfg, params, sampled, held_state,
                            traffic, control=False) -> Dict:
    """The statistics ``correct`` holds to its limits; the reference runs
    at one width, the longest request of the traffic's grid."""
    block = bench.manifest.reference(cfg["reference"]).Q_BLOCK
    width = -(-max(p + o for p, o in traffic.grid) // block) * block
    out = summary(*reference_errors(bench, cfg, params, sampled, width,
                                    control))
    if held_state is None:
        inf = float("inf")
        return {**out, "state": inf, "state_head": inf, "state_layers": [],
                "state_tokens": 0, "state_f32_share": 0.0}
    layer, head, share = state_errors(bench, cfg, params, held_state, width,
                                      control)
    return {**out, "state": float(layer.max()),
            "state_head": float(head.max()), "state_f32_share": share,
            "state_layers": layer.tolist(),
            "state_tokens": len(held_state[0])}
