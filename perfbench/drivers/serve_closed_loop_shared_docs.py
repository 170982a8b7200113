"""Driver ``serve_closed_loop_shared_docs``: the closed loop of
``serve_closed_loop`` (its :class:`ClosedLoop`, the same outputs, counters,
samples, ``ANNOTATIONS`` and ``PROGRAMS``) over a fixed set of long
documents that set-up builds into the engine's prefix cache: every request
is one of the documents, drawn by the seed, followed by a question of its
own, so each prefills one or two chunks of its question against the whole
document and then decodes. The model is built by the program's
``hf_config_to_model_config`` from the configuration file's Hugging Face
keys (a decoder of latent attention with a learned sparse indexer, and
routed experts behind leading dense layers: ``model_type:
glm_moe_dsa``).

It refuses at once, before any weight is made, where the program cannot
state such a model (its ``ModelConfig`` has no indexer, router scoring or
leading dense layers, or its importer refuses the keys): exit code 3, a
line on standard error.

Set-up: weights, then each document prefilled once as a request of its
own (one answer token; its pages stay indexed in the prefix cache), then
``warm_steps`` engine steps of the cell's own traffic, all before the
window. The window must see no prefix-cache eviction and no preemption:
either makes ``correct`` false, since the cell's steps would then be of
another kind than the ones it measures.

``correct``: over CHECK_SLOTS slots still decoding when the window closed,
each with at least CHECK_LAST answer tokens, against the float32
reference's teacher-forced forward over the slot's document + question +
answer so far:

- the chosen-token log-probabilities of the last CHECK_LAST tokens, with
  the statistics and the OUTLIER rule of ``serve_closed_loop_hf`` (bf16
  rounding is smooth; a routing flip or a column swapped at the
  selection's edge is a step): the 75th percentile of the errors and the
  share of outliers are held;
- the columns the engine's last decode step read in every layer (what
  the timed program selected, ``ServingEngine.dsa_last_selection``)
  against the reference's top-``index_topk`` at the same position: the
  mean share of the reference's set the engine read, held to TOL_OVERLAP.
  An engine that reads other columns than its indexer's best (the last
  ``index_topk``, say) moves the log-probabilities of a random-weight
  model by about what bf16 rounding does, but it reads a set that shares
  a few percent of the reference's.

``PERFBENCH_DSA_CONTROL`` in the environment runs the controls, a comma
list of ``e4m3`` (the reference with every matmul operand and every
cached row rounded to e4m3, the nearest precision under the
configuration's bf16) and ``last`` (the reference attending the last
``index_topk`` columns): each stands in for the engine's side of both
comparisons and has to come out as not correct.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import sys
import time
from typing import Dict, List

import numpy as np

from perfbench.drivers.serve_closed_loop import (  # noqa: F401  (re-exported)
    ANNOTATIONS, CHECK_LAST, PROGRAMS, ClosedLoop)
from perfbench.drivers.serve_closed_loop_ssm_attn import step_anatomy
from perfbench.lib import stats
from perfbench.lib.traffic import ClosedLoopTraffic, token_ids

#: Limits of ``correct`` (nats, a share of the checked tokens, and a share
#: of the reference's selected set), each between two readings on the chip
#: (v5e, nine seeds at two checked slots and five at four; PERF.md section
#: 6): this engine's largest, and the reference in place of the engine
#: computed with every matmul operand and cached row in e4m3 (a scale a
#: row), the nearest precision under the configuration's bf16
#: (``PERFBENCH_DSA_CONTROL``; at two slots, then at four):
#:   75th percentile of the errors      0.1873 < 0.30 < 0.6603, 0.7569
#:   share of outliers                  0.1641 < 0.30 < 0.6641, 0.7148
#:   overlap with the reference's sets  0.8904 > 0.80 > 0.6708, 0.6576
#: (the last ``index_topk`` columns in the engine's place: overlap 0.0532,
#: 0.0571).
#: The errors are five times the latent-attention cell's: a query's
#: selection is made on bf16 index scores, and the engine reads 8 to 14%
#: other columns than the float32 reference in the layers after the first
#: (0.5% in the first, whose input is the embedding), each a step in the
#: attention's output. The root mean square of the inliers is reported and
#: not held: it is bounded by TOL_STEP and reads 0.107 to 0.121 here
#: against the e4m3 reference's 0.142 to 0.156, too close to part them.
TOL_STEP = 0.25
TOL_LOGPROB_P75 = 0.30
TOL_OUTLIER_SHARE = 0.30
TOL_OVERLAP = 0.80
#: slots decoding at the window's close whose last tokens are checked:
#: 4 x CHECK_LAST = 256 tokens, and 4 x 5 layers of selected sets (the
#: reference takes ~27 s a slot on a v5e at 40k tokens)
CHECK_SLOTS = 4
#: the controls, a comma list of ``e4m3`` and ``last``
CONTROL = "PERFBENCH_DSA_CONTROL"

#: ModelConfig facts the configuration needs of the program
FACTS = ("index_topk", "index_n_heads", "index_head_dim", "moe_scoring",
         "first_k_dense", "moe_experts_held", "kv_lora_rank")


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
    except (KeyError, ValueError, TypeError, NotImplementedError) as exc:
        refuse(f"hf_config_to_model_config: {exc}")
    if not model_cfg.index_topk:
        refuse("its importer gives no sparse-attention indexer for these "
               "keys")
    return model_cfg


class SharedDocsTraffic:
    """The callers of a ``ClosedLoopTraffic`` whose every prompt is one of
    ``documents`` followed by the request's own question: the file's
    ``prompt`` grid sizes the questions, its ``output`` grid the answers,
    and the seed draws the document of each request, uniformly."""

    def __init__(self, spec: Dict, seed: int, vocab: int,
                 documents: List[int]):
        self.questions = ClosedLoopTraffic(spec, seed, vocab)
        self.n = self.questions.n
        self.seed = int(seed)
        self.docs = [[int(t) for t in token_ids(seed, (6, i), n, vocab)]
                     for i, n in enumerate(documents)]
        self.grid = [(max(map(len, self.docs)) + p, o)
                     for p, o in self.questions.grid]

    def document(self, client: int, k: int) -> int:
        return int(np.random.default_rng(
            [self.seed, 7, int(k), int(client)]).integers(len(self.docs)))

    def request(self, client: int, k: int):
        question, out_len = self.questions.request(client, k)
        return self.docs[self.document(client, k)] + question, out_len


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
    documents = [int(n) for n in mix["shared_documents"]]
    if bench.rehearsal:      # toy windows: documents of one or two chunks
        c = int(srv["prefill_chunk"])
        documents = [c * (1 + i % 2) for i in range(len(documents))]
    traffic = SharedDocsTraffic(mix, bench.seed, int(cfg["vocab_size"]),
                                documents)
    gen = GenerationConfig(max_new_tokens=max(o for _, o in traffic.grid),
                           do_sample=False, eos_token_id=-1)  # to length
    engine = ServingEngine(model, params, gen, ServingConfig(
        page_size=int(srv["page_size"]), num_pages=int(srv["num_pages"]),
        num_slots=int(srv["num_slots"]),
        max_model_len=int(srv["max_model_len"]),
        prefill_chunk=int(srv["prefill_chunk"]), prefix_cache=True))
    loop = ClosedLoop(engine, traffic)
    try:
        # set-up: every document prefilled once and left in the prefix
        # cache, then the cell's own traffic for a fixed count of engine
        # steps (the opening burst of prefills out of the window)
        for doc in traffic.docs:
            engine.submit(doc, 1)
        while engine.has_work():
            engine.step()
        warm_chunks = engine.metrics.snapshot()["serving/prefill/chunks"]
        bench.say(f"{len(traffic.docs)} documents of {documents} tokens in "
                  f"the prefix cache after {warm_chunks:.0f} chunks")
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
        # a rehearsal's answers are a sixteenth as long
        last_n = 8 if bench.rehearsal else CHECK_LAST
        checked = decoding_slots(engine, bench.seed, last_n)
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

    def grew(key: str) -> float:
        return float(after.get(key, 0.0)) - float(before.get(key, 0.0))

    evictions, preemptions = (grew("serving/prefix_cache/evictions"),
                              grew("serving/preemptions"))
    bench.say("steps by kind, median ms: "
              f"{anatomy['steps_prompt_end']} that end a prompt "
              f"{anatomy['step_ms_prompt_end']:.2f}, "
              f"{anatomy['steps_chunk']} others with a chunk "
              f"{anatomy['step_ms_chunk']:.2f}, {anatomy['steps_plain']} "
              f"without {anatomy['step_ms_plain']:.2f}; "
              f"{anatomy['stalled_steps']} took over 1.25 x their kind's "
              f"median, {anatomy['stalled_excess_ms']:.0f} ms beyond it in "
              "all; in the window "
              f"{evictions:.0f} prefix-cache evictions, {preemptions:.0f} "
              f"preemptions, prefix hits {grew('serving/prefix_cache/hit_tokens'):.0f} of "
              f"{grew('serving/prompt_tokens'):.0f} prompt tokens")
    parts = stats.subwindow_rates(
        [s[0] for s in steps], [s[1] for s in steps], t0, window_s,
        float(mix["part_seconds"]))
    end_to_end = {
        "serve_tok_s": tokens / window_s,
        "itl_p99_ms": stats.percentile(gaps_ms, 99.0),
        "ttft_p50_ms": stats.median(ttft_ms) if ttft_ms else float("nan"),
        "setup_s": setup_s,
    }
    counters = {
        "engine_steps": len(steps),
        "tokens": tokens,
        "requests_finished": len(finished),
        "prefill_chunks": grew("serving/prefill/chunks"),
        "preemptions": preemptions,
        "prefix_evictions": evictions,
        "page_occupancy_peak": after["serving/page_occupancy_peak"],
        "num_slots": int(srv["num_slots"]),
        "warm_steps": warm_steps,
        "warm_document_chunks": warm_chunks,
        "clients_not_prefilled_in_warmup": len(late),
        "itl_samples": len(gaps_ms),
        "ttft_samples": len(ttft_ms),
        # the window's dropless routing, summed over layers and steps
        "decode_steps": grew("serving/decode_steps"),
        "moe_experts_hit": grew("serving/moe/experts_hit"),
        "moe_expert_assignments": grew("serving/moe/expert_assignments"),
        "kv_bytes_per_token": float(
            after.get("serving/kv_bytes_per_token", 0.0)),
        # the prefix cache: prompt tokens its pages covered, of those sent
        "prefix_hit_tokens": grew("serving/prefix_cache/hit_tokens"),
        "prompt_tokens": grew("serving/prompt_tokens"),
        # sparse attention, per query over decode steps and chunks
        "dsa_live_tokens": grew("serving/dsa/live_tokens"),
        "dsa_selected_tokens": grew("serving/dsa/selected_tokens"),
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
    ok = (not short and not late and bool(finished) and not evictions
          and not preemptions and len(checked) == CHECK_SLOTS)
    if late:
        bench.say(f"NOT CORRECT: clients {late} had no first token when "
                  "the window opened; raise warm_steps")
    if evictions or preemptions:
        bench.say("NOT CORRECT: the window saw prefix-cache evictions or "
                  "preemptions: its steps are not the cell's")
    if len(checked) < CHECK_SLOTS:
        bench.say(f"NOT CORRECT: {len(checked)} slots with {last_n} "
                  "answer tokens were decoding at the window's close")
    t_ref = time.perf_counter()
    controls = [c for c in os.environ.get(CONTROL, "").split(",") if c]
    width = max(p + o for p, o in traffic.grid)
    engine_side = check(bench, cfg, params, checked, width)
    ok = ok and engine_side["pass"]
    for name in controls:
        side = check(bench, cfg, params, checked, width, control=name)
        bench.say(f"CONTROL {name} ({CONTROL}): the reference in its "
                  f"place reads {side['say']}: "
                  f"{'passes' if side['pass'] else 'fails'} the limits")
        ok = ok and side["pass"]
    bench.say(f"reference: {engine_side['say']}; {len(checked)} slots of "
              f"{[len(f) for f, _, _, _ in checked]} tokens; in "
              f"{time.perf_counter() - t_ref:.1f}s")
    counters["ref_logprob_rms"] = engine_side["rms"]
    counters["ref_logprob_p75"] = engine_side["p75"]
    counters["ref_outlier_share"] = engine_side["outlier_share"]
    counters["ref_selection_overlap"] = engine_side["overlap"]
    return {"correct": ok, "attempted": len(finished), "failed": short,
            "end_to_end": end_to_end, "counters": counters,
            "samples": samples, "window_s": window_s,
            "memory_peak_bytes": memory_peak}


def decoding_slots(engine, seed, last_n: int = CHECK_LAST) -> List[tuple]:
    """CHECK_SLOTS slots that the engine's last decode step ran, each with
    more than ``last_n`` answer tokens, picked by the seed: (the tokens
    fed up to that step's query, the answer's last ``last_n`` tokens and
    their log-probabilities, the last of them chosen by that step, and
    the columns it read [layers, index_topk])."""
    last = engine.dsa_last_selection
    if last is None:
        return []
    slots, lengths, selected = last
    selected = np.asarray(selected)[:, :, 0]              # [L, B, k]
    ok = []
    for i, slot in enumerate(slots):
        req = engine.scheduler.running.get(slot)
        if req is None or len(req.generated) <= last_n:
            continue
        fed = list(req.prompt_tokens) + list(req.generated)[:-1]
        if len(fed) != int(lengths[i]) + 1:
            continue            # not the request that step ran
        ok.append((fed, list(req.generated)[-last_n:],
                   list(req.generated_logprobs)[-last_n:],
                   selected[:, slot]))
    pick = np.random.default_rng([int(seed), 5]).permutation(len(ok))
    return [ok[int(i)] for i in pick[:CHECK_SLOTS]]


def check(bench, cfg, params, checked, width, control=None) -> Dict:
    """The statistics ``correct`` holds to its limits, and whether they
    pass. ``control``: ``e4m3`` or ``last``, the reference so computed in
    place of the engine's side."""
    import jax
    import jax.numpy as jnp

    ref = bench.manifest.reference(cfg["reference"])
    layer = ref.layer_getter(params, cfg)
    held = cfg.get("experts_held")
    experts = ((int(held[0]), int(cfg["n_routed_experts"]))
               if held else None)
    errs, deficits, overlaps = [], [], []

    def forward(ids, n, last_n, lowp=False, select="top"):
        hidden, sets = ref.hidden_states(
            ids, params["embed"]["embedding"], layer, params["final_norm"],
            cfg, experts=experts, lowp=lowp, select=select,
            sets_at=[n - 1])
        at = jnp.asarray(np.arange(n - last_n, n))
        rows = ref.logits(hidden[at], params["lm_head"], lowp=lowp)
        return (np.asarray(rows), np.stack([np.asarray(s[0])
                                            for s in sets]))

    for fed, answer, logprobs, selected in checked:
        # the rows at the last positions of ``fed`` chose the answer's
        # last tokens; the last row is the query of the engine's last
        # decode step
        n, last_n = len(fed), len(answer)
        ids = np.zeros((width,), np.int32)
        ids[:n] = fed                   # padding sits after every query
        chosen, k = np.asarray(answer), np.arange(last_n)
        rows, want_sets = forward(ids, n, last_n)
        logp = np.asarray(jax.nn.log_softmax(rows, axis=-1))
        got, got_sets = np.asarray(logprobs, np.float32), selected
        if control:
            low_rows, got_sets = forward(
                ids, n, last_n, lowp=control == "e4m3",
                select="last" if control == "last" else "top")
            got = np.asarray(jax.nn.log_softmax(low_rows, axis=-1))[
                k, chosen]
        errs.append(np.abs(logp[k, chosen] - got))
        deficits.append(rows.max(axis=-1) - rows[k, chosen])
        overlaps += [len(np.intersect1d(g, w)) / len(w)
                     for g, w in zip(got_sets, want_sets)]
    if not checked:
        errs, deficits = [np.full(1, np.inf)], [np.full(1, np.inf)]
    out = summary(np.concatenate(errs), np.concatenate(deficits))
    out["overlap"] = float(np.mean(overlaps)) if overlaps else 0.0
    out["pass"] = (out["p75"] <= TOL_LOGPROB_P75
                   and out["outlier_share"] <= TOL_OUTLIER_SHARE
                   and out["overlap"] >= TOL_OVERLAP)
    out["say"] = (
        f"|logprob - ref| 75th percentile {out['p75']:.4f} (tol "
        f"{TOL_LOGPROB_P75}), median {out['p50']:.4f}; {out['outliers']} of "
        f"{out['n']} tokens off by more than {TOL_STEP} (largest error "
        f"{out['max']:.4f}, largest top-logit deficit {out['deficit']:.4f}), "
        f"share {out['outlier_share']:.4f} (tol {TOL_OUTLIER_SHARE}); rms "
        f"over the others {out['rms']:.4f} (reported), over "
        f"all {out['rms_all']:.4f}; selected-set overlap with the "
        f"reference's top-k {out['overlap']:.4f} (at least {TOL_OVERLAP}), "
        f"by layer and slot {[round(o, 4) for o in overlaps]}")
    return out


def summary(err, deficit) -> Dict:
    """The statistics of the log-probability comparison."""
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
