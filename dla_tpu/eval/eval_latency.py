"""Latency / throughput harness (phase 5b) — the framework's measurement
tool and the source of BASELINE numbers.

CLI parity: ``python -m dla_tpu.eval.eval_latency --config
config/eval_config.yaml`` (reference src/eval/eval_latency.py). Artifact
parity: ``latency.json`` maps model -> list of {batch_size, seq_length,
tokens_per_second, latency_ms} rows over the configured grid with
warmup + synchronized timing (reference measure_model, :22-63).

Extensions the reference lacks (SURVEY.md sec 6): each row also reports
``tokens_per_second_per_chip``, and a ``decode`` section measures true
autoregressive decode throughput (the reference measured only forward
passes despite its docstring, eval_latency.py:1).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from dla_tpu.generation.engine import GenerationConfig, build_generate_fn
from dla_tpu.training.config import load_config
from dla_tpu.training.model_io import load_causal_lm
from dla_tpu.training.utils import seed_everything
from dla_tpu.utils.compile_cache import enable_compile_cache
from dla_tpu.utils.logging import log_rank_zero, percentile


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description="dla_tpu latency benchmark")
    p.add_argument("--config", required=True)
    p.add_argument("--serving", action="store_true",
                   help="also run the continuous-batching serving engine "
                        "on a synthetic Poisson arrival trace (equivalent "
                        "to latency.serving.enabled: true)")
    p.add_argument("--overload", action="store_true",
                   help="run the overload A/B (burst injected mid-trace,"
                        " admission control on vs off; equivalent to "
                        "latency.serving.overload.enabled: true)")
    p.add_argument("--shared-prefix", action="store_true",
                   help="also run the shared-prefix serving A/B: K prompt "
                        "families x N requests each, prefix cache on vs "
                        "off on the SAME trace (equivalent to "
                        "latency.serving.shared_prefix.enabled: true)")
    p.add_argument("--speculative", action="store_true",
                   help="also run the speculative-decoding serving A/B: "
                        "the SAME Poisson trace through two engines, "
                        "draft/verify speculation on vs off (equivalent "
                        "to latency.serving.speculative.enabled: true)")
    p.add_argument("--fleet", action="store_true",
                   help="also run the fleet routing A/B/C: the SAME "
                        "shared-prefix Poisson trace through a single "
                        "engine, an N-engine fleet with random "
                        "placement, and an N-engine fleet with "
                        "cache-aware routing (equivalent to "
                        "latency.serving.fleet.enabled: true)")
    p.add_argument("--disagg", action="store_true",
                   help="also run the prefill/decode disaggregation "
                        "A/B/C: the SAME long-prompt Poisson trace "
                        "through one chunked engine, a mixed fleet, and "
                        "a prefill+decode role split with KV page "
                        "migration (equivalent to "
                        "latency.serving.disagg.enabled: true)")
    p.add_argument("--gateway", action="store_true",
                   help="also run the gateway wire A/B: the SAME "
                        "Poisson trace in-process vs over localhost "
                        "HTTP through the streaming gateway (SSE "
                        "per-token events; equivalent to "
                        "latency.serving.gateway.enabled: true)")
    p.add_argument("--tenancy", action="store_true",
                   help="also run the multi-tenant serving A/B: N "
                        "tenants' LoRA adapters batched into ONE "
                        "engine (per-slot adapter gather) vs serving "
                        "them serially with merge-and-republish swaps, "
                        "plus a noisy-tenant quota-isolation probe "
                        "(equivalent to "
                        "latency.serving.tenancy.enabled: true)")
    return p.parse_args(argv)


def _sync(out) -> None:
    """Force completion of every computation ``out`` depends on:
    ``block_until_ready`` plus a one-element device->host fetch, which
    cannot return before the value exists. One leaf suffices — all
    outputs of a jitted call materialize with its single XLA
    executable."""
    jax.block_until_ready(out)
    leaves = jax.tree.leaves(out)
    if leaves:
        np.asarray(leaves[0][(0,) * leaves[0].ndim])


def measure_forward(model, params, batch_sizes: List[int],
                    seq_lengths: List[int], warmup: int, steps: int
                    ) -> List[Dict[str, float]]:
    fwd = jax.jit(lambda p, ids, mask: model.apply(
        p, ids, attention_mask=mask))
    rows: List[Dict[str, float]] = []
    n_chips = jax.device_count()
    rs = np.random.RandomState(0)
    for b in batch_sizes:
        for s in seq_lengths:
            ids = jnp.asarray(
                rs.randint(0, model.cfg.vocab_size - 1, (b, s)), jnp.int32)
            mask = jnp.ones((b, s), jnp.int32)
            for _ in range(warmup):
                _sync(fwd(params, ids, mask))
            # dispatch the whole loop, then sync each step's output:
            # steps still pipeline on-device when the backend is sane,
            # and a lazy backend is forced to execute every step (not
            # just the last one it happens to fetch)
            t0 = time.perf_counter()
            outs = [fwd(params, ids, mask) for _ in range(steps)]
            for i in range(steps):
                # drop each reference as it syncs: retaining all
                # [B, S, V] logits buffers would multiply peak HBM
                # by `steps`
                _sync(outs[i])
                outs[i] = None
            dt = time.perf_counter() - t0
            tokens = b * s * steps
            rows.append({
                "batch_size": b,
                "seq_length": s,
                "tokens_per_second": tokens / dt,
                "tokens_per_second_per_chip": tokens / dt / n_chips,
                "latency_ms": dt / steps * 1000,
            })
            log_rank_zero(f"[dla_tpu][latency] b={b} s={s}: "
                          f"{rows[-1]['tokens_per_second']:.0f} tok/s "
                          f"{rows[-1]['latency_ms']:.2f} ms/step")
    return rows


def measure_decode(model, params, batch_size: int, prompt_len: int,
                   new_tokens: int, warmup: int = 1, reps: int = 3
                   ) -> Dict[str, float]:
    """True autoregressive decode throughput through the KV-cache engine."""
    gen = GenerationConfig(max_new_tokens=new_tokens, do_sample=True,
                           temperature=1.0, eos_token_id=-1)  # never stop
    fn = jax.jit(build_generate_fn(model, gen))
    rs = np.random.RandomState(0)
    ids = jnp.asarray(
        rs.randint(3, model.cfg.vocab_size - 1, (batch_size, prompt_len)),
        jnp.int32)
    mask = jnp.ones((batch_size, prompt_len), jnp.int32)
    for _ in range(warmup):
        _sync(fn(params, ids, mask, jax.random.key(0)))
    t0 = time.perf_counter()
    outs = [fn(params, ids, mask, jax.random.key(r)) for r in range(reps)]
    for r in range(reps):
        _sync(outs[r])
        outs[r] = None
    dt = time.perf_counter() - t0
    total_new = batch_size * new_tokens * reps
    return {
        "batch_size": batch_size,
        "prompt_length": prompt_len,
        "new_tokens": new_tokens,
        "decode_tokens_per_second": total_new / dt,
        "decode_tokens_per_second_per_chip": total_new / dt / jax.device_count(),
        "ms_per_token": dt / (new_tokens * reps) * 1000,
    }


def _serving_config(srv: Dict, **overrides):
    """Build a ServingConfig from a ``latency.serving`` mapping —
    including the nested ``prefix_cache:`` / ``chunked_prefill:``
    blocks — with keyword overrides applied last."""
    from dla_tpu.serving import ServingConfig

    pc = srv.get("prefix_cache") or {}
    cp = srv.get("chunked_prefill") or {}
    chunk = cp.get("chunk")
    kw = dict(
        page_size=int(srv.get("page_size", 16)),
        num_pages=int(srv.get("num_pages", 256)),
        num_slots=int(srv.get("num_slots", 8)),
        max_model_len=int(srv.get("max_model_len", 256)),
        # unset, the engine works the chunk out from page_size and
        # max_model_len
        prefill_chunk=None if chunk is None else int(chunk),
        prefill_token_budget=int(cp.get("token_budget", 0)),
        prefix_cache=bool(pc.get("enabled", False)),
        cached_logits_capacity=int(pc.get("cached_logits_capacity", 128)),
        speculative=srv.get("speculative"),
        # pass through the trainer-style profiling window ({trace_dir,
        # start_step, num_steps}) — an xplane trace of the measured
        # serving run is one config key away
        profile=srv.get("profile"))
    kw.update(overrides)
    return ServingConfig(**kw)


def _warm_prompt(eng, new_tokens: int) -> List[int]:
    """One chunk-wide prompt (cut to leave ``new_tokens`` of the slot
    window): it reaches the one prefill program whatever the trace's
    prompt lengths."""
    plen = min(eng.cfg.prefill_chunk,
               eng.cache.geom.slot_window - new_tokens)
    return [3 + (i % 251) for i in range(plen)]


def _warm(eng) -> None:
    """Compile warm-up off the clock, on the engine that is measured:
    the warm prompt's second token reaches the decode step or the
    speculative pair (a 1-token request finishes at prefill). Then zero
    the instrument panel: percentiles must measure serving, not XLA."""
    from dla_tpu.serving.metrics import ServingMetrics

    eng.submit(_warm_prompt(eng, 2), 2)
    eng.run_until_drained()
    eng.metrics = ServingMetrics()


def _drive_open_loop(eng, prompts: List[List[int]], arrivals: np.ndarray,
                     new_tokens: int) -> tuple:
    """Open-loop drive: submit each prompt at its SCHEDULED arrival time
    (so queueing delay under load is measured, not hidden), step the
    engine whenever it has work, idle-spin otherwise. Returns
    ``(duration_s, outputs)`` where outputs[i] is the generated token
    list of prompts[i], collected from the streaming surface."""
    n = len(prompts)
    order: List[int] = []
    toks: Dict[int, List[int]] = {}
    t0 = time.perf_counter()
    submitted = 0
    while submitted < n or eng.has_work():
        now = time.perf_counter() - t0
        while submitted < n and arrivals[submitted] <= now:
            rid = eng.submit(prompts[submitted], new_tokens,
                             arrival_time=t0 + arrivals[submitted])
            order.append(rid)
            toks[rid] = []
            submitted += 1
        if not eng.has_work():
            continue   # open-loop: idle-spin until the next arrival
        for rid, tok in eng.step():
            toks[rid].append(tok)
    dt = time.perf_counter() - t0
    return dt, [toks[r] for r in order]


def measure_serving(model, params, srv: Dict) -> Dict[str, float]:
    """Continuous-batching engine under a synthetic Poisson arrival
    trace: per-request TTFT and inter-token-latency percentiles
    (p50/p95), sustained request/token throughput, preemption count and
    peak page-pool occupancy. Open-loop arrivals — a request's TTFT
    clock starts at its SCHEDULED arrival, so queueing delay under load
    is measured, not hidden."""
    from dla_tpu.serving import ServingConfig, ServingEngine

    n = int(srv.get("num_requests", 16))
    rate = float(srv.get("arrival_rate", 16.0))     # requests / second
    new_tokens = int(srv.get("new_tokens", 32))
    pmin = int(srv.get("prompt_len_min", 8))
    pmax = int(srv.get("prompt_len_max", 64))
    gen = GenerationConfig(max_new_tokens=new_tokens, do_sample=False,
                           eos_token_id=-1)          # run to length
    scfg = _serving_config(srv)
    eng = ServingEngine(model, params, gen, scfg)
    rs = np.random.RandomState(int(srv.get("seed", 0)))
    prompts = [list(rs.randint(3, model.cfg.vocab_size - 1,
                               (rs.randint(pmin, pmax + 1),)))
               for _ in range(n)]
    arrivals = np.cumsum(rs.exponential(1.0 / rate, n))

    _warm(eng)
    dt, _ = _drive_open_loop(eng, prompts, arrivals, new_tokens)
    snap = eng.metrics.snapshot()
    return {
        "num_requests": n,
        "arrival_rate": rate,
        "new_tokens": new_tokens,
        "num_slots": scfg.num_slots,
        "duration_s": dt,
        "requests_per_second": n / dt,
        "serve_tokens_per_second": snap["serving/tokens_generated"] / dt,
        "ttft_ms_p50": snap["serving/ttft_ms_p50"],
        "ttft_ms_p95": snap["serving/ttft_ms_p95"],
        "ttft_ms_p99": snap["serving/ttft_ms_p99"],
        "itl_ms_p50": snap["serving/itl_ms_p50"],
        "itl_ms_p95": snap["serving/itl_ms_p95"],
        "itl_ms_p99": snap["serving/itl_ms_p99"],
        "queue_wait_ms_p50": snap["serving/queue_wait_ms_p50"],
        "queue_wait_ms_p95": snap["serving/queue_wait_ms_p95"],
        "queue_wait_ms_p99": snap["serving/queue_wait_ms_p99"],
        "preemptions": snap["serving/preemptions"],
        "page_occupancy_peak": snap["serving/page_occupancy_peak"],
        "prefill_chunks": snap["serving/prefill/chunks"],
        "prefill_tokens_saved": snap["serving/prefill/tokens_saved"],
        "prefix_cache_hit_tokens": snap["serving/prefix_cache/hit_tokens"],
    }


def measure_shared_prefix(model, params, srv: Dict) -> Dict[str, object]:
    """Shared-prefix A/B: K prompt families x N requests per family, the
    SAME prompts and arrival schedule driven through two engines — prefix
    cache ON vs OFF (both chunked-prefill, both greedy). Reports the
    cache hit rate, the fraction of prefill tokens the cache saved, TTFT
    p50/p95 and ITL p95 for both arms, and whether the generated tokens
    are bit-identical (greedy decode must not change under caching)."""
    from dla_tpu.serving import ServingEngine
    from dla_tpu.serving.metrics import ServingMetrics

    sp = srv.get("shared_prefix") or {}
    families = int(sp.get("families", 8))
    per_family = int(sp.get("requests_per_family", 16))
    prefix_len = int(sp.get("prefix_len", 48))
    suffix_len = int(sp.get("suffix_len", 16))
    new_tokens = int(srv.get("new_tokens", 32))
    rate = float(srv.get("arrival_rate", 16.0))
    gen = GenerationConfig(max_new_tokens=new_tokens, do_sample=False,
                           eos_token_id=-1)          # greedy, run to length
    rs = np.random.RandomState(int(srv.get("seed", 0)))
    vocab = model.cfg.vocab_size
    prompts: List[List[int]] = []
    for _ in range(families):
        head = [int(t) for t in rs.randint(3, vocab - 1, (prefix_len,))]
        for _ in range(per_family):
            prompts.append(head + [int(t) for t in
                                   rs.randint(3, vocab - 1, (suffix_len,))])
    n = len(prompts)
    arrivals = np.cumsum(rs.exponential(1.0 / rate, n))
    prompt_tokens = sum(len(p) for p in prompts)
    cp = srv.get("chunked_prefill") or {}
    # hits are chunk-granular: unless the config picks a chunk, probe
    # with a finer one than the engine's own default
    chunk = int(cp.get("chunk", 0)) or 2 * int(srv.get("page_size", 16))

    def run_arm(cache_on: bool):
        eng = ServingEngine(model, params, gen, _serving_config(
            srv, prefill_chunk=chunk, prefix_cache=cache_on))
        # compile warmup (chunk fn + decode), off the clock; random
        # tokens can't collide with a family prefix, so the cache stays
        # cold for the measured trace
        eng.submit([int(t) for t in
                    rs.randint(3, vocab - 1, (chunk + 1,))], 1)
        eng.run_until_drained()
        eng.metrics = ServingMetrics()
        dt, outs = _drive_open_loop(eng, prompts, arrivals, new_tokens)
        return dt, outs, eng.metrics.snapshot()

    dt_on, outs_on, snap_on = run_arm(True)
    dt_off, outs_off, snap_off = run_arm(False)
    saved = snap_on["serving/prefill/tokens_saved"]
    hit_tok = snap_on["serving/prefix_cache/hit_tokens"]
    return {
        "families": families,
        "requests_per_family": per_family,
        "prefix_len": prefix_len,
        "suffix_len": suffix_len,
        "new_tokens": new_tokens,
        "prefill_chunk": chunk,
        "prompt_tokens": prompt_tokens,
        "outputs_identical": outs_on == outs_off,
        "cache_hit_rate": hit_tok / max(prompt_tokens, 1),
        "prefill_tokens_saved_frac": saved / max(prompt_tokens, 1),
        "cache_lookups": snap_on["serving/prefix_cache/lookups"],
        "cache_evictions": snap_on["serving/prefix_cache/evictions"],
        "ttft_ms_p50_cache_on": snap_on["serving/ttft_ms_p50"],
        "ttft_ms_p95_cache_on": snap_on["serving/ttft_ms_p95"],
        "ttft_ms_p50_cache_off": snap_off["serving/ttft_ms_p50"],
        "ttft_ms_p95_cache_off": snap_off["serving/ttft_ms_p95"],
        "itl_ms_p95_cache_on": snap_on["serving/itl_ms_p95"],
        "itl_ms_p95_cache_off": snap_off["serving/itl_ms_p95"],
        "duration_s_cache_on": dt_on,
        "duration_s_cache_off": dt_off,
    }


def measure_fleet(model, params, srv: Dict) -> Dict[str, object]:
    """Fleet routing A/B/C: the SAME shared-prefix Poisson trace driven
    through (1) a single engine, (2) an N-engine fleet with random
    placement, and (3) an N-engine fleet with cache-aware routing — all
    greedy, all prefix-cache + chunked-prefill on. Reports TTFT/ITL
    p50/p95/p99 per arm, per-engine prefix-cache hit rates, the fleet
    hit-rate retention vs the single engine (random placement destroys
    cross-request prefix locality; routing must recover it), and the
    bit-identity assertion across all three arms (the per-request
    ``fold_in(seed, k)`` sampling contract makes outputs
    placement-independent)."""
    from dla_tpu.serving import (
        FleetConfig, FleetRouter, ServingEngine)
    from dla_tpu.serving.metrics import ServingMetrics

    fl = srv.get("fleet") or {}
    engines = int(fl.get("engines", 4))
    sp = srv.get("shared_prefix") or {}
    families = int(sp.get("families", 8))
    per_family = int(sp.get("requests_per_family", 16))
    prefix_len = int(sp.get("prefix_len", 48))
    suffix_len = int(sp.get("suffix_len", 16))
    new_tokens = int(srv.get("new_tokens", 32))
    rate = float(srv.get("arrival_rate", 16.0))
    gen = GenerationConfig(max_new_tokens=new_tokens, do_sample=False,
                           eos_token_id=-1)          # greedy, run to length
    rs = np.random.RandomState(int(srv.get("seed", 0)))
    vocab = model.cfg.vocab_size
    prompts: List[List[int]] = []
    for _ in range(families):
        head = [int(t) for t in rs.randint(3, vocab - 1, (prefix_len,))]
        for _ in range(per_family):
            prompts.append(head + [int(t) for t in
                                   rs.randint(3, vocab - 1, (suffix_len,))])
    n = len(prompts)
    arrivals = np.cumsum(rs.exponential(1.0 / rate, n))
    prompt_tokens = sum(len(p) for p in prompts)
    cp = srv.get("chunked_prefill") or {}
    chunk = int(cp.get("chunk", 0)) or 2 * int(srv.get("page_size", 16))

    def build_engine(slot=0):
        # fault_plan="" pins every fleet member fault-free even when
        # $DLA_FAULT_PLAN is set in the environment
        return ServingEngine(model, params, gen, _serving_config(
            srv, prefill_chunk=chunk, prefix_cache=True, fault_plan=""))

    def warm(eng):
        # compile warmup (chunk fn + decode) off the clock; random
        # tokens can't collide with a family prefix, so the cache stays
        # cold for the measured trace
        eng.submit([int(t) for t in
                    rs.randint(3, vocab - 1, (chunk + 1,))], 1)
        eng.run_until_drained()
        eng.metrics = ServingMetrics()

    def arm_stats(member_engines, dt, outs):
        ttft = [s for e in member_engines
                for s in e.metrics.ttft_ms.samples]
        itl = [s for e in member_engines
               for s in e.metrics.itl_ms.samples]
        hits = [e.metrics.snapshot()["serving/prefix_cache/hit_tokens"]
                for e in member_engines]
        gen_tokens = sum(len(o) for o in outs)
        return {
            "duration_s": dt,
            "decode_tokens_per_s": gen_tokens / max(dt, 1e-9),
            "hit_rate": sum(hits) / max(prompt_tokens, 1),
            "per_engine_hit_tokens": hits,
            **{f"ttft_ms_p{q}": percentile(ttft, float(q))
               for q in (50, 95, 99)},
            **{f"itl_ms_p{q}": percentile(itl, float(q))
               for q in (50, 95, 99)},
        }

    def run_single():
        eng = build_engine()
        warm(eng)
        dt, outs = _drive_open_loop(eng, prompts, arrivals, new_tokens)
        return outs, arm_stats([eng], dt, outs)

    def run_fleet(placement: str):
        router = FleetRouter(
            lambda slot: build_engine(slot),
            FleetConfig(engines=engines, min_engines=1,
                        max_engines=engines, placement=placement))
        for m in router.members():
            warm(m.engine)
        dt, outs = _drive_open_loop(router, prompts, arrivals, new_tokens)
        stats = arm_stats([m.engine for m in router.members()], dt, outs)
        stats["fleet"] = {k: v for k, v in router.fleet_snapshot().items()
                          if not k.endswith("_peak")}
        router.close()
        return outs, stats

    outs_single, single = run_single()
    outs_random, random_ = run_fleet("random")
    outs_routed, routed = run_fleet("cache_aware")
    return {
        "engines": engines,
        "families": families,
        "requests_per_family": per_family,
        "prefix_len": prefix_len,
        "suffix_len": suffix_len,
        "new_tokens": new_tokens,
        "prefill_chunk": chunk,
        "prompt_tokens": prompt_tokens,
        "outputs_identical": outs_single == outs_random == outs_routed,
        "hit_rate_retention": (routed["hit_rate"]
                               / max(single["hit_rate"], 1e-9)),
        "single": single,
        "fleet_random": random_,
        "fleet_routed": routed,
    }


def measure_disagg(model, params, srv: Dict) -> Dict[str, object]:
    """Prefill/decode disaggregation A/B/C: the SAME long-prompt
    Poisson trace driven through (A) one chunked engine, (B) a mixed
    co-scheduled fleet of P+D members, and (C) a role-split fleet of P
    prefill + D decode members where every finished prefix ships to a
    decode member as a KV migration ticket. All greedy, prefix cache +
    chunked prefill on. Reports TTFT/ITL p50/p95/p99 per arm plus arm
    C's migration counters, and asserts bit-identical outputs across
    all three arms (migration resumes from the exact committed KV, and
    sampling is ``fold_in(seed, k)`` — placement-independent)."""
    from dla_tpu.serving import (
        FleetConfig, FleetRouter, ServingEngine)
    from dla_tpu.serving.metrics import ServingMetrics

    dg = srv.get("disagg") or {}
    n_prefill = int(dg.get("prefill_engines", 1))
    n_decode = int(dg.get("decode_engines", 2))
    n_req = int(dg.get("num_requests", 24))
    rate = float(dg.get("arrival_rate",
                        srv.get("arrival_rate", 16.0)))
    # long prompts: the regime where prefill HOL-blocks co-scheduled
    # decode and a dedicated prefill tier pays for the page transfer
    prompt_len = int(dg.get("prompt_len", 48))
    new_tokens = int(dg.get("new_tokens", srv.get("new_tokens", 32)))
    engines = n_prefill + n_decode
    roles = ("prefill",) * n_prefill + ("decode",) * n_decode
    transport = str((srv.get("migration") or {}).get("transport", "auto"))
    gen = GenerationConfig(max_new_tokens=new_tokens, do_sample=False,
                           eos_token_id=-1)          # greedy, run to length
    rs = np.random.RandomState(int(srv.get("seed", 0)))
    vocab = model.cfg.vocab_size
    prompts = [[int(t) for t in rs.randint(3, vocab - 1, (prompt_len,))]
               for _ in range(n_req)]
    arrivals = np.cumsum(rs.exponential(1.0 / rate, n_req))
    cp = srv.get("chunked_prefill") or {}
    chunk = int(cp.get("chunk", 0)) or 2 * int(srv.get("page_size", 16))

    def build_engine(slot=0, role="mixed"):
        # fault_plan="" pins every member fault-free even when
        # $DLA_FAULT_PLAN is set in the environment
        return ServingEngine(model, params, gen, _serving_config(
            srv, prefill_chunk=chunk, prefix_cache=True, fault_plan="",
            role=role))

    def warm(eng):
        # compile warmup off the clock; decode-role members gate
        # submit(), so warm those through restore() — the handoff-only
        # admission surface — which compiles the same chunk + decode fns
        prompt = [int(t) for t in rs.randint(3, vocab - 1, (chunk + 1,))]
        if eng.cfg.role == "decode":
            eng.restore(prompt, 1, generated=[], arrival_time=0.0)
        else:
            eng.submit(prompt, 1)
        eng.run_until_drained()
        eng.metrics = ServingMetrics()

    def arm_stats(member_engines, dt, outs):
        ttft = [s for e in member_engines
                for s in e.metrics.ttft_ms.samples]
        itl = [s for e in member_engines
               for s in e.metrics.itl_ms.samples]
        gen_tokens = sum(len(o) for o in outs)
        return {
            "duration_s": dt,
            "decode_tokens_per_s": gen_tokens / max(dt, 1e-9),
            **{f"ttft_ms_p{q}": percentile(ttft, float(q))
               for q in (50, 95, 99)},
            **{f"itl_ms_p{q}": percentile(itl, float(q))
               for q in (50, 95, 99)},
        }

    def run_single():
        eng = build_engine()
        warm(eng)
        dt, outs = _drive_open_loop(eng, prompts, arrivals, new_tokens)
        return outs, arm_stats([eng], dt, outs)

    def run_fleet(role_split: bool):
        fc = FleetConfig(engines=engines, min_engines=1,
                         max_engines=engines,
                         roles=roles if role_split else None,
                         migration_transport=transport)
        router = FleetRouter(
            lambda slot: build_engine(
                slot, roles[slot] if role_split else "mixed"), fc)
        for m in router.members():
            warm(m.engine)
        dt, outs = _drive_open_loop(router, prompts, arrivals, new_tokens)
        stats = arm_stats([m.engine for m in router.members()], dt, outs)
        mig_keys = ("migrations", "migrated_pages", "host_bounce_bytes",
                    "failed_migrations")
        snaps = [m.engine.metrics.snapshot() for m in router.members()]
        stats["migration"] = {
            k: sum(s[f"serving/migration/{k}"] for s in snaps)
            for k in mig_keys}
        stats["migration"]["migrated_pages_per_s"] = (
            stats["migration"]["migrated_pages"] / max(dt, 1e-9))
        router.close()
        return outs, stats

    outs_single, single = run_single()
    outs_mixed, mixed = run_fleet(role_split=False)
    outs_split, split = run_fleet(role_split=True)
    return {
        "prefill_engines": n_prefill,
        "decode_engines": n_decode,
        "num_requests": n_req,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "prefill_chunk": chunk,
        "migration_transport": transport,
        "outputs_identical": outs_single == outs_mixed == outs_split,
        "single": single,
        "fleet_mixed": mixed,
        "fleet_disagg": split,
    }


def measure_speculative(model, params, srv: Dict) -> Dict[str, object]:
    """Speculative-decoding A/B: the serving Poisson trace driven
    through two engines — blockwise draft/verify speculation ON vs OFF —
    on the SAME prompts and arrival schedule (both greedy). Reports ITL
    and TTFT p50/p95 for both arms, the measured draft acceptance rate,
    decode rounds vs tokens, and whether the generated tokens are
    bit-identical (speculation must not change greedy output)."""
    from dla_tpu.serving import ServingEngine

    sp = dict(srv.get("speculative") or {})
    sp.pop("enabled", None)
    sp.setdefault("k", 4)
    sp.setdefault("draft", "int8")
    n = int(srv.get("num_requests", 16))
    rate = float(srv.get("arrival_rate", 16.0))
    new_tokens = int(srv.get("new_tokens", 32))
    pmin = int(srv.get("prompt_len_min", 8))
    pmax = int(srv.get("prompt_len_max", 64))
    gen = GenerationConfig(max_new_tokens=new_tokens, do_sample=False,
                           eos_token_id=-1)          # greedy, run to length
    rs = np.random.RandomState(int(srv.get("seed", 0)))
    vocab = model.cfg.vocab_size
    prompts = [list(rs.randint(3, vocab - 1,
                               (rs.randint(pmin, pmax + 1),)))
               for _ in range(n)]
    arrivals = np.cumsum(rs.exponential(1.0 / rate, n))

    def run_arm(spec_on: bool):
        eng = ServingEngine(model, params, gen, _serving_config(
            srv, speculative=dict(sp, enabled=True) if spec_on else None))
        _warm(eng)
        dt, outs = _drive_open_loop(eng, prompts, arrivals, new_tokens)
        return dt, outs, eng.metrics.snapshot()

    dt_on, outs_on, snap_on = run_arm(True)
    dt_off, outs_off, snap_off = run_arm(False)
    return {
        "num_requests": n,
        "arrival_rate": rate,
        "new_tokens": new_tokens,
        "k": int(sp["k"]),
        "draft": str(sp["draft"]),
        "outputs_identical": outs_on == outs_off,
        "acceptance_rate": snap_on["serving/spec/acceptance_rate"],
        "spec_rounds": snap_on["serving/spec/rounds"],
        "spec_rollbacks": snap_on["serving/spec/rollbacks"],
        "tokens_generated": snap_on["serving/tokens_generated"],
        "serve_tokens_per_second_spec_on":
            snap_on["serving/tokens_generated"] / dt_on,
        "serve_tokens_per_second_spec_off":
            snap_off["serving/tokens_generated"] / dt_off,
        "itl_ms_p50_spec_on": snap_on["serving/itl_ms_p50"],
        "itl_ms_p95_spec_on": snap_on["serving/itl_ms_p95"],
        "itl_ms_p50_spec_off": snap_off["serving/itl_ms_p50"],
        "itl_ms_p95_spec_off": snap_off["serving/itl_ms_p95"],
        "ttft_ms_p50_spec_on": snap_on["serving/ttft_ms_p50"],
        "ttft_ms_p95_spec_on": snap_on["serving/ttft_ms_p95"],
        "ttft_ms_p50_spec_off": snap_off["serving/ttft_ms_p50"],
        "ttft_ms_p95_spec_off": snap_off["serving/ttft_ms_p95"],
        "duration_s_spec_on": dt_on,
        "duration_s_spec_off": dt_off,
    }


def measure_overload(model, params, srv: Dict) -> Dict[str, object]:
    """Overload A/B: the serving Poisson trace with a K-request burst
    injected at the mid-trace instant, driven through two engines —
    admission control + load shedding ON vs OFF — on the SAME prompts
    and arrival schedule. Reports the shed rate and p99 TTFT for both
    arms, and asserts the zero-lost-requests invariant: every submitted
    request reaches a terminal state (finished, timed out, or shed) in
    both arms — shedding converts queue collapse into explicit, counted
    rejections, it never loses work silently."""
    from dla_tpu.serving import ServingEngine

    ov = srv.get("overload") or {}
    n = int(srv.get("num_requests", 16))
    rate = float(srv.get("arrival_rate", 16.0))
    burst = int(ov.get("burst", 32))
    new_tokens = int(ov.get("new_tokens", srv.get("new_tokens", 32)))
    pmin = int(srv.get("prompt_len_min", 8))
    pmax = int(srv.get("prompt_len_max", 64))
    gen = GenerationConfig(max_new_tokens=new_tokens, do_sample=False,
                           eos_token_id=-1)          # run to length
    rs = np.random.RandomState(int(srv.get("seed", 0)))
    vocab = model.cfg.vocab_size
    prompts = [list(rs.randint(3, vocab - 1,
                               (rs.randint(pmin, pmax + 1),)))
               for _ in range(n + burst)]
    base = np.cumsum(rs.exponential(1.0 / rate, n))
    # the burst: K requests landing at the SAME mid-trace instant —
    # the adversarial arrival pattern admission control exists for
    t_burst = base[n // 2]
    arrivals = np.sort(np.concatenate([base, np.full(burst, t_burst)]))
    num_slots = int(srv.get("num_slots", 8))
    shed = dict(srv.get("shed") or {})
    shed.pop("enabled", None)
    # a queue bound the burst overflows, so the shed arm actually sheds
    shed.setdefault("max_queue_depth", 2 * num_slots)

    def run_arm(shed_on: bool):
        eng = ServingEngine(model, params, gen, _serving_config(
            srv, shed=shed if shed_on else None))
        _warm(eng)
        dt, _ = _drive_open_loop(eng, prompts, arrivals, new_tokens)
        snap = eng.metrics.snapshot()
        submitted = snap["serving/requests_submitted"]
        terminal = (snap["serving/requests_finished"]
                    + snap["serving/requests_timed_out"]
                    + snap["serving/requests_cancelled"]
                    + snap["serving/requests_shed"])
        return dt, snap, submitted - terminal

    dt_on, snap_on, lost_on = run_arm(True)
    dt_off, snap_off, lost_off = run_arm(False)
    return {
        "num_requests": n,
        "burst": burst,
        "arrival_rate": rate,
        "new_tokens": new_tokens,
        "shed_rate": snap_on["serving/requests_shed"]
        / max(snap_on["serving/requests_submitted"], 1),
        "requests_shed": snap_on["serving/requests_shed"],
        "queue_timeouts_shed_on": snap_on["serving/queue_timeouts"],
        "degradation_level_final": snap_on[
            "serving/degradation_level"],
        "ttft_ms_p99_shed_on": snap_on["serving/ttft_ms_p99"],
        "ttft_ms_p99_shed_off": snap_off["serving/ttft_ms_p99"],
        "ttft_ms_p50_shed_on": snap_on["serving/ttft_ms_p50"],
        "ttft_ms_p50_shed_off": snap_off["serving/ttft_ms_p50"],
        "requests_lost_shed_on": lost_on,
        "requests_lost_shed_off": lost_off,
        "duration_s_shed_on": dt_on,
        "duration_s_shed_off": dt_off,
    }


def measure_gateway(model, params, srv: Dict) -> Dict[str, object]:
    """Gateway A/B: the SAME Poisson trace driven in-process (arm A,
    the engine stepped directly) and over localhost HTTP through the
    streaming gateway (arm B, per-token SSE events read by client
    threads). Reports both arms' client-observed TTFT/ITL percentiles,
    the wire overhead per token, greedy bit-identity across arms, and
    exercises a mid-trace client disconnect (the gateway must cancel
    the orphaned request and count it).

    The wire arm runs with distributed tracing ON (enabled process
    tracer + span spool): after the drive, the spool is merged with
    ``tools/trace_merge.py`` and every completed wire request must
    yield a complete span tree in the merged trace — the A/B output
    reports spans-per-request and the coverage verdict."""
    import http.client
    import shutil
    import tempfile
    import threading

    from dla_tpu.serving import ServingEngine, ServingGateway
    from dla_tpu.telemetry.trace import (Tracer, get_tracer,
                                         install_tracer)

    gwc = srv.get("gateway") or {}
    n = int(gwc.get("num_requests", srv.get("num_requests", 16)))
    rate = float(gwc.get("arrival_rate", srv.get("arrival_rate", 16.0)))
    new_tokens = int(gwc.get("new_tokens", srv.get("new_tokens", 16)))
    pmin = int(srv.get("prompt_len_min", 8))
    pmax = int(srv.get("prompt_len_max", 64))
    gen = GenerationConfig(max_new_tokens=new_tokens, do_sample=False,
                           eos_token_id=-1)          # run to length
    rs = np.random.RandomState(int(srv.get("seed", 0)))
    vocab = model.cfg.vocab_size
    prompts = [[int(t) for t in rs.randint(3, vocab - 1,
                                           (rs.randint(pmin, pmax + 1),))]
               for _ in range(n)]
    arrivals = np.cumsum(rs.exponential(1.0 / rate, n))

    # ---- arm A: in-process (the measure_serving drive) --------------
    eng = ServingEngine(model, params, gen, _serving_config(srv))
    _warm(eng)
    dt_in, out_in = _drive_open_loop(eng, prompts, arrivals, new_tokens)
    snap = eng.metrics.snapshot()

    # ---- arm B: the same trace over localhost HTTP, tracing ON ------
    spool_dir = tempfile.mkdtemp(prefix="dla-gw-spool-")
    prev_tracer = get_tracer()
    install_tracer(Tracer.from_config(
        {"enabled": True, "capacity": 1 << 17,
         "spool_dir": spool_dir, "proc": "gateway"}))
    gw = ServingGateway(ServingEngine(model, params, gen,
                                      _serving_config(srv)))

    def http_generate(prompt, events_out=None, stop_after=None):
        """POST /v1/generate and read the SSE stream; returns the token
        list, appending a perf_counter stamp per event to events_out.
        ``stop_after=k`` closes the socket after k events (the
        disconnect probe)."""
        conn = http.client.HTTPConnection("127.0.0.1", gw.port,
                                          timeout=300)
        try:
            conn.request("POST", "/v1/generate", json.dumps(
                {"prompt": prompt, "max_new_tokens": new_tokens}
            ).encode(), {"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                raise RuntimeError(f"generate -> {resp.status}")
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
                if events_out is not None:
                    events_out.append(time.perf_counter())
                if stop_after is not None and len(toks) >= stop_after:
                    break               # hang up mid-stream
            return toks
        finally:
            conn.close()

    # warm the gateway's engine THROUGH the wire, off the clock (arm A,
    # the same configuration, was warmed in-process)
    http_generate(_warm_prompt(eng, new_tokens))

    out_wire: List[List[int]] = [None] * n
    stamps: List[List[float]] = [[] for _ in range(n)]
    t0 = time.perf_counter()

    def client(i):
        delay = t0 + arrivals[i] - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        out_wire[i] = http_generate(prompts[i], events_out=stamps[i])

    threads = [threading.Thread(target=client, args=(i,),
                                name=f"dla-gwclient-{i}", daemon=True)
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    dt_wire = time.perf_counter() - t0

    ttft = [1e3 * (stamps[i][0] - (t0 + arrivals[i]))
            for i in range(n) if stamps[i]]
    itl = [1e3 * (b - a) for ev in stamps
           for a, b in zip(ev, ev[1:])]
    total_tokens = sum(len(o or []) for o in out_wire)

    # ---- disconnect probe: hang up mid-stream, gateway must cancel --
    before = gw.metrics.registry.snapshot()[
        "serving/gateway/disconnect_cancels"]
    http_generate(prompts[0], stop_after=1)
    deadline = time.perf_counter() + 30
    cancels = before
    while cancels <= before and time.perf_counter() < deadline:
        time.sleep(0.05)
        cancels = gw.metrics.registry.snapshot()[
            "serving/gateway/disconnect_cancels"]
    gw.close()

    # ---- trace coverage: merge the wire arm's spool and demand one
    # complete span tree per completed wire request -------------------
    tracer = get_tracer()
    trace_dropped = tracer.dropped
    tracer.detach_spool()              # flush + close the spool file
    install_tracer(prev_tracer)
    from tools.trace_merge import merge_dir, validate
    merged = merge_dir(Path(spool_dir))
    problems = validate(merged)
    per_trace: Dict[str, List[Dict]] = {}
    for ev in merged["traceEvents"]:
        tid = (ev.get("args") or {}).get("trace")
        if tid and ev.get("ph") in ("X", "b", "i"):
            per_trace.setdefault(tid, []).append(ev)
    # a COMPLETE tree closed its root: the gateway's wire_request span
    # emits on request completion, so a trace without one is a request
    # the wire never finished (or a span the ring evicted)
    complete = {t: evs for t, evs in per_trace.items()
                if any(e["name"] == "wire_request" for e in evs)}
    completed_wire = sum(1 for o in out_wire if o is not None)
    spans_per_request = (sum(len(v) for v in complete.values())
                         / max(len(complete), 1))
    shutil.rmtree(spool_dir, ignore_errors=True)

    return {
        "trace_spans_per_request": spans_per_request,
        "trace_requests_traced": len(complete),
        "trace_coverage_complete": (not problems
                                    and trace_dropped == 0
                                    and len(complete) >= completed_wire),
        "num_requests": n,
        "arrival_rate": rate,
        "new_tokens": new_tokens,
        "duration_s_in_process": dt_in,
        "duration_s_wire": dt_wire,
        "tokens_per_s_in_process": total_tokens / dt_in,
        "tokens_per_s_wire": total_tokens / dt_wire,
        "ttft_ms_p50_in_process": snap["serving/ttft_ms_p50"],
        "ttft_ms_p95_in_process": snap["serving/ttft_ms_p95"],
        "ttft_ms_p99_in_process": snap["serving/ttft_ms_p99"],
        "itl_ms_p50_in_process": snap["serving/itl_ms_p50"],
        "itl_ms_p95_in_process": snap["serving/itl_ms_p95"],
        "itl_ms_p99_in_process": snap["serving/itl_ms_p99"],
        "ttft_ms_p50_wire": percentile(ttft, 50),
        "ttft_ms_p95_wire": percentile(ttft, 95),
        "ttft_ms_p99_wire": percentile(ttft, 99),
        "itl_ms_p50_wire": percentile(itl, 50),
        "itl_ms_p95_wire": percentile(itl, 95),
        "itl_ms_p99_wire": percentile(itl, 99),
        "wire_overhead_ms_per_token":
            1e3 * (dt_wire - dt_in) / max(total_tokens, 1),
        "outputs_identical": out_wire == out_in,
        "disconnect_cancelled": cancels > before,
    }


def measure_multi_tenant(model, params, srv: Dict) -> Dict[str, object]:
    """Multi-tenant serving A/B plus a quota-isolation probe.

    **A/B**: the SAME interleaved round-robin arrival trace, greedy,
    through (a) ONE engine holding every tenant's LoRA adapter in the
    device pool — heterogeneous tenants batch into one decode step via
    the per-slot adapter gather — vs (b) a single-tenant engine serving
    the trace in order, which can only batch CONSECUTIVE same-tenant
    arrivals and pays a ``merge_lora`` + ``publish_params`` weight swap
    at every tenant switch (the dedicated-engine-per-tenant operating
    model, time-sliced over interleaved traffic). Per-tenant outputs
    must be token-identical across arms, and the batched engine's
    decode must have compiled exactly once across the whole tenant
    mix.

    **Isolation**: a fresh tenancy engine gives one noisy tenant a
    near-empty token bucket and floods it; the probe passes when every
    shed lands on the noisy tenant and the other tenants' requests all
    finish — one tenant's overload must not burn its neighbours."""
    from dla_tpu.serving import ServingEngine
    from dla_tpu.serving.metrics import ServingMetrics

    if model.cfg.lora_r <= 0:
        raise ValueError("multi-tenant A/B wants a LoRA-enabled model "
                         "(model.lora.enabled / lora_r > 0)")
    tn = srv.get("tenancy") or {}
    n_tenants = int(tn.get("tenants", 4))
    per_tenant = int(tn.get("requests_per_tenant", 3))
    new_tokens = int(srv.get("new_tokens", 8))
    rate = float(srv.get("arrival_rate", 1000.0))
    gen = GenerationConfig(max_new_tokens=new_tokens, do_sample=False,
                           eos_token_id=-1)          # greedy, run to length
    rs = np.random.RandomState(int(srv.get("seed", 0)))
    vocab = model.cfg.vocab_size
    cp = srv.get("chunked_prefill") or {}
    chunk = int(cp.get("chunk", 0)) or 2 * int(srv.get("page_size", 16))
    pool_cfg = {"max_adapters": n_tenants,
                "max_rank": int(model.cfg.lora_r)}

    tenants = [f"tenant{i}" for i in range(n_tenants)]
    # distinct, NON-trivial adapters per tenant: init_lora zeros the B
    # factors (identity delta), so randomize both factors — every
    # tenant must produce different tokens than base weights would
    adapters: Dict[str, Dict] = {}
    for i, t in enumerate(tenants):
        key = jax.random.key(1000 + i)
        tree = model.init_lora(key)
        layers = {}
        for name, leaf in tree["layers"].items():
            key, sub = jax.random.split(key)
            layers[name] = 0.05 * jax.random.normal(
                sub, leaf.shape, jnp.float32)
        adapters[t] = {"layers": layers}
    prompts: Dict[str, List[List[int]]] = {
        t: [[int(x) for x in rs.randint(3, vocab - 1,
                                        (rs.randint(chunk // 2, chunk),))]
            for _ in range(per_tenant)]
        for t in tenants}
    order = [(tenants[j % n_tenants], j // n_tenants)
             for j in range(n_tenants * per_tenant)]
    arrivals = np.cumsum(rs.exponential(1.0 / rate, len(order)))

    def drain_collect(eng) -> Dict[int, List[int]]:
        toks: Dict[int, List[int]] = {}
        while eng.has_work():
            for rid, tok in eng.step():
                toks.setdefault(rid, []).append(tok)
        return toks

    def warm(eng) -> None:
        # compile warmup (chunk fn + decode) off the clock, then zero
        # the instrument panel so percentiles measure serving, not XLA
        eng.submit([3 + (i % 251) for i in range(chunk + 1)], 1)
        eng.run_until_drained()
        eng.metrics = ServingMetrics()

    # ---- arm A: one engine, every adapter resident, tenants batched --
    eng = ServingEngine(model, params, gen, _serving_config(
        srv, prefill_chunk=chunk, tenancy={"adapter_pool": pool_cfg}))
    for t in tenants:
        eng.publish_adapter(t, adapters[t])
    warm(eng)
    rids: Dict[tuple, int] = {}
    t0 = time.perf_counter()
    for (t, j), at in zip(order, arrivals):
        now = time.perf_counter() - t0
        if at > now:
            time.sleep(at - now)
        rids[(t, j)] = eng.submit(prompts[t][j], new_tokens, tenant=t)
    toks = drain_collect(eng)
    dt_batched = time.perf_counter() - t0
    outs_batched = {t: [toks.get(rids[(t, j)], [])
                        for j in range(per_tenant)] for t in tenants}
    decode_compiles = int(eng.decode_compiles)
    store = eng.adapter_store

    # ---- arm B: one single-tenant engine, serial merge-and-swap ------
    # the SAME interleaved trace: a swap engine can only batch
    # CONSECUTIVE same-tenant arrivals, and pays a merge_lora +
    # publish_params weight swap at every tenant switch — the real
    # cost of time-slicing one engine across interleaved tenants
    eng2 = ServingEngine(model, model.merge_lora(params, adapters[
        tenants[0]]), gen, _serving_config(srv, prefill_chunk=chunk))
    warm(eng2)
    outs_serial: Dict[str, List[List[int]]] = {
        t: [None] * per_tenant for t in tenants}
    swaps, current = 0, None
    t0 = time.perf_counter()
    i = 0
    while i < len(order):
        t = order[i][0]
        run = []
        while i < len(order) and order[i][0] == t:
            run.append(order[i])
            i += 1
        if current != t:
            eng2.publish_params(model.merge_lora(params, adapters[t]))
            current = t
            swaps += 1
        trids = {tj: eng2.submit(prompts[tj[0]][tj[1]], new_tokens)
                 for tj in run}
        toks = drain_collect(eng2)
        for (tt, jj), r in trids.items():
            outs_serial[tt][jj] = toks.get(r, [])
    dt_serial = time.perf_counter() - t0

    total_tokens = n_tenants * per_tenant * new_tokens

    # ---- isolation probe: noisy tenant on a near-empty bucket --------
    eng3 = ServingEngine(model, params, gen, _serving_config(
        srv, prefill_chunk=chunk, tenancy={
            "adapter_pool": pool_cfg,
            "quotas": {tenants[0]: {"rate": 1e-6, "burst": 1.0}}}))
    for t in tenants:
        eng3.publish_adapter(t, adapters[t])
    # warm WITHOUT the metrics reset: the per-tenant panels bind to the
    # registry the engine was constructed with, and the probe reads them
    eng3.submit([3 + (i % 251) for i in range(chunk + 1)], 1)
    eng3.run_until_drained()
    flood = 3 * per_tenant
    for j in range(flood):                # noisy tenant floods its bucket
        eng3.submit(prompts[tenants[0]][j % per_tenant], new_tokens,
                    tenant=tenants[0])
    for t in tenants[1:]:
        for p in prompts[t]:
            eng3.submit(p, new_tokens, tenant=t)
    drain_collect(eng3)
    iso = eng3.metrics.registry.snapshot()

    def tkey(t, name):
        return iso.get(f"serving/tenant/{t}/{name}", 0.0)

    noisy_shed = tkey(tenants[0], "requests_shed")
    others_shed = sum(tkey(t, "requests_shed") for t in tenants[1:])
    others_finished = sum(tkey(t, "requests_finished")
                          for t in tenants[1:])
    return {
        "tenants": n_tenants,
        "requests_per_tenant": per_tenant,
        "new_tokens": new_tokens,
        "prefill_chunk": chunk,
        "lora_rank": int(model.cfg.lora_r),
        "duration_s_batched": dt_batched,
        "duration_s_serial": dt_serial,
        "tokens_per_s_batched": total_tokens / dt_batched,
        "tokens_per_s_serial": total_tokens / dt_serial,
        "batched_speedup": dt_serial / dt_batched,
        "outputs_identical": outs_batched == outs_serial,
        "decode_step_compiles": decode_compiles,
        "adapter_publishes": int(store.publishes),
        "adapter_resident": int(store.resident_count),
        "noisy_shed": noisy_shed,
        "others_shed": others_shed,
        "others_finished": others_finished,
        "noisy_isolated": bool(noisy_shed > 0 and others_shed == 0
                               and others_finished
                               == (n_tenants - 1) * per_tenant),
    }


def main(argv=None) -> None:
    args = parse_args(argv)
    config = load_config(args.config)
    enable_compile_cache()
    rng = seed_everything(int(config.get("seed", 0)))
    lat = config["latency"]
    model_extra = dict(config.get("model", {}))

    # optional xplane trace of the measured grid (`latency.trace_dir`):
    # the TPU-native replacement for the reference's nonexistent profiler
    # story (SURVEY.md sec 5 "Tracing / profiling"). One trace per model,
    # started AFTER load/compile so the dump holds the measured loops, not
    # checkpoint IO. Process 0 only — multi-host writers would race on
    # the directory.
    trace_dir = lat.get("trace_dir") if jax.process_index() == 0 else None

    dev = jax.devices()[0]
    results: Dict[str, object] = {
        "hardware": {"platform": dev.platform,
                     "device_kind": dev.device_kind,
                     "count": jax.device_count()}}
    for model_name, model_path in config["models"].items():
        log_rank_zero(
            f"[dla_tpu][latency] loading {model_name}: {model_path}")
        bundle = load_causal_lm(str(model_path), model_extra, rng)
        entry: Dict[str, object] = {}
        if trace_dir:
            jax.profiler.start_trace(f"{trace_dir}/{model_name}")
        try:
            entry["forward"] = measure_forward(
                bundle.model, bundle.params,
                [int(b) for b in lat.get("batch_sizes", [1, 4, 8])],
                [int(s) for s in lat.get("seq_lengths", [256, 512, 1024])],
                int(lat.get("warmup_steps", 3)),
                int(lat.get("measure_steps", 10)))
            dec = lat.get("decode", {})
            if dec.get("enabled", True):
                entry["decode"] = measure_decode(
                    bundle.model, bundle.params,
                    int(dec.get("batch_size", 8)),
                    int(dec.get("prompt_length", 128)),
                    int(dec.get("new_tokens", 64)))
                log_rank_zero(f"[dla_tpu][latency] decode: "
                              f"{entry['decode']['decode_tokens_per_second']:.0f}"
                              " tok/s")
            srv = lat.get("serving", {})
            if args.serving or srv.get("enabled", False):
                entry["serving"] = measure_serving(
                    bundle.model, bundle.params, srv)
                log_rank_zero(
                    f"[dla_tpu][latency] serving: "
                    f"{entry['serving']['requests_per_second']:.2f} req/s "
                    f"ttft p50 {entry['serving']['ttft_ms_p50']:.1f} "
                    f"p99 {entry['serving']['ttft_ms_p99']:.1f} ms "
                    f"itl p50 {entry['serving']['itl_ms_p50']:.2f} "
                    f"p99 {entry['serving']['itl_ms_p99']:.2f} ms "
                    f"({entry['serving']['preemptions']:.0f} preemptions)")
            if args.overload or \
                    (srv.get("overload") or {}).get("enabled", False):
                entry["overload"] = measure_overload(
                    bundle.model, bundle.params, srv)
                ovr = entry["overload"]
                log_rank_zero(
                    f"[dla_tpu][latency] overload: shed rate "
                    f"{ovr['shed_rate']:.2f}, ttft p99 "
                    f"{ovr['ttft_ms_p99_shed_on']:.1f} ms (shed on) vs "
                    f"{ovr['ttft_ms_p99_shed_off']:.1f} ms (shed off), "
                    f"lost {ovr['requests_lost_shed_on']:.0f}/"
                    f"{ovr['requests_lost_shed_off']:.0f}")
            if args.shared_prefix or \
                    (srv.get("shared_prefix") or {}).get("enabled", False):
                entry["shared_prefix"] = measure_shared_prefix(
                    bundle.model, bundle.params, srv)
                spr = entry["shared_prefix"]
                log_rank_zero(
                    f"[dla_tpu][latency] shared-prefix: hit rate "
                    f"{spr['cache_hit_rate']:.2f} saved "
                    f"{spr['prefill_tokens_saved_frac']:.2f} of prefill, "
                    f"ttft p95 {spr['ttft_ms_p95_cache_on']:.1f} ms (on) "
                    f"vs {spr['ttft_ms_p95_cache_off']:.1f} ms (off), "
                    f"outputs identical: {spr['outputs_identical']}")
            if args.fleet or \
                    (srv.get("fleet") or {}).get("enabled", False):
                entry["fleet"] = measure_fleet(
                    bundle.model, bundle.params, srv)
                flt = entry["fleet"]
                log_rank_zero(
                    f"[dla_tpu][latency] fleet (N="
                    f"{flt['engines']}): hit rate "
                    f"{flt['fleet_routed']['hit_rate']:.2f} routed vs "
                    f"{flt['fleet_random']['hit_rate']:.2f} random vs "
                    f"{flt['single']['hit_rate']:.2f} single "
                    f"(retention {flt['hit_rate_retention']:.2f}), "
                    f"ttft p95 {flt['fleet_routed']['ttft_ms_p95']:.1f}"
                    f" ms routed vs "
                    f"{flt['fleet_random']['ttft_ms_p95']:.1f} ms "
                    f"random, outputs identical: "
                    f"{flt['outputs_identical']}")
            if args.disagg or \
                    (srv.get("disagg") or {}).get("enabled", False):
                entry["disagg"] = measure_disagg(
                    bundle.model, bundle.params, srv)
                dsg = entry["disagg"]
                log_rank_zero(
                    f"[dla_tpu][latency] disagg ("
                    f"{dsg['prefill_engines']}P+"
                    f"{dsg['decode_engines']}D): itl p99 "
                    f"{dsg['fleet_disagg']['itl_ms_p99']:.2f} ms split "
                    f"vs {dsg['fleet_mixed']['itl_ms_p99']:.2f} ms "
                    f"mixed vs {dsg['single']['itl_ms_p99']:.2f} ms "
                    f"single; migrated "
                    f"{dsg['fleet_disagg']['migration']['migrations']:.0f}"
                    f" requests / "
                    f"{dsg['fleet_disagg']['migration']['migrated_pages']:.0f}"
                    f" pages, outputs identical: "
                    f"{dsg['outputs_identical']}")
            if args.gateway or \
                    (srv.get("gateway") or {}).get("enabled", False):
                entry["gateway"] = measure_gateway(
                    bundle.model, bundle.params, srv)
                gwr = entry["gateway"]
                log_rank_zero(
                    f"[dla_tpu][latency] gateway: ttft p95 "
                    f"{gwr['ttft_ms_p95_wire']:.1f} ms wire vs "
                    f"{gwr['ttft_ms_p95_in_process']:.1f} ms "
                    f"in-process, itl p50 "
                    f"{gwr['itl_ms_p50_wire']:.2f} vs "
                    f"{gwr['itl_ms_p50_in_process']:.2f} ms, wire "
                    f"overhead "
                    f"{gwr['wire_overhead_ms_per_token']:.3f} "
                    f"ms/token, outputs identical: "
                    f"{gwr['outputs_identical']}, disconnect "
                    f"cancelled: {gwr['disconnect_cancelled']}")
            if args.tenancy or \
                    (srv.get("tenancy") or {}).get("enabled", False):
                entry["tenancy"] = measure_multi_tenant(
                    bundle.model, bundle.params, srv)
                tnc = entry["tenancy"]
                log_rank_zero(
                    f"[dla_tpu][latency] tenancy (N="
                    f"{tnc['tenants']}): "
                    f"{tnc['tokens_per_s_batched']:.0f} tok/s batched "
                    f"vs {tnc['tokens_per_s_serial']:.0f} serial-swap "
                    f"({tnc['batched_speedup']:.2f}x), decode compiles "
                    f"{tnc['decode_step_compiles']}, outputs identical:"
                    f" {tnc['outputs_identical']}, noisy tenant "
                    f"isolated: {tnc['noisy_isolated']}")
            if args.speculative or \
                    (srv.get("speculative") or {}).get("enabled", False):
                entry["speculative"] = measure_speculative(
                    bundle.model, bundle.params, srv)
                spc = entry["speculative"]
                log_rank_zero(
                    f"[dla_tpu][latency] speculative: acceptance "
                    f"{spc['acceptance_rate']:.2f}, itl p50 "
                    f"{spc['itl_ms_p50_spec_on']:.2f} ms (on) vs "
                    f"{spc['itl_ms_p50_spec_off']:.2f} ms (off), "
                    f"p95 {spc['itl_ms_p95_spec_on']:.2f} vs "
                    f"{spc['itl_ms_p95_spec_off']:.2f} ms, "
                    f"outputs identical: {spc['outputs_identical']}")
        finally:
            # a mid-grid failure must not lose the already-captured trace
            if trace_dir:
                jax.profiler.stop_trace()
                log_rank_zero(
                    f"[dla_tpu][latency] xplane trace in "
                    f"{trace_dir}/{model_name}")
        results[model_name] = entry

    out_path = Path(config.get("logging", {})
                    .get("output_path", "logs/eval/results.json"))
    out_path = out_path.with_name("latency.json")
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(results, indent=2))
    log_rank_zero(f"[dla_tpu][latency] wrote {out_path}")


if __name__ == "__main__":
    main()
