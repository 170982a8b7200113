"""On-chip decode-step ablation: where does the per-token time go?

ROADMAP A3 records the remaining decode headroom at large batch
(b64-rollout 3.4-4.4x roofline, vs 1.62x at b8) and attributes it to
"per-step cache-column scatter and sampling overheads" — an unmeasured
guess. This tool measures the components of one decode step separately,
each as a jitted lax.scan of INNER steps (so per-dispatch overhead
amortizes), synced through the same device-fetch trick as
eval_latency._sync:

  engine(scan)   engine scan path: decode_step + categorical sampling
  engine(while)  engine while_loop (early-exit) path, eos never fires
  greedy    decode_step + argmax instead of categorical
  fixed     decode_step fed a constant token (no sampling at all)
  attn      the decode attention einsums alone over the same cache
  weights   the per-layer projections + unembed alone (weight reads)
  write     the once-per-step cache column write alone
  sample    categorical sampling alone on [B, V] logits

    python tools/profile_decode.py [batch prompt new]   # default 64 128 128

step(fixed-token) ~ attn + weights + write + residue, where the residue
is the structural overhead (carry copies, bookkeeping) the sweep cannot
see; sampling and argmax costs are reported as separate lines.
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

INNER = 32  # decode steps per timed dispatch (fns also take a 2x length)


def _time(fn, *args, reps=3) -> float:
    """ms per inner step, DIFFERENTIAL: time(2*INNER) - time(INNER) over
    INNER steps. A fixed per-dispatch cost would otherwise swamp the
    smaller components; differencing two lengths cancels any per-call
    constant.
    ``fn(length, *args)`` must run ``length`` inner steps."""
    from dla_tpu.eval.eval_latency import _sync

    def best_of(length):
        _sync(fn(length, *args))  # compile + warm this length
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            _sync(fn(length, *args))
            best = min(best, time.perf_counter() - t0)
        return best

    return (best_of(2 * INNER) - best_of(INNER)) / INNER * 1000


def main() -> None:
    import jax
    import jax.numpy as jnp

    from dla_tpu.generation.engine import GenerationConfig, build_generate_fn
    from dla_tpu.models.config import ModelConfig
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.ops.attention import decode_attention
    from dla_tpu.ops.sampling import sample_token

    argv = sys.argv[1:]
    batch, prompt, new = (int(a) for a in (argv[:3] + ["64", "128", "128"][len(argv[:3]):]))
    kv_dtype = argv[3] if len(argv) > 3 else "bfloat16"
    weights = argv[4] if len(argv) > 4 else "bfloat16"
    cfg = ModelConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        num_layers=24, num_heads=8, num_kv_heads=4,
        max_seq_length=4096, attention="flash", remat="none",
        dtype="bfloat16", param_dtype="bfloat16",
        kv_cache_dtype=kv_dtype)
    model = Transformer(cfg)
    params = model.init(jax.random.key(0))
    if weights == "int8":
        params = model.quantize_weights(params)
    jax.block_until_ready(params)
    dev = jax.devices()[0]
    print(f"[profile_decode] {dev.device_kind} batch={batch} "
          f"prompt={prompt} new={new} kv={kv_dtype} weights={weights}",
          flush=True)

    s = prompt + new
    b, l = batch, cfg.num_layers
    kh, dh, h = cfg.num_kv_heads, cfg.head_dim_, cfg.num_heads
    kv_elem = 1 if kv_dtype == "int8" else 2
    res = {}

    # ---- full engine paths -------------------------------------------
    ids = jnp.asarray(np.random.RandomState(0).randint(
        3, cfg.vocab_size - 1, (b, prompt)), jnp.int32)
    mask = jnp.ones((b, prompt), jnp.int32)

    def engine_ms(eos, chunk=0):
        # differential over max_new_tokens: cancels RTT AND prefill
        from dla_tpu.eval.eval_latency import _sync

        def best_of(n_new):
            gen = GenerationConfig(max_new_tokens=n_new, do_sample=True,
                                   temperature=1.0, eos_token_id=eos,
                                   early_exit_chunk=chunk)
            fn = jax.jit(build_generate_fn(model, gen))
            _sync(fn(params, ids, mask, jax.random.key(0)))
            best = float("inf")
            for r in range(3):
                t0 = time.perf_counter()
                _sync(fn(params, ids, mask, jax.random.key(r)))
                best = min(best, time.perf_counter() - t0)
            return best

        return (best_of(new) - best_of(new // 2)) / (new // 2) * 1000

    res["engine(scan)"] = engine_ms(-1)
    unreachable = cfg.vocab_size + 7  # eos never fires: all n steps run
    res["engine(while)"] = engine_ms(unreachable)
    res["engine(chunk16)"] = engine_ms(unreachable, chunk=16)

    # ---- isolated decode_step loop (no prefill in the timing) --------
    # timed from the fresh post-prefill state; fill level does not move
    # HBM traffic because both attention backends read the full
    # preallocated S every step
    logits0, cache = model.start_decode(params, ids, mask, new)
    tok0 = jnp.argmax(logits0, axis=-1).astype(jnp.int32)

    from functools import partial

    @partial(jax.jit, static_argnums=0)
    def steps_fixed(length, params, cache, tok):
        def body(carry, _):
            logits, cache = model.decode_step(params, carry[1], carry[0])
            return (carry[0], cache), logits[0, 0]
        (_, cache2), ys = jax.lax.scan(body, (tok, cache), None, length=length)
        return ys.sum(), cache2["step"]

    @partial(jax.jit, static_argnums=0)
    def steps_greedy(length, params, cache, tok):
        def body(carry, _):
            tok, cache = carry
            logits, cache = model.decode_step(params, cache, tok)
            return (jnp.argmax(logits, -1).astype(jnp.int32), cache), logits[0, 0]
        (_, cache2), ys = jax.lax.scan(body, (tok, cache), None, length=length)
        return ys.sum(), cache2["step"]

    res["step(fixed-token)"] = _time(steps_fixed, params, cache, tok0)
    res["step(greedy)"] = _time(steps_greedy, params, cache, tok0)

    # ---- components --------------------------------------------------
    key = jax.random.key(1)
    kc = jax.random.normal(key, (l, b, s, kh, dh), jnp.bfloat16)
    vc = jax.random.normal(key, (l, b, s, kh, dh), jnp.bfloat16)
    q1 = jax.random.normal(key, (b, 1, h, dh), jnp.bfloat16)
    k1 = jax.random.normal(key, (b, 1, kh, dh), jnp.bfloat16)
    valid = jnp.ones((b, s), bool)
    qpos = jnp.full((b, 1), s // 2, jnp.int32)
    kpos = jnp.broadcast_to(jnp.arange(s)[None], (b, s)).astype(jnp.int32)

    @partial(jax.jit, static_argnums=0)
    def attn_only(length, kc, vc, q1, k1):
        def step(carry, i):
            # q depends on i: the body is NOT loop-invariant, so XLA
            # cannot hoist the attention out of the scan (the r5
            # first-cut tool measured a hoisted no-op here)
            qi = q1 * (1 + jnp.bfloat16(1e-8) * i)

            def layer(acc, kv):
                k_c, v_c = kv
                o = decode_attention(qi, k_c, v_c, k1, k1, kv_valid=valid,
                                     q_positions=qpos, kv_positions=kpos)
                return acc + o.sum().astype(jnp.float32), None
            acc, _ = jax.lax.scan(layer, carry, (kc, vc))
            return acc, None
        acc, _ = jax.lax.scan(step, jnp.float32(0.5), jnp.arange(length))
        return acc

    res["attn-einsums"] = _time(attn_only, kc, vc, q1, k1)

    x0 = jax.random.normal(key, (b, 1, cfg.hidden_size), jnp.bfloat16)

    @partial(jax.jit, static_argnums=0)
    def weights_only(length, params, x0):
        flat = model._flat_layers(params["layers"])

        def layer(carry, lp):
            hx = carry
            hx = model._dense(lp, "wo", model._dense(lp, "wq", hx))
            g = model._dense(lp, "w_gate", hx)
            u = model._dense(lp, "w_up", hx)
            hx = model._dense(lp, "w_down", g * u).astype(jnp.bfloat16)
            kproj = model._dense(lp, "wk", hx).sum()
            vproj = model._dense(lp, "wv", hx).sum()
            return hx, (kproj + vproj).astype(jnp.float32)

        def step(carry, i):
            # carry depends on i: stops XLA hoisting the loop-invariant
            # body out of the scan (the r5 first-cut tool measured a
            # hoisted no-op here)
            hx, aux = jax.lax.scan(layer,
                                   carry + jnp.bfloat16(1e-8) * i, flat)
            logits = model.unembed(params, hx[:, 0])
            return hx, logits[0, 0].astype(jnp.float32) + aux.sum()
        _, ys = jax.lax.scan(step, x0, jnp.arange(length))
        return ys.sum()

    res["weight-reads"] = _time(weights_only, params, x0)

    cols = jax.random.normal(key, (l, b, 1, kh, dh), jnp.bfloat16)

    @partial(jax.jit, static_argnums=0)
    def write_only(length, kc, vc, cols):
        def step(carry, i):
            k_c, v_c = carry
            z = jnp.int32(0)
            idx = (z, z, prompt + (i % new), z, z)
            k_c = jax.lax.dynamic_update_slice(k_c, cols, idx)
            v_c = jax.lax.dynamic_update_slice(v_c, cols, idx)
            return (k_c, v_c), None
        (k_c, v_c), _ = jax.lax.scan(step, (kc, vc), jnp.arange(length))
        # read WRITTEN columns: a read of untouched [0,...] lets XLA
        # dead-code-eliminate every write (r5 first-cut bug)
        return (k_c[:, :, prompt, 0, 0].astype(jnp.float32).sum()
                + v_c[:, :, prompt, 0, 0].astype(jnp.float32).sum())

    res["cache-writes"] = _time(write_only, kc, vc, cols)

    lg = jax.random.normal(key, (b, cfg.vocab_size), jnp.float32)

    @partial(jax.jit, static_argnums=0)
    def sample_only(length, lg):
        def step(carry, i):
            t = sample_token(jax.random.fold_in(jax.random.key(0), i), lg)
            return carry + t.sum(), None
        acc, _ = jax.lax.scan(step, jnp.int32(0), jnp.arange(length))
        return acc

    res["sampling"] = _time(sample_only, lg)

    # consistent decomposition: step(fixed-token) runs NO sampling at
    # all, so its residue is the structural overhead (carry copies,
    # bookkeeping); sampling is reported separately, and the
    # greedy-minus-fixed delta is the argmax cost
    parts = (res["attn-einsums"] + res["weight-reads"]
             + res["cache-writes"])
    res["sum-of-parts(no-sample)"] = parts
    res["residue(fixed-parts)"] = res["step(fixed-token)"] - parts
    res["argmax(greedy-fixed)"] = (res["step(greedy)"]
                                   - res["step(fixed-token)"])

    from bench import hbm_bw
    p_bytes = float(sum(lv.size * lv.dtype.itemsize
                        for lv in jax.tree.leaves(params)))
    # the attention reads the full preallocated S every step (no prefix
    # skip in either backend); int8 caches read 1 byte + fp32 scales
    kv_full = 2 * l * b * s * kh * (dh * kv_elem
                                    + (4 if kv_elem == 1 else 0))
    res["roofline-fullcache"] = (p_bytes + kv_full) / hbm_bw(dev) * 1000

    width = max(len(k) for k in res)
    for k, v in res.items():
        print(f"  {k:<{width}}  {v:7.3f} ms/step", flush=True)


if __name__ == "__main__":
    main()
