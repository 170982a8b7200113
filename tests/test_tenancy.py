"""Multi-tenant LoRA serving tests (dla_tpu/serving/tenancy): the
acceptance pins for the adapter registry + batched multi-adapter
decode + tenant policy plane.

The load-bearing guarantees: N=8 tenants' heterogeneous adapters batch
into ONE decode compile and each tenant's tokens are identical (greedy
AND seeded-sampled, logprobs tight) to a dedicated merged-weights
engine; hot swaps and eviction-recompute and supervisor replay all
preserve that parity; a noisy tenant exhausting its quota sheds only
its own requests; prefix-cache pages never alias across tenants; the
AdapterStore's spill/reload cycle is bit-exact and its refcount
protocol fails loudly on misuse."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from dla_tpu.generation.engine import GenerationConfig
from dla_tpu.models.config import get_model_config
from dla_tpu.models.transformer import Transformer
from dla_tpu.serving import (
    RequestState,
    SamplingParams,
    ServingConfig,
    ServingEngine,
    Supervisor,
    SupervisorConfig,
)
from dla_tpu.serving.tenancy import (
    AdapterPoolConfig,
    AdapterStore,
    export_adapter_tree,
    load_adapter_tree,
)

RANK = 4
ALPHA = 8.0
N_TENANTS = 8
MAX_NEW = 4
CHUNK = 8


@pytest.fixture(scope="module")
def model_and_params():
    cfg = dataclasses.replace(get_model_config("tiny"),
                              lora_r=RANK, lora_alpha=ALPHA)
    model = Transformer(cfg)
    return model, model.init(jax.random.key(7))


@pytest.fixture(scope="module")
def adapters(model_and_params):
    """N distinct adapter trees. init_lora zeros the B factors (an
    identity delta), so BOTH factors are randomized — every tenant must
    decode differently from the base weights and from each other."""
    model, _ = model_and_params
    out = {}
    for i in range(N_TENANTS):
        key = jax.random.key(1000 + i)
        tree = model.init_lora(key)
        layers = {}
        for name, leaf in tree["layers"].items():
            key, sub = jax.random.split(key)
            layers[name] = 0.1 * jax.random.normal(
                sub, leaf.shape, jnp.float32)
        out[f"tenant{i}"] = {"layers": layers}
    return out


def _gen(**kw):
    base = dict(max_new_tokens=MAX_NEW, do_sample=False, eos_token_id=-1,
                pad_token_id=0)
    base.update(kw)
    return GenerationConfig(**base)


def _cfg(n=N_TENANTS, tenancy_extra=None, **over):
    tenancy = {"adapter_pool": {"max_adapters": n, "max_rank": RANK}}
    tenancy.update(tenancy_extra or {})
    base = dict(page_size=4, num_pages=64, num_slots=4, max_model_len=32,
                prefill_chunk=CHUNK, tenancy=tenancy)
    base.update(over)
    return ServingConfig(**base)


def _prompts(n, seed, length=6):
    rs = np.random.RandomState(seed)
    return [list(rs.randint(3, 500, (length,))) for _ in range(n)]


def _drain(eng):
    results = eng.run_until_drained(max_steps=2000)
    eng.scheduler.assert_consistent()
    return results


# ---------------------------------------------------------------------------
# THE parity pin: 8 tenants batched == 8 dedicated merged-weight engines
# ---------------------------------------------------------------------------

def test_eight_tenant_batched_parity_greedy_and_seeded(model_and_params,
                                                       adapters):
    """Every tenant's greedy AND seeded-sampled tokens from the ONE
    batched multi-adapter engine equal a merged-weights engine serving
    that tenant alone, with logprobs tight — and the batched engine's
    decode compiled exactly once across the whole 8-tenant mix."""
    model, params = model_and_params
    tenants = sorted(adapters)
    assert len(tenants) == N_TENANTS >= 8
    prompts = dict(zip(tenants, _prompts(N_TENANTS, seed=3)))
    samp = {t: SamplingParams(temperature=0.9, top_p=0.9, top_k=8,
                              seed=100 + i, do_sample=True)
            for i, t in enumerate(tenants)}

    eng = ServingEngine(model, params, _gen(), _cfg())
    for t in tenants:
        eng.publish_adapter(t, adapters[t])
    rids = {}
    for t in tenants:                        # round-robin: mixes tenants
        rids[(t, "greedy")] = eng.submit(prompts[t], MAX_NEW, tenant=t)
    for t in tenants:
        rids[(t, "seeded")] = eng.submit(prompts[t], MAX_NEW, tenant=t,
                                         sampling=samp[t])
    results = _drain(eng)
    assert eng.decode_compiles == 1, (
        "heterogeneous tenant mix retraced the decode step")
    assert eng.cache.allocator.used_count == 0

    # reference arm: ONE engine serially re-published with each
    # tenant's merged weights (publish_params keeps its compile pinned)
    ref = ServingEngine(model, model.merge_lora(params, adapters[
        tenants[0]]), _gen(), ServingConfig(
            page_size=4, num_pages=64, num_slots=4, max_model_len=32,
            prefill_chunk=CHUNK))
    for t in tenants:
        ref.publish_params(model.merge_lora(params, adapters[t]))
        rg = ref.submit(prompts[t], MAX_NEW)
        rs_ = ref.submit(prompts[t], MAX_NEW, sampling=samp[t])
        out = _drain(ref)
        for kind, rid in (("greedy", rg), ("seeded", rs_)):
            got = results[rids[(t, kind)]]
            want = out[rid]
            assert got.generated == want.generated, (
                f"{t} {kind} diverged from merged-weights engine")
            np.testing.assert_allclose(
                got.generated_logprobs, want.generated_logprobs,
                atol=5e-4, rtol=0, err_msg=f"{t} {kind} logprobs")
    # distinct adapters actually decode distinctly
    greedy_streams = {tuple(results[rids[(t, "greedy")]].generated)
                      for t in tenants}
    assert len(greedy_streams) > 1


def test_hot_swap_changes_output_without_recompile(model_and_params,
                                                   adapters):
    """publish_adapter on a RESIDENT tenant rewrites its pool row in
    place: the next request decodes under the new factors, the compile
    counters never move, and no other tenant is disturbed."""
    model, params = model_and_params
    ta, tb = "tenant0", "tenant1"
    prompt = _prompts(1, seed=9)[0]
    eng = ServingEngine(model, params, _gen(), _cfg(n=2))
    eng.publish_adapter(ta, adapters[ta])
    eng.publish_adapter(tb, adapters[tb])
    r1 = eng.submit(prompt, MAX_NEW, tenant=ta)
    rb1 = eng.submit(prompt, MAX_NEW, tenant=tb)
    out1 = _drain(eng)

    # hot-swap tenant a to a DIFFERENT adapter tree (tenant2's factors)
    eng.publish_adapter(ta, adapters["tenant2"])
    r2 = eng.submit(prompt, MAX_NEW, tenant=ta)
    rb2 = eng.submit(prompt, MAX_NEW, tenant=tb)
    out2 = _drain(eng)
    assert eng.decode_compiles == 1
    assert eng.adapter_store.publishes == 3

    merged = ServingEngine(model, model.merge_lora(
        params, adapters["tenant2"]), _gen(), ServingConfig(
            page_size=4, num_pages=64, num_slots=4, max_model_len=32,
            prefill_chunk=CHUNK))
    rid = merged.submit(prompt, MAX_NEW)
    want = _drain(merged)[rid]
    assert out2[r2].generated == want.generated
    assert out2[r2].generated != out1[r1].generated  # swap took effect
    assert out2[rb2].generated == out1[rb1].generated  # b undisturbed


# ---------------------------------------------------------------------------
# tenant quota isolation
# ---------------------------------------------------------------------------

def test_noisy_tenant_sheds_only_its_own_requests(model_and_params,
                                                  adapters):
    """One tenant floods a near-empty token bucket: every shed lands on
    the noisy tenant (at="tenant_quota"), every other tenant's requests
    finish, and their shed counters stay at zero."""
    model, params = model_and_params
    tenants = ["tenant0", "tenant1", "tenant2"]
    noisy = tenants[0]
    eng = ServingEngine(model, params, _gen(), _cfg(
        n=3, tenancy_extra={
            "quotas": {noisy: {"rate": 1e-6, "burst": 1.0}}}))
    for t in tenants:
        eng.publish_adapter(t, adapters[t])
    prompts = _prompts(6, seed=21)
    flood = [eng.submit(p, MAX_NEW, tenant=noisy) for p in prompts]
    quiet = [eng.submit(p, MAX_NEW, tenant=t)
             for t in tenants[1:] for p in prompts[:2]]
    results = _drain(eng)

    shed = [r for r in flood if results[r].state is RequestState.SHED]
    assert len(shed) == len(flood) - 1     # burst=1 admits exactly one
    assert all(results[r].finish_reason == "shed" for r in shed)
    for r in quiet:
        assert results[r].state is RequestState.FINISHED
    snap = eng.metrics.registry.snapshot()
    assert snap[f"serving/tenant/{noisy}/requests_shed"] == len(shed)
    for t in tenants[1:]:
        assert snap[f"serving/tenant/{t}/requests_shed"] == 0.0
        assert snap[f"serving/tenant/{t}/requests_finished"] == 2.0
        assert snap[f"serving/tenant/{t}/tokens_generated"] \
            == 2.0 * MAX_NEW


# ---------------------------------------------------------------------------
# parity across eviction-recompute and supervisor replay
# ---------------------------------------------------------------------------

def test_eviction_recompute_keeps_tenant_parity(model_and_params,
                                                adapters):
    """A page pool sized to force mid-decode preemption: the evicted
    tenant request re-prefills (releasing and re-acquiring its adapter
    pin) and still lands on the merged-weights reference tokens."""
    model, params = model_and_params
    tenants = ["tenant0", "tenant1"]
    prompts = dict(zip(tenants, _prompts(2, seed=11, length=4)))
    new = 5
    # capacity 7 pages (page 0 reserved): both 4-token prompts admit
    # but cannot both grow to 9 tokens -> someone is preempted
    eng = ServingEngine(model, params, _gen(max_new_tokens=new), _cfg(
        n=2, page_size=2, num_pages=8, num_slots=2, max_model_len=12,
        prefill_chunk=4))
    for t in tenants:
        eng.publish_adapter(t, adapters[t])
    rids = {t: eng.submit(prompts[t], new, tenant=t) for t in tenants}
    results = _drain(eng)
    assert eng.metrics.preemptions.value >= 1, (
        "config was meant to force at least one preemption")
    assert eng.cache.allocator.used_count == 0

    ref = ServingEngine(model, model.merge_lora(params, adapters[
        tenants[0]]), _gen(max_new_tokens=new), ServingConfig(
            page_size=2, num_pages=32, num_slots=2, max_model_len=12,
            prefill_chunk=4))
    for t in tenants:
        ref.publish_params(model.merge_lora(params, adapters[t]))
        rid = ref.submit(prompts[t], new)
        want = _drain(ref)[rid]
        got = results[rids[t]]
        assert got.generated == want.generated, (
            f"{t} eviction recompute diverged "
            f"(evictions={got.evictions})")


def test_supervisor_replay_rebinds_tenants(model_and_params, adapters):
    """A mid-run device error: the Supervisor rebuilds the engine (the
    factory republishes every adapter), replays the journal with each
    request's tenant, and the outputs stay identical to a fault-free
    multi-tenant run. The adapter-pool counters stay monotone across
    the rebuild."""
    model, params = model_and_params
    tenants = ["tenant0", "tenant1"]
    prompts = _prompts(4, seed=31)
    subs = [(prompts[i], tenants[i % 2]) for i in range(4)]

    def build(fault_plan=None):
        eng = ServingEngine(model, params, _gen(), _cfg(
            n=2, num_slots=2, fault_plan=fault_plan))
        for t in tenants:
            eng.publish_adapter(t, adapters[t])
        return eng

    base_eng = build()
    base_rids = [base_eng.submit(p, MAX_NEW, tenant=t) for p, t in subs]
    base = base_eng.run_until_drained(max_steps=2000)
    baseline = [list(base[r].generated) for r in base_rids]
    base_eng.close()

    engines = []

    def factory():
        eng = build(fault_plan="engine_step=3:device_error")
        engines.append(eng)
        return eng

    sup = Supervisor(factory, SupervisorConfig(
        watchdog_timeout_s=0.05, watchdog_poll_s=0.01, max_restarts=2))
    rids = [sup.submit(p, MAX_NEW, tenant=t) for p, t in subs]
    results = sup.run(max_steps=2000)
    sup.close()

    assert sup.restarts == 1 and not sup.tripped
    for i, rid in enumerate(rids):
        assert results[rid].state is RequestState.FINISHED
        assert list(results[rid].generated) == baseline[i], (
            f"request {i} diverged across supervisor replay")
    assert [e.decode_compiles for e in engines] == [1] * len(engines)
    # counters carried: gen-1's publishes fold into gen-2's registry
    final = engines[-1].metrics
    assert final.adapter_publishes.value == 2 * len(tenants)


def test_restore_unknown_tenant_fails_loudly(model_and_params):
    """Replay into a rebuilt engine whose factory did NOT republish the
    adapter must raise, never silently decode on base weights."""
    model, params = model_and_params
    eng = ServingEngine(model, params, _gen(), _cfg(n=2))
    with pytest.raises(ValueError, match="publish_adapter first"):
        eng.restore([5, 6, 7], MAX_NEW, generated=[], arrival_time=0.0,
                    tenant="tenant0")


# ---------------------------------------------------------------------------
# prefix-cache namespace isolation
# ---------------------------------------------------------------------------

def test_prefix_cache_never_aliases_across_tenants(model_and_params,
                                                   adapters):
    """The same prompt tokens under two tenants: each tenant's pages
    register under its own namespace, so the other tenant (and the base
    namespace) see a cold cache — KV computed under adapter A must
    never serve adapter B."""
    model, params = model_and_params
    eng = ServingEngine(model, params, _gen(), _cfg(
        n=2, prefix_cache=True))
    for t in ("tenant0", "tenant1"):
        eng.publish_adapter(t, adapters[t])
    prompt = _prompts(1, seed=41, length=2 * CHUNK)[0]
    eng.submit(prompt, MAX_NEW, tenant="tenant0")
    _drain(eng)
    pc = eng.prefix_cache
    assert pc.peek(prompt, CHUNK, namespace="tenant0") >= CHUNK
    assert pc.peek(prompt, CHUNK, namespace="tenant1") == 0
    assert pc.peek(prompt, CHUNK, namespace=None) == 0
    # and the reverse: tenant1 registers its own copy, tenant0's stays
    eng.submit(prompt, MAX_NEW, tenant="tenant1")
    _drain(eng)
    assert pc.peek(prompt, CHUNK, namespace="tenant1") >= CHUNK
    assert pc.peek(prompt, CHUNK, namespace="tenant0") >= CHUNK


# ---------------------------------------------------------------------------
# AdapterStore unit behavior (no engine)
# ---------------------------------------------------------------------------

def _store(model, max_adapters=2, max_rank=RANK):
    return AdapterStore(model, AdapterPoolConfig(
        max_adapters=max_adapters, max_rank=max_rank))


def test_store_lru_spill_and_reload_bit_identical(model_and_params,
                                                  adapters):
    model, _ = model_and_params
    st = _store(model, max_adapters=2)
    for t in ("tenant0", "tenant1", "tenant2"):
        st.publish(t, adapters[t])
    assert st.tenants == ["tenant0", "tenant1", "tenant2"]
    assert st.publishes == 3 and st.resident_count == 0

    i0 = st.acquire("tenant0")
    i1 = st.acquire("tenant1")
    assert i0 != i1 and 0 not in (i0, i1)   # row 0 = base identity
    key = f"{st.targets[0]}_lora_a"
    row0_before = np.asarray(st.pools[key][i0])
    assert np.any(row0_before)              # factors actually landed

    # both rows pinned: residency for a third tenant must fail loudly
    with pytest.raises(RuntimeError, match="adapter pool exhausted"):
        st.acquire("tenant2")

    st.release("tenant0")                   # refcount 0 -> spillable
    i2 = st.acquire("tenant2")
    assert i2 == i0                         # LRU row reused
    assert st.spills == 1 and not st.resident("tenant0")
    assert st.has("tenant0")                # host copy stays

    st.release("tenant2")
    i0b = st.acquire("tenant0")             # reload from host copy
    np.testing.assert_array_equal(
        np.asarray(st.pools[key][i0b]), row0_before)
    assert st.loads == 4                    # 3 first loads + 1 reload


def test_store_refcount_underflow_and_unknown_tenant(model_and_params,
                                                     adapters):
    model, _ = model_and_params
    st = _store(model)
    st.publish("tenant0", adapters["tenant0"])
    with pytest.raises(RuntimeError, match="release underflow"):
        st.release("tenant0")
    with pytest.raises(KeyError, match="publish_adapter first"):
        st.ensure_resident("nobody")
    with pytest.raises(ValueError, match="invalid tenant id"):
        st.publish("../etc", adapters["tenant0"])


def test_store_rank_padding_and_validation(model_and_params, adapters):
    model, _ = model_and_params
    st = _store(model, max_rank=RANK + 2)
    st.publish("tenant0", adapters["tenant0"])   # r=4 into max_rank=6
    idx = st.acquire("tenant0")
    a = np.asarray(st.pools[f"{st.targets[0]}_lora_a"][idx])
    assert a.shape[-1] == RANK + 2
    assert np.all(a[..., RANK:] == 0.0)          # zero pad: exact math

    st2 = _store(model, max_rank=RANK - 2)
    with pytest.raises(ValueError, match="exceeds the pool's max_rank"):
        st2.publish("tenant0", adapters["tenant0"])

    st3 = _store(model)
    with pytest.raises(ValueError, match="publish_params"):
        # a full param tree is NOT an adapter tree — the error routes
        # the caller to the right publish
        st3.publish("tenant0", {"layers": {"bogus": np.zeros((2, 2))}})


def test_publish_params_routes_adapter_trees_to_publish_adapter(
        model_and_params, adapters):
    """Satellite pin: a would-be full-tree republish with an
    adapter-only tree points at publish_adapter, and vice versa."""
    model, params = model_and_params
    eng = ServingEngine(model, params, _gen(), _cfg(n=2))
    with pytest.raises(ValueError, match="publish_adapter"):
        eng.publish_params(adapters["tenant0"])
    assert "publish_adapter" in (ServingEngine.publish_params.__doc__
                                 or "")
    plain = ServingEngine(model, params, _gen(), ServingConfig(
        page_size=4, num_pages=64, num_slots=2, max_model_len=32,
        prefill_chunk=CHUNK))
    with pytest.raises(RuntimeError, match="cfg.tenancy"):
        plain.publish_adapter("tenant0", adapters["tenant0"])
    with pytest.raises(ValueError, match="cfg.tenancy"):
        plain.submit([5, 6, 7], MAX_NEW, tenant="tenant0")
    with pytest.raises(ValueError, match="unknown tenant"):
        eng.submit([5, 6, 7], MAX_NEW, tenant="never-published")


# ---------------------------------------------------------------------------
# servable export round-trip
# ---------------------------------------------------------------------------

def test_export_load_publish_roundtrip(model_and_params, adapters,
                                       tmp_path):
    """export_adapter_tree -> load_adapter_tree -> publish produces a
    pool row bit-identical to publishing the in-memory tree directly
    (the finished-RLHF-run -> serving path, no checkpoint re-derive)."""
    model, _ = model_and_params
    tree = adapters["tenant0"]
    out = export_adapter_tree(
        str(tmp_path / "servable"), tree,
        targets=tuple(model.cfg.lora_targets), rank=RANK, alpha=ALPHA,
        num_layers=model.cfg.num_layers, tenant="tenant0")
    loaded, manifest = load_adapter_tree(out)
    assert manifest["format"] == "adapter_store/v1"
    assert manifest["rank"] == RANK and manifest["alpha"] == ALPHA
    assert manifest["tenant"] == "tenant0"

    st_direct, st_loaded = _store(model), _store(model)
    st_direct.publish("tenant0", tree)
    st_loaded.publish("tenant0", loaded, alpha=manifest["alpha"],
                      rank=manifest["rank"])
    ia = st_direct.acquire("tenant0")
    ib = st_loaded.acquire("tenant0")
    for key in st_direct.pools:
        np.testing.assert_array_equal(
            np.asarray(st_direct.pools[key][ia]),
            np.asarray(st_loaded.pools[key][ib]), err_msg=key)

    bad = tmp_path / "notservable"
    bad.mkdir()
    (bad / "manifest.json").write_text('{"format": "something/v9"}')
    with pytest.raises(ValueError, match="adapter_store/v1"):
        load_adapter_tree(str(bad))
