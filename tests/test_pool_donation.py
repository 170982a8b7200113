"""The KV pools are owned by ``PagedKVCache`` alone: every jitted serving
program that returns the pools (decode, prefill chunk, the
speculative pair, KV import, copy-on-write) is given them donated, so the
arrays that went in are dead after the call and XLA updates the pool in
place (``telemetry/xla/<fn>/alias_bytes`` = the pools' bytes: no
pool-sized copy leaves a step). The export gather is the one reader and
leaves its engine decoding. A dispatch that fails after its inputs were
consumed leaves the engine with dead pools, which ``step()`` reports as
``DeviceStepError`` (never "Array has been deleted") and a ``Supervisor``
cures by rebuild and replay, bit for bit.

Over both kinds of cache row: the dense toy (keys and values) and the
latent-attention toy with routed experts (one latent pool)."""
import jax
import pytest

from dla_tpu.generation.engine import GenerationConfig
from dla_tpu.models.config import get_model_config
from dla_tpu.models.transformer import Transformer
from dla_tpu.serving import (
    DeviceStepError,
    KVMigrator,
    MigrationError,
    RequestState,
    ServingConfig,
    ServingEngine,
    Supervisor,
    SupervisorConfig,
)

PAGE = 4
MAX_NEW = 6
PROMPT = [5, 9, 3, 7, 11, 2, 8, 6, 4, 10]        # 2.5 pages
SPEC = {"enabled": True, "k": 2, "draft": "self"}


@pytest.fixture(scope="module", params=["tiny", "tiny-mla-moe"])
def setup(request):
    model = Transformer(get_model_config(request.param))
    params = model.init(jax.random.key(7))
    gen = GenerationConfig(max_new_tokens=MAX_NEW, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    return model, params, gen


def _engine(setup, **cfg_kw):
    model, params, gen = setup
    kw = dict(page_size=PAGE, num_pages=64, num_slots=2, max_model_len=32,
              prefill_chunk=PAGE, prefix_cache=True,
              fault_plan="")
    kw.update(cfg_kw)
    return ServingEngine(model, params, gen, ServingConfig(**kw))


def _run_to(eng, rid, n_generated):
    for _ in range(200):
        if len(eng.result(rid).generated) >= n_generated:
            return
        eng.step()
    raise AssertionError(f"request {rid} never reached {n_generated} tokens")


def _drain(eng):
    while eng.has_work():
        eng.step()
    eng.scheduler.assert_consistent()


def _stream(req):
    return list(req.generated), list(req.generated_logprobs)


# --- each program, alone in the step (or call) that is watched -------------

def _decode(setup):
    eng = _engine(setup)
    _run_to(eng, eng.submit(PROMPT, MAX_NEW), 1)
    return eng, eng.step, "decode_steps"


def _chunk(setup):
    eng = _engine(setup)
    eng.submit(PROMPT, MAX_NEW)
    return eng, eng.step, "prefill_chunks"


def _last_chunk(setup):
    # prefill_chunk unset: one chunk as wide as the window holds the prompt
    eng = _engine(setup, prefill_chunk=None, prefix_cache=False)
    eng.submit(PROMPT, 1)           # finishes at its first token: no decode
    return eng, eng.step, "prefill_chunks"


def _speculative_round(setup):
    eng = _engine(setup, speculative=SPEC)
    _run_to(eng, eng.submit(PROMPT, MAX_NEW), 1)
    return eng, eng.step, "decode_steps"


def _cow_page(setup):
    eng = _engine(setup)
    rid = eng.submit(PROMPT, MAX_NEW)
    _run_to(eng, rid, 1)
    req, alloc = eng.result(rid), eng.cache.allocator

    def copy_first_page():          # as Scheduler._ensure_writable does
        old, fresh = req.pages[0], alloc.alloc(1)[0]
        eng.cache.cow_page(req.slot, 0, fresh)
        req.pages[0] = fresh
        alloc.decref(old)
    return eng, copy_first_page, None


def _import_request(setup):
    src, eng = _engine(setup), _engine(setup)
    rid = src.submit(PROMPT, MAX_NEW)
    _run_to(src, rid, 2)
    ticket = src.export_request(rid)
    src.close()
    return eng, lambda: eng.import_request(ticket), None


PROGRAMS = {"decode": _decode, "prefill_chunk": _chunk,
            "last_chunk": _last_chunk, "speculative": _speculative_round,
            "cow_page": _cow_page, "kv_import": _import_request}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_program_consumes_the_pools_it_was_given(setup, program):
    eng, act, counter = PROGRAMS[program](setup)
    went_in = eng.cache.pools
    ran = getattr(eng.metrics, counter).value if counter else None
    act()
    if counter:
        assert getattr(eng.metrics, counter).value == ran + 1
    assert all(p.is_deleted() for p in went_in)
    assert not eng.cache.pools_dead
    assert [p.shape for p in eng.cache.pools] == [p.shape for p in went_in]
    _drain(eng)
    eng.close()


def test_steps_update_the_pools_in_place_and_compile_once(setup):
    """The counter that says the mechanism engages: what each step
    program aliases is exactly the pools. The export gather aliases
    nothing. Two requests of different lengths, one compile each."""
    eng = _engine(setup)
    rids = [eng.submit(PROMPT, MAX_NEW), eng.submit(PROMPT[:6], MAX_NEW)]
    _run_to(eng, rids[0], 2)
    eng.export_request(rids[0])
    _drain(eng)
    snap = eng.metrics.registry.snapshot()
    pool_bytes = float(sum(p.nbytes for p in eng.cache.pools))
    assert pool_bytes > 0
    assert snap["telemetry/xla/decode/alias_bytes"] == pool_bytes
    assert snap["telemetry/xla/prefill_chunk/alias_bytes"] == pool_bytes
    assert snap["telemetry/xla/kv_export/alias_bytes"] == 0.0
    assert eng.decode_compiles == 1 and eng.prefill_chunk_compiles == 1
    assert eng.export_compiles == 1
    eng.close()


def test_export_reads_the_pools_and_the_move_is_bit_identical(setup):
    """Export does not donate: the source's pools are the same live
    arrays after it and the source decodes on; the request installed
    elsewhere (import donates there) ends bit-identical to one that
    never moved."""
    home = _engine(setup)
    rid = home.submit(PROMPT, MAX_NEW)
    _drain(home)
    want = _stream(home.result(rid))
    home.close()

    src, dst = _engine(setup), _engine(setup)
    rid = src.submit(PROMPT, MAX_NEW)
    other = src.submit(PROMPT[:6], MAX_NEW)
    _run_to(src, rid, 2)
    held = src.cache.pools
    ticket = src.export_request(rid)
    assert src.cache.pools is held
    assert not any(p.is_deleted() for p in held)
    moved = KVMigrator().install(dst, ticket)
    src.release_migrated(rid)
    _drain(src)
    _drain(dst)
    assert moved.state is RequestState.FINISHED
    assert _stream(moved) == want
    assert src.result(other).state is RequestState.FINISHED
    src.close()
    dst.close()


# --- a dispatch that fails after it consumed the pools ---------------------

class _Consumed(RuntimeError):
    pass


def _fail_after_consuming(eng, program, pools_arg):
    """Stand-in for one of the engine's programs: the runtime took the
    donated pools, then the dispatch failed. Nothing comes back to
    rebind."""
    def stand_in(*args):
        for p in args[pools_arg]:
            p.delete()
        raise _Consumed("dispatch failed after donation")
    setattr(eng, program, stand_in)


def test_consumed_pools_read_as_device_step_error(setup):
    eng, peer = _engine(setup), _engine(setup)
    rid = eng.submit(PROMPT, MAX_NEW)
    _run_to(eng, rid, 2)
    ticket = eng.export_request(rid)
    _fail_after_consuming(eng, "_decode", 1)
    with pytest.raises(_Consumed):
        eng.step()
    assert eng.cache.pools_dead
    for _ in range(2):              # every later step, not only the next
        with pytest.raises(DeviceStepError,
                           match="pool consumed by a failed dispatch"):
            eng.step()
    # the KV handoff refuses (and counts) instead of reading dead arrays
    with pytest.raises(MigrationError, match="pool consumed"):
        eng.export_request(rid)
    with pytest.raises(MigrationError, match="pool consumed"):
        eng.import_request(ticket)
    assert eng._mig_stats["failed_migrations"] == 2
    # the ticket's payloads are arrays of their own: still installable
    moved = peer.import_request(ticket)
    _drain(peer)
    assert moved.state is RequestState.FINISHED
    eng.close()
    peer.close()


@pytest.mark.parametrize("program,pools_arg",
                         [("_decode", 1), ("_import_kv", 0)])
def test_supervisor_rebuilds_after_consumed_pools_and_replays_bit_identical(
        setup, program, pools_arg):
    """``_decode`` fails inside the supervised step; ``_import_kv`` fails
    outside it (a handoff's install), so it is the next step's
    ``DeviceStepError`` that tells the supervisor. Either way: one
    rebuild through the factory, every stream bit-identical."""
    prompts = [PROMPT, PROMPT[:6]]            # of three slots: one stays free
    clean = _engine(setup, num_slots=3)
    rids = [clean.submit(p, MAX_NEW) for p in prompts]
    _drain(clean)
    want = [_stream(clean.result(r)) for r in rids]
    clean.close()

    engines = []

    def factory():
        eng = _engine(setup, num_slots=3)
        engines.append(eng)
        return eng
    sup = Supervisor(factory, SupervisorConfig(
        watchdog_timeout_s=30.0, max_restarts=3))
    rids = [sup.submit(p, MAX_NEW) for p in prompts]
    for _ in range(4):
        sup.step()
    assert any(r.generated for r in sup.results().values())
    first = engines[0]
    _fail_after_consuming(first, program, pools_arg)
    if program == "_import_kv":
        donor = _engine(setup)
        rid = donor.submit(PROMPT[1:], MAX_NEW)
        _run_to(donor, rid, 2)
        with pytest.raises(_Consumed):
            first.import_request(donor.export_request(rid))
        donor.close()
    results = sup.run(max_steps=500)
    sup.close()
    assert sup.failures == ["device_error"] and sup.restarts == 1
    assert len(engines) == 2 and first.cache.pools_dead
    assert not engines[1].cache.pools_dead
    restart = [e for e in first.recorder.events
               if e["kind"] == "engine_restart"]
    assert len(restart) == 1
    if program == "_import_kv":
        assert "DeviceStepError" in restart[0]["detail"]
        assert "pool consumed by a failed dispatch" in restart[0]["detail"]
    assert [_stream(results[r]) for r in rids] == want
    assert [e.decode_compiles for e in engines] == [1, 1]
