"""Streaming-gateway tests: the HTTP front door (serving/gateway.py)
streams token/logprob/finish SSE events bit-identically — greedy and
explicitly-seeded — to driving the engine in-process, maps
backpressure onto the existing admission machinery (shed -> 429 +
Retry-After, expired deadline -> 408, draining -> 503 + /healthz
flip), cancels and counts requests whose client hung up mid-stream,
binds port=0 to a real ephemeral port with dla-named handler threads,
and the MigrationTicket wire format round-trips bit-identically while
rejecting truncation / bad magic / version skew. The ``net=`` fault
scope parses and fires one-shot like every other scope."""
import http.client
import json
import threading
import time

import jax
import numpy as np
import pytest

from dla_tpu.resilience.faults import FaultPlan
from dla_tpu.serving import (
    MigrationError,
    MigrationTicket,
    RequestState,
    SamplingParams,
    ServingConfig,
    ServingEngine,
    ServingGateway,
    TERMINAL_STATES,
)
from dla_tpu.serving.gateway import GatewayConfig

MAX_NEW = 4
PAGE = 4


def _setup(preset):
    from dla_tpu.generation.engine import GenerationConfig
    from dla_tpu.models.config import get_model_config
    from dla_tpu.models.transformer import Transformer
    model = Transformer(get_model_config(preset))
    params = model.init(jax.random.key(7))
    gen = GenerationConfig(max_new_tokens=16, do_sample=False,
                           eos_token_id=-1, pad_token_id=0)
    return model, params, gen


@pytest.fixture(scope="module")
def serve_setup():
    return _setup("tiny")


@pytest.fixture(scope="module", params=["tiny", "tiny-mla"])
def pool_setup(request):
    """``serve_setup`` over both kinds of cache row (dense keys and
    values; one latent row): a ticket carries one payload per pool."""
    return _setup(request.param)


def _engine(serve_setup, **cfg_kw):
    model, params, gen = serve_setup
    kw = dict(page_size=PAGE, num_pages=64, num_slots=2,
              max_model_len=32, prefill_chunk=PAGE,
              prefix_cache=True, fault_plan="")
    kw.update(cfg_kw)
    return ServingEngine(model, params, gen, ServingConfig(**kw))


def _prompts(n=4, seed=11, length=6):
    rs = np.random.RandomState(seed)
    return [[int(t) for t in rs.randint(3, 500, (length,))]
            for _ in range(n)]


def _open_generate(port, prompt, new_tokens=MAX_NEW, sampling=None,
                   deadline_s=None):
    """POST /v1/generate; returns the live (conn, response)."""
    body = {"prompt": prompt, "max_new_tokens": new_tokens}
    if sampling is not None:
        body["sampling"] = sampling
    if deadline_s is not None:
        body["deadline_s"] = deadline_s
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/v1/generate", json.dumps(body).encode(),
                 {"Content-Type": "application/json"})
    return conn, conn.getresponse()


def _read_stream(resp):
    """-> (tokens, logprobs, done_event_dict)."""
    toks, logps, done = [], [], None
    while True:
        line = resp.readline()
        if not line:
            break
        line = line.strip()
        if not line.startswith(b"data: "):
            continue
        ev = json.loads(line[len(b"data: "):])
        if ev.get("done"):
            done = ev
            break
        toks.append(int(ev["token"]))
        logps.append(float(ev["logprob"]))
    return toks, logps, done


def _generate(port, prompt, **kw):
    conn, resp = _open_generate(port, prompt, **kw)
    try:
        assert resp.status == 200, (resp.status, resp.read())
        return _read_stream(resp)
    finally:
        conn.close()


def _slow(eng, delay_s):
    """Pad each engine step so streams stay open long enough for the
    test to act mid-stream (deterministic on any CPU)."""
    orig = eng.step

    def step():
        time.sleep(delay_s)
        return orig()
    eng.step = step
    return eng


# ---------------------------------------------------------------------------
# MigrationTicket wire format (satellite: versioned header + validation)
# ---------------------------------------------------------------------------

def _mid_decode_ticket(serve_setup):
    eng = _engine(serve_setup)
    rid = eng.submit(_prompts(1)[0], 8,
                     sampling=SamplingParams(seed=5, do_sample=True,
                                             temperature=0.9))
    for _ in range(40):
        eng.step()
        if len(eng.result(rid).generated) >= 3:
            break
    return eng.export_request(rid)


def test_ticket_wire_roundtrip_bit_identical(pool_setup):
    ticket = _mid_decode_ticket(pool_setup)
    blob = ticket.to_bytes()
    back = MigrationTicket.from_bytes(blob)
    assert back.rid == ticket.rid
    assert back.prompt_tokens == ticket.prompt_tokens
    assert back.generated == ticket.generated
    assert back.generated_logprobs == pytest.approx(
        ticket.generated_logprobs)
    assert back.sampling == ticket.sampling
    assert back.committed_len == ticket.committed_len
    assert back.n_pages == ticket.n_pages
    model = pool_setup[0]
    assert len(ticket.payloads) == len(back.payloads) \
        == len(model.cache_rows())
    for sent, got in zip(ticket.payloads, back.payloads):
        sent, got = np.asarray(sent), np.asarray(got)
        assert got.dtype == sent.dtype and got.shape == sent.shape
        # bit-identity, not tolerance: the payload must survive the wire
        assert sent.tobytes() == got.tobytes()
    # serialization is pure: a second encode is byte-stable
    assert MigrationTicket.from_bytes(blob).to_bytes() == blob


def test_ticket_wire_rejects_corruption(serve_setup):
    blob = _mid_decode_ticket(serve_setup).to_bytes()
    with pytest.raises(MigrationError, match="truncat"):
        MigrationTicket.from_bytes(blob[:-7])
    with pytest.raises(MigrationError, match="magic"):
        MigrationTicket.from_bytes(b"NOPE" + blob[4:])
    with pytest.raises(MigrationError, match="version"):
        MigrationTicket.from_bytes(blob[:4] + b"\x63\x00" + blob[6:])
    with pytest.raises(MigrationError):
        MigrationTicket.from_bytes(b"")


# ---------------------------------------------------------------------------
# the front door
# ---------------------------------------------------------------------------

def test_gateway_binds_ephemeral_port_with_dla_threads(serve_setup):
    gw = ServingGateway(_slow(_engine(serve_setup), 0.03))
    try:
        assert gw.port != 0
        assert str(gw.port) in gw.url
        done_box = {}

        def client():
            done_box["out"] = _generate(gw.port, _prompts(1)[0],
                                        new_tokens=8)
        t = threading.Thread(target=client, name="dla-test-client",
                             daemon=True)
        t.start()
        # while the stream is live, the server-side threads are visible
        # and every one carries the dla- prefix (docs/ANALYSIS.md thread
        # naming policy — observable at runtime, not just statically)
        deadline = time.monotonic() + 30
        seen = set()
        while time.monotonic() < deadline:
            seen = {th.name for th in threading.enumerate()
                    if th.name.startswith("dla-")}
            if any(n.startswith("dla-http-") for n in seen):
                break
            time.sleep(0.01)
        assert "dla-gateway-engine" in seen
        assert "dla-gateway-http" in seen
        assert any(n.startswith("dla-http-") for n in seen), seen
        t.join(timeout=60)
        toks, logps, done = done_box["out"]
        assert done["state"] == "finished" and len(toks) == 8
    finally:
        gw.close()


def test_gateway_streams_bit_identical_greedy_and_seeded(serve_setup):
    prompts = _prompts(4)
    eng = _engine(serve_setup)
    sp = dict(temperature=0.9, top_p=0.95, top_k=0, seed=123,
              do_sample=True)
    rids = [eng.submit(p, MAX_NEW) for p in prompts]
    rids += [eng.submit(p, MAX_NEW, sampling=SamplingParams(**sp))
             for p in prompts]
    results = eng.run_until_drained(max_steps=5000)
    ref = [(list(results[r].generated),
            [pytest.approx(lp) for lp in results[r].generated_logprobs])
           for r in rids]

    gw = ServingGateway(_engine(serve_setup))
    try:
        wire = [_generate(gw.port, p) for p in prompts]
        wire += [_generate(gw.port, p, sampling=sp) for p in prompts]
        for (toks, logps, done), (rtoks, rlogps) in zip(wire, ref):
            assert toks == rtoks          # bit-identical token stream
            assert logps == rlogps        # per-event logprobs ride along
            assert done["state"] == "finished"
            assert done["n"] == len(toks)
        # the counter is delta-mirrored by the engine loop, so give the
        # next mirror pass a moment to fold in the final event
        expect = sum(len(w[0]) for w in wire)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            snap = gw.metrics.registry.snapshot()
            if snap["serving/gateway/streamed_tokens"] >= expect:
                break
            time.sleep(0.01)
        assert snap["serving/gateway/streamed_tokens"] == expect
    finally:
        gw.close()


def test_gateway_shed_answers_429_with_retry_after(serve_setup):
    # one slot + a one-deep wait queue, slow steps: the third
    # concurrent request overflows admission and sheds
    gw = ServingGateway(
        _slow(_engine(serve_setup, num_slots=1,
                      shed={"max_queue_depth": 1}), 0.05),
        GatewayConfig(retry_after_s=2.5))
    try:
        outs = []

        def client(i):
            conn, resp = _open_generate(gw.port, _prompts(4, seed=i)[0],
                                        new_tokens=8)
            try:
                outs.append((resp.status,
                             resp.getheader("Retry-After"),
                             _read_stream(resp) if resp.status == 200
                             else resp.read()))
            finally:
                conn.close()

        ts = []
        for i in range(4):
            t = threading.Thread(target=client, args=(i,),
                                 name=f"dla-test-shed-{i}", daemon=True)
            ts.append(t)
            t.start()
            time.sleep(0.05)       # ordered arrivals: 3rd+ must shed
        for t in ts:
            t.join(timeout=120)
        statuses = sorted(s for s, _, _ in outs)
        assert 429 in statuses, statuses
        assert statuses.count(200) >= 1
        for s, retry, _ in outs:
            if s == 429:
                assert retry == "2.5"
        expect = statuses.count(429)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            snap = gw.metrics.registry.snapshot()
            if snap["serving/gateway/http_429"] >= expect:
                break
            time.sleep(0.01)
        assert snap["serving/gateway/http_429"] == expect
    finally:
        gw.close()


def test_gateway_expired_deadline_answers_408(serve_setup):
    gw = ServingGateway(_slow(_engine(serve_setup, num_slots=1), 0.05))
    try:
        # occupy the single slot, then submit with a deadline shorter
        # than the occupant's remaining stream: expires while queued
        hold = {}

        def occupant():
            hold["out"] = _generate(gw.port, _prompts(1, seed=1)[0],
                                    new_tokens=10)
        t = threading.Thread(target=occupant, name="dla-test-occupant",
                             daemon=True)
        t.start()
        time.sleep(0.15)           # occupant is decoding by now
        conn, resp = _open_generate(gw.port, _prompts(1, seed=2)[0],
                                    new_tokens=4, deadline_s=0.05)
        try:
            assert resp.status == 408, (resp.status, resp.read())
        finally:
            conn.close()
        t.join(timeout=120)
        deadline = time.monotonic() + 30
        got = 0.0
        while got < 1 and time.monotonic() < deadline:
            got = gw.metrics.registry.snapshot()[
                "serving/gateway/http_408"]
            time.sleep(0.01)
        assert got >= 1
    finally:
        gw.close()


def test_gateway_drain_answers_503_and_flips_healthz(serve_setup):
    gw = ServingGateway(_engine(serve_setup))
    try:
        conn = http.client.HTTPConnection("127.0.0.1", gw.port,
                                          timeout=30)
        conn.request("GET", "/healthz")
        assert conn.getresponse().status == 200
        conn.close()

        gw.begin_drain()
        conn, resp = _open_generate(gw.port, _prompts(1)[0])
        assert resp.status == 503
        assert resp.getheader("Retry-After") is not None
        conn.close()
        conn = http.client.HTTPConnection("127.0.0.1", gw.port,
                                          timeout=30)
        conn.request("GET", "/healthz")
        assert conn.getresponse().status == 503
        conn.close()
    finally:
        gw.close()


def test_gateway_client_disconnect_cancels_request(serve_setup):
    eng = _slow(_engine(serve_setup), 0.05)
    gw = ServingGateway(eng)
    try:
        conn, resp = _open_generate(gw.port, _prompts(1)[0],
                                    new_tokens=12)
        assert resp.status == 200
        rid = int(resp.headers["X-DLA-Rid"])
        # read one event, then hang up mid-stream
        while True:
            line = resp.readline().strip()
            if line.startswith(b"data: "):
                break
        # close-delimited SSE: the response object owns the socket
        resp.close()
        conn.close()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            snap = gw.metrics.registry.snapshot()
            if snap["serving/gateway/disconnect_cancels"] >= 1:
                break
            time.sleep(0.02)
        assert snap["serving/gateway/disconnect_cancels"] == 1
        req = eng.result(rid)
        assert req.state in TERMINAL_STATES
        assert req.state is not RequestState.TIMEOUT
        # the freed slot serves the next request normally
        toks, _, done = _generate(gw.port, _prompts(1, seed=3)[0])
        assert done["state"] == "finished" and len(toks) == MAX_NEW
    finally:
        gw.close()


# ---------------------------------------------------------------------------
# net= fault scope
# ---------------------------------------------------------------------------

def test_net_fault_scope_parses_and_fires_one_shot():
    plan = FaultPlan.parse(
        "net=1:delay:0.2;net=2:drop;net=3:disconnect")
    assert plan.take("drop", 1, site="net") is None    # not due yet
    d = plan.take("delay", 1, site="net")
    assert d is not None and d.arg == pytest.approx(0.2)
    assert plan.take("delay", 5, site="net") is None   # one-shot
    assert plan.take("drop", 2, site="net").kind == "drop"
    assert plan.take("disconnect", 3, site="net") is not None
    # net kinds stay inside the net scope
    assert FaultPlan.parse("net=1:drop").take("drop", 1) is None
    with pytest.raises(ValueError):
        FaultPlan.parse("net=1:wedge")
    # round-trips through spec() like every other scope
    assert "net=" in FaultPlan.parse("net=4:disconnect").spec()
